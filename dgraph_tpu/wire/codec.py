"""jax wire codecs: encode/decode pairs + custom-VJP wire-trip wrappers.

The jax side of :mod:`dgraph_tpu.wire.spec` (whose numpy codecs are the
ground truth these are tested against). Three layers, all
``lru_cache``'d factories keyed by static (format, dtype) so jit tracing
sees one stable callable per configuration:

- :func:`make_wire_transform` — the raw ``(encode, decode)`` jnp
  functions (``(None, None)`` for the fp32 identity format, so the
  caller's fp32 code path is LITERALLY unchanged — the bit-identity
  guarantee is structural, not numerical).
- :func:`make_wire_codec` — the custom-VJP pair: ``encode``'s bwd
  decodes the cotangent, ``decode``'s bwd encodes it, so a cotangent
  crossing the wire rides it in the SAME format as the forward payload
  and AD never differentiates through the cast.
- :func:`make_a2a_codec` / :func:`make_ppermute_codec` — whole wire
  trips (encode -> collective -> decode) under ONE custom_vjp. These
  exist because the fp8 payload is a uint8 operand: an integer
  intermediate has no tangent space, so plain AD through
  ``all_to_all(encode(x))`` would silently drop the gradient. Wrapping
  the trip makes the integer hop invisible to AD while the hand-written
  bwd encodes the cotangent and rides the transposed collective
  (``all_to_all(split=0, concat=0)`` is its own transpose; a ppermute's
  transpose is the inverted permutation).

The multi-round executor in ``comm.collectives`` (overlap) is
ALREADY a custom-VJP body — opaque to AD — so it calls the raw
transforms directly and encodes its hand-built cotangent legs with the
same pair.

fp8 packing (must match :func:`dgraph_tpu.wire.spec.np_encode` bit for
bit): per-row scale ``max|x| / 448`` (zero rows scale 1.0), payload
``(x/scale) -> e4m3 -> bitcast uint8``, the f32 scale bitcast into 4
trailing uint8 lanes of the same ``[.., F+4]`` operand — one collective,
one priced operand. An all-zero wire row (ppermute's zeros at
non-receivers) decodes to exactly 0.0
because both its payload and its scale lanes are zero bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from dgraph_tpu.wire.spec import E4M3_MAX, FP8_SCALE_BYTES, get_format


def fp8_jnp_ok() -> bool:
    """Does this jax build expose the e4m3 dtype? (Tracks
    :func:`dgraph_tpu.wire.spec.fp8_available`, which gates the
    resolution ladder on the jax-free ml_dtypes probe.)"""
    try:
        jnp.dtype(jnp.float8_e4m3fn)
        return True
    except Exception:  # noqa: BLE001 — absent attr or wedged backend
        return False


def _fp8_encode(x, dtype_name: str):
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, jnp.float32(1.0))
    scale = scale.astype(jnp.float32)
    q = (x32 / scale).astype(jnp.float8_e4m3fn)
    payload = lax.bitcast_convert_type(q, jnp.uint8)
    lanes = lax.bitcast_convert_type(scale, jnp.uint8)  # [.., 1] -> [.., 1, 4]
    lanes = lanes.reshape(scale.shape[:-1] + (FP8_SCALE_BYTES,))
    return jnp.concatenate([payload, lanes], axis=-1)


def _fp8_decode(y, dtype_name: str):
    F = y.shape[-1] - FP8_SCALE_BYTES
    payload = lax.bitcast_convert_type(y[..., :F], jnp.float8_e4m3fn)
    scale = lax.bitcast_convert_type(
        y[..., F:].reshape(y.shape[:-1] + (1, FP8_SCALE_BYTES)), jnp.float32
    )
    return (payload.astype(jnp.float32) * scale).astype(dtype_name)


@functools.lru_cache(maxsize=None)
def make_wire_transform(fmt_name: str, dtype_name: str):
    """Raw ``(encode, decode)`` for activation dtype ``dtype_name``, or
    ``(None, None)`` when the format is the identity (fp32 — and any
    format whose wire dtype already equals the activation dtype, where
    inserting casts would be pure noise in the lowered module)."""
    fmt = get_format(fmt_name)
    if fmt.payload_itemsize is None:
        return None, None
    if fmt.name == "bf16":
        if dtype_name == "bfloat16":
            return None, None  # activations already ride the wire dtype

        def enc(x):
            return x.astype(jnp.bfloat16)

        def dec(y):
            return y.astype(jnp.float32).astype(dtype_name)

        return enc, dec
    if fmt.name == "fp8":
        if not fp8_jnp_ok():
            raise RuntimeError(
                "wire format 'fp8' requires the float8_e4m3fn dtype; "
                "resolve_wire_format should have degraded before tracing"
            )
        return (functools.partial(_fp8_encode, dtype_name=dtype_name),
                functools.partial(_fp8_decode, dtype_name=dtype_name))
    raise ValueError(f"no jax codec for wire format {fmt_name!r}")


@functools.lru_cache(maxsize=None)
def make_wire_codec(fmt_name: str, dtype_name: str):
    """The custom-VJP ``(encode, decode)`` pair: each side's bwd applies
    the opposite raw transform, so cotangents ride the wire encoded with
    the same format.

    Float wire dtypes (bf16) compose through plain-AD collectives.
    Integer-payload formats (fp8) are returned as the RAW transforms:
    a uint8 primal has no tangent space, so a standalone custom-VJP pair
    could never hand its bwd a usable cotangent — fp8 is only legal
    inside already-opaque custom-VJP bodies (the multi-round executors)
    or the wire-trip wrappers below, where AD never meets the integer
    intermediate.
    """
    enc_raw, dec_raw = make_wire_transform(fmt_name, dtype_name)
    if enc_raw is None:
        return None, None
    fmt = get_format(fmt_name)
    if fmt.wire_dtype == "uint8":
        return enc_raw, dec_raw

    @jax.custom_vjp
    def encode(x):
        return enc_raw(x)

    encode.defvjp(lambda x: (enc_raw(x), None),
                  lambda _, g: (dec_raw(g),))

    @jax.custom_vjp
    def decode(y):
        return dec_raw(y)

    decode.defvjp(lambda y: (dec_raw(y), None),
                  lambda _, g: (enc_raw(g),))
    return encode, decode


@functools.lru_cache(maxsize=None)
def make_a2a_codec(axis_name: str, fmt_name: str, dtype_name: str):
    """One custom-VJP wire trip ``decode(all_to_all(encode(x)))`` over
    leading-axis blocks, or ``None`` for the identity format (the caller
    keeps its untouched all_to_all line). ``all_to_all(split_axis=0,
    concat_axis=0)`` is its own transpose, so the bwd is the SAME trip
    on the cotangent — which is exactly "the cotangent rides the reverse
    wire encoded"."""
    enc, dec = make_wire_transform(fmt_name, dtype_name)
    if enc is None:
        return None

    def _trip(v):
        v = enc(v)
        with jax.named_scope("wire"):  # the collective and nothing else
            v = lax.all_to_all(v, axis_name, split_axis=0, concat_axis=0)
        return dec(v)

    @jax.custom_vjp
    def wire_a2a(x):
        return _trip(x)

    wire_a2a.defvjp(lambda x: (_trip(x), None), lambda _, g: (_trip(g),))
    return wire_a2a


@functools.lru_cache(maxsize=None)
def make_ppermute_codec(axis_name: str, perm: tuple, fmt_name: str,
                        dtype_name: str):
    """One custom-VJP wire trip ``decode(ppermute(encode(x), perm))``,
    or ``None`` for the identity format. The bwd trip rides the INVERSE
    permutation (ppermute's transpose), cotangent encoded."""
    enc, dec = make_wire_transform(fmt_name, dtype_name)
    if enc is None:
        return None
    fwd_perm = tuple((int(s), int(d)) for s, d in perm)
    inv_perm = tuple((d, s) for s, d in fwd_perm)

    def _trip(v, p):
        v = enc(v)
        with jax.named_scope("wire"):
            v = lax.ppermute(v, axis_name, p)
        return dec(v)

    @jax.custom_vjp
    def wire_pp(x):
        return _trip(x, fwd_perm)

    wire_pp.defvjp(lambda x: (_trip(x, fwd_perm), None),
                   lambda _, g: (_trip(g, inv_perm),))
    return wire_pp


def encode_compensated(x, resid, fmt_name: str):
    """Error-feedback encode (jax mirror of
    :func:`dgraph_tpu.wire.spec.np_encode_compensated`): quantize
    ``x + resid`` and return ``(wire_payload, new_resid)`` with the
    residual carried at f32. Thread ``new_resid`` into the next step;
    ``resid=None`` starts at zero. With the identity format the payload
    is ``x`` unchanged and the residual stays zero."""
    enc, dec = make_wire_transform(fmt_name, "float32")
    x32 = x.astype(jnp.float32)
    carried = x32 if resid is None else x32 + resid.astype(jnp.float32)
    if enc is None:
        return carried, jnp.zeros_like(carried)
    y = enc(carried)
    return y, carried - dec(y).astype(jnp.float32)
