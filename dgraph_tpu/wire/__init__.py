"""Wire codec layer: compressed halo payloads as verified, tuner-ranked
wire formats (ROADMAP item 1 — attack the dominant halo wire bytes).

Separates *what rows cross the wire* (the plan's send tables) from *how
they are encoded*: a registry of serializable
:class:`~dgraph_tpu.wire.spec.WireFormat` specs (fp32 identity / bf16 /
scaled fp8-e4m3), a resolution ladder mirroring ``resolve_halo_impl``,
and jax codecs whose custom-VJP pairs encode cotangents with the same
format.

The spec, pricing and resolver modules are jax-free by the
lint-enforced contract; the jax codecs live in
:mod:`dgraph_tpu.wire.codec` and are re-exported lazily below (PEP 562)
so jax-free consumers importing ``dgraph_tpu.wire.spec`` never pay the
jax import.
"""

from dgraph_tpu.wire.spec import (
    E4M3_MAX,
    FP8_SCALE_BYTES,
    WIRE_FORMAT_NAMES,
    WIRE_FORMAT_VERSION,
    WIRE_FORMATS,
    WireFormat,
    delta_skip_rows,
    fp8_available,
    get_format,
    np_decode,
    np_encode,
    np_encode_compensated,
    np_roundtrip_bound,
    resolve_wire_format,
)

_CODEC_EXPORTS = (
    "encode_compensated",
    "fp8_jnp_ok",
    "make_a2a_codec",
    "make_ppermute_codec",
    "make_wire_codec",
    "make_wire_transform",
)


def __getattr__(name):  # PEP 562: jax loads only when a codec is asked for
    if name in _CODEC_EXPORTS:
        from dgraph_tpu.wire import codec

        return getattr(codec, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "E4M3_MAX",
    "FP8_SCALE_BYTES",
    "WIRE_FORMATS",
    "WIRE_FORMAT_NAMES",
    "WIRE_FORMAT_VERSION",
    "WireFormat",
    "delta_skip_rows",
    "fp8_available",
    "get_format",
    "np_decode",
    "np_encode",
    "np_encode_compensated",
    "np_roundtrip_bound",
    "resolve_wire_format",
    *_CODEC_EXPORTS,
]
