"""Wire-format specs: WHAT encoding halo payloads ride the wire in.

The codec layer separates *which rows cross the wire* (the plan's halo
send tables) from *how they are encoded*. This module is the format
side: a registry of serializable :class:`WireFormat` specs, the resolution ladder that
decides which one a run adopts, byte pricing (what ``obs.footprint``
and the trace/HLO byte pins charge per row), and numpy reference codecs
that are the ground truth the jax codecs
(:mod:`dgraph_tpu.wire.codec`) are tested against.

Formats:

- ``fp32`` — the identity default: the payload rides the wire in the
  activation dtype, exactly today's path (a bf16-compute program ships
  bf16; the codec layer adds NOTHING — bit-identical end to end).
- ``bf16`` — payload cast to bfloat16 on send, accumulated back at the
  receiver's dtype through f32-exact widening. Halves the wire bytes of
  an f32 program; lossless when the activations are already bf16.
- ``fp8``  — scaled float8 e4m3 with a per-row max-abs scale: each
  ``[F]`` row is divided by ``max|x| / 448`` and cast to e4m3; the f32
  scale is bitcast into 4 trailing uint8 lanes of the SAME payload row,
  so the wire operand is one ``[.., F+4]`` uint8 array (one collective,
  one byte-exact operand to pin — no scale side channel).

Error compensation (opt-in): :func:`np_encode_compensated` carries the
encode residual forward so the values a receiver accumulates over many
steps stay within a pinned tolerance of fp32 — the classic
error-feedback trick, exposed at the codec level for training loops
that thread residual state.

Contracts:

- **jax-free** (``analysis.lint``'s ``jax-free-module`` rule): specs,
  pricing, the resolution ladder, and the selftest codecs must load and
  run on a host where jax is wedged or absent.
- **Hashable + serializable**: :class:`WireFormat` is a frozen
  dataclass of primitives; the format NAME rides
  :class:`~dgraph_tpu.plan.EdgePlan` static aux and tuning records, and
  ``format_id`` is a content hash so two holders of the same id
  provably price the same bytes.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging

import numpy as np

_logger = logging.getLogger("dgraph_tpu.wire")

# Bump when a serialized field changes meaning (additive fields do not).
WIRE_FORMAT_VERSION = 1

# Largest finite float8 e4m3fn magnitude: per-row scales normalize the
# row's max-abs to exactly this, so the quantizer never saturates.
E4M3_MAX = 448.0

# f32 bytes of the per-row scale the fp8 codec bitcasts into trailing
# uint8 payload lanes (the "+4" of its priced row width).
FP8_SCALE_BYTES = 4


@dataclasses.dataclass(frozen=True)
class WireFormat:
    """One wire encoding for halo payload rows.

    ``payload_itemsize`` is the encoded per-feature byte width
    (``None`` = identity: the payload rides the activation dtype);
    ``row_overhead_bytes`` is packed INTO the payload row (the fp8
    scale lanes), so a format's whole wire cost is one operand.
    """

    name: str
    wire_dtype: str  # numpy-style dtype name of the wire operand
    payload_itemsize: "int | None"  # None = activation dtype (identity)
    row_overhead_bytes: int = 0
    scaled: bool = False  # per-row max-abs scale carried in the payload
    lossless_from: tuple = ()  # activation dtypes round-tripped exactly
    description: str = ""

    def wire_row_bytes(self, feat_dim: int, activation_itemsize: int) -> int:
        """Bytes ONE encoded feature row occupies on the wire — the
        number every pricer (footprint, tuner) and every pin (trace,
        HLO) must agree on."""
        if self.payload_itemsize is None:
            return int(feat_dim) * int(activation_itemsize)
        return int(feat_dim) * self.payload_itemsize + self.row_overhead_bytes

    def wire_feat_dim(self, feat_dim: int) -> int:
        """Last-axis length of the encoded operand (the fp8 payload
        widens by its packed scale lanes)."""
        if self.payload_itemsize is None:
            return int(feat_dim)
        return int(feat_dim) + self.row_overhead_bytes // max(
            self.payload_itemsize, 1
        )

    def compression_ratio(self, feat_dim: int, activation_itemsize: int) -> float:
        """activation-row bytes / wire-row bytes (1.0 = identity)."""
        raw = int(feat_dim) * int(activation_itemsize)
        wire = self.wire_row_bytes(feat_dim, activation_itemsize)
        return raw / wire if wire else 1.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["lossless_from"] = list(self.lossless_from)
        d["version"] = WIRE_FORMAT_VERSION
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "WireFormat":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in fields}
        kw["lossless_from"] = tuple(kw.get("lossless_from", ()))
        return cls(**kw)

    @property
    def format_id(self) -> str:
        """Content hash of the canonical serialization (the
        ``schedule_id`` convention): equal ids imply equal pricing."""
        key = json.dumps(self.to_dict(), sort_keys=True)
        return hashlib.sha1(key.encode()).hexdigest()[:12]


WIRE_FORMATS = {
    "fp32": WireFormat(
        name="fp32", wire_dtype="", payload_itemsize=None,
        lossless_from=("float32", "bfloat16", "float16"),
        description="identity: payload rides the activation dtype "
        "(bit-identical to the pre-codec wire)",
    ),
    "bf16": WireFormat(
        name="bf16", wire_dtype="bfloat16", payload_itemsize=2,
        lossless_from=("bfloat16",),
        description="bfloat16 payload, f32-exact widening on receive",
    ),
    "fp8": WireFormat(
        name="fp8", wire_dtype="uint8", payload_itemsize=1,
        row_overhead_bytes=FP8_SCALE_BYTES, scaled=True,
        description="float8 e4m3 payload with a per-row max-abs f32 "
        "scale packed into 4 trailing uint8 lanes",
    ),
}

WIRE_FORMAT_NAMES = tuple(WIRE_FORMATS)


def get_format(name: str) -> WireFormat:
    try:
        return WIRE_FORMATS[name]
    except KeyError:
        raise ValueError(
            f"unknown wire format {name!r}; known: {WIRE_FORMAT_NAMES}"
        ) from None


def fp8_available() -> bool:
    """Can the fp8 codec encode here? ml_dtypes ships with jax's own
    dependency set, but the gate stays explicit: a host without it must
    degrade with one warning, never crash at trace time."""
    try:
        import ml_dtypes  # noqa: F401

        np.dtype(ml_dtypes.float8_e4m3fn)
        return True
    except Exception:  # noqa: BLE001 — any import/dtype wedge = absent
        return False


_degrade_warned: set = set()


def _warn_degrade(name: str, source: str, why: str) -> None:
    key = (name, source, why)
    if key in _degrade_warned:
        return
    _degrade_warned.add(key)
    _logger.warning(
        "wire_format=%r requested by %s but %s; the next resolution "
        "tier decides the format instead", name, source, why,
    )


def resolve_wire_format(
    world_size: int,
    halo_deltas: tuple,
    *,
    plan_format: str = "fp32",
    fp8_ok: "bool | None" = None,
) -> tuple:
    """The wire format a run will actually encode with, plus who decided.

    The exact ladder shape of :func:`dgraph_tpu.plan.resolve_halo_impl`:

    - ``'env'``     — ``DGRAPH_TPU_WIRE_FORMAT`` / ``config.set_flags``
      pins the format ('auto' defers).
    - ``'record'``  — an adopted TuningRecord chose it
      (``config.tuned_wire_format``).
    - ``'plan'``    — the format attached to the plan at build time
      (``EdgePlan.wire_format`` — itself the build-time resolution, so
      a cache round-trip keeps the adopted format).
    - ``'default'`` — nothing chose: the fp32 identity format (a lossy
      codec never engages on its own — the un-A/B'd-kernel discipline).

    A tier naming a format whose preconditions fail (``fp8`` without the
    e4m3 dtype, an unknown name) degrades with ONE warning to the next
    tier — never a silent wrong answer. Plans with no cross-rank traffic
    resolve ``('fp32', 'plan')``: there is no wire to encode.
    """
    from dgraph_tpu import config as _cfg

    if not halo_deltas:
        return "fp32", "plan"

    def _ok(name: str, source: str) -> bool:
        if name not in WIRE_FORMATS:
            _warn_degrade(name, source, f"it is not a registered format "
                          f"(known: {WIRE_FORMAT_NAMES})")
            return False
        if name == "fp8":
            avail = fp8_ok if fp8_ok is not None else fp8_available()
            if not avail:
                _warn_degrade(name, source,
                              "the float8 e4m3 dtype is unavailable here")
                return False
        return True

    env = getattr(_cfg, "wire_format", "auto")
    tuned = getattr(_cfg, "tuned_wire_format", None)
    for name, source in ((env, "env"), (tuned, "record"),
                         (plan_format, "plan")):
        if name in (None, "", "auto"):
            continue
        if name == "fp32" and source == "plan":
            # the attached default is not an adoption — fall through so
            # the source reports 'default' (nothing chose)
            break
        if _ok(name, source):
            return name, source
    return "fp32", "default"


# ---------------------------------------------------------------------------
# numpy reference codecs — ground truth for the jax pair, and what the
# compile-free selftest (wire/__main__.py) runs its vacuity mutants on
# ---------------------------------------------------------------------------


def _bf16_np():
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


def _fp8_np():
    import ml_dtypes

    return np.dtype(ml_dtypes.float8_e4m3fn)


def np_encode(x: np.ndarray, fmt: "WireFormat | str",
              *, _scale_gain: float = 1.0) -> np.ndarray:
    """Reference encode of ``[.., F]`` rows to the wire operand.
    ``_scale_gain`` exists ONLY for the selftest's wrong-scale vacuity
    mutant (a codec whose decode disagrees with its encode scale must
    blow the round-trip bound, proving the bound can go RED)."""
    fmt = get_format(fmt) if isinstance(fmt, str) else fmt
    x = np.asarray(x)
    if fmt.payload_itemsize is None:  # fp32 identity
        return x
    if fmt.name == "bf16":
        return x.astype(_bf16_np())
    if fmt.name == "fp8":
        x32 = np.ascontiguousarray(x, dtype=np.float32)
        amax = np.max(np.abs(x32), axis=-1, keepdims=True)
        scale = np.where(amax > 0, amax / E4M3_MAX, np.float32(1.0))
        scale = scale.astype(np.float32)
        q = (x32 / (scale * _scale_gain)).astype(_fp8_np())
        payload = q.view(np.uint8)
        scale_lanes = np.ascontiguousarray(scale).view(np.uint8)
        return np.concatenate(
            [payload, scale_lanes.reshape(scale.shape[:-1] + (4,))], axis=-1
        )
    raise ValueError(f"no reference encoder for format {fmt.name!r}")


def np_decode(y: np.ndarray, fmt: "WireFormat | str",
              out_dtype=np.float32) -> np.ndarray:
    """Reference decode back to ``out_dtype`` (accumulation happens at
    f32: both lossy payloads widen exactly into f32 before any cast)."""
    fmt = get_format(fmt) if isinstance(fmt, str) else fmt
    y = np.asarray(y)
    if fmt.payload_itemsize is None:
        return y.astype(out_dtype) if y.dtype != out_dtype else y
    if fmt.name == "bf16":
        return y.astype(np.float32).astype(out_dtype)
    if fmt.name == "fp8":
        F = y.shape[-1] - FP8_SCALE_BYTES
        payload = np.ascontiguousarray(y[..., :F]).view(_fp8_np())
        scale = np.ascontiguousarray(y[..., F:]).view(np.float32)
        return (payload.astype(np.float32) * scale).astype(out_dtype)
    raise ValueError(f"no reference decoder for format {fmt.name!r}")


def np_roundtrip_bound(fmt: "WireFormat | str") -> float:
    """Pinned max relative row-wise error of one encode/decode trip:
    0 for identity, one ulp of the payload mantissa for the casts
    (bf16: 8 mantissa bits; e4m3: 3 bits, plus per-row scale rounding)."""
    fmt = get_format(fmt) if isinstance(fmt, str) else fmt
    return {"fp32": 0.0, "bf16": 2.0 ** -8, "fp8": 2.0 ** -3.5}[fmt.name]


def np_encode_compensated(
    x: np.ndarray, resid: "np.ndarray | None", fmt: "WireFormat | str",
    *, _drop_residual: bool = False,
) -> tuple:
    """Error-feedback encode: quantize ``x + resid`` and carry what the
    wire lost forward, so the RECEIVER'S ACCUMULATION over steps tracks
    the fp32 sum within a pinned bound instead of drifting with step
    count. Returns ``(wire_payload, new_resid)``; thread ``new_resid``
    into the next step's call (``resid=None`` starts at zero).
    ``_drop_residual`` is the selftest's dropped-residual vacuity mutant
    (compensation that doesn't carry must drift past the pinned bound)."""
    fmt = get_format(fmt) if isinstance(fmt, str) else fmt
    x32 = np.asarray(x, dtype=np.float32)
    carried = x32 if resid is None else x32 + np.asarray(resid, np.float32)
    y = np_encode(carried, fmt)
    if _drop_residual:
        return y, np.zeros_like(x32)
    return y, carried - np_decode(y, fmt, np.float32)


# ---------------------------------------------------------------------------
# delta-skip accounting: what shipping only live rows would save
# ---------------------------------------------------------------------------


def delta_skip_rows(pair_rows, world_size: int, s_pad: int) -> dict:
    """Row accounting of shipping ONLY live rows (the per-pair heights
    of ``pair_rows``) versus the dense lowerings' padded operands — the
    delta-skip generalization, as numbers: ``live_rows`` a shard where
    ``all_to_all`` ships ``(W-1) * s_pad`` and a ppermute ring
    ``n_deltas * s_pad``."""
    rows = tuple(tuple(int(v) for v in r) for r in pair_rows)
    live = sum(v for r in rows for v in r)
    deltas = sorted({
        (d - s) % world_size
        for s, r in enumerate(rows) for d, v in enumerate(r) if v and s != d
    })
    return {
        "live_rows_total": live,
        "a2a_rows_per_shard": (world_size - 1) * int(s_pad),
        "ppermute_rows_per_shard": len(deltas) * int(s_pad),
        "live_rows_max_shard": max(
            (sum(r) for r in rows), default=0
        ),
        "num_halo_deltas": len(deltas),
    }
