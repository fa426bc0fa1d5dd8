"""Framework flags — one place, env-overridable.

Replaces the reference's scattered env-var flags
(``DGRAPH_CLEAR_BUFFER_CACHE``, ``RGAT_DDP_FIND_UNUSED``,
``DISABLE_DGRAPH_NVSHMEM``, … — SURVEY.md §5 config) with a single module.
"""

from __future__ import annotations

import os


def _env_flag(name: str, default: bool | None = False) -> bool | None:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


# Use the Pallas sorted-segment-sum kernel for owner-side scatter on TPU
# (requires plan.owner_sorted; falls back to jnp segment_sum elsewhere).
# Tri-state: None = auto (ON when the default backend is TPU — e2e A/B'd on
# v5e, logs/pallas_ab_r2.jsonl); env DGRAPH_TPU_PALLAS_SCATTER=0/1 pins it.
use_pallas_scatter: bool | None = _env_flag("DGRAPH_TPU_PALLAS_SCATTER", None)


def pallas_scatter_enabled() -> bool:
    """Resolve the tri-state ``use_pallas_scatter`` (None = TPU backend)."""
    if use_pallas_scatter is not None:
        return use_pallas_scatter
    import jax

    return jax.default_backend() == "tpu"


# The Pallas sorted ROW-GATHER kernel (transpose of the scatter;
# ops.pallas_segment.sorted_row_gather). Tri-state, but unlike the
# scatter its AUTO state is OFF: it has never been A/B'd on a real chip
# (r2's XLA-gather numbers were invalidated by the timing-harness fix),
# so it engages only on an explicit DGRAPH_TPU_PALLAS_GATHER=1 (or
# set_flags) until on-chip data says otherwise.
use_pallas_gather: bool | None = _env_flag("DGRAPH_TPU_PALLAS_GATHER", None)


def pallas_gather_enabled() -> bool:
    return use_pallas_gather is True


# The FUSED bias+relu scatter kernel gets its own kill switch (tri-state;
# None = follow the plain-scatter decision): a Mosaic regression in one
# kernel must be disablable without losing the other (bench's self-check
# sets these independently).
use_pallas_fused: bool | None = _env_flag("DGRAPH_TPU_PALLAS_FUSED", None)


def pallas_fused_enabled() -> bool:
    if use_pallas_fused is not None:
        return use_pallas_fused
    return pallas_scatter_enabled()


# The fused-BACKWARD kernel pair (chunk-major gd, with d_w where the op
# takes an edge weight, + epilogue="act" d_bias) inside the fused op's
# VJP, weighted or not. Tri-state; None = engage whenever the fused op
# itself runs. A Mosaic regression hitting only the bwd kernels can be
# disabled here without vetoing the whole fused op (ADVICE r4): the
# composed bwd fallback stays available as the A/B control.
use_pallas_fused_bwd: bool | None = _env_flag("DGRAPH_TPU_PALLAS_FUSED_BWD", None)


def pallas_fused_bwd_enabled() -> bool:
    if use_pallas_fused_bwd is not None:
        return use_pallas_fused_bwd
    return True

# Mosaic attention kernels (splash or the library's flash, by the call:
# parallel/sequence.py::_flash_dense) wherever a device holds a full-sequence
# view: the single-device LM and the Ulysses per-head stage. Tri-state like
# the scatter kernels: None = auto (ON on TPU when shapes qualify), env
# DGRAPH_TPU_FLASH_ATTN pins it; no value stands in for the kernels' chip
# self-check, flash_attention_selfcheck(), which consumers run first (same
# Mosaic-divergence rationale as the scatter self-checks).
use_flash_attention: bool | None = _env_flag("DGRAPH_TPU_FLASH_ATTN", None)


def flash_attention_enabled() -> bool:
    if use_flash_attention is not None:
        return use_flash_attention
    import jax

    return jax.default_backend() == "tpu"


# Compute dtype for model matmuls (bfloat16 keeps the MXU fed; params stay
# float32). Models resolve dtype=None through resolve_compute_dtype(), so
# DGRAPH_TPU_COMPUTE_DTYPE=bfloat16 flips every model at once.
default_compute_dtype: str = os.environ.get("DGRAPH_TPU_COMPUTE_DTYPE", "float32")


def resolve_compute_dtype(dtype):
    """None -> the configured default ('float32' stays None: flax Dense's
    native f32 path); an explicit dtype wins. Unknown config strings raise
    (a typo like 'bf16' silently training in f32 would misattribute every
    benchmark)."""
    if dtype is not None:
        return dtype
    name = default_compute_dtype
    if name in ("float32", "f32"):
        return None
    import jax.numpy as jnp

    table = {"bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16, "float16": jnp.float16}
    if name not in table:
        raise ValueError(
            f"DGRAPH_TPU_COMPUTE_DTYPE={name!r} not understood; expected "
            "float32, bfloat16, or float16"
        )
    return table[name]

# Column-chunk width for row gathers (ops.local.row_take). XLA's TPU
# row-gather fast path covers one 128-lane tile; wider rows are gathered
# in <=this many columns per pass. 0 disables splitting. A chunk is also
# the unit of on-chip placement of a gather's table
# (collectives.map_vertex_chunks); 256 was turned down for the memory it
# costs (docs/tuning.md).
gather_col_block: int = int(os.environ.get("DGRAPH_TPU_GATHER_COL_BLOCK", "128"))

# Halo exchange lowering: 'auto' (ppermute neighbor rounds when the plan's
# active peer-delta set is sparse, else one padded all_to_all; 'overlap'
# — interior/boundary split with the boundary rounds hidden behind
# interior aggregation — whenever the plan carries its OverlapSpec), or
# one of plan.HALO_IMPLS: 'all_to_all', 'ppermute' or 'overlap'. Any
# other value is refused.
# Resolution precedence lives in plan.resolve_halo_impl: this env pin >
# the adopted tuning record (tuned_halo_impl below) > the cost-model
# heuristic.
halo_impl: str = os.environ.get("DGRAPH_TPU_HALO_IMPL", "auto")

# Edge-axis chunk count for the overlap lowering's interior aggregation
# (comm.collectives._interior_chunks): 1 = one sorted segment-sum (the
# default — XLA already overlaps a single independent op with in-flight
# rounds, and chunk partial sums regroup float adds, costing bit-parity
# with the serial path); >1 splits the interior sum so pieces interleave
# with individual ppermute rounds (capped at the live-delta count).
overlap_interior_chunks: int = int(
    os.environ.get("DGRAPH_TPU_OVERLAP_CHUNKS", "1")
)

# Wire codec for halo payloads (dgraph_tpu.wire): 'auto' (defer to the
# adopted tuning record, then the plan-attached format, then the fp32
# identity — a lossy codec never engages on its own), or an explicit
# 'fp32' / 'bf16' / 'fp8' pin. Resolution precedence lives in
# wire.spec.resolve_wire_format: this env pin > tuned_wire_format
# (below) > EdgePlan.wire_format > 'fp32'; a pinned format whose
# preconditions fail (fp8 without the e4m3 dtype) degrades with one
# warning to the next tier.
wire_format: str = os.environ.get("DGRAPH_TPU_WIRE_FORMAT", "auto")

# Wire format chosen by an adopted TuningRecord: set by
# tune.record.adopt_record, consulted by wire.spec.resolve_wire_format
# AFTER the env pin. None = no record adopted.
tuned_wire_format: str | None = None

# Halo lowering chosen by an adopted TuningRecord (dgraph_tpu.tune):
# set by tune.record.adopt_record, consulted by plan.resolve_halo_impl
# AFTER the env pin — an operator's explicit DGRAPH_TPU_HALO_IMPL always
# beats a persisted search result. None = no record adopted.
tuned_halo_impl: str | None = None

# record_id of the MOST RECENTLY adopted TuningRecord (None = defaults in
# effect). Set by tune.record.adopt_record, reset by clear_adoption on a
# lookup miss; process-level attribution for consumers without a graph
# handle (artifact writers read the id off their graph/engine directly).
tuning_record_id: str | None = None


def set_flags(**kw) -> None:
    g = globals()
    for k, v in kw.items():
        if k not in g:
            raise KeyError(f"unknown dgraph_tpu.config flag: {k}")
        g[k] = v
