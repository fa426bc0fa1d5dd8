"""Pallas TPU kernel: sorted-segment sum via blocked one-hot MXU matmuls.

This is the TPU-native replacement for the reference's CUDA scatter-add
kernels (``Rank_Local_Scatter_Kernel`` / ``Masked_Scatter_Gather_Kernel``,
``DGraph/distributed/csrc/local_data_kernels.cuh:208-342``): TPU has no
atomics, so the kernel exploits the plan-guaranteed MONOTONE segment ids
(``EdgePlan.owner_sorted``) instead:

- Edges are processed in chunks of ``block_e``; output vertices in blocks of
  ``block_n``. Because ids are sorted, each vertex block's edges form ONE
  contiguous chunk range, found with a cheap in-jit searchsorted and handed
  to the kernel via scalar prefetch (``pltpu.PrefetchScalarGridSpec``).
- Within a chunk, scatter becomes a one-hot [block_e, block_n] matmul
  against the data chunk — an MXU contraction, not a serial scatter. This
  is the TPU analogue of the reference's float4-vectorized atomic kernel
  (``local_data_kernels.cuh:353-406``): same "make the memory system move
  wide rows" idea, expressed as systolic-array work.
- The grid is (num_vertex_blocks, max_chunks_per_block); the output block
  stays resident in VMEM across its chunk iterations (sequential TPU grid),
  accumulating partials, and spills to HBM once per vertex block.

The jnp ``segment_sum`` path remains the oracle and fallback
(``dgraph_tpu.ops.local``), mirroring the reference's dual CUDA/torch
implementation pattern (``RankLocalOps.py:21-31,66-70``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    starts_ref, counts_ref, ids_ref, data_ref, out_ref, *, block_n, block_e, input_op,
    precision,
):
    b = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k < counts_ref[b])
    def _accumulate():
        ids = ids_ref[0, 0]  # [block_e] int32 (global segment ids)
        chunk = data_ref[0]  # [block_e, F]
        if input_op == "relu":
            # fused ReLU epilogue on the scatter input — the reference's
            # Fused_ReLU_Scatter_Kernel (local_data_kernels.cuh:34-72) done
            # in-VMEM before the one-hot contraction
            chunk = jnp.maximum(chunk, 0)
        rel = ids - b * block_n
        # Mosaic can't insert a minor dim on 1-D bool vectors ("only
        # supported for 32-bit types"), so build the mask in 2-D int32
        # space: rel[:, None] is a 32-bit reshape, comparisons stay 2-D.
        rel2 = rel[:, None]  # [block_e, 1] int32
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_e, block_n), 1)
        onehot = jnp.where(
            (cols == rel2) & (rel2 >= 0) & (rel2 < block_n), 1.0, 0.0
        ).astype(chunk.dtype)
        out_ref[...] += jax.lax.dot_general(
            onehot,
            chunk,
            (((0,), (0,)), ((), ())),  # contract over block_e: [BN, F]
            preferred_element_type=out_ref.dtype,
            precision=precision,
        )


class _ChunkSchedule:
    """Shared scaffold of the sorted-CSR kernels: pad edges to chunk
    multiples, compute per-vertex-block chunk ranges (in-jit searchsorted;
    ids sorted), and hand out BlockSpecs over the (nb, max_chunks) grid.

    Iterations past counts[b] clamp to the block's LAST VALID chunk: Mosaic
    skips the DMA when consecutive grid steps map to the same block index,
    so the padded tail of the grid costs no HBM traffic (each kernel's
    @pl.when guard skips its compute).

    ids are carried as [num_chunks, 1, block_e]: Mosaic requires the last
    two block dims to be (8,128)-tileable OR equal to the array dims — a
    (1, block_e) block over [num_chunks, block_e] violates the sublane rule
    on real TPU (interpret mode doesn't check), so the explicit singleton
    sublane dim IS the full array dim.
    """

    def __init__(self, segment_ids, num_segments, E, *, block_e, block_n,
                 max_chunks_per_block):
        self.block_e, self.block_n = block_e, block_n
        self.max_chunks = max_chunks_per_block
        self.E_pad = pl.cdiv(E, block_e) * block_e
        self.N_pad = pl.cdiv(num_segments, block_n) * block_n
        self.num_chunks = self.E_pad // block_e
        self.nb = self.N_pad // block_n
        if self.E_pad != E:
            segment_ids = jnp.pad(
                segment_ids, (0, self.E_pad - E), constant_values=num_segments + 1
            )
        self.ids = segment_ids
        self.ids3d = segment_ids.reshape(self.num_chunks, 1, block_e)
        starts = jnp.searchsorted(segment_ids, jnp.arange(self.nb) * block_n)
        ends = jnp.searchsorted(
            segment_ids, jnp.arange(1, self.nb + 1) * block_n, side="left"
        )
        self.chunk_start = (starts // block_e).astype(jnp.int32)
        self.chunk_counts = jnp.minimum(
            pl.cdiv(ends, block_e).astype(jnp.int32) - self.chunk_start,
            max_chunks_per_block,
        ).astype(jnp.int32)

    def pad_edges(self, arr):
        """Pad an [E, ...] per-edge operand to E_pad rows."""
        pad = self.E_pad - arr.shape[0]
        if pad:
            arr = jnp.pad(arr, ((0, pad),) + ((0, 0),) * (arr.ndim - 1))
        return arr

    def chunk_spec(self, block_shape):
        """BlockSpec streaming a per-chunk operand ([num_chunks, ...])."""
        num_chunks = self.num_chunks

        def index(b, k, starts, counts):
            return (
                jnp.minimum(
                    starts[b]
                    + jnp.minimum(k, jnp.maximum(counts[b] - 1, 0)),
                    num_chunks - 1,
                ),
            ) + (0,) * (len(block_shape) - 1)

        return pl.BlockSpec(block_shape, index)

    def block_spec(self, F):
        """BlockSpec for an [N_pad, F] owner-side operand/output."""
        return pl.BlockSpec((self.block_n, F), lambda b, k, s, c: (b, 0))


def _precision(precision: str):
    return (
        jax.lax.Precision.HIGHEST if precision == "highest"
        else jax.lax.Precision.DEFAULT
    )


def _out_struct(shape, dtype, *operands):
    """``out_shape`` entry for a ``pallas_call`` made from ``operands``:
    inside ``jax.shard_map`` with ``check_vma`` on, the output must declare
    which manual axes it varies over — the union of its operands' (empty
    outside shard_map)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _sorted_segment_sum_impl(
    data, segment_ids, num_segments, *, max_chunks_per_block, block_e, block_n,
    interpret, input_op, precision,
):
    if input_op not in ("none", "relu"):
        raise ValueError(f"input_op must be 'none' or 'relu', got {input_op!r}")
    E, F = data.shape
    chunks = _ChunkSchedule(
        segment_ids, num_segments, E, block_e=block_e, block_n=block_n,
        max_chunks_per_block=max_chunks_per_block,
    )
    data3d = chunks.pad_edges(data).reshape(chunks.num_chunks, block_e, F)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(chunks.nb, chunks.max_chunks),
        in_specs=[
            chunks.chunk_spec((1, 1, block_e)),
            chunks.chunk_spec((1, block_e, F)),
        ],
        out_specs=chunks.block_spec(F),
    )
    # The MXU accumulator must be 32-bit ('tpu.matmul' rejects a bf16 acc),
    # and f32 accumulation over long segments is the atomicAdd-parity
    # semantics anyway — so the VMEM-resident output block is ALWAYS f32
    # (bf16 inputs still ride the fast bf16 MXU passes under
    # precision='default'); cast back to the input dtype on the way out.
    operands = (chunks.chunk_start, chunks.chunk_counts, chunks.ids3d, data3d)
    out = pl.pallas_call(
        functools.partial(
            _kernel, block_n=block_n, block_e=block_e, input_op=input_op,
            precision=_precision(precision),
        ),
        grid_spec=grid_spec,
        out_shape=_out_struct((chunks.N_pad, F), jnp.float32, *operands),
        interpret=interpret,
    )(*operands)
    return out[:num_segments].astype(data.dtype)


@functools.lru_cache(maxsize=None)
def _make_sss(num_segments, max_chunks_per_block, block_e, block_n, interpret,
              input_op, precision, gather_mv=0):
    impl = functools.partial(
        _sorted_segment_sum_impl,
        num_segments=num_segments, max_chunks_per_block=max_chunks_per_block,
        block_e=block_e, block_n=block_n, interpret=interpret,
        input_op=input_op, precision=precision,
    )

    @jax.custom_vjp
    def f(data, segment_ids):
        return impl(data, segment_ids)

    def fwd(data, segment_ids):
        res = (segment_ids, data if input_op == "relu" else None)
        return impl(data, segment_ids), res

    def bwd(res, g):
        segment_ids, data = res
        # grad gather of the cotangent rows: sorted-row-gather kernel
        # when pinned on (config read at trace time; bench sets flags
        # before compiling), else the column-chunked take (the >128-lane
        # row-gather cliff applies to the grad gather too)
        gd = _take_sorted(g, segment_ids, gather_mv,
                          block_e, block_n, max_chunks_per_block)
        if input_op == "relu":
            gd = gd * (data > 0).astype(gd.dtype)
        return gd, None

    f.defvjp(fwd, bwd)
    return f


def sorted_segment_sum(
    data: jax.Array,  # [E, F]
    segment_ids: jax.Array,  # [E] int32, MONOTONE non-decreasing
    num_segments: int,
    *,
    max_chunks_per_block: int,
    block_e: int = 512,
    block_n: int = 256,
    interpret: bool = False,
    input_op: str = "none",  # "none" | "relu" (fused input epilogue)
    gather_mv: int = 0,  # >0: the VJP's cotangent-row gather may use the
    # sorted-row-gather kernel (explicit config opt-in; plan.gather_mv)
    precision: str = "highest",  # MXU passes for the one-hot contraction:
    # "highest" = f32-faithful accumulation (matches the CUDA atomicAdd
    # semantics, ~1.4x XLA's scatter path on v5e); "default" = bf16 input
    # truncation (fastest; right when the model already computes in bf16)
) -> jax.Array:
    """Segment sum for sorted ids. Rows with ids outside [0, num_segments)
    are dropped (use an out-of-range id for masked edges).

    Differentiable: the VJP is the gather transpose ``g[ids]`` (exactly the
    reference's gather-bwd = scatter-sum duality, ``_torch_func_impl.py``),
    with OOB ids contributing zero.

    ``max_chunks_per_block`` must be >= the true maximum
    ceil(edges_in_any_block/block_e) + 1 (the +1 covers chunk misalignment);
    compute it at plan-build time with :func:`max_chunks_hint`.
    """
    return _make_sss(
        num_segments, max_chunks_per_block, block_e, block_n, interpret,
        input_op, precision, gather_mv,
    )(data, segment_ids)


def _kernel_bias_relu(
    starts_ref, counts_ref, ids_ref, *refs,
    block_n, block_e, precision, has_weight, epilogue="relu",
):
    """out[v] += sum_e onehot[e,v] * w[e] * relu(data[e] + bias[v]).

    The bias lookup bias[ids[e]] is itself a one-hot matmul against the
    block's resident bias tile — per-edge rows of the OWNER-side vertex
    operand never touch HBM. This is the full TPU analogue of the
    reference's fused scatter family (``Fused_ReLU_Scatter_Kernel`` /
    ``Fused_Sum_Norm_Scatter_Kernel``, ``local_data_kernels.cuh:34-116``):
    XLA alone cannot do it because ``pallas_call`` is a fusion barrier, so
    the [E, F] message tensor would round-trip HBM.

    ``epilogue="act"`` accumulates w[e] * 1[data[e]+bias[v] > 0] instead —
    the VJP's d_bias reduction (d_bias[v] = g[v] * Σ w·act), computed from
    ONE pass over data with no [E, F] HBM intermediates.

    ``epilogue="grad"`` takes a second streamed operand ``other`` (after
    ``data``) and accumulates w[e] * other[e] * 1[data[e]+bias[v] > 0]: the
    gradient of the op with the two sides exchanged
    (:func:`sorted_segment_grad_bias_relu`). Per edge it is
    :func:`_fused_bwd_kernel`'s ``gd`` — the product in float32, rounded
    once to the data dtype — contracted here instead of written out.
    """
    wgt_ref = other_ref = None
    refs = list(refs)
    if has_weight:
        wgt_ref = refs.pop(0)
    data_ref = refs.pop(0)
    if epilogue == "grad":
        other_ref = refs.pop(0)
    bias_ref, out_ref = refs
    b = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k < counts_ref[b])
    def _accumulate():
        ids = ids_ref[0, 0]  # [block_e]
        chunk = data_ref[0]  # [block_e, F]
        rel2 = (ids - b * block_n)[:, None]
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_e, block_n), 1)
        onehot = jnp.where(
            (cols == rel2) & (rel2 >= 0) & (rel2 < block_n), 1.0, 0.0
        ).astype(chunk.dtype)
        # bias[ids[e]] for in-block edges (OOB rows get 0 — they're dropped
        # by the output contraction anyway)
        bias_rows = jax.lax.dot_general(
            onehot, bias_ref[...].astype(chunk.dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        in_dtype = data_ref.dtype
        pre = chunk.astype(jnp.float32) + bias_rows
        if epilogue == "act":
            chunk = (pre > 0).astype(jnp.float32)
        elif epilogue == "grad":
            chunk = other_ref[0].astype(jnp.float32) * (pre > 0).astype(
                jnp.float32)
        else:
            chunk = jnp.maximum(pre, 0)
        if has_weight:
            # cast BEFORE the [:, None]: Mosaic can only insert a minor dim
            # on 32-bit vectors (bf16 here fails "Insertion of minor dim
            # that is not a no-op only supported for 32-bit types")
            chunk = chunk * wgt_ref[0, 0].astype(jnp.float32)[:, None]
        # back to the input dtype for the contraction (bf16 inputs keep the
        # fast MXU passes; matches the unfused path where m was bf16)
        chunk = chunk.astype(in_dtype)
        out_ref[...] += jax.lax.dot_general(
            onehot,
            chunk,
            (((0,), (0,)), ((), ())),
            preferred_element_type=out_ref.dtype,
            precision=precision,
        )


def _take_sorted(g, ids, gather_mv, block_e, block_n, mc):
    """Bwd-side row take by PLAN-SORTED ids: the Pallas sorted-row-gather
    kernel when the explicit opt-in flag is pinned and the plan carried a
    span hint, ops.local.row_take otherwise (OOB ids -> zero rows)."""
    from dgraph_tpu import config as _cfg
    from dgraph_tpu.ops.local import row_take

    if gather_mv > 0 and _cfg.pallas_gather_enabled():
        prec = "default" if g.dtype == jnp.bfloat16 else "highest"
        return sorted_row_gather(
            g, ids, max_vblocks=gather_mv, block_e=block_e, block_n=block_n,
            scatter_mc=mc, precision=prec,
        )
    return row_take(g, ids, oob="fill")


@functools.lru_cache(maxsize=None)
def _make_ssbr_impl(num_segments, max_chunks_per_block, block_e, block_n,
                    interpret, precision, has_weight):
    """The vblock-major pass of :func:`_kernel_bias_relu` under one of its
    epilogues, not differentiable: the fused op's forward (``"relu"``), its
    backward's Σ w·act (``"act"``) and the transposed gradient
    (``"grad"``, with the second streamed operand ``other``)."""

    def impl(data, segment_ids, bias, edge_weight, epilogue="relu",
             other=None):
        E, F = data.shape
        chunks = _ChunkSchedule(
            segment_ids, num_segments, E, block_e=block_e, block_n=block_n,
            max_chunks_per_block=max_chunks_per_block,
        )
        if chunks.N_pad != num_segments:
            bias = jnp.pad(bias, ((0, chunks.N_pad - num_segments), (0, 0)))
        # data, then epilogue="grad"'s second streamed operand
        streamed = [
            chunks.pad_edges(t).reshape(chunks.num_chunks, block_e, F)
            for t in ((data,) if other is None else (data, other))
        ]
        in_specs = [
            chunks.chunk_spec((1, 1, block_e)),
            *[chunks.chunk_spec((1, block_e, F)) for _ in streamed],
            chunks.block_spec(F),
        ]
        operands = [chunks.ids3d, *streamed, bias]
        if has_weight:
            wgt3d = chunks.pad_edges(edge_weight).reshape(
                chunks.num_chunks, 1, block_e
            )
            in_specs.insert(1, chunks.chunk_spec((1, 1, block_e)))
            operands.insert(1, wgt3d)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(chunks.nb, chunks.max_chunks),
            in_specs=in_specs,
            out_specs=chunks.block_spec(F),
        )
        call_args = (chunks.chunk_start, chunks.chunk_counts, *operands)
        out = pl.pallas_call(
            functools.partial(
                _kernel_bias_relu, block_n=block_n, block_e=block_e,
                precision=_precision(precision), has_weight=has_weight,
                epilogue=epilogue,
            ),
            grid_spec=grid_spec,
            out_shape=_out_struct((chunks.N_pad, F), jnp.float32, *call_args),
            interpret=interpret,
        )(*call_args)
        if epilogue == "act":
            # the act-count reduction is bwd-internal and vertex-sized —
            # keep the f32 accumulator precision (a bf16 count saturates)
            return out[:num_segments]
        return out[:num_segments].astype(data.dtype)

    return impl


@functools.lru_cache(maxsize=None)
def _make_ssbr(num_segments, max_chunks_per_block, block_e, block_n, interpret,
               precision, has_weight, gather_mv=0):
    impl = _make_ssbr_impl(
        num_segments, max_chunks_per_block, block_e, block_n, interpret,
        precision, has_weight,
    )

    @jax.custom_vjp
    def f(data, segment_ids, bias, edge_weight):
        return impl(data, segment_ids, bias, edge_weight)

    def fwd(data, segment_ids, bias, edge_weight):
        return impl(data, segment_ids, bias, edge_weight), (
            data, segment_ids, bias, edge_weight,
        )

    def bwd(res, g):
        data, segment_ids, bias, edge_weight = res
        cdt = data.dtype

        # fused-bwd kernel pair, with or without an edge weight: gd (and
        # d_w) from ONE chunk-major pass (no bias-rows take, no g-rows
        # take, no act tensor — the composed bwd streams all three
        # through HBM), d_bias's Σ w·act from ONE vblock-major pass
        # (epilogue="act"). Engages when the plan carried the vblock-span
        # hint (gather_mv) and the kernels can run (TPU, or interpret
        # mode for tests); the fused kill switch already gated entry into
        # this op at the dispatch point, and
        # config.pallas_fused_bwd_enabled() (trace-time read) disables
        # just this pair for debugging/A-B without losing the fused fwd.
        # Which branch each traced backward took is counted
        # (segsum.bwd_fused / segsum.bwd_composed, docs/tracing.md).
        from dgraph_tpu import config as _config
        from dgraph_tpu.obs.metrics import default_registry

        if (gather_mv > 0
                and _config.pallas_fused_bwd_enabled()
                and (interpret or jax.default_backend() == "tpu")):
            default_registry.counter("segsum.bwd_fused")
            gd, d_w = _make_fused_bwd(
                num_segments, gather_mv, block_e, block_n, interpret,
                precision, has_weight,
            )(data, g.astype(cdt), bias.astype(cdt), segment_ids,
              edge_weight)
            sum_act = impl(data, segment_ids, bias, edge_weight,
                           epilogue="act")  # f32 [N, F], Σ w·act
            d_bias = (sum_act * g.astype(jnp.float32)).astype(bias.dtype)
            if d_w is None:
                d_w = jnp.zeros_like(edge_weight)
            return gd, None, d_bias, d_w.astype(edge_weight.dtype)

        default_registry.counter("segsum.bwd_composed")
        # composed fallback: recompute the activation mask (remat: the
        # [E,F] pre-activation was never materialized in the forward —
        # that's the point); both row takes are by the plan's sorted ids
        # -> kernel-upgradeable.
        # Every [E, F] tensor that REACHES HBM stays in the COMPUTE dtype:
        # upcasting the gathers/products to f32 doubled every bwd HBM
        # stream (the r4 TPU export showed six 1.2 GB f32 [E,128] gathers
        # per step from exactly this block). The mask itself is still
        # DECIDED in f32 — the forward kernel computes data+bias[id] in
        # f32, and a bf16 recompute can flip edges at the ReLU boundary
        # (an O(|g|) error, not rounding). The f32 add/compare lives in
        # the fusion's registers; its input streams are bf16.
        # bias.astype(cdt) matches the FORWARD's rounding, not a new one:
        # the kernel computes bias_rows = dot(onehot, bias_ref.astype(
        # chunk.dtype)) — i.e. the forward's mask also sees bias rounded
        # to the data dtype (a one-hot contraction of cdt values under a
        # f32 preferred_element_type is exact), so fwd/bwd masks agree
        # even for an f32 bias passed with bf16 data.
        bias_rows = _take_sorted(
            bias.astype(cdt), segment_ids, gather_mv,
            block_e, block_n, max_chunks_per_block,
        )
        pre = data.astype(jnp.float32) + bias_rows.astype(jnp.float32)
        act = (pre > 0).astype(cdt)
        g_rows = _take_sorted(
            g.astype(cdt), segment_ids, gather_mv,
            block_e, block_n, max_chunks_per_block,
        )
        w = edge_weight[:, None].astype(cdt) if has_weight else 1.0
        gd = g_rows * act * w  # d/d(data)
        # d/d(bias[v]) = g[v] * sum_e w_e*act_e  (sorted ids -> fast path;
        # f32 accumulation guaranteed by sorted_segment_sum_any for BOTH
        # the kernel path (VMEM acc) and the jnp fallback — a bf16
        # accumulate would saturate the count at vertex degree ~256)
        from dgraph_tpu.ops.local import sorted_segment_sum_any

        d_bias = sorted_segment_sum_any(
            act * w if has_weight else act, segment_ids, num_segments,
            block_e, block_n, max_chunks_per_block,
        ).astype(jnp.float32) * g.astype(jnp.float32)
        if has_weight:
            d_w = (g_rows * jnp.maximum(pre, 0)).sum(axis=-1).astype(
                edge_weight.dtype
            )
        else:
            d_w = jnp.zeros_like(edge_weight)
        return gd.astype(data.dtype), None, d_bias.astype(bias.dtype), d_w

    f.defvjp(fwd, bwd)
    return f


def sorted_segment_grad_bias_relu(
    rows: jax.Array,  # [E, F] the OTHER side's vertex operand, a row an edge
    g_rows: jax.Array,  # [E, F] the other side's cotangent, a row an edge
    segment_ids: jax.Array,  # [E] int32 MONOTONE ids of THIS side
    table: jax.Array,  # [num_segments, F] this side's vertex operand
    num_segments: int,
    *,
    edge_weight: Optional[jax.Array] = None,  # [E], in the ids' order
    max_chunks_per_block: int,
    block_e: int = 512,
    block_n: int = 256,
    interpret: bool = False,
    precision: str = "default",
) -> jax.Array:
    """d_table[u] = Σ_{e: ids[e]=u} w[e] · g_rows[e] · 1[rows[e] + table[u] > 0]:
    the gradient of ``Σ w·relu(table[src] + h[dst])`` to ``table`` as the
    TRANSPOSED aggregation, edges in ``table``'s sorted order and the other
    side's two vertex operands gathered a row an edge. The forward kernel
    with ``epilogue="grad"`` (see :func:`_kernel_bias_relu`): ``table``'s
    block is the resident operand the forward calls bias. Not
    differentiable (a backward-internal pass)."""
    impl = _make_ssbr_impl(
        num_segments, max_chunks_per_block, block_e, block_n, interpret,
        precision, edge_weight is not None,
    )
    return impl(rows, segment_ids, table.astype(rows.dtype), edge_weight,
                epilogue="grad", other=g_rows)


def sorted_segment_sum_bias_relu(
    data: jax.Array,  # [E, F] per-edge partial messages (e.g. gathered src proj)
    segment_ids: jax.Array,  # [E] int32 MONOTONE owner-side ids
    bias: jax.Array,  # [num_segments, F] owner-side vertex operand
    num_segments: int,
    *,
    edge_weight: Optional[jax.Array] = None,  # [E] post-activation scale
    max_chunks_per_block: int,
    block_e: int = 512,
    block_n: int = 256,
    interpret: bool = False,
    gather_mv: int = 0,  # vblock-span hint (plan.gather_mv). >0 selects
    # the op's Pallas backward KERNEL PAIR on TPU, weighted or not
    # (_fused_bwd_kernel gd [+ d_w] + epilogue="act" d_bias), additionally
    # gated by config.pallas_fused_bwd_enabled() read at trace time
    # (DGRAPH_TPU_PALLAS_FUSED_BWD — the pair's own kill switch; the
    # fused op as a whole still gates at the dispatch point). In
    # the composed backward it additionally lets the cotangent
    # gather use sorted_row_gather under DGRAPH_TPU_PALLAS_GATHER.
    precision: str = "default",
) -> jax.Array:
    """out[v] = Σ_{e: ids[e]=v} w[e] * relu(data[e] + bias[v]) without ever
    materializing the [E, F] message tensor in HBM (see
    :func:`_kernel_bias_relu`). Differentiable (remat-style VJP)."""
    has_w = edge_weight is not None
    fn = _make_ssbr(
        num_segments, max_chunks_per_block, block_e, block_n, interpret,
        precision, has_w, gather_mv,
    )
    if not has_w:
        edge_weight = jnp.zeros((data.shape[0],), data.dtype)
    return fn(data, segment_ids, bias, edge_weight)


def block_chunk_counts(
    segment_ids, num_segments: int, block_e: int = 512, block_n: int = 256
):
    """Host-side (concrete sorted ids): for each ``block_n``-row vertex
    block, the number of ``block_e`` edge chunks its edges touch — what
    :class:`_SortedSchedule` computes in-jit as ``chunk_counts`` before the
    clamp. The kernels' grid is (blocks, the maximum of these), so their
    sum over the grid's size is the share of grid steps that do work."""
    import numpy as np

    ids = np.asarray(segment_ids)
    nb = -(-num_segments // block_n)
    starts = np.searchsorted(ids, np.arange(nb) * block_n)
    ends = np.searchsorted(ids, np.arange(1, nb + 1) * block_n, side="left")
    return -(-ends // block_e) - starts // block_e


def max_chunks_hint(
    segment_ids, num_segments: int, block_e: int = 512, block_n: int = 256
) -> int:
    """Host-side (concrete ids) bound for ``max_chunks_per_block``."""
    counts = block_chunk_counts(segment_ids, num_segments, block_e, block_n)
    return max(1, int(counts.max(initial=1)))


# --- sorted row gather: the transpose kernel -------------------------------


class _VBlockSchedule:
    """Chunk-major scheduling for sorted-id kernels whose output block is
    an EDGE chunk and whose inner grid dim iterates the chunk's vertex-
    block span (sorted_row_gather, the fused-bwd gd kernel). The shared
    scaffold: edge/vertex padding, per-chunk span bounds, and the clamped
    vertex-block index map."""

    def __init__(self, ids, num_rows, E, *, block_e, block_n, max_vblocks):
        self.E = E
        self.E_pad = pl.cdiv(E, block_e) * block_e
        self.N_pad = pl.cdiv(num_rows, block_n) * block_n
        self.nb = self.N_pad // block_n
        self.num_chunks = self.E_pad // block_e
        self.block_e, self.block_n = block_e, block_n
        ids_p = ids
        if self.E_pad != E:
            ids_p = jnp.pad(ids, (0, self.E_pad - E),
                            constant_values=num_rows + 1)
        self.ids3d = ids_p.reshape(self.num_chunks, 1, block_e)
        # per-chunk vertex-block span (ids sorted within each chunk):
        # first/last element of the chunk, clamped into [0, nb)
        firsts = jnp.clip(ids_p.reshape(self.num_chunks, block_e)[:, 0], 0,
                          self.N_pad - 1)
        lasts = jnp.clip(ids_p.reshape(self.num_chunks, block_e)[:, -1], 0,
                         self.N_pad - 1)
        self.vb_start = (firsts // block_n).astype(jnp.int32)
        self.vb_counts = jnp.minimum(
            (lasts // block_n).astype(jnp.int32) - self.vb_start + 1,
            max_vblocks,
        ).astype(jnp.int32)

    def pad_vertices(self, x):
        if self.N_pad != x.shape[0]:
            x = jnp.pad(x, ((0, self.N_pad - x.shape[0]), (0, 0)))
        return x

    def pad_edges(self, arr):
        if self.E_pad != arr.shape[0]:
            arr = jnp.pad(
                arr, ((0, self.E_pad - arr.shape[0]),)
                + ((0, 0),) * (arr.ndim - 1))
        return arr

    def vtx_index(self, k, j, starts, counts):
        # clamp past-count iterations onto the last valid block: Mosaic
        # skips the DMA when consecutive steps map to the same block
        return (
            jnp.minimum(
                starts[k] + jnp.minimum(j, jnp.maximum(counts[k] - 1, 0)),
                self.nb - 1,
            ),
            0,
        )

    def vtx_spec(self, F):
        return pl.BlockSpec((self.block_n, F), self.vtx_index)

    def ids_spec(self):
        return pl.BlockSpec((1, 1, self.block_e), lambda k, j, s, c: (k, 0, 0))

    def out_spec(self, F):
        return pl.BlockSpec((self.block_e, F), lambda k, j, s, c: (k, 0))


def _gather_kernel(
    vb_starts_ref, vb_counts_ref, ids_ref, x_ref, out_ref, *,
    block_n, block_e, precision,
):
    k = pl.program_id(0)  # edge chunk (owns the resident out block)
    j = pl.program_id(1)  # vertex-block iteration within the chunk's span

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j < vb_counts_ref[k])
    def _accumulate():
        ids = ids_ref[0, 0]  # [block_e] int32 (global, sorted)
        vb = vb_starts_ref[k] + j  # this iteration's vertex block
        rel2 = (ids - vb * block_n)[:, None]  # [block_e, 1] (2-D: Mosaic)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_e, block_n), 1)
        onehot = jnp.where(
            (cols == rel2) & (rel2 >= 0) & (rel2 < block_n), 1.0, 0.0
        ).astype(x_ref.dtype)
        # [block_e, block_n] @ [block_n, F] -> rows selected on the MXU;
        # OOB/masked ids match no column and stay zero
        out_ref[...] += jax.lax.dot_general(
            onehot, x_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=out_ref.dtype,
            precision=precision,
        )


@functools.lru_cache(maxsize=None)
def _make_srg(num_rows, max_vblocks, block_e, block_n, interpret, precision,
              scatter_mc):
    def impl(x, ids):
        E = ids.shape[0]
        F = x.shape[1]
        vs = _VBlockSchedule(ids, num_rows, E, block_e=block_e,
                             block_n=block_n, max_vblocks=max_vblocks)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(vs.num_chunks, max_vblocks),
            in_specs=[vs.ids_spec(), vs.vtx_spec(F)],
            out_specs=vs.out_spec(F),
        )
        operands = (vs.vb_start, vs.vb_counts, vs.ids3d, vs.pad_vertices(x))
        out = pl.pallas_call(
            functools.partial(
                _gather_kernel, block_n=block_n, block_e=block_e,
                precision=_precision(precision),
            ),
            grid_spec=grid_spec,
            out_shape=_out_struct((vs.E_pad, F), jnp.float32, *operands),
            interpret=interpret,
        )(*operands)
        return out[:E].astype(x.dtype)

    @jax.custom_vjp
    def f(x, ids):
        return impl(x, ids)

    def fwd(x, ids):
        return impl(x, ids), ids

    def bwd(ids, g):
        # exact transpose: segment-sum of the cotangent rows back onto the
        # gathered vertices — the EXISTING sorted scatter kernel
        from dgraph_tpu.ops.local import sorted_segment_sum_any

        dx = sorted_segment_sum_any(
            g, ids, num_rows, block_e, block_n, scatter_mc
        )
        return dx, None

    f.defvjp(fwd, bwd)
    return f


def _fused_bwd_kernel(
    vb_starts_ref, vb_counts_ref, ids_ref, *refs,
    block_n, block_e, precision, has_weight,
):
    """gd[e] = w[e] * g[ids[e]] * 1[data[e] + bias[ids[e]] > 0] in ONE
    chunk-major pass: the fused scatter's data-gradient with no [E, F]
    HBM intermediates (no bias-rows take, no g-rows take, no act
    materialization — the r4 composed bwd streamed all three).

    ``has_weight`` adds the chunk's edge weights as an operand (the
    forward's ``[num_chunks, 1, block_e]`` layout) and a second output,
    d_w[e] = Σ_f g[ids[e]] * relu(data[e] + bias[ids[e]]): at the finish
    step the gathered g rows, the pre-activation and the weights are all
    in VMEM, so the product is a multiply and the row-dot one small MXU
    contraction there. Without it the kernel is operand for operand the
    unweighted one.

    Chunk-major grid like :func:`_gather_kernel`; g and bias rows are
    accumulated per vertex-block via one-hot matmuls (disjoint per edge,
    so plain += is exact), and the activation mask is decided in f32 at
    the last vertex block of the chunk's span — the same rounding story
    as the forward kernel (operands rounded to the data dtype, compare
    in f32). Masked and padded edges match no one-hot column (or only
    zero-padded vertex rows), so their gd and d_w read 0."""
    if has_weight:
        (wgt_ref, data_ref, g_ref, bias_ref, out_ref, dw_ref,
         g_acc, bias_acc) = refs
    else:
        data_ref, g_ref, bias_ref, out_ref, g_acc, bias_acc = refs
    k = pl.program_id(0)  # edge chunk (owns the resident out block)
    j = pl.program_id(1)  # vertex-block iteration within the chunk's span

    @pl.when(j == 0)
    def _init():
        # accumulate in f32 VMEM SCRATCH, not in the output: an f32
        # [E, F] out would be an f32 HBM stream (the discipline the gd
        # kernel exists to avoid) — out is written once, in the compute
        # dtype, at the last step of the span
        g_acc[...] = jnp.zeros_like(g_acc)
        bias_acc[...] = jnp.zeros_like(bias_acc)

    @pl.when(j < vb_counts_ref[k])
    def _accumulate():
        ids = ids_ref[0, 0]  # [block_e] int32 (global, sorted)
        vb = vb_starts_ref[k] + j
        rel2 = (ids - vb * block_n)[:, None]  # [block_e, 1] (2-D: Mosaic)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_e, block_n), 1)
        onehot = jnp.where(
            (cols == rel2) & (rel2 >= 0) & (rel2 < block_n), 1.0, 0.0
        ).astype(g_ref.dtype)
        g_acc[...] += jax.lax.dot_general(
            onehot, g_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=g_acc.dtype, precision=precision,
        )
        bias_acc[...] += jax.lax.dot_general(
            onehot, bias_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=bias_acc.dtype, precision=precision,
        )

    # runs AFTER this step's accumulation (kernel body is sequential), so
    # the span's g/bias sums are complete exactly once per chunk
    @pl.when(j == vb_counts_ref[k] - 1)
    def _finish():
        chunk = data_ref[0]  # [block_e, F]
        pre = chunk.astype(jnp.float32) + bias_acc[...]
        act = (pre > 0).astype(jnp.float32)
        gd = g_acc[...] * act
        if has_weight:
            # the row-dot Σ_f g·relu(pre) on the MXU: a ones tile
            # contracted with the product over F leaves the edges on the
            # LANES, which is d_w's layout; a VPU `.sum(axis=-1)` is a
            # cross-lane reduction per row plus a sublane->lane relayout
            # (measured: +4.5 ms a call at 2.33 M edges, v5e). Row 0 of the
            # eight identical rows is stored. The product enters the MXU
            # in the data dtype, as every other operand of this op does.
            prod = (g_acc[...] * jnp.maximum(pre, 0)).astype(data_ref.dtype)
            dw_ref[0] = jax.lax.dot_general(
                jnp.ones((8, prod.shape[1]), prod.dtype), prod,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision,
            )[0:1]
            # f32 BEFORE the [:, None] (Mosaic inserts a minor dim on
            # 32-bit vectors only: see _kernel_bias_relu)
            gd = gd * wgt_ref[0, 0].astype(jnp.float32)[:, None]
        out_ref[...] = gd.astype(out_ref.dtype)


@functools.lru_cache(maxsize=None)
def _make_fused_bwd(num_rows, max_vblocks, block_e, block_n, interpret,
                    precision, has_weight=False):
    """Builder for the fused scatter's data-gradient kernel (see
    :func:`_fused_bwd_kernel`). Returns fn(data, g, bias, ids,
    edge_weight) -> ([E, F] gd in data's dtype, [E] f32 d_w; None where
    ``has_weight`` is off, and ``edge_weight`` is then not read)."""

    def impl(data, g, bias, ids, edge_weight=None):
        E, F = data.shape
        vs = _VBlockSchedule(ids, num_rows, E, block_e=block_e,
                             block_n=block_n, max_vblocks=max_vblocks)
        data3d = vs.pad_edges(data).reshape(vs.num_chunks, block_e, F)
        in_specs = [
            vs.ids_spec(),
            pl.BlockSpec((1, block_e, F), lambda k, j, s, c: (k, 0, 0)),
            vs.vtx_spec(F),
            vs.vtx_spec(F),
        ]
        operands = [vs.ids3d, data3d, vs.pad_vertices(g),
                    vs.pad_vertices(bias)]
        if has_weight:
            # one value an edge, chunk-major like the ids
            in_specs.insert(1, vs.ids_spec())
            operands.insert(1, vs.pad_edges(edge_weight).reshape(
                vs.num_chunks, 1, block_e))
        call_args = (vs.vb_start, vs.vb_counts, *operands)
        out_specs = [vs.out_spec(F)]
        out_shape = [_out_struct((vs.E_pad, F), data.dtype, *call_args)]
        if has_weight:
            out_specs.append(vs.ids_spec())
            out_shape.append(_out_struct(
                (vs.num_chunks, 1, block_e), jnp.float32, *call_args))
        gd, *d_w = pl.pallas_call(
            functools.partial(
                _fused_bwd_kernel, block_n=block_n, block_e=block_e,
                precision=_precision(precision), has_weight=has_weight,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(vs.num_chunks, max_vblocks),
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=[
                    pltpu.VMEM((block_e, F), jnp.float32),  # g-rows acc
                    pltpu.VMEM((block_e, F), jnp.float32),  # bias-rows acc
                ],
            ),
            out_shape=out_shape,
            interpret=interpret,
        )(*call_args)
        return gd[:E], (d_w[0].reshape(vs.E_pad)[:E] if d_w else None)

    return impl


def sorted_row_gather(
    x: jax.Array,  # [N, F] vertex table
    ids: jax.Array,  # [E] int32 MONOTONE non-decreasing row ids
    *,
    max_vblocks: int,  # >= max vertex blocks any edge chunk spans
    block_e: int = 512,
    block_n: int = 256,
    scatter_mc: int = 1,  # max_chunks hint for the VJP's segment sum
    interpret: bool = False,
    precision: str = "highest",  # the op is a pure row COPY: f32 inputs
    # must come back bit-faithful by default; callers in a bf16 compute
    # path pass "default" explicitly (the shared dtype->precision policy)
) -> jax.Array:
    """``x[ids]`` for sorted ids as blocked one-hot MXU matmuls — the exact
    TRANSPOSE of :func:`sorted_segment_sum` (same tiles, roles of the
    resident/streamed operands swapped). Rows whose id falls outside
    [0, N) come back zero (masked-edge convention). Differentiable: the
    VJP is the sorted segment-sum kernel.

    Compute ``max_vblocks`` at plan-build time with
    :func:`max_vblocks_hint`; the schedule reads only each chunk's
    first/last id (sortedness), so it is computed in-jit.
    """
    return _make_srg(
        x.shape[0], max_vblocks, block_e, block_n, interpret, precision,
        scatter_mc,
    )(x, ids)


def chunk_vblock_spans(
    segment_ids, num_rows: int, block_e: int = 512, block_n: int = 256
):
    """Host-side (concrete sorted ids): for each ``block_e`` edge chunk, the
    number of ``block_n``-row vertex blocks it spans (the grid of
    :func:`sorted_row_gather` is (chunks, the maximum of these))."""
    import numpy as np

    ids = np.clip(np.asarray(segment_ids), 0, max(num_rows - 1, 0))
    E = ids.shape[0]
    if E == 0:
        return np.zeros(0, np.int64)
    E_pad = -(-E // block_e) * block_e
    ids_p = np.pad(ids, (0, E_pad - E), constant_values=ids[-1])
    chunks = ids_p.reshape(-1, block_e)
    return chunks[:, -1] // block_n - chunks[:, 0] // block_n + 1


def max_vblocks_hint(
    segment_ids, num_rows: int, block_e: int = 512, block_n: int = 256
) -> int:
    """Host-side (concrete sorted ids) bound for
    :func:`sorted_row_gather`'s ``max_vblocks``: the max number of
    ``block_n``-row vertex blocks any ``block_e`` edge chunk spans."""
    spans = chunk_vblock_spans(segment_ids, num_rows, block_e, block_n)
    return max(1, int(spans.max(initial=1)))
