"""Rank-local gather/scatter primitives (the ``torch_local`` CUDA kernels'
TPU equivalents).

Reference: ``DGraph/distributed/RankLocalOps.py`` +
``DGraph/distributed/csrc/local_data_kernels.cuh`` — masked gather
(``Rank_Local_Gather_Kernel``, ``local_data_kernels.cuh:160-206``),
atomicAdd scatter (``:208-253``), generic set/add masked scatter-gather
(``:301-342``) with a float4-vectorized variant (``:353-406``).

TPU-first: there are no atomics on TPU; scatter-add is expressed as a
segment reduction, which XLA lowers to an efficient sorted/one-hot scheme
on the MXU/VPU, and which a Pallas kernel (``dgraph_tpu.ops.pallas_segment``)
can further specialize for sorted-by-destination edge plans (the plan
builder already emits dst-sorted edges within each rank — same prerequisite
the reference's dedup/renumbering establishes for its alltoallv path).

The reference keeps a torch fallback beside its CUDA kernels
(``RankLocalOps.py:21-31,66-70``); we keep jnp implementations beside the
Pallas kernels the same way — the jnp path is also the oracle in tests.

This module is the single dispatch point: swap ``segment_sum`` here and
every collective / model picks it up.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# The device operations of a local gather run under three named scopes, the
# children of ``dgraph.local_take`` (docs/tracing.md): ``rows`` a row gather,
# ``mask`` a select or mask pass over gathered rows, ``slice`` a table slice,
# its tie and its copy. The names are what the per-layer metrics
# ``gather_rows_ms.*`` / ``gather_mask_ms.*`` match in a device trace.


@jax.custom_jvp
def run_after(token, cols):
    """``cols`` unchanged, but not available before ``token`` is."""
    return jax.lax.optimization_barrier((token, cols))[1]


@run_after.defjvp
def _run_after_jvp(primals, tangents):
    # the tie is the forward's alone: tangents (and so cotangents) pass
    # straight through and the token gets none. The barrier's own
    # transpose would order the chunks' cotangents as well, which holds
    # [E, chunk] tensors longer (+3.5 % temporaries on gcn_papers100m.w4,
    # PERF.md PR 31)
    return run_after(*primals), tangents[1]


# On-chip memory a row gather's table can be placed in: 128 MiB on a TPU
# v5e (as on a v4 and a v6e). A hardware fact, not an option.
ON_CHIP_BYTES = 128 << 20
# What the compiler gives ONE gather table of it: seven eighths. Read off
# compiles for a described v5e (a [rows, 128] bf16 table alone in its
# program, 2.56 M ids or 128): placed at 112 MiB, left in HBM at 113; on
# the chip a row from a 104 MB table costs what one from 43 MB does
# (1.86 ns), from a 112 MiB one 1.96 (PERF.md PR 35).
GATHER_TABLE_BYTES = ON_CHIP_BYTES // 8 * 7
# Row parts a gather may be taken in (on_chip_row_parts has the arithmetic).
MAX_ROW_PARTS = 3


def on_chip_row_parts(n_rows: int, row_bytes: int) -> int:
    """How a row gather from an ``[n_rows, row_bytes]`` table slice reads
    on-chip memory: 1 = the slice fits whole (``GATHER_TABLE_BYTES``), k =
    taken in k equal row parts that fit (:func:`row_take`), 0 = not at all.

    The one size rule of the local gather: ``row_take`` cuts by it,
    ``collectives.map_vertex_chunks`` ties a chunk where it is not 0, the
    fused GCN layer's transposed backward asks for 1.

    Why k stops at ``MAX_ROW_PARTS``: every part gathers every id, so at
    2.56 M ids k parts cost k x 4.77 ms of gathers from on-chip memory and
    one select pass over k + 1 ``[E, C]`` streams against 30.1 ms for the
    one gather from HBM with its mask pass. Measured alone on a v5e, mask
    pass included: 13.7 ms at k = 2 (a 207 MB table) and 19.3 at k = 3
    (311 MB); by the same arithmetic ~25 at k = 4 with the slice copies
    and the ordering still to pay, ~31 at 5 (PERF.md PR 35). An ``[E, C]``
    edge tensor (597-655 MB: six parts) keeps its one gather."""
    nbytes = n_rows * row_bytes
    if nbytes <= GATHER_TABLE_BYTES:
        return 1
    k = -(-nbytes // GATHER_TABLE_BYTES)
    return k if k <= MAX_ROW_PARTS else 0


def _take_in_row_parts(chunk, idx, k, oob):
    """:func:`row_take`'s gather from a table too large for on-chip memory:
    ``chunk`` cut by rows into ``k`` equal parts that fit, each part
    gathered with the ids shifted and clamped into it, and the part that
    holds an id chosen per row in ONE select chain over the k results (it
    fuses with ``local_take``'s edge-mask multiply: one pass over ``[E,
    C]`` where the unparted gather has one). Every output row is one table
    row (or a zero row), so the result is ``row_take``'s to the bit.

    Order is what gets the parts placed (read off compiled modules,
    scripts/gather_placement.py): part p + 1 is cut out of the WHOLE chunk
    after the chunk has been tied behind part p's gather, so it is a
    buffer written for its own gather and needs its place through that
    gather only (cut before the tie it is made early and held in HBM: the
    bias slice of PR 33). The ids stay whole: tied to the gathers and
    selected once, gcn_papers100m.w4's train step holds 4.52 GB of
    temporaries against the unparted 4.64; ids in two pieces that each
    select and are concatenated cost two more passes and leave a part a
    layer in HBM (PERF.md PR 35)."""
    from dgraph_tpu.obs.metrics import default_registry

    n = chunk.shape[0]
    rows = -(-n // k)
    idx = jnp.where(idx < 0, idx + n, idx)  # numpy's wrap, as x[idx] has it
    if oob != "fill":
        idx = jnp.clip(idx, 0, n - 1)
    taken = []
    for lo in range(0, n, rows):
        with jax.named_scope("slice"):
            part = (run_after(taken[-1], chunk) if taken else chunk)[
                lo : lo + rows]
        with jax.named_scope("rows"):
            taken.append(jnp.take(
                part, jnp.clip(idx - lo, 0, part.shape[0] - 1), axis=0,
                mode="clip"))
    default_registry.counter("gather.row_parts", len(taken))
    with jax.named_scope("mask"):
        out = taken[-1]
        for p in range(len(taken) - 2, -1, -1):
            out = jnp.where((idx < (p + 1) * rows)[:, None], taken[p], out)
        if oob == "fill":
            out = jnp.where(((idx >= 0) & (idx < n))[:, None], out, 0)
    return out


def row_take(
    x: jax.Array,
    idx: jax.Array,
    col_block: int | None = None,
    *,
    oob: str = "clamp",  # "clamp" (x[idx] semantics) | "fill" (OOB rows -> 0)
) -> jax.Array:
    """``x[idx]`` for [N, F] row gathers, split into <=``col_block``-wide
    column chunks.

    XLA's TPU row-gather fast path covers one (8,128) lane tile per row;
    rows wider than 128 f32 lanes fall off it (measured 28.9 ms plain vs
    4.3 ms split for [2.33M, 256] f32 on v5e, logs/kernels_r2.jsonl).
    Chunking the minor dim keeps every piece on the fast path — the TPU
    analogue of the reference's float4-vectorized gather
    (``local_data_kernels.cuh:353-406``): reshape the access so the memory
    system moves full-width units.

    A column chunk too large for on-chip memory, where a row costs 1.85 ns
    against 10.6 from HBM, is taken in row parts that fit
    (:func:`on_chip_row_parts`, :func:`_take_in_row_parts`): the same
    bits, gcn_papers100m.w4's 207 MB forward tables.

    Every gather runs under the named scope ``rows`` (a part gather's
    select chain under ``mask``, its slice under ``slice``): children of
    whatever scope the caller opened, ``dgraph.local_take`` above all.

    ``col_block=None`` reads :data:`dgraph_tpu.config.gather_col_block`;
    0 disables splitting. ``oob="fill"`` zeroes out-of-range rows (the
    padding convention VJPs need); "clamp" keeps plain-indexing semantics.
    """
    if col_block is None:
        from dgraph_tpu import config as _cfg

        col_block = _cfg.gather_col_block

    def one(chunk):
        if chunk.ndim == 2 and idx.ndim == 1:
            k = on_chip_row_parts(
                chunk.shape[0], chunk.shape[1] * chunk.dtype.itemsize)
            if k > 1:
                return _take_in_row_parts(chunk, idx, k, oob)
        with jax.named_scope("rows"):
            if oob == "fill":
                return jnp.take(chunk, idx, axis=0, mode="fill", fill_value=0)
            return chunk[idx]

    F = x.shape[-1]
    if not col_block or F <= col_block:
        return one(x)
    return jnp.concatenate(
        [one(x[..., j : j + col_block]) for j in range(0, F, col_block)], axis=-1
    )


def take_values(values: jax.Array, idx: jax.Array, lanes: int = 128,
                pieces: int = 8) -> jax.Array:
    """``values[idx]`` for a 1-D vector and in-range ids, as a ROW gather:
    the vector is viewed as ``[n / lanes, lanes]``, row ``idx // lanes`` is
    taken an id and lane ``idx % lanes`` selected from it. XLA's element
    gather costs 7.1 ns an id on a v5e whatever the width (16.7 ms for the
    2.33 M edge weights of gcn_arxiv.w1), its row gather of 512-byte rows
    from a table this small 3.2, select included (7.5 ms; PERF.md PR 33).
    Exact: one term of the lane sum is the value, the rest are zeros.

    The taken rows are ``lanes`` times the result (1.19 GB there), so the
    ids are cut into ``pieces`` that run one after the other (each piece's
    ids wait on the piece before: an ``optimization_barrier``, as
    ``collectives.map_vertex_chunks`` orders a layer's chunks) and only a
    piece's rows are live. A vector that does not divide into ``lanes``, or
    ids that do not divide into ``pieces``, take the element gather."""
    n = values.shape[0]
    if (values.ndim != 1 or idx.ndim != 1 or n % lanes
            or idx.shape[0] % pieces):
        return jnp.take(values, idx)
    table = values.reshape(n // lanes, lanes)
    out = []
    with jax.named_scope("slice"):
        id_pieces = jnp.split(idx, pieces)
    for ids in id_pieces:
        if out:
            with jax.named_scope("slice"):
                ids = jax.lax.optimization_barrier((out[-1], ids))[1]
        with jax.named_scope("rows"):
            rows = jnp.take(table, ids // lanes, axis=0)
        with jax.named_scope("mask"):
            hit = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1) == (
                ids % lanes)[:, None]
            out.append(jnp.where(hit, rows, 0).sum(-1))
    return jnp.concatenate(out)


@functools.lru_cache(maxsize=None)
def _make_take_rows(n_rows, sorted_ids, col_block, pallas, block_e, block_n,
                    mc, gather_mv=0, oob="fill"):
    """Row gather whose VJP is an explicitly-routed segment reduction.

    JAX's default transpose of ``x[idx]`` is a generic XLA scatter-add —
    measured 56 ms for [2.33M, 256] f32 on v5e, ~2x slower than a
    sorted-segment reduction and blind to both the plan's monotone owner
    ordering and the >128-lane gather cliff. This wrapper pins the
    backward to the same fast paths the forward collectives use (the
    reference hand-writes these transposes for the identical reason,
    ``_torch_func_impl.py:112-191``):
      - sorted ids + Pallas available -> one-hot MXU sorted_segment_sum
      - otherwise -> jax.ops.segment_sum (with the sortedness hint)

    The FORWARD can additionally run as the Pallas sorted-row-gather
    kernel when ``gather_mv > 0`` (the caller resolves
    ``config.use_pallas_gather`` — explicit opt-in until on-chip A/B data
    exists — BEFORE this lru-cached factory, so the flag is part of the
    cache key); it defines its own exact-transpose VJP, so the custom-VJP
    wrapper below is bypassed entirely in that case.
    """
    if pallas and gather_mv > 0:
        from dgraph_tpu.ops.pallas_segment import sorted_row_gather

        def take_kernel(x, idx):
            prec = "default" if x.dtype == jnp.bfloat16 else "highest"
            with jax.named_scope("rows"):
                return sorted_row_gather(
                    x, idx, max_vblocks=gather_mv, block_e=block_e,
                    block_n=block_n, scatter_mc=mc, precision=prec,
                )

        return take_kernel

    @jax.custom_vjp
    def take(x, idx):
        return row_take(x, idx, col_block, oob=oob)

    def fwd(x, idx):
        return take(x, idx), idx

    def bwd(idx, g):
        if pallas:
            from dgraph_tpu.ops.pallas_segment import sorted_segment_sum

            prec = "default" if g.dtype == jnp.bfloat16 else "highest"
            dx = sorted_segment_sum(
                g, idx, n_rows, max_chunks_per_block=mc,
                block_e=block_e, block_n=block_n, precision=prec,
            )
        else:
            dx = _acc_segment_sum(g, idx, n_rows, sorted_ids)
        return dx, None

    take.defvjp(fwd, bwd)
    return take


def take_rows(
    x: jax.Array,
    idx: jax.Array,
    *,
    indices_are_sorted: bool = False,
    col_block: int | None = None,
    pallas_hints: tuple | None = None,  # (block_e, block_n, max_chunks) or None
    gather_mv: int = 0,  # >0 + config.use_pallas_gather: Pallas fwd kernel
    oob: str = "fill",
) -> jax.Array:
    """``x[idx]`` row gather with a fast-path VJP (see
    :func:`_make_take_rows`). Out-of-range ids produce zero rows (padding
    convention) unless the caller says its ids are all in range
    (``oob="clamp"``: :func:`row_take`'s plain indexing, without the
    ``[E, F]`` select that zeroes a row). ``pallas_hints`` enables the
    sorted one-hot MXU kernel for the backward when ids are monotone
    (plan-guaranteed); ``gather_mv`` additionally enables the
    sorted-row-gather FORWARD kernel when ``config.use_pallas_gather`` is
    pinned on."""
    from dgraph_tpu import config as _cfg

    if col_block is None:
        col_block = _cfg.gather_col_block
    use_pallas = (
        pallas_hints is not None
        and indices_are_sorted
        and jax.default_backend() == "tpu"
    )
    be, bn, mc = pallas_hints if use_pallas else (0, 0, 0)
    mv = gather_mv if (use_pallas and _cfg.pallas_gather_enabled()) else 0
    return _make_take_rows(
        x.shape[0], indices_are_sorted, col_block, use_pallas, be, bn, mc, mv,
        oob,
    )(x, idx)


def sorted_segment_sum_any(data, sorted_ids, n_rows, be, bn, mc, gather_mv=0):
    """Sorted segment-sum via the Pallas MXU kernel when it's enabled AND
    the backend is TPU, jnp elsewhere. The single dispatch point for every
    sorted reduction (owner-side scatter and the halo sort route) so the
    kill switch (``config.use_pallas_scatter``, e.g. bench's failed
    self-check fallback) and the precision policy cannot diverge between
    call sites."""
    from dgraph_tpu import config as _cfg

    if _cfg.pallas_scatter_enabled() and jax.default_backend() == "tpu":
        from dgraph_tpu.ops.pallas_segment import sorted_segment_sum

        prec = "default" if data.dtype == jnp.bfloat16 else "highest"
        return sorted_segment_sum(
            data, sorted_ids, n_rows, max_chunks_per_block=mc,
            block_e=be, block_n=bn, gather_mv=gather_mv, precision=prec,
        )
    # fallback keeps the col-split-take VJP pinning (segment_sum wrapper),
    # not jax.ops.segment_sum's plain wide-gather transpose; the wrapper's
    # reduction runs through _acc_segment_sum, so low-precision inputs
    # accumulate in f32 exactly like the kernel's VMEM accumulator.
    return segment_sum(data, sorted_ids, n_rows, indices_are_sorted=True)


def fused_bias_relu_kernel_runs() -> bool:
    """The dispatch rule of the fused bias-relu kernel family: its kill
    switch is not thrown and the backend is a TPU."""
    from dgraph_tpu import config as _cfg

    return _cfg.pallas_fused_enabled() and jax.default_backend() == "tpu"


def sorted_segment_grad_bias_relu(
    rows, g_rows, sorted_ids, table, be, bn, mc, edge_weight=None,
):
    """Σ_{e: ids[e]=u} w·g_rows·1[rows + table[u] > 0], the fused op's
    gradient with its sides exchanged
    (``ops.pallas_segment.sorted_segment_grad_bias_relu``), under the
    precision policy of :func:`sorted_segment_sum_bias_relu_any`. Kernel
    only: callers ask :func:`fused_bias_relu_kernel_runs` first."""
    from dgraph_tpu.ops import pallas_segment

    return pallas_segment.sorted_segment_grad_bias_relu(
        rows, g_rows, sorted_ids, table, table.shape[0],
        edge_weight=edge_weight, max_chunks_per_block=mc, block_e=be,
        block_n=bn,
        precision="default" if rows.dtype == jnp.bfloat16 else "highest",
    )


def sorted_segment_sum_bias_relu_any(
    edata, sorted_ids, bias, n_rows, be, bn, mc, edge_weight=None,
    gather_mv=0,
):
    """Fused Σ w·relu(edata + bias[id]) for sorted ids — Pallas on TPU
    (``ops.pallas_segment.sorted_segment_sum_bias_relu``), composed jnp ops
    elsewhere. Same single-dispatch-point contract as
    :func:`sorted_segment_sum_any`: kill switch + precision policy live
    HERE, not at call sites."""
    from dgraph_tpu import config as _cfg

    # precision policy lives HERE: the kernel casts bias to the data dtype
    # internally; the composed fallback must match, or a f32 bias with
    # bf16 edata would promote every [e_pad, F] tensor of the fallback
    bias = bias.astype(edata.dtype)
    if fused_bias_relu_kernel_runs():
        from dgraph_tpu.ops.pallas_segment import sorted_segment_sum_bias_relu

        prec = "default" if edata.dtype == jnp.bfloat16 else "highest"
        return sorted_segment_sum_bias_relu(
            edata, sorted_ids, bias, n_rows, edge_weight=edge_weight,
            max_chunks_per_block=mc, block_e=be, block_n=bn,
            gather_mv=gather_mv, precision=prec,
        )
    # take via take_rows WITH the sorted hints so the bias-gradient
    # transpose rides the sorted segment-sum path, not XLA scatter-add;
    # hints honor the scatter kill switch (a vetoed kernel must not keep
    # running via the hinted VJP, and the noscatter A/Bs must really
    # measure the XLA path)
    hints = ((be, bn, mc)
             if _cfg.pallas_scatter_enabled() else None)
    bias_rows = take_rows(
        bias, sorted_ids, indices_are_sorted=True,
        pallas_hints=hints, gather_mv=gather_mv,
    )
    m = jax.nn.relu(edata + bias_rows)
    if edge_weight is not None:
        m = m * edge_weight[:, None].astype(m.dtype)
    # route the reduction through sorted_segment_sum_any, NOT the plain
    # wrapper: with the fused kernel off but the plain scatter on (the
    # r4 bench exactly — fused self-check vetoed by the Mosaic bf16 bug)
    # the wrapper sent the model's MAIN aggregation to XLA scatter-add,
    # bypassing the healthy Pallas kernel
    return sorted_segment_sum_any(m, sorted_ids, n_rows, be, bn, mc,
                                  gather_mv=gather_mv)


@functools.lru_cache(maxsize=None)
def _make_take_rows_sortroute(n_rows, col_block, be, bn, mc, oob="fill"):
    """Row gather for UNSORTED ids whose VJP still runs the sorted fast
    path: the plan carries a static permutation ``perm`` with
    ``ids[perm]`` monotone (``EdgePlan.halo_sort_perm``), so the transpose
    is gather-by-perm (cheap, col-split) + sorted segment-sum (Pallas MXU)
    instead of XLA's generic scatter-add (~2x slower at arxiv scale)."""

    @jax.custom_vjp
    def take(x, idx, perm, sorted_ids):
        return row_take(x, idx, col_block, oob=oob)

    def fwd(x, idx, perm, sorted_ids):
        return take(x, idx, perm, sorted_ids), (perm, sorted_ids)

    def bwd(res, g):
        perm, sorted_ids = res
        gp = row_take(g, perm, col_block)  # static permutation, in-range
        dx = sorted_segment_sum_any(gp, sorted_ids, n_rows, be, bn, mc)
        return dx, None, None, None

    take.defvjp(fwd, bwd)
    return take


def take_rows_sort_route(x, idx, perm, sorted_ids, *, pallas_hints,
                         col_block=None, oob="fill"):
    """``x[idx]`` (OOB -> 0, or :func:`row_take`'s plain indexing for a
    caller whose ids are all in range: ``oob="clamp"``) with the VJP routed
    through a plan-provided sorting permutation of ``idx`` (see
    :func:`_make_take_rows_sortroute`)."""
    if col_block is None:
        from dgraph_tpu import config as _cfg

        col_block = _cfg.gather_col_block
    be, bn, mc = pallas_hints
    return _make_take_rows_sortroute(x.shape[0], col_block, be, bn, mc, oob)(
        x, idx, perm, sorted_ids
    )


@functools.lru_cache(maxsize=None)
def _make_segment_sum_sortroute(n_rows, col_block, be, bn, mc):
    """segment-sum for UNSORTED ids via the plan's sorting permutation:
    forward = gather-by-perm + sorted segment-sum (Pallas MXU); VJP = plain
    row gather by the original ids (the composite's exact transpose —
    d_data[i] = g[ids[i]] — so the permutation drops out of the backward)."""

    @jax.custom_vjp
    def segsum(data, ids, perm, sorted_ids):
        dp = row_take(data, perm, col_block)
        return sorted_segment_sum_any(dp, sorted_ids, n_rows, be, bn, mc)

    def fwd(data, ids, perm, sorted_ids):
        return segsum(data, ids, perm, sorted_ids), ids

    def bwd(ids, g):
        return row_take(g, ids, col_block, oob="fill"), None, None, None

    segsum.defvjp(fwd, bwd)
    return segsum


def segment_sum_sort_route(data, ids, perm, sorted_ids, n_rows, *,
                           pallas_hints, col_block=None):
    """Segment-sum of rows with unsorted ``ids`` routed through the plan's
    sorting permutation (see :func:`_make_segment_sum_sortroute`)."""
    if col_block is None:
        from dgraph_tpu import config as _cfg

        col_block = _cfg.gather_col_block
    be, bn, mc = pallas_hints
    return _make_segment_sum_sortroute(n_rows, col_block, be, bn, mc)(
        data, ids, perm, sorted_ids
    )


def _acc_segment_sum(data, ids, num_segments, indices_are_sorted):
    """``jax.ops.segment_sum`` with a 32-bit accumulator for low-precision
    data: a bf16 running sum saturates (1.0 < ulp(256) = 2, so summing
    0/1 masks stalls at 256 and hub-vertex feature sums lose terms the
    same way). The Pallas kernels accumulate f32 in VMEM and the
    reference accumulates via f32 atomicAdd — every XLA reduction path
    goes through here so the three implementations agree to one output
    rounding."""
    if data.dtype in (jnp.bfloat16, jnp.float16):
        return jax.ops.segment_sum(
            data.astype(jnp.float32), ids, num_segments=num_segments,
            indices_are_sorted=indices_are_sorted,
        ).astype(data.dtype)
    return jax.ops.segment_sum(
        data, ids, num_segments=num_segments,
        indices_are_sorted=indices_are_sorted,
    )


@functools.lru_cache(maxsize=None)
def _make_segment_sum(num_segments, sorted_ids, col_block):
    """segment_sum whose VJP is a column-split take (the >128-lane row
    gather cliff applies to the backward's ``g[ids]`` exactly as it does to
    forward gathers — measured 28.9 ms plain vs 4.3 ms col-split for
    [2.33M, 256] f32 on v5e)."""

    @jax.custom_vjp
    def segsum(data, ids):
        return _acc_segment_sum(data, ids, num_segments, sorted_ids)

    def fwd(data, ids):
        return segsum(data, ids), ids

    def bwd(ids, g):
        return row_take(g, ids, col_block, oob="fill"), None

    segsum.defvjp(fwd, bwd)
    return segsum


def masked_gather(src: jax.Array, idx: jax.Array, mask: jax.Array) -> jax.Array:
    """out[i] = src[idx[i]] * mask[i] — ``Rank_Local_Gather_Kernel`` parity."""
    return row_take(src, idx) * mask[..., None].astype(src.dtype)


def masked_scatter(
    dst: jax.Array, idx: jax.Array, src: jax.Array, mask: jax.Array
) -> jax.Array:
    """dst[idx[i]] = src[i] where mask[i] — ``Masked_Scatter_Gather_Kernel``
    with the Set op (``local_data_kernels.cuh:301-342``); set semantics, last
    writer wins on duplicates (XLA scatter)."""
    safe_idx = jnp.where(mask > 0, idx, dst.shape[0])  # OOB rows dropped
    return dst.at[safe_idx].set(src, mode="drop")


def segment_sum(
    data: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    indices_are_sorted: bool = False,
) -> jax.Array:
    """Sum rows of ``data`` into ``num_segments`` buckets by ``segment_ids``.

    The TPU replacement for atomicAdd scatter (``local_data_kernels.cuh:208-253``).
    ``indices_are_sorted=True`` (plan-guaranteed when
    ``EdgePlan.owner_sorted``) lets XLA use the cheaper monotone-scatter path.

    For [E, F] data the VJP is pinned to a column-split take
    (:func:`_make_segment_sum`) instead of JAX's default plain gather.
    """
    if data.ndim == 2:
        from dgraph_tpu import config as _cfg

        return _make_segment_sum(
            num_segments, indices_are_sorted, _cfg.gather_col_block
        )(data, segment_ids)
    return _acc_segment_sum(data, segment_ids, num_segments,
                            indices_are_sorted)


def scatter_add_relu(
    data: jax.Array, segment_ids: jax.Array, num_segments: int,
    indices_are_sorted: bool = False,
) -> jax.Array:
    """out[s] = Σ max(data[i], 0) over segment s — parity with the reference's
    fused ReLU+atomicAdd kernel (``Fused_ReLU_Scatter_Kernel``,
    ``local_data_kernels.cuh:34-72``). On TPU the ReLU fuses into the
    segment reduction's input by XLA; expressing it as one call keeps the
    reference's fused API surface."""
    return segment_sum(
        jax.nn.relu(data), segment_ids, num_segments, indices_are_sorted
    )


def scatter_add_sum_relu(
    data1: jax.Array, data2: jax.Array, segment_ids: jax.Array, num_segments: int,
    indices_are_sorted: bool = False,
) -> jax.Array:
    """out[s] = Σ max(data1[i] + data2[i], 0) — parity with
    ``Fused_Sum_Norm_Scatter_Kernel`` (``local_data_kernels.cuh:74-116``):
    residual-add + ReLU fused into the scatter. One XLA fusion on TPU."""
    return segment_sum(
        jax.nn.relu(data1 + data2), segment_ids, num_segments, indices_are_sorted
    )


def sparse_scatter_add(dst: jax.Array, idx: jax.Array, src: jax.Array) -> jax.Array:
    """dst[idx[i]] += src[i], rows with idx < 0 (or >= len(dst)) dropped —
    parity with ``Sparse_Scatter_Kernel`` (``local_data_kernels.cuh:117-158``),
    the reference's "-1 means skip" masking convention (SURVEY §7).

    Negative indices would WRAP under JAX's .at[] semantics, so they are
    remapped to an out-of-bounds sentinel that mode="drop" discards.
    """
    idx = jnp.where(idx < 0, dst.shape[0], idx)
    return dst.at[idx].add(src, mode="drop")


def segment_max(data: jax.Array, segment_ids: jax.Array, num_segments: int,
                indices_are_sorted: bool = False) -> jax.Array:
    """Per-segment max (for attention softmax stabilization). Empty segments
    produce -inf; callers mask afterwards."""
    return jax.ops.segment_max(
        data, segment_ids, num_segments=num_segments,
        indices_are_sorted=indices_are_sorted,
    )


def segment_mean(
    data: jax.Array, segment_ids: jax.Array, num_segments: int, eps: float = 1e-12
) -> jax.Array:
    """Per-segment mean with safe division for empty segments."""
    sums = segment_sum(data, segment_ids, num_segments)
    counts = segment_sum(jnp.ones((data.shape[0], 1), data.dtype), segment_ids, num_segments)
    return sums / jnp.maximum(counts, eps)


def segment_softmax(
    logits: jax.Array, segment_ids: jax.Array, num_segments: int, mask: jax.Array,
    indices_are_sorted: bool = False,  # plan-guaranteed for owner-side ids
) -> jax.Array:
    """Numerically-stable softmax over segments (per-dst-vertex attention).

    The reference RGAT computes this with an explicit gather/scatter round
    trip over the network (denominator scatter + gather,
    ``experiments/OGB-LSC/RGAT.py:174-206``); with dst-owned edges it is a
    purely local segment operation.

    Args:
      logits: [E, H] per-edge (per-head) attention logits.
      mask: [E] 1.0 for real edges.
    Returns [E, H] normalized weights (masked edges -> 0).
    """
    logits = jnp.where(mask[..., None] > 0, logits, -jnp.inf)
    seg_max = segment_max(logits, segment_ids, num_segments, indices_are_sorted)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    shifted = jnp.where(mask[..., None] > 0, logits - seg_max[segment_ids], -jnp.inf)
    expd = jnp.where(mask[..., None] > 0, jnp.exp(shifted), 0.0)
    denom = segment_sum(expd, segment_ids, num_segments, indices_are_sorted)
    return expd / jnp.maximum(denom[segment_ids], 1e-12)
