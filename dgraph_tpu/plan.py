"""Communication planning: host-side builders of static-shape, padded plans.

This module is the TPU-native re-design of the reference's planning layer:

- ``DGraph/distributed/commInfo.py`` (CommunicationPattern +
  build_communication_pattern): reproduced here as :class:`CommPattern` /
  :func:`build_comm_pattern` with the same semantics (per-rank local/halo
  vertex sets, local edge list with halo appended after locals, CSR send
  indices/offsets, comm_map, one-sided put offsets) — but built with a
  *global* host view (no collectives at build time; on TPU the host sees the
  whole graph, so ``compute_comm_map``'s ``dist.all_gather``
  (``commInfo.py:148-155``) becomes a pure bincount).
- ``DGraph/distributed/nccl/_NCCLCommPlan.py`` (NCCLGraphCommPlan +
  COO_to_NCCLCommPlan): its internal/boundary edge split, (rank, vertex-id)
  dedup and per-peer split bookkeeping are subsumed by :class:`EdgePlan` /
  :func:`build_edge_plan`, which additionally **pads every per-peer segment
  to a single static size** so one XLA program covers every rank and every
  step (the reference computes exact per-peer splits for alltoallv;
  XLA's static-shape model wants maxima + masks instead).

Conventions (differ from the reference where TPU-first design wins):

- Edge lists are ``[2, E]`` (src row 0, dst row 1), not ``[E, 2]``.
- Vertices must be renumbered into contiguous per-rank blocks
  (:func:`dgraph_tpu.partition.renumber_contiguous`) before plan build.
  Contiguity makes "sorted by global id" == "grouped by owner rank", the
  invariant both the reference's halo ordering and ours rely on.
- Default edge owner is the **dst** rank (the reference uses src,
  ``commInfo.py:64-78``): with dst ownership every aggregation
  (scatter-add, softmax-over-incoming-edges for attention) is rank-local
  and only the src-side feature gather communicates. The reference's RGAT
  needs 6 comm ops per layer per relation (``RGAT.py:174-206``); dst
  ownership needs 1-2. ``edge_owner="src"`` is supported for parity.
- All plan arrays are stacked with a leading ``[world_size]`` axis, ready to
  shard over the ``graph`` mesh axis with ``PartitionSpec('graph')``.

Halo slot numbering: on a rank r with ``n_pad`` padded local vertices and
send pad ``s_pad``, the halo copy of a vertex owned by rank p that appears at
position i of p's send-list-to-r lives at index ``n_pad + p*s_pad + i`` of
the concatenated ``[local ; halo]`` feature buffer. After
``lax.all_to_all`` the received block from peer p lands exactly at rows
``[p*s_pad, (p+1)*s_pad)`` of the halo buffer, so no post-exchange scatter
is needed (the reference needs an explicit recv-placement scatter,
``_torch_func_impl.py:98-107``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import logging
from typing import Any, Optional

import numpy as np

import jax

_logger = logging.getLogger("dgraph_tpu.plan")

# ---------------------------------------------------------------------------
# pytree dataclass helper
# ---------------------------------------------------------------------------


def pytree_dataclass(cls=None, *, static: tuple[str, ...] = ()):
    """Register a frozen dataclass as a JAX pytree with some static fields."""

    def wrap(c):
        c = dataclasses.dataclass(frozen=True)(c)
        fields = [f.name for f in dataclasses.fields(c)]
        leaf_names = tuple(n for n in fields if n not in static)

        def flatten(obj):
            return tuple(getattr(obj, n) for n in leaf_names), tuple(
                getattr(obj, n) for n in static
            )

        def unflatten(aux, leaves):
            kwargs = dict(zip(leaf_names, leaves))
            kwargs.update(dict(zip(static, aux)))
            return c(**kwargs)

        jax.tree_util.register_pytree_node(c, flatten, unflatten)
        return c

    return wrap if cls is None else wrap(cls)


# ---------------------------------------------------------------------------
# Parity layer: per-rank CommPattern (reference commInfo.py semantics)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CommPattern:
    """Per-rank halo-exchange metadata, parity with the reference's
    ``CommunicationPattern`` (``DGraph/distributed/commInfo.py:7-32``).

    Unpadded, host-side (numpy). The padded SPMD plan is :class:`EdgePlan`.
    """

    rank: int
    world_size: int
    num_local_vertices: int
    num_halo_vertices: int
    # [E_r, 2] local-numbered edges; halo ids appended after locals
    local_edge_list: np.ndarray
    # CSR send indexing: local vertex ids to send, grouped by target rank
    send_local_idx: np.ndarray  # [total_sends]
    send_offset: np.ndarray  # [world_size + 1]
    recv_offset: np.ndarray  # [world_size + 1]
    comm_map: np.ndarray  # [world_size, world_size]
    # one-sided put offsets (parity with commInfo.py:29-31; on TPU these are
    # not needed at runtime — all_to_all computes placement — but they are
    # kept for API parity and test cross-checks)
    put_forward_remote_offset: np.ndarray  # [world_size]
    put_backward_remote_offset: np.ndarray  # [world_size]


def compute_local_vertices(partitioning: np.ndarray, rank: int) -> np.ndarray:
    """Global ids owned by `rank`. Parity: ``commInfo.py:35-38``."""
    return np.nonzero(np.asarray(partitioning) == rank)[0]


def compute_halo_vertices(
    edge_index: np.ndarray,
    src_partitioning: np.ndarray,
    rank: int,
    dst_partitioning: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Unique remote dst vertices of edges whose src is local to `rank`.

    Parity: ``commInfo.py:41-62`` (supports bipartite via dst_partitioning).
    """
    if dst_partitioning is None:
        dst_partitioning = src_partitioning
    src, dst = edge_index
    cross = (src_partitioning[src] == rank) & (dst_partitioning[dst] != rank)
    return np.unique(dst[cross])


def compute_local_edge_list(
    edge_index: np.ndarray,
    partitioning: np.ndarray,
    local_vertices: np.ndarray,
    halo_vertices: np.ndarray,
    rank: int,
) -> np.ndarray:
    """Edges owned by `rank` (src-local), remapped to local numbering with
    halo ids appended after locals. Parity: ``commInfo.py:64-91``.
    Returns [E_r, 2].
    """
    src, dst = edge_index
    mine = partitioning[src] == rank
    num_local = len(local_vertices)
    g2l = np.full(len(partitioning), -1, dtype=np.int64)
    g2l[local_vertices] = np.arange(num_local)
    g2l[halo_vertices] = np.arange(num_local, num_local + len(halo_vertices))
    return np.stack([g2l[src[mine]], g2l[dst[mine]]], axis=1)


def compute_boundary_vertices(
    edge_index: np.ndarray,
    src_partitioning: np.ndarray,
    local_vertices: np.ndarray,
    rank: int,
    world_size: int,
    dst_partitioning: Optional[np.ndarray] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Deduped (src, dst_rank) send list sorted by target rank then vertex id,
    remapped to local indices, with CSR offsets. Parity: ``commInfo.py:94-145``.
    """
    if dst_partitioning is None:
        dst_partitioning = src_partitioning
    src, dst = edge_index
    cross = (src_partitioning[src] == rank) & (dst_partitioning[dst] != rank)
    pairs = np.stack([dst_partitioning[dst[cross]], src[cross]], axis=1)
    pairs = np.unique(pairs, axis=0)  # sorted by (target_rank, global_src)
    target_ranks, src_global = pairs[:, 0], pairs[:, 1]
    g2l = np.full(len(src_partitioning), -1, dtype=np.int64)
    g2l[local_vertices] = np.arange(len(local_vertices))
    send_local_idx = g2l[src_global]
    send_offset = np.zeros(world_size + 1, dtype=np.int64)
    np.add.at(send_offset, target_ranks + 1, 1)
    send_offset = np.cumsum(send_offset)
    return send_local_idx, send_offset


def compute_comm_map(
    edge_index: np.ndarray,
    src_partitioning: np.ndarray,
    world_size: int,
    dst_partitioning: Optional[np.ndarray] = None,
) -> np.ndarray:
    """``comm_map[p, r]`` = number of (deduped) vertices rank p sends to rank r.

    The reference builds this with a ``dist.all_gather`` of per-rank send
    counts (``commInfo.py:148-155``); on host with the global graph it is a
    pure bincount over unique (src, dst_rank) pairs.
    """
    if dst_partitioning is None:
        dst_partitioning = src_partitioning
    src, dst = edge_index
    sp = src_partitioning[src]
    dp = dst_partitioning[dst]
    cross = sp != dp
    # unique (src_vertex, dst_rank) pairs, attributed to src's owner rank
    v_total = len(src_partitioning)
    enc = dp[cross].astype(np.int64) * v_total + src[cross].astype(np.int64)
    enc = np.unique(enc)
    senders = src_partitioning[enc % v_total]
    targets = enc // v_total
    comm_map = np.zeros((world_size, world_size), dtype=np.int64)
    np.add.at(comm_map, (senders, targets), 1)
    return comm_map


def compute_recv_offsets(comm_map: np.ndarray, rank: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-source-rank recv CSR offsets. Parity: ``commInfo.py:157-164``."""
    recv_counts = comm_map[:, rank]
    recv_offset = np.zeros(comm_map.shape[0] + 1, dtype=np.int64)
    recv_offset[1:] = np.cumsum(recv_counts)
    recv_backward_offset = comm_map[:rank, :].sum(axis=0)
    return recv_offset, recv_backward_offset


def build_comm_pattern(
    edge_index: np.ndarray,
    partitioning: np.ndarray,
    rank: int,
    world_size: int,
) -> CommPattern:
    """Build the per-rank halo-exchange pattern.

    Parity: ``commInfo.py:167-207`` (build_communication_pattern), including
    the §2.6-noted fix: on TPU this is collective-free and device-agnostic
    (the reference hardcodes ``.cuda()`` in compute_comm_map).
    """
    edge_index = np.asarray(edge_index)
    partitioning = np.asarray(partitioning)
    local = compute_local_vertices(partitioning, rank)
    halo = compute_halo_vertices(edge_index, partitioning, rank)
    local_edges = compute_local_edge_list(edge_index, partitioning, local, halo, rank)
    send_idx, send_off = compute_boundary_vertices(
        edge_index, partitioning, local, rank, world_size
    )
    comm_map = compute_comm_map(edge_index, partitioning, world_size)
    recv_off, _ = compute_recv_offsets(comm_map, rank)
    return CommPattern(
        rank=rank,
        world_size=world_size,
        num_local_vertices=len(local),
        num_halo_vertices=len(halo),
        local_edge_list=local_edges,
        send_local_idx=send_idx,
        send_offset=send_off,
        recv_offset=recv_off,
        comm_map=comm_map,
        put_forward_remote_offset=comm_map[:rank, :].sum(axis=0),
        put_backward_remote_offset=comm_map[:, :rank].sum(axis=1),
    )


# ---------------------------------------------------------------------------
# SPMD padded plan: EdgePlan (the TPU-native hot-path plan)
# ---------------------------------------------------------------------------


@pytree_dataclass(static=("s_pad",))
class HaloSpec:
    """Halo-exchange spec for one vertex set, stacked over ranks.

    ``send_idx[r, p, i]`` = local vertex id (on rank r) of the i-th vertex r
    sends to rank p; ``send_mask`` marks real (non-padded) slots. After
    ``all_to_all``, rank r's received block from p occupies halo rows
    ``[p*s_pad, (p+1)*s_pad)``.
    """

    send_idx: Any  # i32[W, W, S]
    send_mask: Any  # f32[W, W, S]
    s_pad: int


@pytree_dataclass(
    static=("e_int_pad", "e_bnd_pad", "interior_mc", "boundary_mc")
)
class OverlapSpec:
    """Interior/boundary edge split for the compute–communication-overlap
    halo lowering (the reference's internal/boundary split,
    ``_NCCLCommPlan.py:14``, lifted into the padded SPMD plan).

    Per rank, the plan's live edges are partitioned into **interior**
    edges (both endpoints local — no halo slot referenced) and
    **boundary** edges (halo-side endpoint remote). Each subset keeps the
    plan's owner-sorted edge order (a subsequence of a monotone sequence
    is monotone), so owner-side aggregation over either subset still
    rides the sorted segment-sum fast path. The split lets the hot path
    issue the boundary collective first, aggregate interior edges while
    it is in flight, and merge boundary contributions last
    (``comm.collectives.halo_exchange_overlap`` / ``scatter_sum_overlap``).

    Index conventions (per rank shard):

    - ``int_src``/``int_dst``: as ``EdgePlan.src_index``/``dst_index``
      restricted to interior edges; halo-side entries are plain local row
      ids (< ``n_halo_pad``). Padded slots carry the owner-side fill
      ``n_owner_pad`` (monotone tail) / halo-side fill ``n_halo_pad``
      (out of range -> zero rows on take).
    - ``bnd_src``/``bnd_dst``: boundary edges; the halo-side entry is
      REBASED into the halo buffer, i.e. ``slot - n_halo_pad`` in
      ``[0, W*s_pad)`` — it indexes the ``[W*S, F]`` exchange output
      directly, no ``[local ; halo]`` concat needed. Padded halo-side
      slots carry ``W*s_pad`` (out of range).
    - ``int_epos``/``bnd_epos``: position of each subset edge within the
      plan's ``[0, e_pad)`` edge axis (fill ``e_pad``), for subsetting
      per-edge data (edge weights, plan-layout messages) by take.
    """

    int_src: Any  # i32[W, Ei]
    int_dst: Any  # i32[W, Ei]
    int_mask: Any  # f32[W, Ei]
    int_epos: Any  # i32[W, Ei]
    bnd_src: Any  # i32[W, Eb]
    bnd_dst: Any  # i32[W, Eb]
    bnd_mask: Any  # f32[W, Eb]
    bnd_epos: Any  # i32[W, Eb]
    num_interior: Any  # i32[W]
    num_boundary: Any  # i32[W]
    e_int_pad: int
    e_bnd_pad: int
    # Pallas max-chunks hints for owner-side sorted segment-sums over each
    # subset (same contract as EdgePlan.scatter_mc, computed for the same
    # recorded block sizes)
    interior_mc: int = 1
    boundary_mc: int = 1

    def side(self, which: str, side: str):
        """The ``side`` ('src'/'dst') index array of subset ``which``
        ('interior'/'boundary')."""
        if which == "interior":
            return self.int_src if side == "src" else self.int_dst
        return self.bnd_src if side == "src" else self.bnd_dst


@pytree_dataclass(
    static=(
        "world_size",
        "n_src_pad",
        "n_dst_pad",
        "e_pad",
        "halo_side",
        "homogeneous",
        "owner_sorted",
        "scatter_mc",
        "scatter_block_e",
        "scatter_block_n",
        "halo_deltas",
        "halo_sort_mc",
        "gather_mv",
        "halo_pair_rows",
        "wire_format",
    )
)
class EdgePlan:
    """Padded, static-shape plan for one edge set (relation), stacked over ranks.

    Subsumes the reference's ``NCCLGraphCommPlan``
    (``nccl/_NCCLCommPlan.py:10-58``) and the hetero
    ``NCCLEdgeConditionedGraphCommPlan`` (``:103-137``): a bipartite relation
    is just ``src`` and ``dst`` vertex sets with different partitions.

    Index spaces (per rank shard):
      - ``src_index``: [E] into ``[0, n_src_pad + W*s_pad)`` if
        ``halo_side=='src'`` else ``[0, n_src_pad)``.
      - ``dst_index``: [E] into ``[0, n_dst_pad + W*s_pad)`` if
        ``halo_side=='dst'`` else ``[0, n_dst_pad)``.
    ``edge_mask`` is the PADDING mask and nothing else: 1 on the slots real
    edges were written to, 0 on the padded tail, whose halo-side index is 0
    and whose owner-side index is the out-of-range ``n_owner_pad``
    (:func:`validate_plan` holds every plan to it).
    """

    # leaves (leading axis = world_size, shard over 'graph')
    src_index: Any  # i32[W, E]
    dst_index: Any  # i32[W, E]
    edge_mask: Any  # f32[W, E]
    num_local_src: Any  # i32[W]
    num_local_dst: Any  # i32[W]
    num_edges: Any  # i32[W]
    halo: HaloSpec
    # static
    world_size: int
    n_src_pad: int
    n_dst_pad: int
    e_pad: int
    halo_side: str  # 'src' or 'dst'
    homogeneous: bool
    # True when each rank's edges are sorted by the owner-side vertex index:
    # aggregation segment-ids are then monotone, enabling
    # indices_are_sorted segment reductions and sorted-CSR Pallas kernels
    # (the analogue of the sorted/deduped order the reference's plan build
    # establishes for its alltoallv path, _NCCLCommPlan.py:221-226).
    # Padded edge slots carry the out-of-range owner-side id n_pad (monotone
    # tail; dropped by scatter, clamped-and-masked by gather).
    owner_sorted: bool = True
    # Pallas scheduling hint: max edge-chunks any (scatter_block_n) vertex
    # block spans at chunk size scatter_block_e, maxed over shards (see
    # ops.pallas_segment). The block sizes the hint was computed FOR are
    # recorded alongside so kernel invocation and hint cannot desynchronize
    # (plans are pickled into the on-disk cache; a default drift would
    # otherwise silently under-visit chunks).
    scatter_mc: int = 1
    scatter_block_e: int = 512
    scatter_block_n: int = 256
    # Static tuple of rank-deltas ((peer - rank) mod W) with nonzero halo
    # traffic anywhere in the mesh. When sparse (locality partitions), the
    # halo exchange can run as len(halo_deltas) ppermute rounds instead of a
    # padded all_to_all — SURVEY §7 "ppermute rounds only to actual
    # neighbors". () means no cross-rank traffic.
    halo_deltas: tuple = ()
    # Sorted route for the HALO-side index (whose ids are NOT monotone —
    # local rows then halo slots): a static permutation putting them in
    # sorted order, so the halo-side gather's VJP and the halo-side
    # scatter's forward run as gather-by-perm + sorted segment-sum (Pallas
    # MXU kernel) instead of XLA's generic unsorted scatter-add (measured
    # ~2x slower at arxiv scale, ops/local.py). None on plans built with
    # sort_route=False (e.g. billion-edge plans where the extra 2x[W,E]
    # int32 isn't worth host RAM).
    # PADDING CONVENTION of the route (halo_sort_route; the index arrays
    # themselves keep 0 for a padded edge): a masked edge sorts after every
    # real edge and its sorted id is the sentinel halo_sort_sentinel(), the
    # first id past the kernel's last vertex block, so no block's chunk
    # range holds it and halo_sort_mc is the widest block of REAL edges.
    # The rows it no longer reaches are exact zeros where gather and
    # scatter multiply by edge_mask before the route, and are not looked
    # at where they do not: the fused GCN layer
    # (comm.collectives.take_scatter_bias_relu) takes its per-edge rows
    # UNMASKED and relies on the ids alone. A masked slot is a padded one,
    # its owner-side id is n_owner_pad and its sorted id this sentinel, so
    # every aggregation by either id drops it (segment reductions drop an
    # out-of-range id, the kernels' one-hot finds no output row for it),
    # in the forward, its backward and the transposed route.
    halo_sort_perm: Any = None  # i32[W, E] or None
    halo_sorted_ids: Any = None  # i32[W, E] or None
    # the OWNER-side index in the same order (owner_index[halo_sort_perm];
    # a padded edge keeps its out-of-range n_owner_pad): with it the route
    # is a second CSR of the graph, and the fused GCN layer's gradient to
    # its halo-side table is an aggregation over it from owner-side VERTEX
    # tables (comm.collectives.take_scatter_bias_relu), not a permutation
    # of an [E, F] edge tensor
    halo_sorted_owner_ids: Any = None  # i32[W, E] or None
    halo_sort_mc: int = 1  # static; max_chunks hint for the sorted route
    # Pallas sorted-row-gather hint: max vertex blocks any scatter_block_e
    # edge chunk spans (ops.pallas_segment.sorted_row_gather). 0 on plans
    # predating the kernel (stale caches rebuild via PLAN_FORMAT_VERSION).
    gather_mv: int = 0
    # Interior/boundary edge split for the compute–communication-overlap
    # lowering (an :class:`OverlapSpec`), or None on plans built without
    # it. Built on request (build_edge_plan(overlap=True)) or when the
    # resolved halo lowering asks for it (env pin / adopted tuning record
    # — see resolve_halo_impl); costs ~2x the plan's per-edge index bytes.
    overlap: Any = None
    # Static [W][W] traffic matrix: deduped live halo rows per
    # (sender, needer) pair — halo_counts as plain nested int tuples, so
    # it survives plan pickling/sharding and rides the jit cache key.
    # Feeds the row-weighted pick_halo_impl heuristic. () on plans
    # without the matrix (stale caches rebuild via PLAN_FORMAT_VERSION).
    halo_pair_rows: tuple = ()
    # Wire format name (dgraph_tpu.wire.spec.WIRE_FORMATS) attached
    # deterministically at plan build — the build-time resolution of the
    # adoption ladder, so a cache round-trip keeps an adopted codec.
    # Runtime resolution (wire.spec.resolve_wire_format) still lets an
    # env pin or a freshly adopted record override it. "fp32" (the
    # identity) on plans predating the codec layer (stale caches rebuild
    # via PLAN_FORMAT_VERSION).
    wire_format: str = "fp32"

    def ids_sorted(self, side: str) -> bool:
        """True iff this side's per-edge index is monotone: the OWNER side
        of an owner-sorted plan. The halo side mixes local rows with halo
        slots and is never monotone — asserting sortedness there makes
        XLA's monotone-scatter path silently corrupt reductions, so every
        ``indices_are_sorted`` hint must come from here, not from a
        re-derived ``owner_sorted and ...`` expression at the call site."""
        return self.owner_sorted and side != self.halo_side


def dtype_nbytes(dtype) -> int:
    """Itemsize for numpy dtypes, jax dtypes, and the bf16 family names
    numpy doesn't know. Lives HERE (the base layer) so both this module's
    byte accounting and ``obs.footprint``'s (which re-exports it as
    ``dtype_bytes``) share one table without a downward import."""
    name = getattr(dtype, "__name__", None) or str(dtype)
    if name in ("bfloat16", "bf16"):
        return 2
    if name in ("float8_e4m3fn", "fp8", "f8E4M3FN"):
        return 1
    return int(np.dtype(name).itemsize)


def plan_memory_usage(
    plan: EdgePlan, feature_dim: int, dtype_bytes: int = 4, *, dtype=None
) -> dict:
    """Byte accounting of a plan and its runtime buffers — parity with
    ``NCCLGraphCommPlan.memory_usage`` (``_NCCLCommPlan.py:68-100``), printed
    by the reference before training (``Trainer.py:113-123``).

    ``dtype`` (a numpy/jax dtype or its name, e.g. ``"bfloat16"``), when
    given, overrides ``dtype_bytes`` — the runtime buffers scale with the
    ACTIVATION dtype, and the old fixed-4-bytes default silently doubled
    every bf16 accounting. ``obs.footprint`` passes the activation dtype
    through here.

    Returns per-shard byte counts (every shard is identical in the padded
    design, unlike the reference's per-rank variable sizes).
    """
    if dtype is not None:
        dtype_bytes = dtype_nbytes(dtype)
    W, S = plan.world_size, plan.halo.s_pad
    idx_bytes = plan.e_pad * 4 * 2 + plan.e_pad * 4  # src/dst idx + mask
    if plan.halo_sort_perm is not None:
        # halo_sort_perm + halo_sorted_ids + halo_sorted_owner_ids
        idx_bytes += plan.e_pad * 4 * 3
    ov = getattr(plan, "overlap", None)
    if ov is not None:
        # interior/boundary split: src+dst+epos (i32) + mask (f32) per slot
        idx_bytes += (ov.e_int_pad + ov.e_bnd_pad) * 4 * 4
    send_bytes = W * S * (4 + 4)  # send_idx + send_mask
    halo_buffer = W * S * feature_dim * dtype_bytes
    send_buffer = W * S * feature_dim * dtype_bytes
    edge_buffer = plan.e_pad * feature_dim * dtype_bytes
    return {
        "plan_index_bytes": idx_bytes + send_bytes,
        "halo_buffer_bytes": halo_buffer,
        "send_buffer_bytes": send_buffer,
        "edge_buffer_bytes": edge_buffer,
        "total_runtime_bytes": halo_buffer + send_buffer + edge_buffer,
        "dtype_bytes": dtype_bytes,
    }


def interior_boundary_edge_counts(plan: EdgePlan) -> dict:
    """Per-shard interior (both endpoints local) vs boundary (halo-side
    endpoint remote) live-edge counts, derived from the plan's index
    arrays — works on any plan, with or without an :class:`OverlapSpec`.
    The fractions are what ``bench.py`` and ``obs.footprint`` report next
    to the halo lowering: they bound how much compute the overlap
    lowering has available to hide the boundary collective behind."""
    halo_idx = np.asarray(
        plan.src_index if plan.halo_side == "src" else plan.dst_index
    )
    n_halo_pad = plan.n_src_pad if plan.halo_side == "src" else plan.n_dst_pad
    live = np.asarray(plan.edge_mask) > 0
    boundary = ((halo_idx >= n_halo_pad) & live).sum(axis=1).astype(np.int64)
    total = live.sum(axis=1).astype(np.int64)
    interior = total - boundary
    tot = int(total.sum())
    return {
        "interior_per_shard": [int(v) for v in interior],
        "boundary_per_shard": [int(v) for v in boundary],
        "interior_total": int(interior.sum()),
        "boundary_total": int(boundary.sum()),
        "interior_frac": float(interior.sum() / tot) if tot else 1.0,
        "boundary_frac": float(boundary.sum() / tot) if tot else 0.0,
    }


def pick_halo_impl(
    world_size: int, halo_deltas: tuple, pair_rows: tuple = (),
) -> str:
    """The heuristic halo-exchange lowering from the plan's active peer set.

    Cost model: one padded ``all_to_all`` moves ``(W-1) * s_pad`` remote rows
    per shard no matter how many peer pairs are actually live; ``ppermute``
    neighbor rounds move ``len(deltas) * s_pad`` rows but pay one collective
    launch per round. Rounds win when the peer set is sparse (locality
    partitions on mesh-like graphs — SURVEY §7 "ppermute rounds only to
    actual neighbors"); the crossover is ~W/2 live deltas.
    Returns 'none' | 'ppermute' | 'all_to_all'.

    ``pair_rows`` (the plan's static ``[W][W]`` live-row traffic matrix,
    ``plan.halo_pair_rows``) weights the delta count by actual traffic:
    the EFFECTIVE round count is how many max-pair-sized rounds the total
    traffic fills, ``ceil(total_rows / max_pair_rows)``, capped by the
    ring count. A single giant delta among near-empty ones used to read
    as "many deltas -> all_to_all" even though one ring carries ~all the
    bytes; weighted, it reads as ~1 effective round -> ppermute. A
    uniform matrix (and the no-matrix legacy case) reduces exactly to the
    old ``len(halo_deltas)`` rule.

    This is the FALLBACK tier only: runtime call sites resolve through
    :func:`resolve_halo_impl`, which lets an env pin or an adopted tuning
    record override the heuristic.
    """
    if not halo_deltas:
        return "none"
    n_eff = len(halo_deltas)
    if pair_rows:
        live = [int(v) for row in pair_rows for v in row if int(v) > 0]
        if live:
            n_eff = min(n_eff, -(-sum(live) // max(live)))  # ceil div
    return "ppermute" if n_eff <= max(1, world_size // 2) else "all_to_all"


def plan_wire_format(world_size: int, halo_deltas: tuple) -> str:
    """The ONE attach rule for a plan's wire format
    (:mod:`dgraph_tpu.wire`): both plan-build paths
    (:func:`_finalize_plan`) and the shard assembler
    (:func:`assemble_plan`, for pre-codec manifests) stamp through here,
    so a monolithic build and a cache round-trip of the same graph under
    the same adoption state carry the identical format. This is the
    build-time pass of the adoption ladder WITHOUT a plan tier (the plan
    is being built): env pin > adopted tuning record > the fp32
    identity. Runtime consumers re-resolve through
    :func:`dgraph_tpu.wire.spec.resolve_wire_format` with this value as
    the plan tier, so a later env pin or record adoption still wins.
    """
    if not halo_deltas:
        return "fp32"
    from dgraph_tpu.wire.spec import resolve_wire_format

    name, _source = resolve_wire_format(
        int(world_size), tuple(halo_deltas), plan_format="fp32"
    )
    return name


# Every lowering of the halo exchange, named ONCE. The resolver's legal set,
# the audit tiers' columns, the tuner's candidates and the record validator
# all import or derive from this tuple; ``'none'`` (a plan with no
# cross-rank traffic) is a verdict of the resolver, not a lowering.
HALO_IMPLS = ("all_to_all", "ppermute", "overlap")


def resolve_halo_impl(
    world_size: int, halo_deltas: tuple, *, overlap_available: bool = False,
    pair_rows: tuple = (),
) -> tuple[str, str]:
    """The halo lowering the run will actually execute, plus who decided.

    Returns ``(impl, source)`` with impl ``'none'`` or one of
    :data:`HALO_IMPLS`, and source one of:

    - ``'env'``       — ``DGRAPH_TPU_HALO_IMPL`` (or ``config.set_flags``)
      pins the lowering; the operator's word is final.
    - ``'record'``    — an adopted :class:`~dgraph_tpu.tune.record.
      TuningRecord` chose it (``config.tuned_halo_impl``).
    - ``'heuristic'`` — :func:`pick_halo_impl`'s cost model (or, when the
      plan carries an interior/boundary split, the overlap lowering: its
      exposed comm time is never worse than the serial rounds it is built
      from).
    - ``'plan'``      — the plan has no cross-rank traffic at all; there is
      nothing to choose (impl is ``'none'``).

    ``overlap_available`` says whether the plan carries an
    :class:`OverlapSpec` (``plan.overlap is not None``). An ``'overlap'``
    pin (env or record) on a plan WITHOUT the split cannot lower — that
    tier is skipped (logged once per process) and the NEXT tier decides
    (an env-pin miss still honors an adopted record, then the heuristic),
    never a silent wrong answer. A pin that names no lowering at all
    (anything outside :data:`HALO_IMPLS` and ``'auto'``) raises
    ``ValueError``: a typo like ``alltoall`` silently training on the
    heuristic's choice would misattribute every measurement.

    ``pair_rows`` (``plan.halo_pair_rows``) is forwarded to
    :func:`pick_halo_impl` so the heuristic tier weighs actual per-pair
    traffic, not just the ring count.

    Every consumer of the decision (``comm.collectives``'s runtime dispatch,
    ``obs.footprint``'s byte accounting, :func:`plan_efficiency`'s report)
    resolves through here, so what runs, what is accounted, and what is
    reported can never be three different lowerings.
    """
    from dgraph_tpu import config as _cfg

    if not halo_deltas:
        return "none", "plan"

    legal = tuple(
        k for k in HALO_IMPLS if k != "overlap" or overlap_available
    )
    for impl, source, flag, unset in (
        (_cfg.halo_impl, "env", "DGRAPH_TPU_HALO_IMPL", "auto"),
        (_cfg.tuned_halo_impl, "record", "config.tuned_halo_impl", None),
    ):
        if impl == unset:
            continue
        if impl not in HALO_IMPLS:
            raise ValueError(
                f"{flag}={impl!r} names no halo lowering; expected "
                f"{unset!r} or one of {HALO_IMPLS}"
            )
        if impl in legal:
            return impl, source
        if impl == "overlap":  # pinned but the plan carries no split
            _warn_overlap_unavailable(source)
    if overlap_available:
        return "overlap", "heuristic"
    return pick_halo_impl(world_size, halo_deltas, pair_rows), "heuristic"


def resolve_overlap_intent() -> bool:
    """Whether a plan built RIGHT NOW with ``overlap=None`` (auto) would
    attach the interior/boundary split: the env pin or the adopted tuning
    record asks for the overlap lowering. The ONE copy of this rule —
    ``build_edge_plan``'s auto default and the plan cache's fingerprint
    (``train.checkpoint.cached_edge_plan``) both resolve through here, so
    what gets built and what the cache key claims was built can never
    diverge."""
    from dgraph_tpu import config as _cfg

    return "overlap" in (_cfg.halo_impl, _cfg.tuned_halo_impl)


_overlap_warned: set = set()


def _warn_overlap_unavailable(source: str) -> None:
    if source not in _overlap_warned:
        _overlap_warned.add(source)
        _logger.warning(
            "halo_impl='overlap' requested by %s but the plan carries no "
            "interior/boundary split (built without overlap=True); the "
            "next resolution tier decides the lowering instead", source,
        )


def plan_efficiency(plan: EdgePlan, layout: EdgePlanLayout) -> dict:
    """Real/padded fill ratios — the padded design's skew telemetry.

    Every per-peer segment pads to the global max, so one hub vertex on a
    power-law graph can inflate ``s_pad`` for all W² peer pairs; these ratios
    are the number that decides whether that happened (and which halo
    lowering to use). The reference reports plan bytes before training
    (``Trainer.py:113-123``); this is the utilization companion.
    """
    W, S, E = plan.world_size, plan.halo.s_pad, plan.e_pad
    real_edges = int(np.asarray(plan.num_edges).sum())
    real_halo = int(layout.halo_counts.sum())
    active_pairs = int((layout.halo_counts > 0).sum())
    n_deltas = len(plan.halo_deltas)
    src_total = int(layout.src_counts.sum())
    dst_total = int(layout.dst_counts.sum())
    impl, impl_source = resolve_halo_impl(
        W, plan.halo_deltas, overlap_available=plan.overlap is not None,
        pair_rows=plan.halo_pair_rows,
    )
    return {
        "edge_fill": real_edges / max(W * E, 1),
        "src_vertex_fill": src_total / max(W * plan.n_src_pad, 1),
        "dst_vertex_fill": dst_total / max(W * plan.n_dst_pad, 1),
        # fill of the peer segments that actually carry traffic
        "halo_fill_active": real_halo / max(active_pairs * S, 1),
        # fraction of all_to_all wire bytes that are real rows (a2a moves all
        # W*(W-1) remote blocks at s_pad each, live or not)
        "halo_wire_fill_all_to_all": real_halo / max(W * (W - 1) * S, 1),
        # same for ppermute rounds (only live deltas move)
        "halo_wire_fill_ppermute": real_halo / max(n_deltas * W * S, 1) if n_deltas else 1.0,
        "active_peer_pairs": active_pairs,
        "num_halo_deltas": n_deltas,
        "halo_impl": impl,
        # who decided the lowering: 'env' pin, adopted tuning 'record',
        # cost-model 'heuristic', or 'plan' (no traffic to lower)
        "halo_impl_source": impl_source,
    }


def _padding_mask_errors(src, dst, mask, halo_side: str, n_src_pad: int,
                         n_dst_pad: int) -> list:
    """What is wrong with ``edge_mask`` as the PADDING mask of ``[R, E]``
    index rows (``mask`` boolean): a masked slot must carry the out-of-range
    owner-side id ``n_owner_pad`` and the halo-side id 0, as every builder
    fills it. The fused GCN layer
    (``comm.collectives.take_scatter_bias_relu``) takes its per-edge rows
    unmasked and drops a padded edge by these ids alone, so a plan that
    masks a REAL edge (its owner id in range) would have that edge
    aggregated there and dropped by every other model. Held where plans
    enter the program (:func:`_finalize_plan`, :func:`assemble_plan`) and
    by :func:`validate_plan`; a row at a time, so the transient is one
    shard's."""
    owner_idx, n_owner_pad, halo_idx = (
        (dst, n_dst_pad, src) if halo_side == "src" else (src, n_src_pad, dst))
    errors = []
    for r in range(len(mask)):
        pad = ~mask[r]
        if (owner_idx[r][pad] != n_owner_pad).any():
            errors.append(
                f"row {r}: a masked edge's owner-side index is not "
                f"n_owner_pad ({n_owner_pad}): edge_mask must be the "
                f"padding mask")
        if (halo_idx[r][pad] != 0).any():
            errors.append(
                f"row {r}: a masked edge's halo-side index is not 0")
    return errors


def _require_padding_mask(src, dst, edge_mask, halo_side, n_src_pad,
                          n_dst_pad) -> None:
    """Raise where a plan enters the program if its ``edge_mask`` is not
    the padding mask (:func:`_padding_mask_errors`)."""
    errors = _padding_mask_errors(
        np.asarray(src), np.asarray(dst), np.asarray(edge_mask) > 0,
        halo_side, n_src_pad, n_dst_pad)
    if errors:
        raise ValueError("invalid EdgePlan: " + "; ".join(errors))


def validate_plan(plan: EdgePlan) -> None:
    """Host-side structural validation (the index-bounds asserts the
    reference scatters through its kernels, ``RankLocalOps.py:183-184``;
    here checked once at build/load time since plans are static).
    Raises ValueError on any violation."""
    import numpy as np_

    W, S = plan.world_size, plan.halo.s_pad
    src_hi = plan.n_src_pad + (W * S if plan.halo_side == "src" else 0)
    dst_hi = plan.n_dst_pad + (W * S if plan.halo_side == "dst" else 0)
    src = np_.asarray(plan.src_index)
    dst = np_.asarray(plan.dst_index)
    mask = np_.asarray(plan.edge_mask) > 0
    errors = []
    if src[mask].size and (src[mask].min() < 0 or src[mask].max() >= src_hi):
        errors.append(f"src_index out of [0,{src_hi})")
    if dst[mask].size and (dst[mask].min() < 0 or dst[mask].max() >= dst_hi):
        errors.append(f"dst_index out of [0,{dst_hi})")
    send_idx = np_.asarray(plan.halo.send_idx)
    send_mask = np_.asarray(plan.halo.send_mask) > 0
    n_halo_owner = plan.n_src_pad if plan.halo_side == "src" else plan.n_dst_pad
    if send_idx[send_mask].size and (
        send_idx[send_mask].min() < 0 or send_idx[send_mask].max() >= n_halo_owner
    ):
        errors.append(f"halo send_idx out of [0,{n_halo_owner})")
    for r in range(W):
        if send_mask[r, r].any():
            errors.append(f"rank {r} sends to itself")
    counts = np_.asarray(plan.num_edges)
    if (counts > plan.e_pad).any():
        errors.append("num_edges exceeds e_pad")
    errors += _padding_mask_errors(
        src, dst, mask, plan.halo_side, plan.n_src_pad, plan.n_dst_pad)
    if plan.halo_sort_perm is not None:
        # sorted route: perm must be a permutation of [0, e_pad) per shard
        # and the recorded sorted ids monotone and equal to the key
        # halo_sort_route sorts by, permuted: halo_idx for a real edge, the
        # sentinel past the last vertex block for a masked one (monotone,
        # so the masked edges are the tail).
        # Vectorized WITHIN each rank (no O(E log E) sort — the old check's
        # dominant cost at billion-edge scale, VERDICT r2 #8) but looped
        # over ranks: all-at-once [W, e_pad] temporaries would multiply
        # transient host RAM W-fold on every cache load of a huge plan.
        perm = np_.asarray(plan.halo_sort_perm)
        sids = np_.asarray(plan.halo_sorted_ids)
        halo_idx, owner_idx = (
            (src, dst) if plan.halo_side == "src" else (dst, src))
        oids = (None if plan.halo_sorted_owner_ids is None
                else np_.asarray(plan.halo_sorted_owner_ids))
        if oids is None:
            errors.append(
                "halo_sorted_owner_ids missing on a plan with a sorted route")
        sentinel = halo_sort_sentinel(
            src_hi if plan.halo_side == "src" else dst_hi,
            plan.scatter_block_n)
        seen = np_.empty(plan.e_pad, bool)
        for r in range(W):
            pr = perm[r]
            in_range = (pr >= 0) & (pr < plan.e_pad)
            seen[:] = False
            seen[pr[in_range]] = True
            if not (in_range.all() and seen.all()):
                errors.append(f"halo_sort_perm[{r}] is not a permutation")
                break
            if (np_.diff(sids[r]) < 0).any():
                errors.append(f"halo_sorted_ids[{r}] not monotone")
                break
            real = mask[r][pr]
            want = np_.where(real, halo_idx[r][pr], sentinel)
            bad = np_.flatnonzero(want != sids[r])
            if bad.size:
                i = int(bad[0])
                errors.append(
                    f"halo_sorted_ids[{r}][{i}] = {int(sids[r][i])} != "
                    f"{int(want[i])}: a real edge carries halo_index[perm], "
                    f"a masked edge the sentinel {sentinel} (this one is "
                    f"{'real' if real[i] else 'masked'})")
                break
            if oids is not None and not np_.array_equal(
                    oids[r], owner_idx[r][pr]):
                errors.append(
                    f"halo_sorted_owner_ids[{r}] != owner_index[halo_sort_perm]")
                break
    ov = plan.overlap
    if ov is not None:
        # interior/boundary split invariants: the two subsets must exactly
        # tile the live edge set, interior halo-side ids must be local,
        # boundary halo-side slots must land inside the halo buffer, and
        # owner-side ids must stay monotone per subset (the property the
        # overlap lowering's chunked sorted segment-sums rely on)
        n_halo_pad = plan.n_src_pad if plan.halo_side == "src" else plan.n_dst_pad
        n_owner_pad = plan.n_dst_pad if plan.halo_side == "src" else plan.n_src_pad
        im = np_.asarray(ov.int_mask) > 0
        bm = np_.asarray(ov.bnd_mask) > 0
        n_int = np_.asarray(ov.num_interior)
        n_bnd = np_.asarray(ov.num_boundary)
        if not np_.array_equal(im.sum(1), n_int):
            errors.append("overlap int_mask count != num_interior")
        if not np_.array_equal(bm.sum(1), n_bnd):
            errors.append("overlap bnd_mask count != num_boundary")
        if not np_.array_equal(n_int + n_bnd, np_.asarray(plan.num_edges)):
            errors.append("overlap split does not tile the live edge set")
        int_halo = np_.asarray(ov.side("interior", plan.halo_side))
        bnd_halo = np_.asarray(ov.side("boundary", plan.halo_side))
        if int_halo[im].size and int_halo[im].max(initial=0) >= n_halo_pad:
            errors.append("overlap interior halo-side id not local")
        if bnd_halo[bm].size and (
            bnd_halo[bm].min(initial=0) < 0
            or bnd_halo[bm].max(initial=0) >= W * S
        ):
            errors.append(f"overlap boundary slot out of [0,{W * S})")
        owner_side = "dst" if plan.halo_side == "src" else "src"
        for which, epos in (
            ("interior", np_.asarray(ov.int_epos)),
            ("boundary", np_.asarray(ov.bnd_epos)),
        ):
            own = np_.asarray(ov.side(which, owner_side))
            if plan.owner_sorted and (np_.diff(own, axis=1) < 0).any():
                errors.append(f"overlap {which} owner ids not monotone")
            if own.max(initial=0) > n_owner_pad:
                errors.append(f"overlap {which} owner id > {n_owner_pad}")
            msk = im if which == "interior" else bm
            if epos[msk].size and epos[msk].max(initial=0) >= plan.e_pad:
                errors.append(f"overlap {which} epos out of [0,{plan.e_pad})")
            # epos strictly increasing within each rank's live region
            # (subsets preserve the plan's edge order)
            live_pairs = msk[:, 1:] & msk[:, :-1]
            if live_pairs.size and (np_.diff(epos, axis=1) <= 0)[live_pairs].any():
                errors.append(f"overlap {which} epos not strictly increasing")
    if errors:
        raise ValueError("invalid EdgePlan: " + "; ".join(errors))
    impl, impl_source = resolve_halo_impl(
        W, plan.halo_deltas, overlap_available=plan.overlap is not None,
        pair_rows=plan.halo_pair_rows,
    )
    _logger.info(
        "validate_plan OK: W=%d e_pad=%d s_pad=%d; halo lowering=%s "
        "(decided by %s)", W, plan.e_pad, S, impl, impl_source,
    )


@dataclasses.dataclass
class EdgePlanLayout:
    """Host-side companion of :class:`EdgePlan` (not a pytree; build metadata).

    ``edge_rank``/``edge_slot``: for global edge i (in the caller's original
    edge order), the owning rank and its padded slot — use
    :func:`shard_edge_data` to lay per-edge features/weights into the
    ``[W, E_pad]`` plan layout (the analogue of the reference's edge
    renumber+sort, ``DGraph/data/preprocess.py:43-92``).
    """

    edge_rank: np.ndarray  # [E_total]
    edge_slot: np.ndarray  # [E_total]
    halo_counts: np.ndarray  # [W, W] (sender, needer) deduped halo vertex counts
    src_counts: np.ndarray  # [W]
    dst_counts: np.ndarray  # [W]


# v5e-tuned Pallas scatter tiles (ops.pallas_segment): block_e=1024 measured
# 29.0 ms vs 512's 34.1 ms for [2.33M, 256] f32 sorted segment-sum
# (logs/kernels_r2.jsonl). New plans carry these; old pickled plans keep the
# blocks they were built with (EdgePlan field defaults + PLAN_FORMAT_VERSION).
# Env-overridable so an on-chip tile sweep (kernel_benchmarks --sweep) can
# be applied to a fresh plan build without a code edit.
import os as _os

SCATTER_BLOCK_E = int(_os.environ.get("DGRAPH_TPU_SCATTER_BLOCK_E", "1024"))
SCATTER_BLOCK_N = int(_os.environ.get("DGRAPH_TPU_SCATTER_BLOCK_N", "256"))
del _os

# Edge count above which build_edge_plan dispatches to the native streaming
# core by default (the numpy path's lexsort/unique int64 temporaries are
# ~10x E bytes; at papers100M's 1.6e9 edges that exceeds host RAM).
NATIVE_PLAN_MIN_EDGES = 1 << 24


def _pad_to(x: int, multiple: int) -> int:
    if multiple <= 1:
        return max(x, 1)
    return max(-(-x // multiple) * multiple, multiple)


def _reject_incompatible_knobs(
    pad_multiple: int, e_pad: Optional[int], s_pad: Optional[int],
    overlap: Optional[bool] = None, sort_edges: bool = True,
) -> None:
    """Fail fast on tunable combinations that cannot lower cleanly, naming
    the conflicting knobs — the autotuner (and any caller sweeping plan
    geometry) must get a structured rejection here, not a shape error deep
    in ``_finalize_plan`` or a silent per-step re-pad inside the Pallas
    kernels. Raises ValueError."""
    if overlap and not sort_edges:
        raise ValueError(
            "overlap=True conflicts with sort_edges=False: the "
            "interior/boundary split's chunked interior aggregation relies "
            "on owner-sorted edge order (monotone segment ids per subset); "
            "drop one of the two knobs"
        )
    if pad_multiple < 1:
        raise ValueError(f"pad_multiple={pad_multiple} must be >= 1")
    if e_pad is not None:
        if e_pad < 1:
            raise ValueError(f"e_pad={e_pad} must be >= 1")
        if pad_multiple > 1 and e_pad % pad_multiple:
            raise ValueError(
                f"e_pad={e_pad} conflicts with pad_multiple={pad_multiple}: "
                f"an explicit e_pad must be a multiple of pad_multiple "
                f"(lane tiling); pick e_pad={_pad_to(e_pad, pad_multiple)} "
                f"or drop one of the two knobs"
            )
        if e_pad >= SCATTER_BLOCK_E and e_pad % SCATTER_BLOCK_E:
            # kernel-scale plans must align to the scatter block: a
            # non-multiple makes every pallas_call re-pad its [E, F]
            # operand — a full HBM copy per kernel per step (the r4c
            # finding _edge_pad_align exists to prevent). Sub-block plans
            # (e_pad < SCATTER_BLOCK_E) are exempt: the in-op pad there is
            # negligible and hand-analyzed test plans pin exact tiny shapes.
            raise ValueError(
                f"e_pad={e_pad} conflicts with scatter_block_e="
                f"{SCATTER_BLOCK_E}: a kernel-scale e_pad must be a "
                f"multiple of the Pallas scatter block (or stay below it); "
                f"pick e_pad={_pad_to(e_pad, SCATTER_BLOCK_E)} or set "
                f"DGRAPH_TPU_SCATTER_BLOCK_E to a divisor of e_pad"
            )
    if s_pad is not None:
        if s_pad < 1:
            raise ValueError(f"s_pad={s_pad} must be >= 1")
        if pad_multiple > 1 and s_pad % pad_multiple:
            raise ValueError(
                f"s_pad={s_pad} conflicts with pad_multiple={pad_multiple}: "
                f"an explicit s_pad must be a multiple of pad_multiple; "
                f"pick s_pad={_pad_to(s_pad, pad_multiple)}"
            )


def _edge_pad_align(e_max: int, pad_multiple: int) -> int:
    """Alignment for the per-rank edge padding (SHARED by the numpy and
    native builders — a divergence would give the two paths different
    e_pad for the same graph). Once the plan reaches kernel scale, e_pad
    aligns to the Pallas scatter block too: a non-block_e-multiple e_pad
    makes every kernel invocation re-pad its [E, F] operand — a full HBM
    copy per pallas_call per step (r4c finding; the bench plan's 2332544
    was 896 past a 1024 block). Cost: <= block_e-1 extra masked edge
    slots. Sub-block plans keep the caller's pad_multiple (the in-op pad
    there is negligible, and hand-analyzed test plans pin exact tiny
    shapes)."""
    import math

    if e_max >= SCATTER_BLOCK_E:
        return math.lcm(pad_multiple, SCATTER_BLOCK_E)
    return pad_multiple


def build_edge_plan(
    edge_index: np.ndarray,
    src_partition: np.ndarray,
    dst_partition: Optional[np.ndarray] = None,
    *,
    world_size: int,
    edge_owner: str = "dst",
    n_src_pad: Optional[int] = None,
    n_dst_pad: Optional[int] = None,
    e_pad: Optional[int] = None,
    s_pad: Optional[int] = None,
    pad_multiple: int = 8,
    sort_edges: bool = True,
    use_native: Optional[bool] = None,  # None = auto (E >= NATIVE_PLAN_MIN_EDGES)
    sort_route: Optional[bool] = None,  # None = auto (skip at billion-edge
    # scale: the two extra [W, E] int32 arrays aren't worth host RAM there)
    overlap: Optional[bool] = None,  # None = auto: build the
    # interior/boundary split when the configured halo lowering asks for
    # it (env pin DGRAPH_TPU_HALO_IMPL=overlap or an adopted tuning
    # record's tuned_halo_impl='overlap'); True/False force it
) -> tuple[EdgePlan, EdgePlanLayout]:
    """Build the padded SPMD plan for one edge set.

    Args:
      edge_index: [2, E] global edges in *contiguous-block* numbering
        (per-rank blocks; see :func:`dgraph_tpu.partition.renumber_contiguous`).
      src_partition / dst_partition: [V_src] / [V_dst] owner rank per vertex;
        dst_partition=None means homogeneous (same vertex set both sides).
      edge_owner: 'dst' (TPU-native default: local aggregations) or 'src'
        (reference parity, ``commInfo.py:64-78``).
      pad_multiple: round padded sizes up to this multiple (TPU lane tiling).
      overlap: attach an :class:`OverlapSpec` (interior/boundary edge
        split) so the runtime can lower the halo exchange as overlappable
        ppermute rounds hidden behind interior aggregation.

    Returns (plan, layout).
    """
    from dgraph_tpu.obs import spans

    with spans.stage(
        "setup.plan", num_edges=int(np.shape(edge_index)[1]),
        world_size=world_size,
    ):
        pro = _plan_build_prologue(
            edge_index, src_partition, dst_partition, edge_owner=edge_owner,
            sort_edges=sort_edges, sort_route=sort_route, overlap=overlap,
            pad_multiple=pad_multiple, e_pad=e_pad, s_pad=s_pad,
            world_size=world_size,
        )
        src, dst, E = pro.src, pro.dst, pro.E
        src_partition, dst_partition = pro.src_partition, pro.dst_partition
        homogeneous = pro.homogeneous
        src_counts, dst_counts = pro.src_counts, pro.dst_counts
        src_offsets, dst_offsets = pro.src_offsets, pro.dst_offsets
        sort_route, overlap = pro.sort_route, pro.overlap
        W = world_size
        from dgraph_tpu import native as _native

        if use_native is None:
            use_native = sort_edges and _native.available() and E >= NATIVE_PLAN_MIN_EDGES
        if use_native:
            if not sort_edges:
                raise ValueError("native plan core always owner-sorts (sort_edges=True)")
            return _build_edge_plan_native(
                src, dst, src_partition, dst_partition, src_offsets, dst_offsets,
                src_counts, dst_counts, W, edge_owner, homogeneous,
                n_src_pad, n_dst_pad, e_pad, s_pad, pad_multiple,
                sort_route=sort_route, overlap=overlap,
            )

        prep = _numpy_plan_prep(
            src, dst, src_partition, dst_partition, src_offsets, dst_offsets,
            src_counts, dst_counts, W, edge_owner, sort_edges,
            n_src_pad, n_dst_pad, e_pad, s_pad, pad_multiple,
        )

        # --- scatter into padded [W, E_pad] layout ---
        def to_padded(vals, dtype, fill=0):
            out = np.full((W, prep.e_pad), fill, dtype=dtype)
            out[prep.edge_rank, prep.edge_slot] = vals
            return out

        edge_mask = np.zeros((W, prep.e_pad), dtype=np.float32)
        edge_mask[prep.edge_rank, prep.edge_slot] = 1.0
        # owner-side padding = n_pad: keeps sorted order monotone through the
        # padded tail and is dropped by segment reductions
        if prep.halo_side == "src":
            src_idx_arr = to_padded(prep.halo_side_local_idx.astype(np.int32), np.int32)
            dst_idx_arr = to_padded(
                prep.own_local.astype(np.int32), np.int32, fill=prep.n_owner_pad)
        else:
            src_idx_arr = to_padded(
                prep.own_local.astype(np.int32), np.int32, fill=prep.n_owner_pad)
            dst_idx_arr = to_padded(prep.halo_side_local_idx.astype(np.int32), np.int32)

        return _finalize_plan(
            src_idx_arr=src_idx_arr, dst_idx_arr=dst_idx_arr, edge_mask=edge_mask,
            src_counts=src_counts, dst_counts=dst_counts, e_counts=prep.e_counts,
            send_idx=prep.send_idx, send_mask=prep.send_mask,
            s_pad_val=prep.s_pad, W=W, E=E,
            n_src_pad_val=prep.n_src_pad, n_dst_pad_val=prep.n_dst_pad,
            e_pad_val=prep.e_pad,
            halo_side=prep.halo_side, homogeneous=homogeneous,
            edge_owner=edge_owner, owner_sorted=sort_edges,
            halo_deltas=prep.halo_deltas,
            edge_rank=prep.edge_rank, edge_slot=prep.edge_slot,
            halo_counts=prep.halo_counts,
            tag="", sort_route=sort_route, overlap=overlap,
        )


def _plan_build_prologue(
    edge_index, src_partition, dst_partition, *, edge_owner, sort_edges,
    sort_route, overlap, pad_multiple, e_pad, s_pad, world_size,
):
    """Shared validation + derived inputs for the monolithic AND streaming
    plan builds (ONE copy, so the two entry points cannot drift): shape /
    owner / knob rejection, the resolved overlap intent, per-rank
    counts/offsets, the contiguity check, and the sort_route default."""
    import types

    edge_index = np.asarray(edge_index)
    if edge_index.ndim != 2 or edge_index.shape[0] != 2:
        raise ValueError(f"edge_index must be [2, E], got {edge_index.shape}")
    if overlap is None:
        overlap = resolve_overlap_intent()
    _reject_incompatible_knobs(pad_multiple, e_pad, s_pad, overlap, sort_edges)
    if edge_owner not in ("src", "dst"):
        raise ValueError("edge_owner must be 'src' or 'dst'")
    src_partition = np.asarray(src_partition)
    homogeneous = dst_partition is None
    dst_partition = src_partition if homogeneous else np.asarray(dst_partition)
    W = world_size
    # copy=False: at billion-edge scale a silent astype copy is 26 GB
    src = edge_index[0].astype(np.int64, copy=False)
    dst = edge_index[1].astype(np.int64, copy=False)
    E = len(src)
    src_counts = np.bincount(src_partition, minlength=W).astype(np.int64)
    dst_counts = np.bincount(dst_partition, minlength=W).astype(np.int64)
    src_offsets = np.concatenate([[0], np.cumsum(src_counts)])
    dst_offsets = np.concatenate([[0], np.cumsum(dst_counts)])
    # contiguity check (cheap): partition must be non-decreasing
    if np.any(np.diff(src_partition) < 0) or np.any(np.diff(dst_partition) < 0):
        raise ValueError(
            "partitions must be contiguous per-rank blocks; run "
            "dgraph_tpu.partition.renumber_contiguous first"
        )
    if sort_route is None:
        sort_route = E < NATIVE_PLAN_MIN_EDGES
    return types.SimpleNamespace(
        src=src, dst=dst, E=E,
        src_partition=src_partition, dst_partition=dst_partition,
        homogeneous=homogeneous,
        src_counts=src_counts, dst_counts=dst_counts,
        src_offsets=src_offsets, dst_offsets=dst_offsets,
        sort_route=sort_route, overlap=overlap,
    )


def _numpy_plan_prep(
    src, dst, src_partition, dst_partition, src_offsets, dst_offsets,
    src_counts, dst_counts, W, edge_owner, sort_edges,
    n_src_pad, n_dst_pad, e_pad, s_pad, pad_multiple,
):
    """Host-side skeleton of the numpy plan build: every per-edge / per-peer
    intermediate needed to assemble the padded index arrays, WITHOUT
    materializing any ``[W, E_pad]`` stack.  The monolithic path scatters
    the whole stack from this in one shot; the streaming path
    (:func:`build_edge_plan_sharded`) assembles one rank's rows at a time
    from the same skeleton, so the two builds cannot diverge — the
    resumed/streamed plan is bit-identical to the in-RAM one (pinned by
    ``tests/test_plan_shards.py``)."""
    import types

    E = len(src)
    if edge_owner == "dst":
        owner = dst_partition[dst]
        halo_side = "src"
        halo_vid, halo_part = src, src_partition
    else:
        owner = src_partition[src]
        halo_side = "dst"
        halo_vid, halo_part = dst, dst_partition

    # --- group edges by owner rank; optionally sort by owner-side vertex
    # within each rank so aggregation segment ids are monotone ---
    owner_side_vid = dst if edge_owner == "dst" else src
    if sort_edges:
        order = np.lexsort((owner_side_vid, owner))
    else:
        order = np.argsort(owner, kind="stable")
    e_counts = np.bincount(owner, minlength=W).astype(np.int64)
    _e_max = int(e_counts.max(initial=1))
    E_pad = e_pad if e_pad is not None else _pad_to(
        _e_max, _edge_pad_align(_e_max, pad_multiple))
    if int(e_counts.max(initial=0)) > E_pad:
        raise ValueError(f"e_pad={E_pad} < max per-rank edges {int(e_counts.max())}")
    e_starts = np.concatenate([[0], np.cumsum(e_counts)])
    # slot within owner rank (original relative order preserved)
    slot_sorted = np.arange(E, dtype=np.int64) - e_starts[owner[order]]
    edge_slot = np.empty(E, dtype=np.int64)
    edge_slot[order] = slot_sorted
    edge_rank = owner

    # --- halo sets: unique (needer_rank, halo_vertex) pairs of cross edges ---
    cross = halo_part[halo_vid] != owner
    v_total = len(halo_part)
    from dgraph_tpu import native as _native

    if _native.available() and cross.sum() > (1 << 16):
        enc_u = _native.unique_encoded_pairs(owner[cross], halo_vid[cross], v_total)
    else:
        enc = owner[cross].astype(np.int64) * v_total + halo_vid[cross]
        enc_u = np.unique(enc)  # sorted by (needer, vid); vid-sorted == owner-grouped
    needer = enc_u // v_total
    hvid = enc_u % v_total
    sender = halo_part[hvid]
    # counts per (sender p, needer r)
    halo_counts = np.zeros((W, W), dtype=np.int64)
    np.add.at(halo_counts, (sender, needer), 1)
    S_pad = s_pad if s_pad is not None else _pad_to(int(halo_counts.max(initial=1)), pad_multiple)
    if int(halo_counts.max(initial=0)) > S_pad:
        raise ValueError(f"s_pad={S_pad} < max per-peer halo {int(halo_counts.max())}")

    halo_side_offsets = src_offsets if halo_side == "src" else dst_offsets
    N_src_pad = n_src_pad if n_src_pad is not None else _pad_to(int(src_counts.max(initial=1)), pad_multiple)
    N_dst_pad = n_dst_pad if n_dst_pad is not None else _pad_to(int(dst_counts.max(initial=1)), pad_multiple)
    N_halo_pad = N_src_pad if halo_side == "src" else N_dst_pad

    # position of each (needer, vid) within its (sender->needer) segment:
    # enc_u is sorted by (needer, vid) and vid-sorted groups sender blocks
    # contiguously (contiguous renumbering), so positions are running indices
    # within (needer, sender) runs.
    seg_key = needer * W + sender
    # running position within equal-key runs of the sorted seg_key sequence
    change = np.concatenate([[True], seg_key[1:] != seg_key[:-1]])
    run_starts = np.nonzero(change)[0]
    run_id = np.cumsum(change) - 1
    pos_in_seg = np.arange(len(seg_key)) - run_starts[run_id]

    # send arrays on the sender shard: send_idx[p, r, i]
    send_idx = np.zeros((W, W, S_pad), dtype=np.int32)
    send_mask = np.zeros((W, W, S_pad), dtype=np.float32)
    send_local = hvid - halo_side_offsets[sender]
    send_idx[sender, needer, pos_in_seg] = send_local.astype(np.int32)
    send_mask[sender, needer, pos_in_seg] = 1.0

    # halo slot (on the needer shard) for each unique (needer, vid) pair
    halo_slot = N_halo_pad + sender * S_pad + pos_in_seg

    # map (needer, vid) -> halo_slot for edge remapping: edges on owner rank
    # r referencing remote vid v find their slot by searchsorted into enc_u
    edge_enc = owner.astype(np.int64) * v_total + halo_vid
    idx_in_u = np.searchsorted(enc_u, edge_enc)
    # guard for purely-local edges (no match needed)
    idx_in_u = np.clip(idx_in_u, 0, max(len(enc_u) - 1, 0))

    # --- per-edge local indices ---
    if halo_side == "src":
        own_side_vid, own_side_off = dst, dst_offsets
        halo_side_vid = src
    else:
        own_side_vid, own_side_off = src, src_offsets
        halo_side_vid = dst

    own_local = own_side_vid - own_side_off[owner]
    halo_is_local = ~cross
    local_halo_side = halo_side_vid - halo_side_offsets[owner]
    if len(enc_u) > 0:
        remote_slot = halo_slot[idx_in_u]
    else:
        remote_slot = np.zeros(E, dtype=np.int64)
    halo_side_local_idx = np.where(halo_is_local, local_halo_side, remote_slot)

    n_owner_pad = N_dst_pad if edge_owner == "dst" else N_src_pad
    return types.SimpleNamespace(
        W=W, E=E, halo_side=halo_side, e_counts=e_counts, e_pad=E_pad,
        edge_rank=edge_rank, edge_slot=edge_slot, cross=cross,
        halo_counts=halo_counts, s_pad=S_pad,
        n_src_pad=N_src_pad, n_dst_pad=N_dst_pad, n_halo_pad=N_halo_pad,
        n_owner_pad=n_owner_pad,
        send_idx=send_idx, send_mask=send_mask,
        own_local=own_local, halo_side_local_idx=halo_side_local_idx,
        src_counts=src_counts, dst_counts=dst_counts,
        halo_deltas=tuple(int(d) for d in np.unique((needer - sender) % W)),
    )


# route of a sorted-id kernel -> the static hint that is its grid's width
GRID_ROUTES = {
    "scatter": "scatter_mc", "gather_mv": "gather_mv",
    "halo_sort": "halo_sort_mc", "interior": "interior_mc",
    "boundary": "boundary_mc",
}


def _count_grid(
    route: str, per_rank_counts, width: Optional[int] = None
) -> int:
    """How full one sorted-id kernel's grid is, counted where its hint is
    made. The grid is (rows, width): rows are a rank's vertex blocks (its
    edge chunks, for ``gather_mv``) and width is the plan's static hint,
    the most steps ANY row of ANY rank needs; every other row pays for it
    in steps its ``pl.when`` guard skips. ``per_rank_counts`` holds one
    array a rank of per-row step counts
    (``ops.pallas_segment.block_chunk_counts`` / ``chunk_vblock_spans``);
    ``width`` defaults to their maximum (a resumed sharded build passes
    the plan's, which shards it did not rebuild may have set). Adds the
    registry counters ``plan.segsum_grid_steps`` (rows x width) and
    ``plan.segsum_used_chunks`` (the counts' sum), each also under
    ``.<route>``, and returns the width. Host numbers only: nothing here
    reaches the plan."""
    from dgraph_tpu.obs.metrics import default_registry

    if width is None:
        width = max(
            [1] + [int(c.max(initial=1)) for c in per_rank_counts])
    rows = sum(len(c) for c in per_rank_counts)
    used = sum(int(c.sum()) for c in per_rank_counts)
    for suffix in ("", "." + route):
        default_registry.counter(
            "plan.segsum_grid_steps" + suffix, rows * width)
        default_registry.counter("plan.segsum_used_chunks" + suffix, used)
    return width


def halo_sort_sentinel(n_halo_rows: int, block_n: int) -> int:
    """Sorted id of a masked edge on the halo-sorted route: the first id
    past the last ``block_n``-row vertex block of the kernel's grid. The
    kernel rounds its rows up to whole blocks
    (``ops.pallas_segment._ChunkSchedule.N_pad``, ``block_chunk_counts``
    likewise), so ``n_halo_rows`` itself would lie INSIDE the last block
    whenever it is not a multiple of ``block_n``."""
    return _pad_to(n_halo_rows, block_n)


def halo_sort_route(halo_idx, edge_mask, n_halo_rows: int, owner_idx):
    """The halo-sorted route ``(halo_sort_perm, halo_sorted_ids,
    halo_sorted_owner_ids)`` of one rank's halo-side index row (or of the
    ``[W, e_pad]`` stack of them), its ``edge_mask`` and its owner-side
    index row: a stable sort by ``where(mask > 0, idx, sentinel)``,
    so real edges keep the order a sort by index gives them and every
    masked edge follows at :func:`halo_sort_sentinel`, outside every vertex
    block; the owner-side ids ride along in that order. The ONE place the
    route is made (monolithic, native and streamed
    builds call it); a row with no padding gets what a plain argsort gives.

    The ids stay monotone through the kernel's own tail pad
    (``num_segments + 1``) when that pad is empty, i.e. ``e_pad`` is a
    multiple of ``SCATTER_BLOCK_E`` (every kernel-scale plan since format
    v6). On a sub-block plan the tail is lower than the sentinel; both are
    dropped (one-hot guard, ``out[:num_segments]``) and only the LAST
    block's end is searched past them, so at worst that block runs its
    full hint width over chunks of zeros."""
    key = np.where(
        np.asarray(edge_mask) > 0, halo_idx,
        np.int32(halo_sort_sentinel(n_halo_rows, SCATTER_BLOCK_N)),
    ).astype(np.int32, copy=False)
    perm = np.argsort(key, axis=-1, kind="stable").astype(np.int32)
    return (
        perm,
        np.take_along_axis(key, perm, axis=-1),
        np.take_along_axis(
            np.asarray(owner_idx).astype(np.int32, copy=False), perm, axis=-1),
    )


def halo_wire_rows(plan: "EdgePlan", impl: str) -> int:
    """Rows one halo exchange puts on the wire, over all ranks, under the
    lowering ``impl``, padding included (``obs.footprint`` prices the same
    rows in bytes): all_to_all moves every remote peer block at ``s_pad``;
    the round lowerings move one ``s_pad`` block a live delta."""
    W, S = plan.world_size, plan.halo.s_pad
    if impl == "all_to_all":
        return W * (W - 1) * S
    if impl in ("ppermute", "overlap"):
        return len(plan.halo_deltas) * W * S
    return 0


def _finalize_plan(
    *, src_idx_arr, dst_idx_arr, edge_mask, src_counts, dst_counts, e_counts,
    send_idx, send_mask, s_pad_val, W, E, n_src_pad_val, n_dst_pad_val,
    e_pad_val, halo_side, homogeneous, edge_owner, owner_sorted, halo_deltas,
    edge_rank, edge_slot, halo_counts, tag: str, sort_route: bool,
    overlap: bool = False,
) -> tuple[EdgePlan, EdgePlanLayout]:
    """Shared assembly tail of the numpy and native plan builders: Pallas
    scheduling hints, EdgePlan/EdgePlanLayout construction, efficiency log.
    Keeping it in one place means a plan-format change cannot silently
    diverge between the two paths."""
    _require_padding_mask(src_idx_arr, dst_idx_arr, edge_mask, halo_side,
                          n_src_pad_val, n_dst_pad_val)
    n_owner_pad = n_dst_pad_val if edge_owner == "dst" else n_src_pad_val
    owner_idx_arr = dst_idx_arr if edge_owner == "dst" else src_idx_arr
    scatter_block_e, scatter_block_n = SCATTER_BLOCK_E, SCATTER_BLOCK_N
    if owner_sorted:
        from dgraph_tpu.ops.pallas_segment import (
            block_chunk_counts,
            chunk_vblock_spans,
        )

        scatter_mc = _count_grid("scatter", [
            block_chunk_counts(
                owner_idx_arr[r], n_owner_pad,
                block_e=scatter_block_e, block_n=scatter_block_n,
            )
            for r in range(W)
        ])
        gather_mv = _count_grid("gather_mv", [
            chunk_vblock_spans(
                owner_idx_arr[r], n_owner_pad,
                block_e=scatter_block_e, block_n=scatter_block_n,
            )
            for r in range(W)
        ])
    else:
        scatter_mc = 1
        gather_mv = 0

    # halo-side sorted route (see EdgePlan.halo_sort_perm)
    halo_sort_perm = halo_sorted_ids = halo_sorted_owner_ids = None
    halo_sort_mc = 1
    if sort_route:
        from dgraph_tpu.ops.pallas_segment import block_chunk_counts

        halo_idx_arr = src_idx_arr if halo_side == "src" else dst_idx_arr
        n_halo_rows = (
            n_src_pad_val if halo_side == "src" else n_dst_pad_val
        ) + W * s_pad_val
        halo_sort_perm, halo_sorted_ids, halo_sorted_owner_ids = (
            halo_sort_route(halo_idx_arr, edge_mask, n_halo_rows,
                            owner_idx_arr))
        halo_sort_mc = _count_grid("halo_sort", [
            block_chunk_counts(
                halo_sorted_ids[r], n_halo_rows,
                block_e=scatter_block_e, block_n=scatter_block_n,
            )
            for r in range(W)
        ])

    overlap_spec = None
    if overlap:
        overlap_spec = _build_overlap_spec(
            src_idx_arr, dst_idx_arr, edge_mask, halo_side,
            n_src_pad_val, n_dst_pad_val, s_pad_val, W, e_pad_val,
            owner_sorted, scatter_block_e, scatter_block_n,
        )

    halo_pair_rows = tuple(
        tuple(int(v) for v in row) for row in np.asarray(halo_counts)
    )

    plan = EdgePlan(
        src_index=src_idx_arr,
        dst_index=dst_idx_arr,
        edge_mask=edge_mask,
        num_local_src=src_counts.astype(np.int32),
        num_local_dst=dst_counts.astype(np.int32),
        num_edges=e_counts.astype(np.int32),
        halo=HaloSpec(send_idx=send_idx, send_mask=send_mask, s_pad=s_pad_val),
        world_size=W,
        n_src_pad=n_src_pad_val,
        n_dst_pad=n_dst_pad_val,
        e_pad=e_pad_val,
        halo_side=halo_side,
        homogeneous=homogeneous,
        owner_sorted=owner_sorted,
        scatter_mc=scatter_mc,
        scatter_block_e=scatter_block_e,
        scatter_block_n=scatter_block_n,
        halo_deltas=halo_deltas,
        halo_sort_perm=halo_sort_perm,
        halo_sorted_ids=halo_sorted_ids,
        halo_sorted_owner_ids=halo_sorted_owner_ids,
        halo_sort_mc=halo_sort_mc,
        gather_mv=gather_mv,
        overlap=overlap_spec,
        halo_pair_rows=halo_pair_rows,
        wire_format=plan_wire_format(W, halo_deltas),
    )
    layout = EdgePlanLayout(
        edge_rank=edge_rank,
        edge_slot=edge_slot,
        halo_counts=halo_counts,
        src_counts=src_counts,
        dst_counts=dst_counts,
    )
    eff = plan_efficiency(plan, layout)
    # the exchange's fill under the lowering the run will execute: the
    # quantities behind eff's halo_wire_fill_*, as registry counters
    from dgraph_tpu.obs.metrics import default_registry

    default_registry.counter(
        "plan.halo_wire_rows", halo_wire_rows(plan, eff["halo_impl"]))
    default_registry.counter(
        "plan.halo_real_rows", int(np.asarray(halo_counts).sum()))
    _logger.info(
        "EdgePlan built%s: W=%d E=%d e_pad=%d (fill %.3f) s_pad=%d "
        "halo_fill_active=%.3f wire_fill[a2a=%.3f pp=%.3f] deltas=%d -> %s",
        tag, W, E, e_pad_val, eff["edge_fill"], s_pad_val,
        eff["halo_fill_active"], eff["halo_wire_fill_all_to_all"],
        eff["halo_wire_fill_ppermute"], eff["num_halo_deltas"], eff["halo_impl"],
    )
    return plan, layout


def _overlap_rows_for_rank(
    src_row, dst_row, mask_row, *, halo_side, n_halo_pad, n_owner_pad,
    s_pad, W, e_pad, e_int_pad, e_bnd_pad, owner_sorted,
    scatter_block_e, scatter_block_n,
):
    """ONE rank's interior/boundary split rows + the per-block chunk counts
    its Pallas hints are the maxima of (route -> counts, see
    :func:`_count_grid`; empty unless ``owner_sorted``) — the single
    per-rank core behind both build modes: the monolithic
    :func:`_build_overlap_spec` stacks these rows into an
    :class:`OverlapSpec`, and the streaming shard assembler
    (:func:`_assemble_overlap_rows`) ships them in the shard payload, so
    the fill/rebase/hint conventions cannot diverge between the two.

    Interior halo-side padded fill is OUT of the local table
    (``n_halo_pad``); owner-side padded fill is ``n_owner_pad`` (monotone
    tail); ``epos`` fill is ``e_pad``; the boundary halo-side entry is
    rebased into the ``[0, W*s_pad)`` exchange buffer (padded slots ->
    ``W*s_pad``, out of range of the buffer)."""
    halo_row = src_row if halo_side == "src" else dst_row
    live = mask_row > 0
    is_bnd = live & (halo_row >= n_halo_pad)
    is_int = live & ~is_bnd

    def subset(sel_mask, e_sub_pad):
        pos = np.nonzero(sel_mask)[0]
        k = len(pos)
        epos = np.full(e_sub_pad, e_pad, np.int32)
        s_arr = np.full(e_sub_pad, n_owner_pad if halo_side == "dst"
                        else n_halo_pad, np.int32)
        d_arr = np.full(e_sub_pad, n_owner_pad if halo_side == "src"
                        else n_halo_pad, np.int32)
        mask = np.zeros(e_sub_pad, np.float32)
        epos[:k] = pos
        s_arr[:k] = src_row[pos]
        d_arr[:k] = dst_row[pos]
        mask[:k] = 1.0
        return epos, s_arr, d_arr, mask

    int_epos, int_src, int_dst, int_mask = subset(is_int, e_int_pad)
    bnd_epos, bnd_src, bnd_dst, bnd_mask = subset(is_bnd, e_bnd_pad)
    bnd_halo = bnd_src if halo_side == "src" else bnd_dst
    rebased = np.where(
        bnd_mask > 0, bnd_halo - n_halo_pad, W * s_pad
    ).astype(np.int32)
    if halo_side == "src":
        bnd_src = rebased
    else:
        bnd_dst = rebased
    counts = {}
    if owner_sorted:
        from dgraph_tpu.ops.pallas_segment import block_chunk_counts

        int_owner = int_dst if halo_side == "src" else int_src
        bnd_owner = bnd_dst if halo_side == "src" else bnd_src
        counts["interior"] = block_chunk_counts(
            int_owner, n_owner_pad,
            block_e=scatter_block_e, block_n=scatter_block_n,
        )
        counts["boundary"] = block_chunk_counts(
            bnd_owner, n_owner_pad,
            block_e=scatter_block_e, block_n=scatter_block_n,
        )
    rows = {
        "int_src": int_src, "int_dst": int_dst, "int_mask": int_mask,
        "int_epos": int_epos,
        "bnd_src": bnd_src, "bnd_dst": bnd_dst, "bnd_mask": bnd_mask,
        "bnd_epos": bnd_epos,
        "num_interior": int(is_int.sum()),
        "num_boundary": int(is_bnd.sum()),
    }
    return rows, counts


def _build_overlap_spec(
    src_idx_arr, dst_idx_arr, edge_mask, halo_side, n_src_pad, n_dst_pad,
    s_pad, W, e_pad, owner_sorted, scatter_block_e, scatter_block_n,
) -> OverlapSpec:
    """Derive the interior/boundary edge split from the assembled padded
    index arrays — shared by the numpy and native builders (both feed the
    same arrays through ``_finalize_plan``, so the split cannot diverge
    between them), and each rank's rows come from the same per-rank core
    the streaming shard builder uses (:func:`_overlap_rows_for_rank`).
    See :class:`OverlapSpec` for the index conventions."""
    halo_idx = src_idx_arr if halo_side == "src" else dst_idx_arr
    n_halo_pad = n_src_pad if halo_side == "src" else n_dst_pad
    n_owner_pad = n_dst_pad if halo_side == "src" else n_src_pad
    live = edge_mask > 0
    is_bnd = live & (halo_idx >= n_halo_pad)
    n_bnd = is_bnd.sum(axis=1).astype(np.int64)
    n_int = live.sum(axis=1).astype(np.int64) - n_bnd
    int_max = int(n_int.max(initial=1))
    bnd_max = int(n_bnd.max(initial=1))
    # subset padding follows the plan's edge-pad alignment rule (lane tile
    # floor of 8; Pallas scatter-block alignment once at kernel scale)
    e_int_pad = _pad_to(int_max, _edge_pad_align(int_max, 8))
    e_bnd_pad = _pad_to(bnd_max, _edge_pad_align(bnd_max, 8))

    per_rank = [
        _overlap_rows_for_rank(
            src_idx_arr[r], dst_idx_arr[r], edge_mask[r],
            halo_side=halo_side, n_halo_pad=n_halo_pad,
            n_owner_pad=n_owner_pad, s_pad=s_pad, W=W, e_pad=e_pad,
            e_int_pad=e_int_pad, e_bnd_pad=e_bnd_pad,
            owner_sorted=owner_sorted, scatter_block_e=scatter_block_e,
            scatter_block_n=scatter_block_n,
        )
        for r in range(W)
    ]
    rows = [p[0] for p in per_rank]
    interior_mc = boundary_mc = 1
    if owner_sorted:
        interior_mc = _count_grid(
            "interior", [p[1]["interior"] for p in per_rank])
        boundary_mc = _count_grid(
            "boundary", [p[1]["boundary"] for p in per_rank])

    def stack(key):
        return np.stack([row[key] for row in rows])

    return OverlapSpec(
        int_src=stack("int_src"), int_dst=stack("int_dst"),
        int_mask=stack("int_mask"), int_epos=stack("int_epos"),
        bnd_src=stack("bnd_src"), bnd_dst=stack("bnd_dst"),
        bnd_mask=stack("bnd_mask"), bnd_epos=stack("bnd_epos"),
        num_interior=n_int.astype(np.int32),
        num_boundary=n_bnd.astype(np.int32),
        e_int_pad=e_int_pad, e_bnd_pad=e_bnd_pad,
        interior_mc=interior_mc, boundary_mc=boundary_mc,
    )


def _build_edge_plan_native(
    src, dst, src_partition, dst_partition, src_offsets, dst_offsets,
    src_counts, dst_counts, W, edge_owner, homogeneous,
    n_src_pad, n_dst_pad, e_pad, s_pad, pad_multiple,
    sort_route: bool, overlap: bool = False,
) -> tuple[EdgePlan, EdgePlanLayout]:
    """Billion-edge path: the per-edge sort/dedup/fill runs in the native
    core (csrc plan_core_*, bounded-memory radix sorts) and numpy only
    assembles the (cheap) metadata. Output is identical to the numpy path —
    pinned by tests/test_plan.py::test_native_plan_matches_numpy."""
    from dgraph_tpu import native as _native

    E = len(src)
    core = _native.PlanCore(
        src, dst, src_partition, dst_partition, src_offsets, dst_offsets,
        W, edge_owner,
    )
    E_pad = e_pad if e_pad is not None else _pad_to(
        core.e_max, _edge_pad_align(core.e_max, pad_multiple))
    if core.e_max > E_pad:
        raise ValueError(f"e_pad={E_pad} < max per-rank edges {core.e_max}")
    S_pad = s_pad if s_pad is not None else _pad_to(max(core.s_max, 1), pad_multiple)
    if core.s_max > S_pad:
        raise ValueError(f"s_pad={S_pad} < max per-peer halo {core.s_max}")
    N_src_pad = n_src_pad if n_src_pad is not None else _pad_to(int(src_counts.max(initial=1)), pad_multiple)
    N_dst_pad = n_dst_pad if n_dst_pad is not None else _pad_to(int(dst_counts.max(initial=1)), pad_multiple)
    halo_side = "src" if edge_owner == "dst" else "dst"
    n_owner_pad = N_dst_pad if edge_owner == "dst" else N_src_pad
    N_halo_pad = N_src_pad if halo_side == "src" else N_dst_pad

    src_idx_arr = np.empty((W, E_pad), np.int32)
    dst_idx_arr = np.empty((W, E_pad), np.int32)
    edge_mask = np.empty((W, E_pad), np.float32)
    send_idx = np.empty((W, W, S_pad), np.int32)
    send_mask = np.empty((W, W, S_pad), np.float32)
    halo_counts = np.empty((W, W), np.int64)
    edge_rank = np.empty(E, np.int32)
    edge_slot = np.empty(E, np.int64)
    core.fill(
        E_pad, S_pad, n_owner_pad, N_halo_pad,
        src_idx_arr, dst_idx_arr, edge_mask.reshape(-1),
        send_idx.reshape(-1), send_mask.reshape(-1),
        halo_counts.reshape(-1), edge_rank, edge_slot,
    )
    e_counts = np.bincount(edge_rank, minlength=W).astype(np.int64)
    core.close()

    sender_r, needer_r = np.nonzero(halo_counts)
    return _finalize_plan(
        src_idx_arr=src_idx_arr, dst_idx_arr=dst_idx_arr, edge_mask=edge_mask,
        src_counts=src_counts, dst_counts=dst_counts, e_counts=e_counts,
        send_idx=send_idx, send_mask=send_mask, s_pad_val=S_pad, W=W, E=E,
        n_src_pad_val=N_src_pad, n_dst_pad_val=N_dst_pad, e_pad_val=E_pad,
        halo_side=halo_side, homogeneous=homogeneous, edge_owner=edge_owner,
        owner_sorted=True,
        halo_deltas=tuple(int(d) for d in np.unique((needer_r - sender_r) % W)),
        edge_rank=edge_rank.astype(np.int64), edge_slot=edge_slot,
        halo_counts=halo_counts, tag=" (native core)", sort_route=sort_route,
        overlap=overlap,
    )


# ---------------------------------------------------------------------------
# Streaming per-rank plan builds (sharded artifacts, cache format v8)
# ---------------------------------------------------------------------------


def _shard_statics(prep, *, homogeneous, edge_owner, sort_edges, sort_route,
                   overlap) -> dict:
    """The manifest's JSON-able static description of a sharded plan —
    everything :func:`assemble_plan` needs besides the per-rank payloads.
    Per-rank Pallas hints are maxed in at finalize time
    (:func:`build_edge_plan_sharded`)."""
    st = {
        "world_size": int(prep.W),
        "n_src_pad": int(prep.n_src_pad),
        "n_dst_pad": int(prep.n_dst_pad),
        "e_pad": int(prep.e_pad),
        "s_pad": int(prep.s_pad),
        "halo_side": prep.halo_side,
        "homogeneous": bool(homogeneous),
        "edge_owner": edge_owner,
        "owner_sorted": bool(sort_edges),
        "sort_route": bool(sort_route),
        "overlap": bool(overlap),
        "scatter_block_e": SCATTER_BLOCK_E,
        "scatter_block_n": SCATTER_BLOCK_N,
        "halo_deltas": [int(d) for d in prep.halo_deltas],
        # full-world traffic matrix: rank-subset loads keep whole-world
        # statics, so every host resolves the identical halo lowering
        "halo_pair_rows": [
            [int(v) for v in row] for row in np.asarray(prep.halo_counts)
        ],
        # build-time wire-format resolution (same ONE attach rule as the
        # monolithic path), stamped so a cache round-trip keeps an
        # adopted codec even if the loading process has no record
        "wire_format": plan_wire_format(prep.W, tuple(prep.halo_deltas)),
    }
    if overlap:
        # subset pads are global maxima over ranks — computable from the
        # skeleton alone (boundary == cross edges), so every shard pads
        # its subsets identically whether built in one run or resumed
        n_bnd = np.bincount(
            prep.edge_rank[prep.cross], minlength=prep.W
        ).astype(np.int64)
        n_int = prep.e_counts - n_bnd
        int_max = int(n_int.max(initial=1))
        bnd_max = int(n_bnd.max(initial=1))
        st["e_int_pad"] = _pad_to(int_max, _edge_pad_align(int_max, 8))
        st["e_bnd_pad"] = _pad_to(bnd_max, _edge_pad_align(bnd_max, 8))
    return st


def shard_nbytes_estimate(statics: dict) -> int:
    """Upper-bound bytes of ONE rank's shard payload, from the manifest
    statics alone — the number the streaming build's upfront memory-budget
    check uses (so an over-budget build fails before assembling anything)."""
    e_pad, W, s_pad = statics["e_pad"], statics["world_size"], statics["s_pad"]
    n = e_pad * (4 + 4 + 4)  # src/dst idx + mask
    if statics.get("sort_route"):
        # halo_sort_perm + halo_sorted_ids + halo_sorted_owner_ids
        n += 3 * e_pad * 4
    if statics.get("overlap"):
        n += (statics["e_int_pad"] + statics["e_bnd_pad"]) * 4 * 4
    n += 2 * W * s_pad * 4  # send_idx + send_mask rows
    return n


def _assemble_shard_payload(prep, r: int, *, sort_edges: bool,
                            sort_route: bool, overlap: bool,
                            overlap_pads: tuple = (None, None)):
    """One rank's plan arrays + Pallas hints + the per-row step counts the
    hints are the maxima of (route -> counts, for :func:`_count_grid`),
    assembled from the shared numpy skeleton. Row-for-row identical to
    what the monolithic path's ``[W, E_pad]`` stack holds at index ``r``
    (the property the kill-and-resume bit-parity pin rides on)."""
    W, E_pad = prep.W, prep.e_pad
    sel = prep.edge_rank == r
    slots = prep.edge_slot[sel]
    halo_row = np.zeros(E_pad, np.int32)
    halo_row[slots] = prep.halo_side_local_idx[sel].astype(np.int32)
    own_row = np.full(E_pad, prep.n_owner_pad, np.int32)
    own_row[slots] = prep.own_local[sel].astype(np.int32)
    mask_row = np.zeros(E_pad, np.float32)
    mask_row[slots] = 1.0
    if prep.halo_side == "src":
        src_row, dst_row = halo_row, own_row
    else:
        src_row, dst_row = own_row, halo_row

    hints = {"scatter_mc": 1, "gather_mv": 0, "halo_sort_mc": 1,
             "interior_mc": 1, "boundary_mc": 1}
    counts = {}
    if sort_edges:
        from dgraph_tpu.ops.pallas_segment import (
            block_chunk_counts,
            chunk_vblock_spans,
        )

        counts["scatter"] = block_chunk_counts(
            own_row, prep.n_owner_pad,
            block_e=SCATTER_BLOCK_E, block_n=SCATTER_BLOCK_N,
        )
        counts["gather_mv"] = chunk_vblock_spans(
            own_row, prep.n_owner_pad,
            block_e=SCATTER_BLOCK_E, block_n=SCATTER_BLOCK_N,
        )

    perm = sorted_ids = sorted_owner_ids = None
    if sort_route:
        from dgraph_tpu.ops.pallas_segment import block_chunk_counts

        n_halo_rows = prep.n_halo_pad + W * prep.s_pad
        perm, sorted_ids, sorted_owner_ids = halo_sort_route(
            halo_row, mask_row, n_halo_rows, own_row)
        counts["halo_sort"] = block_chunk_counts(
            sorted_ids, n_halo_rows,
            block_e=SCATTER_BLOCK_E, block_n=SCATTER_BLOCK_N,
        )

    payload = {
        "src_index": src_row,
        "dst_index": dst_row,
        "edge_mask": mask_row,
        "num_local_src": int(prep.src_counts[r]),
        "num_local_dst": int(prep.dst_counts[r]),
        "num_edges": int(prep.e_counts[r]),
        "send_idx": prep.send_idx[r],
        "send_mask": prep.send_mask[r],
        "halo_sort_perm": perm,
        "halo_sorted_ids": sorted_ids,
        "halo_sorted_owner_ids": sorted_owner_ids,
        "overlap": None,
    }
    if overlap:
        payload["overlap"], ov_counts = _assemble_overlap_rows(
            prep, src_row, dst_row, mask_row, sort_edges,
            e_int_pad=overlap_pads[0], e_bnd_pad=overlap_pads[1],
        )
        counts.update(ov_counts)
    for route, c in counts.items():
        hints[GRID_ROUTES[route]] = max(1, int(c.max(initial=1)))
    return payload, hints, counts


def _assemble_overlap_rows(prep, src_row, dst_row, mask_row,
                           sort_edges: bool, *, e_int_pad: int,
                           e_bnd_pad: int):
    """Per-rank interior/boundary split rows for one shard — a thin
    wrapper over :func:`_overlap_rows_for_rank` (the same core the
    monolithic :func:`_build_overlap_spec` stacks, so streamed and
    monolithic splits are structurally identical). The subset pads are
    the global maxima the manifest statics record
    (:func:`_shard_statics`)."""
    return _overlap_rows_for_rank(
        src_row, dst_row, mask_row,
        halo_side=prep.halo_side, n_halo_pad=prep.n_halo_pad,
        n_owner_pad=prep.n_owner_pad, s_pad=prep.s_pad, W=prep.W,
        e_pad=prep.e_pad, e_int_pad=e_int_pad, e_bnd_pad=e_bnd_pad,
        owner_sorted=sort_edges,
        scatter_block_e=SCATTER_BLOCK_E, scatter_block_n=SCATTER_BLOCK_N,
    )


def _content_fingerprint(edge_index, src_partition, dst_partition) -> str:
    """Streaming SHA-256 of the build inputs (dtype, shape, bytes) —
    chunked, so a memmap'd edge list is read through in windows and
    never materialized.  The default shard-build fingerprint when the
    caller supplies none: without it, a resumed manifest could adopt
    shards built from DIFFERENT edges that happen to share statics
    (same per-rank counts and pads)."""
    h = hashlib.sha256()
    for arr in (edge_index, src_partition, dst_partition):
        if arr is None:
            h.update(b"|none")
            continue
        a = np.asarray(arr)
        h.update(f"|{a.dtype.str}{a.shape}".encode())
        if not a.flags.c_contiguous:
            a = np.ascontiguousarray(a)
        flat = a.reshape(-1)
        step = max(1, (1 << 26) // max(a.itemsize, 1))  # 64 MiB windows
        for i in range(0, flat.size, step):
            h.update(flat[i:i + step].data)
    return "content:" + h.hexdigest()[:24]


def build_plan_shards(
    edge_index: np.ndarray,
    src_partition: np.ndarray,
    dst_partition: Optional[np.ndarray] = None,
    *,
    out_dir: str,
    world_size: int,
    memory_budget_bytes: Optional[int] = None,
    resume: bool = True,
    rebuild_ranks: tuple = (),
    write_layout: bool = True,
    fingerprint: str = "",
    edge_owner: str = "dst",
    n_src_pad: Optional[int] = None,
    n_dst_pad: Optional[int] = None,
    e_pad: Optional[int] = None,
    s_pad: Optional[int] = None,
    pad_multiple: int = 8,
    sort_edges: bool = True,
    sort_route: Optional[bool] = None,
    overlap: Optional[bool] = None,
    use_native: Optional[bool] = None,
) -> dict:
    """Streaming-mode plan build: assemble ONE rank's shard at a time
    (directly off a memmap'd edge list — nothing here forces the ``[2, E]``
    input resident) and write it durably under ``out_dir`` (cache format
    v8: ``shard_XXXX.pkl`` + checksummed ``manifest.json`` +
    ``layout.pkl``, :mod:`dgraph_tpu.plan_shards`).  Returns the final
    manifest WITHOUT assembling an in-RAM :class:`EdgePlan` — at real
    papers100M scale the assembled stack is the ~40+ GB allocation this
    mode exists to avoid; use :func:`build_edge_plan_sharded` (or
    :func:`load_sharded_plan` with a rank subset) when you want one.

    Peak RSS beyond the O(E) skeleton is ONE shard's arrays, enforced by
    the memory budget (``memory_budget_bytes`` /
    ``$DGRAPH_PLAN_MEMORY_BUDGET_MB``) which raises a structured
    :class:`~dgraph_tpu.plan_shards.PlanBuildMemoryExceeded` instead of
    getting OOM-killed — the r5 papers100M failure mode (ROADMAP item 3).
    A killed build **resumes**: shards already durable in the manifest
    (same fingerprint/format/statics, checksums intact) are skipped, and
    the resumed result is bit-identical to an uninterrupted build.
    ``rebuild_ranks`` forces named shards to rebuild even when the
    manifest says they are done (the loaders' single-corrupt-shard repair
    path).

    The ``plan.build_shard`` chaos point fires before each rank's
    assembly (index = rank), ``plan.write`` before each shard write.

    The per-rank streaming core is the numpy skeleton
    (:func:`_numpy_plan_prep`); ``use_native=True`` is rejected — the
    native core fills the whole ``[W, E_pad]`` stack at once, which is
    exactly the allocation this mode exists to avoid.

    ``fingerprint`` defaults to a streaming content hash of the inputs
    (:func:`_content_fingerprint`); pass an explicit value only when it
    is already content-derived — a constant label would let a resumed
    build adopt shards from different inputs with coinciding statics.
    """
    from dgraph_tpu.obs import spans

    with spans.stage(
        "setup.plan", num_edges=int(np.shape(edge_index)[1]),
        world_size=world_size, sharded=True,
    ):
        from dgraph_tpu import chaos
        from dgraph_tpu import plan_shards as ps

        if use_native:
            raise ValueError(
                "build_plan_shards streams through the numpy per-rank "
                "core; use_native=True would materialize the full [W, E_pad] "
                "stack this mode exists to avoid"
            )
        if not fingerprint:
            # an un-keyed manifest must still be bound to the build INPUTS:
            # statics (counts, pads) can coincide between two different edge
            # lists, and a resumed build that adopts shards from the other
            # one is a silently wrong comm plan
            fingerprint = _content_fingerprint(
                edge_index, src_partition, dst_partition
            )
        pro = _plan_build_prologue(
            edge_index, src_partition, dst_partition, edge_owner=edge_owner,
            sort_edges=sort_edges, sort_route=sort_route, overlap=overlap,
            pad_multiple=pad_multiple, e_pad=e_pad, s_pad=s_pad,
            world_size=world_size,
        )
        homogeneous, E, W = pro.homogeneous, pro.E, world_size
        src_counts, dst_counts = pro.src_counts, pro.dst_counts
        sort_route, overlap = pro.sort_route, pro.overlap

        prep = _numpy_plan_prep(
            pro.src, pro.dst, pro.src_partition, pro.dst_partition,
            pro.src_offsets, pro.dst_offsets,
            src_counts, dst_counts, W, edge_owner, sort_edges,
            n_src_pad, n_dst_pad, e_pad, s_pad, pad_multiple,
        )
        statics = _shard_statics(
            prep, homogeneous=homogeneous, edge_owner=edge_owner,
            sort_edges=sort_edges, sort_route=sort_route, overlap=overlap,
        )
        writer = ps.PlanShardWriter(
            out_dir,
            fingerprint=fingerprint,
            world_size=W,
            statics=statics,
            build_kwargs={
                "edge_owner": edge_owner, "pad_multiple": pad_multiple,
                "sort_edges": sort_edges, "sort_route": bool(sort_route),
                "overlap": bool(overlap), "num_edges": E,
            },
            memory_budget_bytes=memory_budget_bytes,
            resume=resume,
            rebuild_ranks=rebuild_ranks,
        )
        # fail BEFORE assembling anything when even one shard cannot fit
        writer.check_budget(shard_nbytes_estimate(statics))
        built = 0
        grid_counts: dict = {}  # of the shards assembled in this process
        for r in range(W):
            if writer.done(r):
                continue
            chaos.fire("plan.build_shard", index=r)
            payload, hints, counts = _assemble_shard_payload(
                prep, r, sort_edges=sort_edges, sort_route=sort_route,
                overlap=overlap,
                overlap_pads=(statics.get("e_int_pad"), statics.get("e_bnd_pad")),
            )
            for route, c in counts.items():
                grid_counts.setdefault(route, []).append(c)
            writer.write(r, payload, hints=hints)
            built += 1
        # plan-level Pallas hints are maxima over the per-shard values the
        # manifest recorded — identical whether the shards were built in one
        # pass or across resumed processes
        entries = writer.manifest["shards"]
        hint_names = ("scatter_mc", "gather_mv", "halo_sort_mc",
                      "interior_mc", "boundary_mc")
        hints_max = {
            name: max(int(entries[str(r)].get("hints", {}).get(name, 0))
                      for r in range(W))
            for name in hint_names
        }
        for route, per_rank in grid_counts.items():
            _count_grid(route, per_rank, width=hints_max[GRID_ROUTES[route]])
        # the layout sidecar is O(E) (edge_rank/edge_slot): at papers100M
        # scale it pickles to tens of GB, and atomic_pickle_dump transiently
        # doubles that on disk — callers that never consume it (the p100m
        # plan stage, per-host shard loading) opt out with write_layout=False
        layout_payload = None
        if write_layout:
            layout_payload = {
                "edge_rank": prep.edge_rank,
                "edge_slot": prep.edge_slot,
                "halo_counts": prep.halo_counts,
                "src_counts": src_counts,
                "dst_counts": dst_counts,
            }
        manifest = writer.finalize(layout_payload, statics_update=hints_max)
        _logger.info(
            "sharded EdgePlan built in %s: W=%d E=%d e_pad=%d s_pad=%d "
            "(%d shard(s) assembled this run, %d resumed)",
            out_dir, W, E, prep.e_pad, prep.s_pad, built, W - built,
        )
        return manifest


def build_edge_plan_sharded(
    edge_index: np.ndarray,
    src_partition: np.ndarray,
    dst_partition: Optional[np.ndarray] = None,
    *,
    out_dir: str,
    ranks: Optional[list] = None,
    load_layout: Optional[bool] = None,
    **build_kwargs: Any,
) -> tuple:
    """:func:`build_plan_shards` + :func:`load_sharded_plan`: the
    streaming-mode :func:`build_edge_plan` for callers that want the
    assembled ``(plan, layout)`` back (accepts every
    :func:`build_plan_shards` keyword).

    ``ranks=None`` assembles all ranks — bit-identical to the monolithic
    build (pinned by ``tests/test_plan_shards.py``).  A subset returns a
    plan whose leading axis is ``len(ranks)`` while every static —
    including ``world_size`` — still describes the full W-rank world, the
    each-host-loads-its-shard shape ``comm.multihost`` consumes.
    ``load_layout=None`` loads the O(E) layout sidecar only for a
    full-world load — a rank subset is the per-host path, which must not
    read (or SHA-verify) an artifact as big as the edge list.
    """
    build_plan_shards(
        edge_index, src_partition, dst_partition, out_dir=out_dir,
        **build_kwargs,
    )
    if load_layout is None:
        load_layout = ranks is None and build_kwargs.get("write_layout", True)
    # verify=False: every shard was either written moments ago by this
    # process or checksum-verified when the writer adopted it for resume —
    # re-hashing a ~40+ GB artifact straight after writing it would double
    # the build's IO. Cold loads (cached_edge_plan's hit path) verify.
    return load_sharded_plan(
        out_dir, ranks=ranks, load_layout=load_layout, verify=False
    )


def assemble_plan(manifest: dict, payloads: dict, ranks: list) -> EdgePlan:
    """Stack per-rank shard payloads (``ranks`` order) into an
    :class:`EdgePlan` under the manifest's statics. ``ranks == range(W)``
    reproduces the monolithic build bit-for-bit; a subset yields the
    partial stack a multi-controller host feeds its own devices."""
    st = manifest["statics"]

    def stack(key):
        return np.stack([payloads[r][key] for r in ranks])

    def counts(key):
        return np.asarray([payloads[r][key] for r in ranks], np.int32)

    # a cached or hand-edited shard is held to the padding convention as
    # a fresh build is (_finalize_plan)
    src_index, dst_index, edge_mask = (
        stack("src_index"), stack("dst_index"), stack("edge_mask"))
    _require_padding_mask(src_index, dst_index, edge_mask, st["halo_side"],
                          int(st["n_src_pad"]), int(st["n_dst_pad"]))
    sort_route = st.get("sort_route", False)
    pair_rows = tuple(
        tuple(int(v) for v in row) for row in st.get("halo_pair_rows", [])
    )
    overlap_spec = None
    if st.get("overlap"):
        def ostack(key):
            return np.stack([payloads[r]["overlap"][key] for r in ranks])

        overlap_spec = OverlapSpec(
            int_src=ostack("int_src"), int_dst=ostack("int_dst"),
            int_mask=ostack("int_mask"), int_epos=ostack("int_epos"),
            bnd_src=ostack("bnd_src"), bnd_dst=ostack("bnd_dst"),
            bnd_mask=ostack("bnd_mask"), bnd_epos=ostack("bnd_epos"),
            num_interior=np.asarray(
                [payloads[r]["overlap"]["num_interior"] for r in ranks],
                np.int32),
            num_boundary=np.asarray(
                [payloads[r]["overlap"]["num_boundary"] for r in ranks],
                np.int32),
            e_int_pad=int(st["e_int_pad"]), e_bnd_pad=int(st["e_bnd_pad"]),
            interior_mc=int(st.get("interior_mc", 1)),
            boundary_mc=int(st.get("boundary_mc", 1)),
        )
    return EdgePlan(
        src_index=src_index,
        dst_index=dst_index,
        edge_mask=edge_mask,
        num_local_src=counts("num_local_src"),
        num_local_dst=counts("num_local_dst"),
        num_edges=counts("num_edges"),
        halo=HaloSpec(
            send_idx=stack("send_idx"), send_mask=stack("send_mask"),
            s_pad=int(st["s_pad"]),
        ),
        world_size=int(st["world_size"]),
        n_src_pad=int(st["n_src_pad"]),
        n_dst_pad=int(st["n_dst_pad"]),
        e_pad=int(st["e_pad"]),
        halo_side=st["halo_side"],
        homogeneous=bool(st["homogeneous"]),
        owner_sorted=bool(st["owner_sorted"]),
        scatter_mc=int(st.get("scatter_mc", 1)),
        scatter_block_e=int(st["scatter_block_e"]),
        scatter_block_n=int(st["scatter_block_n"]),
        halo_deltas=tuple(int(d) for d in st["halo_deltas"]),
        halo_sort_perm=stack("halo_sort_perm") if sort_route else None,
        halo_sorted_ids=stack("halo_sorted_ids") if sort_route else None,
        halo_sorted_owner_ids=(
            stack("halo_sorted_owner_ids") if sort_route else None),
        halo_sort_mc=int(st.get("halo_sort_mc", 1)),
        gather_mv=int(st.get("gather_mv", 0)),
        overlap=overlap_spec,
        halo_pair_rows=pair_rows,
        # stamped manifests carry their build-time resolution; pre-codec
        # manifests (no key) re-resolve through the same ONE attach rule
        wire_format=st.get("wire_format") or plan_wire_format(
            int(st["world_size"]),
            tuple(int(d) for d in st["halo_deltas"]),
        ),
    )


def load_sharded_plan(
    plan_dir: str,
    *,
    ranks: Optional[list] = None,
    verify: bool = True,
    load_layout: bool = True,
) -> tuple:
    """Load ``(plan, layout)`` from a v8 sharded-plan directory, reading
    ONLY the requested ranks' shards (checksum-verified on read; the
    ``plan.load`` chaos point fires per shard).  Raises
    :class:`~dgraph_tpu.plan_shards.PlanManifestError` /
    :class:`~dgraph_tpu.plan_shards.PlanShardError` — callers that can
    rebuild (``train.checkpoint.cached_edge_plan``) repair the named
    shard; callers that cannot should surface the structured error.
    ``load_layout=False`` returns ``layout=None`` (the layout sidecar is
    O(E) — per-host shard loading has no use for it)."""
    from dgraph_tpu import plan_shards as ps

    manifest = ps.read_manifest(plan_dir)
    if not manifest.get("complete"):
        raise ps.PlanManifestError(
            ps.manifest_path(plan_dir),
            "build incomplete (resume it with build_edge_plan_sharded)",
        )
    W = manifest["world_size"]
    rank_list = list(range(W)) if ranks is None else [int(r) for r in ranks]
    payloads = {
        r: ps.read_shard(plan_dir, r, manifest["shards"][str(r)], verify=verify)
        for r in rank_list
    }
    plan = assemble_plan(manifest, payloads, rank_list)
    layout = None
    if load_layout:
        lp = ps.read_layout(plan_dir, manifest, verify=verify)
        layout = EdgePlanLayout(
            edge_rank=lp["edge_rank"],
            edge_slot=lp["edge_slot"],
            halo_counts=lp["halo_counts"],
            src_counts=lp["src_counts"],
            dst_counts=lp["dst_counts"],
        )
    return plan, layout


# ---------------------------------------------------------------------------
# Data layout helpers
# ---------------------------------------------------------------------------


def shard_vertex_data(
    x: np.ndarray, counts: np.ndarray, n_pad: int
) -> np.ndarray:
    """[V, ...] global (contiguous-block numbered) -> [W, n_pad, ...] padded."""
    W = len(counts)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    out = np.zeros((W, n_pad) + x.shape[1:], dtype=x.dtype)
    for r in range(W):
        out[r, : counts[r]] = x[offsets[r] : offsets[r + 1]]
    return out


def unshard_vertex_data(x: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """[W, n_pad, ...] -> [V, ...] dropping padding."""
    return np.concatenate([x[r, : counts[r]] for r in range(len(counts))], axis=0)


def reshard_vertex_data(
    x: np.ndarray,
    old_counts: np.ndarray,
    new_index: np.ndarray,
    new_counts: np.ndarray,
    new_n_pad: int,
) -> np.ndarray:
    """Redistribute ``[W, n_pad, ...]`` vertex-sharded data to a different
    world: ``[W', n_pad', ...]``.

    ``new_index`` maps new global vertex id -> old global vertex id (a
    :class:`~dgraph_tpu.partition.Renumbering` ``inv`` — the composition
    across generations when shrinking repeatedly), so rows follow their
    vertex through an arbitrary renumbering.  This is the checkpoint-
    reshard primitive of elastic rank-loss recovery
    (:mod:`dgraph_tpu.train.shrink`): unshard by the old counts, reorder,
    reshard by the new — the padded rows never leak between worlds.
    """
    global_x = unshard_vertex_data(np.asarray(x), old_counts)
    return shard_vertex_data(
        global_x[np.asarray(new_index)], new_counts, int(new_n_pad)
    )


def shard_edge_data(
    vals: np.ndarray, layout: EdgePlanLayout, e_pad: int
) -> np.ndarray:
    """[E, ...] per-edge data (original edge order) -> [W, e_pad, ...] padded."""
    W = layout.src_counts.shape[0]
    out = np.zeros((W, e_pad) + vals.shape[1:], dtype=vals.dtype)
    out[layout.edge_rank, layout.edge_slot] = vals
    return out
