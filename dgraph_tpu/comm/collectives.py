"""Differentiable distributed graph primitives (per-shard, inside shard_map).

TPU-native re-design of the reference's L5 "differentiable comm primitives"
(``DGraph/distributed/haloExchange.py``, ``nccl/_torch_func_impl.py``,
SURVEY.md §1 L5):

- ``HaloExchangeImpl`` (alltoallv by put-offsets, ``haloExchange.py:37-88``)
  ↦ :func:`halo_exchange`: a feature gather + one ``lax.all_to_all`` whose
  received blocks land directly in halo-slot order (no recv scatter needed).
- ``CommPlan_GatherFunction`` (local copy → all_to_all → boundary scatter,
  ``_torch_func_impl.py:27-191``) ↦ :func:`gather`.
- ``CommPlan_ScatterFunction`` (``_torch_func_impl.py:194-352``) ↦
  :func:`scatter_sum`.

No custom_vjp is required: every op here is linear in the data (take,
all_to_all, segment-sum, concat), and JAX's AD transposes them to exactly
the reference's hand-written backward pairs (gather-bwd = scatter-sum with
reversed splits, scatter-bwd = gather; ``_torch_func_impl.py:112-191,282-352``
and ``haloExchange.py:66-88``). The gradient tests in
``tests/test_collectives_grad.py`` pin this against the analytic transpose.

All functions take the PER-SHARD plan (leading [world_size] axis already
split off by shard_map; see :func:`dgraph_tpu.comm.mesh.squeeze_plan`) and an
``axis_name`` (None = single-device, world_size must be 1 — the reference's
SingleProcessDummyCommunicator pattern, ``GraphCast/dist_utils.py:8-39``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from dgraph_tpu.plan import EdgePlan, HaloSpec, resolve_halo_impl
from dgraph_tpu.ops import local as local_ops


# Every collective shows up as a named region in jax.profiler/Perfetto
# traces (canonical alias lives in utils.timing).
from dgraph_tpu.utils.timing import named_scope as _scoped  # noqa: E402

# The children of the ``dgraph.halo_*`` scopes, under every lowering
# (docs/tracing.md has what each one's transposed and rematted form is):
# ``send_gather`` the gather of the rows to send by ``send_idx``; ``wire``
# the collective and nothing else; ``scatter_add`` the sum of received rows
# into the owner's table; ``mask`` the send / receive mask multiplies;
# ``concat`` ``halo_extend``'s concatenate. The per-layer metrics
# ``halo_*_ms.train`` match the names in a device trace.


def _masked_send(x, idx, msk):
    """``x[idx] * msk``: the rows one peer is sent, masked in ``x``'s dtype."""
    with _scoped("send_gather"):
        rows = x[idx]
    with _scoped("mask"):
        return rows * msk[..., None].astype(x.dtype)


def _masked_scatter_add(back, send_idx, send_mask, n_pad):
    """Received rows ``[W, S, F]`` masked and summed into the owner's table
    by ``send_idx``: the reduction every reverse lowering ends in."""
    with _scoped("mask"):
        back = back * send_mask[..., None].astype(back.dtype)
    flat_idx = send_idx.reshape(-1)
    with _scoped("scatter_add"):
        return local_ops.segment_sum(
            back.reshape(flat_idx.shape[0], -1), flat_idx, n_pad)


def resolve_plan_impl(plan: EdgePlan, axis_name) -> str:
    """The halo lowering THIS call site will use — resolved exactly ONCE
    (env pin > adopted tuning record > heuristic; plan.resolve_halo_impl)
    and then threaded as a static ``impl`` argument into every leg of the
    op. The old scheme re-read the config at every trace of every leg, so
    a mid-run flag flip could hand the forward exchange and its transpose
    DIFFERENT lowerings inside one jitted step; resolving once per call
    site makes that impossible."""
    if axis_name is None:
        return "none"
    impl, _ = resolve_halo_impl(
        plan.world_size, plan.halo_deltas,
        overlap_available=getattr(plan, "overlap", None) is not None,
        pair_rows=getattr(plan, "halo_pair_rows", ()),
    )
    return impl


def resolve_plan_wire_format(plan: EdgePlan, axis_name) -> str:
    """The wire format THIS call site will encode halo payloads with —
    resolved exactly ONCE (env pin > adopted tuning record > the plan's
    build-time attachment > fp32 identity;
    :func:`dgraph_tpu.wire.spec.resolve_wire_format`) and threaded as a
    static ``wire_format`` argument into every leg of the op, for the
    same reason :func:`resolve_plan_impl` resolves once: a mid-run flag
    flip must never hand the forward exchange and its transpose
    DIFFERENT codecs inside one jitted step."""
    if axis_name is None:
        return "fp32"
    from dgraph_tpu.wire.spec import resolve_wire_format

    name, _source = resolve_wire_format(
        plan.world_size, tuple(plan.halo_deltas),
        plan_format=getattr(plan, "wire_format", "fp32"),
    )
    return name


def _wire_fns(wire_format, dtype):
    """Raw (encode, decode) for this format at this activation dtype —
    ``(None, None)`` keeps the caller's pre-codec code path byte-for-byte
    unchanged (the fp32 identity guarantee). Only called from inside the
    custom-VJP round executors, whose bodies are opaque to AD; plain-AD
    paths go through the wire-trip wrappers instead (an fp8 payload is a
    uint8 operand, and AD through an integer intermediate silently drops
    the gradient)."""
    if wire_format in (None, "fp32"):
        return None, None
    from dgraph_tpu.wire.codec import make_wire_transform

    return make_wire_transform(wire_format, str(jnp.dtype(dtype)))


def _resolve_halo_arg(impl, deltas, W) -> str:
    """Resolution for call sites that only hold a HaloSpec (no plan):
    ``impl=None`` resolves here; ``deltas=None`` means the caller carries
    no round info, which only the padded all_to_all can lower."""
    if impl is not None:
        return impl
    if deltas is None:
        return "all_to_all"
    impl, _ = resolve_halo_impl(W, tuple(deltas))
    return impl


def overlap_active(plan: EdgePlan, axis_name) -> bool:
    """True when THIS plan on THIS axis lowers its halo exchange as the
    interior/boundary overlap schedule (spec present + resolution says
    so) — the models' routing predicate."""
    return (
        axis_name is not None
        and getattr(plan, "overlap", None) is not None
        and resolve_plan_impl(plan, axis_name) == "overlap"
    )


def shard_map_checks(
    plan: Optional[EdgePlan] = None,
    axis_name=None,
    *,
    impl: Optional[str] = None,
    relax: Optional[str] = None,
) -> dict:
    """THE one source of ``jax.shard_map`` check kwargs — every call site
    in the tree routes through here (enforced by the
    ``no-unchecked-shard-map`` lint rule), so which programs run with the
    vma checker relaxed is a single greppable decision, not a sprinkle of
    raw ``check_vma=False``.

    The decision today: none. Every program — every halo lowering, the
    Pallas kernels (their ``out_shape`` declares its ``vma``), init,
    ring attention — traces with the checker ON, so this returns ``{}``
    for each spelling. The arguments say what a call site knows (its
    ``plan``/``axis_name``, a fixed ``impl``, or the ``relax="<why>"``
    reason it once needed an exemption); a relaxation, should one ever be
    needed again, is added here and nowhere else.
    """
    del plan, axis_name, impl, relax
    return {}


def _overlap_rounds_fwd(x, send_idx, send_mask, axis_name, deltas, W, S,
                        wire_format="fp32"):
    """Double-buffered ppermute rounds: every round's send block is
    gathered up front and every CollectivePermute is issued before any
    received block is placed, so XLA's latency-hiding scheduler is free to
    run independent compute (the interior aggregation the callers
    interleave) while the wire is busy. Result layout and values are
    bit-identical to the padded all_to_all lowering (under the same
    ``wire_format``: each round's masked block is encoded per-row exactly
    as the a2a operand would be)."""
    F = x.shape[-1]
    me = lax.axis_index(axis_name)
    enc, dec = _wire_fns(wire_format, x.dtype)
    sends = []
    for d in deltas:
        peer_row = (me + d) % W
        idx = jnp.take(send_idx, peer_row, axis=0)
        msk = jnp.take(send_mask, peer_row, axis=0)
        blk = _masked_send(x, idx, msk)  # [S, F]
        sends.append(enc(blk) if enc is not None else blk)
    with _scoped("wire"):
        recvs = [
            lax.ppermute(s, axis_name, [(i, (i + d) % W) for i in range(W)])
            for s, d in zip(sends, deltas)
        ]
    out = jnp.zeros((W * S, F), x.dtype)
    for d, recv in zip(deltas, recvs):
        src_rank = (me - d) % W
        if dec is not None:
            recv = dec(recv)
        out = lax.dynamic_update_slice(out, recv, (src_rank * S, 0))
    return out


def _overlap_rounds_rev(h, send_idx, send_mask, n_pad, axis_name, deltas, W, S,
                        wire_format="fp32"):
    """Reverse of :func:`_overlap_rounds_fwd`: all reverse ppermutes are
    issued up front; the returned blocks are then placed into one [W, S]
    buffer and reduced with the SAME masked flat segment-sum the
    all_to_all path uses — so values are bit-identical to it, while the
    rounds themselves stay individually overlappable. The returning
    cotangent blocks ride the wire encoded with the same format as the
    forward payloads (decode happens BEFORE the mask-and-reduce, so the
    accumulation runs at the activation dtype)."""
    F = h.shape[-1]
    me = lax.axis_index(axis_name)
    enc, dec = _wire_fns(wire_format, h.dtype)
    h = h.reshape(W * S, F)
    blocks = []
    for d in deltas:
        src_rank = (me - d) % W
        blk = lax.dynamic_slice(h, (src_rank * S, 0), (S, F))
        blocks.append(enc(blk) if enc is not None else blk)
    with _scoped("wire"):
        recvs = [
            lax.ppermute(b, axis_name, [(i, (i - d) % W) for i in range(W)])
            for b, d in zip(blocks, deltas)
        ]
    back = jnp.zeros((W, S, F), h.dtype)
    for d, recv in zip(deltas, recvs):
        peer_row = (me + d) % W
        if dec is not None:
            recv = dec(recv)
        back = lax.dynamic_update_slice(back, recv[None], (peer_row, 0, 0))
    return _masked_scatter_add(back, send_idx, send_mask, n_pad)


@functools.lru_cache(maxsize=None)
def _make_overlap_pair(axis_name, deltas, W, S, n_pad, wire_format="fp32",
                       dtype_name="float32"):
    """The overlap exchange/unexchange custom-VJP pair. Mirrors the
    existing gather/scatter adjoint structure: the exchange's backward IS
    the reverse rounds (halo values delivered back to their owners) and
    the reverse's backward IS the forward rounds — pinned explicitly so
    the transpose keeps the double-buffered round schedule (JAX's default
    transpose would serialize placement chains) and keeps the masked
    segment-sum on the fast wrapper paths. The cache key carries the
    (static) wire format + activation dtype, so two configurations never
    share an executor — and because these bodies are opaque to AD, the
    codec's integer payloads (fp8) are safe inside them."""

    @jax.custom_vjp
    def exchange(x, send_idx, send_mask):
        return _overlap_rounds_fwd(x, send_idx, send_mask, axis_name, deltas,
                                   W, S, wire_format)

    def ex_fwd(x, send_idx, send_mask):
        return exchange(x, send_idx, send_mask), (send_idx, send_mask)

    def ex_bwd(res, g):
        send_idx, send_mask = res
        dx = _overlap_rounds_rev(
            g, send_idx, send_mask, n_pad, axis_name, deltas, W, S,
            wire_format)
        return dx, None, None

    exchange.defvjp(ex_fwd, ex_bwd)

    @jax.custom_vjp
    def unexchange(h, send_idx, send_mask):
        return _overlap_rounds_rev(
            h, send_idx, send_mask, n_pad, axis_name, deltas, W, S,
            wire_format)

    def un_fwd(h, send_idx, send_mask):
        return unexchange(h, send_idx, send_mask), (send_idx, send_mask)

    def un_bwd(res, g):
        send_idx, send_mask = res
        dh = _overlap_rounds_fwd(g, send_idx, send_mask, axis_name, deltas,
                                 W, S, wire_format)
        return dh, None, None

    unexchange.defvjp(un_fwd, un_bwd)
    return exchange, unexchange


@_scoped("dgraph.halo_exchange_overlap")
def halo_exchange_overlap(
    x: jax.Array,
    halo: HaloSpec,
    axis_name: Optional[str],
    deltas: tuple,
    wire_format: str = "fp32",
) -> jax.Array:
    """:func:`halo_exchange` lowered as double-buffered ppermute rounds
    built for compute–communication overlap: all sends are gathered and
    all rounds issued before any receive is consumed, so interior work
    scheduled between this call and the first use of its result hides the
    wire time (the redistribution-as-overlappable-rounds strategy of
    arxiv 2112.01075). Values are bit-identical to the all_to_all
    lowering; the custom VJP is the mirrored reverse-round schedule."""
    W, S = halo.send_idx.shape[0], halo.s_pad
    if axis_name is None or not deltas:
        return halo_exchange(x, halo, axis_name, deltas=deltas, impl="none")
    ex, _ = _make_overlap_pair(axis_name, tuple(deltas), W, S, x.shape[0],
                               wire_format, str(jnp.dtype(x.dtype)))
    return ex(x, halo.send_idx, halo.send_mask)


@_scoped("dgraph.halo_scatter_sum_overlap")
def halo_scatter_sum_overlap(
    h: jax.Array,
    halo: HaloSpec,
    n_pad: int,
    axis_name: Optional[str],
    deltas: tuple,
    wire_format: str = "fp32",
) -> jax.Array:
    """:func:`halo_scatter_sum` lowered as double-buffered reverse
    ppermute rounds (the overlap pair's transpose): issue every reverse
    round first, reduce after — the caller's interior aggregation runs
    while the rounds are in flight. Bit-identical to the all_to_all
    reverse path (same masked flat segment-sum over the same buffer)."""
    W, S = halo.send_idx.shape[0], halo.s_pad
    if axis_name is None or not deltas:
        return halo_scatter_sum(h, halo, n_pad, axis_name, deltas=deltas,
                                impl="none")
    _, unex = _make_overlap_pair(axis_name, tuple(deltas), W, S, n_pad,
                                 wire_format, str(jnp.dtype(h.dtype)))
    return unex(h, halo.send_idx, halo.send_mask)


@_scoped("dgraph.halo_exchange")
def halo_exchange(
    x: jax.Array,
    halo: HaloSpec,
    axis_name: Optional[str],
    deltas: Optional[tuple] = None,
    impl: Optional[str] = None,
    wire_format: Optional[str] = None,
) -> jax.Array:
    """Exchange boundary vertex features; returns the halo buffer.

    Several lowerings, same result layout and values:
    - all_to_all (default): one padded collective; received block from peer
      p lands at rows ``[p*S, (p+1)*S)`` — exactly the plan's halo-slot
      numbering, no receive-placement pass.
    - ppermute neighbor rounds (when ``deltas`` — the static set of rank
      offsets with traffic — is sparse): one CollectivePermute per delta,
      skipping empty peer pairs entirely (SURVEY §7 "ppermute rounds only
      to actual neighbors"; the NVSHMEM one-sided put analogue).
    - overlap: the double-buffered round schedule
      (:func:`halo_exchange_overlap`).

    Args:
      x: [n_pad, F] local (padded) vertex features of this shard.
      halo: per-shard spec; send_idx [W, S], send_mask [W, S].
      axis_name: mesh axis to exchange over, or None (single device).
      deltas: static tuple of active (peer-rank) mod W offsets
        (``EdgePlan.halo_deltas``); None disables the round-based paths.
      impl: the lowering, already resolved by the CALLER (one resolution
        per call site — see :func:`resolve_plan_impl`); None resolves
        here for direct/legacy callers.
      wire_format: the payload codec (dgraph_tpu.wire), already resolved
        by the CALLER like ``impl`` (one resolution per call site — see
        :func:`resolve_plan_wire_format`). None = 'fp32' identity, which
        leaves every lowering's program literally unchanged.
    """
    F = x.shape[-1]
    W, S = halo.send_idx.shape[0], halo.s_pad
    wf = wire_format or "fp32"
    if axis_name is not None and deltas is not None and len(deltas) == 0:
        # no live cross-rank traffic anywhere in the mesh (send_mask is
        # all-zero): the exchange is identically zero, so skip the padded
        # collective entirely — this is what makes the resolver's
        # 'none' verdict (and obs.footprint's 0-byte accounting) truthful
        return jnp.zeros((W * S, F), x.dtype)
    if axis_name is None:
        # mask in x's dtype: the plan stores send_mask as f32, and a raw
        # multiply silently upcasts a bf16 stream — which then upcasts the
        # halo_extend concat and EVERY downstream [E, F] tensor of the
        # layer (caught in the r4 TPU export: the whole edge pipeline ran
        # f32 and the scatter kernel picked its "highest" precision path)
        send = _masked_send(x, halo.send_idx, halo.send_mask)
        return send.reshape(-1, F)  # world size 1: mask is all-zero
    impl = _resolve_halo_arg(impl, deltas, W)
    if impl == "overlap":
        return halo_exchange_overlap(x, halo, axis_name, tuple(deltas), wf)
    if impl == "ppermute":
        from dgraph_tpu.wire.codec import make_ppermute_codec

        me = lax.axis_index(axis_name)
        out = jnp.zeros((W * S, F), x.dtype)
        for d in deltas:
            peer_row = (me + d) % W
            idx = jnp.take(halo.send_idx, peer_row, axis=0)
            msk = jnp.take(halo.send_mask, peer_row, axis=0)
            send = _masked_send(x, idx, msk)  # [S, F]
            perm = tuple((i, (i + d) % W) for i in range(W))
            # trip = decode(ppermute(encode(.))) wrapped in a custom VJP
            # (the fp8 payload is uint8 — plain AD would drop the
            # cotangent); None = identity format, plain ppermute
            trip = make_ppermute_codec(axis_name, perm, wf,
                                       str(jnp.dtype(x.dtype)))
            if trip is None:
                with _scoped("wire"):
                    recv = lax.ppermute(send, axis_name, list(perm))
            else:
                recv = trip(send)  # opens ``wire`` around its collective
            src_rank = (me - d) % W
            out = lax.dynamic_update_slice(out, recv, (src_rank * S, 0))
        return out
    from dgraph_tpu.wire.codec import make_a2a_codec

    send = _masked_send(x, halo.send_idx, halo.send_mask)
    trip = make_a2a_codec(axis_name, wf, str(jnp.dtype(x.dtype)))
    if trip is None:
        with _scoped("wire"):
            recv = lax.all_to_all(send, axis_name, split_axis=0,
                                  concat_axis=0)
    else:
        recv = trip(send)
    return recv.reshape(-1, F)


@_scoped("dgraph.halo_scatter_sum")
def halo_scatter_sum(
    h: jax.Array,
    halo: HaloSpec,
    n_pad: int,
    axis_name: Optional[str],
    deltas: Optional[tuple] = None,
    impl: Optional[str] = None,
    wire_format: Optional[str] = None,
) -> jax.Array:
    """Linear transpose of :func:`halo_exchange`: deliver halo-slot values
    back to their owner ranks and sum into local vertices.

    This is the reference's halo-exchange backward (reversed put offsets,
    ``haloExchange.py:66-88``) and the boundary leg of
    ``CommPlan_ScatterFunction.forward`` (``_torch_func_impl.py:194-280``).

    Args:
      h: [W*S, F] halo-buffer values on this shard.
      impl: the lowering, resolved once by the caller (see
        :func:`resolve_plan_impl`); None resolves here.
      wire_format: payload codec, resolved by the caller like ``impl``
        (:func:`resolve_plan_wire_format`); None = fp32 identity.
    Returns: [n_pad, F] per-local-vertex sums.
    """
    W, S = halo.send_idx.shape[0], halo.s_pad
    F = h.shape[-1]
    wf = wire_format or "fp32"
    if axis_name is not None and deltas is not None and len(deltas) == 0:
        # transpose of the empty exchange: no halo slot maps anywhere
        return jnp.zeros((n_pad, F), h.dtype)
    if axis_name is not None:
        impl = _resolve_halo_arg(impl, deltas, W)
        if impl == "overlap":
            return halo_scatter_sum_overlap(h, halo, n_pad, axis_name,
                                            tuple(deltas), wf)
        if impl == "ppermute":
            from dgraph_tpu.wire.codec import make_ppermute_codec

            me = lax.axis_index(axis_name)
            out = jnp.zeros((n_pad, F), h.dtype)
            for d in deltas:
                # my halo rows from rank (me-d) go back to their owner
                # (me-d); I receive my own vertices' partials from (me+d)
                src_rank = (me - d) % W
                block = lax.dynamic_slice(
                    h.reshape(W * S, F), (src_rank * S, 0), (S, F))
                perm = tuple((i, (i - d) % W) for i in range(W))
                trip = make_ppermute_codec(axis_name, perm, wf,
                                           str(jnp.dtype(h.dtype)))
                if trip is None:
                    with _scoped("wire"):
                        recv = lax.ppermute(block, axis_name, list(perm))
                else:
                    recv = trip(block)  # from (me+d)
                peer_row = (me + d) % W
                idx = jnp.take(halo.send_idx, peer_row, axis=0)
                msk = jnp.take(halo.send_mask, peer_row, axis=0)
                with _scoped("mask"):
                    recv = recv * msk[..., None].astype(h.dtype)
                with _scoped("scatter_add"):
                    out = out + local_ops.segment_sum(recv, idx, n_pad)
            return out
    h = h.reshape(W, S, F)
    if axis_name is None:
        back = h
    else:
        from dgraph_tpu.wire.codec import make_a2a_codec

        trip = make_a2a_codec(axis_name, wf, str(jnp.dtype(h.dtype)))
        if trip is None:
            with _scoped("wire"):
                back = lax.all_to_all(h, axis_name, split_axis=0,
                                      concat_axis=0)
        else:
            # cotangent rows ride the wire encoded UNMASKED (the mask
            # applies after decode, below) — same ordering as every
            # round-based reverse lowering, so wire bytes stay identical
            back = trip(h)
    return _masked_scatter_add(back, halo.send_idx, halo.send_mask, n_pad)


def _side_index(plan: EdgePlan, side: str) -> jax.Array:
    return plan.src_index if side == "src" else plan.dst_index


def _side_npad(plan: EdgePlan, side: str) -> int:
    return plan.n_src_pad if side == "src" else plan.n_dst_pad


def _chunk_slices(width: int, chunk: Optional[int]) -> list:
    from dgraph_tpu import config as _cfg

    cb = chunk or _cfg.gather_col_block or width
    return [slice(j, min(j + cb, width)) for j in range(0, width, cb)]


def _concat_chunks(outs: list) -> jax.Array:
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


def map_feature_chunks(fn, width: int, chunk: Optional[int] = None):
    """Scaffold of the feature-chunked edge pipeline (models/gcn.py
    rationale): apply ``fn(slice)`` over <=chunk-wide feature slices and
    concat the results on the last axis. ``chunk`` defaults to
    ``config.gather_col_block``. Callers are responsible for the gates
    (feature-separable per-edge math, collective-free per-chunk ops —
    pair with :func:`halo_extend` + :func:`local_take`). The chunks are
    independent expressions: the compiler may run them in any order. A
    call site whose chunk ends in a vertex-level reduction wants
    :func:`map_vertex_chunks`."""
    return _concat_chunks([fn(sl) for sl in _chunk_slices(width, chunk)])


def map_vertex_chunks(fn, tables, chunk: Optional[int] = None):
    """:func:`map_feature_chunks` for a pipeline whose chunk result is
    vertex-level (``fn(*cols) -> [N, chunk]``: take -> edge math ->
    segment-sum), with the chunks run ONE AFTER THE OTHER where that can
    pay: chunk j+1's column slices of ``tables`` wait on chunk j's result
    (an ``optimization_barrier``: same values, same bits, forward only).

    Why: a chunk is also the unit of on-chip placement. The compiler
    keeps a row gather's table in on-chip memory when it fits (from
    there a row costs 1.85 ns, from HBM 10.6), and as independent
    expressions all of a layer's ``[N, chunk]`` tables are written by one
    fusion and want their place through all of its gathers, so only one
    of gcn_arxiv's two 43 MB tables was placed. Ordered, a table needs
    its place through its own gather only (the compiler moves it on chip
    once the previous chunk is done) and every one is placed. Waiting on
    an ``[N, chunk]`` token is free; an ``[E, chunk]`` chunk result
    (GraphCast, edge attention) would be held by it — those call sites
    stay on :func:`map_feature_chunks`.

    Ordering is not free: XLA masks a layer's independent chunks in one
    fusion and ordered chunks in one each (+1.5 ms a layer at 2.3 M
    edges). So a chunk is tied only where its largest table slice can be
    gathered from on-chip memory, whole or in the row parts
    ``ops.local.row_take`` takes it in (``ops.local.on_chip_row_parts``:
    gcn_papers100m.w4's 207 MB slices, in two; PERF.md, PR 31 and PR 35);
    chunks whose tables cannot be placed anyway (an ``[E, chunk]`` edge
    tensor) stay independent."""
    from dgraph_tpu.obs.metrics import default_registry

    out = []
    for sl in _chunk_slices(tables[0].shape[-1], chunk):
        cols = tuple(t[:, sl] for t in tables)
        largest = max(cols, key=lambda c: c.size * c.dtype.itemsize)
        if out and local_ops.on_chip_row_parts(
                largest.shape[0],
                largest.shape[-1] * largest.dtype.itemsize):
            cols = local_ops.run_after(out[-1], cols)
            default_registry.counter("gather.chunks_sequenced")
        out.append(fn(*cols))
    return _concat_chunks(out)


@_scoped("dgraph.halo_extend")
def halo_extend(
    x: jax.Array, plan: EdgePlan, side: str, axis_name: Optional[str],
    impl: Optional[str] = None,
) -> jax.Array:
    """The COMMUNICATION half of :func:`gather`: one full-width halo
    exchange producing the extended vertex table ``local_take`` indexes
    into ([n_pad + W*S, F] on the halo side; ``x`` unchanged elsewhere).

    Split out so feature-chunked edge pipelines (models/gcn.py) can pay
    the cross-rank exchange ONCE per layer at full width and chunk only
    the local take — chunking through plain ``gather`` would re-issue the
    all_to_all per 128-wide slice.
    """
    if side != plan.halo_side:
        return x
    if impl is None and axis_name is not None:
        impl = resolve_plan_impl(plan, axis_name)
    haloed = halo_exchange(x, plan.halo, axis_name, deltas=plan.halo_deltas,
                           impl=impl,
                           wire_format=resolve_plan_wire_format(
                               plan, axis_name))
    with _scoped("concat"):
        return jnp.concatenate([x, haloed], axis=0)


def _side_rows(full: jax.Array, plan: EdgePlan, side: str,
               oob: str = "fill") -> jax.Array:
    """The row-taking half of :func:`local_take`: row ``side``-index[e] of
    the (already halo-extended) vertex table an edge slot, unmasked. A
    padded slot reads what its id says: row 0 on the halo side, a zero row
    on the owner side, whose ``n_owner_pad`` is out of range.
    ``oob="clamp"`` is for a caller whose ids are all in range:
    ``ops.local.row_take``'s plain indexing, without the select over the
    rows by which ``"fill"`` zeroes an out-of-range one."""
    from dgraph_tpu import config as _cfg

    idx = _side_index(plan, side)
    if side == plan.halo_side:
        # halo-side ids are NOT monotone (local rows then halo slots); the
        # plan's sorting permutation still gives the VJP a sorted
        # segment-sum path (gather-by-perm first) when present
        if plan.halo_sort_perm is not None:
            return local_ops.take_rows_sort_route(
                full, idx, plan.halo_sort_perm, plan.halo_sorted_ids,
                pallas_hints=(
                    plan.scatter_block_e, plan.scatter_block_n, plan.halo_sort_mc
                ),
                oob=oob,
            )
        sorted_ids = False
    else:
        # owner-side ids are plan-sorted; route the VJP (a scatter-sum
        # transpose, _torch_func_impl.py:112-191) through the sorted path
        sorted_ids = plan.ids_sorted(side)
    hints = (
        (plan.scatter_block_e, plan.scatter_block_n, plan.scatter_mc)
        if (sorted_ids and _cfg.pallas_scatter_enabled())
        else None
    )
    return local_ops.take_rows(
        full, idx, indices_are_sorted=sorted_ids, pallas_hints=hints,
        gather_mv=plan.gather_mv, oob=oob,
    )


@_scoped("dgraph.local_take")
def local_take(full: jax.Array, plan: EdgePlan, side: str) -> jax.Array:
    """The LOCAL half of :func:`gather`: per-edge rows taken from the
    (already halo-extended) vertex table. No collectives; masked edges are
    zero."""
    taken = _side_rows(full, plan, side)
    with _scoped("mask"):
        return taken * plan.edge_mask[:, None].astype(full.dtype)


@_scoped("dgraph.local_take")
def _local_take_unmasked(full: jax.Array, plan: EdgePlan, side: str) -> jax.Array:
    """:func:`local_take` without its ``[E, F]`` pass after the gather, for
    the one caller whose aggregation drops a padded edge by its ID
    (:func:`take_scatter_bias_relu`; the property of every ``EdgePlan`` it
    rests on is stated at ``EdgePlan.halo_sort_perm``). That pass is ONE
    fusion of two things (``select_multiply_fusion`` in the compiled
    module): the edge-mask multiply and the select by which an
    out-of-range id reads a zero row. Neither is made here for the halo
    side, whose ids are all in range (a padded slot's is 0, so its row is
    table row 0, not zeros); the owner side's padded id is out of range and
    keeps its select."""
    return _side_rows(full, plan, side,
                      oob="clamp" if side == plan.halo_side else "fill")


@_scoped("dgraph.gather")
def gather(
    x: jax.Array, plan: EdgePlan, side: str, axis_name: Optional[str]
) -> jax.Array:
    """Per-edge features gathered from one endpoint side.

    Parity: ``Communicator.gather`` / ``CommPlan_GatherFunction``
    (``_torch_func_impl.py:27-110``): local vertex→edge copy + boundary
    all_to_all + received-row placement. Here the non-halo side is a pure
    local take; the halo side prepends one halo exchange
    (= :func:`halo_extend` then :func:`local_take`).

    Args:
      x: [n_pad, F] per-shard vertex features for that side's vertex set.
    Returns: [e_pad, F] per-edge features (masked edges are zero).
    """
    return local_take(halo_extend(x, plan, side, axis_name), plan, side)


@_scoped("dgraph.scatter_sum")
def scatter_sum(
    edata: jax.Array, plan: EdgePlan, side: str, axis_name: Optional[str]
) -> jax.Array:
    """Sum per-edge values into that side's vertices (cross-rank aware).

    Parity: ``Communicator.scatter`` / ``CommPlan_ScatterFunction``
    (``_torch_func_impl.py:194-280``). TPU has no remote atomics (the NVSHMEM
    backend's CAS scatter-add, ``nvshmem_comm_kernels.cuh:17-54``), so the
    remote leg is: local segment-sum into halo slots (pre-aggregation per
    unique remote vertex — the reference's dedup does the same,
    ``_NCCLCommPlan.py:221-226``) → reverse all_to_all → local segment-sum.

    Args:
      edata: [e_pad, F] per-edge values.
    Returns: [n_pad, F] per-vertex sums for the requested side.
    """
    # mask in the activation dtype — a f32 mask would silently upcast bf16
    # edge tensors (and disable the bf16 kernel fast path below)
    edata = edata * plan.edge_mask[:, None].astype(edata.dtype)
    idx = _side_index(plan, side)
    n_pad = _side_npad(plan, side)
    if side != plan.halo_side:
        # owner-side aggregation: plan-sorted monotone segment ids ride the
        # shared Pallas-or-jnp dispatch (kill switch + precision policy in
        # ONE place: ops.local.sorted_segment_sum_any)
        if plan.ids_sorted(side):
            return local_ops.sorted_segment_sum_any(
                edata, idx, n_pad, plan.scatter_block_e, plan.scatter_block_n,
                plan.scatter_mc, gather_mv=plan.gather_mv,
            )
        return local_ops.segment_sum(edata, idx, n_pad, indices_are_sorted=False)
    # halo-side scatter: resolve the lowering ONCE for both legs (the slot
    # reduction's shape and the reverse collective must agree)
    impl = resolve_plan_impl(plan, axis_name) if axis_name is not None else None
    if impl == "overlap":
        return _scatter_sum_overlap(edata, plan, side, axis_name)
    W = plan.world_size
    n_full = n_pad + W * plan.halo.s_pad
    if plan.halo_sort_perm is not None:
        # unsorted halo-side ids, but the plan's sorting permutation turns
        # the forward into gather-by-perm + sorted segment-sum (Pallas MXU)
        full = local_ops.segment_sum_sort_route(
            edata, idx, plan.halo_sort_perm, plan.halo_sorted_ids, n_full,
            pallas_hints=(
                plan.scatter_block_e, plan.scatter_block_n, plan.halo_sort_mc
            ),
        )
    else:
        full = local_ops.segment_sum(edata, idx, n_full)
    local_part = full[:n_pad]
    remote_part = full[n_pad:]
    return local_part + halo_scatter_sum(
        remote_part, plan.halo, n_pad, axis_name, deltas=plan.halo_deltas,
        impl=impl,
        wire_format=resolve_plan_wire_format(plan, axis_name),
    )


# ---------------------------------------------------------------------------
# Interior/boundary split ops (the compute–communication-overlap hot path)
# ---------------------------------------------------------------------------


def _overlap_spec(plan: EdgePlan):
    ov = getattr(plan, "overlap", None)
    if ov is None:
        raise ValueError(
            "plan carries no interior/boundary split; build it with "
            "build_edge_plan(overlap=True) (or adopt a tuning record whose "
            "halo_impl is 'overlap' before building)"
        )
    return ov


def _interior_chunks(n_deltas: int) -> int:
    """How many edge-axis chunks the interior aggregation splits into so
    individual pieces interleave with the boundary rounds. Default 1 (one
    sorted segment-sum — XLA can already overlap a single independent op
    with the in-flight rounds, and chunk partial-sums regroup float adds,
    breaking bit-parity with the serial path); raise
    ``config.overlap_interior_chunks`` / DGRAPH_TPU_OVERLAP_CHUNKS for
    finer-grained hiding once on-chip traces justify it."""
    from dgraph_tpu import config as _cfg

    c = getattr(_cfg, "overlap_interior_chunks", 1)
    return max(1, min(int(c) if c else 1, max(n_deltas, 1)))


@_scoped("dgraph.interior_take")
def interior_take(x: jax.Array, plan: EdgePlan, side: str) -> jax.Array:
    """Per-edge rows of the INTERIOR subset, taken from the local vertex
    table only — by construction no interior edge references a halo slot,
    so this op is collective-free and independent of the in-flight
    boundary exchange. Padded subset slots produce zero rows."""
    ov = _overlap_spec(plan)
    idx = ov.side("interior", side)
    sorted_ids = side != plan.halo_side and plan.ids_sorted(side)
    return local_ops.take_rows(x, idx, indices_are_sorted=sorted_ids)


@_scoped("dgraph.boundary_take")
def boundary_take(x_or_halo: jax.Array, plan: EdgePlan, side: str) -> jax.Array:
    """Per-edge rows of the BOUNDARY subset. On the halo side, ``x_or_halo``
    is the [W*S, F] halo buffer returned by
    :func:`halo_exchange_overlap` (boundary halo-side indices are rebased
    into it — no ``[local ; halo]`` concat is ever materialized); on the
    owner side it is the local vertex table."""
    ov = _overlap_spec(plan)
    idx = ov.side("boundary", side)
    sorted_ids = side != plan.halo_side and plan.ids_sorted(side)
    return local_ops.take_rows(x_or_halo, idx, indices_are_sorted=sorted_ids)


def _subset_owner_sum(edata, plan, ov, side, which, chunks=1):
    """Owner-side segment-sum of one subset's per-edge rows (monotone ids
    — subsets preserve the plan's owner-sorted order), optionally split
    into edge-axis chunks whose partial sums interleave with the boundary
    rounds in the schedule."""
    ids = ov.side(which, side)
    n_pad = _side_npad(plan, side)
    mc = ov.interior_mc if which == "interior" else ov.boundary_mc
    if not plan.ids_sorted(side):
        return local_ops.segment_sum(edata, ids, n_pad, indices_are_sorted=False)
    E = edata.shape[0]
    if chunks <= 1 or E < 2 * chunks:
        return local_ops.sorted_segment_sum_any(
            edata, ids, n_pad, plan.scatter_block_e, plan.scatter_block_n, mc
        )
    step = -(-E // chunks)
    out = None
    for j in range(0, E, step):
        part = local_ops.sorted_segment_sum_any(
            edata[j : j + step], ids[j : j + step], n_pad,
            plan.scatter_block_e, plan.scatter_block_n, mc,
        )
        out = part if out is None else out + part
    return out


@_scoped("dgraph.interior_scatter_sum")
def interior_scatter_sum(
    edata_int: jax.Array, plan: EdgePlan, side: str, chunks: Optional[int] = None
) -> jax.Array:
    """Sum INTERIOR per-edge rows into ``side``'s vertices. On the owner
    side this is the sorted fast path, chunked so the pieces interleave
    with the in-flight boundary rounds; on the halo side ids are local
    rows (interior edges never touch halo slots)."""
    ov = _overlap_spec(plan)
    if side == plan.halo_side:
        return local_ops.segment_sum(
            edata_int, ov.side("interior", side), _side_npad(plan, side),
            indices_are_sorted=False,
        )
    if chunks is None:
        chunks = _interior_chunks(len(plan.halo_deltas))
    return _subset_owner_sum(edata_int, plan, ov, side, "interior", chunks)


@_scoped("dgraph.boundary_scatter_sum")
def boundary_scatter_sum(
    edata_bnd: jax.Array, plan: EdgePlan, side: str
) -> jax.Array:
    """Sum BOUNDARY per-edge rows into ``side``'s OWNER vertices (the
    merge step after the exchange lands). Halo-side boundary ids are halo
    slots, not local vertices — scatter those through
    :func:`scatter_sum_overlap`, which runs the reverse rounds."""
    ov = _overlap_spec(plan)
    if side == plan.halo_side:
        raise ValueError(
            "boundary_scatter_sum targets the owner side; halo-side "
            "boundary scatters need the reverse exchange — use "
            "scatter_sum_overlap (or scatter_sum, which dispatches there)"
        )
    return _subset_owner_sum(edata_bnd, plan, ov, side, "boundary", chunks=1)


def overlap_edge_weight(
    edge_weight: Optional[jax.Array], plan: EdgePlan
) -> tuple:
    """Split a [e_pad] per-edge weight vector into its (interior,
    boundary) subsets (padded slots -> 0). Returns (None, None) when
    there is no weight."""
    if edge_weight is None:
        return None, None
    ov = _overlap_spec(plan)
    w_int = jnp.take(edge_weight, ov.int_epos, mode="fill", fill_value=0)
    w_bnd = jnp.take(edge_weight, ov.bnd_epos, mode="fill", fill_value=0)
    return w_int, w_bnd


@_scoped("dgraph.gather_scatter_overlap")
def gather_scatter_overlap(
    x_local: jax.Array,
    halo_buf: jax.Array,
    plan: EdgePlan,
    edge_weight: Optional[jax.Array] = None,
) -> jax.Array:
    """Fused neighbor aggregation ``out[v] = Σ_e w_e · x[halo-side endpoint
    of e]`` into the OWNER side, overlap-scheduled: interior edges read the
    local table ``x_local`` (independent of the exchange), boundary edges
    read the in-flight ``halo_buf`` from :func:`halo_exchange_overlap`, and
    the two partials merge at the end — the SAGE/GCN identity-message hot
    path with the collective hidden behind the interior work."""
    ov = _overlap_spec(plan)
    owner = "dst" if plan.halo_side == "src" else "src"
    w_int, w_bnd = overlap_edge_weight(edge_weight, plan)
    m_int = interior_take(x_local, plan, plan.halo_side)
    if w_int is not None:
        m_int = m_int * w_int[:, None].astype(m_int.dtype)
    agg_int = interior_scatter_sum(m_int, plan, owner)
    m_bnd = boundary_take(halo_buf, plan, plan.halo_side)
    if w_bnd is not None:
        m_bnd = m_bnd * w_bnd[:, None].astype(m_bnd.dtype)
    return agg_int + boundary_scatter_sum(m_bnd, plan, owner)


@_scoped("dgraph.scatter_sum_overlap")
def _scatter_sum_overlap(
    edata: jax.Array, plan: EdgePlan, side: str, axis_name: Optional[str]
) -> jax.Array:
    """Halo-side :func:`scatter_sum` under the overlap schedule: the
    boundary subset is pre-reduced into halo slots and handed to the
    reverse ppermute rounds (:func:`halo_scatter_sum_overlap`) FIRST; the
    interior subset (local-vertex targets) aggregates while they fly;
    local and returned remote partials merge last. The VJP composes the
    building blocks' pinned transposes — takes transpose to segment-sums
    and the reverse rounds to the forward rounds — mirroring the
    gather/scatter adjoint pair. ``edata`` must already be edge-masked
    (the public :func:`scatter_sum` wrapper does this).

    Values against the serial path: bit-identical at W = 2; the same
    terms, regrouped, beyond. Each piece taken alone (the interior sum,
    the slot partials, the reverse rounds' masked flat segment-sum) is
    bit-identical to its serial twin; what differs is the last merge. A
    vertex's sum is its local partial plus one returned partial per
    delta, and once the op is compiled XLA folds that ``+`` into the
    segment-sum's accumulation differently for the two programs, so the
    partial sums over deltas are added in another order than the serial
    path's slots. With one delta there is one order; with more, float32
    results differ by rounding (≤ 1e-6 on sums of O(1) terms; bounded by
    ``2 u (n-1) Σ|terms|``, which ``tests/test_overlap.py`` holds it to).
    The gather, its gradient and this op's gradient only move rows or
    run the same reduction, and stay bit-identical at every W."""
    ov = _overlap_spec(plan)
    n_pad = _side_npad(plan, side)
    W, S = plan.world_size, plan.halo.s_pad
    # boundary leg first: rows -> slot partials -> reverse rounds
    bnd_rows = local_ops.take_rows(edata, ov.bnd_epos)
    slot_sums = local_ops.segment_sum(
        bnd_rows, ov.side("boundary", side), W * S, indices_are_sorted=False
    )
    remote = halo_scatter_sum_overlap(
        slot_sums, plan.halo, n_pad, axis_name, tuple(plan.halo_deltas),
        resolve_plan_wire_format(plan, axis_name),
    )
    # interior leg while the rounds are in flight
    int_rows = local_ops.take_rows(edata, ov.int_epos)
    interior = local_ops.segment_sum(
        int_rows, ov.side("interior", side), n_pad, indices_are_sorted=False
    )
    return interior + remote


def scatter_sum_overlap(
    edata: jax.Array, plan: EdgePlan, side: str, axis_name: Optional[str]
) -> jax.Array:
    """Public spelling of the overlap halo-side scatter (masks ``edata``
    like :func:`scatter_sum` does, then runs the overlap schedule)."""
    edata = edata * plan.edge_mask[:, None].astype(edata.dtype)
    if side != plan.halo_side:
        raise ValueError(
            "scatter_sum_overlap is the HALO-side scatter; owner-side "
            "aggregation has no collective to overlap — use scatter_sum "
            "(or interior/boundary_scatter_sum for split streams)"
        )
    return _scatter_sum_overlap(edata, plan, side, axis_name)


@_scoped("dgraph.scatter_bias_relu_overlap")
def scatter_bias_relu_overlap(
    stream_local: jax.Array,  # [n_halo_pad, F] halo-side stream (local table)
    halo_buf: jax.Array,  # [W*S, F] in-flight exchange output
    bias: jax.Array,  # [n_owner_pad, F] owner-side vertex operand
    plan: EdgePlan,
    side: str,  # owner side to aggregate into
    axis_name: Optional[str],
    edge_weight: Optional[jax.Array] = None,  # [e_pad]
) -> jax.Array:
    """Overlap-scheduled :func:`scatter_bias_relu`: the fused
    Σ w·relu(stream + bias) aggregation runs once over the interior subset
    (reading only local rows — free to execute while the boundary rounds
    fly) and once over the boundary subset (reading the landed halo
    buffer), merging at the end. Exact same math as the unsplit op: relu
    is per-edge and the aggregation is a sum over a partitioned edge set."""
    ov = _overlap_spec(plan)
    n_pad = _side_npad(plan, side)
    bias = bias.astype(stream_local.dtype)
    w_int, w_bnd = overlap_edge_weight(edge_weight, plan)
    int_rows = interior_take(stream_local, plan, plan.halo_side)
    a = local_ops.sorted_segment_sum_bias_relu_any(
        int_rows, ov.side("interior", side), bias, n_pad,
        plan.scatter_block_e, plan.scatter_block_n, ov.interior_mc,
        edge_weight=w_int,
    )
    bnd_rows = boundary_take(halo_buf, plan, plan.halo_side)
    b = local_ops.sorted_segment_sum_bias_relu_any(
        bnd_rows, ov.side("boundary", side), bias, n_pad,
        plan.scatter_block_e, plan.scatter_block_n, ov.boundary_mc,
        edge_weight=w_bnd,
    )
    return a + b


@_scoped("dgraph.scatter_bias_relu")
def scatter_bias_relu(
    edata: jax.Array,  # [e_pad, F] per-edge stream (e.g. gathered src proj)
    bias: jax.Array,  # [n_pad, F] owner-side vertex operand
    plan: EdgePlan,
    side: str,
    axis_name: Optional[str],
    edge_weight: Optional[jax.Array] = None,  # [e_pad]
) -> jax.Array:
    """Fused owner-side aggregation: out[v] = Σ_e w_e · relu(edata_e + bias_v).

    Parity: the reference's fused scatter kernels
    (``Fused_ReLU_Scatter_Kernel`` / ``Fused_Sum_Norm_Scatter_Kernel``,
    ``local_data_kernels.cuh:34-116``). On TPU the fusion must live INSIDE
    the Pallas kernel (``pallas_call`` is an XLA fusion barrier, so the
    composed path materializes the [E, F] message tensor in HBM); off-TPU
    (or non-owner side) it falls back to the exact composed ops.
    """
    idx = _side_index(plan, side)
    n_pad = _side_npad(plan, side)
    # one compute dtype on both paths: the kernel runs bias at edata's
    # precision, so the fallback must too (cross-backend equivalence)
    bias = bias.astype(edata.dtype)
    if plan.ids_sorted(side):
        # owner side: shared Pallas-or-jnp dispatch (kill switch + precision
        # policy in ONE place — ops.local)
        return local_ops.sorted_segment_sum_bias_relu_any(
            edata, idx, bias, n_pad,
            plan.scatter_block_e, plan.scatter_block_n, plan.scatter_mc,
            edge_weight=edge_weight, gather_mv=plan.gather_mv,
        )
    m = jax.nn.relu(edata + gather(bias, plan, side, axis_name))
    if edge_weight is not None:
        m = m * edge_weight[:, None].astype(m.dtype)
    return scatter_sum(m, plan, side, axis_name)


def _transposed_bwd_applies(table, bias, plan: EdgePlan, stream_side: str,
                            owner_side: str) -> bool:
    """Whether :func:`take_scatter_bias_relu`'s gradient to ``table`` can
    run as the transposed aggregation: the streamed side is the plan's
    halo side and its sorted route carries the owner ids in that order,
    the owner side is plan-sorted with the fused backward's span hint,
    the fused kernels run here (``ops.local``'s dispatch rule, and the
    backward pair's own switch), and an owner-side table slice fits
    on-chip memory WHOLE (``ops.local.on_chip_row_parts`` = 1, the size
    rule :func:`map_vertex_chunks` ties by): the route trades one
    permutation of an ``[E, chunk]`` tensor for TWO row gathers from
    ``[n_owner_pad, chunk]`` tables, 4.3 ms each from on-chip memory and
    24.8 from HBM against the permutation's 24.8 (PERF.md, PR 33), so a
    table too large to place keeps the permutation, and so does one that
    would be gathered in row parts (four gathers and two select passes)."""
    from dgraph_tpu import config as _cfg

    chunk = _chunk_slices(bias.shape[-1], None)[0]
    return (
        stream_side == plan.halo_side
        and plan.halo_sorted_owner_ids is not None
        and plan.ids_sorted(owner_side)
        and plan.gather_mv > 0
        and local_ops.fused_bias_relu_kernel_runs()
        and _cfg.pallas_fused_bwd_enabled()
        and local_ops.on_chip_row_parts(
            bias.shape[0], (chunk.stop - chunk.start) * table.dtype.itemsize
        ) == 1
    )


def _take_then_scatter(table, bias, edge_weight, plan, stream_side,
                       owner_side, axis_name, chunk_fn=None):
    """The layer's forward: per column chunk, ``scatter_bias_relu`` of the
    chunk's per-edge rows, the chunks in :func:`map_vertex_chunks`' order.
    ``chunk_fn(edata, bias_chunk)`` stands in for the fused scatter when
    the caller wants more than its value (its VJP).

    The rows are taken UNMASKED (:func:`_local_take_unmasked`):
    ``edge_mask`` is the padding mask, and ``scatter_bias_relu`` drops a
    padded edge by its owner-side id ``n_owner_pad`` on every route it has
    (the kernels' one-hot and ``[:num_segments]`` slice, the sorted
    segment-sum off a TPU, ``scatter_sum``'s own mask on the unsorted
    fallback), as do its ``act`` pass, its VJP (a padded edge's ``gd`` and
    ``d_w`` read 0: no row of ``g`` has its id) and the take's (the route's
    sentinel). So the row such a slot gathered reaches no output row,
    multiplied by zero first or not, and the ``[E, chunk]`` multiply
    between the gather and the kernel (2.55 ms each of gcn_arxiv.w1's four
    a step, PERF.md PR 37) is not made."""

    def fused(edata, b):
        return scatter_bias_relu(edata, b, plan, owner_side, axis_name,
                                 edge_weight=edge_weight)

    chunk_fn = chunk_fn or fused
    return map_vertex_chunks(
        lambda t, b: chunk_fn(_local_take_unmasked(t, plan, stream_side), b),
        (table, bias),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _take_scatter_bias_relu(table, bias, edge_weight, plan, stream_side,
                            owner_side, axis_name):
    return _take_then_scatter(table, bias, edge_weight, plan, stream_side,
                              owner_side, axis_name)


def _tsbr_fwd(table, bias, edge_weight, plan, stream_side, owner_side,
              axis_name):
    if not _transposed_bwd_applies(table, bias, plan, stream_side,
                                   owner_side):
        # every op's own VJP, as composed autodiff runs them
        out, vjp = jax.vjp(
            lambda t, b, w: _take_then_scatter(
                t, b, w, plan, stream_side, owner_side, axis_name),
            table, bias, edge_weight,
        )
        return out, (vjp, None)
    # the same calls; each chunk's fused scatter keeps its VJP for d_bias
    # and d_w, the gathers' is what the transposed route replaces
    chunk_vjps = []

    def fused_with_vjp(edata, b):
        out, vjp = jax.vjp(
            lambda e, b_, w: scatter_bias_relu(
                e, b_, plan, owner_side, axis_name, edge_weight=w),
            edata, b, edge_weight,
        )
        chunk_vjps.append(vjp)
        return out

    out = _take_then_scatter(table, bias, edge_weight, plan, stream_side,
                             owner_side, axis_name, chunk_fn=fused_with_vjp)
    return out, (tuple(chunk_vjps), (table, bias, edge_weight, plan))


def _tsbr_bwd(stream_side, owner_side, axis_name, res, g):
    from dgraph_tpu.obs.metrics import default_registry

    vjps, transposed = res
    chunks = len(_chunk_slices(g.shape[-1], None))
    default_registry.counter("gather.bwd_chunks", chunks)
    if transposed is None:
        default_registry.counter("gather.bwd_permuted", chunks)
        return (*vjps(g), None)
    table, bias, edge_weight, plan = transposed
    cdt = table.dtype
    owner_ids = plan.halo_sorted_owner_ids
    with _scoped("dgraph.local_take"):
        # once a step: every layer's and chunk's is this expression, and
        # the compiler shares it
        w_sorted = None if edge_weight is None else local_ops.take_values(
            edge_weight, plan.halo_sort_perm)
    d_table, d_bias, d_w = [], [], None
    for vjp, sl in zip(vjps, _chunk_slices(g.shape[-1], None)):
        default_registry.counter("gather.bwd_transposed")
        # gd, the [E, chunk] cotangent the gather's own VJP would permute,
        # is not read: the compiler drops the kernel that writes it unless
        # an edge weight is differentiated (d_w is its second output)
        g_cols = g[:, sl]
        _, d_bias_c, d_w_c = vjp(g_cols)
        d_bias.append(d_bias_c)
        if edge_weight is not None:
            d_w = d_w_c if d_w is None else d_w + d_w_c
        with _scoped("dgraph.local_take"):
            # every factor of gd[e] is a row of an owner-side VERTEX
            # table: take them in the halo-sorted order, each table after
            # the gather or chunk before it (map_vertex_chunks' tie), so
            # that it needs its place on chip through its own gather
            # only. The bias slice is cut out of the whole table AFTER
            # the tie: cut before it, it is the forward's own slice, made
            # then and held in HBM since; cut after, it is written to
            # on-chip memory for this gather (read off the compiled
            # modules, scripts/gather_placement.py: 12 of 12 tables
            # placed in gcn_arxiv.w1 against 8, PERF.md PR 33). A padded
            # edge's owner id clamps onto the last row; its sorted id is
            # the route's sentinel, past every vertex block, so no
            # one-hot column reads the row.
            with _scoped("slice"):
                g_table = g_cols.astype(cdt)
                if d_table:
                    g_table = local_ops.run_after(d_table[-1], g_table)
            g_rows = local_ops.row_take(g_table, owner_ids)
            with _scoped("slice"):
                bias_table = local_ops.run_after(g_rows, bias)[:, sl].astype(cdt)
            bias_rows = local_ops.row_take(bias_table, owner_ids)
        with _scoped("dgraph.scatter_bias_relu"):
            d_table.append(local_ops.sorted_segment_grad_bias_relu(
                bias_rows, g_rows, plan.halo_sorted_ids, table[:, sl],
                plan.scatter_block_e, plan.scatter_block_n,
                plan.halo_sort_mc, edge_weight=w_sorted,
            ))
    return _concat_chunks(d_table), _concat_chunks(d_bias), d_w, None


_take_scatter_bias_relu.defvjp(_tsbr_fwd, _tsbr_bwd)


def take_scatter_bias_relu(
    table: jax.Array,  # [n_rows, F] stream-side vertex table (halo-extended)
    bias: jax.Array,  # [n_owner_pad, F] owner-side vertex operand
    plan: EdgePlan,
    stream_side: str,
    owner_side: str,
    axis_name: Optional[str],
    edge_weight: Optional[jax.Array] = None,  # [e_pad]
) -> jax.Array:
    """The fused GCN layer's aggregation, out[v] = Σ_{e: owner_e = v} w_e ·
    relu(table[stream_e] + bias[v]), as ONE op with one VJP. The forward
    is ``scatter_bias_relu(local_take(table), bias)`` a column chunk, the
    chunks in :func:`map_vertex_chunks`' order, without ``local_take``'s
    edge-mask pass: the aggregation drops a padded edge by its id
    (:func:`_take_then_scatter`).

    CONTRACT: ``plan.edge_mask`` is the PADDING mask and nothing else. A
    masked slot's owner-side id is ``n_owner_pad`` and its halo-side id 0,
    as every builder fills it; ``plan._finalize_plan`` and
    ``plan.assemble_plan`` (a fresh build, a cache load) refuse a plan
    that breaks this, as ``validate_plan`` does. On the routes that end in
    a sorted segment-sum (every owner-sorted plan) the op never reads
    ``edge_mask``: a REAL edge masked out by hand (its owner id in range)
    would be aggregated here and dropped by ``gather`` / ``scatter_sum``,
    i.e. by the unfused GCN branch, SAGE and GraphCast. (A mask
    multiply before the aggregation would not drop such an edge either:
    its zeroed row still adds ``w_e · relu(bias[v])`` to its owner.)

    The gradient to ``table``, which the two ops' own VJPs compute by
    writing gd[e] = w_e ·
    g[owner_e] · 1[table[stream_e] + bias[owner_e] > 0] as an ``[E,
    chunk]`` tensor in owner-sorted order, permuting it by
    ``halo_sort_perm`` and segment-summing it, runs as the transposed
    aggregation where :func:`_transposed_bwd_applies`: two rows an edge
    gathered from the owner-side vertex tables ``g`` and ``bias`` in the
    halo-sorted order, contracted by ``halo_sorted_ids`` with ``table``'s
    block resident (``ops.pallas_segment.sorted_segment_grad_bias_relu``:
    the forward kernel, sides exchanged; the same rounding points). The
    backward is one op over the layer's chunks so that it can run them one
    after the other, as the forward does. d_bias and d_w are the fused
    scatter's own. Elsewhere every VJP runs as it did. Which route a
    chunk's traced backward took is counted: ``gather.bwd_transposed`` /
    ``gather.bwd_permuted`` of ``gather.bwd_chunks`` (docs/tracing.md)."""
    return _take_scatter_bias_relu(
        table, bias, edge_weight, plan, stream_side, owner_side, axis_name)


@_scoped("dgraph.gather_concat")
def gather_concat(
    x_src: jax.Array,
    x_dst: jax.Array,
    plan: EdgePlan,
    axis_name: Optional[str],
) -> jax.Array:
    """[e_pad, F_src+F_dst] concat of src- and dst-side per-edge features.

    The reference's GCN/GAT layers start with exactly this double gather
    (``experiments/OGB/GCN.py:28-67``, ``RGAT.py:174-206``).
    """
    hs = gather(x_src, plan, "src", axis_name)
    hd = gather(x_dst, plan, "dst", axis_name)
    return jnp.concatenate([hs, hd], axis=-1)


@_scoped("dgraph.psum_mean")
def psum_mean(x, axis_name: Optional[str]):
    """Mean over a mesh axis (None = identity). For DP gradient sync —
    replaces the reference's DDP all-reduce (``experiments/OGB/main.py:111``)."""
    if axis_name is None:
        return x
    return lax.pmean(x, axis_name)
