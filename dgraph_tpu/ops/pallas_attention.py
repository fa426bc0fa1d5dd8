"""The backward of a tile-skipping (splash) attention call as ONE Pallas
kernel: a visited tile's scores, probabilities, ``dp`` and ``ds`` are
computed once and feed all three of ``dv += p^T do``, ``dk += ds^T q`` and
``dq += ds k``; every sum is float32 and on chip, each of ``dq``, ``dk``,
``dv`` is rounded once, and HBM holds no partial or unreduced copy of any.

The library's backward (``jax.experimental.pallas.ops.tpu.splash_attention``)
is two kernels, each of which forms ``q k^T``, its ``exp`` and ``do v^T`` for
itself: seven products a visited tile where five are needed. Its own fused
form writes ``dq`` once a KEY tile into a ``[T / tile, H, T, D]`` buffer,
rounded, and sums that afterwards (PERF.md section 6, PR 32 and PR 34).

This one walks QUERY-major. The grid is (KV head, query head of its group,
query tile), in that order and all in sequence. A KV head's whole ``K`` and
``V`` are one block whose index follows the KV head alone, so they are
fetched once a head and stay in VMEM; beside them the head's ``dk`` and
``dv`` accumulate in float32 scratches over the group's query heads and
query tiles and are written once, at the head's last step. A grid step takes
one query tile's ``q``, ``do``, log-sum-exp and ``di = sum(o do)`` (both as
ROWS, a query a lane: ``[T / tile, tile]`` a query head, unpadded in HBM,
where the library's kernels take ``[8, T]`` broadcasts), loops over the key tiles the mask lets that query
tile see (a list a query tile, scalar-prefetched: which tiles, and which of
them the mask cuts), and sums the tile's ``dq`` in a float32 scratch that is
written once when the loop ends.

A visit works on ``CHUNK`` keys at a time, keys on the sublanes and queries
on the lanes as the library's dkv kernel has them (so the row vectors
broadcast along sublanes and ``dv``, ``dk`` are plain products): ``s^T = k
q^T``; a tile the mask cuts is masked from ``mask.allowed`` on row-id iotas,
a whole tile is not; ``p^T = exp(s^T - lse)``; ``dp^T = v do^T``; ``ds^T =
(dp^T - di) p^T``. ``p`` and ``ds`` enter the MXU in the data's type, as in
the library's kernels. ``dq`` is summed TRANSPOSED, ``dq^T [D, tile] += k^T
ds^T``: the head is then the product's rows, which the 128 x 128 arrays do
not pad (a head of 192 or of 64 as the columns of ``ds k`` costs them 256 or
128), and what is transposed on the way in is a chunk of ``k``, not of
``ds^T``; the query tile's ``dq^T`` is transposed once, when it is written
(stand-alone on the chip, the backward at 16 384 rows: 48.9 ms against 51.7
at 192 | 128, 29.3 against 33.4 at 64, 21.7 against 21.6 at 128: PERF.md
section 6, PR 50).

Which shapes take this kernel is :func:`applies`: whole tiles, and a KV
head's resident blocks and accumulators within ``VMEM_BUDGET``; the limit
handed to the compiler is their count (:func:`vmem_bytes`) and a slack.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dgraph_tpu.ops.pallas_segment import _out_struct
from dgraph_tpu.ops.pallas_ssd import _dot, _f32, _nt, _tn

LANES = 128
# Keys a visit takes through the softmax at a time (the library's
# block_kv_dkv_compute): 256 and 512 time alike on the chip, 128 is 3-4 %
# slower (PERF.md section 6, PR 50).
CHUNK = 256
# What the kernel's blocks, scratches and temporaries (vmem_bytes) may take
# of a v5e's 128 MiB of VMEM, and what the limit handed to the compiler adds.
VMEM_BUDGET = 100 << 20
VMEM_SLACK = 8 << 20
# what a masked score is set to: the library's DEFAULT_MASK_VALUE, finite so
# that exp(masked - lse) is a plain 0
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


def _chunk(tile: int) -> int:
    return min(tile, CHUNK)


def vmem_bytes(T: int, D: int, Dv: int, itemsize: int, tile: int) -> int:
    """What the kernel holds in VMEM at ``T`` rows, heads of ``D | Dv`` (a
    head fills whole lane tiles there), streams of ``itemsize`` bytes and
    tiles of ``tile`` rows: a KV head's ``K``, ``V`` and its ``dk``, ``dv``
    blocks (double-buffered, as every block is), their float32 accumulators,
    a step's ``q``, ``do``, ``dq``, the two rows, ``dq^T``'s accumulator, and
    the ``[CHUNK, tile]`` float32 temporaries of a visit's body."""
    wide = lambda d: -(-d // LANES) * LANES
    head = T * (wide(D) + wide(Dv))
    step = tile * (2 * wide(D) + wide(Dv)) * itemsize + 2 * T * 4
    body = 8 * _chunk(tile) * tile * 4
    return (2 * 2 * head * itemsize + 4 * head + 2 * step
            + 4 * tile * wide(D) + body)


def applies(T: int, D: int, Dv: int, itemsize: int, tile: int) -> bool:
    """Whether the kernel takes ``T`` rows at heads of ``D | Dv``: whole
    tiles of whole lane tiles, and a KV head's blocks within the budget."""
    return (tile % LANES == 0 and T % tile == 0
            and vmem_bytes(T, D, Dv, itemsize, tile) <= VMEM_BUDGET)


def visits(data_next, block_mask):
    """The key tiles each query tile visits, from the library's forward
    ``MaskInfo`` (one mask for every head: ``[1, T / tile, width]`` each;
    ``block_mask`` 0 skipped, 1 cut by the mask, 2 whole; ``data_next`` the
    key tile of a slot that is not skipped): ``(tiles [nq, width], cut [nq,
    width], counts [nq])`` int32, a query tile's visits packed to the front."""
    data_next, block_mask = (np.asarray(a, np.int32) for a in
                             (data_next, block_mask))
    if block_mask.shape[0] != 1:
        raise ValueError(f"a mask a head: {block_mask.shape}")
    nq, width = block_mask.shape[1:]
    tiles, cut = (np.zeros((nq, width), np.int32) for _ in range(2))
    counts = np.zeros((nq,), np.int32)
    for i in range(nq):
        live = np.nonzero(block_mask[0, i])[0]
        counts[i] = len(live)
        tiles[i, :len(live)] = data_next[0, i, live]
        cut[i, :len(live)] = block_mask[0, i, live] == 1
    return tiles, cut, counts


def _kernel(tiles_ref, cut_ref, counts_ref, q_ref, k_ref, v_ref, do_ref,
            lse_ref, di_ref, dq_ref, dk_ref, dv_ref, dqt_scr, dk_scr, dv_scr,
            *, allowed, tile: int):
    g, i = pl.program_id(1), pl.program_id(2)
    chunk = _chunk(tile)

    def every_tile(fn):
        """``fn(rows)`` for each tile of rows of the KV head's blocks."""
        def body(r, _):
            fn(pl.ds(pl.multiple_of(r * tile, tile), tile))
        lax.fori_loop(0, k_ref.shape[0] // tile, body, None)

    @pl.when((g == 0) & (i == 0))
    def _():
        def zero(rows):
            dk_scr[rows, :] = jnp.zeros((tile, dk_scr.shape[1]), _f32)
            dv_scr[rows, :] = jnp.zeros((tile, dv_scr.shape[1]), _f32)
        every_tile(zero)

    dqt_scr[...] = jnp.zeros_like(dqt_scr)
    q, do = q_ref[...], do_ref[...]
    # the head's rows, a query tile a sublane: this step's, a query a lane
    lse, di = lse_ref[pl.ds(i, 1), :], di_ref[pl.ds(i, 1), :]

    def visit(w, masked: bool):
        first = tiles_ref[i, w] * tile
        for c in range(tile // chunk):
            start = pl.multiple_of(first + c * chunk, chunk)
            rows = pl.ds(start, chunk)
            k, v = k_ref[rows, :], v_ref[rows, :]
            s = _nt(k, q)  # [chunk, tile]: a key a sublane
            if masked:
                k_ids = start + lax.broadcasted_iota(
                    jnp.int32, (chunk, tile), 0)
                q_ids = i * tile + lax.broadcasted_iota(
                    jnp.int32, (chunk, tile), 1)
                s = jnp.where(allowed(q_ids, k_ids), s, MASK_VALUE)
            p = jnp.exp(s - lse)
            dv_scr[rows, :] += _dot(p.astype(do.dtype), do)
            ds = ((_nt(v, do) - di) * p).astype(q.dtype)
            dk_scr[rows, :] += _dot(ds, q)
            dqt_scr[...] += _tn(k, ds)  # dq^T [D, tile]

    def one(w, _):
        is_cut = cut_ref[i, w] == 1
        pl.when(is_cut)(lambda: visit(w, True))
        pl.when(jnp.logical_not(is_cut))(lambda: visit(w, False))

    lax.fori_loop(0, counts_ref[i], one, None)
    dq_ref[...] = dqt_scr[...].T.astype(dq_ref.dtype)

    @pl.when((g == pl.num_programs(1) - 1) & (i == pl.num_programs(2) - 1))
    def _():
        def write(rows):
            dk_ref[rows, :] = dk_scr[rows, :].astype(dk_ref.dtype)
            dv_ref[rows, :] = dv_scr[rows, :].astype(dv_ref.dtype)
        every_tile(write)


def backward(q, k, v, do, lse, di, tile_visits, *, allowed, tile: int,
             interpret: bool = False):
    """``(dq, dk, dv)`` in ``q``'s, ``k``'s and ``v``'s types and shapes.
    ``q [Hkv, G, T, D]`` (scaled: the kernel has no scale of its own), ``k
    [Hkv, T, D]``, ``v [Hkv, T, Dv]``, ``do [Hkv, G, T, Dv]``; ``lse`` and
    ``di = sum(o do)`` ``[Hkv, G, T]`` float32; ``tile_visits`` from
    :func:`visits` at tiles of ``tile`` rows; ``allowed(q_ids, k_ids)`` the
    mask on row ids (``BlockDiffusionMask.allowed`` and its like)."""
    Hkv, G, T, D = q.shape
    Dv = v.shape[-1]
    itemsize = jnp.dtype(q.dtype).itemsize
    rows = lambda t: t.astype(_f32).reshape(Hkv, G, T // tile, tile)
    ins = (q, k, v, do, rows(lse), rows(di))
    per_step = lambda d: pl.BlockSpec(
        (None, None, tile, d), lambda h, g, i, *_: (h, g, i, 0))
    per_head = lambda d: pl.BlockSpec(
        (None, T, d), lambda h, g, i, *_: (h, 0, 0))
    row = pl.BlockSpec((None, None, T // tile, tile),
                       lambda h, g, i, *_: (h, g, 0, 0))
    out = lambda t: _out_struct(t.shape, t.dtype, *ins)
    return pl.pallas_call(
        functools.partial(_kernel, allowed=allowed, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Hkv, G, T // tile),
            in_specs=[per_step(D), per_head(D), per_head(Dv), per_step(Dv),
                      row, row],
            out_specs=[per_step(D), per_head(D), per_head(Dv)],
            scratch_shapes=[pltpu.VMEM((D, tile), _f32),
                            pltpu.VMEM((T, D), _f32),
                            pltpu.VMEM((T, Dv), _f32)]),
        out_shape=[out(q), out(k), out(v)],
        compiler_params=pltpu.CompilerParams(
            # dk and dv are summed over a head's steps, and dq's list is a
            # step's own: nothing here is another core's to take
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(T, D, Dv, itemsize, tile)
            + VMEM_SLACK),
        name="splash_bwd_one_kernel",
        interpret=interpret,
    )(*(jnp.asarray(a) for a in tile_visits), *ins)
