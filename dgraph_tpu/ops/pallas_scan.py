"""The selective scan's recurrence as Pallas kernels that keep the ``[N, C]``
state in on-chip memory over time, forward and backward.

The same recurrence as :mod:`dgraph_tpu.ops.selective_scan` (its module
docstring has the equations), the other schedule of it: time runs step by
step and exactly, a grid of channel blocks (independent) by time blocks (in
order, innermost), and what crosses from one time block to the next (the
state forward, ``G = a_{t+1} dL/ds_{t+1}`` and ``dA`` backward) stays in a
VMEM scratch for the whole of time instead of going through HBM once an
iteration. States are ``[N, bc]`` float32, channels on the lanes and states
on the sublanes; float32 inside whatever the streams' types.

- :func:`fused_forward`: a time block loads ``u``, ``delta`` ``[bt, bc]`` and
  ``B``, ``Cm`` ``[N, bt]``, runs its ``bt`` steps, writes ``y`` (with the
  ``D u`` term) and the state it started from (``starts [T / bt, N, C]``,
  kept for the backward), and the last block the last state.
- :func:`fused_backward`: time blocks in reverse. A block redoes its states
  from its kept start into a VMEM buffer (``[bt + 1, N, bc]``, with the
  decays ``[bt, N, bc]`` beside it), then one reverse loop over its steps
  forms every cotangent as ``selective_scan._backward::back`` does. ``dB``
  and ``dCm`` are sums over channels: a lane reduction a step inside a
  channel block, the channel blocks' partials summed outside.

``B_t`` and ``Cm_t`` meet the state as ``[N, 1]`` columns across the lanes;
a block spreads its ``[N, bt]`` columns over 128 lanes once (``_spread``)
and a step loads the ``[N, 128]`` tile it needs.

Which shapes these kernels take is :func:`applies`: those whose tiles are
whole and whose narrowest channel block fits a VMEM budget. The channel
block is the widest that fits it (:func:`channel_block`), and the limit
handed to the compiler is what the blocks take (:func:`vmem_bytes`) and a
slack, not a chip's whole VMEM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dgraph_tpu.ops.pallas_segment import _out_struct

LANES = 128
SUBLANES = 8
# What a kernel's blocks and scratches (vmem_bytes) may take of VMEM, and
# what the limit handed to the compiler adds to them for its own temporaries
# (compiled for a v5e at eight shapes, Mosaic counts at most 0.4 MiB over
# vmem_bytes: 24.79 MiB against 24.44 at the cell's blocks).
VMEM_BUDGET = 32 << 20
VMEM_SLACK = 4 << 20
# Steps of a block traced into one loop body.
UNROLL = 4


def vmem_bytes(N: int, bt: int, bc: int, itemsize: int) -> int:
    """What the backward kernel holds in VMEM at time blocks of ``bt`` steps
    and channel blocks of ``bc`` lanes, ``u`` of ``itemsize`` bytes: its
    scratches (the states ``[bt + 1, N, bc]``, the decays ``[bt, N, bc]``,
    ``G``, three ``[bt, bc]`` and the two spread ``[bt, N, 128]``) and its
    grid step's blocks, double-buffered. The forward's are a subset."""
    scratch = 4 * ((2 * bt + 2) * N * bc + 3 * bt * bc + 2 * bt * N * LANES)
    blocks = (bt * bc * (12 + 2 * itemsize)  # delta, dy, ddelta; u, du
              + 4 * (5 * N * bc  # At, starts, d_last, dAt, ds0
                     + 2 * SUBLANES * bc  # D, dD: a row fills a sublane tile
                     + 4 * N * max(bt, LANES)))  # columns of B, Cm, dB, dCm
    return scratch + 2 * blocks


def channel_block(C: int, N: int, bt: int, itemsize: int):
    """The widest ``128 * 2^k`` lanes that divide ``C`` and keep
    :func:`vmem_bytes` inside ``VMEM_BUDGET``; None if one lane tile does
    not. (On the chip at T 8192, C 5120, N 16, bt 128, forward / backward
    ms: 256 4.80 / 10.44, 512 3.62 / 7.95, 640 3.24 / 7.05, 1024 2.94 /
    6.33, 1280 2.99 / 9.63, 2560 3.05 / 6.92: PERF.md section 6, PR 41; ten
    lane tiles a state row read worse than eight or twenty, so powers of
    two.)"""
    fits = lambda bc: vmem_bytes(N, bt, bc, itemsize) <= VMEM_BUDGET
    if not fits(LANES):
        return None
    bc = LANES
    while C % (2 * bc) == 0 and fits(2 * bc):
        bc *= 2
    return bc


def applies(u, A, bt: int) -> bool:
    """Whether the kernels can take ``u [T, C]``, ``A [C, N]`` in time blocks
    of ``bt`` steps: channels a multiple of the lanes, states of the
    sublanes, a time block that is whole sublane tiles of ``u``'s type
    (rows pack ``4 / itemsize`` to a sublane), and a channel block that fits
    (a ``bt`` near a long ``T`` leaves none: the buffers grow with it)."""
    C, N = A.shape
    itemsize = jnp.dtype(u.dtype).itemsize
    rows = SUBLANES * max(1, 4 // itemsize)
    return (C % LANES == 0 and N % SUBLANES == 0 and bt % rows == 0
            and channel_block(C, N, bt, itemsize) is not None)


def _params(N: int, bt: int, bc: int, itemsize: int):
    """Channel blocks independent, time in order; the VMEM the blocks take."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=vmem_bytes(N, bt, bc, itemsize) + VMEM_SLACK)


def _spread(cols_ref, out_ref):
    """``out[j] = cols[:, j]`` across 128 lanes, for every step of a block:
    ``[N, bt] -> [bt, N, 128]``."""
    cols = cols_ref[0]
    N, bt = cols.shape
    for j in range(bt):
        out_ref[j] = jnp.broadcast_to(cols[:, j:j + 1], (N, LANES))


def _wide(tile, bc: int):
    """An ``[N, 128]`` tile against ``[N, bc]``."""
    return tile if bc == LANES else jnp.concatenate(
        [tile] * (bc // LANES), axis=1)


def _steps(bt: int, step, carry):
    """``step(i, carry)`` for ``i = 0 .. bt - 1`` in order, ``UNROLL`` steps to
    a loop body (Mosaic unrolls a ``fori_loop`` whole or not at all)."""
    k = max(k for k in range(1, min(UNROLL, bt) + 1) if bt % k == 0)

    def some(j, carry):
        for i in range(k):
            carry = step(j * k + i, carry)
        return carry

    return lax.fori_loop(0, bt // k, some, carry)


def _lane_sum(x):
    """``[N, bc] -> [N, 1]``: the lane tiles added, then one lane reduction."""
    N, bc = x.shape
    tile = x[:, :LANES]
    for k in range(1, bc // LANES):
        tile = tile + x[:, k * LANES:(k + 1) * LANES]
    return tile.sum(axis=1, keepdims=True)


def _forward_kernel(u_ref, d_ref, At_ref, B_ref, C_ref, D_ref, s0_ref,
                    y_ref, starts_ref, last_ref,
                    s_scr, du_scr, Bb_scr, Cb_scr):
    t, bt, bc = pl.program_id(1), u_ref.shape[0], u_ref.shape[1]

    @pl.when(t == 0)
    def _():
        s_scr[...] = s0_ref[...]

    starts_ref[0] = s_scr[...]
    u32 = u_ref[...].astype(jnp.float32)
    du_scr[...] = d_ref[...] * u32
    _spread(B_ref, Bb_scr)
    _spread(C_ref, Cb_scr)
    At = At_ref[...]

    def step(i, s):
        row = pl.ds(i, 1)
        s = jnp.exp(d_ref[row, :] * At) * s \
            + _wide(Bb_scr[i], bc) * du_scr[row, :]
        y_ref[row, :] = (_wide(Cb_scr[i], bc) * s).sum(axis=0, keepdims=True)
        return s

    s = _steps(bt, step, s_scr[...])
    s_scr[...] = s
    y_ref[...] += D_ref[...] * u32

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        last_ref[...] = s


def _backward_kernel(u_ref, d_ref, dy_ref, At_ref, B_ref, C_ref, D_ref,
                     starts_ref, dlast_ref,
                     du_ref, dd_ref, dAt_ref, dD_ref, dB_ref, dC_ref, ds0_ref,
                     G_scr, s_scr, a_scr, u_scr, du_scr, dux_scr, Bb_scr,
                     Cb_scr):
    t, bt, bc = pl.program_id(1), u_ref.shape[0], u_ref.shape[1]
    N = At_ref.shape[0]

    @pl.when(t == 0)  # the LAST block of time: the grid runs time in reverse
    def _():
        G_scr[...] = dlast_ref[...]
        dAt_ref[...] = jnp.zeros_like(dAt_ref)
        dD_ref[...] = jnp.zeros_like(dD_ref)

    u_scr[...] = u_ref[...].astype(jnp.float32)
    du_scr[...] = d_ref[...] * u_scr[...]
    _spread(B_ref, Bb_scr)
    _spread(C_ref, Cb_scr)
    At = At_ref[...]

    # the block's states from its kept start: s_scr[i] before step i,
    # s_scr[i + 1] after it, a_scr[i] its decay
    def redo(i, s):
        row = pl.ds(i, 1)
        a = jnp.exp(d_ref[row, :] * At)
        a_scr[i] = a
        s = a * s + _wide(Bb_scr[i], bc) * du_scr[row, :]
        s_scr[i + 1] = s
        return s

    s_scr[0] = starts_ref[0]
    _steps(bt, redo, s_scr[0])  # (read back: a carry of the scratch's type)

    step_of = lax.broadcasted_iota(jnp.int32, (N, bt), 1)

    def back(j, carry):
        G, dAt, dB, dC = carry
        i = bt - 1 - j
        row = pl.ds(i, 1)
        d, dyt, a = d_ref[row, :], dy_ref[row, :], a_scr[i]
        g = _wide(Cb_scr[i], bc) * dyt + G  # dL/ds_t
        gsa = g * s_scr[i] * a  # dL/da_t * a_t
        gb = (g * _wide(Bb_scr[i], bc)).sum(axis=0, keepdims=True)
        dd_ref[row, :] = (gsa * At).sum(axis=0, keepdims=True) \
            + gb * u_scr[row, :]
        dux_scr[row, :] = gb * d
        here = step_of == i
        dB = jnp.where(here, _lane_sum(g * du_scr[row, :]), dB)
        dC = jnp.where(here, _lane_sum(s_scr[i + 1] * dyt), dC)
        return a * g, dAt + gsa * d, dB, dC

    zeros = jnp.zeros((N, bt), jnp.float32)
    G, dAt, dB, dC = _steps(
        bt, back, (G_scr[...], jnp.zeros_like(At), zeros, zeros))
    G_scr[...] = G
    dAt_ref[...] += dAt
    dB_ref[0, 0] = dB
    dC_ref[0, 0] = dC
    dy = dy_ref[...]
    du_ref[...] = (dux_scr[...] + D_ref[...] * dy).astype(du_ref.dtype)
    dD_ref[...] += (dy * u_scr[...]).sum(axis=0, keepdims=True)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        ds0_ref[...] = G


def _time_blocks(x, nt: int, bt: int):
    """``[T, k] -> [nt * bt, k]``, zero-padded at the end of time (a step of
    ``delta = 0`` passes the state through)."""
    pad = nt * bt - x.shape[0]
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _columns(x, nt: int, bt: int):
    """``[T, N] -> [nt, N, bt]`` float32: a time block's columns."""
    x = _time_blocks(x.astype(jnp.float32), nt, bt)
    return x.reshape(nt, bt, x.shape[1]).swapaxes(1, 2)


def _specs(N: int, bt: int, bc: int, when):
    """The blocks of a grid step ``(c, t)``, ``when(t)`` its time block:
    ``[T, C]`` streams, ``[N, C]`` states, the ``[nt, N, bt]`` columns, a
    ``[1, C]`` row a channel, the ``[nt, N, C]`` start states."""
    return (pl.BlockSpec((bt, bc), lambda c, t: (when(t), c)),
            pl.BlockSpec((N, bc), lambda c, t: (0, c)),
            pl.BlockSpec((1, N, bt), lambda c, t: (when(t), 0, 0)),
            pl.BlockSpec((1, bc), lambda c, t: (0, c)),
            pl.BlockSpec((1, N, bc), lambda c, t: (when(t), 0, c)))


def fused_forward(u, delta, At, B, Cm, D, s0, bt: int, *,
                  interpret: bool = False):
    """``(y [T, C] float32 with the D u term, the state every time block
    starts from [nt, N, C], the last state [N, C])``; ``At``, ``s0`` are
    ``[N, C]`` float32, ``delta`` float32."""
    T, C = u.shape
    N = At.shape[0]
    nt = -(-T // bt)
    itemsize = jnp.dtype(u.dtype).itemsize
    bc = channel_block(C, N, bt, itemsize)
    f32 = jnp.float32
    stream, state, cols, chan, start = _specs(N, bt, bc, lambda t: t)
    ins = (_time_blocks(u, nt, bt), _time_blocks(delta, nt, bt), At,
           _columns(B, nt, bt), _columns(Cm, nt, bt), D[None, :], s0)
    out = lambda *shape: _out_struct(shape, f32, *ins)
    y, starts, last = pl.pallas_call(
        _forward_kernel,
        grid=(C // bc, nt),
        in_specs=[stream, stream, state, cols, cols, chan, state],
        out_specs=[stream, start, state],
        out_shape=[out(nt * bt, C), out(nt, N, C), out(N, C)],
        scratch_shapes=[pltpu.VMEM((N, bc), f32), pltpu.VMEM((bt, bc), f32)]
        + [pltpu.VMEM((bt, N, LANES), f32)] * 2,
        compiler_params=_params(N, bt, bc, itemsize),
        interpret=interpret,
    )(*ins)
    return y[:T], starts, last


def fused_backward(u, delta, At, B, Cm, D, starts, dy, d_last, bt: int, *,
                   interpret: bool = False):
    """Cotangents ``(du [T, C] in u's type with the D dy term, ddelta [T, C],
    dAt [N, C], dB [T, N], dCm [T, N], dD [C], ds0 [N, C])``, float32 but
    for ``du``; ``dy [T, C]``, ``d_last [N, C]`` float32."""
    T, C = u.shape
    N = At.shape[0]
    nt = starts.shape[0]
    itemsize = jnp.dtype(u.dtype).itemsize
    bc = channel_block(C, N, bt, itemsize)
    f32 = jnp.float32
    back = lambda t: nt - 1 - t
    stream, state, cols, chan, start = _specs(N, bt, bc, back)
    part = pl.BlockSpec((1, 1, N, bt), lambda c, t: (c, back(t), 0, 0))
    ins = (_time_blocks(u, nt, bt), _time_blocks(delta, nt, bt),
           _time_blocks(dy, nt, bt), At, _columns(B, nt, bt),
           _columns(Cm, nt, bt), D[None, :], starts, d_last)
    out = lambda *shape, dtype=f32: _out_struct(shape, dtype, *ins)
    parts = out(C // bc, nt, N, bt)
    d_u, d_delta, dAt, dD, dB, dC, d_s0 = pl.pallas_call(
        _backward_kernel,
        grid=(C // bc, nt),
        in_specs=[stream, stream, stream, state, cols, cols, chan, start,
                  state],
        out_specs=[stream, stream, state, chan, part, part, state],
        out_shape=[out(nt * bt, C, dtype=u.dtype), out(nt * bt, C), out(N, C),
                   out(1, C), parts, parts, out(N, C)],
        scratch_shapes=[pltpu.VMEM((N, bc), f32),
                        pltpu.VMEM((bt + 1, N, bc), f32),
                        pltpu.VMEM((bt, N, bc), f32)]
        + [pltpu.VMEM((bt, bc), f32)] * 3
        + [pltpu.VMEM((bt, N, LANES), f32)] * 2,
        compiler_params=_params(N, bt, bc, itemsize),
        interpret=interpret,
    )(*ins)
    rows = lambda p: p.sum(0).swapaxes(1, 2).reshape(nt * bt, N)[:T]
    return d_u[:T], d_delta[:T], dAt, rows(dB), rows(dC), dD[0], d_s0
