"""The Mamba-2 chunked recurrence as Pallas kernels that keep a chunk's
``[L, L]`` decay tiles and the ``[P, N]`` states in on-chip memory, forward
and backward.

The same algorithm as :mod:`dgraph_tpu.ops.ssd`'s ``lax`` form (its module
docstring has the equations and the precision), on a grid of groups
(independent) by chunks (in order, innermost). One grid step is one chunk of
``L`` steps of one group's ``K`` heads: it forms ``C B^T`` once, and a head
at a time the masked tile ``exp(c_i - c_j) dt_j`` (float32, the mask in
before the ``exp``), its product with ``C B^T`` rounded to the streams' type
and the ``[L, L] x [L, P]`` product with ``x``; the start state's read-out
and the state's update are one product each for the whole group. What
crosses from one chunk to the next (the state forward, its cotangent
backward) stays in a VMEM scratch ``[N, K P]`` for the whole of time; no
``[L, L]`` tile reaches HBM.

Layouts. States are held transposed, ``[N, K P]`` float32: states on the
sublanes, a group's heads side by side on the lanes, so the read-out ``C
S^T`` and the update ``B^T (w x)`` are single products over all the group's
heads. ``dt`` and the running log-decays ``c`` come with time on the LANES
(``[G, K, T]``: a head's row against the columns ``j`` of a tile); a step
turns its ``[K, L]`` block into columns (a head's ``[L, 1]`` against the
rows ``i``) by one padded ``[128, L]`` transpose, and spreads a head's
column over its ``P`` lanes by broadcasts and selects (``_spread``). A head
narrower than a lane tile (``P < 128``) meets its tile through ``x`` with
its neighbours' lanes zeroed: the product's width is the MXU's either way.

- :func:`fused_forward` writes ``y`` (with the ``D x`` term), the state each
  chunk STARTS from (``[T / L, N, H P]`` float32, kept for the backward) and
  the last state.
- :func:`fused_backward`: chunks in reverse, the end state's cotangent in the
  scratch. A chunk redoes its tiles from the inputs and its kept start state
  and forms ``dx``, ``dB``, ``dC`` (summed over a group's heads inside the
  step), ``d dt``, the running log-decays' cotangent ``dc`` (both with time
  on the lanes: sums over a head's ``P`` lanes are products with a 0 / 1
  matrix, the float32 summands split in three bf16 pieces so nothing is
  rounded), ``dD`` (a row a lane, summed over ``P`` outside) and ``d s0``.
  ``sum_j dM_ij M_ij``, the tiles' part of ``dc_i``, is ``dy_i . y_i`` of the
  redone in-chunk output: a ``[L, K P]`` product instead of ``K`` row sums.
  What ``dc_j`` loses, ``sum_i dM_ij M_ij``, is summed over the same rounded
  ``dy`` and ``M`` that product saw: the two meet again in ``dA`` and ``d
  dt``, summed over a chunk's later steps, where they all but cancel, and a
  rounding on one side alone read 3.6 % off in ``dA`` (0.3 % now, the
  ``lax`` form's 0.2: against float32, bf16 streams).

Which shapes these kernels take is :func:`applies`: whole tiles, and blocks
that fit a VMEM budget; the limit handed to the compiler is what the blocks
take (:func:`vmem_bytes`) and a slack.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dgraph_tpu.ops.pallas_segment import _out_struct

LANES = 128
# What a kernel's blocks, scratches and float32 temporaries (vmem_bytes) may
# take of VMEM, and what the limit handed to the compiler adds to them
# (compiled for a v5e at six shapes, Mosaic's own count is 0.74-1.0 of
# vmem_bytes: 6.11 MiB against 6.25 at the cell's blocks, 27.5 against 28.7
# at chunks of 512 steps).
VMEM_BUDGET = 32 << 20
VMEM_SLACK = 4 << 20

_f32 = jnp.float32
_dot = functools.partial(jnp.dot, preferred_element_type=_f32)


def _nt(a, b):
    """``a [m, k] x b [n, k]^T -> [m, n]`` float32."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=_f32)


def _tn(a, b):
    """``a [k, m]^T x b [k, n] -> [m, n]`` float32."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=_f32)


def vmem_bytes(L: int, K: int, P: int, N: int, itemsize: int) -> int:
    """What the backward kernel holds in VMEM at chunks of ``L`` steps of
    ``K`` heads of ``P`` channels and ``N`` states, streams of ``itemsize``
    bytes: its grid step's blocks, double-buffered, its scratches, and the
    temporaries of the step's body (``[L, K P]``, ``[L, L]`` and ``[N, K P]``
    float32 values, as many of each as the compiler was seen to keep). The
    forward's are a subset."""
    KP = K * P
    blocks = (L * KP * (2 * itemsize + 4)  # x, dx; dy
              + 4 * L * N * itemsize  # B, C; dB, dC
              + 4 * 4 * max(K, 8) * L  # dt, c; d dt, dc
              + 4 * (3 * N * KP + 9 * KP))  # start, d last, d s0; D, dD
    scratch = 4 * (N * KP + LANES * L)
    body = 4 * (7 * L * KP + 14 * L * L + 2 * N * KP)
    return 2 * blocks + scratch + body


def applies(x, B, chunk: int) -> bool:
    """Whether the kernels can take ``x [T, H, P]``, ``B [T, G, N]`` in
    chunks of ``chunk`` steps: time in whole chunks, chunks and states in
    whole lane tiles, a group's ``K P`` channels too, a head that is a
    divisor or a multiple of a lane tile, a group's heads within one, and
    blocks that fit the budget."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    if H % G or chunk <= 0:
        return False
    K = H // G
    return (T % chunk == 0 and chunk % LANES == 0 and N % LANES == 0
            and (K * P) % LANES == 0 and K <= LANES
            and (P % LANES == 0 or LANES % P == 0)
            and vmem_bytes(chunk, K, P, N, jnp.dtype(x.dtype).itemsize)
            <= VMEM_BUDGET)


def _params(L: int, K: int, P: int, N: int, itemsize: int):
    """Groups independent, chunks in order; the VMEM the blocks take."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=vmem_bytes(L, K, P, N, itemsize) + VMEM_SLACK)


def _columns(rows, pad_scr):
    """``[K, L] -> [L, 128]``: head ``k``'s row as lane ``k``'s column (one
    padded transpose; the lanes past ``K`` are zeros)."""
    pad_scr[0:rows.shape[0], :] = rows
    return pad_scr[...].T


def _tiles(P: int):
    """The lane tiles of a group's ``K P`` channels: ``(width, heads a
    tile)``; a head of 128 lanes or more is its own tile."""
    W = max(P, LANES)
    return W, W // P


def _spread(cols, K: int, P: int):
    """``[L, 128] -> [L, K P]``: head ``k``'s column over its ``P`` lanes."""
    L = cols.shape[0]
    W, q = _tiles(P)
    lane = lax.broadcasted_iota(jnp.int32, (L, W), 1)
    out = []
    for t in range(K * P // W):
        tile = jnp.broadcast_to(cols[:, t * q:t * q + 1], (L, W))
        for r in range(1, q):
            tile = jnp.where(lane >= r * P, jnp.broadcast_to(
                cols[:, t * q + r:t * q + r + 1], (L, W)), tile)
        out.append(tile)
    return jnp.concatenate(out, axis=1)


def _own(tile32, r: int, P: int, q: int):
    """The tile with the lanes of every head but its ``r``-th zeroed."""
    if q == 1:
        return tile32
    lane = lax.broadcasted_iota(jnp.int32, tile32.shape, 1)
    return jnp.where((lane >= r * P) & (lane < (r + 1) * P), tile32, 0.0)


def _below(L: int):
    """``[L, L]``: the pairs ``j <= i`` (a step sees itself and the past)."""
    return lax.broadcasted_iota(jnp.int32, (L, L), 0) \
        >= lax.broadcasted_iota(jnp.int32, (L, L), 1)


def _decay(cum_c, cum_r, k: int, below):
    """Head ``k``'s ``[L, L]`` tile ``exp(c_i - c_j)`` for ``j <= i``, zero
    above the diagonal (the mask goes in before the ``exp``)."""
    diff = cum_c[:, k:k + 1] - cum_r[k:k + 1, :]
    return jnp.exp(jnp.where(below, diff, -jnp.inf))


def _scales(dt_ref, cum_ref, pad_scr, K: int, P: int):
    """A step's ``dt`` and running log-decays ``[K, L]``, the latter as
    columns ``[L, 128]``, and over the group's lanes ``[L, K P]``: ``e_i =
    exp(c_i)`` and ``w_j = exp(c_L - c_j) dt_j``."""
    dt_r, cum_r = dt_ref[0], cum_ref[0]
    L = dt_r.shape[1]
    dt_c, cum_c = _columns(dt_r, pad_scr), _columns(cum_r, pad_scr)
    e = _spread(jnp.exp(cum_c), K, P)
    w = _spread(jnp.exp(cum_c[L - 1:L, :] - cum_c) * dt_c, K, P)
    return dt_r, cum_r, cum_c, e, w


def _forward_kernel(x_ref, dt_ref, cum_ref, B_ref, C_ref, D_ref, s0_ref,
                    y_ref, starts_ref, last_ref, S_scr, pad_scr, *, P: int):
    c = pl.program_id(1)
    L, KP = x_ref.shape
    K = KP // P
    W, q = _tiles(P)

    @pl.when(c == 0)
    def _():
        S_scr[...] = s0_ref[...]
        pad_scr[...] = jnp.zeros_like(pad_scr)

    S = S_scr[...]
    starts_ref[0] = S
    x, Bm, Cm = x_ref[...], B_ref[...], C_ref[...]
    x32 = x.astype(_f32)
    dt_r, cum_r, cum_c, e, w = _scales(dt_ref, cum_ref, pad_scr, K, P)
    cb, below = _nt(Cm, Bm), _below(L)  # [L, L]: C_i . B_j
    y = e * _dot(Cm, S.astype(x.dtype)) + D_ref[...] * x32
    for t in range(KP // W):
        lanes = slice(t * W, (t + 1) * W)
        yt = y[:, lanes]
        for r in range(q):
            k = t * q + r
            m = (cb * (_decay(cum_c, cum_r, k, below) * dt_r[k:k + 1, :])
                 ).astype(x.dtype)
            yt = yt + _dot(m, _own(x32[:, lanes], r, P, q).astype(x.dtype))
        y_ref[:, lanes] = yt
    S = e[L - 1:L, :] * S + _tn(Bm, (x32 * w).astype(x.dtype))
    S_scr[...] = S

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        last_ref[...] = S


def _head_sums(z, K: int, P: int):
    """``[L, K P] -> [K, L]`` float32: the sum over each head's ``P`` lanes,
    time on the lanes: a product with a 0 / 1 matrix, ``z`` in three bf16
    pieces (all of a float32's mantissa)."""
    rows = -(-K // 16) * 16
    KP = K * P
    head = lax.broadcasted_iota(jnp.int32, (rows, KP), 0)
    lane = lax.broadcasted_iota(jnp.int32, (rows, KP), 1)
    ones = jnp.where((lane >= head * P) & (lane < (head + 1) * P), 1.0, 0.0
                     ).astype(jnp.bfloat16)
    out = jnp.zeros((rows, z.shape[0]), _f32)
    for _ in range(3):
        piece = z.astype(jnp.bfloat16)
        out = out + _nt(ones, piece)
        z = z - piece.astype(_f32)
    return out[:K]


def _backward_kernel(x_ref, dt_ref, cum_ref, B_ref, C_ref, D_ref, dy_ref,
                     starts_ref, dlast_ref,
                     dx_ref, ddt_ref, dcum_ref, dB_ref, dC_ref, dD_ref,
                     ds0_ref, G_scr, pad_scr, *, P: int):
    c = pl.program_id(1)  # the LAST chunk first: the grid runs time in reverse
    L, KP = x_ref.shape
    K = KP // P
    W, q = _tiles(P)

    @pl.when(c == 0)
    def _():
        G_scr[...] = dlast_ref[...]
        pad_scr[...] = jnp.zeros_like(pad_scr)
        dD_ref[...] = jnp.zeros_like(dD_ref)

    dS, S0 = G_scr[...], starts_ref[0]  # of the chunk's END, its start
    x, Bm, Cm, dy = x_ref[...], B_ref[...], C_ref[...], dy_ref[...]
    low = x.dtype
    x32 = x.astype(_f32)
    dt_r, cum_r, cum_c, e, w = _scales(dt_ref, cum_ref, pad_scr, K, P)
    S0b, dSb = S0.astype(low), dS.astype(low)
    edy = (e * dy).astype(low)
    xw = (x32 * w).astype(low)
    cb, below = _nt(Cm, Bm), _below(L)
    head_of = lax.broadcasted_iota(jnp.int32, (K, L), 0)
    read = e * _dot(Cm, S0b)  # what the start state adds to the output
    gm = _dot(Bm, dSb)  # [L, K P]: dS_end B_j a head
    dx = w * gm + D_ref[...] * dy
    dcb = jnp.zeros((L, L), _f32)
    # the tiles' parts, a row a head: sum_i dM_ij CB_ij decay_ij (d dt_j) and
    # sum_i dM_ij M_ij, of the M the product saw (what dc_j loses)
    ddt, lost = jnp.zeros((K, L), _f32), jnp.zeros((K, L), _f32)
    ys, dxs = [], []
    for t in range(KP // W):
        lanes = slice(t * W, (t + 1) * W)
        yt, dxt = jnp.zeros((L, W), _f32), dx[:, lanes]
        for r in range(q):
            k = t * q + r
            lam = _decay(cum_c, cum_r, k, below)
            ldt = lam * dt_r[k:k + 1, :]
            m = (cb * ldt).astype(low)
            xk = _own(x32[:, lanes], r, P, q).astype(low)
            dyk = _own(dy[:, lanes], r, P, q).astype(low)
            dm = _nt(dyk, xk)  # [L, L]: dy_i . x_j
            dcb = dcb + dm * ldt
            ddt = jnp.where(head_of == k,
                            (dm * cb * lam).sum(axis=0, keepdims=True), ddt)
            lost = jnp.where(head_of == k, (dm * m.astype(_f32)).sum(
                axis=0, keepdims=True), lost)
            yt = yt + _dot(m, xk)
            dxt = dxt + _tn(m, dyk)
        ys.append(yt)
        dxs.append(dxt)
    # the in-chunk output alone; all of dx
    y, dx = jnp.concatenate(ys, axis=1), jnp.concatenate(dxs, axis=1)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dcbb = dcb.astype(low)
    dC_ref[...] = (_dot(dcbb, Bm) + _nt(edy, S0b)).astype(dC_ref.dtype)
    dB_ref[...] = (_tn(dcbb, Cm) + _nt(xw, dSb)).astype(dB_ref.dtype)
    # time on the lanes: d dt and the running log-decays' cotangent
    last = lax.broadcasted_iota(jnp.int32, (K, L), 1) == L - 1
    to_end = jnp.exp(cum_r[:, L - 1:L] - cum_r)
    dw = _head_sums(x32 * gm, K, P)  # dL/dw_j
    ddt_ref[0] = ddt + dw * to_end
    # dc_i: dy_i . y_i (the read-out's, and the tiles' rows: sum_j dM_ij M_ij
    # of the rounded dy and M the products saw, so that they cancel against
    # `lost` in the sums over a chunk's later steps); at the chunk's last step
    # also the whole decay's exp(c_L) <dS, S0> and sum_j dw_j w_j
    whole = e[L - 1:L, :] * (dS * S0).sum(axis=0, keepdims=True)  # [1, K P]
    at_last = lax.broadcasted_iota(jnp.int32, (L, KP), 0) == L - 1
    dyb = dy.astype(low).astype(_f32)
    dcum = _head_sums(
        dy * read + dyb * y + jnp.where(at_last, whole, 0.0), K, P)
    dww = dw * to_end * dt_r
    dcum_ref[0] = dcum - lost - dww + jnp.where(
        last, dww.sum(axis=1, keepdims=True), 0.0)
    dD_ref[...] += (dy * x32).reshape(L // 8, 8, KP).sum(axis=0)
    dS = e[L - 1:L, :] * dS + _tn(Cm, edy)
    G_scr[...] = dS

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        ds0_ref[...] = dS


def _specs(L: int, K: int, P: int, N: int, when):
    """The blocks of a grid step ``(g, c)``, ``when(c)`` its chunk: the
    ``[T, H P]`` streams, ``B`` and ``C`` ``[T, G N]``, the ``[G, K, T]``
    rows, a ``[., H P]`` row a lane, the ``[N, H P]`` states, the
    ``[T / L, N, H P]`` start states."""
    KP = K * P
    return (pl.BlockSpec((L, KP), lambda g, c: (when(c), g)),
            pl.BlockSpec((L, N), lambda g, c: (when(c), g)),
            pl.BlockSpec((1, K, L), lambda g, c: (g, 0, when(c))),
            lambda rows: pl.BlockSpec((rows, KP), lambda g, c: (0, g)),
            pl.BlockSpec((N, KP), lambda g, c: (0, g)),
            pl.BlockSpec((1, N, KP), lambda g, c: (when(c), 0, g)))


def fused_forward(x, dt, cum, B, Cm, D, s0, L: int, P: int, *,
                  interpret: bool = False):
    """``(y [T, H P] float32 with the D x term, the state every chunk starts
    from [T / L, N, H P], the last state [N, H P])``. ``x [T, H P]``, ``B``,
    ``Cm [T, G N]`` in the streams' type; ``dt``, ``cum`` (the running sum of
    ``dt A`` inside each chunk) ``[G, K, T]``, ``D [1, H P]`` (a head's over
    its lanes), ``s0 [N, H P]`` float32."""
    T, HP = x.shape
    G, K = dt.shape[:2]
    N = B.shape[1] // G
    nc = T // L
    itemsize = jnp.dtype(x.dtype).itemsize
    stream, bc, rows, lane_row, state, start = _specs(L, K, P, N, lambda c: c)
    ins = (x, dt, cum, B, Cm, D, s0)
    out = lambda *shape: _out_struct(shape, _f32, *ins)
    return pl.pallas_call(
        functools.partial(_forward_kernel, P=P),
        grid=(G, nc),
        in_specs=[stream, rows, rows, bc, bc, lane_row(1), state],
        out_specs=[stream, start, state],
        out_shape=[out(T, HP), out(nc, N, HP), out(N, HP)],
        scratch_shapes=[pltpu.VMEM((N, K * P), _f32),
                        pltpu.VMEM((LANES, L), _f32)],
        compiler_params=_params(L, K, P, N, itemsize),
        interpret=interpret,
    )(*ins)


def fused_backward(x, dt, cum, B, Cm, D, starts, dy, d_last, L: int, P: int,
                   *, interpret: bool = False):
    """Cotangents ``(dx [T, H P] in x's type with the D dy term, d dt and
    dcum [G, K, T], dB, dCm [T, G N] in theirs, dD [8, H P] (summed over its
    rows and a head's lanes outside), ds0 [N, H P])``, float32 where not said;
    ``dy [T, H P]``, ``d_last [N, H P]`` float32."""
    T, HP = x.shape
    G, K = dt.shape[:2]
    N = B.shape[1] // G
    nc = T // L
    itemsize = jnp.dtype(x.dtype).itemsize
    back = lambda c: nc - 1 - c
    stream, bc, rows, lane_row, state, start = _specs(L, K, P, N, back)
    ins = (x, dt, cum, B, Cm, D, dy, starts, d_last)
    out = lambda *shape, dtype=_f32: _out_struct(shape, dtype, *ins)
    return pl.pallas_call(
        functools.partial(_backward_kernel, P=P),
        grid=(G, nc),
        in_specs=[stream, rows, rows, bc, bc, lane_row(1), stream, start,
                  state],
        out_specs=[stream, rows, rows, bc, bc, lane_row(8), state],
        out_shape=[out(T, HP, dtype=x.dtype), out(G, K, T), out(G, K, T),
                   out(T, G * N, dtype=B.dtype), out(T, G * N, dtype=Cm.dtype),
                   out(8, HP), out(N, HP)],
        scratch_shapes=[pltpu.VMEM((N, K * P), _f32),
                        pltpu.VMEM((LANES, L), _f32)],
        compiler_params=_params(L, K, P, N, itemsize),
        interpret=interpret,
    )(*ins)
