"""The Mamba-2 recurrence (state-space duality; Dao & Gu 2024) in its chunked
form: a state that is a MATRIX a head, under a scalar decay a head.

For inputs ``x [T, H, P]`` (H heads of P channels), step sizes ``dt [T, H]``
(positive, float32), ``A [H]`` (negative), ``B, C [T, G, N]`` (G groups of N
states; head h reads group ``h // (H / G)``), ``D [H]`` and a start state
``S_{-1} = s0 [H, P, N]`` (zeros if None), per head:

    a_t = exp(dt_t A)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T          ([P, N])
    y_t = S_t C_t + D x_t                        ([P])

:func:`ssd` returns ``(y [T, H, P] float32, S_{T-1} [H, P, N] float32)``. The
same recurrence broadcast to a diagonal ``[N, H P]`` state is
:mod:`dgraph_tpu.ops.selective_scan`'s (``T N H P`` multiply-adds on the
vector unit); here time is cut into chunks of ``chunk`` steps (L) and the
work inside a chunk is three matrix products, with the log-decays
``l_t = dt_t A`` and their running sums ``c_i = sum_{t <= i} l_t`` a chunk:

- scope ``chunk``: ``y_i += sum_{j <= i} (C_i . B_j) exp(c_i - c_j) dt_j
  x_j``: ``C B^T`` a group (``[L, N] x [N, L]``), times the masked decay
  matrix and ``dt_j`` a head, times ``x`` (``[L, L] x [L, P]``);
- scope ``state``: every chunk's end state from a zero start, ``sum_j
  exp(c_L - c_j) dt_j x_j B_j^T`` (``[P, L] x [L, N]``); the true state at
  each chunk's start from those and the chunks' whole decays ``exp(c_L)`` in
  ``T / L`` hops over ``[H, P, N]``; what the start state adds to each step,
  ``y_i += exp(c_i) S_start C_i`` (``[P, N] x [N]`` a step: the read-out).

Log-decays, their sums, every ``exp`` and the carried states are float32, and
``exp`` is only ever taken of a difference that is <= 0 (the mask goes in
BEFORE it). The operands of the products are in the streams' type (``x``'s:
the compute dtype), their results float32. A ``T`` that is no multiple of
``chunk`` is padded with steps of ``dt = 0`` (decay 1, input 0: the state
passes through).

Two routes run this one algorithm, chosen a call from the backend and the
shapes and counted (``ssd.core_calls``, ``ssd.core_fused``):

- the ``lax`` form below, everywhere but where the kernels apply: the CPU,
  a ``T`` the chunk does not divide, chunks, states or a group's channels
  that are no whole lane tiles. Its backward pass is the automatic
  differentiation of this form, and what it keeps (the ``[T / L, H, L, L]``
  float32 decay matrix, 268 MB at T 8192, H 64, L 128, among it) is this
  route's alone; the caller's layer is rematerialised, so it lives for one
  layer's backward. It is also the oracle of the other route's tests.
- on a TPU where :func:`pallas_ssd.applies`, the kernels of
  :mod:`dgraph_tpu.ops.pallas_ssd`, forward and backward (``_fused``, its own
  ``custom_vjp``), all under the scope ``chunk``: a chunk's ``[L, L]`` tiles
  and the ``[P, N]`` states between chunks stay in on-chip memory, the hops
  are the grid's order, and what is kept for the backward is the inputs and
  each chunk's start state (``[T / L, N, H P]`` float32: 134 MB at those
  sizes). XLA's part there: the log-decays' running sums and their
  transposed cotangent (``dA`` a sum of it), ``dt`` with time on the lanes,
  ``D`` over a head's lanes, the states' transposes.

:func:`ssd_sequence` is the operator over a sequence sharded on a mesh axis,
by :func:`~dgraph_tpu.ops.selective_scan.scan_sequence`'s contract: each rank
runs its shard from a zero state, the ranks' end states and whole decays are
gathered, and every rank folds the ones before it into its own start state,
whose read-out it adds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from dgraph_tpu.obs.metrics import default_registry
from dgraph_tpu.ops import pallas_ssd

SSD_CHUNK = 128  # steps a chunk: the published chunk_size of the one model

_f32_out = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)


def _chunks(x, nc: int, L: int):
    """``[T, ...] -> [nc, L, ...]``, zero-padded at the end of time."""
    pad = nc * L - x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x.reshape((nc, L) + x.shape[1:])


def _read_out(Cm, cum, start, like):
    """What a start state adds to the steps after it: ``exp(cum_i) start
    C_i``; ``Cm [..., L, G, N]``, ``cum [..., L, H]`` (the running sum of the
    log-decays), ``start [..., G, K, P, N]`` -> ``[..., L, G, K, P]``. The
    product's operands in ``like``'s type, the decay float32."""
    y = _f32_out("...ign,...gkpn->...igkp", Cm.astype(like.dtype),
                 start.astype(like.dtype))
    G, K = start.shape[-4:-2]
    return y * jnp.exp(cum).reshape(cum.shape[:-1] + (G, K, 1))


def _rows(t, G: int):
    """``[T, H] -> [G, K, T]``: time on the lanes, a group's heads together."""
    return t.T.reshape(G, t.shape[1] // G, t.shape[0])


def _lanes(s):
    """A state ``[H, P, N] -> [N, H P]``: states on the sublanes."""
    return s.transpose(2, 0, 1).reshape(s.shape[2], -1)


def _kernel_inputs(x, dt, A, B, Cm, D, L: int):
    """What both kernels read, in their layouts: ``x [T, H P]``, ``dt`` and
    the running sum of the log-decays ``dt A`` inside each chunk ``[G, K,
    T]``, ``B``, ``Cm [T, G N]``, ``D`` over a head's lanes ``[1, H P]``."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    cum = jnp.cumsum((dt * A).reshape(T // L, L, H), axis=1).reshape(T, H)
    return (x.reshape(T, H * P), _rows(dt, G), _rows(cum, G),
            B.reshape(T, G * N), Cm.reshape(T, G * N), jnp.repeat(D, P)[None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _fused(x, dt, A, B, Cm, D, s0, L):
    return _fused_fwd(x, dt, A, B, Cm, D, s0, L)[0]


def _fused_fwd(x, dt, A, B, Cm, D, s0, L):
    """The kernels' route: ``dt``, ``A``, ``D``, ``s0`` float32, ``B`` and
    ``Cm`` in ``x``'s type, ``T`` whole chunks of ``L``."""
    H, P, N = s0.shape
    y, starts, last = pallas_ssd.fused_forward(
        *_kernel_inputs(x, dt, A, B, Cm, D, L), _lanes(s0), L, P)
    return (y.reshape(x.shape), last.reshape(N, H, P).transpose(1, 2, 0)), \
        (x, dt, A, B, Cm, D, starts)


def _fused_bwd(L, res, cts):
    x, dt, A, B, Cm, D, starts = res
    dy, d_last = cts
    T, H, P = x.shape
    f32 = jnp.float32
    dx, ddt, dcum, dB, dC, dD, ds0 = pallas_ssd.fused_backward(
        *_kernel_inputs(x, dt, A, B, Cm, D, L), starts,
        dy.astype(f32).reshape(T, H * P), _lanes(d_last.astype(f32)), L, P)
    steps = lambda t: t.reshape(H, T).T  # [G, K, T] -> [T, H]
    # c_i = sum_{t <= i} dt_t A inside a chunk: each step's log-decay gathers
    # the cotangents of the running sums at and after it
    dl = jnp.flip(jnp.cumsum(jnp.flip(
        steps(dcum).reshape(T // L, L, H), 1), axis=1), 1).reshape(T, H)
    return (dx.reshape(x.shape), steps(ddt) + dl * A, (dl * dt).sum(0),
            dB.reshape(B.shape), dC.reshape(Cm.shape),
            dD.sum(0).reshape(H, P).sum(1),
            ds0.reshape(-1, H, P).transpose(1, 2, 0))


_fused.defvjp(_fused_fwd, _fused_bwd)


def ssd(x, dt, A, B, Cm, D, s0=None, *, chunk: int = SSD_CHUNK):
    """``(y [T, H, P] float32, the last state [H, P, N] float32)`` of the
    recurrence in the module docstring, differentiable in every argument
    (``s0`` too). On a TPU, where the shapes allow
    (:func:`pallas_ssd.applies`: whole chunks, lane tiles, blocks within the
    VMEM budget), forward and backward are the kernels of
    :mod:`dgraph_tpu.ops.pallas_ssd`, which keep the inputs and each chunk's
    start state; everywhere else (the CPU, every other shape) the ``lax``
    form, differentiated automatically, whose ``[T / L, H, L, L]`` float32
    decay matrix (268 MB at T 8192, H 64, L 128) is that route's alone.
    Counted a traced call: ``ssd.core_calls``, ``ssd.core_fused``."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    if H % G:
        raise ValueError(f"{H} heads do not divide into {G} groups of B and C")
    K = H // G
    L = max(1, min(chunk, T))
    nc = -(-T // L)
    f32 = jnp.float32
    dt = dt.astype(f32)
    if s0 is None:
        s0 = jnp.zeros((H, P, N), f32)
    fused = jax.default_backend() == "tpu" and pallas_ssd.applies(x, B, L)
    default_registry.counter("ssd.core_calls")
    if fused:
        default_registry.counter("ssd.core_fused")
        with jax.named_scope("chunk"):
            return _fused(x, dt, A.astype(f32), B.astype(x.dtype),
                          Cm.astype(x.dtype), D.astype(f32), s0.astype(f32), L)
    x_c = _chunks(x, nc, L).reshape(nc, L, G, K, P)
    B_c, C_c = (_chunks(t.astype(x.dtype), nc, L) for t in (B, Cm))
    dt_c = _chunks(dt, nc, L)  # [nc, L, H]
    with jax.named_scope("chunk"):
        cum = jnp.cumsum(dt_c * A.astype(f32), axis=1)  # c_i, <= 0 and falling
        rows = cum.transpose(0, 2, 1)  # [nc, H, L]
        later = jnp.tril(jnp.ones((L, L), bool))  # j <= i
        decay = jnp.exp(jnp.where(
            later, rows[..., :, None] - rows[..., None, :], -jnp.inf))
        cb = _f32_out("cign,cjgn->cgij", C_c, B_c)  # [nc, G, L, L]
        m = cb[:, :, None] * (decay * dt_c.transpose(0, 2, 1)[:, :, None, :]
                              ).reshape(nc, G, K, L, L)
        y = _f32_out("cgkij,cjgkp->cigkp", m.astype(x.dtype), x_c)
    with jax.named_scope("state"):
        last = cum[:, -1]  # [nc, H]: a chunk's whole log-decay
        w = (jnp.exp(last[:, None] - cum) * dt_c).reshape(nc, L, G, K, 1)
        ends = _f32_out("cjgkp,cjgn->cgkpn",
                        (x_c.astype(f32) * w).astype(x.dtype), B_c)

        def hop(s, z):
            whole, end = z
            return whole[:, None, None] * s + end, s  # emits the chunk's start

        final, starts = lax.scan(
            hop, s0.astype(f32),
            (jnp.exp(last), ends.reshape(nc, H, P, N)))
        y = y + _read_out(C_c, cum, starts.reshape(nc, G, K, P, N), x)
    y = y.reshape(nc * L, H, P)[:T]
    return y + D.astype(f32)[:, None] * x.astype(f32), final


def ssd_sequence(x, dt, A, B, Cm, D, comm=None, *, chunk: int = SSD_CHUNK):
    """``y [T_loc, H, P]`` float32 of the recurrence over the whole sequence,
    this rank holding rows ``[rank T_loc, (rank + 1) T_loc)`` of it (``comm``
    with a graph axis, inside ``shard_map``); on one device the operator
    itself."""
    if comm is None or comm.graph_axis is None:
        return ssd(x, dt, A, B, Cm, D, chunk=chunk)[0]
    axis = comm.graph_axis
    H, P = x.shape[1:]
    G, N = B.shape[1:]
    # the parameters vary over the axis from here on: their cotangents, a
    # partial sum a rank, are summed where this cast is transposed
    A, D, zero = (lax.pcast(t, axis, to="varying") for t in (
        A, D, jnp.zeros((H, P, N), jnp.float32)))
    y, end = ssd(x, dt, A, B, Cm, D, zero, chunk=chunk)
    with jax.named_scope("state"):
        cum = jnp.cumsum(dt.astype(jnp.float32) * A.astype(jnp.float32), 0)
        wholes, ends = (lax.all_gather(t, axis)
                        for t in (jnp.exp(cum[-1]), end))

        def hop(s, z):
            return z[0][:, None, None] * s + z[1], s  # the rank's start

        _, starts = lax.scan(hop, jnp.zeros_like(end), (wholes, ends))
        start = starts[lax.axis_index(axis)].reshape(G, H // G, P, N)
        return y + _read_out(Cm, cum, start, x).reshape(y.shape)
