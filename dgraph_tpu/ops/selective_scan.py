"""The selective state-space scan (Mamba-1; Gu & Dao 2023) with its own
backward, in two schedules of one recurrence: chunked in plain ``lax``, and
step by step in Pallas kernels that keep the state on chip.

For inputs ``u, delta [T, C]``, ``A [C, N]`` (negative), ``B, Cm [T, N]``,
``D [C]`` and a start state ``s_{-1} = s0 [C, N]`` (zeros if None), per
channel c and state n:

    s_t[c, n] = exp(delta_t[c] A[c, n]) s_{t-1}[c, n] + delta_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n Cm_t[n] s_t[c, n] + D[c] u_t[c]

:func:`selective_scan` returns ``(y [T, C] float32, s_{T-1} [C, N])``. The
``[T, C, N]`` states (2.7 GB at T 8192, C 5120, N 16) are never whole. The
``lax`` form, which runs anywhere and is the oracle of the other:

- forward: time is cut into chunks of ``chunk`` steps. Every chunk runs its
  steps from a ZERO state, all chunks side by side (``chunk`` sequential
  iterations over ``[T / chunk, N, C]``, not T); the true state at each
  chunk's start follows from the chunks' end states and whole decays
  ``exp(A sum delta)`` in ``T / chunk`` hops, and what it adds to each step's
  output, ``sum_n Cm_t[n] exp(A[c, n] cumsum(delta)_t[c]) start[c, n]``, is one
  fused pass. Decays only ever multiply (no division by one), so nothing
  overflows. Kept for the backward: the inputs and the start states
  ``[T / chunk, N, C]``.
- backward: the forward mirrored in time. What flows into each chunk's END,
  ``G = a_{t+1} dL/ds_{t+1}``, comes from every chunk's reverse steps from a
  zero ``G`` (side by side) and ``T / chunk`` hops. Then ``SCAN_GROUP`` chunks
  at a time, side by side: their states are recomputed from their kept
  starts (one ``[chunk, group, N, C]`` buffer), and one reverse loop over
  their steps forms ``g_t = Cm_t dy_t + G`` and every cotangent from ``g_t``,
  ``s_{t-1}`` and ``a_t``. No loop runs over more than ``chunk`` steps.

Its steps are vector-unit work in ``while`` loops whose carries go through
HBM every iteration. On a TPU, where the shapes allow (channels a multiple
of 128 lanes, states of 8 sublanes, a ``chunk`` of whole sublane tiles whose
buffers at one lane tile fit the kernels' VMEM budget:
:func:`pallas_scan.applies`), forward and backward are instead the kernels of
:mod:`dgraph_tpu.ops.pallas_scan`: the sequential recurrence itself, ``chunk``
steps a time block, the ``[N, C]`` state (and the backward's ``G`` and ``dA``)
in VMEM for the whole of time; no zero-state chunks, hops or correction
pass. The route is decided a call from the backend and the shapes, and
counted (``ssm.scan_calls``, ``ssm.scan_fused``); both routes keep the inputs
and the start states ``[T / chunk, N, C]`` for the backward.

Everything inside is float32 whatever the streams' types; a ``T`` that is no
multiple of ``chunk`` is padded with steps of ``delta = 0`` (decay 1, input
0: the state passes through).

:func:`scan_sequence` is the operator over a sequence sharded on a mesh axis:
each rank scans its shard from a zero state, the ranks' end states and whole
decays are gathered, and every rank folds the ones before it into its own
start state. The state crosses the ranks in order; nothing is a halo.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from dgraph_tpu.obs.metrics import default_registry
from dgraph_tpu.ops import pallas_scan

# Steps a chunk (both routes: the kernels' time block too), and for the lax
# route alone chunks the backward takes side by side (its stored states are
# [SCAN_CHUNK, SCAN_GROUP, N, C] float32: 335 MB at N 16, C 5120) and steps of
# a sequential loop traced into one body: what the chip measured fastest for
# the lax route at T 8192, C 5120, N 16 (the kernels there: 2.94 / 9.27,
# PERF.md section 6, PR 41); as (chunk, group, unroll), forward / forward +
# backward in ms (PERF.md section 6, PR 39): (128, 8, 4) 11.86 / 28.46; (256, 4, 4)
# 11.67 / 32.39; (256, 8, 4) 11.70 / 32.51; (256, 1, 4) 11.68 / 39.57; (512,
# 2, 4) 11.30 / 37.52; (256, 4, 1) 19.60 / 33.42; (256, 4, 8) 14.15 / 34.33.
SCAN_CHUNK = 128
SCAN_GROUP = 8
SCAN_UNROLL = 4


def _wide(x):
    """``[chunks, C]`` against ``[chunks, N, C]``."""
    return x[:, None, :]


def _tall(x):
    """``[chunks, N]`` against ``[chunks, N, C]``."""
    return x[:, :, None]


def _chunks(x, nc: int, L: int):
    """``[T, k] -> [nc, L, k]``, zero-padded at the end of time."""
    pad = nc * L - x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x.reshape((nc, L) + x.shape[1:])


def _start_adds(Cm, cum, At, start):
    """What a chunk's start state adds to its outputs: ``[L, C]`` from
    ``Cm [L, N]``, the running sum of delta ``cum [L, C]`` and ``start
    [N, C]``."""
    return (Cm[:, :, None] * jnp.exp(cum[:, None, :] * At) * start).sum(1)


def _forward(u, delta, At, B, Cm, s0, L: int):
    """(y without the ``D u`` term [T, C], start state of every chunk
    [nc, N, C], the last state [N, C]); ``At`` is ``A`` transposed, states are
    ``[N, C]`` (channels on the lanes)."""
    T, C = u.shape
    nc = -(-T // L)
    f32 = lambda x: x.astype(jnp.float32)
    d_c, du_c, B_c, C_c = (_chunks(x, nc, L) for x in (
        delta, delta * f32(u), f32(B), f32(Cm)))
    steps = lambda x: x.swapaxes(0, 1)  # [L, nc, .]: one step of every chunk

    def step(s, x):
        d, du, b, c = x
        s = jnp.exp(_wide(d) * At) * s + _tall(b) * _wide(du)
        return s, (_tall(c) * s).sum(1)

    with jax.named_scope("chunks"):
        ends, y_loc = lax.scan(  # (zeros that vary over a mesh axis as s0)
            step, jnp.broadcast_to(s0 * 0.0, (nc,) + At.shape),
            (steps(d_c), steps(du_c), steps(B_c), steps(C_c)),
            unroll=SCAN_UNROLL)
    with jax.named_scope("carry"):
        cum = jnp.cumsum(d_c, axis=1)

        def hop(s, x):
            decay, end = x
            return decay * s + end, s  # emits the state the chunk starts from

        last, starts = lax.scan(
            hop, s0, (jnp.exp(cum[:, -1, None, :] * At), ends))
        # a chunk at a time: the [L, N, C] factor is bounded if it is not fused
        y = steps(y_loc) + lax.map(
            lambda x: _start_adds(x[0], x[1], At, x[2]), (C_c, cum, starts))
    return y.reshape(nc * L, C)[:T], starts, last


def _backward(u, delta, At, B, Cm, starts, dy, d_last, L: int):
    """Cotangents ``(du [T, C] without the D term, ddelta [T, C], dAt [N, C],
    dB [T, N], dCm [T, N], ds0 [N, C])``."""
    T, C = u.shape
    nc = starts.shape[0]
    f32 = lambda x: x.astype(jnp.float32)
    d_c, u_c, B_c, C_c, dy_c = (_chunks(f32(x), nc, L)
                                for x in (delta, u, B, Cm, dy))
    steps = lambda x: x.swapaxes(0, 1)  # [L, chunks, .]

    # what flows into each chunk's END from the chunks after it, G = a_{t+1}
    # dL/ds_{t+1}: as the forward's start states, mirrored in time
    def tail(G, x):
        d, c, dyt = x
        return jnp.exp(_wide(d) * At) * (_tall(c) * _wide(dyt) + G), None

    with jax.named_scope("flows"):
        outs, _ = lax.scan(
            tail, jnp.broadcast_to(d_last * 0.0, starts.shape),
            (steps(d_c), steps(C_c), steps(dy_c)), reverse=True,
            unroll=SCAN_UNROLL)

        def hop(G, x):
            decay, out = x
            return out + decay * G, G  # emits what flows INTO the chunk's end

        d_s0, flows = lax.scan(
            hop, d_last, (jnp.exp(_wide(d_c.sum(1)) * At), outs), reverse=True)

    # groups of chunks side by side, each from its kept start state and the
    # flow into its end: the states before each step redone and stored
    # ([L, group, N, C]), then one reverse loop that forms every cotangent
    group = max(g for g in range(1, min(SCAN_GROUP, nc) + 1) if nc % g == 0)

    def one_group(x):
        d_g, u_g, B_g, C_g, dy_g = (steps(t) for t in x[:5])
        start, flow = x[5:]
        du_g = d_g * u_g

        def redo(s, x):  # emits the state BEFORE the step
            d, du, b = x
            return jnp.exp(_wide(d) * At) * s + _tall(b) * _wide(du), s

        with jax.named_scope("redo"):
            _, before = lax.scan(redo, start, (d_g, du_g, B_g),
                                 unroll=SCAN_UNROLL)

        def back(carry, x):
            G, dAt = carry
            d, uu, du, b, c, dyt, s = x
            a = jnp.exp(_wide(d) * At)
            g = _tall(c) * _wide(dyt) + G  # dL/ds_t
            gsa = g * s * a  # dL/da_t * a_t
            gb = (g * _tall(b)).sum(1)  # [group, C]
            out = ((gsa * At).sum(1) + gb * uu,  # ddelta_t
                   gb * d,  # du_t
                   (g * _wide(du)).sum(2),  # dB_t
                   ((a * s + _tall(b) * _wide(du))  # dCm_t
                    * _wide(dyt)).sum(2))
            return (a * g, dAt + gsa * _wide(d)), out

        with jax.named_scope("back"):
            (_, dAt), out = lax.scan(
                back, (flow, flow * 0.0),
                (d_g, u_g, du_g, B_g, C_g, dy_g, before), reverse=True,
                unroll=SCAN_UNROLL)
        return dAt.sum(0), tuple(steps(o) for o in out)

    grouped = lambda x: x.reshape((nc // group, group) + x.shape[1:])
    dAt, outs = lax.map(one_group, tuple(
        grouped(x) for x in (d_c, u_c, B_c, C_c, dy_c, starts, flows)))
    d_delta, d_u, d_B, d_C = (o.reshape((nc * L,) + o.shape[3:])[:T]
                              for o in outs)
    return d_u, d_delta, dAt.sum(0), d_B, d_C, d_s0


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _scan(u, delta, A, B, Cm, D, s0, chunk, fused):
    return _scan_fwd(u, delta, A, B, Cm, D, s0, chunk, fused)[0]


def _scan_fwd(u, delta, A, B, Cm, D, s0, chunk, fused):
    if fused:
        with jax.named_scope("fused_fwd"):
            y, starts, last = pallas_scan.fused_forward(
                u, delta, A.T, B, Cm, D, s0.T, chunk)
    else:
        y, starts, last = _forward(u, delta, A.T, B, Cm, s0.T, chunk)
        y = y + D * u.astype(jnp.float32)
    return (y, last.T), (u, delta, A, B, Cm, D, starts)


def _scan_bwd(chunk, fused, res, cts):
    u, delta, A, B, Cm, D, starts = res
    dy, d_last = cts
    dy = dy.astype(jnp.float32)
    if fused:
        with jax.named_scope("fused_bwd"):
            d_u, d_delta, dAt, d_B, d_C, d_D, d_s0 = \
                pallas_scan.fused_backward(
                    u, delta, A.T, B, Cm, D, starts, dy,
                    d_last.astype(jnp.float32).T, chunk)
        return (d_u, d_delta, dAt.T, d_B.astype(B.dtype),
                d_C.astype(Cm.dtype), d_D, d_s0.T)
    d_u, d_delta, dAt, d_B, d_C, d_s0 = _backward(
        u, delta, A.T, B, Cm, starts, dy, d_last.astype(jnp.float32).T, chunk)
    u32 = u.astype(jnp.float32)
    return ((d_u + D * dy).astype(u.dtype), d_delta.astype(delta.dtype),
            dAt.T.astype(A.dtype), d_B.astype(B.dtype), d_C.astype(Cm.dtype),
            (dy * u32).sum(0).astype(D.dtype), d_s0.T)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u, delta, A, B, Cm, D, s0=None, *, chunk: int = SCAN_CHUNK):
    """``(y [T, C] float32, the last state [C, N] float32)`` of the
    recurrence in the module docstring; differentiable in every argument
    (``s0`` too) through its own backward. ``delta`` is float32. On a TPU,
    where the shapes allow (:func:`pallas_scan.applies`), forward and
    backward are the kernels of :mod:`dgraph_tpu.ops.pallas_scan` with
    ``chunk`` the steps of a time block; everywhere else the chunked ``lax``
    form. Counted a traced call: ``ssm.scan_calls``, ``ssm.scan_fused``."""
    if s0 is None:
        s0 = jnp.zeros(A.shape, jnp.float32)
    chunk = max(1, min(chunk, u.shape[0]))
    fused = jax.default_backend() == "tpu" and pallas_scan.applies(u, A, chunk)
    default_registry.counter("ssm.scan_calls")
    if fused:
        default_registry.counter("ssm.scan_fused")
    return _scan(u, delta.astype(jnp.float32), A.astype(jnp.float32), B, Cm,
                 D.astype(jnp.float32), s0.astype(jnp.float32), chunk, fused)


def scan_sequence(u, delta, A, B, Cm, D, comm=None, *,
                  chunk: int = SCAN_CHUNK):
    """``y [T_loc, C]`` float32 of the scan over the whole sequence, this
    rank holding rows ``[rank T_loc, (rank + 1) T_loc)`` of it (``comm`` with
    a graph axis, inside ``shard_map``); on one device the operator itself."""
    if comm is None or comm.graph_axis is None:
        return selective_scan(u, delta, A, B, Cm, D, chunk=chunk)[0]
    axis = comm.graph_axis
    # the parameters vary over the axis from here on: their cotangents, a
    # partial sum a rank, are summed where this cast is transposed
    A, D, zero = (lax.pcast(x, axis, to="varying") for x in (
        A, D, jnp.zeros(A.shape, jnp.float32)))
    y, end = selective_scan(u, delta, A, B, Cm, D, zero, chunk=chunk)
    A32, d32 = A.astype(jnp.float32), delta.astype(jnp.float32)
    decay = jnp.exp(A32 * d32.sum(0)[:, None])  # of the whole shard, [C, N]
    decays, ends = (lax.all_gather(x, axis) for x in (decay, end))

    def hop(s, x):
        return x[0] * s + x[1], s  # emits the state the rank starts from

    _, starts = lax.scan(hop, jnp.zeros_like(end), (decays, ends))
    start = starts[lax.axis_index(axis)]
    adds = jax.checkpoint(_start_adds)(  # its [T_loc, N, C] factor is redone
        Cm.astype(jnp.float32), jnp.cumsum(d32, axis=0), A32.T, start.T)
    return y + adds
