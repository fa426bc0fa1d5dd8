"""The Mamba-2 recurrence's Pallas kernels (``ops/pallas_ssd.py``) in interpret
mode on the CPU: forward and every cotangent against the ``lax`` form and
against the loop by hand, low-precision streams, two calls chained through
the state, which shapes and backends take which route (with the counters
that say so), the VMEM budget, and ``ssd_sequence`` over a sharded sequence
with the kernels on every rank."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dgraph_tpu.obs.metrics import default_registry
from dgraph_tpu.ops import pallas_ssd as ps
from dgraph_tpu.ops import ssd as ssd_op
from test_nemotron_h import recurrence_by_hand, weighted

ARGS = ("x", "dt", "A", "B", "Cm", "D", "s0")


def ssd_inputs(T, H, Pd, G, N, seed=0):
    """As ``tests/test_nemotron_h.py::ssd_inputs``, with the step sizes and
    ``B``, ``C`` scaled so that 128 steps a chunk keep the sums near one."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(T, H, Pd), 0.1 * jax.nn.softplus(f(T, H)), -jnp.exp(f(H)),
            f(T, G, N) / 4, f(T, G, N) / 4, f(H), f(H, Pd, N))


def by_hand(x, dt, A, B, Cm, D, s0, piece=32):
    """``tests/test_nemotron_h.py``'s loop by hand, ``piece`` steps compiled
    at a time and chained through the state (hundreds of unrolled steps in
    one program cost XLA minutes)."""
    loop, ys = jax.jit(recurrence_by_hand), []
    for i in range(0, x.shape[0], piece):
        at = slice(i, i + piece)
        y, s0 = loop(x[at], dt[at], A, B[at], Cm[at], D, s0)
        ys.append(y)
    return jnp.concatenate(ys), s0


def counters():
    snap = default_registry.snapshot()["counters"]
    return snap.get("ssd.core_calls", 0.0), snap.get("ssd.core_fused", 0.0)


# (T, H, P, G, N, chunk): eight heads a lane tile; two; a head that is one
SHAPES = [(256, 16, 16, 2, 128, 128), (256, 4, 64, 2, 128, 128),
          (128, 2, 128, 1, 128, 128)]


@pytest.mark.parametrize("oracle", ["lax", "hand"])
@pytest.mark.parametrize("T,H,Pd,G,N,L", SHAPES,
                         ids=[f"T{t}-H{h}-P{p}-G{g}" for t, h, p, g, _, _ in SHAPES])
def test_kernels_are_the_lax_form_and_the_loop_by_hand(monkeypatch, oracle,
                                                       T, H, Pd, G, N, L):
    """The value of a loss through ``y`` and the last state, and every
    argument's cotangent (``s0``'s, and all through the last state too),
    from a NONZERO start state. (One case a shape and oracle, not one an
    argument: the workers of a run share no fixture.)"""
    args = ssd_inputs(T, H, Pd, G, N)
    w, w2 = ssd_inputs(T, H, Pd, G, N, seed=1)[0], \
        ssd_inputs(T, H, Pd, G, N, seed=1)[6]
    assert ps.applies(args[0], args[3], L)
    for name in ("fused_forward", "fused_backward"):
        monkeypatch.setattr(ps, name, functools.partial(
            getattr(ps, name), interpret=True))
    vg = lambda f: jax.value_and_grad(weighted(f, w, w2), tuple(range(7)))
    got = jax.jit(vg(lambda *a: ssd_op._fused(*a, L)))(*args)
    want = vg(by_hand)(*args) if oracle == "hand" else jax.jit(vg(
        lambda *a: ssd_op.ssd(*a, chunk=L)))(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, a, b in zip(ARGS, got[1], want[1]):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-4 * float(jnp.abs(b).max()), err_msg=name)


def test_kernels_take_low_precision_streams_and_keep_float32_inside(
        tpu_interpret):
    """bf16 ``x``, ``B``, ``C``: a rounding of each product's operands, the
    same ones as the ``lax`` form's; results, decays and states float32;
    every cotangent within the roundings of the float32 truth, the decays'
    (sums that cancel) too."""
    T, H, Pd, G, N, L = 256, 2, 64, 1, 128, 128
    x, dt, A, B, Cm, D, s0 = ssd_inputs(T, H, Pd, G, N, seed=2)
    w, w2 = ssd_inputs(T, H, Pd, G, N, seed=3)[0], \
        ssd_inputs(T, H, Pd, G, N, seed=3)[6]
    low = (x.astype(jnp.bfloat16), dt, A, B.astype(jnp.bfloat16),
           Cm.astype(jnp.bfloat16), D, s0)
    exact = tuple(a.astype(jnp.float32) for a in low)
    y, last = ssd_op.ssd(*low, chunk=L)
    assert y.dtype == jnp.float32 and last.dtype == jnp.float32
    y32, last32 = ssd_op.ssd(*exact, chunk=L)
    assert float(jnp.abs(y - y32).max()) > 0  # operands were rounded
    np.testing.assert_allclose(y, y32, rtol=0.05, atol=0.05)
    np.testing.assert_allclose(last, last32, rtol=0.05, atol=0.05)
    every = tuple(range(7))
    grads = jax.jit(jax.grad(weighted(
        lambda *a: ssd_op.ssd(*a, chunk=L), w, w2), every))
    got, truth = grads(*low), grads(*exact)
    assert [g.dtype for g in got] == [a.dtype for a in low]
    for name, a, b in zip(ARGS, got, truth):
        gap = float(jnp.linalg.norm((a.astype(jnp.float32) - b).ravel())
                    / jnp.linalg.norm(b.ravel()))
        assert gap < 0.01, (name, gap)
    # decays of 60 a step over a chunk: exp only of differences <= 0
    y, last = ssd_op.ssd(x, 60.0 * jnp.ones_like(dt), A - 1.0, B, Cm, D,
                         chunk=L)
    assert bool(jnp.isfinite(y).all() and jnp.isfinite(last).all())
    grads = jax.grad(lambda dt_: ssd_op.ssd(
        x, dt_, A - 1.0, B, Cm, D, chunk=L)[0].sum())(60.0 * jnp.ones_like(dt))
    assert bool(jnp.isfinite(grads).all())


def test_start_and_last_state_chain_two_calls_into_one(tpu_interpret):
    T, H, Pd, G, N, L = 256, 4, 64, 2, 128, 128
    x, dt, A, B, Cm, D, s0 = ssd_inputs(T, H, Pd, G, N, seed=4)
    whole, end = ssd_op.ssd(x, dt, A, B, Cm, D, s0, chunk=L)
    y1, mid = ssd_op.ssd(x[:L], dt[:L], A, B[:L], Cm[:L], D, s0, chunk=L)
    y2, end2 = ssd_op.ssd(x[L:], dt[L:], A, B[L:], Cm[L:], D, mid, chunk=L)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), whole, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(end2, end, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(mid).max()) > 0
    # no start state is a zero start state
    np.testing.assert_allclose(
        ssd_op.ssd(x, dt, A, B, Cm, D, chunk=L)[0],
        ssd_op.ssd(x, dt, A, B, Cm, D, jnp.zeros_like(s0), chunk=L)[0])


# (backend, T, H, P, G, N, chunk, x's type) -> the kernels or the lax form
ROUTES = [
    ("tpu", 256, 16, 16, 2, 128, 128, "float32", True),
    ("tpu", 256, 4, 64, 2, 128, 128, "bfloat16", True),
    ("tpu", 512, 8, 256, 4, 256, 256, "bfloat16", True),
    ("cpu", 256, 4, 64, 2, 128, 128, "float32", False),  # no TPU: never
    ("tpu", 200, 4, 64, 2, 128, 128, "float32", False),  # T, whole chunks
    ("tpu", 256, 4, 64, 2, 128, 64, "float32", False),  # a chunk, 128 lanes
    ("tpu", 64, 4, 64, 2, 128, 128, "float32", False),  # (T under a chunk)
    ("tpu", 256, 4, 64, 2, 64, 128, "float32", False),  # states, 128 lanes
    ("tpu", 256, 4, 16, 2, 128, 128, "float32", False),  # K P = 32 lanes
    ("tpu", 256, 4, 96, 1, 128, 128, "float32", False),  # 96 | 128? no
    ("tpu", 128, 8, 8, 2, 8, 32, "float32", False),  # the tiny preset's
    # the blocks grow with the chunk, the group's width and the states
    ("tpu", 4096, 16, 64, 2, 128, 1024, "bfloat16", False),
    ("tpu", 256, 64, 64, 1, 128, 128, "bfloat16", False),
    ("tpu", 256, 8, 64, 1, 2048, 128, "bfloat16", False),
]


@pytest.mark.parametrize(
    "backend,T,H,Pd,G,N,chunk,dtype,fused", ROUTES,
    ids=[f"{b}-T{t}-H{h}-P{p}-G{g}-N{n}-chunk{c}-{d}"
         for b, t, h, p, g, n, c, d, _ in ROUTES])
def test_route_follows_backend_and_shapes(monkeypatch, backend, T, H, Pd, G,
                                          N, chunk, dtype, fused):
    """What ``ssd`` traces, forward and backward, and what it counts:
    ``ssd.core_calls`` every time, ``ssd.core_fused`` for the kernels."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    args = jax.eval_shape(lambda: ssd_inputs(T, H, Pd, G, N))
    low = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.dtype(dtype))
    args = (low(args[0]), args[1], args[2], low(args[3]), low(args[4])) \
        + args[5:]
    assert ps.applies(args[0], args[3], min(chunk, T)) == (
        fused or backend != "tpu")
    before = counters()
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: ssd_op.ssd(*a, chunk=chunk)[0].sum().astype(jnp.float32),
        tuple(range(7))))(*args))
    assert tuple(np.subtract(counters(), before)) == (1.0, float(fused))
    assert jaxpr.count("pallas_call") == (2 if fused else 0)


def test_shapes_the_kernels_do_not_take_give_the_lax_forms_result(
        tpu_interpret):
    """On a TPU too: a ``T`` the chunk does not divide (padded with steps of
    dt = 0) and states off the lanes are the loop by hand's."""
    for T, H, Pd, G, N, L in [(200, 4, 64, 2, 128, 128),
                              (256, 4, 64, 2, 8, 128)]:
        args = ssd_inputs(T, H, Pd, G, N, seed=5)
        assert not ps.applies(args[0], args[3], L)
        before = counters()
        y, last = ssd_op.ssd(*args, chunk=L)
        assert tuple(np.subtract(counters(), before)) == (1.0, 0.0)
        wy, wl = by_hand(*args)
        np.testing.assert_allclose(y, wy, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(last, wl, rtol=1e-4, atol=1e-4)


# (chunk, K, P, N, x's bytes): what the kernels may hold, and whether they do
BLOCKS = [
    (128, 8, 64, 128, 2, True),  # the cell's: 6.25 MiB
    (256, 8, 64, 128, 2, True),
    (128, 8, 64, 256, 2, True),
    (512, 8, 64, 128, 2, True),  # 28.7 MiB
    (1024, 8, 64, 128, 2, False),
    (128, 64, 64, 128, 2, False),
    (128, 8, 64, 2048, 2, False),
]


@pytest.mark.parametrize("L,K,Pd,N,itemsize,fits", BLOCKS,
                         ids=[f"L{l}-K{k}-P{p}-N{n}-{i}B"
                              for l, k, p, n, i, _ in BLOCKS])
def test_the_vmem_limit_is_what_the_blocks_take(L, K, Pd, N, itemsize, fits):
    """``applies`` holds the blocks to the budget, and the limit handed to
    the compiler is what they take and a slack, not a chip's whole VMEM."""
    need = ps.vmem_bytes(L, K, Pd, N, itemsize)
    assert (need <= ps.VMEM_BUDGET) == fits
    x = jax.ShapeDtypeStruct((4 * L, K, Pd), jnp.dtype(f"float{8 * itemsize}"))
    assert ps.applies(x, jax.ShapeDtypeStruct((4 * L, 1, N), x.dtype), L) \
        == fits
    assert ps._params(L, K, Pd, N, itemsize).vmem_limit_bytes \
        == need + ps.VMEM_SLACK


def test_sharded_sequence_with_the_kernels_on_every_rank_equals_one_device(
        monkeypatch):
    """``ssd_sequence`` inside ``shard_map`` with the checker on, 4 virtual
    ranks of a chunk each, the kernels forced (the TPU interpreter): a
    ``pallas_call`` whose ``out_shape`` declares no ``vma`` does not trace
    there. The result and every gradient against one device's ``lax`` form."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm.collectives import shard_map_checks
    from dgraph_tpu.train import lm

    W, T, H, Pd, G, N, L = 4, 512, 4, 64, 2, 128, 128
    x, dt, A, B, Cm, D, _ = ssd_inputs(T, H, Pd, G, N, seed=6)
    w = ssd_inputs(T, H, Pd, G, N, seed=7)[0]
    comm4, mesh = lm.lm_comm(W), lm.lm_mesh(W, jax.devices()[:W])
    for name in ("fused_forward", "fused_backward"):
        monkeypatch.setattr(ps, name, functools.partial(
            getattr(ps, name), interpret=pltpu.InterpretParams()))
    calls = []
    real = ssd_op._fused
    monkeypatch.setattr(ssd_op, "_fused",
                        lambda *a: calls.append(a[0].shape) or real(*a))

    def sharded(x, dt, A, B, Cm, D):
        def rank(A, D, x, dt, B, Cm):
            y = ssd_op.ssd_sequence(x, dt, A, B, Cm, D, comm4, chunk=L)
            return y, jax.lax.psum((y * w_loc(y)).sum(), comm4.graph_axis)

        def w_loc(y):
            r = jax.lax.axis_index(comm4.graph_axis)
            return jax.lax.dynamic_slice_in_dim(w, r * y.shape[0], y.shape[0])

        return jax.shard_map(
            rank, mesh=mesh,
            in_specs=(P(), P()) + (P(comm4.graph_axis),) * 4,
            out_specs=(P(comm4.graph_axis), P()),
            **shard_map_checks(relax="test: the recurrence's gathered "
                                     "states"))(A, D, x, dt, B, Cm)

    args = (x, dt, A, B, Cm, D)
    every = tuple(range(6))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        y4, _ = jax.jit(sharded)(*args)
        g4 = jax.jit(jax.grad(lambda *a: sharded(*a)[1], every))(*args)
    assert calls and set(calls) == {(T // W, H, Pd)}
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    one = lambda *a: ssd_op.ssd_sequence(*a, None, chunk=L)
    np.testing.assert_allclose(y4, one(*args), rtol=1e-4, atol=1e-4)
    g1 = jax.grad(lambda *a: (one(*a) * w).sum(), every)(*args)
    for name, a, b in zip(ARGS, g4, g1):
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=1e-3 * float(jnp.abs(b).max()),
            err_msg=name)
