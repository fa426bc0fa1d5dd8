"""The selective scan's Pallas kernels (``ops/pallas_scan.py``) in interpret
mode on the CPU: forward and every cotangent against the loop by hand, the
kernel route against the ``lax`` route, low-precision streams, which shapes
and backends take which route (with the counters that say so), and the tiny
preset's train step, which keeps the ``lax`` route, against its parent's."""

import functools
import hashlib
import json
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from dgraph_tpu.obs.metrics import default_registry
from dgraph_tpu.ops import pallas_scan as ps
from dgraph_tpu.ops import selective_scan as ss
from dgraph_tpu.train import lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
ARGS = ("u", "delta", "A", "B", "Cm", "D", "s0")


def scan_inputs(T, C, N, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(T, C), jax.nn.softplus(f(T, C)), -jnp.exp(f(C, N)), f(T, N),
            f(T, N), f(C), f(C, N))


def scan_by_hand(u, delta, A, B, Cm, D, s0):
    s, ys = s0, []
    for t in range(u.shape[0]):
        s = jnp.exp(delta[t][:, None] * A) * s \
            + delta[t][:, None] * B[t][None] * u[t][:, None]
        ys.append((s * Cm[t][None]).sum(1) + D * u[t])
    return jnp.stack(ys), s


def weighted(f, w, w2):
    """A loss through both results: ``y`` and the last state."""
    def of(*a):
        y, last = f(*a)
        return (y * w).sum() + (last * w2).sum()
    return of


def counters():
    snap = default_registry.snapshot()["counters"]
    return snap.get("ssm.scan_calls", 0.0), snap.get("ssm.scan_fused", 0.0)


# (T, time block, channel block) at C = 256, N = 8: a T that is no multiple
# of the block, one that is, one block for all of time, one channel block
CASES = [(37, 8, 128), (40, 8, 256), (64, 16, 128), (24, 24, 128)]


@pytest.mark.parametrize("arg", range(8), ids=("forward",) + ARGS)
@pytest.mark.parametrize("T,bt,bc", CASES,
                         ids=[f"T{t}-bt{b}-bc{c}" for t, b, c in CASES])
def test_kernels_are_the_loop_by_hand(tpu_interpret, monkeypatch, T, bt, bc,
                                      arg):
    """Forward and each cotangent (``s0``'s, and all through the last state
    too) from a non-zero start state; the channel block forced through the
    budget it is derived from."""
    C, N = 256, 8
    monkeypatch.setattr(ps, "VMEM_BUDGET", ps.vmem_bytes(N, bt, bc, 4))
    assert ps.channel_block(C, N, bt, 4) == bc
    args = scan_inputs(T, C, N)
    w, w2 = scan_inputs(T, C, N, seed=1)[0], scan_inputs(T, C, N, seed=1)[6]
    fused = lambda *a: ss.selective_scan(*a, chunk=bt)
    before = counters()
    if arg == 0:
        (y, last), (wy, wl) = fused(*args), scan_by_hand(*args)
        np.testing.assert_allclose(y, wy, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(last, wl, rtol=1e-5, atol=1e-5)
    else:
        got = jax.grad(weighted(fused, w, w2), arg - 1)(*args)
        want = jax.grad(weighted(scan_by_hand, w, w2), arg - 1)(*args)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert tuple(np.subtract(counters(), before)) == (1.0, 1.0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_route_is_the_lax_route(monkeypatch, dtype):
    """One recurrence, two schedules: the same inputs through ``_scan`` with
    and without the kernels, results and all seven cotangents."""
    T, C, N, bt = 48, 128, 8, 16
    args = scan_inputs(T, C, N)
    args = (args[0].astype(dtype),) + args[1:]
    w, w2 = scan_inputs(T, C, N, seed=1)[0], scan_inputs(T, C, N, seed=1)[6]
    for name in ("fused_forward", "fused_backward"):
        monkeypatch.setattr(ps, name, functools.partial(
            getattr(ps, name), interpret=True))
    (v1, g1), (v0, g0) = [jax.value_and_grad(weighted(
        lambda *a: ss._scan(*a, bt, fused), w, w2), tuple(range(7)))(*args)
        for fused in (True, False)]
    np.testing.assert_allclose(v1, v0, rtol=1e-5)
    tol = 2e-2 if dtype == "bfloat16" else 2e-4  # du is rounded to u's type
    for name, a, b in zip(ARGS, g1, g0):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_allclose(a.astype(jnp.float32), b.astype(jnp.float32),
                                   rtol=tol, atol=tol, err_msg=name)


def test_kernels_take_low_precision_streams_and_keep_float32_inside(
        tpu_interpret):
    u, delta, A, B, Cm, D, _ = scan_inputs(64, 128, 8)
    ub = u.astype(jnp.bfloat16)
    y32, _ = ss.selective_scan(u, delta, A, B, Cm, D, chunk=16)
    y, last = ss.selective_scan(ub, delta, A, B, Cm, D, chunk=16)
    assert y.dtype == jnp.float32 and last.dtype == jnp.float32
    want, _ = scan_by_hand(ub.astype(jnp.float32), delta, A, B, Cm, D,
                           jnp.zeros_like(A))
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(y - y32).max()) > 0  # the stream was rounded, once
    g = jax.grad(lambda u_: ss.selective_scan(
        u_, delta, A, B, Cm, D, chunk=16)[0].sum())(ub)
    assert g.dtype == jnp.bfloat16


# (backend, T, C, N, chunk, u's type) -> the kernels or the lax form
ROUTES = [
    ("tpu", 32, 128, 8, 16, "float32", True),
    ("tpu", 32, 256, 16, 16, "bfloat16", True),
    ("tpu", 1024, 128, 16, 512, "bfloat16", True),
    ("cpu", 32, 128, 8, 16, "float32", False),  # no TPU: never
    ("tpu", 32, 128, 4, 16, "float32", False),  # the tiny preset's N
    ("tpu", 32, 96, 8, 16, "float32", False),  # channels, 128 lanes
    ("tpu", 32, 128, 12, 16, "float32", False),  # states, 8 sublanes
    ("tpu", 32, 128, 8, 12, "float32", False),  # a block that is no whole tiles
    ("tpu", 32, 128, 8, 8, "bfloat16", False),  # 16 bf16 rows a sublane tile
    # a chunk of all of time: its states and decays at one lane tile (134 MB
    # of them at 8192 steps) are over the budget, whatever VMEM the chip has
    ("tpu", 1024, 128, 16, 1024, "bfloat16", False),
    ("tpu", 8192, 128, 16, 8192, "float32", False),
]


@pytest.mark.parametrize(
    "backend,T,C,N,chunk,dtype,fused", ROUTES,
    ids=[f"{b}-T{t}-C{c}-N{n}-chunk{k}-{d}" for b, t, c, n, k, d, _ in ROUTES])
def test_route_follows_backend_and_shapes(monkeypatch, backend, T, C, N, chunk,
                                          dtype, fused):
    """What ``selective_scan`` traces, forward and backward, and what it
    counts: ``ssm.scan_calls`` every time, ``ssm.scan_fused`` for the
    kernels."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    args = scan_inputs(T, C, N)
    args = (args[0].astype(dtype),) + args[1:]
    before = counters()
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: ss.selective_scan(*a, chunk=chunk)[0].sum(),
        tuple(range(7))))(*args))
    assert tuple(np.subtract(counters(), before)) == (1.0, float(fused))
    assert jaxpr.count("pallas_call") == (2 if fused else 0)


def test_kernels_inside_shard_map_with_the_checker_on(monkeypatch):
    """On chips a sharded sequence scans rank by rank inside ``shard_map``
    (``scan_sequence``): a ``pallas_call`` whose ``out_shape`` declares no
    ``vma`` does not trace there. Two ranks, each its own shard, forward and
    all seven cotangents against the ``lax`` route in the same ``shard_map``
    (the TPU interpreter, as ``tests/test_pallas_shard_map.py``)."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm.collectives import shard_map_checks
    from dgraph_tpu.comm.mesh import GRAPH_AXIS, make_graph_mesh

    W, T, C, N, bt = 2, 32, 128, 8, 16
    mesh = make_graph_mesh(W, devices=jax.devices()[:W])
    args = [jnp.stack(x) for x in zip(*(scan_inputs(T, C, N, seed=r)
                                        for r in range(W)))]
    for name in ("fused_forward", "fused_backward"):
        monkeypatch.setattr(ps, name, functools.partial(
            getattr(ps, name), interpret=pltpu.InterpretParams()))

    def run(fused):
        def loss(*a):
            def rank(*shard):
                y, last = ss._scan(*(x[0] for x in shard), bt, fused)
                return jnp.concatenate([y, last.T])[None]
            out = jax.shard_map(
                rank, mesh=mesh, in_specs=tuple(P(GRAPH_AXIS) for _ in a),
                out_specs=P(GRAPH_AXIS), **shard_map_checks())(*a)
            return (out ** 2).sum(), out
        return jax.jit(jax.value_and_grad(
            loss, tuple(range(7)), has_aux=True))(*args)

    ((_, out1), g1), ((_, out0), g0) = run(True), run(False)
    np.testing.assert_allclose(out1, out0, rtol=1e-4, atol=1e-4)
    for name, a, b in zip(ARGS, g1, g0):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3, err_msg=name)


# (C, N, time block, u's bytes) -> lanes a channel block, None if none fits
BLOCKS = [
    (5120, 16, 128, 2, 1024),  # the cell's: 24.4 MiB, 16.8 of them states
    (5120, 16, 512, 2, 256),
    (384, 8, 8, 4, 128),  # 128 * 2^k that divide C
    (4096, 8, 8, 4, 4096),
    (5120, 16, 1024, 2, None),  # one lane tile is over the budget
    (128, 8, 4096, 4, None),
]


@pytest.mark.parametrize("C,N,bt,itemsize,bc", BLOCKS,
                         ids=[f"C{c}-N{n}-bt{b}-{i}B" for c, n, b, i, _ in BLOCKS])
def test_channel_block_follows_the_budget(C, N, bt, itemsize, bc):
    """The widest block whose buffers fit, and with it the limit handed to
    the compiler: what the blocks take and a slack, under budget + slack."""
    assert ps.channel_block(C, N, bt, itemsize) == bc
    if bc is None:
        assert ps.vmem_bytes(N, bt, ps.LANES, itemsize) > ps.VMEM_BUDGET
        return
    limit = ps._params(N, bt, bc, itemsize).vmem_limit_bytes
    assert limit == ps.vmem_bytes(N, bt, bc, itemsize) + ps.VMEM_SLACK
    assert limit <= ps.VMEM_BUDGET + ps.VMEM_SLACK
    if C % (2 * bc) == 0:  # the next block up would not fit
        assert ps.vmem_bytes(N, bt, 2 * bc, itemsize) > ps.VMEM_BUDGET


# sha256[:16] of (the parameter tree's shapes, the train step's jaxpr) of
# phi4_mini_flash's tiny preset at commit 1eb632a (PR 39), before the scan
# had a second route: N = 4 keeps the lax form, so that program; its exit
# loss is one loop since PR 48 (daef51665e9b5afa until then).
PARENT_TINY = ("7a2acd26e2122411", "3465530399e477c6")


def test_tiny_preset_is_the_parents_program():
    from benchmark.builders.phi4flash import model_of

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi4_mini_flash.json")) as f:
        size = json.load(f)["tiny"]
    model, T = model_of(size, lm.lm_comm(1)), size["seq_len"]
    opt = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    mesh = lm.lm_mesh(1)
    tr = lm.lm_setup(model, opt, mesh, model.comm, seq_len=T, seed=0)
    tree = str(jax.tree.map(lambda a: (a.shape, str(a.dtype)), tr.params))
    before = counters()
    with jax.set_mesh(mesh):
        jaxpr = str(jax.make_jaxpr(
            lambda p, o, b: tr.train_step.__wrapped__(p, o, b))(
                tr.params, tr.opt_state, jnp.zeros(T, jnp.int32)))
    jaxpr = re.sub(r"0x[0-9a-f]+", "0x", jaxpr)
    digest = lambda s: hashlib.sha256(s.encode()).hexdigest()[:16]
    assert (digest(tree), digest(jaxpr)) == PARENT_TINY
    calls, fused = np.subtract(counters(), before)
    assert calls >= 2 and fused == 0  # two state-space layers, both lax
