"""Ring attention (sequence/context parallelism) vs the dense oracle.

The reference has no sequence-parallel primitive (SURVEY.md §2.3); these
tests pin our addition: 8-way ring attention must equal dense attention on
the gathered sequence — values AND gradients — for causal and masked
variants, with the backward emitting ring comm via AD (no hand transpose).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dgraph_tpu.parallel.sequence import (
    dense_attention,
    ring_attention,
    ring_attention_sharded,
)

W = 8
T, H, D = 64, 4, 16  # T_loc = 8 per shard


def _mesh():
    devs = jax.devices()
    if len(devs) < W:
        pytest.skip(f"need {W} devices")
    return Mesh(np.array(devs[:W]), ("seq",))


def _qkv(seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((T, H, D)), jnp.float32)
        for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_equals_dense(causal):
    mesh = _mesh()
    q, k, v = _qkv()
    out_ring = ring_attention_sharded(q, k, v, mesh, causal=causal)
    out_dense = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out_ring), np.asarray(out_dense), rtol=2e-5, atol=2e-5
    )


def test_ring_kv_mask():
    """Padded tail positions are excluded exactly like the dense mask."""
    mesh = _mesh()
    q, k, v = _qkv(1)
    valid = 50  # last 14 positions are padding
    kv_mask = (jnp.arange(T) < valid).astype(jnp.float32)

    out_ring = ring_attention_sharded(q, k, v, mesh, kv_mask=kv_mask)
    out_dense = dense_attention(q, k, v, kv_mask=kv_mask)
    np.testing.assert_allclose(
        np.asarray(out_ring)[:valid], np.asarray(out_dense)[:valid],
        rtol=2e-5, atol=2e-5,
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_gradients_equal_dense(causal):
    """jax.grad through the ring (scan + ppermute) equals dense-attention
    gradients: AD's transpose of the ring IS the ring backward."""
    mesh = _mesh()
    q, k, v = _qkv(2)
    tgt = jnp.asarray(np.random.default_rng(3).standard_normal((T, H, D)),
                      jnp.float32)

    def loss_ring(q, k, v):
        out = ring_attention_sharded(q, k, v, mesh, causal=causal)
        return ((out - tgt) ** 2).sum()

    def loss_dense(q, k, v):
        out = dense_attention(q, k, v, causal=causal)
        return ((out - tgt) ** 2).sum()

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gr, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5,
            err_msg=f"d{name}",
        )


def _walk_eqns(jx):
    """Every eqn in a jaxpr INCLUDING nested sub-jaxprs (shard_map body,
    scan body, custom_vjp calls, ...)."""
    for eqn in jx.eqns:
        yield eqn
        for p in eqn.params.values():
            items = p if isinstance(p, (list, tuple)) else [p]
            for item in items:
                inner = getattr(item, "jaxpr", None)  # ClosedJaxpr
                if inner is not None:
                    yield from _walk_eqns(inner)
                elif hasattr(item, "eqns"):  # raw Jaxpr
                    yield from _walk_eqns(item)


def test_ring_memory_is_blockwise():
    """Structural pin: NO intermediate anywhere in the (recursively walked)
    jaxpr may hold more elements than ~2 K/V blocks — a regression that
    all-gathers K/V ([T, H, D] = W x bigger) or attends densely
    ([T_loc, H, T]) would exceed it. The whole point of the ring is
    O(T_loc) memory per device."""
    mesh = _mesh()
    q, k, v = _qkv(4)
    fn = shard_map(
        functools.partial(ring_attention, axis_name="seq"),
        mesh=mesh,
        in_specs=(P("seq"), P("seq"), P("seq")),
        out_specs=P("seq"),
    )
    jaxpr = jax.make_jaxpr(fn)(q, k, v)
    # outer jaxpr avals are GLOBAL shapes; the memory claim is about the
    # per-shard program, i.e. the shard_map body's jaxpr
    bodies = [
        e for e in jaxpr.jaxpr.eqns if "shard_map" in e.primitive.name
    ]
    assert bodies, "no shard_map eqn found"
    body = bodies[0].params["jaxpr"]
    t_loc = T // W
    block_elems = t_loc * H * D  # one K/V block
    limit = 2 * block_elems  # dense logits [t_loc, H, T] = 4x; K gathered = Wx
    seen = 0
    for eqn in _walk_eqns(getattr(body, "jaxpr", body)):
        for var in eqn.outvars:
            shape = getattr(getattr(var, "aval", None), "shape", ())
            seen += 1
            assert int(np.prod(shape, initial=1)) <= limit, (
                f"over-budget intermediate {shape} in ring jaxpr "
                f"(> {limit} elems = 2 K/V blocks)"
            )
    assert seen > 20, "jaxpr walk saw suspiciously few eqns — recursion broken?"


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_equals_dense(causal):
    """The all-to-all (head-scatter) lowering is exact too."""
    from dgraph_tpu.parallel.sequence import ulysses_attention

    mesh = _mesh()
    H8 = 8  # heads must divide by the axis size
    rng = np.random.default_rng(7)
    q, k, v = (
        jnp.asarray(rng.standard_normal((T, H8, D)), jnp.float32)
        for _ in range(3)
    )
    fn = shard_map(
        functools.partial(ulysses_attention, axis_name="seq", causal=causal),
        mesh=mesh,
        in_specs=(P("seq"), P("seq"), P("seq")),
        out_specs=P("seq"),
    )
    out = fn(q, k, v)
    want = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_kv_mask_and_grads():
    from dgraph_tpu.parallel.sequence import ulysses_attention

    mesh = _mesh()
    H8 = 8
    rng = np.random.default_rng(8)
    q, k, v = (
        jnp.asarray(rng.standard_normal((T, H8, D)), jnp.float32)
        for _ in range(3)
    )
    kv_mask = (jnp.arange(T) < 50).astype(jnp.float32)
    fn = shard_map(
        lambda q, k, v, m: ulysses_attention(q, k, v, "seq", kv_mask=m),
        mesh=mesh,
        in_specs=(P("seq"),) * 4,
        out_specs=P("seq"),
    )

    def loss_u(q, k, v):
        return ((fn(q, k, v, kv_mask)[:50]) ** 2).sum()

    def loss_d(q, k, v):
        return ((dense_attention(q, k, v, kv_mask=kv_mask)[:50]) ** 2).sum()

    gu = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gu, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5, err_msg=f"d{name}")


def test_comm_seq_attention_impl_routing():
    """comm.seq_attention(impl=...) — ring and ulysses agree with the dense
    oracle through the facade, and unknown impls raise."""
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm import Communicator

    mesh = _mesh()
    rng = np.random.default_rng(5)
    q, k, v = (
        jnp.asarray(rng.standard_normal((T, 8, D)), jnp.float32)
        for _ in range(3)
    )  # 8 heads: ulysses needs heads % axis == 0
    comm = Communicator.init_process_group("tpu", world_size=W,
                                           graph_axis="seq")
    want = dense_attention(q, k, v, causal=True)
    for impl in ("ring", "ulysses"):
        fn = jax.shard_map(
            lambda q, k, v: comm.seq_attention(q, k, v, causal=True,
                                               impl=impl),
            mesh=mesh, in_specs=(P("seq"),) * 3, out_specs=P("seq"),
            check_vma=False,
        )
        with jax.set_mesh(mesh):
            got = fn(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
            err_msg=impl,
        )
    with pytest.raises(ValueError, match="unknown seq_attention impl"):
        fn = jax.shard_map(
            lambda q, k, v: comm.seq_attention(q, k, v, impl="bogus"),
            mesh=mesh, in_specs=(P("seq"),) * 3, out_specs=P("seq"),
            check_vma=False,
        )
        with jax.set_mesh(mesh):
            fn(q, k, v)


def test_flash_gating_off_tpu():
    """On CPU the flash path must NEVER engage — not in auto, and (since
    the r3 hardening) not even when the flag is pinned True: the kernel
    is Mosaic-only and a pinned flag copied from a TPU runbook must not
    crash CPU runs. Auto additionally requires the chip self-check latch."""
    from dgraph_tpu import config as cfg
    from dgraph_tpu.parallel import sequence as seq

    q = jnp.zeros((256, 2, 128), jnp.float32)
    old = cfg.use_flash_attention
    try:
        cfg.set_flags(use_flash_attention=None)  # auto
        assert seq._flash_applicable(q) is False
        cfg.set_flags(use_flash_attention=True)  # pinned — still CPU
        assert seq._flash_applicable(q) is False
        assert seq._flash_applicable(q, require_pinned=True) is False
    finally:
        cfg.set_flags(use_flash_attention=old)
    assert seq.flash_attention_selfcheck() is False  # off-TPU: no verdict
    assert seq._flash_verified is False  # and the auto latch stays cold


def test_flash_shape_gate(monkeypatch):
    """The T%128 / D%128 shape gate, exercised on CPU by faking the
    backend (the real backend check short-circuits first otherwise — a
    broken shape gate must not wait for a scarce TPU window to surface)."""
    from dgraph_tpu import config as cfg
    from dgraph_tpu.parallel import sequence as seq

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    old = cfg.use_flash_attention
    try:
        cfg.set_flags(use_flash_attention=True)  # pinned
        # the pin stands in for no self-check: the flash kernels' own latch
        assert seq._flash_applicable(jnp.zeros((256, 2, 128))) is False
        monkeypatch.setattr(seq, "_flash_verified", True)
        assert seq._flash_applicable(jnp.zeros((256, 2, 128))) is True
        assert seq._flash_applicable(
            jnp.zeros((256, 2, 128)), require_pinned=True) is True
        assert seq._flash_applicable(jnp.zeros((250, 2, 128))) is False
        assert seq._flash_applicable(jnp.zeros((256, 2, 64))) is False
        # auto (None) needs the self-check latch as well
        cfg.set_flags(use_flash_attention=None)
        assert seq._flash_applicable(jnp.zeros((256, 2, 128))) is True
        monkeypatch.setattr(seq, "_flash_verified", False)
        assert seq._flash_applicable(jnp.zeros((256, 2, 128))) is False
    finally:
        cfg.set_flags(use_flash_attention=old)


@pytest.mark.parametrize("causal", [False, True])
def test_padded_rows_zero_in_every_impl(causal):
    """The shared contract (_zero_padded_rows): padded QUERY rows are zero
    in every implementation, so FULL tensors agree across impls — not just
    the real-row prefix the other mask tests slice to (flash is TPU-only
    and carries the same zeroing in _flash_dense)."""
    from dgraph_tpu.parallel.sequence import ulysses_attention

    mesh = _mesh()
    H8 = 8
    rng = np.random.default_rng(11)
    q, k, v = (
        jnp.asarray(rng.standard_normal((T, H8, D)), jnp.float32)
        for _ in range(3)
    )
    valid = 41
    kv_mask = (jnp.arange(T) < valid).astype(jnp.float32)

    out_dense = dense_attention(q, k, v, causal=causal, kv_mask=kv_mask)
    pad = np.asarray(out_dense)[valid:]
    np.testing.assert_array_equal(pad, np.zeros_like(pad))

    out_ring = ring_attention_sharded(q, k, v, mesh, causal=causal,
                                      kv_mask=kv_mask)
    out_uly = shard_map(
        lambda q_, k_, v_, m_: ulysses_attention(
            q_, k_, v_, "seq", causal=causal, kv_mask=m_),
        mesh=mesh,
        in_specs=(P("seq"), P("seq"), P("seq"), P("seq")),
        out_specs=P("seq"),
    )(q, k, v, kv_mask)
    # FULL tensors, padded rows included
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_dense),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out_uly), np.asarray(out_dense),
                               rtol=2e-5, atol=2e-5)
