"""Test environment: force JAX onto 8 virtual CPU devices.

This replaces the reference's torchrun/mpirun multi-process test launches
(``tests/README.md:1-17``): with ``xla_force_host_platform_device_count`` we
get *real* multi-device SPMD semantics (true all_to_all/psum over 8 device
shards) in a single process with no cluster — SURVEY.md §4.

Must run before any test module imports jax.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache: the suite is compile-dominated (every
# shard_map train step traces to a fresh executable), and jax's
# content-addressed cache (keyed on HLO + compile options + backend) makes
# repeat runs in one container reuse yesterday's binaries. The resolved
# directory is exported so that subprocess tests (rank workers, CLI smokes)
# share it.
from dgraph_tpu.utils import compile_cache  # noqa: E402

os.environ.setdefault(
    compile_cache.ENV_VAR, compile_cache.enable_compile_cache()
)

import gc  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _drop_what_outlives_the_module():
    """Tier-1 is one long process, and what one test file leaves behind
    slows every later one: with the jit/compilation caches of ~60 files
    alive, ``test_tp_training.py`` took 49 s in the suite against 20 s
    alone, and every full pass of the cyclic GC re-walks all survivors (a
    file run after the auditors' files took 1.6x its stand-alone time,
    1.0x with the GC off). After each module: drop JAX's caches (modules
    share almost no programs; the persistent cache keeps the big ones),
    collect what died, and move the rest to the permanent generation.
    Whole suite, same machine: 1059 s -> 778 s."""
    yield
    import jax

    jax.clear_caches()
    gc.collect()
    gc.freeze()


@pytest.fixture
def tpu_interpret(monkeypatch):
    """The program's TPU branches, their kernels interpreted."""
    import jax
    from jax.experimental import pallas

    real_call = pallas.pallas_call

    def interpreted(*args, **kwargs):
        kwargs["interpret"] = True
        return real_call(*args, **kwargs)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas, "pallas_call", interpreted)


@pytest.fixture
def splash_backward(monkeypatch):
    """``check(mask, group, head_dim, v_head_dim, dtype)``: the splash
    attention's three gradients in interpret mode, tiles of 128 rows (pick a
    mask under which whole, partial and skipped tiles all occur), through the
    ONE backward kernel (``ops/pallas_attention.py``) against the dense
    oracle's and against the library's two kernels', the route of a shape
    past the VMEM budget; and the counters of both routes: one traced
    backward each, ``attn.bwd_one_kernel`` under the one kernel alone."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask_info as mi)

    from dgraph_tpu.obs.metrics import default_registry
    from dgraph_tpu.ops import pallas_attention
    from dgraph_tpu.parallel import sequence as seq

    monkeypatch.setattr(seq, "FLASH_BLOCK", 128)

    def counted():
        c = default_registry.snapshot()["counters"]
        return np.array([c.get("attn.bwd_calls", 0),
                         c.get("attn.bwd_one_kernel", 0)])

    def check(mask, group, head_dim, v_head_dim, dtype):
        rows, kv_heads = mask.rows, 2
        info, _ = mi.process_mask(
            seq._splash_mask(mask, group), (128, 128),
            downcast_smem_data=True, head_shards=1, q_seq_shards=1)
        _, cut, counts = pallas_attention.visits(
            info.data_next, info.block_mask)
        live = np.arange(cut.shape[1])[None, :] < counts[:, None]
        # the kernel visits the tiles the program counts (attn.tile_pairs):
        # some skipped, some cut by the mask, some whole
        assert counts.sum() * 128 * 128 == mask.tile_pairs(128)
        assert counts.sum() < (rows // 128) ** 2
        assert 0 < cut[live].sum() < live.sum()
        rng = np.random.default_rng(1)
        q, w = (jnp.asarray(rng.standard_normal((rows, kv_heads * group, d)),
                            dtype) for d in (head_dim, v_head_dim))
        k, v = (jnp.asarray(rng.standard_normal((rows, kv_heads, d)), dtype)
                for d in (head_dim, v_head_dim))

        def grads(attend):
            # as a trainer differentiates a layer: jitted, under
            # jax.checkpoint (the rules of a custom_vjp are then traced after
            # the trace that made the call has ended)
            loss = jax.checkpoint(
                lambda *a: (attend(*a).astype(jnp.float32)
                            * w.astype(jnp.float32)).sum())
            return [np.asarray(g, np.float32) for g in jax.jit(
                jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)]

        splash = lambda *a: seq._splash_dense(
            *a, mask=mask, scale=None, interpret=True)
        before = counted()
        assert seq._one_kernel_backward(rows, head_dim, v_head_dim, dtype,
                                        interpret=True)
        one = grads(splash)
        assert (counted() - before == [1, 1]).all()
        with monkeypatch.context() as m:
            m.setattr(pallas_attention, "VMEM_BUDGET", 0)
            two = grads(splash)
        assert (counted() - before == [2, 1]).all()
        want = grads(lambda *a: seq.dense_attention(*a, mask=mask))
        exact = jnp.dtype(dtype) == jnp.float32
        for mine, theirs, oracle in zip(one, two, want):
            # float32: the same sums in another order; bfloat16: each
            # rounded once from a float32 sum (a rounding apart at most),
            # then dq once more by the softmax scale outside the kernels
            np.testing.assert_allclose(mine, theirs, rtol=1e-5 if exact
                                       else 2 ** -6, atol=1e-5)
            np.testing.assert_allclose(mine, oracle, rtol=1e-5 if exact
                                       else 5e-2, atol=1e-5 if exact else 5e-2)

    return check


@pytest.fixture
def compiled_fresh():
    """A test whose programs are compiled here, none loaded from the
    persistent compilation cache (and none written to it)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # forget that the cache was in use
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def mesh8():
    import jax
    from dgraph_tpu.comm.mesh import make_graph_mesh

    assert len(jax.devices()) == 8, "conftest env did not take effect"
    return make_graph_mesh(ranks_per_graph=8)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def tensor_mesh8():
    """8-device 1-D mesh named 'tensor' (tensor-parallel tests)."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("need 8 devices")
    return Mesh(np.array(devs[:8]), ("tensor",))
