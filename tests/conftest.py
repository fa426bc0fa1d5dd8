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
