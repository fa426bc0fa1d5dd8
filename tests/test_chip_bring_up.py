"""What the program needs to start on the chip, checked on the CPU: the
smoke's two modes, where the compile cache goes, the mesh's axis types and
sub-mesh, and 1/W placement."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from dgraph_tpu.comm.mesh import (
    GRAPH_AXIS,
    REPLICA_AXIS,
    make_graph_mesh,
    put_on_graph_axis,
)
from dgraph_tpu.utils import compile_cache

REPO = Path(__file__).resolve().parent.parent


def _run_smoke(*flags):
    # four virtual devices: the four-chip host's W
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *flags], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=600,
    )


def test_chip_smoke_tiny_cpu_mode_passes_and_says_so():
    p = _run_smoke("--tiny-cpu", "--steps", "3")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "TINY CPU MODE" in p.stdout
    assert "every plan/batch leaf holds 1/4 per device" in p.stdout
    assert "W=4 vs W=1, step-0 bf16 logits" in p.stdout
    last = p.stdout.strip().splitlines()[-1]
    assert '"ok": true' in last and '"platform": "cpu"' in last
    assert '"count": 4' in last and '"tiny_cpu": true' in last


def test_chip_smoke_without_a_tpu_fails_with_no_result():
    p = _run_smoke()
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"ok"' not in p.stdout


def test_compile_cache_env_set_means_nothing_set_in_code(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_env_unset_means_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        want = str(REPO / "cache" / "xla")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_graph_mesh_axes_are_auto_and_sub_mesh_works():
    full = make_graph_mesh(ranks_per_graph=4, num_replicas=2)
    assert full.axis_names == (REPLICA_AXIS, GRAPH_AXIS)
    assert full.axis_types == (AxisType.Auto, AxisType.Auto)
    sub = make_graph_mesh(ranks_per_graph=2)  # 2 of the 8 devices
    assert dict(sub.shape) == {REPLICA_AXIS: 1, GRAPH_AXIS: 2}
    assert list(sub.devices.flat) == jax.devices()[:2]
    one = make_graph_mesh(ranks_per_graph=1, devices=jax.devices()[:1])
    assert one.devices.size == 1
    with pytest.raises(ValueError, match="needs 16 devices; have 8"):
        make_graph_mesh(ranks_per_graph=8, num_replicas=2)


def test_put_on_graph_axis_holds_one_row_per_device(rng):
    W = 4
    mesh = make_graph_mesh(ranks_per_graph=W)
    tree = {"x": rng.normal(size=(W, 6, 3)).astype(np.float32),
            "ids": rng.integers(0, 9, (W, 5)).astype(np.int64)}
    placed = put_on_graph_axis(tree, mesh)
    for name, leaf in placed.items():
        assert leaf.sharding == NamedSharding(mesh, P(GRAPH_AXIS))
        shards = leaf.addressable_shards
        assert [s.device for s in shards] == jax.devices()[:W]
        for r, s in enumerate(shards):
            assert s.data.shape == (1,) + tree[name].shape[1:]
            np.testing.assert_array_equal(np.asarray(s.data)[0], tree[name][r])
