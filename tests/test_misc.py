"""Smaller parity pieces: bf16 compute path, MessagePassing wrapper,
Communicator facade surface, stage totals, LR schedule, utils."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def test_gcn_bfloat16_compute(rng):
    from dgraph_tpu.comm import Communicator
    from dgraph_tpu.data import DistributedGraph, synthetic
    from dgraph_tpu.models import GCN

    data = synthetic.sbm_classification_graph(num_nodes=100, seed=3)
    g = DistributedGraph.from_global(
        data["edge_index"], data["features"], data["labels"], data["masks"], 1
    )
    comm = Communicator.init_process_group("single")
    model = GCN(16, 4, comm=comm, dtype=jnp.bfloat16)
    plan = jax.tree.map(lambda l: jnp.asarray(l[0]), g.plan)
    x = jnp.asarray(g.features[0])
    params = model.init(jax.random.key(0), x, plan)
    out = model.apply(params, x, plan)
    assert out.dtype == jnp.float32  # head casts back
    assert np.isfinite(np.asarray(out)).all()
    # params stay float32
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(params))


def test_message_passing_wrapper(rng):
    from dgraph_tpu.comm import Communicator
    from dgraph_tpu.data import DistributedGraph, synthetic
    from dgraph_tpu.models.message_passing import MessagePassing
    from dgraph_tpu.ops import local as local_ops

    data = synthetic.sbm_classification_graph(num_nodes=80, seed=4)
    g = DistributedGraph.from_global(
        data["edge_index"], data["features"], data["labels"], data["masks"], 1
    )
    comm = Communicator.init_process_group("single")

    def layer(full, plan):
        msgs = full[plan.src_index] * plan.edge_mask[:, None]
        return local_ops.segment_sum(msgs, plan.dst_index, plan.n_dst_pad)

    mp = MessagePassing(layer=layer, comm=comm)
    plan = jax.tree.map(lambda l: jnp.asarray(l[0]), g.plan)
    x = jnp.asarray(g.features[0])
    params = mp.init(jax.random.key(0), x, plan)
    out = mp.apply(params, x, plan)
    # oracle: dense scatter of src features to dst
    from dgraph_tpu.testing import dense_scatter_sum
    from dgraph_tpu.plan import unshard_vertex_data

    got = unshard_vertex_data(np.asarray(out)[None], g.ren.counts)
    x_global = unshard_vertex_data(g.features, g.ren.counts)
    expected = dense_scatter_sum(x_global[g.edge_index[0]], g.edge_index, "dst", g.num_nodes)
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)


def test_communicator_facade_surface():
    from dgraph_tpu.comm import Communicator, SingleComm, TpuComm

    c = Communicator.init_process_group("single")
    assert isinstance(c, SingleComm)
    assert c.get_world_size() == 1 and c.get_rank() == 0
    c.barrier()
    c.destroy()
    assert c.alloc_buffer((3, 4)).shape == (3, 4)

    t = Communicator.init_process_group("tpu", world_size=8)
    assert isinstance(t, TpuComm) and t.get_world_size() == 8
    with pytest.raises(ValueError, match="not supported"):
        Communicator.init_process_group("nccl")
    with pytest.raises(ValueError):
        Communicator.init_process_group("tpu")  # missing world_size


def test_stage_totals_hold_the_phases():
    """The phases the static phase timer held, through the recorder's
    always-on table: one entry a finished stage, counted and summed."""
    from dgraph_tpu.obs import spans

    before = spans.stage_totals().get("test.phase", {"count": 0, "total_s": 0.0})
    for _ in range(2):
        with spans.stage("test.phase", rows=100):
            x = jnp.ones((100, 100)) @ jnp.ones((100, 100))
            jax.block_until_ready(x)  # the stage covers the device's part
    got = spans.stage_totals()["test.phase"]
    assert got["count"] == before["count"] + 2
    assert got["total_s"] > before["total_s"]
    assert 0 < got["last_s"] <= got["max_s"] <= got["total_s"]
    # a copy: editing it does not reach the table
    got["count"] = -1
    assert spans.stage_totals()["test.phase"]["count"] == before["count"] + 2


def test_three_phase_schedule():
    from dgraph_tpu.train.schedules import graphcast_three_phase

    s = graphcast_three_phase(peak_lr=1e-3, warmup_steps=10, decay_steps=100, floor_lr=1e-6)
    assert float(s(0)) == 0.0
    assert float(s(10)) == pytest.approx(1e-3, rel=1e-5)
    assert float(s(60)) < 1e-3
    assert float(s(500)) == pytest.approx(1e-6, rel=1e-3)


def test_split_helpers():
    from dgraph_tpu.utils import largest_split, split_per_rank

    assert largest_split(10, 4) == 3
    assert [split_per_rank(10, r, 4) for r in range(4)] == [3, 3, 3, 1]


def test_parallel_namespace():
    from dgraph_tpu import parallel

    assert callable(parallel.halo_exchange)
    assert parallel.GRAPH_AXIS == "graph"


def test_fused_scatter_variants(rng):
    """Fused ReLU / sum+ReLU / sparse scatter vs dense-loop golden
    (Fused_ReLU_Scatter_Kernel, Fused_Sum_Norm_Scatter_Kernel,
    Sparse_Scatter_Kernel semantics)."""
    import numpy as np
    import jax.numpy as jnp
    from dgraph_tpu.ops import local as L

    E, N, F = 200, 40, 8
    ids = rng.integers(0, N, E).astype(np.int32)
    v1 = rng.normal(size=(E, F)).astype(np.float32)
    v2 = rng.normal(size=(E, F)).astype(np.float32)

    exp = np.zeros((N, F), np.float32)
    np.add.at(exp, ids, np.maximum(v1, 0))
    np.testing.assert_allclose(
        np.asarray(L.scatter_add_relu(jnp.asarray(v1), jnp.asarray(ids), N)),
        exp, rtol=1e-5, atol=1e-5)

    exp2 = np.zeros((N, F), np.float32)
    np.add.at(exp2, ids, np.maximum(v1 + v2, 0))
    np.testing.assert_allclose(
        np.asarray(L.scatter_add_sum_relu(jnp.asarray(v1), jnp.asarray(v2), jnp.asarray(ids), N)),
        exp2, rtol=1e-5, atol=1e-5)

    # sparse: -1 rows dropped, accumulates into existing dst
    sidx = ids.astype(np.int64).copy()
    sidx[: E // 4] = -1
    dst = rng.normal(size=(N, F)).astype(np.float32)
    exp3 = dst.copy()
    np.add.at(exp3, sidx[E // 4:], v1[E // 4:])
    got3 = L.sparse_scatter_add(jnp.asarray(dst), jnp.asarray(sidx), jnp.asarray(v1))
    np.testing.assert_allclose(np.asarray(got3), exp3, rtol=1e-5, atol=1e-5)


def test_row_take_column_split(rng):
    """row_take == x[idx] for widths straddling the 128-lane tile boundary,
    and its VJP matches the plain gather's (the column-split is a pure
    re-association)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from dgraph_tpu.ops import local as L

    N, E = 50, 173
    idx = rng.integers(0, N, E).astype(np.int32)
    for F in (8, 128, 200, 256, 384):
        x = rng.normal(size=(N, F)).astype(np.float32)
        got = L.row_take(jnp.asarray(x), jnp.asarray(idx), col_block=128)
        np.testing.assert_array_equal(np.asarray(got), x[idx])

    x = rng.normal(size=(N, 256)).astype(np.float32)
    g_out = rng.normal(size=(E, 256)).astype(np.float32)

    def loss_split(a):
        return (L.row_take(a, jnp.asarray(idx), col_block=128) * g_out).sum()

    def loss_plain(a):
        return (a[jnp.asarray(idx)] * g_out).sum()

    gs = jax.grad(loss_split)(jnp.asarray(x))
    gp = jax.grad(loss_plain)(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gp), rtol=1e-5, atol=1e-5)


def test_ema_update():
    """EMA converges toward the tracked params at rate (1-decay)."""
    import jax.numpy as jnp

    from dgraph_tpu.train.ema import ema_init, ema_update

    p0 = {"w": jnp.zeros(4), "b": jnp.zeros(2)}
    tgt = {"w": jnp.ones(4), "b": jnp.ones(2)}
    ema = ema_init(p0)
    for _ in range(10):
        ema = ema_update(ema, tgt, decay=0.9)
    expect = 1.0 - 0.9 ** 10
    np.testing.assert_allclose(np.asarray(ema["w"]), expect, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ema["b"]), expect, rtol=1e-6)


def test_timed_scan_actually_measures_the_op():
    """Regression pin for the r3 measurement-integrity fix: the scan
    protocol must consume the WHOLE op output with a live carry
    dependency. Before the fix, XLA sliced through the single-element
    fetch (a row gather collapsed to one row) and constant-folded the
    `salt * 0` chain, so a 50000x-bigger op measured the same ~0 ms.
    A 3x time-ratio floor is far below the real ~1000x+ but far above
    the broken-case ratio (~1x)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from dgraph_tpu.utils.timing import salt_input, timed_scan_ms

    rng = np.random.default_rng(0)
    big = jnp.asarray(rng.standard_normal((400_000, 128)), jnp.float32)
    idx_big = jnp.asarray(rng.integers(0, 400_000, 400_000), jnp.int32)
    idx_one = idx_big[:8]

    t_big = timed_scan_ms(
        lambda s: salt_input(big, s)[idx_big], reps=3, n_long=6)
    t_one = timed_scan_ms(
        lambda s: salt_input(big, s)[idx_one], reps=3, n_long=6)
    assert t_big is not None
    # t_one can be None (too fast for a positive delta) — that's fine;
    # the broken case made t_big equally immeasurable
    floor = 3 * (t_one or 0.05)
    assert t_big > floor, (t_big, t_one)
