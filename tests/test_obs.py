"""Observability layer: static footprint accounting vs hand-computed and
traced byte counts, the step-metrics pipeline (including the disabled ==
zero-recompile invariant), JSONL schema round-trips, and RunHealth
classification — the contracts docs/observability.md documents."""

import json

import numpy as np
import pytest

from dgraph_tpu.obs import footprint as fp
from dgraph_tpu.obs.health import RunHealth, classify_wedge, startup_record
from dgraph_tpu.obs.metrics import Metrics, StepMetrics, step_record
from dgraph_tpu.plan import build_edge_plan


# ---------------------------------------------------------------------------
# footprint: hand-computed tiny plan
# ---------------------------------------------------------------------------


def _tiny_plan():
    # V=4 split [0,0 | 1,1]; edges (src->dst): 0->2, 1->3, 2->3, 3->0.
    # dst ownership: ranks own edges (r0: 3->0), (r1: the other three).
    # halo: r1 needs {0,1} from r0; r0 needs {3} from r1.
    edge_index = np.array([[0, 1, 2, 3], [2, 3, 3, 0]])
    part = np.array([0, 0, 1, 1])
    return build_edge_plan(edge_index, part, world_size=2, pad_multiple=1)


def test_footprint_tiny_plan_hand_computed():
    plan, layout = _tiny_plan()
    np.testing.assert_array_equal(layout.halo_counts, [[0, 2], [1, 0]])
    out = fp.plan_footprint(plan, "float32", feat_dim=4)

    row = 4 * 4  # feat_dim * f32
    assert out["world_size"] == 2 and out["s_pad"] == 2
    assert out["halo"]["real_rows_total"] == 3
    assert out["halo"]["real_bytes_total"] == 3 * row
    assert out["halo"]["per_shard_send_rows"] == [2, 1]
    assert out["halo"]["per_shard_recv_rows"] == [1, 2]
    assert out["halo"]["per_shard_send_bytes"] == [2 * row, 1 * row]
    # padded collective volumes: a2a operand [W=2, S=2, F=4] f32 per shard
    ex = out["collectives"]["halo_exchange"]
    assert ex["a2a_operand_bytes_per_shard"] == 2 * 2 * row
    assert out["halo"]["wire_bytes_per_shard"]["all_to_all"] == 1 * 2 * row
    # one live delta (both directions are (peer-rank) mod 2 == 1)
    assert out["num_halo_deltas"] == 1
    assert out["halo"]["wire_bytes_per_shard"]["ppermute"] == 1 * 2 * row
    assert ex["impl"] == "ppermute"  # 1 delta <= W/2
    # scatter's remote leg is the exact transpose
    assert out["collectives"]["halo_scatter_sum"] == ex
    # wire_efficiency (derived from send_mask) must equal plan_efficiency's
    # halo_wire_fill (derived from layout.halo_counts) — two data paths,
    # one published number
    from dgraph_tpu.plan import plan_efficiency

    eff = plan_efficiency(plan, layout)
    assert ex["wire_efficiency"] == pytest.approx(
        eff["halo_wire_fill_ppermute"], abs=1e-4
    )
    # lowering-aware HBM model: ppermute gathers/reads only the 1 live
    # delta's [S, F] block but still writes the full [W*S, F] halo buffer
    assert ex["hbm_bytes_per_shard"] == (2 * 1 + 2) * 2 * row
    assert ex["operand_bytes_per_shard"] == 1 * 2 * row  # one [S, F] round
    # edges: per-rank [1, 3] -> e_pad 3, max/mean imbalance 1.5
    assert out["e_pad"] == 3
    assert out["imbalance"]["edges"]["max_over_mean"] == pytest.approx(1.5)
    assert out["local_streams"]["edge_tensor_bytes"] == 3 * row
    # bf16 halves every byte figure
    out16 = fp.plan_footprint(plan, "bfloat16", feat_dim=4)
    assert out16["halo"]["real_bytes_total"] == out["halo"]["real_bytes_total"] // 2
    # the whole report is JSONL-able as-is
    json.dumps(out)


def test_footprint_honors_halo_impl_pin():
    """A DGRAPH_TPU_HALO_IMPL pin overrides the cost model at runtime, so
    the report must account the pinned lowering, not the auto pick."""
    from dgraph_tpu import config as cfg

    plan, _ = _tiny_plan()
    row = 4 * 4
    prev = cfg.halo_impl
    try:
        cfg.set_flags(halo_impl="all_to_all")
        out = fp.plan_footprint(plan, "float32", feat_dim=4)
        ex = out["collectives"]["halo_exchange"]
        assert ex["impl"] == "all_to_all"
        assert ex["operand_bytes_per_shard"] == 2 * 2 * row
        assert ex["ici_bytes_per_shard"] == 1 * 2 * row
        assert ex["hbm_bytes_per_shard"] == (2 * 2 + 2) * 2 * row
    finally:
        cfg.set_flags(halo_impl=prev)


def test_footprint_none_impl_matches_runtime_no_collective(mesh8):
    """Empty halo_deltas: footprint reports impl 'none' / 0 ICI bytes, and
    the runtime must agree by issuing NO collective at all (the exchange
    is identically zero) — report and execution cannot diverge."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm import collectives
    from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan

    # all edges rank-local under the contiguous block partition
    edge_index = np.array([[0, 1, 2, 3], [1, 0, 3, 2]])
    part = np.array([0, 0, 1, 1])
    plan, _ = build_edge_plan(edge_index, part, world_size=2, pad_multiple=1)
    assert plan.halo_deltas == ()
    out = fp.plan_footprint(plan, "float32", feat_dim=4)
    ex = out["collectives"]["halo_exchange"]
    assert ex["impl"] == "none"
    assert ex["ici_bytes_per_shard"] == 0 and ex["operand_bytes_per_shard"] == 0

    recorded = []
    orig = jax.lax.all_to_all

    def spy(x, *args, **kwargs):
        recorded.append(x.shape)
        return orig(x, *args, **kwargs)

    plan_dev = jax.tree.map(jnp.asarray, plan)
    devices = np.array(jax.devices()[:2]).reshape(1, 2)
    mesh = jax.sharding.Mesh(devices, ("replica", GRAPH_AXIS))

    def body(x, plan_):
        p = squeeze_plan(plan_)
        return collectives.halo_exchange(
            x[0], p.halo, GRAPH_AXIS, deltas=p.halo_deltas
        )[None]

    f = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(GRAPH_AXIS), plan_in_specs(plan_dev)),
        out_specs=P(GRAPH_AXIS),
    ))
    x = jnp.ones((2, plan.n_src_pad, 4), jnp.float32)
    try:
        jax.lax.all_to_all = spy
        got = np.asarray(f(x, plan_dev))
    finally:
        jax.lax.all_to_all = orig
    assert not recorded, "impl 'none' still lowered a collective"
    assert (got == 0).all()


def test_footprint_psum_grad_sync_accounting():
    plan, _ = _tiny_plan()
    out = fp.plan_footprint(plan, "float32", feat_dim=4, param_count=1000)
    psum = out["collectives"]["psum_grad_sync"]
    # ring all-reduce at f32: 2 * (W-1)/W of the payload per member
    assert psum["payload_bytes"] == 4000
    assert psum["ici_bytes_per_shard"] == 4000  # 2 * 4000 * 1/2
    assert psum["roofline"]["bound"] in ("ici", "hbm")


def test_footprint_matches_traced_all_to_all_arxiv(mesh8):
    """Acceptance pin: on the bench's arxiv-shaped synthetic graph, the
    static per-collective byte totals must match the operand the lowered
    program actually hands to all_to_all within 5% (they are exact)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu import partition as pt
    from dgraph_tpu.comm import collectives
    from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan

    V, E_half, F = 169_343, 1_166_243, 128
    rng = np.random.default_rng(0)
    src = rng.integers(0, V, E_half)
    dst = rng.integers(0, V, E_half)
    edge_index = np.stack(
        [np.concatenate([src, dst]), np.concatenate([dst, src])]
    ).astype(np.int64)
    new_edges, ren = pt.partition_graph(edge_index, V, 8, method="block")
    plan, _ = build_edge_plan(
        new_edges, ren.partition, world_size=8, pad_multiple=128,
        sort_route=False,
    )
    report = fp.plan_footprint(plan, "float32", feat_dim=F)
    assert report["collectives"]["halo_exchange"]["impl"] == "all_to_all"

    recorded = []
    orig = jax.lax.all_to_all

    def spy(x, *args, **kwargs):
        recorded.append(int(np.prod(x.shape)) * x.dtype.itemsize)
        return orig(x, *args, **kwargs)

    plan_dev = jax.tree.map(jnp.asarray, plan)

    def body(x, plan_):
        p = squeeze_plan(plan_)
        return collectives.halo_exchange(
            x[0], p.halo, GRAPH_AXIS, deltas=p.halo_deltas
        )[None]

    f = jax.jit(jax.shard_map(
        body, mesh=mesh8,
        in_specs=(P(GRAPH_AXIS), plan_in_specs(plan_dev)),
        out_specs=P(GRAPH_AXIS),
    ))
    x = jnp.zeros((8, plan.n_src_pad, F), jnp.float32)
    try:
        jax.lax.all_to_all = spy
        f.lower(x, plan_dev)  # trace only; the spy sees the real operand
    finally:
        jax.lax.all_to_all = orig

    assert recorded, "halo_exchange lowered without an all_to_all"
    measured = recorded[0]
    predicted = report["collectives"]["halo_exchange"][
        "a2a_operand_bytes_per_shard"
    ]
    assert abs(measured - predicted) / measured < 0.05, (measured, predicted)


def test_footprint_cli_prints_json(capsys):
    report = fp.main(fp.Config(
        nodes=256, edges=1024, world=4, pad_multiple=8, feat_dim=8, indent=0
    ))
    out = capsys.readouterr().out.strip()
    assert json.loads(out.splitlines()[-1]) == report


# ---------------------------------------------------------------------------
# metrics: step pipeline + registry
# ---------------------------------------------------------------------------


def _sbm_training(step_metrics, nonfinite_guard=False):
    import jax
    import jax.numpy as jnp
    import optax

    from dgraph_tpu.comm import Communicator, make_graph_mesh
    from dgraph_tpu.data import DistributedGraph, synthetic
    from dgraph_tpu.models import GCN
    from dgraph_tpu.train.loop import init_params, make_train_step

    data = synthetic.sbm_classification_graph(
        num_nodes=200, num_classes=3, feat_dim=8, avg_degree=6.0
    )
    g = DistributedGraph.from_global(
        data["edge_index"], data["features"], data["labels"], data["masks"],
        world_size=8, partition_method="random",
    )
    mesh = make_graph_mesh(ranks_per_graph=8)
    comm = Communicator.init_process_group("tpu", world_size=8)
    model = GCN(8, 3, comm=comm, num_layers=2)
    batch = jax.tree.map(
        jnp.asarray, dict(g.batch("train"), y=g.labels)
    )
    plan = jax.tree.map(jnp.asarray, g.plan)
    params = init_params(model, mesh, plan, batch)
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    step = make_train_step(
        model, opt, mesh, plan, donate=False, step_metrics=step_metrics,
        nonfinite_guard=nonfinite_guard,
    )
    return mesh, step, params, opt_state, batch, plan


def test_step_metrics_disabled_no_recompile(mesh8):
    """The build-time flag must add NOTHING when off: same legacy dict
    shape, and repeated same-shape calls hit the jit cache (exactly one
    compile)."""
    import jax

    mesh, step, params, opt_state, batch, plan = _sbm_training(False)
    with jax.set_mesh(mesh):
        # two warm calls reach the steady state (the first call's outputs
        # carry mesh shardings its uncommitted inputs did not, which is a
        # legitimate one-time second compile on any jitted step)
        params, opt_state, m = step(params, opt_state, batch, plan)
        params, opt_state, m = step(params, opt_state, batch, plan)
        warm = step._cache_size() if hasattr(step, "_cache_size") else None
        params, opt_state, m = step(params, opt_state, batch, plan)
        params, opt_state, m = step(params, opt_state, batch, plan)
    assert set(m.keys()) == {"loss", "accuracy"}
    if warm is not None:
        assert step._cache_size() == warm, "metrics-off step recompiled"


def test_step_metrics_enabled_pipeline(mesh8, tmp_path):
    """Enabled: StepMetrics comes back (dict-compatible), grad_norm and
    mask_count are real, and the record round-trips through ExperimentLog's
    JSONL."""
    import jax

    from dgraph_tpu.utils import ExperimentLog

    mesh, step, params, opt_state, batch, plan = _sbm_training(True)
    with jax.set_mesh(mesh):
        params, opt_state, m = step(params, opt_state, batch, plan)
    assert isinstance(m, StepMetrics)
    assert float(m["loss"]) > 0 and float(m.grad_norm) > 0
    assert float(m.mask_count) == float(np.asarray(batch["mask"]).sum())

    log = ExperimentLog(str(tmp_path / "log.jsonl"), echo=False)
    log.write(step_record(m, step=0, wall_ms=1.25))
    rec = json.loads(
        [l for l in open(log.path) if l.startswith("{")][-1]
    )
    assert rec["kind"] == "step" and rec["step"] == 0
    back = StepMetrics.from_record(rec)
    assert back.loss == pytest.approx(float(m.loss), rel=1e-6)
    assert back.grad_norm == pytest.approx(float(m.grad_norm), rel=1e-6)


def test_nonfinite_guard_skips_poisoned_step_zero_recompiles(mesh8):
    """The chaos acceptance pin for the guard: a host-poisoned (NaN) batch
    makes that step's grads non-finite; the guard carries params/opt_state
    forward, reports nonfinite_skipped=1, and — because the select is
    jnp.where inside the one traced program — the jit cache does NOT grow
    (a poisoned step replays the same executable)."""
    import jax

    from dgraph_tpu import chaos

    mesh, step, params, opt_state, batch, plan = _sbm_training(
        True, nonfinite_guard=True
    )
    with jax.set_mesh(mesh):
        # reach the jit steady state (the usual one-time second compile)
        params, opt_state, m = step(params, opt_state, batch, plan)
        params, opt_state, m = step(params, opt_state, batch, plan)
        assert float(m.nonfinite_skipped) == 0.0
        warm = step._cache_size() if hasattr(step, "_cache_size") else None
        before = jax.tree.map(np.asarray, params)

        bad = dict(batch, x=jax.numpy.asarray(chaos.poison_array(batch["x"])))
        params, opt_state, m = step(params, opt_state, bad, plan)
        assert float(m.nonfinite_skipped) == 1.0
        assert not np.isfinite(float(m.grad_norm))
        # carried forward bit-for-bit: the poisoned update never landed
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
            params, before,
        )
        if warm is not None:
            assert step._cache_size() == warm, "poisoned step recompiled"

        # clean step afterwards applies normally again
        params, opt_state, m = step(params, opt_state, batch, plan)
        assert float(m.nonfinite_skipped) == 0.0
        changed = any(
            not np.array_equal(np.asarray(a), b)
            for a, b in zip(
                jax.tree.leaves(params), jax.tree.leaves(before)
            )
        )
        assert changed, "clean step after a skip did not update params"
        if warm is not None:
            assert step._cache_size() == warm


def test_nonfinite_guard_clean_run_matches_unguarded(mesh8):
    """identical results on clean runs: one guarded step from the same
    (params, opt_state, batch) produces the same params as the unguarded
    step — the guard may only ever *select*, never perturb."""
    import jax

    mesh, step_g, params, opt_state, batch, plan = _sbm_training(
        False, nonfinite_guard=True
    )
    _, step_u, _, _, _, _ = _sbm_training(False, nonfinite_guard=False)
    with jax.set_mesh(mesh):
        pg, og, mg = step_g(params, opt_state, batch, plan)
        pu, ou, mu = step_u(params, opt_state, batch, plan)
    assert set(mg.keys()) == {"loss", "accuracy", "nonfinite_skipped"}
    assert float(mg["nonfinite_skipped"]) == 0.0
    assert float(mg["loss"]) == pytest.approx(float(mu["loss"]), rel=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        pg, pu,
    )


def test_step_record_schema_roundtrip():
    sm = StepMetrics(loss=1.5, accuracy=0.25, grad_norm=2.0, mask_count=10.0)
    rec = json.loads(json.dumps(sm.record(step=3, wall_ms=12.5)))
    assert rec["kind"] == "step" and rec["schema"] == 1
    assert StepMetrics.from_record(rec) == sm
    # None fields vanish from the record (and from_record tolerates that)
    rec2 = StepMetrics(loss=0.5).record(step=0)
    assert "grad_norm" not in rec2 and "accuracy" not in rec2
    assert StepMetrics.from_record(rec2).loss == 0.5
    with pytest.raises(ValueError):
        StepMetrics.from_record({"kind": "run_health"})


def test_histogram_quantiles_match_numpy():
    """The registry's quantile math (linear interpolation between order
    statistics) must agree with np.percentile's default method, so live
    snapshots and offline JSONL analysis publish the same numbers."""
    rng = np.random.default_rng(7)
    vals = rng.lognormal(3.0, 1.0, size=257)
    m = Metrics()
    for v in vals:
        m.histogram("lat_ms", v)
    for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        assert m.quantile("lat_ms", q) == pytest.approx(
            float(np.percentile(vals, q * 100)), rel=1e-12
        )
    snap = m.snapshot()["histograms"]["lat_ms"]
    assert snap["p50"] == pytest.approx(float(np.percentile(vals, 50)))
    assert snap["p95"] == pytest.approx(float(np.percentile(vals, 95)))
    assert snap["p99"] == pytest.approx(float(np.percentile(vals, 99)))
    json.dumps(snap)


def test_histogram_quantile_edge_cases():
    m = Metrics()
    m.histogram("one", 42.0)
    # single observation: every quantile is that value
    for q in (0.0, 0.5, 0.99, 1.0):
        assert m.quantile("one", q) == 42.0
    # two observations: exact midpoint interpolation
    m.histogram("two", 10.0)
    m.histogram("two", 20.0)
    assert m.quantile("two", 0.5) == 15.0
    assert m.quantile("two", 0.25) == 12.5
    # insertion order must not matter (quantile sorts)
    m.histogram("rev", 5.0)
    m.histogram("rev", 1.0)
    m.histogram("rev", 3.0)
    assert m.quantile("rev", 0.5) == 3.0
    # errors: out-of-range q, never-observed name, empty histogram
    with pytest.raises(ValueError):
        m.quantile("one", 1.5)
    with pytest.raises(ValueError):
        m.quantile("one", -0.1)
    with pytest.raises(KeyError):
        m.quantile("never", 0.5)
    from dgraph_tpu.obs.metrics import _Histogram

    with pytest.raises(ValueError):
        _Histogram().quantile(0.5)
    assert _Histogram().snapshot() == {"count": 0}


def test_histogram_memory_bounded_reservoir():
    """Past MAX_SAMPLES observations the histogram must stop growing
    (serving records several per request, forever); count/mean/min/max stay
    exact and reservoir quantiles stay close on a uniform stream."""
    from dgraph_tpu.obs.metrics import _Histogram

    h = _Histogram()
    n = h.MAX_SAMPLES * 4
    for i in range(n):
        h.observe(float(i))
    assert len(h.values) == h.MAX_SAMPLES
    snap = h.snapshot()
    assert snap["count"] == n
    assert snap["min"] == 0.0 and snap["max"] == float(n - 1)
    assert snap["mean"] == pytest.approx((n - 1) / 2)
    # uniform stream: reservoir p50 within a few percent of the true median
    assert snap["p50"] == pytest.approx((n - 1) / 2, rel=0.05)


def test_metrics_registry():
    m = Metrics()
    m.counter("plans_built")
    m.counter("plans_built", 2)
    m.gauge("halo_fill", 0.75)
    for v in (1.0, 2.0, 3.0, 4.0):
        m.histogram("step_ms", v)
    snap = m.snapshot()
    assert snap["counters"]["plans_built"] == 3
    assert snap["gauges"]["halo_fill"] == 0.75
    h = snap["histograms"]["step_ms"]
    assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 4.0
    json.dumps(snap)
    m.reset()
    assert m.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


# ---------------------------------------------------------------------------
# health: RunHealth + wedge classification + bench failure path
# ---------------------------------------------------------------------------


def test_run_health_roundtrip_and_wedge_classification():
    h = RunHealth.begin("bench.supervisor")
    h.record_probe(1, 150.2, "hang", "probe hung (wedged lease)")
    h.record_probe(2, 148.9, "hang", "probe hung (wedged lease)")
    d = h.finish("backend never initialized within 2 probes; wedged TPU lease")
    assert d["wedge"] == "init_wedge"
    assert d["schema"] == 1 and len(d["probes"]) == 2
    assert d["probes"][0]["outcome"] == "hang"
    back = RunHealth.from_dict(json.loads(json.dumps(d)))
    assert back.component == "bench.supervisor" and back.wedge == "init_wedge"

    # fail-fast probes (bad platform) are an init FAILURE, not a wedge
    probes_err = [{"attempt": 1, "outcome": "error"}]
    assert classify_wedge("backend never initialized within 1 probes",
                          probes_err) == "init_failure"
    assert classify_wedge(None) == "none"
    assert classify_wedge("watchdog: incomplete within 2400s") == \
        "watchdog_timeout"
    assert classify_wedge("bench child hung past its own watchdog; killed") \
        == "dispatch_wedge"
    assert classify_wedge("supervisor received signal 15") == "interrupted"
    # platform mismatch mentions 'wedged lease' but is a config problem,
    # not a wedge — waiting can never fix it
    assert classify_wedge(
        "backend is 'cpu', need 'tpu' (silent CPU fallback from a wedged "
        "lease?)") == "backend_lost"
    assert classify_wedge("gcn stage failed: RuntimeError: boom") == \
        "stage_failure"
    # interpolated exception text can contain wedge-ish words; the stage
    # anchor must win over the generic substring scans
    assert classify_wedge(
        "gcn stage failed: RuntimeError: collective hung after mesh sync"
    ) == "stage_failure"


def test_startup_record_has_backend_snapshot():
    rec = startup_record("experiments.test", snapshot_backend=True)
    assert rec["kind"] == "run_health"
    assert rec["backend"]["platform"] == "cpu"
    assert rec["backend"]["device_count"] == 8
    json.dumps(rec)
    # host-only flows never dial the accelerator
    rec2 = startup_record("experiments.plan_only", snapshot_backend=False)
    assert rec2["backend"] is None


def test_bench_fails_instead_of_falling_back(monkeypatch, capsys):
    """bench.py measures the chip or fails: no TPU (and no explicit smoke
    mode) -> non-zero and no JSON line; a device without published peaks
    is an error; a kernel self-check that disagrees raises — exercised
    in-process, no subprocess and no compile needed."""
    import importlib.util
    import os
    import sys

    import numpy as np

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_bench_under_test", os.path.join(repo, "bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    sys.modules["_bench_under_test"] = bench
    spec.loader.exec_module(bench)
    try:
        monkeypatch.delenv("DGRAPH_BENCH_SMOKE", raising=False)
        assert bench.main() == 1  # the test backend is the CPU
        captured = capsys.readouterr()
        assert captured.out == "" and "no TPU" in captured.err
        assert bench.device_peaks("TPU v5 lite")["hbm_gbps"] == 819.0
        with pytest.raises(KeyError, match="no published peaks"):
            bench.device_peaks("cpu")
        ref = np.zeros(4, np.float32)
        bench._check_one("same", lambda: ref, ref, 1e-4)
        with pytest.raises(AssertionError, match="exceeds tol"):
            bench._check_one("off", lambda: ref + 1.0, ref, 1e-4)
    finally:
        sys.modules.pop("_bench_under_test", None)
