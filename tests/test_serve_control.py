"""Serving control plane: hot-swap checkpoint rollover (parity + jit-cache
pins, chaos rollback with zero dropped in-flight requests), per-tenant
quotas and degraded isolation (noisy-neighbor pin), the reset_degraded
failure-epoch race fix, the model registry's atomic between-batches flip,
and live graph deltas (append -> background replan -> atomic adoption,
pinned bit-identical against a from-scratch rebuild oracle and chaos
sigterm-torn at the commit boundary)."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from dgraph_tpu.serve.bucketing import BucketLadder
from dgraph_tpu.serve.errors import (
    QuotaExceeded,
    SwapRejected,
    TenantDegraded,
)
from dgraph_tpu.serve.tenancy import TenantQuota, TenantTable

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# shared warmed stack (same graph/model/ladder shapes as test_serve's
# fixture on purpose: the persistent XLA cache makes the warmup a replay)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def control(mesh8, tmp_path_factory):
    import jax
    import jax.numpy as jnp

    from dgraph_tpu.comm import Communicator
    from dgraph_tpu.data import DistributedGraph, synthetic
    from dgraph_tpu.models import GCN
    from dgraph_tpu.obs.metrics import Metrics
    from dgraph_tpu.serve.engine import ServeEngine
    from dgraph_tpu.train.checkpoint import save_checkpoint
    from dgraph_tpu.train.loop import init_params

    data = synthetic.sbm_classification_graph(
        num_nodes=200, num_classes=3, feat_dim=8, avg_degree=6.0
    )
    g = DistributedGraph.from_global(
        data["edge_index"], data["features"], data["labels"], data["masks"],
        world_size=8, partition_method="random",
    )
    comm = Communicator.init_process_group("tpu", world_size=8)
    model = GCN(8, 3, comm=comm, num_layers=2)
    plan = jax.tree.map(jnp.asarray, g.plan)
    batch = jax.tree.map(jnp.asarray, dict(g.batch("train"), y=g.labels))
    params = init_params(model, mesh8, plan, batch, seed=0)
    params2 = init_params(model, mesh8, plan, batch, seed=1)
    ckpt = str(tmp_path_factory.mktemp("rollover") / "ckpt")
    save_checkpoint(ckpt, {"params": params, "step": 0}, 0)
    save_checkpoint(ckpt, {"params": params2, "step": 1}, 1)
    engine = ServeEngine.from_distributed_graph(
        model, mesh8, g, params,
        ladder=BucketLadder((8, 16, 32)), registry=Metrics(),
    )
    engine.ckpt_dir = ckpt
    engine.warmup()
    return engine, g, model, params, params2, ckpt


# ---------------------------------------------------------------------------
# hot-swap rollover
# ---------------------------------------------------------------------------


def test_swap_parity_pin_and_jit_cache_pin(control, rng):
    """The rollover acceptance pin: post-swap served logits are
    bit-identical to the eval forward of the NEW checkpoint, across every
    bucket, with ZERO new jit-cache entries."""
    engine, *_ = control
    before = engine._total_compiles()
    rec = engine.swap_params(step=1)  # resolves against engine.ckpt_dir
    assert rec["adopted"] and not rec["rolled_back"]
    assert rec["step"] == 1
    assert engine._total_compiles() == before
    assert engine.recompiles_since_warmup() == 0
    full_new = engine.full_logits()  # eval forward of the new checkpoint
    for n in (1, 8, 13, 27, 32):
        ids = rng.choice(engine.num_nodes, size=n, replace=False)
        out = engine.infer(ids)
        r, s = engine.rank_slot(ids)
        np.testing.assert_array_equal(out, full_new[r, s])
    assert engine.recompiles_since_warmup() == 0
    # the attempt is on the lineage record (and therefore in serve_health)
    assert any(
        l.get("event") == "swap" and l.get("adopted") and l.get("step") == 1
        for l in engine.lineage
    )
    json.dumps(engine.lineage)


def test_swap_rejects_structural_mismatch_and_nonfinite(control):
    """A checkpoint that cannot replay the warmed executables (different
    tree / shapes) or carries non-finite weights is rolled back before the
    live pointer ever moves."""
    import jax

    engine, *_ = control
    full_before = engine.full_logits()

    wrong = {"not_the_params": np.zeros(3, np.float32)}
    with pytest.raises(SwapRejected) as ei:
        engine.swap_params(params=wrong)
    assert ei.value.context["reason"] == "structure_mismatch"
    assert ei.value.context["rolled_back"] is True

    bad = jax.tree.map(lambda x: np.array(x), engine._params)
    jax.tree.leaves(bad)[0].reshape(-1)[0] = np.nan
    with pytest.raises(SwapRejected) as ei:
        engine.swap_params(params=bad)
    rec = ei.value.record()
    assert rec["reason"] == "nonfinite_params" and rec["error"] == "swap_rejected"
    json.dumps(rec)

    # restore-phase rejections (missing checkpoint) also land one lineage
    # record — the contract is one record per ATTEMPT, adopted or not
    lineage_before = len(engine.lineage)
    with pytest.raises(SwapRejected) as ei:
        engine.swap_params("/nonexistent/ckpt_dir")
    assert ei.value.context["reason"] == "not_found"
    assert len(engine.lineage) == lineage_before + 1
    assert engine.lineage[-1]["reason"] == "not_found"

    # both rollbacks left serving bit-identical, compile-free
    np.testing.assert_array_equal(engine.full_logits(), full_before)
    assert engine.recompiles_since_warmup() == 0


def test_swap_chaos_rollback_zero_dropped_inflight(control, rng):
    """The e2e acceptance pin: a fault injected mid-swap
    (``serve.swap=raise@0``) rolls back to the prior params while
    concurrent in-flight requests ALL resolve, bit-identical to the
    pre-swap oracle — zero drops, zero compiles."""
    from dgraph_tpu import chaos
    from dgraph_tpu.serve.batcher import MicroBatcher

    engine, *_ = control
    full = engine.full_logits()
    bat = MicroBatcher(
        engine, max_batch_size=4, max_delay_ms=1.0, max_queue_depth=64
    )
    try:
        futs, refs = [], []
        for _ in range(12):
            ids = rng.choice(engine.num_nodes, size=int(rng.integers(1, 33)),
                             replace=False)
            futs.append(bat.submit(ids))
            r, s = engine.rank_slot(ids)
            refs.append(full[r, s])
        chaos.arm("serve.swap=raise@0")
        try:
            with pytest.raises(SwapRejected) as ei:
                engine.swap_params(step=0)
            assert ei.value.context["reason"] == "fault"
            assert ei.value.context["rolled_back"] is True
        finally:
            chaos.reset()
        # every in-flight request resolves against the UNmoved params
        for fut, ref in zip(futs, refs):
            np.testing.assert_array_equal(fut.result(timeout=60), ref)
        assert engine.recompiles_since_warmup() == 0
    finally:
        bat.stop()


# ---------------------------------------------------------------------------
# reset_degraded atomicity (the failure-epoch race fix)
# ---------------------------------------------------------------------------


def test_reset_degraded_not_resurrected_by_inflight_failure(control, rng):
    """The satellite pin: an infer DISPATCHED before reset_degraded() whose
    failure lands after it must not resurrect degraded mode. Without the
    failure-epoch gate, the worker's late failure re-degrades the engine
    the instant after the operator re-admitted traffic."""
    from dgraph_tpu import chaos

    engine, *_ = control
    saved = (engine.degrade_after, engine.retry_backoff_s)
    engine.degrade_after, engine.retry_backoff_s = 1, 0.2
    try:
        chaos.arm("serve.infer=raise@0:count=1000")
        errs = []

        def failing_infer():
            try:
                engine.infer(rng.choice(engine.num_nodes, size=3,
                                        replace=False))
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        t = threading.Thread(target=failing_infer)
        t.start()
        time.sleep(0.1)  # in flight: inside the ~0.4s retry backoff window
        engine.reset_degraded()  # operator re-admits mid-request
        t.join(timeout=30)
        assert errs, "chaos-armed infer did not fail"
        # the stale failure was attributed to the OLD epoch: with
        # degrade_after=1 a post-reset attribution would have re-degraded
        assert engine.degraded is False
        assert engine._consecutive_failures == 0
    finally:
        chaos.reset()
        engine.degrade_after, engine.retry_backoff_s = saved
        engine.reset_degraded()


def test_reset_degraded_serializes_under_engine_lock(control):
    """reset_degraded takes the engine lock — a control-plane mutation in
    flight (swap/append/accounting) blocks it rather than interleaving."""
    engine, *_ = control
    done = threading.Event()
    engine._lock.acquire()
    try:
        t = threading.Thread(
            target=lambda: (engine.reset_degraded(), done.set())
        )
        t.start()
        time.sleep(0.05)
        assert not done.is_set(), "reset_degraded did not take the lock"
    finally:
        engine._lock.release()
    assert done.wait(timeout=10)


# ---------------------------------------------------------------------------
# model registry: atomic between-batches flip
# ---------------------------------------------------------------------------


def test_registry_flip_serves_through_batcher(control, rng):
    from dgraph_tpu.serve.batcher import MicroBatcher
    from dgraph_tpu.serve.registry import ModelRegistry

    engine, *_ = control
    reg = ModelRegistry()
    reg.register("blue", engine, activate=True)
    assert reg.active_name == "blue"
    full = engine.full_logits()
    bat = MicroBatcher(reg, max_batch_size=4, max_delay_ms=0.5)
    try:
        ids = rng.choice(engine.num_nodes, size=9, replace=False)
        r, s = engine.rank_slot(ids)
        np.testing.assert_array_equal(bat.infer(ids), full[r, s])
        # flip to a second named entry mid-traffic (same engine object:
        # the flip machinery, not a second warmup, is under test)
        reg.register("green", engine)
        reg.activate("green")
        assert reg.active_name == "green"
        np.testing.assert_array_equal(bat.infer(ids), full[r, s])
        rec = reg.record()
        assert rec["active"] == "green" and set(rec["models"]) == {"blue", "green"}
        json.dumps(rec)
    finally:
        bat.stop()
    # a replacement whose ladder shrank below the active one's is refused:
    # requests admitted against the old ladder could no longer fit
    class _Tiny:
        ladder = BucketLadder((8,))

    with pytest.raises(ValueError):
        reg.activate("green", _Tiny())
    with pytest.raises(KeyError):
        reg.get("red")
    with pytest.raises(ValueError):
        reg.retire("green")  # active entry
    reg.retire("blue")
    assert reg.names() == ["green"]


def test_registry_empty_fails_loudly():
    from dgraph_tpu.serve.registry import ModelRegistry

    reg = ModelRegistry()
    with pytest.raises(KeyError):
        _ = reg.active_engine


class _BlockingFakeEngine:
    """Fake engine whose infer blocks on an event, with a configurable
    graph size — the deterministic scaffold for flip-under-load tests."""

    def __init__(self, ladder, num_nodes, block=None, started=None):
        from dgraph_tpu.obs.metrics import Metrics

        self.ladder = ladder
        self.num_nodes = num_nodes
        self.registry = Metrics()
        self.calls = []
        self._block = block
        self._started = started

    def infer(self, ids):
        if self._started is not None:
            self._started.set()
        if self._block is not None:
            assert self._block.wait(timeout=30)
        ids = np.asarray(ids)
        if ids.size and ids.max() >= self.num_nodes:
            raise ValueError("engine saw an id it was never validated for")
        self.calls.append(ids)
        return np.zeros((len(ids), 3), np.float32)


def test_registry_flip_revalidates_queued_requests():
    """A request validated against the OLD engine but flushed on a NEW one
    (registry flip to a smaller graph between submit and flush) fails
    individually with a structured stale rejection instead of reaching the
    engine and fanning its failure out to the co-batched requests."""
    from dgraph_tpu.serve.batcher import MicroBatcher
    from dgraph_tpu.serve.registry import ModelRegistry

    block, started = threading.Event(), threading.Event()
    eng_a = _BlockingFakeEngine(BucketLadder((8,)), 100, block, started)
    eng_b = _BlockingFakeEngine(BucketLadder((8,)), 50)
    reg = ModelRegistry()
    reg.register("m", eng_a, activate=True)
    bat = MicroBatcher(reg, max_batch_size=1, max_delay_ms=0.0,
                       max_queue_depth=8)
    try:
        f0 = bat.submit(np.array([1, 2]))  # holds the worker inside infer
        assert started.wait(timeout=10)
        f_stale = bat.submit(np.array([80]))  # valid on A, stale on B
        f_ok = bat.submit(np.array([10]))  # valid on both
        reg.activate("m", eng_b)  # rollback to a smaller graph
        block.set()
        f0.result(timeout=10)
        with pytest.raises(ValueError, match="engine now active"):
            f_stale.result(timeout=10)
        assert f_ok.result(timeout=10).shape == (1, 3)
        # the stale request never reached engine B (no fan-out, no crash)
        assert all(c.max() < 50 for c in eng_b.calls if c.size)
        assert bat.registry.snapshot()["counters"]["serve.rejected_stale"] == 1
    finally:
        block.set()
        bat.stop()
    # entry-replacing register on the ACTIVE name enforces the same
    # ladder-coverage rule as activate
    with pytest.raises(ValueError):
        reg.register(
            "m", _BlockingFakeEngine(BucketLadder((4,)), 50), activate=True
        )


def test_engine_outage_does_not_degrade_tenants():
    """Engine-level STRUCTURED rejections (degraded shed, backpressure)
    are the engine's state, not any tenant's payload: they must not feed
    per-tenant degrading — a backend outage + reset would otherwise leave
    every innocent tenant individually shed."""
    from dgraph_tpu.serve.batcher import MicroBatcher
    from dgraph_tpu.serve.errors import QueueFull

    class _DegradedEngine:
        def __init__(self):
            from dgraph_tpu.obs.metrics import Metrics

            self.ladder = BucketLadder((8,))
            self.registry = Metrics()

        def infer(self, ids):
            raise QueueFull("engine degraded; shedding", degraded=True)

    table = TenantTable(
        TenantQuota(rps=0.0, burst=64, max_queue_share=0.9, degrade_after=1)
    )
    bat = MicroBatcher(_DegradedEngine(), max_delay_ms=0.0, tenants=table)
    try:
        for _ in range(3):
            with pytest.raises(QueueFull):
                bat.infer(np.arange(2), tenant="calm")
        snap = table.snapshot()
        # with degrade_after=1, ONE attributed failure would have flipped
        # the tenant — the engine's shed must not count as one
        assert snap["calm"]["degraded"] is False
        assert snap["calm"]["failures"] == 0
    finally:
        bat.stop()


def test_empty_string_tenant_is_its_own_bucket():
    """'' and None must not split across tenant buckets: failure
    attribution, admission, and degrading all key the same resolved id."""
    from dgraph_tpu.serve.batcher import MicroBatcher

    table = TenantTable(
        TenantQuota(rps=0.0, burst=64, max_queue_share=0.9, degrade_after=2)
    )
    eng = _SlowFakeEngine(BucketLadder((8,)), infer_s=0.0)
    eng.num_nodes = 10
    bat = MicroBatcher(eng, max_delay_ms=0.0, tenants=table)
    try:
        for _ in range(2):
            with pytest.raises(ValueError):
                bat.submit(np.array([99]), tenant="")
        with pytest.raises(TenantDegraded):
            bat.submit(np.array([1]), tenant="")
        # the anonymous/default tenant was never touched by ''s poison
        assert bat.infer(np.array([1])).shape == (1, 3)
        snap = table.snapshot()
        assert snap[""]["degraded"] is True
        assert snap.get("default", {}).get("degraded", False) is False
        # the submit-validation path ticks the shared degraded counter
        # exactly like the worker path would
        counters = bat.registry.snapshot()["counters"]
        assert counters["serve.tenant_degraded"] == 1
    finally:
        bat.stop()


def test_tenant_table_caps_lazily_materialized_tenants():
    """Client-supplied tenant ids are unbounded input: past max_tenants,
    unseen ids fold into the shared default bucket instead of growing
    process memory without bound."""
    from dgraph_tpu.serve.tenancy import DEFAULT_TENANT

    table = TenantTable(
        TenantQuota(rps=0.0, burst=8, max_queue_share=0.9), max_tenants=2
    )
    assert table.admit("t1", 64) == "t1"
    assert table.admit("t2", 64) == "t2"
    # the cap: a third distinct id resolves to the shared default bucket
    assert table.admit("t3", 64) == DEFAULT_TENANT
    assert table.admit("t4", 64) == DEFAULT_TENANT
    snap = table.snapshot()
    assert "t3" not in snap and "t4" not in snap
    assert snap[DEFAULT_TENANT]["admitted"] == 2
    with pytest.raises(ValueError):
        TenantTable(max_tenants=0)


# ---------------------------------------------------------------------------
# tenancy: deterministic policy units + noisy-neighbor isolation
# ---------------------------------------------------------------------------


def test_token_bucket_deterministic_clock():
    clock = [0.0]
    table = TenantTable(
        TenantQuota(rps=2.0, burst=2, max_queue_share=1.0),
        clock=lambda: clock[0],
    )
    assert table.admit("a", 64) == "a"
    assert table.admit("a", 64) == "a"
    with pytest.raises(QuotaExceeded) as ei:
        table.admit("a", 64)
    rec = ei.value.record()
    assert rec["error"] == "quota" and rec["reason"] == "rate"
    json.dumps(rec)
    clock[0] += 0.5  # one token refilled at 2 rps
    assert table.admit("a", 64) == "a"
    with pytest.raises(QuotaExceeded):
        table.admit("a", 64)
    # a second tenant has its own bucket
    assert table.admit("b", 64) == "b"


def test_tenant_queue_share_and_release():
    table = TenantTable(TenantQuota(rps=0.0, burst=8, max_queue_share=0.25))
    for _ in range(4):  # 25% of depth 16
        table.admit("a", 16)
    with pytest.raises(QuotaExceeded) as ei:
        table.admit("a", 16)
    assert ei.value.context["reason"] == "queue_share"
    table.release("a")  # one slot frees one admission
    table.admit("a", 16)
    snap = table.snapshot()
    assert snap["a"]["queued"] == 4 and snap["a"]["shed_quota"] == 1


def test_tenant_quota_validation():
    with pytest.raises(ValueError):
        TenantQuota(burst=0)
    with pytest.raises(ValueError):
        TenantQuota(max_queue_share=0.0)
    with pytest.raises(ValueError):
        TenantQuota(max_queue_share=1.5)
    with pytest.raises(ValueError):
        TenantQuota(degrade_after=-1)


class _SlowFakeEngine:
    """Deterministic engine stand-in with a small per-batch cost, so a
    flooding tenant actually creates queue contention."""

    def __init__(self, ladder, infer_s=0.002):
        from dgraph_tpu.obs.metrics import Metrics

        self.ladder = ladder
        self.registry = Metrics()
        self.infer_s = infer_s
        self.calls = 0

    def infer(self, ids):
        self.calls += 1
        time.sleep(self.infer_s)
        return np.zeros((len(ids), 3), np.float32)


def test_noisy_neighbor_flood_sheds_only_the_flooder():
    """The isolation pin: tenant A floods far past its quota; A is shed
    with the structured ``quota`` error, B's requests ALL complete, B is
    never shed, and B's p99 stays bounded."""
    from dgraph_tpu.serve.batcher import MicroBatcher

    table = TenantTable(
        TenantQuota(rps=0.0, burst=8, max_queue_share=0.9),
        quotas={"A": TenantQuota(rps=0.001, burst=4, max_queue_share=0.25)},
    )
    eng = _SlowFakeEngine(BucketLadder((64,)))
    bat = MicroBatcher(
        eng, max_batch_size=4, max_delay_ms=0.2, max_queue_depth=16,
        tenants=table,
    )
    try:
        a_ok, a_shed = 0, 0
        b_futs = []

        def flood_a():
            nonlocal a_ok, a_shed
            for _ in range(40):
                try:
                    bat.submit(np.arange(3), tenant="A")
                    a_ok += 1
                except QuotaExceeded:
                    a_shed += 1

        t = threading.Thread(target=flood_a)
        t.start()
        for _ in range(10):
            b_futs.append(bat.submit(np.arange(2), tenant="B"))
            time.sleep(0.003)
        t.join(timeout=30)
        for f in b_futs:
            assert f.result(timeout=30).shape == (2, 3)  # all of B served
        snap = table.snapshot()
        assert a_shed > 0 and snap["A"]["shed_quota"] == a_shed
        assert snap["B"]["shed_quota"] == 0 and snap["B"]["shed_degraded"] == 0
        # B's p99-under-contention is recorded and bounded (well under the
        # batcher's own timeout — the flood did not starve B's tail)
        b_hist = bat.registry.snapshot()["histograms"].get(
            "serve.tenant.B.request_ms"
        )
        assert b_hist and b_hist["count"] == 10
        assert b_hist["p99"] < 5_000.0
    finally:
        bat.stop()


def test_tenant_degraded_isolation_and_reset():
    """Poisoned payloads degrade ONLY their tenant: bad submissions from
    'poison' flip it into degraded shedding while 'good' keeps flowing;
    reset() re-admits."""
    from dgraph_tpu.serve.batcher import MicroBatcher

    table = TenantTable(
        TenantQuota(rps=0.0, burst=64, max_queue_share=0.9, degrade_after=2)
    )
    eng = _SlowFakeEngine(BucketLadder((8,)), infer_s=0.0)
    eng.num_nodes = 100
    bat = MicroBatcher(eng, max_delay_ms=0.0, max_queue_depth=16,
                       tenants=table)
    try:
        for _ in range(2):  # poisoned payloads: ids out of range
            with pytest.raises(ValueError):
                bat.submit(np.array([500]), tenant="poison")
        with pytest.raises(TenantDegraded) as ei:
            bat.submit(np.array([1]), tenant="poison")
        assert ei.value.record()["error"] == "tenant_degraded"
        # the neighbor is untouched
        assert bat.infer(np.array([1, 2]), tenant="good").shape == (2, 3)
        snap = table.snapshot()
        assert snap["poison"]["degraded"] is True
        assert snap["good"]["degraded"] is False
        table.reset("poison")
        assert bat.infer(np.array([3]), tenant="poison").shape == (1, 3)
    finally:
        bat.stop()


def test_serve_health_carries_tenants_and_lineage(control):
    from dgraph_tpu.serve.batcher import MicroBatcher
    from dgraph_tpu.serve.health import serve_health_record

    engine, *_ = control
    # the swap whose record the health record must carry is made HERE: the
    # module-scoped engine starts with an empty lineage, so relying on an
    # earlier test's swap made this one fail when run alone
    swap = engine.swap_params(step=1)
    assert swap["adopted"]
    table = TenantTable(TenantQuota(rps=0.0, burst=8, max_queue_share=0.9))
    bat = MicroBatcher(engine, max_delay_ms=0.2, tenants=table)
    try:
        bat.infer(np.arange(4), tenant="acme")
        rec = serve_health_record(engine, bat)
        assert "acme" in rec["tenants"]
        assert rec["tenants"]["acme"]["admitted"] == 1
        assert rec["tenants"]["acme"]["latency_ms"]["count"] == 1
        assert isinstance(rec["lineage"], list)
        assert rec["lineage"][-1] == engine.lineage[-1]
        assert rec["lineage"][-1]["event"] == "swap"
        assert rec["lineage"][-1]["step"] == 1
        json.dumps(rec, default=str)
    finally:
        bat.stop()


# ---------------------------------------------------------------------------
# live graph deltas: append -> replan -> atomic adoption (+ oracle pin)
# ---------------------------------------------------------------------------


def test_delta_append_replan_adopt_matches_from_scratch_oracle(
    mesh8, tmp_path, rng
):
    """The delta acceptance pin: queries over appended vertices after
    adoption are BIT-IDENTICAL to a from-scratch monolithic rebuild of the
    composed graph; live pad-slot placement matches the re-plan's
    partition; appends and adoption mint zero new executables on the
    running engine."""
    import jax
    import jax.numpy as jnp

    from dgraph_tpu.comm import Communicator
    from dgraph_tpu.data import synthetic
    from dgraph_tpu.models import GCN
    from dgraph_tpu.obs.metrics import Metrics
    from dgraph_tpu.partition import renumber_contiguous
    from dgraph_tpu.plan import build_edge_plan, shard_vertex_data
    from dgraph_tpu.serve import deltas
    from dgraph_tpu.serve.batcher import MicroBatcher
    from dgraph_tpu.serve.engine import ServeEngine
    from dgraph_tpu.serve.registry import ModelRegistry
    from dgraph_tpu.train.loop import init_params

    run_dir = str(tmp_path / "world")
    data = synthetic.sbm_classification_graph(
        num_nodes=96, num_classes=3, feat_dim=8, avg_degree=4.0
    )
    deltas.init_world(
        run_dir, data["edge_index"], data["features"], world_size=8,
        partition_method="random", seed=0,
    )
    comm = Communicator.init_process_group("tpu", world_size=8)
    model = GCN(8, 3, comm=comm, num_layers=2)
    ladder = BucketLadder((8,))

    info0 = deltas.load_generation(run_dir)
    params = init_params(
        model, mesh8, jax.tree.map(jnp.asarray, info0["plan"]),
        jax.tree.map(jnp.asarray, info0["batch"]), seed=0,
    )
    eng0 = deltas.build_engine(run_dir, model, mesh8, params, ladder=ladder,
                               registry=Metrics())
    assert eng0.generation == 0
    eng0.infer(np.arange(8))  # compile the single bucket once

    # durable staging FIRST, then the live install (crash between the two
    # replays the append from disk at the next re-plan)
    new_feats = rng.normal(size=(4, 8)).astype(np.float32)
    new_edges = np.array([[0, 1, 96, 97], [96, 97, 2, 99]])
    drec = deltas.append_delta(run_dir, new_feats, new_edges)
    assert drec["id_base"] == 96 and drec["new_nodes"] == 4
    compiles_before = eng0._total_compiles()
    live_ids = eng0.append_vertices(new_feats)
    np.testing.assert_array_equal(live_ids, [96, 97, 98, 99])
    assert eng0.num_nodes == 100
    # appended vertices are queryable NOW (isolated semantics), compile-free
    assert eng0.infer(live_ids).shape == (4, 3)
    assert eng0._total_compiles() == compiles_before

    # background re-plan + atomic pointer flip
    w1 = deltas.replan(run_dir)
    assert w1["generation"] == 1 and w1["num_nodes"] == 100
    assert deltas.read_world(run_dir)["generation"] == 1

    # adoption: fresh engine over generation 1, flipped live via the
    # registry behind one batcher — old ids and appended ids both served
    eng1 = deltas.build_engine(run_dir, model, mesh8, params, ladder=ladder,
                               registry=Metrics())
    assert eng1.generation == 1
    reg = ModelRegistry()
    reg.register("default", eng0, activate=True)
    bat = MicroBatcher(reg, max_batch_size=4, max_delay_ms=0.5)
    try:
        assert bat.infer(np.arange(5)).shape == (5, 3)
        reg.activate("default", eng1)  # the adoption flip
        out_live = bat.infer(live_ids)
    finally:
        bat.stop()

    full1 = eng1.full_logits()
    r1, s1 = eng1.rank_slot(live_ids)
    np.testing.assert_array_equal(out_live, full1[r1, s1])

    # from-scratch rebuild oracle: monolithic build_edge_plan over the
    # SAME composed graph + partition — a different assembly path whose
    # forward must agree bit-for-bit on EVERY vertex
    g1 = np.load(deltas.graph_path(run_dir, 1))
    ren = renumber_contiguous(np.asarray(g1["partition"]), 8)
    oplan, _ = build_edge_plan(
        np.asarray(ren.perm)[np.asarray(g1["edge_index"])], ren.partition,
        world_size=8, pad_multiple=8,
    )
    feats_sh = shard_vertex_data(
        np.asarray(g1["features"])[ren.inv], ren.counts, oplan.n_src_pad
    ).astype(np.float32)
    vmask = shard_vertex_data(np.ones(100, np.float32), ren.counts,
                              oplan.n_src_pad)
    id_rank = np.asarray(ren.partition)[np.asarray(ren.perm)]
    id_slot = np.asarray(ren.perm) - np.asarray(ren.offsets)[id_rank]
    oracle = ServeEngine(
        model, mesh8, oplan, params, {"x": feats_sh, "vmask": vmask},
        id_rank, id_slot, ladder=ladder, registry=Metrics(),
    )
    all_ids = np.arange(100)
    ra, sa = eng1.rank_slot(all_ids)
    ro, so = oracle.rank_slot(all_ids)
    np.testing.assert_array_equal(full1[ra, sa], oracle.full_logits()[ro, so])

    # live placement == the re-plan's recomputed partition (the shared
    # deterministic waterfill)
    np.testing.assert_array_equal(
        eng0.rank_slot(live_ids)[0], np.asarray(g1["partition"])[96:]
    )


def test_delta_validation_and_pad_budget(mesh8, tmp_path):
    from dgraph_tpu.serve import deltas

    run_dir = str(tmp_path / "world")
    edges = np.stack([np.arange(24), (np.arange(24) + 1) % 24])
    feats = np.ones((24, 4), np.float32)
    deltas.init_world(run_dir, edges, feats, world_size=4,
                      partition_method="block", pad_multiple=4)
    with pytest.raises(deltas.DeltaError):  # wrong feature width
        deltas.append_delta(run_dir, np.ones((2, 5), np.float32),
                            np.zeros((2, 0), np.int64))
    with pytest.raises(deltas.DeltaError):  # edge beyond the id horizon
        deltas.append_delta(run_dir, np.ones((1, 4), np.float32),
                            np.array([[0], [99]]))
    # sequenced appends extend the id horizon
    r1 = deltas.append_delta(run_dir, np.ones((2, 4), np.float32),
                             np.array([[24], [25]]))
    r2 = deltas.append_delta(run_dir, np.ones((1, 4), np.float32),
                             np.array([[26], [0]]))
    assert (r1["id_base"], r2["id_base"]) == (24, 26)
    # a replan with nothing staged is a no-op returning the same pointer
    w1 = deltas.replan(run_dir)
    assert w1["generation"] == 1 and w1["deltas_adopted"] == 2
    assert deltas.replan(run_dir) == deltas.read_world(run_dir)


def test_free_pad_slots_clamps_without_appendable_batch(control):
    engine, *_ = control
    saved = engine._host_x
    try:
        engine._host_x = None
        assert engine.free_pad_slots() == 0
    finally:
        engine._host_x = saved


def _tiny_delta_world(tmp_path):
    from dgraph_tpu.serve import deltas

    run_dir = str(tmp_path / "world")
    edges = np.stack([np.arange(24), (np.arange(24) + 1) % 24])
    deltas.init_world(run_dir, edges, np.ones((24, 4), np.float32),
                      world_size=4, partition_method="block", pad_multiple=4)
    return run_dir


def test_replan_folds_deltas_that_land_mid_build(tmp_path, monkeypatch):
    """A delta appended while the background replan is building must not
    be orphaned: the commit re-snapshots the staged set and folds another
    round instead of adopting a generation that silently drops it."""
    import dgraph_tpu.plan as plan_mod
    from dgraph_tpu.serve import deltas

    run_dir = _tiny_delta_world(tmp_path)
    deltas.append_delta(run_dir, np.ones((2, 4), np.float32),
                        np.array([[0, 24], [24, 25]]))
    real_build = plan_mod.build_plan_shards
    rounds = {"n": 0}

    def racing_build(*args, **kwargs):
        rounds["n"] += 1
        if rounds["n"] == 1:
            # the mid-build append (request thread racing the replanner)
            deltas.append_delta(run_dir, np.full((1, 4), 2.0, np.float32),
                                np.array([[25], [26]]))
        return real_build(*args, **kwargs)

    monkeypatch.setattr(plan_mod, "build_plan_shards", racing_build)
    world = deltas.replan(run_dir)
    assert rounds["n"] == 2  # the commit refused round 1 and re-folded
    assert world["generation"] == 1
    assert world["num_nodes"] == 27  # 24 base + 2 + the late 1
    assert world["deltas_adopted"] == 2
    # gen-0 staged files remain as history; the ADOPTED graph carries them
    assert len(deltas.staged_delta_paths(run_dir, 0)) == 2
    # exhaustion is a structured error, not an orphaning adoption
    def always_racing(*args, **kwargs):
        deltas.append_delta(
            run_dir,
            np.ones((1, 4), np.float32),
            np.zeros((2, 0), np.int64),
        )
        return real_build(*args, **kwargs)

    monkeypatch.setattr(plan_mod, "build_plan_shards", always_racing)
    deltas.append_delta(run_dir, np.ones((1, 4), np.float32),
                        np.zeros((2, 0), np.int64))
    with pytest.raises(deltas.DeltaError, match="quiesce appends"):
        deltas.replan(run_dir, max_rounds=2)
    assert deltas.read_world(run_dir)["generation"] == 1  # nothing adopted


def test_append_delta_concurrent_appends_never_collide(tmp_path):
    """Concurrent appends (request threads) get distinct seq files and a
    contiguous, collision-free id space; a racer's already-published file
    is detected by the no-clobber link and retried, never overwritten."""
    from dgraph_tpu.serve import deltas

    run_dir = _tiny_delta_world(tmp_path)
    recs = []

    def appender(i):
        recs.append(deltas.append_delta(
            run_dir, np.full((1, 4), float(i), np.float32),
            np.zeros((2, 0), np.int64),
        ))

    threads = [threading.Thread(target=appender, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    paths = deltas.staged_delta_paths(run_dir, 0)
    assert len(paths) == 8
    bases = sorted(r["id_base"] for r in recs)
    assert bases == list(range(24, 32))  # contiguous, no collisions
    assert sorted(r["seq"] for r in recs) == list(range(8))


def test_replan_sigterm_is_atomic_old_or_new_never_torn(tmp_path):
    """The chaos acceptance pin, subprocess-for-real: SIGTERM at the
    commit boundary (all generation-1 artifacts durable, pointer not yet
    flipped) leaves generation 0 adopted; SIGTERM mid shard stream leaves
    generation 0 adopted; a chaos-free rerun resumes and adopts
    generation 1 — old or new, never torn."""
    from dgraph_tpu.plan_shards import read_manifest
    from dgraph_tpu.serve import deltas

    worker = os.path.join(REPO, "tests", "_replan_worker.py")
    env_base = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONUNBUFFERED": "1",
    }
    env_base.pop("DGRAPH_CHAOS", None)

    for clause, label in (
        # index 1 = the second serve.replan consult: the commit boundary
        ("serve.replan=sigterm@1", "commit-boundary"),
        # kill mid shard writes: the resumable-build torn window
        ("plan.write=sigterm@2", "mid-shard-stream"),
    ):
        run_dir = str(tmp_path / label)
        out = subprocess.run(
            [sys.executable, worker, run_dir, "init"],
            capture_output=True, text=True, timeout=300, env=env_base,
            cwd=REPO,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        assert deltas.read_world(run_dir)["generation"] == 0

        out = subprocess.run(
            [sys.executable, worker, run_dir, "replan"],
            capture_output=True, text=True, timeout=300,
            env={**env_base, "DGRAPH_CHAOS": clause}, cwd=REPO,
        )
        assert out.returncode != 0, (
            f"{label}: chaos sigterm did not kill the replan: "
            + out.stdout + out.stderr
        )
        # the adoption contract: pointer still names the OLD generation
        world = deltas.read_world(run_dir)
        assert world["generation"] == 0, f"{label}: torn adoption: {world}"

        # chaos-free rerun: the streaming build resumes, adoption commits
        out = subprocess.run(
            [sys.executable, worker, run_dir, "replan"],
            capture_output=True, text=True, timeout=300, env=env_base,
            cwd=REPO,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        world = deltas.read_world(run_dir)
        assert world["generation"] == 1 and world["num_nodes"] == 51
        manifest = read_manifest(deltas.plan_dir(run_dir, 1))
        assert manifest["complete"], f"{label}: adopted an incomplete plan"
