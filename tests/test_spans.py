"""Flight recorder (obs.spans) + CPU scan-delta attribution (obs.
attribution): ID propagation, Perfetto export schema, the disabled ==
one-attr-read no-op pin (zero new XLA compiles), the supervised-restart
lineage acceptance pin (both attempts under ONE trace id, valid Chrome
trace JSON), serve per-stage quantiles through a stub engine (zero
compiles), and one scan-delta attribution smoke on the smallest 2-shard
graph — the contracts docs/tracing.md documents."""

import ast
import json
import os
import sys

import numpy as np
import pytest

from dgraph_tpu.obs import spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with the default tracer disabled — an
    enabled global tracer leaking between tests would silently change
    other suites' hot paths."""
    spans.disable()
    yield
    spans.disable()


# ---------------------------------------------------------------------------
# core: IDs, propagation, disabled pin
# ---------------------------------------------------------------------------


def test_disabled_is_one_attr_read_noop(tmp_path):
    t = spans.Tracer()
    assert t.span("anything", x=1) is spans.NOOP_SPAN
    assert spans.span("anything") is spans.NOOP_SPAN  # module default too
    # the noop is inert end-to-end: context manager, annotate, end
    with spans.span("x") as s:
        s.annotate(a=1)
        s.end(error="ignored")
    assert not s and s.trace_id is None
    assert spans.current_trace_id() is None
    assert spans.child_env() == {}
    # and nothing was ever written anywhere (no default sink file)
    assert not (tmp_path / "spans.jsonl").exists()


def test_stage_totals_are_kept_with_the_tracer_off():
    """stage() is the always-on entry point: its seconds reach the table
    with tracing off, while span() stays the shared no-op; with tracing on
    the same stage is also one normal span record with its attributes."""
    name = "test.stage_off"
    assert not spans.enabled()
    with spans.stage(name, rows=7) as st:
        st.annotate(more=1)  # inert while tracing is off
        assert spans.span("anything") is spans.NOOP_SPAN
        assert spans.current_span() is None  # no span was opened
    row = spans.stage_totals()[name]
    assert row["count"] == 1 and row["total_s"] == row["last_s"] == row["max_s"] > 0

    recs = []
    spans.enable(sink=recs.append)
    with pytest.raises(RuntimeError):
        with spans.stage(name, rows=9):
            raise RuntimeError("a failed stage still counts its time")
    spans.disable()
    row2 = spans.stage_totals()[name]
    assert row2["count"] == 2 and row2["total_s"] > row["total_s"]
    assert [(r["name"], r["status"], r["attrs"]) for r in recs] == [
        (name, "error", {"rows": 9})]


def test_enabled_span_is_a_host_event_of_the_profiler_trace(tmp_path):
    """On the profiler's clock: under jax.profiler.trace an enabled span is
    a host event of the same name, a child inside its parent's interval; a
    span that is never entered (ended by hand) leaves none."""
    import glob
    import gzip

    import jax

    spans.enable(sink=lambda rec: None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("test.parent"):
            with spans.span("test.child"):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
            with spans.stage("test.stage_child"):
                pass
            spans.span("test.by_hand").end()
    finally:
        jax.profiler.stop_trace()
        spans.disable()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.trace.json.gz"))
    with gzip.open(path) as f:
        events = json.load(f)["traceEvents"]
    found = {e["name"]: e for e in events
             if e.get("ph") == "X" and e["name"].startswith("test.")}
    assert set(found) == {"test.parent", "test.child", "test.stage_child"}
    parent = found["test.parent"]
    for name in ("test.child", "test.stage_child"):
        child = found[name]
        assert (child["pid"], child["tid"]) == (parent["pid"], parent["tid"])
        assert parent["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]


def test_spans_module_is_jax_free_static_pin():
    """The supervisor and bench's standalone loader import spans.py on
    machines where any jax call can hang — pin (statically, so the pin
    holds even with jax preloaded by conftest) that the module never
    imports jax anywhere."""
    tree = ast.parse(open(os.path.join(
        REPO, "dgraph_tpu", "obs", "spans.py")).read())
    for node in ast.walk(tree):
        mods = []
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.module:
            mods = [node.module]
        for m in mods:
            assert not (m == "jax" or m.startswith("jax.")), (
                f"spans.py imports {m!r}"
            )


def test_enabled_spans_zero_new_compiles():
    """Tracing around a jitted call must not grow its jit cache: spans are
    host-side only (the obs.metrics zero-overhead discipline, extended)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return x * 2

    f(jnp.float32(1.0))
    f(jnp.float32(2.0))
    warm = f._cache_size() if hasattr(f, "_cache_size") else None
    recs = []
    spans.enable(sink=recs.append)
    with spans.span("jitted-call"):
        f(jnp.float32(3.0))
    spans.disable()
    f(jnp.float32(4.0))
    if warm is not None:
        assert f._cache_size() == warm, "span tracing caused a recompile"
    assert len(recs) == 1 and recs[0]["name"] == "jitted-call"


def test_id_propagation_and_schema():
    recs = []
    tid = spans.enable(sink=recs.append)
    assert spans.enabled() and spans.current_trace_id() == tid
    with spans.span("outer", component="test") as outer:
        assert spans.current_span() is outer
        with spans.span("inner") as inner:
            assert inner.parent_id == outer.span_id
        manual = spans.span("manual", parent=outer)
        manual.end(n=5)
        manual.end(n=99)  # idempotent: second end must not double-write
    assert spans.current_span() is None
    assert [r["name"] for r in recs] == ["inner", "manual", "outer"]
    for r in recs:
        assert r["kind"] == "span" and r["schema"] == 1
        assert r["trace"] == tid and r["dur_ms"] >= 0
        assert r["status"] == "ok" and r["pid"] == os.getpid()
    by_name = {r["name"]: r for r in recs}
    assert by_name["manual"]["attrs"]["n"] == 5
    assert by_name["outer"]["parent"] is None
    json.dumps(recs)  # JSONL-able as-is


def test_exception_marks_error_and_reraises():
    recs = []
    spans.enable(sink=recs.append)
    with pytest.raises(RuntimeError):
        with spans.span("boom"):
            raise RuntimeError("kapow")
    assert recs[0]["status"] == "error" and "kapow" in recs[0]["error"]


def test_child_env_cross_process_lineage():
    recs = []
    tid = spans.enable(sink=recs.append)
    with spans.span("parent") as p:
        env = spans.child_env()
    assert env[spans.ENV_TRACE_ID] == tid
    assert env[spans.ENV_PARENT] == p.span_id
    child = spans.Tracer()
    assert child.configure_from_env(env)
    child._set_sink(recs.append)
    child.span("child-root").end()
    assert recs[-1]["trace"] == tid and recs[-1]["parent"] == p.span_id
    # a process that inherits the id WITHOUT enabling still reports it
    # (the RunHealth trace_id fallback path)
    old = os.environ.get(spans.ENV_TRACE_ID)
    try:
        os.environ[spans.ENV_TRACE_ID] = "abc123"
        spans.disable()
        assert spans.current_trace_id() == "abc123"
    finally:
        if old is None:
            os.environ.pop(spans.ENV_TRACE_ID, None)
        else:
            os.environ[spans.ENV_TRACE_ID] = old


def test_run_health_carries_trace_id():
    from dgraph_tpu.obs.health import RunHealth

    spans.enable(sink=lambda r: None, trace_id="cafe0000cafe0000")
    h = RunHealth.begin("test.component").finish()
    assert h["trace_id"] == "cafe0000cafe0000"
    spans.disable()
    assert RunHealth.begin("test.component").finish()["trace_id"] is None


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------


def test_perfetto_export_schema(tmp_path):
    recs = []
    spans.enable(sink=recs.append)
    with spans.span("a", component="serve"):
        with spans.span("b"):
            pass
    out_path = str(tmp_path / "trace.json")
    trace = spans.export_perfetto(recs, out_path)
    # the file must load as valid Chrome trace JSON
    loaded = json.load(open(out_path))
    assert loaded == json.loads(json.dumps(trace))
    assert loaded["displayTimeUnit"] == "ms"
    xs = [e for e in loaded["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:
        assert {"name", "cat", "ts", "dur", "pid", "tid", "args"} <= set(e)
        assert e["dur"] >= 0 and e["ts"] > 0
    # span/parent ids survive into args for trace reconstruction
    by_name = {e["name"]: e for e in xs}
    assert by_name["b"]["args"]["parent"] == by_name["a"]["args"]["span"]
    # metadata process_name events are present and well-formed
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in loaded["traceEvents"])


def test_perfetto_export_reads_jsonl_skipping_other_kinds(tmp_path):
    path = str(tmp_path / "log.jsonl")
    with open(path, "w") as fh:
        fh.write("# log opened\n")
        fh.write(json.dumps({"kind": "run_health", "wedge": "none"}) + "\n")
        fh.write(json.dumps({
            "kind": "span", "schema": 1, "trace": "t", "span": "s",
            "parent": None, "name": "x", "ts_unix": 1.0, "dur_ms": 2.0,
            "status": "ok", "pid": 1, "tid": 1,
        }) + "\n")
        fh.write("not json\n")
    trace = spans.export_perfetto(path)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 1 and xs[0]["name"] == "x"


# ---------------------------------------------------------------------------
# acceptance pin: supervised train run with one injected restart -> one
# trace, both attempts, valid Chrome trace JSON (no manual step)
# ---------------------------------------------------------------------------


def test_supervised_restart_one_trace_two_attempts(tmp_path):
    from dgraph_tpu.train.supervise import supervise

    log_path = str(tmp_path / "spans.jsonl")
    tid = spans.enable(sink=log_path)
    # child exits 17 (wedged) on attempt 0, cleanly on attempt 1 — the
    # injected-restart scenario, driven by the supervisor's own
    # DGRAPH_CHAOS_ATTEMPT export
    code = ("import os, sys; "
            "sys.exit(17 if os.environ['DGRAPH_CHAOS_ATTEMPT'] == '0' "
            "else 0)")
    try:
        lineage = supervise([sys.executable, "-c", code], backoff_s=0.01)
    finally:
        spans.disable()
    assert lineage["final_exit_code"] == 0 and lineage["restarts"] == 1
    # lineage is joinable: trace id + per-attempt span ids
    assert lineage["trace_id"] == tid
    span_ids = [a["span_id"] for a in lineage["attempts"]]
    assert len(span_ids) == 2 and all(span_ids)
    # the children inherited the trace env
    recs = spans.read_spans(log_path)
    attempts = [r for r in recs if r["name"] == "supervise.attempt"]
    assert len(attempts) == 2
    assert {r["span"] for r in attempts} == set(span_ids)
    assert all(r["trace"] == tid for r in recs)
    assert attempts[0]["status"] == "error"  # exit 17
    assert attempts[1]["status"] == "ok"
    run = [r for r in recs if r["name"] == "train.supervise"]
    assert len(run) == 1
    assert all(a["parent"] == run[0]["span"] for a in attempts)
    # Perfetto export loads as valid Chrome trace JSON with BOTH attempts
    # under one trace id — pinned here, no manual step
    out = str(tmp_path / "trace.perfetto.json")
    spans.export_perfetto(log_path, out)
    loaded = json.load(open(out))
    xs = [e for e in loaded["traceEvents"] if e["ph"] == "X"]
    assert sum(e["name"] == "supervise.attempt" for e in xs) == 2
    assert {e["args"]["trace"] for e in xs} == {tid}


def test_lineage_without_tracing_is_nullsafe():
    """Tracing off: the lineage schema still carries the (null) join keys
    and nothing else changes — schema 1 readers unaffected."""
    from dgraph_tpu.train.supervise import supervise

    lineage = supervise([sys.executable, "-c", "raise SystemExit(0)"],
                        backoff_s=0.01)
    assert lineage["trace_id"] is None
    assert lineage["attempts"][0]["span_id"] is None


# ---------------------------------------------------------------------------
# serve: per-stage quantiles + trace-id-surviving rejections (stub engine
# -> zero XLA compiles)
# ---------------------------------------------------------------------------


class _StubLadder:
    sizes = (8, 16)
    max_size = 16

    def bucket_for(self, n):
        from dgraph_tpu.serve.errors import RequestTooLarge

        if n > self.max_size:
            raise RequestTooLarge(f"request of {n} exceeds ladder")
        return 8 if n <= 8 else 16


class _StubEngine:
    """Just enough engine surface for the batcher + health record: infer
    returns zeros and stamps stage times like the real engine."""

    def __init__(self, registry):
        from dgraph_tpu.obs.metrics import Metrics

        self.ladder = _StubLadder()
        self.registry = registry or Metrics()
        self.num_nodes = 100
        self.warmup_s = 0.01
        self.degraded = False
        self.tuning_record_id = None

    def infer(self, ids):
        self.last_stage_ms = {"pad": 0.05, "infer": 0.2}
        self.registry.histogram("serve.stage.pad_ms", 0.05)
        self.registry.histogram("serve.stage.infer_ms", 0.2)
        return np.zeros((len(ids), 4), np.float32)

    def recompiles_since_warmup(self):
        return 0


def test_serve_stage_quantiles_and_request_spans():
    from dgraph_tpu.obs.metrics import Metrics
    from dgraph_tpu.serve.batcher import MicroBatcher
    from dgraph_tpu.serve.errors import QueueFull, RequestTooLarge
    from dgraph_tpu.serve.health import serve_health_record

    recs = []
    tid = spans.enable(sink=recs.append)
    reg = Metrics()
    engine = _StubEngine(reg)
    batcher = MicroBatcher(engine, max_batch_size=4, max_delay_ms=1.0,
                           max_queue_depth=8, registry=reg)
    try:
        for _ in range(6):
            out = batcher.infer(np.arange(3))
            assert out.shape == (3, 4)
        # a too-large request still lands an error-status span under the
        # SAME trace id (trace survives the rejection path)
        with pytest.raises(RequestTooLarge):
            batcher.submit(np.arange(40))
    finally:
        batcher.stop()
        spans.disable()

    req = [r for r in recs if r["name"] == "serve.request"]
    assert len(req) == 7
    assert all(r["trace"] == tid for r in recs)
    ok = [r for r in req if r["status"] == "ok"]
    assert len(ok) == 6
    # the request span carries the full stage breakdown
    for r in ok:
        a = r["attrs"]
        assert {"queue_wait_ms", "batch_form_ms", "pad_ms", "infer_ms",
                "reply_ms", "batch_size"} <= set(a)
    rejected = [r for r in req if r["status"] == "error"]
    assert len(rejected) == 1 and rejected[0]["error"] == "too_large"
    # batch spans exist and the engine stage numbers rode through
    assert any(r["name"] == "serve.batch" for r in recs)

    # per-stage p50/p95/p99 folded into the health record
    rec = serve_health_record(engine, batcher)
    stages = rec["stages_ms"]
    for stage in ("queue_wait", "batch_form", "pad", "infer", "reply"):
        assert stages[stage]["count"] > 0, stage
        assert {"p50", "p95", "p99"} <= set(stages[stage]), stage
    json.dumps(rec, default=str)

    # QueueFull shed (degraded) also ends the span with the trace intact
    recs2 = []
    spans.enable(sink=recs2.append, trace_id=tid)
    engine2 = _StubEngine(Metrics())
    batcher2 = MicroBatcher(engine2, max_queue_depth=8,
                            registry=engine2.registry)
    try:
        batcher2._stopped = True  # reject without racing the worker
        from dgraph_tpu.serve.errors import EngineStopped

        with pytest.raises(EngineStopped):
            batcher2.submit(np.arange(2))
    finally:
        batcher2._stopped = False
        batcher2.stop()
        spans.disable()
    errs = [r for r in recs2 if r["name"] == "serve.request"]
    assert errs and errs[0]["status"] == "error"
    assert errs[0]["trace"] == tid
    assert QueueFull  # imported for the API surface; shed path is above


def test_batcher_disabled_tracing_unchanged():
    """Tracing off: the batcher serves normally and writes no spans (the
    noop rides the _Pending record)."""
    from dgraph_tpu.obs.metrics import Metrics
    from dgraph_tpu.serve.batcher import MicroBatcher

    engine = _StubEngine(Metrics())
    batcher = MicroBatcher(engine, registry=engine.registry)
    try:
        out = batcher.infer(np.arange(5))
        assert out.shape == (5, 4)
    finally:
        batcher.stop()
    # stage histograms still populate (metrics are independent of spans)
    snap = engine.registry.snapshot()
    assert snap["histograms"]["serve.stage.queue_wait_ms"]["count"] == 1


# ---------------------------------------------------------------------------
# scan-delta attribution smoke (smallest 2-shard graph, one lowering,
# minimal scan lengths — compile budget guard)
# ---------------------------------------------------------------------------


def test_scan_delta_attribution_smoke(mesh8):
    from dgraph_tpu.obs.attribution import scan_delta_attribution

    # n_long=6: the per-round delta amortizes over 5 steps, which is what
    # keeps sub-ms CPU steps above dispatch jitter (n_long only changes
    # the scan's static length, not the compile count)
    rec = scan_delta_attribution(
        2, num_nodes=48, num_edges=200, feat_dim=8, hidden=8, num_classes=4,
        impls=("all_to_all",), n_long=6, reps=1, fold_multichip=True,
    )
    assert rec["kind"] == "cpu_scan_delta"
    assert rec["tier"] == "cpu_scan_delta" and rec["schema"] == 1
    assert rec["backend"] == "cpu"
    by = rec["by_impl"]["all_to_all"]
    phases = by["phases_ms"]
    assert set(phases) == {"interior", "exchange", "optimizer", "other"}
    # a smoke on CPU must at least land real positive full-step numbers;
    # phase terms are deltas and may individually be None only if the
    # timing protocol failed (which fails this assert via full_ms)
    assert by["full_ms"] is not None and by["full_ms"] > 0
    assert phases["interior"] is not None and phases["interior"] >= 0
    assert phases["exchange"] is not None and phases["exchange"] >= 0
    # schema-stable + strictly valid JSON (no NaN leaks)
    json.dumps(rec, allow_nan=False)
    # the MULTICHIP fold is present (table may be empty on old artifacts)
    mc = rec["multichip_dryrun"]
    assert mc is None or "step_ms_by_family" in mc


def test_multichip_family_table_parses_stamped_tail(tmp_path):
    from dgraph_tpu.obs.attribution import multichip_family_table

    with open(tmp_path / "MULTICHIP_r09.json", "w") as fh:
        json.dump({
            "n_devices": 8, "ok": True,
            "tail": ("dryrun GCN OK: mesh=(2x4) loss=1.44 "
                     "param_delta=8.680e-01 step_ms=123.4\n"
                     "dryrun RGAT OK: mesh=(1x8) loss=1.95 "
                     "param_delta=6.999e-01 step_ms=77.0\n"),
        }, fh)
    table = multichip_family_table(str(tmp_path))
    assert table["source"] == "MULTICHIP_r09.json"
    assert table["step_ms_by_family"] == {"GCN": 123.4, "RGAT": 77.0}
    assert multichip_family_table(str(tmp_path / "nowhere")) is None
