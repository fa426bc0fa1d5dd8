"""Perf-trajectory ledger + drift sentinel: per-kind ingestion
normalizers (including the wedge-era probe stub and corrupt-line
skip-with-reason paths), the median+MAD tolerance math, the exact-class
zero-tolerance contract, the seeded-drift vacuity mutants, backfill over
the repo's real artifact corpus, and the CLI surfaces — the contracts
docs/perf-ledger.md documents. Everything here is compile-free."""

import json
import os
import subprocess
import sys

import pytest

from dgraph_tpu.obs import regress, report
from dgraph_tpu.obs.ledger import (
    DEFAULT_LEDGER_DIR,
    LEDGER_SCHEMA_VERSION,
    SERVE_HEALTH_SCHEMA_VERSION,
    TIER_KINDS,
    _fixture_bench_round,
    atomic_append_jsonl,
    backfill,
    ingest,
    ledger_path,
    maybe_ingest,
    normalize_record,
    read_ledger,
    resolve_ledger_dir,
    summarize,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# normalizers: one per record kind
# ---------------------------------------------------------------------------


def test_bench_round_normalizes_with_tiers_and_git_rev():
    entries, skips = normalize_record(_fixture_bench_round(), "BENCH_r06.json")
    assert not skips
    kinds = {e["kind"] for e in entries}
    assert {"bench_round", "schedule_drift", "cpu_scan_delta"} <= kinds
    head = next(e for e in entries if e["kind"] == "bench_round")
    assert head["metrics"]["epoch_time_ms"] == 400.0
    assert head["git_rev"] == "abc1234"
    assert head["schema"] == LEDGER_SCHEMA_VERSION
    # tiers inherit the round's commit (the bisect key travels with them)
    assert all(e["git_rev"] == "abc1234" for e in entries)


def test_probe_stub_ingests_as_probe_wedge():
    # the BENCH_r05 shape: the driver wrapper whose child never produced
    # JSON — wedge history is trajectory, never a crash or a silent drop
    stub = {"n": 5, "cmd": "timeout 1500 python bench.py", "rc": 3,
            "tail": "probe attempt 7 hung (wedged lease)", "parsed": None}
    entries, skips = normalize_record(stub, "BENCH_r05.json")
    assert not skips and len(entries) == 1
    (e,) = entries
    assert e["kind"] == "probe_wedge" and e["round"] == 5
    assert "wedged lease" in e["meta"]["last_line"]


def test_structured_null_round_is_probe_wedge_but_tiers_survive():
    # the r03/r04 shape: parsed JSON with value null + attached tiers —
    # the round is wedge history but its fallback tiers are real signal
    obj = {"n": 4, "cmd": "python bench.py", "rc": 3, "tail": "",
           "parsed": dict(_fixture_bench_round(), value=None,
                          vs_baseline=None,
                          error="backend never initialized; wedged lease")}
    entries, _ = normalize_record(obj, "BENCH_r04.json")
    kinds = [e["kind"] for e in entries]
    assert "probe_wedge" in kinds and "bench_round" not in kinds
    assert "schedule_drift" in kinds and "cpu_scan_delta" in kinds


def test_multichip_tail_parses_families():
    obj = {"n": 3, "n_devices": 8, "ok": True, "rc": 0,
           "tail": ("dryrun GCN OK: step_ms=12.5\n"
                    "dryrun GraphTransformer OK:\n"
                    "dryrun dryrun_multichip OK:\n")}
    entries, skips = normalize_record(obj, "MULTICHIP_r03.json")
    assert not skips and len(entries) == 1
    m = entries[0]["metrics"]
    assert m["n_families"] == 2 and m["step_ms/GCN"] == 12.5
    assert "step_ms/GraphTransformer" not in m  # untimed dryrun
    assert entries[0]["meta"]["families"] == ["GCN", "GraphTransformer"]


def test_tune_record_and_serve_health_normalize():
    tune = {"kind": "tune_record", "record_id": "sig-abc", "phase": "train",
            "created_at": "2026-08-01T00:00:00Z",
            "config": {"halo_impl": "overlap", "pad_multiple": 8},
            "cost": {"step_ms": 12.0}}
    (e,), skips = normalize_record(tune, "tune_sig.json")
    assert not skips
    assert e["kind"] == "tune_record" and e["halo_impl"] == "overlap"
    assert e["workload"] == "sig-abc" and e["metrics"]["step_ms"] == 12.0

    serve = regress._fx_serve(0)
    (e,), skips = normalize_record(serve, "serve.jsonl")
    assert not skips
    assert e["kind"] == "serve_health"
    assert e["metrics"]["p99_ms"] == 50.0
    assert e["metrics"]["infer_p99_ms"] == 8.0
    assert e["metrics"]["recompiles_since_warmup"] == 0


def test_serve_health_newer_schema_skips_with_reason():
    serve = dict(regress._fx_serve(0),
                 schema_version=SERVE_HEALTH_SCHEMA_VERSION + 1)
    entries, skips = normalize_record(serve, "serve.jsonl")
    assert not entries and len(skips) == 1
    assert "newer than supported" in skips[0]["reason"]


def test_lineage_and_run_health_normalize():
    lineage = {"kind": "supervise_lineage", "restarts": 2, "gave_up": False,
               "final_exit_code": 0, "attempts": [{}, {}, {}],
               "run_health": {"wall_s": 30.0, "wedge": "none",
                              "git_rev": "rev9", "started_at": "t"}}
    (e,), skips = normalize_record(lineage, "logs/supervise.jsonl")
    assert not skips
    assert e["kind"] == "supervise_lineage" and e["git_rev"] == "rev9"
    assert e["metrics"]["restarts"] == 2 and e["metrics"]["attempts"] == 3

    rh = {"kind": "run_health", "component": "serve.engine", "wall_s": 1.0,
          "probes": [{}], "wedge": "none", "started_at": "t"}
    (e,), skips = normalize_record(rh, "logs/serve.jsonl")
    assert not skips
    assert e["kind"] == "run_health" and e["workload"] == "serve.engine"


def test_unrecognized_and_declined_payloads_skip_with_reason():
    entries, skips = normalize_record({"surprise": True}, "mystery.json")
    assert not entries and "unrecognized" in skips[0]["reason"]
    entries, skips = normalize_record({"kind": "span"}, "spans.jsonl")
    assert not entries and "high-volume" in skips[0]["reason"]
    entries, skips = normalize_record([1, 2, 3], "list.json")
    assert not entries and "not an object" in skips[0]["reason"]


# ---------------------------------------------------------------------------
# store: append durability, dedup, torn lines, the env knob
# ---------------------------------------------------------------------------


def test_ingest_is_idempotent_and_reads_back(tmp_path):
    d = str(tmp_path)
    r = ingest(_fixture_bench_round(), "BENCH_r06.json", d)
    assert r["appended"] >= 3 and r["deduped"] == 0
    r2 = ingest(_fixture_bench_round(), "BENCH_r06.json", d)
    assert r2["appended"] == 0 and r2["deduped"] == r["appended"]
    entries, skips = read_ledger(d)
    assert len(entries) == r["appended"] and not skips
    ids = [e["entry_id"] for e in entries]
    assert len(set(ids)) == len(ids)


def test_torn_trailing_line_skipped_earlier_entries_intact(tmp_path):
    d = str(tmp_path)
    ingest(_fixture_bench_round(), "BENCH_r06.json", d)
    n = len(read_ledger(d)[0])
    with open(ledger_path(d), "a") as fh:
        fh.write('{"schema": 1, "kind": "bench_ro')  # crash mid-append
    entries, skips = read_ledger(d)
    assert len(entries) == n and len(skips) == 1
    assert "torn" in skips[0]["reason"]
    # the next durable append lands on its own line regardless
    atomic_append_jsonl(ledger_path(d), [{"entry_id": "x", "kind": "t"}])
    entries, skips = read_ledger(d)
    assert len(entries) == n + 1 and len(skips) == 1


def test_a_compiled_schedules_record_is_skipped_and_the_rest_ingests(
        tmp_path):
    """A ``sched_compile`` artifact, as a run of an earlier tree wrote
    them, names a lowering no run can take: it is declined with a reason
    and counted, whether it arrives as an artifact or already lies in the
    stored file, and everything around it is kept."""
    d = str(tmp_path)
    stale = {
        "kind": "sched_compile",
        "workload": {"world_size": 4, "nodes": 96, "feat_dim": 8},
        "schedule_id": "abc123def456", "rounds": 3, "transfers": 5,
        "operand_bytes_per_shard": 4096, "round_rows": [64, 32, 32],
        "exposed_us": 7.5,
    }
    entries, skips = normalize_record(stale, "old_run.json")
    assert not entries and len(skips) == 1
    assert "compiled schedule" in skips[0]["reason"]
    reports = [
        ingest(_fixture_bench_round(), "BENCH_r06.json", d),
        ingest(stale, "old_run.json", d),
        ingest(regress._fx_wire(0), "wire_r00.json", d),
    ]
    assert [r["appended"] > 0 for r in reports] == [True, False, True]
    assert [len(r["skipped"]) for r in reports] == [0, 1, 0]
    # a line an earlier tree stored under that kind is read like any other
    # entry and gates nothing: the rest of the file still checks GREEN
    atomic_append_jsonl(ledger_path(d), [{
        "entry_id": "old-sched-0", "kind": "sched_compile",
        "workload": "w4_n96", "metrics": {"operand_bytes": 4096},
    }])
    kept, read_skips = read_ledger(d)
    assert not read_skips
    assert summarize(d)["by_kind"]["sched_compile"] == 1
    assert len(kept) == sum(r["appended"] for r in reports) + 1
    assert regress.check_ledger(d)["ok"]


def test_ledger_dir_knob(tmp_path, monkeypatch):
    monkeypatch.delenv("DGRAPH_LEDGER_DIR", raising=False)
    assert resolve_ledger_dir(default_on=True) == DEFAULT_LEDGER_DIR
    assert resolve_ledger_dir(default_on=False) is None
    for off in ("0", "off", "none", ""):
        monkeypatch.setenv("DGRAPH_LEDGER_DIR", off)
        assert resolve_ledger_dir(default_on=True) is None
    monkeypatch.setenv("DGRAPH_LEDGER_DIR", str(tmp_path))
    assert resolve_ledger_dir() == str(tmp_path)
    # maybe_ingest honors the knob and swallows bad payloads
    assert maybe_ingest(_fixture_bench_round(), "t")["appended"] >= 3
    assert maybe_ingest(object(), "t") is not None  # skip, not crash
    monkeypatch.setenv("DGRAPH_LEDGER_DIR", "off")
    assert maybe_ingest(_fixture_bench_round(), "t", default_on=True) is None


def test_default_dir_matches_tune_record_dir(monkeypatch):
    # the ledger may not import tune.record (jax-free contract), so the
    # "artifacts that travel together live together" dir is a duplicated
    # literal — this pin is what keeps the two from drifting apart
    monkeypatch.delenv("DGRAPH_TUNE_DIR", raising=False)
    from dgraph_tpu.tune.record import default_record_dir

    assert DEFAULT_LEDGER_DIR == default_record_dir()


def test_serve_health_writer_shares_schema_constant():
    # serve/health.py stamps the SAME constant the normalizer validates —
    # read the source rather than build an engine (compile-free suite)
    src = open(os.path.join(REPO, "dgraph_tpu", "serve", "health.py")).read()
    assert "SERVE_HEALTH_SCHEMA_VERSION" in src
    assert '"schema_version": SERVE_HEALTH_SCHEMA_VERSION' in src


# ---------------------------------------------------------------------------
# sentinel: tolerance math + verdict classes
# ---------------------------------------------------------------------------


def test_baseline_stats_median_mad_math():
    s = regress.baseline_stats([20.0, 20.4, 19.8, 20.1, 20.3])
    assert s["median"] == pytest.approx(20.1)
    assert s["mad"] == pytest.approx(0.2)
    # REL_FLOOR dominates here: max(4*1.4826*0.2, 0.25*20.1, 0.5)
    assert s["tolerance"] == pytest.approx(0.25 * 20.1)
    # and the MAD term dominates for a noisy series
    s = regress.baseline_stats([10.0, 14.0, 6.0, 18.0, 2.0])
    assert s["mad"] == pytest.approx(4.0)
    assert s["tolerance"] == pytest.approx(4.0 * 1.4826 * 4.0)


def test_metric_class_partition():
    assert regress.metric_class("traced_bytes") == "exact"
    assert regress.metric_class("collective_count") == "exact"
    assert regress.metric_class("identical") == "exact"
    assert regress.metric_class("recompiles_since_warmup") == "exact"
    assert regress.metric_class("step_ms/GCN") == "timing"
    assert regress.metric_class("p99_ms") == "timing"
    assert regress.metric_class("vs_baseline") == "timing"
    assert regress.metric_class("wall_s") == "info"
    assert regress.metric_class("rc") == "info"


def test_exact_class_zero_tolerance(tmp_path):
    # +64 bytes is ~1.6% — invisible to any percentage gate; the exact
    # class must go RED on ANY change, which is the whole point of it
    d = str(tmp_path)
    regress._seed(d)
    ingest(regress._fx_round(6, traced_bytes=4096 + 64), "r06", d)
    rep = regress.check_ledger(d)
    reds = [v for v in rep["verdicts"] if v["verdict"] == "RED"]
    assert not rep["ok"]
    assert any(v["metric"] == "traced_bytes" and "zero tolerance"
               in v["reason"] for v in reds)
    # every RED names the offending ledger entry
    assert all(v["entry_id"] for v in reds)


def test_timing_class_tolerates_jitter_but_not_regression(tmp_path):
    d = str(tmp_path)
    regress._seed(d)
    rep = regress.check_ledger(d)
    assert rep["ok"] and rep["counts"]["RED"] == 0
    assert rep["counts"]["GREEN"] >= 8  # the gate is not vacuous
    # a within-tolerance wobble stays GREEN
    ingest(regress._fx_round(6, exchange_ms=21.0), "r06", d)
    assert regress.check_ledger(d)["ok"]
    # a real regression goes RED
    ingest(regress._fx_round(7, exchange_ms=36.0), "r07", d)
    rep = regress.check_ledger(d)
    assert not rep["ok"]
    assert any(v["metric"] == "exchange_ms" and v["verdict"] == "RED"
               for v in rep["verdicts"])


def test_no_baseline_verdict_below_min_points(tmp_path):
    d = str(tmp_path)
    for i in range(2):  # 1 prior point < MIN_TIMING_BASELINE
        ingest(regress._fx_serve(i), f"serve_r{i:02d}", d)
    rep = regress.check_ledger(d)
    nb = [v for v in rep["verdicts"] if v["verdict"] == "NO_BASELINE"]
    assert rep["ok"] and any(v["metric"] == "p99_ms" for v in nb)


def test_dropped_tier_goes_red(tmp_path):
    d = str(tmp_path)
    regress._seed(d)
    ingest(regress._fx_round(6, include_hlo=False), "r06", d)
    rep = regress.check_ledger(d)
    hit = next(v for v in rep["verdicts"]
               if v["metric"] == "fallback_tiers")
    assert hit["verdict"] == "RED" and "hlo_drift" in hit["reason"]
    assert set(TIER_KINDS) >= set(hit["baseline"]["tiers"])


def test_seeded_drift_selftests_pass():
    # the vacuity guards themselves: ledger fixtures, the four drift
    # mutants (each must go RED), and the report render pins
    from dgraph_tpu.obs import ledger

    assert ledger._selftest()["ok"]
    assert regress._selftest()["ok"]
    assert report._selftest()["ok"]


# ---------------------------------------------------------------------------
# backfill + report over the REAL artifact corpus
# ---------------------------------------------------------------------------


def test_backfill_real_corpus_and_report(tmp_path):
    d = str(tmp_path)
    rep = backfill(REPO, d)
    assert rep["files"] >= 6  # BASELINE + MULTICHIP_r* (the BENCH_* rounds
    # of the removed installation are deleted; their shapes stay covered
    # by the normalize_record fixtures above)
    assert rep["appended"] >= 6
    s = summarize(d)
    assert s["by_kind"]["multichip_dryrun"] >= 5
    assert s["by_kind"]["reference_note"] == 1
    entries, _ = read_ledger(d)
    # idempotent: a second run appends nothing
    rep2 = backfill(REPO, d)
    assert rep2["appended"] == 0 and rep2["deduped"] == rep["appended"]
    # the real corpus gates GREEN (no synthetic drift in history)
    assert regress.check_ledger(d)["ok"]
    # and the trajectory renders the corpus
    md = report.render_trajectory(entries, directory=d)
    assert "## multichip_dryrun" in md and "n_families" in md


# ---------------------------------------------------------------------------
# CLI smokes (subprocesses kept to the compile-free minimum)
# ---------------------------------------------------------------------------


def _run_cli(args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m"] + args, cwd=REPO, env=env,
        capture_output=True, text=True, timeout=180,
    )


def test_cli_backfill_regress_report_roundtrip(tmp_path):
    d = str(tmp_path / "ledger")
    p = _run_cli(["dgraph_tpu.obs.ledger", "--backfill", REPO, "--dir", d])
    assert p.returncode == 0, p.stderr
    assert json.loads(p.stdout.splitlines()[-1])["appended"] >= 6

    log = str(tmp_path / "regress.jsonl")
    p = _run_cli(["dgraph_tpu.obs.regress", "--dir", d,
                  "--log_path", log])
    assert p.returncode == 0, p.stderr
    out = json.loads(p.stdout.splitlines()[-1])
    assert out["ok"] and out["kind"] == "regress_report"
    # RunHealth + report JSONL landed (the every-exit-path contract)
    lines = [json.loads(x) for x in open(log)]
    assert [x["kind"] for x in lines] == ["run_health", "regress_report"]

    md_path = str(tmp_path / "TRAJECTORY.md")
    p = _run_cli(["dgraph_tpu.obs.report", "--dir", d, "--out", md_path])
    assert p.returncode == 0, p.stderr
    assert "## multichip_dryrun" in open(md_path).read()


def test_cli_regress_exits_nonzero_on_red(tmp_path):
    d = str(tmp_path)
    regress._seed(d)
    ingest(regress._fx_round(6, exchange_ms=36.0), "r06", d)
    log = str(tmp_path / "regress.jsonl")
    p = _run_cli(["dgraph_tpu.obs.regress", "--dir", d, "--log_path", log])
    assert p.returncode == 1
    out = json.loads(p.stdout.splitlines()[-1])
    assert not out["ok"] and out["counts"]["RED"] >= 1
    # the log still landed on the failing path
    assert [json.loads(x)["kind"] for x in open(log)] == [
        "run_health", "regress_report"]
