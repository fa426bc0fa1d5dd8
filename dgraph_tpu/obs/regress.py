"""Drift sentinel over the perf-trajectory ledger: noise-aware
regression gating with per-(metric, workload, halo lowering) baselines.

A priced number is only useful if drift against it is detected. This
module turns the ledger (:mod:`dgraph_tpu.obs.ledger`) into a gate:

- **Exact class** — the byte-exact metrics (traced/lowered/footprint
  bytes, collective counts, the SPMD identity bit): these are outputs of
  deterministic lowering, so they must never drift *at all*. Any change
  vs the previous entry is RED with zero tolerance.
- **Timing class** — wall-clock metrics (cpu_scan_delta phase ms, serve
  p50/p95/p99, bench epoch ms): baselined by the median of a trailing
  window with a MAD-scaled tolerance (median absolute deviation × 1.4826
  estimates sigma for normal noise), floored so shared-CPU jitter can't
  flap the gate. Only regressions (latest above median + tolerance) go
  RED — getting faster is the point, not an alarm.
- **Dropped-tier** — a bench round that silently loses one of the four
  fallback tiers (schedule_drift / cpu_scan_delta / hlo_drift /
  spmd_drift) regressed the *observability*, which is exactly how a perf
  regression next hides; the sentinel compares each round's tier set
  against the previous round's.

Verdicts are structured (GREEN / RED / NO_BASELINE) and carry the
offending ledger entry ids. ``python -m dgraph_tpu.obs.regress`` exits
nonzero on any RED and writes a RunHealth + report record to a JSONL
log on every exit path (a stdlib sink with the ExperimentLog line
format — ``utils.logging.ExperimentLog`` itself imports jax, which this
module may not: it is jax-free by the same lint-enforced contract as the
ledger, and runs on a machine where jax is wedged or absent).

``--selftest`` seeds a synthetic trajectory and six drifted mutants
(inflated wire bytes, slowed scan-delta, fattened p99, dropped tier,
drifted wire-format bytes, drifted grown world) — each must go RED, and
the clean trajectory must stay GREEN, or the selftest itself fails (the
vacuity guard: a sentinel that can't see seeded drift gates nothing).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

from dgraph_tpu.obs.health import RunHealth
from dgraph_tpu.obs.ledger import (
    DEFAULT_LEDGER_DIR,
    TIER_KINDS,
    atomic_append_jsonl,
    ingest,
    read_ledger,
    resolve_ledger_dir,
)

# --- metric classification -------------------------------------------------

# byte-exact outputs of deterministic lowering: zero tolerance
EXACT_SUFFIXES = ("_bytes", "_count", "_collectives")
EXACT_NAMES = frozenset({
    "identical",                 # spmd_drift: ranks agree on the schedule
    "drift",                     # any tier's own drift verdict bit
    "n_families",                # multichip dryrun family coverage
    "recompiles_since_warmup",   # serving steady-state SLO: must be 0
})

# wall-clock metrics: median + MAD window
TIMING_SUFFIXES = ("_ms", "_us")
TIMING_NAMES = frozenset({"vs_baseline"})  # ratio of the primary metric

# numbers stored for context, not gated (wall budgets, exit codes, ...)
IGNORE_NAMES = frozenset({
    "wall_s", "warmup_s", "rc", "final_exit_code", "restarts", "attempts",
    "requests", "queue_depth", "n_tenants", "n_probes", "final_world",
})

# tolerance model (documented in docs/perf-ledger.md; tests pin the math)
MIN_TIMING_BASELINE = 3   # fewer prior points -> NO_BASELINE
K_MAD = 4.0               # tolerance = K_MAD * 1.4826 * MAD ...
REL_FLOOR = 0.25          # ... floored at 25% of the median ...
ABS_FLOOR = 0.5           # ... and at 0.5 (ms/us) absolute

_MAD_SIGMA = 1.4826  # MAD -> sigma for normally-distributed noise


def metric_class(name: str) -> str:
    """'exact' | 'timing' | 'info' for one normalized metric name."""
    if name in IGNORE_NAMES:
        return "info"
    base = name.split("/", 1)[0]  # "step_ms/GCN" classifies as step_ms
    if base in EXACT_NAMES or base.endswith(EXACT_SUFFIXES):
        return "exact"
    if base in TIMING_NAMES or base.endswith(TIMING_SUFFIXES):
        return "timing"
    return "info"


def baseline_stats(values: list) -> dict:
    """Median + MAD of a series (the noise-aware baseline for the timing
    class), plus the derived tolerance."""
    vs = sorted(values)
    n = len(vs)
    mid = n // 2
    median = vs[mid] if n % 2 else (vs[mid - 1] + vs[mid]) / 2.0
    devs = sorted(abs(v - median) for v in values)
    mad = devs[mid] if n % 2 else (devs[mid - 1] + devs[mid]) / 2.0
    tol = max(K_MAD * _MAD_SIGMA * mad, REL_FLOOR * abs(median), ABS_FLOOR)
    return {"median": median, "mad": mad, "tolerance": tol, "n": n}


# --- verdicts --------------------------------------------------------------


def _series(entries: list) -> dict:
    """(kind, workload, halo_impl, metric) -> ordered [(value, entry_id)].
    File order is ingestion order — the trajectory's arrow of time."""
    out: dict = {}
    for e in entries:
        for metric, value in (e.get("metrics") or {}).items():
            key = (e.get("kind"), e.get("workload"), e.get("halo_impl"),
                   metric)
            out.setdefault(key, []).append((value, e.get("entry_id")))
    return out


def _verdict(key, points, window: int) -> Optional[dict]:
    kind, workload, halo_impl, metric = key
    cls = metric_class(metric)
    if cls == "info" or len(points) == 0:
        return None
    latest_v, latest_id = points[-1]
    history = points[:-1][-window:]
    base = {
        "kind": kind, "workload": workload, "halo_impl": halo_impl,
        "metric": metric, "class": cls, "latest": latest_v,
        "entry_id": latest_id,
        "baseline_ids": [pid for _, pid in history],
    }
    if cls == "exact":
        if not history:
            return {**base, "verdict": "NO_BASELINE",
                    "reason": "no prior entry for an exact-class metric"}
        prev_v, prev_id = history[-1]
        if latest_v != prev_v:
            return {**base, "verdict": "RED",
                    "baseline": {"value": prev_v, "entry_id": prev_id},
                    "reason": f"exact-class metric drifted: {prev_v!r} -> "
                              f"{latest_v!r} (zero tolerance)"}
        return {**base, "verdict": "GREEN",
                "baseline": {"value": prev_v, "entry_id": prev_id}}
    # timing
    if len(history) < MIN_TIMING_BASELINE:
        return {**base, "verdict": "NO_BASELINE",
                "reason": f"{len(history)} prior points < "
                          f"{MIN_TIMING_BASELINE} needed for a "
                          f"median+MAD baseline"}
    stats = baseline_stats([v for v, _ in history])
    limit = stats["median"] + stats["tolerance"]
    if latest_v > limit:
        return {**base, "verdict": "RED", "baseline": stats,
                "reason": f"timing regression: {latest_v:.4g} > median "
                          f"{stats['median']:.4g} + tolerance "
                          f"{stats['tolerance']:.4g}"}
    return {**base, "verdict": "GREEN", "baseline": stats}


def round_groups(entries: list) -> list:
    """Bench rounds in trajectory order, each with the tier kinds that
    landed for it (a bench_round/probe_wedge entry heads a round; the
    tier entries ingested with it follow in file order)."""
    groups: list = []
    cur = None
    for e in entries:
        if e.get("kind") in ("bench_round", "probe_wedge"):
            cur = {"head_id": e.get("entry_id"), "round": e.get("round"),
                   "source": e.get("source"), "tiers": []}
            groups.append(cur)
        elif e.get("kind") in TIER_KINDS and cur is not None:
            if e["kind"] not in cur["tiers"]:
                cur["tiers"].append(e["kind"])
    return groups


def dropped_tier_verdicts(entries: list) -> list:
    """RED when the latest round lost a fallback tier the previous
    tier-bearing round had — silent observability loss is itself drift."""
    groups = round_groups(entries)
    if len(groups) < 2:
        return []
    last = groups[-1]
    prev = next((g for g in reversed(groups[:-1]) if g["tiers"]), None)
    if prev is None:
        return []
    missing = [t for t in prev["tiers"] if t not in last["tiers"]]
    if not missing:
        return []
    return [{
        "kind": "bench_round", "workload": "tiers", "halo_impl": None,
        "metric": "fallback_tiers", "class": "exact",
        "verdict": "RED", "entry_id": last["head_id"],
        "baseline_ids": [prev["head_id"]],
        "latest": last["tiers"], "baseline": {"tiers": prev["tiers"]},
        "reason": f"round dropped fallback tier(s) {missing} that the "
                  f"previous round ({prev['source']}) landed",
    }]


def check_ledger(
    directory: Optional[str] = None, entries: Optional[list] = None,
    *, window: int = 20,
) -> dict:
    """The sentinel: one structured ``regress_report`` over a ledger dir
    (or a pre-read entry list), RED iff any gated metric regressed."""
    skips: list = []
    if entries is None:
        entries, skips = read_ledger(directory)
    verdicts = [v for v in (
        _verdict(key, pts, window) for key, pts in _series(entries).items()
    ) if v is not None]
    verdicts += dropped_tier_verdicts(entries)
    order = {"RED": 0, "NO_BASELINE": 1, "GREEN": 2}
    verdicts.sort(key=lambda v: (order[v["verdict"]], str(v["metric"])))
    counts = {"RED": 0, "GREEN": 0, "NO_BASELINE": 0}
    for v in verdicts:
        counts[v["verdict"]] += 1
    return {
        "kind": "regress_report",
        "ok": counts["RED"] == 0,
        "dir": directory,
        "entries": len(entries),
        "counts": counts,
        "window": window,
        "verdicts": verdicts,
        "read_skips": skips,
    }


# ---------------------------------------------------------------------------
# selftest — seeded-drift vacuity mutants
# ---------------------------------------------------------------------------


def _fx_round(i: int, *, traced_bytes: int = 4096, exchange_ms: float = 20.0,
              include_hlo: bool = True) -> dict:
    """One synthetic bench round with the tiers the mutants perturb.
    ``i`` varies the timestamp (entry ids must differ per round) and adds
    deterministic sub-tolerance jitter to the timing series."""
    jitter = [0.0, 0.4, -0.2, 0.1, 0.3, -0.1, 0.2][i % 7]
    wl = {"world_size": 2, "nodes": 96, "edges": 400, "feat_dim": 8,
          "seed": 0}
    rec = {
        "metric": "arxiv_gcn_epoch_time", "value": 450.0 + jitter,
        "unit": "ms", "vs_baseline": (450.0 + jitter) / 456.898,
        "git_rev": f"rev{i:04d}",
        "run_health": {"child": {
            "started_at": f"2026-08-01T00:{i:02d}:00Z", "wedge": "none"}},
        "schedule_drift": {
            "kind": "schedule_drift", "workload": wl,
            "train_step_by_impl": {
                "all_to_all": {"collective_count": 3,
                               "traced_bytes": traced_bytes,
                               "footprint_bytes": traced_bytes},
                "overlap": {"collective_count": 4,
                            "traced_bytes": traced_bytes + 512,
                            "footprint_bytes": traced_bytes + 512},
            },
        },
        "cpu_scan_delta": {
            "kind": "cpu_scan_delta", "workload": wl,
            "by_impl": {"all_to_all": {
                "full_ms": 100.0 + jitter,
                "exchange_only_ms": exchange_ms + jitter,
                "exposed_exchange_ms": 10.0 + jitter,
                "phases_ms": {"interior": 60.0 + jitter,
                              "exchange": exchange_ms + jitter,
                              "optimizer": 15.0, "other": 5.0},
            }},
        },
    }
    if include_hlo:
        rec["hlo_drift"] = {
            "kind": "hlo_drift", "workload": wl,
            "train_step_by_impl": {
                "all_to_all": {"collective_count": 3, "lowered_bytes": 8192,
                               "footprint_bytes": 8192},
            },
        }
    return rec


def _fx_serve(i: int, *, p99: float = 50.0) -> dict:
    jitter = [0.0, 1.0, -0.5, 0.5, 0.8, -0.3, 0.2][i % 7]
    return {
        "kind": "serve_health", "schema_version": 1,
        "started_at": f"2026-08-01T01:{i:02d}:00Z",
        "tuning_record": "tune-fixture-v1",
        "recompiles_since_warmup": 0, "warmup_s": 2.0,
        "latency_ms": {"count": 100, "p50": 10.0 + jitter,
                       "p95": 30.0 + jitter, "p99": p99 + jitter},
        "stages_ms": {"infer": {"count": 100, "p99": 8.0 + jitter}},
    }


def _fx_wire(i: int, *, operand_bytes: int = 1024) -> dict:
    """One resolved-wire-format record (dgraph_tpu.wire -> obs.ledger
    ``wire_compile``). ``operand_bytes`` carries the exact-class suffix,
    so the mutant's +64 bytes must go RED with zero tolerance."""
    return {
        "kind": "wire_compile",
        "workload": {"world_size": 2, "nodes": 96, "edges": 400,
                     "feat_dim": 8, "seed": 0},
        "wire_format": "bf16", "wire_format_source": "tune",
        "operand_bytes": operand_bytes, "compression_ratio": 2.0,
        "git_rev": f"rev{i:04d}",
        "recorded_at": f"2026-08-01T03:{i:02d}:00Z",
    }


def _fx_grow(i: int, *, new_world: int = 3) -> dict:
    """One adopted grow transition (train.grow -> obs.ledger
    ``grow_transition``). The world/shard counts carry the exact-class
    ``_count`` suffixes, so the mutant's drifted world size must go RED
    with zero tolerance; ``replan_ms`` rides the timing gate."""
    jitter = [0.0, 0.4, -0.2, 0.1, 0.3, -0.1, 0.2][i % 7]
    return {
        "kind": "grow_transition",
        "generation": 1, "old_world": 2, "new_world": new_world,
        "resume_step": 3, "joined": ["newcomer-a"],
        "replan_s": (120.0 + jitter) / 1000.0, "shards": new_world,
        "git_rev": f"rev{i:04d}",
        "recorded_at": f"2026-08-01T04:{i:02d}:00Z",
    }


def _seed(tmp: str, n: int = 6) -> None:
    for i in range(n):
        ingest(_fx_round(i), f"fixture_r{i:02d}", tmp)
        ingest(_fx_serve(i), f"fixture_serve_r{i:02d}", tmp)
        ingest(_fx_wire(i), f"fixture_wire_r{i:02d}", tmp)
        ingest(_fx_grow(i), f"fixture_grow_r{i:02d}", tmp)


def _selftest() -> dict:
    """Clean trajectory GREEN + the seeded-drift mutants each RED."""
    import tempfile

    failures: list = []

    def check(cond, msg):
        if not cond:
            failures.append(msg)

    def reds(report):
        return [v for v in report["verdicts"] if v["verdict"] == "RED"]

    # clean trajectory: zero RED, real GREENs, and every RED-capable
    # metric actually baselined (a gate with no baselines gates nothing)
    with tempfile.TemporaryDirectory(prefix="dgraph_regress_clean_") as tmp:
        _seed(tmp)
        report = check_ledger(tmp)
        check(report["ok"] and not reds(report),
              f"clean trajectory went RED: "
              f"{[v['reason'] for v in reds(report)]}")
        check(report["counts"]["GREEN"] >= 8,
              f"clean trajectory produced too few GREEN verdicts "
              f"({report['counts']}) — the gate is vacuous")

    mutants = {
        # 1. inflated wire bytes: +64 traced bytes is invisible to any
        # percentage tolerance — the exact class must catch it
        "inflated_wire_bytes": (
            lambda tmp: ingest(_fx_round(6, traced_bytes=4096 + 64),
                               "fixture_r06", tmp),
            "traced_bytes",
        ),
        # 2. slowed scan-delta: exchange phase 20 -> 36 ms, well past
        # median + max(MAD-scaled, 25%) tolerance
        "slowed_scan_delta": (
            lambda tmp: ingest(_fx_round(6, exchange_ms=36.0),
                               "fixture_r06", tmp),
            "exchange",
        ),
        # 3. fattened serve p99: 50 -> 120 ms
        "fattened_p99": (
            lambda tmp: ingest(_fx_serve(6, p99=120.0),
                               "fixture_serve_r06", tmp),
            "p99_ms",
        ),
        # 4. dropped tier: the new round silently loses hlo_drift
        "dropped_tier": (
            lambda tmp: ingest(_fx_round(6, include_hlo=False),
                               "fixture_r06", tmp),
            "fallback_tiers",
        ),
        # 5. drifted wire bytes: +64 priced operand bytes for the same
        # workload at the same format — a codec/pricing change altering
        # what ships on the wire must hit the byte-exact class too
        "drifted_wire_bytes": (
            lambda tmp: ingest(_fx_wire(6, operand_bytes=1024 + 64),
                               "fixture_wire_r06", tmp),
            "operand_bytes",
        ),
        # 6. drifted grown world: a re-recorded generation-1 transition
        # whose adopted world size changed 3 -> 4 — a grow path that
        # reshards to the wrong world must hit the byte-exact class
        "drifted_world": (
            lambda tmp: ingest(_fx_grow(6, new_world=4),
                               "fixture_grow_r06", tmp),
            "world_count",
        ),
    }
    for name, (mutate, expect_metric) in mutants.items():
        with tempfile.TemporaryDirectory(
            prefix=f"dgraph_regress_{name}_"
        ) as tmp:
            _seed(tmp)
            mutate(tmp)
            report = check_ledger(tmp)
            hits = [v for v in reds(report)
                    if expect_metric in str(v["metric"])]
            check(not report["ok"] and hits,
                  f"seeded-drift mutant {name!r} stayed GREEN "
                  f"(vacuous gate): reds="
                  f"{[v['metric'] for v in reds(report)]}")
            check(all(v.get("entry_id") for v in hits),
                  f"mutant {name!r} RED verdict carries no offending "
                  f"entry id")

    return {"kind": "regress_selftest", "failures": failures,
            "ok": not failures}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Config:
    """Drift sentinel CLI: gate the active ledger (exit 1 on RED), or
    ``--selftest true`` for the seeded-drift vacuity mutants."""

    dir: str = ""        # ledger dir ("" = DGRAPH_LEDGER_DIR or default)
    window: int = 20     # trailing baseline window per metric
    log_path: str = "logs/regress.jsonl"
    selftest: bool = False
    indent: int = 0


def _write_log(path: str, health: dict, report: dict) -> None:
    """RunHealth + report JSONL on every exit path — the stdlib
    stand-in for ExperimentLog (same line format; see module header)."""
    try:
        atomic_append_jsonl(path, [{"kind": "run_health", **health}, report])
    except OSError:
        pass  # a read-only checkout must not turn the verdict into a crash


def main(cfg: Config) -> dict:
    h = RunHealth.begin("obs.regress")
    rc = 0
    try:
        if cfg.selftest:
            out = _selftest()
            rc = 1 if out["failures"] else 0
            error = (f"selftest failures: {out['failures']}"
                     if out["failures"] else None)
        else:
            directory = (cfg.dir or resolve_ledger_dir(default_on=True)
                         or DEFAULT_LEDGER_DIR)
            out = check_ledger(directory, window=cfg.window)
            rc = 0 if out["ok"] else 1
            error = None if out["ok"] else (
                f"{out['counts']['RED']} RED verdict(s)")
    except Exception as e:  # every exit path stays structured
        out = {"kind": "regress_report", "ok": False,
               "error": f"{type(e).__name__}: {e}"}
        rc, error = 2, f"sentinel crashed: {type(e).__name__}: {e}"
    out["run_health"] = h.finish(error)
    _write_log(cfg.log_path, out["run_health"], out)
    print(json.dumps(out, indent=cfg.indent or None, default=str))
    if rc:
        raise SystemExit(rc)
    return out


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
