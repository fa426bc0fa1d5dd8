"""Longitudinal perf-trajectory ledger: one append-only JSONL store for
every record the system emits.

The consumption side of observability. PRs built the emitters (bench
round JSONs, ``tune_<sig>.json`` TuningRecords, ``serve_health``,
``supervise_lineage``, the four wedged-round analysis tiers) — but each
artifact was write-only, and the trajectory visible to a reviewer was
empty. This module ingests them all and *normalizes* them into one
versioned schema (:data:`LEDGER_SCHEMA_VERSION`) keyed by (workload
signature, record kind, halo lowering, git rev, wall time), appended to
``ledger.jsonl`` under the plan-cache dir so the artifacts that must
travel together keep living together.

Contracts:

- **jax-free + stdlib-only** (``analysis.lint``'s ``jax-free-module``
  rule): the ledger must be writable from bench's wedge-surviving
  supervisor, which loads this file standalone by path (as
  ``_dgraph_obs_ledger``) and must never trigger the package
  ``__init__``'s jax import. Nothing here may import another dgraph_tpu
  module.
- **Durable appends**: every write flows through
  :func:`atomic_append_jsonl` (append + flush + fsync — the append-side
  sibling of ``plan_shards.atomic_write_json``'s fsync+rename), which
  the host durability auditor (``analysis.host``) recognizes as a
  blessed writer; a bare ``open(ledger_path(...), 'a')`` anywhere in
  scope goes RED.
- **Never a crash**: unrecognized or corrupt payloads become a
  structured skip-with-reason, and wedge-era probe stubs (BENCH_r05's
  ``parsed: null`` shape) ingest as ``kind="probe_wedge"`` — the wedge
  history is part of the trajectory, not noise to drop.

Ingestion at the emission sites is gated by ``DGRAPH_LEDGER_DIR``
(:func:`resolve_ledger_dir`): unset means "on with the default dir" for
bench and "off" everywhere else; a falsy value (``0``/``off``/``none``)
disables it everywhere; a path enables it everywhere.

CLI::

    python -m dgraph_tpu.obs.ledger --backfill /root/repo   # seed from
                                                # BENCH_*/MULTICHIP_*/BASELINE
    python -m dgraph_tpu.obs.ledger --dir cache/plans       # summary
    python -m dgraph_tpu.obs.ledger --selftest true
"""

from __future__ import annotations

import dataclasses
import glob
import hashlib
import json
import os
import re
import time
from typing import Optional

# Bump when an ENTRY field changes meaning or is removed; additive fields
# do not bump (readers ignore unknown keys). The version every entry
# carries in its "schema" field.
LEDGER_SCHEMA_VERSION = 1

# The serve_health writer (dgraph_tpu/serve/health.py) stamps THIS
# constant into its records and the normalizer below validates against
# it — one constant, imported by both sides, pinned by test, so the two
# schemas cannot drift apart silently.
SERVE_HEALTH_SCHEMA_VERSION = 1

ENV_LEDGER_DIR = "DGRAPH_LEDGER_DIR"
_DISABLE_VALUES = ("", "0", "off", "none", "disabled", "false")

LEDGER_FILENAME = "ledger.jsonl"
# the plan-cache dir (tune.record.default_record_dir's default) — the
# literal is duplicated here because this module may not import
# tune.record; tests/test_ledger.py pins the two equal
DEFAULT_LEDGER_DIR = os.path.join("cache", "plans")

# every kind a normalized entry may carry (documented surface; new kinds
# are additive)
ENTRY_KINDS = (
    "bench_round",       # bench.py round JSON (value/vs_baseline/roofline)
    "probe_wedge",       # wedge-era stub: a round that never reached a chip
    "multichip_dryrun",  # MULTICHIP_r*.json per-family dryrun table
    "schedule_drift",    # fallback tier 1: traced-vs-footprint bytes
    "cpu_scan_delta",    # fallback tier 2: per-phase CPU step timing
    "hlo_drift",         # fallback tier 3: lowered-vs-footprint bytes
    "spmd_drift",        # fallback tier 4: cross-rank schedule identity
    "tune_record",       # tune_<sig>.json TuningRecord
    "wire_compile",      # resolved wire format: name, priced operand bytes
    "serve_health",      # serving latency/recompile/tenant record
    "supervise_lineage",        # single-child restart lineage
    "supervise_group_lineage",  # multi-rank group lineage
    "grow_transition",   # adopted W -> W+k elastic expansion (train.grow)
    "run_health",        # standalone CLI startup/exit health record
    "reference_note",    # BASELINE.json-style reference metadata
)

# the four wedged-round analysis tiers, in bench's attach order — the
# sentinel's dropped-tier check compares rounds against this set
TIER_KINDS = ("schedule_drift", "cpu_scan_delta", "hlo_drift", "spmd_drift")

# MULTICHIP_r*.json tails carry per-family dryrun lines; step_ms appears
# when the dryrun timed (same pattern obs.attribution parses)
_DRYRUN_RE = re.compile(r"dryrun (\S+) OK:(.*)")
_STEP_MS_RE = re.compile(r"step_ms=([0-9.]+)")


# ---------------------------------------------------------------------------
# knob + paths + durable append
# ---------------------------------------------------------------------------


def resolve_ledger_dir(default_on: bool = False) -> Optional[str]:
    """The active ledger directory, or None when ingestion is off.

    ``DGRAPH_LEDGER_DIR`` set to a path wins; set to a falsy value
    (``0``/``off``/``none``/...) disables ingestion everywhere; unset
    falls back to :data:`DEFAULT_LEDGER_DIR` when the call site opted in
    with ``default_on=True`` (bench does; tune/serve/supervise don't).
    """
    raw = os.environ.get(ENV_LEDGER_DIR)
    if raw is None:
        return DEFAULT_LEDGER_DIR if default_on else None
    if raw.strip().lower() in _DISABLE_VALUES:
        return None
    return raw


def ledger_path(directory: str) -> str:
    """The one ledger file under a plan-cache dir."""
    return os.path.join(directory, LEDGER_FILENAME)


def atomic_append_jsonl(path: str, records: list) -> int:
    """Append ``records`` as JSONL with the durable-append discipline:
    one write, flushed and fsync'd before return, so a host crash can
    lose at most the trailing partial line (which readers skip with a
    reason) — never an earlier, already-acknowledged entry. The
    append-side sibling of ``plan_shards.atomic_write_json``; listed in
    ``analysis.host.ATOMIC_WRITERS`` as a blessed durable writer."""
    if not records:
        return 0
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    payload = "".join(
        json.dumps(r, sort_keys=True, default=str) + "\n" for r in records
    )
    # self-healing append: a prior crash can leave a torn line with no
    # trailing newline — gluing onto it would corrupt THIS write too, so
    # terminate the fragment first (readers already skip it with a reason)
    try:
        with open(path, "rb") as fh:
            fh.seek(-1, os.SEEK_END)
            if fh.read(1) not in (b"\n", b""):
                payload = "\n" + payload
    except OSError:
        pass  # no file yet (or empty): nothing to heal
    with open(path, "a") as fh:
        fh.write(payload)
        fh.flush()
        os.fsync(fh.fileno())
    return len(records)


# ---------------------------------------------------------------------------
# normalized entries
# ---------------------------------------------------------------------------


def _skip(source: str, reason: str) -> dict:
    return {"source": source, "reason": reason}


def _num(v) -> Optional[float]:
    """A JSON-able finite number or None (NaN would poison baselines)."""
    if isinstance(v, bool):
        return int(v)
    if isinstance(v, (int, float)) and v == v:
        return v
    return None


def _entry(
    kind: str,
    metrics: dict,
    *,
    workload: str = "default",
    halo_impl: Optional[str] = None,
    git_rev: Optional[str] = None,
    recorded_at: Optional[str] = None,
    source: str = "",
    round_n: Optional[int] = None,
    meta: Optional[dict] = None,
) -> dict:
    """One normalized ledger entry. ``entry_id`` hashes the key fields +
    metrics so re-ingesting the same artifact (backfill is re-runnable)
    dedups instead of duplicating the trajectory."""
    clean = {k: _num(v) for k, v in metrics.items()}
    clean = {k: v for k, v in clean.items() if v is not None}
    e = {
        "schema": LEDGER_SCHEMA_VERSION,
        "kind": kind,
        "workload": workload or "default",
        "halo_impl": halo_impl,
        "git_rev": git_rev or "unknown",
        "recorded_at": recorded_at
        or time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "round": round_n,
        "source": source,
        "metrics": clean,
        "meta": meta or {},
    }
    key = json.dumps(
        [kind, e["workload"], halo_impl, e["git_rev"], recorded_at or "",
         source, round_n, clean],
        sort_keys=True,
    )
    e["entry_id"] = hashlib.sha1(key.encode()).hexdigest()[:12]
    return e


def _workload_tag(w) -> str:
    """Canonical workload string for the analysis tiers' workload dicts."""
    if not isinstance(w, dict):
        return str(w) if w else "default"
    parts = []
    for k in ("world_size", "nodes", "edges", "feat_dim", "hidden", "seed"):
        if k in w:
            parts.append(f"{k[0] if k != 'world_size' else 'ws'}{w[k]}")
    return "_".join(parts) or "default"


# ---------------------------------------------------------------------------
# per-kind normalizers — each returns (entries, skips)
# ---------------------------------------------------------------------------


def _norm_tier(obj: dict, source: str, round_n, git_rev) -> tuple:
    """schedule_drift / hlo_drift / spmd_drift: one entry per halo
    lowering from the ``train_step_by_impl`` table (the per-lowering
    bytes/identity numbers the sentinel's exact class gates)."""
    kind = obj["kind"]
    if obj.get("error") and "train_step_by_impl" not in obj:
        # bench attaches {"kind": ..., "error": "..."} when a tier's
        # subprocess failed — record the miss, don't fake numbers
        return [_entry(
            kind, {}, workload="default", source=source, round_n=round_n,
            git_rev=git_rev, meta={"error": str(obj["error"])[:300]},
        )], []
    wl = _workload_tag(obj.get("workload"))
    entries = []
    for impl, row in (obj.get("train_step_by_impl") or {}).items():
        if not isinstance(row, dict):
            continue
        metrics = {k: v for k, v in row.items()
                   if isinstance(v, (int, float, bool))}
        meta = {k: v for k, v in row.items() if k not in metrics}
        if "drift" in obj:
            metrics["drift"] = bool(obj["drift"])
        entries.append(_entry(
            kind, metrics, workload=wl, halo_impl=impl, source=source,
            round_n=round_n, git_rev=git_rev, meta=meta,
        ))
    if not entries:
        return [], [_skip(source, f"{kind} record carries no per-impl table")]
    return entries, []


def _norm_scan_delta(obj: dict, source: str, round_n, git_rev) -> tuple:
    """cpu_scan_delta (obs.attribution): per-impl phase timings, plus the
    folded multichip dryrun step_ms table when present."""
    wl = _workload_tag(obj.get("workload"))
    entries = []
    for impl, row in (obj.get("by_impl") or {}).items():
        if not isinstance(row, dict):
            continue
        metrics = {
            "full_ms": row.get("full_ms"),
            "exchange_only_ms": row.get("exchange_only_ms"),
            "exposed_exchange_ms": row.get("exposed_exchange_ms"),
        }
        for phase, v in (row.get("phases_ms") or {}).items():
            metrics[f"{phase}_ms"] = v
        entries.append(_entry(
            "cpu_scan_delta", metrics, workload=wl, halo_impl=impl,
            source=source, round_n=round_n, git_rev=git_rev,
        ))
    mc = obj.get("multichip_dryrun")
    if isinstance(mc, dict):
        fam = mc.get("step_ms_by_family") or {}
        metrics = {f"step_ms/{name}": v for name, v in fam.items()}
        if metrics:
            entries.append(_entry(
                "multichip_dryrun", metrics, workload=wl, source=source,
                round_n=round_n, git_rev=git_rev,
                meta={"folded_from": "cpu_scan_delta"},
            ))
    if not entries and obj.get("error"):
        entries.append(_entry(
            "cpu_scan_delta", {}, workload=wl, source=source,
            round_n=round_n, git_rev=git_rev,
            meta={"error": str(obj["error"])[:300]},
        ))
    if not entries:
        return [], [_skip(source, "cpu_scan_delta record has no by_impl")]
    return entries, []


def _norm_bench_round(obj: dict, source: str, round_n=None) -> tuple:
    """A bench.py round JSON (success OR structured failure): the primary
    metric + roofline context as one ``bench_round`` entry, then every
    attached fallback tier / lineage record as its own entries."""
    entries, skips = [], []
    rh = obj.get("run_health") or {}
    child = (rh.get("bench") or rh.get("child") or rh.get("supervisor")
             or {})
    git_rev = obj.get("git_rev") or child.get("git_rev")
    recorded = child.get("started_at") or obj.get("recorded")
    metrics = {
        "epoch_time_ms": obj.get("value"),
        "vs_baseline": obj.get("vs_baseline"),
        "model_tflops_s": obj.get("model_tflops_s"),
        "mfu_pct": obj.get("mfu_pct"),
        "hbm_gbps_min": obj.get("hbm_gbps_min"),
        "hbm_peak_gb_gcn": obj.get("hbm_peak_gb_gcn"),
        "graphcast_step_ms": obj.get("graphcast_step_ms"),
        "hbm_peak_gb_graphcast": obj.get("hbm_peak_gb_graphcast"),
        "wall_s": obj.get("wall_s"),
    }
    meta = {}
    for k in ("unit", "hardware", "error", "config", "graphcast_config"):
        if obj.get(k) is not None:
            meta[k] = obj[k]
    for role, h in rh.items():
        if isinstance(h, dict) and h.get("wedge") not in (None, "none"):
            meta.setdefault("wedge", {})[role] = h["wedge"]
    entries.append(_entry(
        "bench_round", metrics,
        workload=str(obj.get("metric") or "arxiv_gcn_epoch_time"),
        git_rev=git_rev, recorded_at=recorded, source=source,
        round_n=round_n, meta=meta,
    ))
    for kind in ("schedule_drift", "hlo_drift", "spmd_drift"):
        sub = obj.get(kind)
        if isinstance(sub, dict):
            es, ss = _norm_tier(dict(sub, kind=kind), source, round_n, git_rev)
            entries += es
            skips += ss
    sub = obj.get("cpu_scan_delta")
    if isinstance(sub, dict):
        es, ss = _norm_scan_delta(sub, source, round_n, git_rev)
        entries += es
        skips += ss
    sub = obj.get("supervise_lineage")
    if isinstance(sub, dict):
        es, ss = _norm_lineage(sub, source, round_n=round_n, git_rev=git_rev)
        entries += es
        skips += ss
    return entries, skips


def _norm_driver_wrapper(obj: dict, source: str) -> tuple:
    """The driver's ``BENCH_rNN.json`` wrapper ({n, cmd, rc, tail,
    parsed}): recurse into ``parsed`` when the round produced JSON;
    otherwise the round never reached a chip — ingest the stub as
    ``kind="probe_wedge"`` (the r01–r05 wedge history IS trajectory)."""
    round_n = obj.get("n")
    parsed = obj.get("parsed")
    if isinstance(parsed, dict) and parsed.get("value") is not None:
        return _norm_bench_round(parsed, source, round_n=round_n)
    if isinstance(parsed, dict):
        # the r03–r05 shape: a structured failure JSON whose value is
        # null ("backend never initialized ...; wedged TPU lease") — the
        # round never reached a chip, so it is wedge history, but any
        # attached fallback tiers / lineage are still real signal
        entries, skips = [], []
        wedge = _entry(
            "probe_wedge", {"rc": obj.get("rc")},
            workload=str(parsed.get("metric") or "arxiv_gcn_epoch_time"),
            git_rev=parsed.get("git_rev"), source=source, round_n=round_n,
            meta={"error": str(parsed.get("error") or "")[:300]},
        )
        entries.append(wedge)
        tiers_and_lineage = _norm_bench_round(parsed, source, round_n=round_n)
        # keep everything EXCEPT the empty bench_round shell
        entries += [e for e in tiers_and_lineage[0]
                    if e["kind"] != "bench_round"]
        skips += tiers_and_lineage[1]
        return entries, skips
    tail = (obj.get("tail") or "").strip().splitlines()
    return [_entry(
        "probe_wedge", {"rc": obj.get("rc")},
        workload="arxiv_gcn_epoch_time", source=source, round_n=round_n,
        meta={"last_line": tail[-1][:300] if tail else "",
              "cmd": str(obj.get("cmd", ""))[:200]},
    )], []


def _norm_multichip(obj: dict, source: str) -> tuple:
    """``MULTICHIP_rNN.json``: the per-family dryrun table parsed from the
    tail (step_ms when the dryrun timed; family presence always)."""
    tail = obj.get("tail") or ""
    metrics, families = {}, []
    for line in tail.splitlines():
        m = _DRYRUN_RE.match(line.strip())
        if not m or m.group(1) == "dryrun_multichip":
            continue
        families.append(m.group(1))
        ms = _STEP_MS_RE.search(m.group(2))
        if ms:
            metrics[f"step_ms/{m.group(1)}"] = float(ms.group(1))
    metrics["n_families"] = len(families)
    metrics["rc"] = obj.get("rc")
    return [_entry(
        "multichip_dryrun", metrics, workload="multichip_dryrun",
        source=source, round_n=obj.get("n"),
        meta={"n_devices": obj.get("n_devices"), "ok": obj.get("ok"),
              "skipped": obj.get("skipped"), "families": families},
    )], []


def _norm_tune_record(obj: dict, source: str) -> tuple:
    """A ``tune_<sig>.json`` TuningRecord: the workload key IS the
    signature (via the record_id tune.signature minted)."""
    cost = obj.get("cost") or {}
    cfg = obj.get("config") or {}
    metrics = {k: v for k, v in cost.items() if isinstance(v, (int, float))}
    return [_entry(
        "tune_record", metrics,
        workload=str(obj.get("record_id") or "tune"),
        halo_impl=cfg.get("halo_impl"),
        recorded_at=obj.get("created_at") or None,
        source=source,
        meta={"phase": obj.get("phase"),
              "partition_method": cfg.get("partition_method"),
              "pad_multiple": cfg.get("pad_multiple")},
    )], []


def _norm_serve_health(obj: dict, source: str) -> tuple:
    """A serve_health record: headline latency percentiles, per-stage
    p99s, and the steady-state SLO counters."""
    ver = obj.get("schema_version")
    if ver is not None and ver > SERVE_HEALTH_SCHEMA_VERSION:
        return [], [_skip(
            source,
            f"serve_health schema_version {ver} is newer than supported "
            f"{SERVE_HEALTH_SCHEMA_VERSION}",
        )]
    lat = obj.get("latency_ms") or {}
    metrics = {
        "p50_ms": lat.get("p50"),
        "p95_ms": lat.get("p95"),
        "p99_ms": lat.get("p99"),
        "requests": lat.get("count"),
        "recompiles_since_warmup": obj.get("recompiles_since_warmup"),
        "warmup_s": obj.get("warmup_s"),
        "n_tenants": len(obj.get("tenants") or {}) or None,
        "queue_depth": (obj.get("queue") or {}).get("depth"),
        "wall_s": obj.get("wall_s"),
    }
    for stage, hist in (obj.get("stages_ms") or {}).items():
        if isinstance(hist, dict):
            metrics[f"{stage}_p99_ms"] = hist.get("p99")
    return [_entry(
        "serve_health", metrics,
        workload=str(obj.get("tuning_record") or "serve"),
        git_rev=obj.get("git_rev"),
        recorded_at=obj.get("started_at"), source=source,
        meta={"degraded": obj.get("degraded"),
              "generation": obj.get("generation"),
              "buckets": obj.get("buckets"),
              "schema_version": ver},
    )], []


def _norm_lineage(obj: dict, source: str, round_n=None, git_rev=None) -> tuple:
    """supervise_lineage / supervise_group_lineage: restart counts and
    outcome — the availability half of the trajectory."""
    rh = obj.get("run_health") or {}
    metrics = {
        "restarts": obj.get("restarts"),
        "attempts": len(obj.get("attempts") or []),
        "final_exit_code": obj.get("final_exit_code"),
        "wall_s": rh.get("wall_s"),
        "final_world": obj.get("final_world"),
    }
    return [_entry(
        obj.get("kind", "supervise_lineage"), metrics,
        workload="supervise",
        git_rev=git_rev or rh.get("git_rev"),
        recorded_at=rh.get("started_at"), source=source, round_n=round_n,
        meta={"gave_up": obj.get("gave_up"),
              "budget_exhausted": obj.get("budget_exhausted"),
              "wedge": rh.get("wedge")},
    )], []


def _norm_wire_compile(obj: dict, source: str) -> tuple:
    """wire_compile: one resolved wire format (dgraph_tpu.wire) with its
    priced exchange operand. ``operand_bytes`` rides obs.regress's
    byte-exact zero-tolerance class: a codec or pricing change that
    alters what the same workload ships on the wire goes RED across
    commits. The format name, who resolved it, and the compression ratio
    are provenance (meta), not gated numbers."""
    metrics = {
        "operand_bytes": obj.get("operand_bytes"),
    }
    return [_entry(
        "wire_compile", metrics,
        workload=_workload_tag(obj.get("workload")),
        halo_impl=obj.get("halo_impl"),
        git_rev=obj.get("git_rev"), recorded_at=obj.get("recorded_at"),
        source=source, round_n=obj.get("round"),
        meta={"wire_format": obj.get("wire_format"),
              "wire_format_source": obj.get("wire_format_source"),
              "compression_ratio": obj.get("compression_ratio")},
    )], []


def _norm_grow_transition(obj: dict, source: str) -> tuple:
    """grow_transition: one adopted W -> W+k elastic expansion
    (``train.grow.grow_record``). The world/shard counts carry the
    exact-class ``_count`` suffixes, so a transition that resharded to
    the wrong world size — or wrote a different shard count for the same
    generation — goes RED with zero tolerance, while the re-plan wall
    time rides the noise-aware timing gate. The joined tokens and the
    resume step are provenance (meta), not gated numbers."""
    replan_s = obj.get("replan_s")
    metrics = {
        "old_world_count": obj.get("old_world"),
        "new_world_count": obj.get("new_world"),
        "shards_count": obj.get("shards"),
        "replan_ms": (replan_s * 1000.0
                      if isinstance(replan_s, (int, float))
                      and not isinstance(replan_s, bool) else None),
    }
    return [_entry(
        "grow_transition", metrics,
        workload=f"grow_g{obj.get('generation')}",
        git_rev=obj.get("git_rev"), recorded_at=obj.get("recorded_at"),
        source=source,
        meta={"generation": obj.get("generation"),
              "resume_step": obj.get("resume_step"),
              "joined": obj.get("joined")},
    )], []


def _norm_run_health(obj: dict, source: str) -> tuple:
    metrics = {"wall_s": obj.get("wall_s"),
               "n_probes": len(obj.get("probes") or [])}
    return [_entry(
        "run_health", metrics,
        workload=str(obj.get("component") or "unknown"),
        git_rev=obj.get("git_rev"), recorded_at=obj.get("started_at"),
        source=source,
        meta={"wedge": obj.get("wedge"),
              "error": (obj.get("error") or "")[:300] or None},
    )], []


def _norm_reference(obj: dict, source: str) -> tuple:
    """BASELINE.json-style reference metadata: no numbers, but the
    trajectory's provenance note belongs in the store too."""
    return [_entry(
        "reference_note", {},
        workload=str(obj.get("metric") or "reference"), source=source,
        meta={k: obj[k] for k in
              ("reference_repo", "north_star", "published") if k in obj},
    )], []


# kinds intentionally not stored (high-volume or meta-artifacts), each
# with the reason the skip record carries
_DECLINED_KINDS = {
    "span": "span records are high-volume; query them via obs.spans",
    "step_metrics": "per-step metrics are high-volume; the ledger stores "
                    "round/record-level summaries",
    "lint_report": "analysis reports are regenerated by scripts/check.py",
    "check_report": "analysis reports are regenerated by scripts/check.py",
    "sched_compile": "no halo lowering replays a compiled schedule; the "
                     "record describes nothing a run can take",
}


def normalize_record(obj, source: str = "") -> tuple:
    """Normalize one emitted record/artifact into ledger entries.

    Returns ``(entries, skips)``; never raises on payload shape — an
    unrecognized payload becomes one skip-with-reason so ingestion can
    never crash an emitting run (the BENCH_r05 lesson: a wedge-era
    artifact is still data)."""
    if not isinstance(obj, dict):
        return [], [_skip(source, f"payload is {type(obj).__name__}, "
                                  f"not an object")]
    try:
        kind = obj.get("kind")
        if kind in _DECLINED_KINDS:
            return [], [_skip(source, _DECLINED_KINDS[kind])]
        if kind in ("schedule_drift", "hlo_drift", "spmd_drift"):
            return _norm_tier(obj, source, None, obj.get("git_rev"))
        if kind == "cpu_scan_delta":
            return _norm_scan_delta(obj, source, None, obj.get("git_rev"))
        if kind == "serve_health":
            return _norm_serve_health(obj, source)
        if kind in ("supervise_lineage", "supervise_group_lineage"):
            return _norm_lineage(obj, source)
        if kind == "grow_transition":
            return _norm_grow_transition(obj, source)
        if kind == "run_health":
            return _norm_run_health(obj, source)
        if kind == "wire_compile":
            return _norm_wire_compile(obj, source)
        if kind == "tune_record" or (
            kind is None and "record_id" in obj and "signature" in obj
            and "cost" in obj
        ):
            return _norm_tune_record(obj, source)
        if kind is None and "parsed" in obj and "tail" in obj and "n" in obj:
            return _norm_driver_wrapper(obj, source)
        if kind is None and "n_devices" in obj and "tail" in obj:
            return _norm_multichip(obj, source)
        if kind is None and "reference_repo" in obj:
            return _norm_reference(obj, source)
        if kind is None and "metric" in obj and "value" in obj:
            return _norm_bench_round(obj, source)
        return [], [_skip(
            source, f"unrecognized payload (kind={kind!r}, "
                    f"keys={sorted(obj)[:8]})",
        )]
    except Exception as e:  # normalization must never break the emitter
        return [], [_skip(source, f"normalizer crashed: "
                                  f"{type(e).__name__}: {e}")]


# ---------------------------------------------------------------------------
# store: append / read / ingest
# ---------------------------------------------------------------------------


def read_ledger(directory: str) -> tuple:
    """All entries in a ledger dir + skips for undecodable lines (a torn
    trailing append after a crash is expected, not fatal)."""
    path = ledger_path(directory)
    entries, skips = [], []
    if not os.path.exists(path):
        return entries, skips
    with open(path) as fh:
        for i, line in enumerate(fh, 1):
            if not line.strip():
                continue
            try:
                e = json.loads(line)
            except ValueError:
                skips.append(_skip(f"{path}:{i}",
                                   "undecodable JSONL line (torn append?)"))
                continue
            if not isinstance(e, dict) or "entry_id" not in e:
                skips.append(_skip(f"{path}:{i}",
                                   "line is not a ledger entry"))
                continue
            entries.append(e)
    return entries, skips


def ingest(obj, source: str, directory: str) -> dict:
    """Normalize ``obj`` and durably append the entries not already in
    the ledger (idempotent by ``entry_id`` — backfill is re-runnable)."""
    entries, skips = normalize_record(obj, source)
    existing, read_skips = read_ledger(directory)
    seen = {e.get("entry_id") for e in existing}
    fresh = [e for e in entries if e["entry_id"] not in seen]
    appended = atomic_append_jsonl(ledger_path(directory), fresh)
    return {
        "appended": appended,
        "deduped": len(entries) - len(fresh),
        "skipped": skips + read_skips,
    }


def maybe_ingest(obj, source: str, default_on: bool = False) -> Optional[dict]:
    """The guarded emission-site hook: resolve the knob, ingest, and
    swallow EVERYTHING — a ledger problem (read-only filesystem, torn
    store, bad payload) must never cost the run that was merely trying
    to record itself. Returns the ingest report, or None when the knob
    is off or ingestion failed."""
    try:
        directory = resolve_ledger_dir(default_on=default_on)
        if not directory:
            return None
        return ingest(obj, source, directory)
    except Exception:
        return None


# ---------------------------------------------------------------------------
# backfill — seed the ledger from the historical artifact corpus
# ---------------------------------------------------------------------------

_BACKFILL_GLOBS = (
    "BENCH_BASELINE.json", "BENCH_r*.json", "MULTICHIP_r*.json",
    "BASELINE.json",
)


def backfill(root: str, directory: str) -> dict:
    """Ingest the repo's historical artifact corpus (``BENCH_*.json``,
    ``MULTICHIP_r*.json``, ``BASELINE.json``, whichever exist) as the
    ledger's first entries. Idempotent: re-running dedups by entry_id."""
    report = {"kind": "ledger_backfill", "root": os.path.abspath(root),
              "dir": directory, "files": 0, "appended": 0, "deduped": 0,
              "skipped": []}
    for pat in _BACKFILL_GLOBS:
        for path in sorted(glob.glob(os.path.join(root, pat))):
            report["files"] += 1
            try:
                with open(path) as fh:
                    obj = json.load(fh)
            except (OSError, ValueError) as e:
                report["skipped"].append(_skip(
                    path, f"unreadable artifact: {type(e).__name__}: {e}"))
                continue
            r = ingest(obj, os.path.basename(path), directory)
            report["appended"] += r["appended"]
            report["deduped"] += r["deduped"]
            report["skipped"] += r["skipped"]
    return report


def summarize(directory: str) -> dict:
    """Per-kind entry counts + the read skips — the CLI's default view."""
    entries, skips = read_ledger(directory)
    by_kind: dict = {}
    for e in entries:
        by_kind[e.get("kind", "?")] = by_kind.get(e.get("kind", "?"), 0) + 1
    return {
        "kind": "ledger_summary",
        "dir": directory,
        "path": ledger_path(directory),
        "entries": len(entries),
        "by_kind": dict(sorted(by_kind.items())),
        "skipped": skips,
        "schema": LEDGER_SCHEMA_VERSION,
    }


# ---------------------------------------------------------------------------
# selftest — ingestion fixtures for every kind (the vacuity guards live
# in obs.regress's selftest; this one proves the normalizers + store)
# ---------------------------------------------------------------------------


def _fixture_bench_round(value=400.0, rnd=6, git_rev="abc1234") -> dict:
    return {
        "metric": "arxiv_gcn_epoch_time", "value": value, "unit": "ms",
        "vs_baseline": value / 456.898, "mfu_pct": 1.2,
        "git_rev": git_rev,
        "run_health": {"child": {"started_at": f"2026-08-0{rnd}T00:00:00Z",
                                 "wedge": "none"}},
        "schedule_drift": {
            "kind": "schedule_drift",
            "workload": {"world_size": 8, "nodes": 4096, "edges": 16384,
                         "feat_dim": 32, "seed": 0},
            "train_step_by_impl": {
                "all_to_all": {"collective_count": 3, "traced_bytes": 4096,
                               "footprint_bytes": 4096},
            },
        },
        "cpu_scan_delta": {
            "kind": "cpu_scan_delta",
            "workload": {"world_size": 2, "nodes": 96, "edges": 400,
                         "feat_dim": 8, "seed": 0},
            "by_impl": {"all_to_all": {
                "full_ms": 100.0, "exchange_only_ms": 20.0,
                "exposed_exchange_ms": 10.0,
                "phases_ms": {"interior": 60.0, "exchange": 20.0,
                              "optimizer": 15.0, "other": 5.0},
            }},
        },
    }


def _selftest() -> dict:
    import tempfile

    failures = []

    def check(cond, msg):
        if not cond:
            failures.append(msg)

    with tempfile.TemporaryDirectory(prefix="dgraph_ledger_selftest_") as tmp:
        # every normalizer lands the right kind
        r = ingest(_fixture_bench_round(), "BENCH_r06.json", tmp)
        check(r["appended"] >= 3 and not r["skipped"],
              f"bench fixture ingest: {r}")
        entries, _ = read_ledger(tmp)
        kinds = {e["kind"] for e in entries}
        for want in ("bench_round", "schedule_drift", "cpu_scan_delta"):
            check(want in kinds, f"missing kind {want!r} after bench ingest")
        check(all(e["git_rev"] == "abc1234" for e in entries
                  if e["kind"] == "bench_round"),
              "git_rev did not propagate into the bench_round entry")

        # probe stub -> probe_wedge, never a crash
        stub = {"n": 5, "cmd": "python bench.py", "rc": 3,
                "tail": "probe attempt 7 hung (wedged lease)",
                "parsed": None}
        r = ingest(stub, "BENCH_r05.json", tmp)
        check(r["appended"] == 1, f"probe stub ingest: {r}")
        entries, _ = read_ledger(tmp)
        check(any(e["kind"] == "probe_wedge" and e["round"] == 5
                  for e in entries), "probe stub did not land as probe_wedge")

        # grow transition -> exact-class world/shard counts + timing
        grow = {"kind": "grow_transition", "generation": 1, "old_world": 2,
                "new_world": 3, "resume_step": 3, "joined": ["newcomer-a"],
                "replan_s": 0.125, "shards": 3, "git_rev": "abc1234",
                "recorded_at": "2026-08-06T00:00:00Z"}
        r = ingest(grow, "grow_g1.json", tmp)
        check(r["appended"] == 1 and not r["skipped"],
              f"grow_transition ingest: {r}")
        entries, _ = read_ledger(tmp)
        ge = next((e for e in entries if e["kind"] == "grow_transition"),
                  None)
        check(ge is not None
              and ge["metrics"].get("new_world_count") == 3
              and ge["metrics"].get("old_world_count") == 2
              and ge["metrics"].get("shards_count") == 3
              and ge["metrics"].get("replan_ms") == 125.0
              and ge["meta"].get("joined") == ["newcomer-a"],
              f"grow_transition entry malformed: {ge}")

        # idempotence: same artifact again -> all deduped
        r = ingest(_fixture_bench_round(), "BENCH_r06.json", tmp)
        check(r["appended"] == 0 and r["deduped"] >= 3,
              f"re-ingest was not idempotent: {r}")

        # unrecognized payload -> skip-with-reason, rc still fine
        r = ingest({"surprise": True}, "mystery.json", tmp)
        check(r["appended"] == 0 and r["skipped"]
              and "unrecognized" in r["skipped"][0]["reason"],
              f"unrecognized payload not skipped-with-reason: {r}")

        # torn trailing append -> one skip, earlier entries intact (the
        # bare open is the POINT here: simulate the host crash the
        # durable-write rule exists to prevent)
        n_before = len(read_ledger(tmp)[0])
        with open(ledger_path(tmp), "a") as fh:  # lint: allow(host-durable-write)
            fh.write('{"schema": 1, "kind": "bench_ro')
        entries, skips = read_ledger(tmp)
        check(len(entries) == n_before and len(skips) == 1,
              f"torn trailing line not skipped cleanly "
              f"({len(entries)} vs {n_before}, skips={skips})")

    return {"kind": "ledger_selftest", "failures": failures,
            "ok": not failures}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Config:
    """Perf-trajectory ledger CLI: ``--backfill <repo-root>`` seeds the
    store from the historical artifact corpus; the default prints a
    per-kind summary of the active ledger."""

    backfill: str = ""   # repo root to backfill from ("" = no backfill)
    dir: str = ""        # ledger dir ("" = DGRAPH_LEDGER_DIR or default)
    selftest: bool = False
    indent: int = 0


def main(cfg: Config) -> dict:
    if cfg.selftest:
        out = _selftest()
        print(json.dumps(out, indent=cfg.indent or None))
        if out["failures"]:
            raise SystemExit(1)
        return out
    # an explicit CLI invocation always has a directory: --dir wins, then
    # the env knob, then the default (even when the env knob says "off" —
    # "off" gates the emission-site hooks, not the operator's own CLI)
    directory = (cfg.dir or resolve_ledger_dir(default_on=True)
                 or DEFAULT_LEDGER_DIR)
    if cfg.backfill:
        out = backfill(cfg.backfill, directory)
    else:
        out = summarize(directory)
    print(json.dumps(out, indent=cfg.indent or None))
    return out


if __name__ == "__main__":
    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
