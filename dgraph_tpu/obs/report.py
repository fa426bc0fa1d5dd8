"""Longitudinal trajectory report over the perf ledger: "what did PR N
do to perf" as one command.

Renders the ledger (:mod:`dgraph_tpu.obs.ledger`) as a markdown
artifact: the bench-round table (real-chip epoch times AND the wedge
history — a round that never reached a chip is part of the trajectory,
not a gap), then one table per record kind with each metric's latest
value, its delta against the previous entry, and a sparkline over the
trailing window. jax-free + stdlib-only by the same lint-enforced
contract as the ledger: the trajectory must be readable on a machine
where jax is wedged or absent.

CLI::

    python -m dgraph_tpu.obs.report                     # active ledger
    python -m dgraph_tpu.obs.report --dir cache/plans --out TRAJECTORY.md
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

from dgraph_tpu.obs.ledger import (
    DEFAULT_LEDGER_DIR,
    LEDGER_SCHEMA_VERSION,
    ledger_path,
    read_ledger,
    resolve_ledger_dir,
)

_SPARK_BLOCKS = "▁▂▃▄▅▆▇█"


def sparkline(values: list, width: int = 16) -> str:
    """Unicode sparkline of a numeric series (trailing ``width`` points).
    A constant series renders mid-block — flat is a shape too."""
    vs = [v for v in values if isinstance(v, (int, float))][-width:]
    if not vs:
        return ""
    lo, hi = min(vs), max(vs)
    if hi == lo:
        return _SPARK_BLOCKS[3] * len(vs)
    scale = (len(_SPARK_BLOCKS) - 1) / (hi - lo)
    return "".join(_SPARK_BLOCKS[int((v - lo) * scale)] for v in vs)


def _fmt(v) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def _delta(prev, latest) -> str:
    if not isinstance(prev, (int, float)) or not isinstance(
        latest, (int, float)
    ):
        return "—"
    d = latest - prev
    if d == 0:
        return "="
    pct = f" ({d / prev:+.1%})" if prev else ""
    return f"{d:+.4g}{pct}"


def _round_rows(entries: list) -> list:
    rows = []
    for e in entries:
        if e.get("kind") not in ("bench_round", "probe_wedge"):
            continue
        m = e.get("metrics") or {}
        meta = e.get("meta") or {}
        note = ""
        if e["kind"] == "probe_wedge":
            note = (meta.get("error") or meta.get("last_line")
                    or "wedged")[:60]
        elif meta.get("wedge"):
            note = json.dumps(meta["wedge"])[:60]
        rows.append({
            "round": e.get("round"),
            "source": e.get("source"),
            "kind": e["kind"],
            "epoch_ms": m.get("epoch_time_ms"),
            "vs_baseline": m.get("vs_baseline"),
            "graphcast_ms": m.get("graphcast_step_ms"),
            "git_rev": e.get("git_rev"),
            "note": note,
        })
    return rows


def render_trajectory(entries: list, *, directory: str = "",
                      width: int = 16) -> str:
    """The full markdown artifact for one ledger's entry list."""
    lines = [
        "# Perf trajectory",
        "",
        f"*Ledger: `{ledger_path(directory) if directory else '(in-memory)'}`"
        f" — {len(entries)} entries, schema {LEDGER_SCHEMA_VERSION}.*",
        "",
    ]
    if not entries:
        lines += ["(empty ledger — run `python -m dgraph_tpu.obs.ledger "
                  "--backfill <repo-root>` to seed it)", ""]
        return "\n".join(lines)

    # --- bench rounds: the headline table -------------------------------
    rows = _round_rows(entries)
    if rows:
        lines += ["## Bench rounds", ""]
        lines += ["| round | source | epoch ms | vs baseline | "
                  "graphcast ms | git rev | note |",
                  "|---|---|---|---|---|---|---|"]
        for r in rows:
            epoch = (f"{r['epoch_ms']:.1f}"
                     if isinstance(r["epoch_ms"], (int, float)) else
                     ("WEDGED" if r["kind"] == "probe_wedge" else "—"))
            lines.append(
                f"| {_fmt(r['round'])} | {r['source']} | {epoch} | "
                f"{_fmt(r['vs_baseline'])} | {_fmt(r['graphcast_ms'])} | "
                f"{r['git_rev']} | {r['note']} |")
        epochs = [r["epoch_ms"] for r in rows
                  if isinstance(r["epoch_ms"], (int, float))]
        if epochs:
            lines += ["",
                      f"epoch ms trend: `{sparkline(epochs, width)}` "
                      f"(latest {epochs[-1]:.1f} ms over {len(epochs)} "
                      f"measured round(s))"]
        lines.append("")

    # --- every other kind: per-(workload, lowering) metric tables -------
    by_kind: dict = {}
    for e in entries:
        if e.get("kind") in ("bench_round", "probe_wedge",
                             "reference_note"):
            continue
        key = (e["kind"], e.get("workload"), e.get("halo_impl"))
        by_kind.setdefault(e["kind"], {}).setdefault(key, []).append(e)
    for kind in sorted(by_kind):
        lines += [f"## {kind}", ""]
        for (_, workload, halo_impl), group in sorted(
            by_kind[kind].items(), key=lambda kv: str(kv[0])
        ):
            label = workload + (f" / {halo_impl}" if halo_impl else "")
            lines += [f"### {label}", "",
                      "| metric | latest | Δ prev | trend |",
                      "|---|---|---|---|"]
            series: dict = {}
            for e in group:
                for metric, v in (e.get("metrics") or {}).items():
                    series.setdefault(metric, []).append(v)
            for metric in sorted(series):
                vs = series[metric]
                prev = vs[-2] if len(vs) > 1 else None
                lines.append(
                    f"| {metric} | {_fmt(vs[-1])} | "
                    f"{_delta(prev, vs[-1])} | "
                    f"`{sparkline(vs, width)}` |")
            lines.append("")

    refs = [e for e in entries if e.get("kind") == "reference_note"]
    if refs:
        lines += ["## Reference", ""]
        for e in refs:
            meta = e.get("meta") or {}
            lines.append(f"- `{e.get('workload')}` "
                         f"(source `{e.get('source')}`): "
                         f"{meta.get('reference_repo', '')}")
        lines.append("")
    return "\n".join(lines)


def _selftest() -> dict:
    """Render the regress fixtures + an empty ledger without crashing,
    and pin the headline pieces the render must carry."""
    import tempfile

    # submodule form, not `from dgraph_tpu.obs import ...`: naming the
    # package would flag the jax-free lint (its __init__ pulls jax)
    from dgraph_tpu.obs.regress import _seed

    failures = []

    def check(cond, msg):
        if not cond:
            failures.append(msg)

    check(render_trajectory([]).strip(), "empty ledger rendered nothing")
    check(sparkline([1.0, 1.0]) == _SPARK_BLOCKS[3] * 2,
          "constant-series sparkline broke")
    with tempfile.TemporaryDirectory(prefix="dgraph_report_selftest_") as tmp:
        _seed(tmp)
        entries, _ = read_ledger(tmp)
        md = render_trajectory(entries, directory=tmp)
        for want in ("## Bench rounds", "## cpu_scan_delta",
                     "## serve_health", "## wire_compile",
                     "## grow_transition",
                     "operand_bytes", "exchange_ms", "p99_ms",
                     "new_world_count", "450."):
            check(want in md, f"rendered trajectory lacks {want!r}")
    return {"kind": "report_selftest", "failures": failures,
            "ok": not failures}


@dataclasses.dataclass
class Config:
    """Trajectory report CLI: render the active ledger as markdown (to
    stdout, or ``--out <path>``)."""

    dir: str = ""    # ledger dir ("" = DGRAPH_LEDGER_DIR or default)
    out: str = ""    # output markdown path ("" = stdout)
    width: int = 16  # sparkline window
    selftest: bool = False
    indent: int = 0


def main(cfg: Config) -> Optional[str]:
    if cfg.selftest:
        out = _selftest()
        print(json.dumps(out, indent=cfg.indent or None))
        if out["failures"]:
            raise SystemExit(1)
        return None
    directory = (cfg.dir or resolve_ledger_dir(default_on=True)
                 or DEFAULT_LEDGER_DIR)
    entries, skips = read_ledger(directory)
    md = render_trajectory(entries, directory=directory, width=cfg.width)
    if skips:
        md += f"\n*({len(skips)} undecodable ledger line(s) skipped.)*\n"
    if cfg.out:
        with open(cfg.out, "w") as fh:  # a regenerable view, not a
            fh.write(md)                # durable artifact
        print(f"wrote {cfg.out} ({len(md)} chars)")
    else:
        print(md)
    return md


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
