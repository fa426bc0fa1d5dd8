"""Replay the driver's check of one cell: the command of ``BENCHMARK.json``
as new processes from the root of a checkout, never touching JAX here (a
parent that has touched it holds the chip).

    python3 benchmark/tools/replay.py --workload <cell> --seeds 1,2,3,4,5,6 \\
        [--sets 2] [--traced-seed 7] [--short-seeds 8,9] [--control-seeds 10] \\
        [--frozen-seeds 11] [--out chiprun_out/replay]

Runs ``--sets`` sets over the same seeds back to back at ``run_seconds`` (so
that the second set loads what the first compiled), one traced run, short
runs (``--short-seconds``) on further seeds, and the tests-only controls
(``--control 1``, ``--break-step frozen``), which have to come out as not
correct. Prints, per end-to-end metric and set, the median and the spread as
the contract defines it (the distance between the quartiles of
``statistics.quantiles(values, n=4)`` over the median), the spread of all
runs, the second set's median against the first's, and each metric's bound
beside five times the widest spread. Every run's stdout and stderr are kept
under ``--out``. Exit code 1 if a sound run was not correct or failed, or a
control was correct.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values) -> float:
    if len(values) < 2 or statistics.median(values) == 0:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def one_run(command, out_dir, tag, workload, seed, seconds, trace, extra=()):
    cmd = command + ["--workload", workload, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", str(trace), *extra]
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    t0 = time.time()
    with open(os.path.join(out_dir, tag + ".log"), "w") as so, \
            open(os.path.join(out_dir, tag + ".err"), "w") as se:
        rc = subprocess.run(cmd, cwd=ROOT, env=env, stdout=so, stderr=se).returncode
    took = time.time() - t0
    result = None
    with open(os.path.join(out_dir, tag + ".log")) as f:
        lines = f.read().strip().splitlines()
    if rc == 0 and lines and lines[-1].startswith("{"):
        result = json.loads(lines[-1])
    with open(os.path.join(out_dir, tag + ".err")) as f:
        err_tail = [l for l in f.read().strip().splitlines()
                    if l.startswith("benchmark: ")][-12:]
    flagged = sum("FAILED" in l for l in lines)
    shown = {k: v["value"] for k, v in (result or {}).get("metrics", {}).items()}
    print(f"[replay] {tag} rc={rc} took={took:.0f}s correct="
          f"{None if result is None else result['correct']} FAILED_lines={flagged} "
          + " ".join(f"{k}={v:.6g}" for k, v in shown.items()), flush=True)
    for l in err_tail:
        print(f"[replay]   stderr: {l}", flush=True)
    return rc, result, flagged


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced-seed", default="")
    ap.add_argument("--short-seeds", default="")
    ap.add_argument("--short-seconds", type=float, default=5)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--frozen-seeds", default="")
    ap.add_argument("--out", default=os.path.join("chiprun_out", "replay"))
    ap.add_argument("--seconds", type=float, default=0,
                    help="window of the sets; default: run_seconds")
    ap.add_argument("--rehearse-cpu", type=int, default=0,
                    help="benchmark/tests only: pass --rehearse-cpu 1 on")
    args = ap.parse_args()
    ints = lambda s: [int(v) for v in s.split(",") if v]

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    if args.rehearse_cpu:
        bench["command"] = bench["command"] + ["--rehearse-cpu", "1"]
    out_dir = os.path.join(ROOT, args.out, args.workload)
    os.makedirs(out_dir, exist_ok=True)
    bad = 0
    sets = []
    for k in range(1, args.sets + 1):
        rows = []
        for seed in ints(args.seeds):
            rc, result, flagged = one_run(
                bench["command"], out_dir, f"set{k}_{seed}", args.workload,
                seed, seconds, 0)
            ok = rc == 0 and result is not None and result["correct"] \
                and not flagged and result["failed"] == 0
            bad += not ok
            if result is not None:
                rows.append({n: v["value"] for n, v in result["metrics"].items()})
        sets.append(rows)
    extras = [(f"traced_{s}", s, min(seconds, 30), 1, ()) for s in ints(args.traced_seed)]
    extras += [(f"short_{s}", s, args.short_seconds, 0, ()) for s in ints(args.short_seeds)]
    for tag, seed, secs, trace, extra in extras:
        rc, result, flagged = one_run(bench["command"], out_dir, tag,
                                      args.workload, seed, secs, trace, extra)
        bad += not (rc == 0 and result is not None and result["correct"]
                    and not flagged)
    controls = [(f"control_{s}", s, ("--control", "1")) for s in ints(args.control_seeds)]
    controls += [(f"frozen_{s}", s, ("--break-step", "frozen"))
                 for s in ints(args.frozen_seeds)]
    for tag, seed, extra in controls:
        rc, result, _ = one_run(bench["command"], out_dir, tag, args.workload,
                                seed, args.short_seconds, 0, extra)
        if not (rc == 0 and result is not None and result["correct"] is False):
            print(f"[replay] {tag}: a control that did not fail", flush=True)
            bad += 1

    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    for name in (sets[0][0] if sets and sets[0] else {}):
        per_set = [[r[name] for r in rows if name in r] for rows in sets]
        every = [v for vs in per_set for v in vs]
        med = [statistics.median(vs) for vs in per_set if vs]
        line = f"[replay] {args.workload} {name}: " + " | ".join(
            f"set{k + 1} median={statistics.median(vs):.6g} spread={spread(vs):.4%} "
            f"min={min(vs):.6g} max={max(vs):.6g}" for k, vs in enumerate(per_set) if vs)
        widest = max([spread(vs) for vs in per_set if len(vs) > 1] or [float("nan")])
        line += f" | all spread={spread(every):.4%} widest={widest:.4%} 5x={5 * widest:.4%}"
        if len(med) > 1:
            line += f" | set2/set1-1={med[1] / (med[0] or float('nan')) - 1:+.4%}"
        line += f" | bound={bounds.get(name.removeprefix('cpu_rehearsal.'))}"
        print(line, flush=True)
    print(f"[replay] {args.workload}: {'ALL OK' if not bad else f'{bad} BAD RUN(S)'}",
          flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
