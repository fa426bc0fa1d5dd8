"""Readings for the limits of ``correct``: in one process, over several
seeds, the gaps of the program's first steps from the float32 reference
(sound runs) and the gaps of the reference computed in the configuration's
control precision from the same float32 reference (the control).

    python3 benchmark/tools/limits.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 [--rehearse-cpu 1] [--dump readings.jsonl]

Prints one line per seed and, at the end, per number: the largest sound
readings, the smallest control reading, their ratio and, beside the cell's
limit (``limits/<cell>.json``), the room on both sides. ``--dump`` appends
each seed's numbers, with the per-leaf norms they were worked out from, to a
file, so that another statistic can be tried without the chip. No window is
measured: training's readings need none.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearse-cpu", type=int, default=0)
    ap.add_argument("--dump", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}

    import jax

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell_entry, config, traffic = harness.find_cell(bench, args.workload)
    devices = jax.devices()
    if not args.rehearse_cpu and (
            devices[0].platform != "tpu" or len(devices) < cell_entry["chips"]):
        print("limits: needs the cell's TPU chips", file=sys.stderr)
        return 2
    from dgraph_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    watch = harness.CompileWatch()
    precision = config["correct"]["control_precision"]
    sound, control = {}, {}

    def record(into, kind, seed, got, ref):
        g = harness.gaps(got, ref)
        print(f"seed {seed} {kind} " + json.dumps(g), flush=True)
        for k, v in g.items():
            if isinstance(v, float):
                into.setdefault(k, []).append(v)
        if args.dump:
            with open(args.dump, "a") as f:
                f.write(json.dumps({
                    "cell": args.workload, "seed": seed, "kind": kind,
                    "gaps": g, "loss": got["loss"], "ref_loss": ref["loss"],
                    "grad_norm": got["grad_norm"],
                    "ref_grad_norm": ref["grad_norm"],
                    "grad_diff_norm": harness.leaf_diff_norms(got, ref),
                    "delta_norm": got["delta_norm"],
                    "ref_delta_norm": ref["delta_norm"]}) + "\n")

    cell = None
    for seed in seeds:
        if cell is not None and hasattr(cell, "reseed"):
            cell.reseed(seed)  # keeps the compiled step for the next seed
        else:
            cell = harness.build_cell(
                config, traffic, seed, devices[:cell_entry["chips"]],
                bool(args.rehearse_cpu))
        with cell.context():
            got, _, _ = harness.first_steps(cell, watch)
        if hasattr(cell, "reseed"):
            cell.host_params0 = jax.device_get(cell.params0)
        else:
            cell.release()
        ref = cell.reference(harness.CHECK_STEPS, "float32")
        record(sound, "sound  ", seed, got, ref)
        if seed in control_seeds:
            low = cell.reference(harness.CHECK_STEPS, precision)
            record(control, f"control({precision})", seed, low, ref)
    limits = harness.cell_limits(args.workload, bool(args.rehearse_cpu))
    for k in sound:
        top = sorted(sound[k], reverse=True)
        line = (f"{k}: sound max {top[0]:.6g} over {len(top)} seeds (largest "
                + " ".join(f"{v:.3g}" for v in top[:6]) + ")")
        if k in control:
            low = min(control[k])
            line += (f"; control min {low:.6g} over {len(control[k])} seeds; "
                     f"ratio {low / max(top[0], 1e-30):.3g}")
        if k in limits:
            line += f"; limit {limits[k]:g} = {limits[k] / max(top[0], 1e-30):.2f} x sound max"
            if k in control:
                line += f", control min = {min(control[k]) / limits[k]:.2f} x limit"
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
