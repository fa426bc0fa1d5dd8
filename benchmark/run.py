"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer metric
is a file of its own, found by the name ``BENCHMARK.json`` gives:
``configs/<config>.json`` (sizes, source, its ``builder``),
``builders/<builder>.py``, ``reference/<reference>.py``,
``traffic/<traffic>.json`` (its ``graph_law`` is ``laws/<law>.py``),
``limits/<cell>.json`` (the limits of ``correct``, with the readings each was
placed from), ``layer_metrics/<metric>.json`` (its ``reducer`` and parameters;
a roofline's ``work`` is ``work/<name>.py``) and ``reducers/<reducer>.py``.
This file knows none of them by name.

A run: set-up (inputs and weights from ``--seed``, graph and plan build,
placement, the program's first steps, which compile, and a warm-up of the
cell's own shapes), a measured window of ``--seconds`` with garbage collection
frozen and nothing printed, then — outside ``setup_s`` and the window — the
comparison with the plain reference that decides ``correct``. With
``--trace 1`` the window is a few steps under ``jax.profiler`` and the line
carries the cell's per-layer metrics. The last line of stdout is the result.

Every number compared is printed beside its limit (``[bench] check ...``). A
run whose check fails also says so as its last lines on stderr: one line per
failed check (name, value, limit, cell, seed, traced or not), then every
other number compared. A run that raises prints the exception there too.

Without a TPU, or with fewer chips than the cell asks for, the run exits 2
and prints no result. ``--rehearse-cpu 1`` is the explicit exception, for
``benchmark/tests``: tiny sizes on CPU devices, every metric renamed
``cpu_rehearsal.<name>``.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import math
import os
import shutil
import statistics
import sys
import time

_T_IMPORT = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CHECK_STEPS = 3
MIN_SAMPLE_S = 0.25  # a host-clock reading spans at least this long
MAX_SAMPLES = 1 << 16


def process_start_time() -> float:
    """Epoch seconds at which this process started (Linux), else import time."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration):
        return _T_IMPORT


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r} (known: {sorted(cells)})")
    cell = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, load_json(ROOT, cfg_entry["file"]), \
        load_json(HERE, "traffic", cell["traffic"] + ".json")


def cell_limits(name: str, rehearsal: bool) -> dict:
    """{number: limit} of ``limits/<cell>.json``: the cell's own, placed from
    its own readings. A number the file does not limit is only printed."""
    entries = load_json(HERE, "limits", name + ".json")[
        "tiny_limits" if rehearsal else "limits"]
    return {k: (v["limit"] if isinstance(v, dict) else v)
            for k, v in entries.items()}


def build_cell(config: dict, traffic: dict, seed: int, devices: list,
               rehearsal: bool, traced: bool = False, spans=None,
               say=lambda msg: None):
    """The cell of ``builders/<config's builder>.py``, at the configuration's
    sizes (its ``tiny`` ones in a CPU rehearsal), with its plain reference
    ``reference/<config's reference>.py`` beside it."""
    from benchmark.cells import Context

    ctx = Context(
        traffic=traffic, sizes=config["tiny" if rehearsal else "sizes"],
        reference=importlib.import_module(
            f"benchmark.reference.{config['reference']}"),
        seed=seed, devices=devices, traced=traced,
        spans={} if spans is None else spans, say=say)
    return importlib.import_module(
        f"benchmark.builders.{config['builder']}").build(ctx)


def metrics_of(entries, cell_name: str, e2e_reported=None):
    """The metric entries of ``BENCHMARK.json`` that this cell reports."""
    out = []
    for m in entries:
        cells = m.get("workloads")
        if cells is not None and cell_name not in cells:
            continue
        if cells is None and e2e_reported is not None \
                and m["moves"] not in e2e_reported:
            continue
        out.append(m)
    return out


class CompileWatch:
    """Durations JAX reports while it traces, lowers and compiles (or loads
    from the persistent cache), by event; ``count`` is the backend compiles."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_or_load_s",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
        "/jax/compilation_cache/compile_time_saved_sec": "compile_saved_s",
    }

    def __init__(self):
        import jax.monitoring

        self.sums = {v: 0.0 for v in self.EVENTS.values()}
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        key = self.EVENTS.get(name)
        if key:
            self.sums[key] += secs
            self.count += key == "compile_or_load_s"

    def snapshot(self):
        return dict(self.sums, count=self.count)


def timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def settle(step, least=5, most=20, tol=0.02):
    """Warm a step until its last three times agree within ``tol``; returns
    their median."""
    times = []
    while len(times) < most:
        times.append(timed(step))
        last = times[-3:]
        if len(times) >= least and max(last) - min(last) <= tol * min(last):
            break
    return statistics.median(times[-3:])


def run_phase(step, group: int, seconds: float, buf) -> int:
    """Fill ``buf`` with per-step seconds, each sample ``group`` steps long,
    until ``seconds`` have passed. A sample starts where the last one ended,
    so the samples add up to the phase's elapsed time. Returns their number."""
    n, now = 0, time.perf_counter()
    end = now + seconds
    while now < end and n < len(buf):
        t0 = now
        for _ in range(group):
            step()
        now = time.perf_counter()
        buf[n] = (now - t0) / group
        n += 1
    return n


def phase_line(name: str, ms, group: int) -> str:
    """A phase's step statistics, for an earlier line: ``mean`` is the
    phase's elapsed time over its steps (the samples follow one another with
    no gap), which is what the end-to-end metric reports; the median, the
    tail and the slow samples say how it came about."""
    import numpy as np

    order = np.sort(ms)
    med = float(np.median(ms))
    slow = [f"{i}:{ms[i]:.1f}" for i in np.flatnonzero(ms > 1.2 * med)[:12]]
    return (f"phase {name}: steps={len(ms) * group} samples={len(ms)} "
            f"steps_per_sample={group} elapsed={ms.sum() * group / 1e3:.4f} s "
            f"mean={ms.mean():.4f} ms median={med:.4f} "
            f"p95={order[int(0.95 * (len(ms) - 1))]:.4f} min={order[0]:.4f} "
            f"max={order[-1]:.4f} slow(>1.2x median, index:ms)="
            f"[{' '.join(slow)}]")


def leaf_diff_norms(got: dict, ref: dict) -> dict:
    """{leaf: norm of (program's first gradient - reference's)}."""
    from benchmark.weights import leaf_norms

    return leaf_norms(got["grad"], ref["grad"])


def gaps(got: dict, ref: dict) -> dict:
    """The numbers compared: how far the program's first steps lie from the
    reference's.

    Norms are compared by the worst leaf: the gap between the program's norm
    and the reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Such a gap hardly sees rounding that is
    unbiased, so the first gradient is also compared element by element
    (``grad_diff_gap``: the norm of the difference over all leaves against
    the norm of the reference's gradient): the weights are one tree in one
    layout on both sides. That is the number a lower precision fails. Which
    of these a cell limits is its limits file's to say (``cell_limits``): the
    rest are printed. The evaluation's accuracy is among them everywhere: at
    seeded weights the top two logits of many vertices are all but tied, so a
    rounding flips hundreds of them at once (sound runs read 0 to 0.0093);
    the evaluation step is held by its loss."""
    def rel(a, b):
        return abs(a - b) / max(abs(b), 1e-30)

    def worst_leaf(a: dict, b: dict):
        if set(a) != set(b):
            return float("inf")
        floor = statistics.median(b.values())
        return max(abs(a[k] - b[k]) / max(b[k], floor, 1e-30) for k in b)

    out = {
        "loss_gap": max(rel(a, b) for a, b in zip(got["loss"], ref["loss"])),
        "grad_norm_gap": worst_leaf(got["grad_norm"], ref["grad_norm"]),
        "delta_norm_gap": worst_leaf(got["delta_norm"], ref["delta_norm"]),
    }
    diff = leaf_diff_norms(got, ref)
    out["grad_diff_gap"] = math.sqrt(sum(v * v for v in diff.values())) \
        / max(math.sqrt(sum(v * v for v in ref["grad_norm"].values())), 1e-30)
    floor = statistics.median(ref["grad_norm"].values())
    worst = max(diff, key=lambda k: diff[k] / max(ref["grad_norm"][k], floor, 1e-30))
    out["grad_diff_worst_leaf"] = diff[worst] / max(
        ref["grad_norm"][worst], floor, 1e-30)
    out["worst_leaf"] = worst
    if "eval_loss" in got:
        out["eval_loss_gap"] = rel(got["eval_loss"], ref["eval_loss"])
        out["eval_accuracy_gap"] = abs(
            got["eval_accuracy"] - ref["eval_accuracy"])
    return out


def compare(got: dict, ref: dict, limits: dict) -> list:
    """[(name, value, limit, ok)] — each number compared, beside its limit;
    a number the cell's limits file does not name is only printed, and has
    the limit None."""
    found = gaps(got, ref)
    missing = sorted(set(limits) - set(found))
    if missing:
        raise KeyError(f"limited, but not among the numbers compared: {missing}")
    rows = []
    for name, v in found.items():
        if name not in limits:
            rows.append((name, v, None, True))
        else:
            rows.append((name, v, limits[name],
                         math.isfinite(v) and v <= limits[name]))
    return rows


def report_failure(rows: list, workload: str, seed: int, traced: int) -> None:
    """The last lines a failing run writes to stderr: one per failed check,
    then every other number compared, so that a refusal on ``correct`` names
    its number wherever only the tail of stderr is kept."""
    where = f"cell={workload} seed={seed} traced={traced}"
    lines = [f"benchmark: correct=false {where}"]
    for name, v, limit, ok in rows:
        if not ok:
            over = v / limit if limit else float("inf")
            lines.append(f"benchmark: FAILED check {name}: value={v:.6g} "
                         f"limit={limit:g} value/limit={over:.3g} {where}")
    for name, v, limit, ok in rows:
        if ok:
            shown = f"{v:.6g}" if isinstance(v, (int, float)) else str(v)
            lines.append(f"benchmark: other {name}: value={shown} limit="
                         + ("none" if limit is None else f"{limit:g}"))
    sys.stdout.flush()
    print("\n".join(lines), file=sys.stderr, flush=True)


def first_steps(cell, watch) -> tuple:
    """Drive the cell from its seeded state through its first steps, through
    the window's own calls. Returns (program's numbers, first-call seconds by
    phase, what JAX reported of trace/lower/compile during those calls)."""
    import jax

    from benchmark.weights import leaf_norms

    by_name = {p.name: p for p in cell.phases}
    check = by_name[cell.check_phase]
    got, first = {"loss": []}, {}
    split = {k: 0.0 for k in watch.snapshot()}

    def first_call(phase):
        before = watch.snapshot()
        first[phase.name] = timed(phase.step)
        after = watch.snapshot()
        for k in split:
            split[k] += after[k] - before[k]

    for k in range(CHECK_STEPS):
        if k == 0:
            first_call(check)
            got["grad"] = jax.device_get(cell.first_gradient())
            got["grad_norm"] = leaf_norms(got["grad"])
        else:
            check.step()
        got["loss"].append(cell.loss())
    got["delta_norm"] = cell.delta_norms()
    for p in cell.phases:
        if p.name not in first:
            first_call(p)
    got.update(cell.eval_numbers())
    return got, first, split


def memory_peaks(devices) -> list:
    """Per device, the allocator's peak: buffers in use plus what the runtime
    reserved for the loaded programs' temporaries. ``peak_bytes_in_use`` alone
    leaves the temporaries out (PERF.md, section 6, PR 24)."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return out


def main() -> int:
    args = parse_args()
    try:
        return run(args)
    except Exception as e:  # said on stderr's last lines too
        import traceback

        sys.stdout.flush()
        traceback.print_exc()
        print(f"benchmark: raised {type(e).__name__}: {e} "
              f"cell={args.workload} seed={args.seed} traced={args.trace}",
              file=sys.stderr, flush=True)
        return 1


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", type=int, choices=(0, 1), default=0)
    ap.add_argument("--break-step", default="",
                    help="tests only: name of a fault to put under the timed path")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="tests only: put the reference in the configuration's "
                         "control precision in the program's place")
    return ap.parse_args()


def run(args) -> int:
    t_start = process_start_time()
    sys.path.insert(0, ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell_entry, config, traffic = find_cell(bench, args.workload)
    chips = cell_entry["chips"]

    import jax
    import numpy as np

    devices = jax.devices()
    rehearsal = bool(args.rehearse_cpu)
    if not rehearsal and (devices[0].platform != "tpu" or len(devices) < chips):
        print(f"benchmark: {args.workload} needs {chips} TPU chip(s); JAX "
              f"reports {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 2
    if rehearsal and len(devices) < chips:
        print("benchmark: rehearsal needs XLA_FLAGS=--xla_force_host_platform_"
              f"device_count>={chips}", file=sys.stderr)
        return 2
    from dgraph_tpu.utils.compile_cache import enable_compile_cache

    say(f"{args.workload} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} platform={devices[0].platform} "
        f"kind={devices[0].device_kind!r} devices={len(devices)}")
    if rehearsal:
        say("CPU REHEARSAL: tiny sizes on a non-TPU backend; no number below "
            "is a device metric")
    say(f"compile cache: {enable_compile_cache()}")
    watch = CompileWatch()

    spans = {}
    t0 = time.perf_counter()
    cell = build_cell(config, traffic, args.seed, devices[:chips], rehearsal,
                      bool(args.trace), spans, say)
    spans["build_s"] = time.perf_counter() - t0
    if args.break_step:
        cell.break_step(args.break_step)

    # --- first steps (compile here), warm-up of the cell's shapes, window ---
    counts = {}
    with cell.context():
        got, first, split = first_steps(cell, watch)
        steady = {p.name: settle(p.step) for p in cell.phases}
        spans["compile_s"] = sum(first[n] - steady[n] for n in first)
        compiles_before = watch.count
        # a traced step is timed alone; otherwise a sample is >= MIN_SAMPLE_S
        group = {n: 1 if args.trace else max(1, math.ceil(MIN_SAMPLE_S / s))
                 for n, s in steady.items()}
        bufs = {p.name: np.empty(MAX_SAMPLES) for p in cell.phases}
        gc.collect()
        gc.freeze()
        gc.disable()
        setup_s = time.time() - t_start
        if args.trace:
            from benchmark import xtrace

            trace_dir = os.path.join(ROOT, "cache", "bench_trace",
                                     f"{args.workload}.{args.seed}")
            try:
                traced = xtrace.record(cell.phases, trace_dir, bufs, counts,
                                       min(args.seconds, xtrace.MAX_TRACED_S))
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
        else:
            for p in cell.phases:
                if p.share > 0:
                    counts[p.name] = run_phase(
                        p.step, group[p.name], p.share * args.seconds,
                        bufs[p.name])
        gc.enable()
        gc.unfreeze()
    compiled_in_window = watch.count - compiles_before
    peaks = memory_peaks(devices[:chips])

    # --- earlier lines: set-up spans, step statistics, memory ---
    for k in ("input_synthesis_s", "plan_build_s", "placement_s", "weights_s",
              "build_s", "compile_s"):
        if k in spans:
            say(f"setup span {k} = {spans[k]:.4f}")
    say("compile split: " + " ".join(
        f"{k}={split[k]:.3f}" for k in sorted(split))
        + " (a nested trace is counted in its caller's too) "
        + " ".join(f"first_call.{n}={v:.3f}" for n, v in first.items()))
    for p in cell.phases:
        n = counts.get(p.name, 0)
        if n:
            say(phase_line(p.name, bufs[p.name][:n] * 1e3, group[p.name]))
    say("peak bytes (in use + reserved) per device after the window: "
        + " ".join(str(p) for p in peaks))
    phases = {p.name: p.metric for p in cell.phases}

    # --- the comparison that decides `correct` ---
    t0 = time.perf_counter()
    cell.release()
    ref = cell.reference(CHECK_STEPS, "float32")
    if args.control:
        precision = config["correct"]["control_precision"]
        say(f"CONTROL: the reference in {precision} stands in the program's place")
        got = cell.reference(CHECK_STEPS, precision)
    rows = compare(got, ref, cell_limits(args.workload, rehearsal))
    rows.append(("compiles_in_window", compiled_in_window, 0,
                 compiled_in_window == 0))
    for name, v, limit, ok in rows:
        if limit is None:
            say(f"not limited {name}: value={v}")
        else:
            say(f"check {name}: value={v:.6g} limit={limit:g} "
                f"{'ok' if ok else 'FAILED'}")
    say(f"reference and comparison took {time.perf_counter() - t0:.2f} s; "
        f"losses program {got['loss']} reference {ref['loss']}")
    correct = all(r[3] for r in rows)
    say("peak bytes (in use + reserved) per device after the reference: "
        + " ".join(str(p) for p in memory_peaks(devices[:chips])))

    # --- the result line ---
    e2e = metrics_of(bench["end_to_end"], args.workload)
    values = {"setup_s": setup_s, "hbm_peak_gb": max(peaks) / 1e9}
    attempted = 0
    for name, metric in phases.items():
        n = counts.get(name, 0)
        attempted += n * group[name]
        if n and metric:
            # the phase's elapsed time over all its steps: run_phase's samples
            # follow one another with no gap, so their mean is exactly that
            values[metric] = float(bufs[name][:n].mean() * 1e3)
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": max(peaks)}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": 0 if correct else attempted}
    if args.trace:
        per_layer = metrics_of(bench["per_layer"], args.workload,
                               {m["name"] for m in e2e})
        run = xtrace.RunRecord(
            trace=traced, spans=spans, info=cell.info, counts=counts,
            step_times={k: bufs[k][:n].tolist() for k, n in counts.items()},
            device_kind=devices[0].device_kind, say=say)
        metrics, breakdown = xtrace.reduce(run, per_layer, HERE)
        device["busy_s"], device["window_s"] = traced.busy_s, traced.window_s
        result["breakdown"] = breakdown
    else:
        metrics = {m["name"]: values[m["name"]] for m in e2e
                   if m["name"] in values}
    prefix = "cpu_rehearsal." if rehearsal else ""
    result["metrics"] = {prefix + k: {"value": v, "unit": units[k]}
                         for k, v in metrics.items()}
    result["device"] = device
    if rehearsal:
        result["rehearsal"] = "cpu"
    if not correct:
        report_failure(rows, args.workload, args.seed, args.trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
