"""What the span recorder costs in ``fit()``, and what ``fit()``'s own spans
say under the profiler. One process, on the chip:

    chiprun --chips 1 -- python scripts/span_cost.py

1. ``fit()`` at ``chip_smoke.py``'s arxiv shape, ``--epochs`` epochs with the
   tracer off and as many with it on, alternating, ``--reps`` times each. The
   loop's period is read the same way in both modes: the jitted train step is
   wrapped to note the clock when it is called, so one period is dispatch,
   wait, spans and the loop's own Python. With the tracer on, the mean
   ``train.step`` record is printed beside it.
2. One more ``fit()`` with the tracer on under ``jax.profiler``, reduced
   with ``benchmark/xtrace.py``'s interval functions: the device's idle
   gaps by the PROGRAM's span names (``step_dispatch``, ``block``), the idle
   share, ``train.step``'s mean on the profiler's clock, and the device time
   a step under each of the program's layer scopes (``dgraph.local_take``,
   ``dgraph.halo_exchange``, ...) with, beside each sum, its child scopes'
   (``rows``, ``mask``, ``slice``; ``send_gather``, ``wire``, ...:
   docs/tracing.md): every operation of the traced steps is in it, not the
   ten costliest.

Without a TPU it exits 2; ``--tiny-cpu`` is the explicit tiny mode (code path
only: its times say nothing about the chip). Writes
``chiprun_out/span_cost.json``; the last stdout line is that record.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
WARM = 2  # epochs that may compile; left out of every mean


def periods(fit_once, epochs: int) -> list:
    """Seconds between successive calls of the train step over one fit()."""
    from dgraph_tpu.train import loop

    called = []
    make = loop.make_train_step

    def make_noting(*a, **kw):
        step = make(*a, **kw)

        def noting(*args):
            called.append(time.perf_counter())
            return step(*args)

        return noting

    loop.make_train_step = make_noting
    try:
        fit_once(epochs + 1)  # the last call only closes the last period
    finally:
        loop.make_train_step = make
    return [b - a for a, b in zip(called, called[1:])][WARM:]


# a layer scope of an operation's path, and the scope opened under it (a
# lower-case name followed by more of the path: a primitive ends the path)
SCOPE = re.compile(r"(dgraph\.[\w.]+)\)*/(?:([a-z_]+)/)?")


def scope_sums(trace, phase: str = "train") -> dict:
    """``{layer scope: {"all": ms, child: ms, ...}}`` a traced step, averaged
    over the devices: each operation under its innermost ``dgraph.*`` scope,
    and under the child scope that follows it, if one does."""
    from benchmark import xtrace

    by_dev = xtrace.phase_ops(trace, phase)
    share = 1e3 / max(len(by_dev), 1) / len(trace.phases[phase]["steps"])
    out = {}
    for ops in by_dev.values():
        for o in ops:
            found = SCOPE.findall(o.scope)
            if not found:
                continue
            scope, child = found[-1]
            row = out.setdefault(scope, {"all": 0.0})
            row["all"] += o.dur * share
            if child:
                row[child] = row.get(child, 0.0) + o.dur * share
    return out


def reduce_trace(trace_dir: str) -> dict:
    """fit()'s own spans and the device's operations, on one clock."""
    from benchmark import xtrace

    with gzip.open(xtrace.find_trace(trace_dir)) as f:
        events = json.load(f)["traceEvents"]
    steps = sorted((e for e in events
                    if e.get("ph") == "X" and e["name"] == "train.step"),
                   key=lambda e: e["ts"])[WARM:]
    if not steps:
        return {"error": "no train.step event in the trace"}
    # xtrace assigns device work to a phase by the harness's span names:
    # give it fit()'s steady steps under those names, on the same thread
    last = steps[-1]
    events.append(dict(steps[0], name="bench_phase.train",
                       dur=last["ts"] + last["dur"] - steps[0]["ts"]))
    events += [dict(e, name="bench_step.train") for e in steps]
    trace = xtrace.from_events(events)
    out = {"train_step_ms_profiler_clock":
           statistics.fmean(e["dur"] for e in steps) / 1e3,
           "steps": len(steps), "devices": len(trace.devices)}
    lo, hi = steps[0]["ts"] * 1e-6, (last["ts"] + last["dur"]) * 1e-6
    for name in xtrace.HOST_SPANS:
        inside = [s.dur for s in trace.host
                  if s.name == name and lo <= s.start < hi]
        if inside:
            out[f"{name}_ms"] = 1e3 * sum(inside) / len(steps)
    if trace.devices:
        out.update(xtrace.account(trace, "train"))
        out["device_idle_pct"] = 100.0 * out["idle_ms"] / out["step_ms"]
        out["idle_gaps"] = xtrace.breakdown(trace)["idle_gaps"]
        out["scope_ms"] = scope_sums(trace)
        for scope, row in sorted(out["scope_ms"].items()):
            print(f"[span_cost] {scope}: " + " ".join(
                f"{k}={v:.3f}" for k, v in row.items()) + " ms a step",
                flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--tiny-cpu", action="store_true")
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu" and not args.tiny_cpu:
        print("span_cost: no TPU; this measurement only counts on the chip",
              file=sys.stderr)
        return 2
    import jax.numpy as jnp

    import chip_smoke
    from dgraph_tpu.comm import Communicator, make_graph_mesh
    from dgraph_tpu.data.synthetic import ARXIV_EDGES, ARXIV_NODES
    from dgraph_tpu.models import GCN
    from dgraph_tpu.obs import spans
    from dgraph_tpu.train.loop import fit
    from dgraph_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    size = dict(zip(
        ("nodes", "edges", "feat", "hidden", "classes"),
        (4_096, 16_384, 32, 64, 8) if args.tiny_cpu
        else (ARXIV_NODES, ARXIV_EDGES, 128, 256, 40)))
    d = jax.devices()[0]
    result = {"device": {"platform": d.platform, "kind": d.device_kind},
              "tiny_cpu": bool(args.tiny_cpu), "epochs": args.epochs}
    g = chip_smoke.build_graph(size, args.seed, 1)
    mesh = make_graph_mesh(ranks_per_graph=1, devices=jax.devices()[:1])
    comm = Communicator.init_process_group("tpu", world_size=1)
    model = GCN(size["hidden"], size["classes"], comm=comm, num_layers=2,
                dtype=jnp.bfloat16)

    def fit_once(epochs):
        fit(model, g, mesh, num_epochs=epochs, seed=args.seed)

    fit_once(WARM)  # fills the compile cache: later fits load from it
    runs = {"off": [], "on": []}
    span_ms = []
    for _ in range(args.reps):
        for mode in ("off", "on"):
            records = []
            if mode == "on":
                spans.enable(sink=records.append)
            try:
                got = periods(fit_once, args.epochs + WARM)
            finally:
                spans.disable()
            runs[mode].append(1e3 * statistics.fmean(got))
            steady = [r["dur_ms"] for r in records
                      if r["name"] == "train.step"
                      and r["attrs"]["epoch"] >= WARM][:args.epochs]
            if steady:
                span_ms.append(statistics.fmean(steady))
            print(f"[span_cost] tracer {mode}: loop period mean "
                  f"{runs[mode][-1]:.4f} ms median "
                  f"{1e3 * statistics.median(got):.4f} ms over {len(got)} "
                  f"epochs" + (f"; train.step records mean {span_ms[-1]:.4f} ms"
                               if steady else ""), flush=True)
    result["loop_period_ms"] = runs
    result["train_step_span_ms"] = span_ms
    result["on_minus_off_ms"] = (statistics.fmean(runs["on"])
                                 - statistics.fmean(runs["off"]))

    trace_dir = os.path.join(ROOT, "cache", "span_cost_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    spans.enable(sink=lambda rec: None)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        fit_once(WARM + 6)
    finally:
        jax.profiler.stop_trace()
        spans.disable()
    result["traced"] = reduce_trace(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    result["stage_totals"] = spans.stage_totals()

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "span_cost.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
