"""The traced window and the reduction from a profiler trace to numbers.

``record`` runs a few steps of each phase under ``jax.profiler`` (the Python
tracer off, so that the host's ``python3`` line holds little more than the
benchmark's own ``TraceAnnotation`` spans) and ``load`` reads the
``*.trace.json.gz`` the profiler writes beside its ``.xplane.pb``: per
``/device:TPU:n`` process the ``XLA Ops`` thread (one event per executed HLO
operation; ``args.tf_op`` is the operation's ``jax.named_scope`` path,
``args.hlo_category`` the profiler's category), and from ``/host:CPU`` the
benchmark's spans. Device events are assigned to a phase by the host span
they fall in; every step ends in ``block_until_ready``, so a step's device
work lies inside its host span. The device's clock runs a fraction of a
millisecond ahead of the host's in these files, so device times are shifted
until the first traced operation starts with the first traced step.

Interval arithmetic (union, gaps, attribution of a gap to the host span that
covers most of it, the part of one set of intervals that another does not
cover) is here, for the reducers under ``reducers/``; it is checked on the
small recorded trace under ``tests/``.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import importlib
import json
import os
import shutil
import time

MAX_TRACED_S = 8.0
TRACED_STEPS = 6
HOST_SPANS = ("host_feed", "step_dispatch", "block")
OPS_LINE = "XLA Ops"
H2D_DONE = "tpu::System::TransferToDevice=>IssueEvent=>Done"  # args.size: bytes


@dataclasses.dataclass
class Op:
    name: str  # the HLO operation, e.g. 'fusion.229'
    scope: str  # its named-scope path, e.g. 'jit(step)/.../dgraph.local_take/gather'
    category: str  # the profiler's category, e.g. 'convolution fusion'
    start: float  # seconds
    dur: float


@dataclasses.dataclass
class Span:
    name: str
    start: float
    dur: float

    @property
    def end(self):
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: dict  # device plane name -> [Op], by start
    host: list  # [Span]: the benchmark's annotations
    phases: dict  # phase name -> {'span': Span, 'steps': [Span]}
    transfers: list = dataclasses.field(default_factory=list)  # [(s, bytes)]
    busy_s: float = 0.0
    window_s: float = 0.0


@dataclasses.dataclass
class RunRecord:
    """What a reducer gets."""

    trace: Trace
    spans: dict  # set-up spans, seconds
    info: dict  # the cell's padded shapes
    counts: dict  # steps traced, by phase
    step_times: dict  # host-clock seconds of each traced step, by phase
    device_kind: str
    say: object


# --- interval arithmetic ---------------------------------------------------

def union(intervals) -> list:
    """Sorted, disjoint [(start, end)] covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        elif e > s:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(covered, lo, hi) -> list:
    """The parts of [lo, hi] that the disjoint sorted ``covered`` leaves."""
    out, at = [], lo
    for s, e in clip(covered, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def uncovered(a, b) -> float:
    """Seconds of the disjoint sorted ``a`` during which nothing of the
    disjoint sorted ``b`` runs."""
    out = 0.0
    for s, e in a:
        out += (e - s) - total(clip(b, s, e))
    return out


def attribute(gap, spans) -> str:
    """The host span that covers most of ``gap``, or 'host_other'."""
    best, name = 0.0, "host_other"
    for sp in spans:
        cover = min(gap[1], sp.end) - max(gap[0], sp.start)
        if cover > best:
            best, name = cover, sp.name
    return name


# --- recording -------------------------------------------------------------

def record(phases, trace_dir, bufs, counts, seconds) -> Trace:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for p in phases:
            end = time.perf_counter() + seconds / len(phases)
            with jax.profiler.TraceAnnotation(f"bench_phase.{p.name}"):
                n = 0
                while n < TRACED_STEPS and (n < 2 or time.perf_counter() < end):
                    with jax.profiler.TraceAnnotation(f"bench_step.{p.name}"):
                        t0 = time.perf_counter()
                        p.step()
                        bufs[p.name][n] = time.perf_counter() - t0
                    n += 1
                counts[p.name] = n
    finally:
        jax.profiler.stop_trace()
    return load(trace_dir)


def find_trace(trace_dir) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    if not found:
        raise FileNotFoundError(f"no .trace.json.gz under {trace_dir}")
    return found[-1]


def load(trace_dir) -> Trace:
    return load_file(find_trace(trace_dir))


def load_file(path) -> Trace:
    with gzip.open(path) as f:
        return from_events(json.load(f)["traceEvents"])


def from_events(events) -> Trace:
    """The Chrome-trace events of one profile -> Trace."""
    process, thread = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            process[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            thread[(e["pid"], e["tid"])] = e["args"]["name"]
    devices, host, transfers = {}, [], []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = process.get(e["pid"], "")
        if e["name"] == H2D_DONE:
            transfers.append((e["ts"] * 1e-6, int(e["args"]["size"])))
        if proc.startswith("/device:TPU") \
                and thread.get((e["pid"], e["tid"])) == OPS_LINE:
            a = e.get("args", {})
            devices.setdefault(proc, []).append(Op(
                e["name"], a.get("tf_op", ""), a.get("hlo_category", ""),
                e["ts"] * 1e-6, e["dur"] * 1e-6))
        elif proc.startswith("/host:") and (
                e["name"] in HOST_SPANS or e["name"].startswith("bench_")):
            host.append(Span(e["name"], e["ts"] * 1e-6, e["dur"] * 1e-6))
    for ops in devices.values():
        ops.sort(key=lambda o: o.start)
    return assemble(devices, sorted(host, key=lambda s: s.start), transfers)


def assemble(devices, host, transfers=()) -> Trace:
    phases = {}
    for sp in host:
        if sp.name.startswith("bench_phase."):
            phases[sp.name.split(".", 1)[1]] = {"span": sp, "steps": []}
    for sp in host:
        if sp.name.startswith("bench_step."):
            phases[sp.name.split(".", 1)[1]]["steps"].append(sp)
    tr = Trace(devices, [s for s in host if s.name in HOST_SPANS], phases,
               sorted(transfers))
    if phases and devices:
        lo = min(p["span"].start for p in phases.values())
        hi = max(p["span"].end for p in phases.values())
        for ops in devices.values():
            # one clock: the first traced operation starts with the first step
            first = next((o.start for o in ops if o.start > lo - 0.005), None)
            if first is not None and first < lo:
                for o in ops:
                    o.start += lo - first
        tr.window_s = hi - lo
        tr.busy_s = sum(
            total(clip(union((o.start, o.start + o.dur) for o in ops), lo, hi))
            for ops in devices.values()) / len(devices)
    return tr


def phase_ops(trace: Trace, phase: str) -> dict:
    """device -> the ops inside the phase's traced steps."""
    steps = trace.phases[phase]["steps"]
    lo, hi = steps[0].start, steps[-1].end
    return {d: [o for o in ops if lo <= o.start < hi]
            for d, ops in trace.devices.items()}


# --- reduction ---------------------------------------------------------------

def reduce(run: RunRecord, per_layer: list, here: str):
    """({metric: value}, breakdown) for the per-layer metrics of this cell.
    A reducer that finds nothing to read returns None and the metric is left
    out of the line."""
    metrics = {}
    for m in per_layer:
        with open(os.path.join(here, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        reducer = importlib.import_module(f"benchmark.reducers.{spec['reducer']}")
        value = reducer.reduce(run, spec.get("params", {}))
        if value is not None:
            metrics[m["name"]] = float(value)
    for phase, ph in run.trace.phases.items():
        if ph["steps"] and run.trace.devices:
            run.say(f"traced phase {phase}: " + " ".join(
                f"{k}={v:.4f}" for k, v in account(run.trace, phase).items()))
            n, b = host_to_device(run.trace, phase)
            run.say(f"traced phase {phase}: host->device per step: "
                    f"{n:g} transfers, {b:.0f} bytes (the batch on the host: "
                    f"{run.info.get('h2d_bytes_per_step', {}).get(phase, 0)} "
                    f"bytes)")
    return metrics, breakdown(run.trace)


def breakdown(trace: Trace) -> dict:
    """The ten device operations that took most time (seconds per traced
    step of their phase, averaged over devices) and the ten longest idle
    gaps, by what the host was doing."""
    per_op, gaps_out = {}, []
    nd = max(len(trace.devices), 1)
    for phase, ph in trace.phases.items():
        if not ph["steps"]:
            continue
        lo, hi = ph["steps"][0].start, ph["steps"][-1].end
        share = 1.0 / nd / len(ph["steps"])
        for ops in phase_ops(trace, phase).values():
            for o in ops:
                key = f"{phase}:{o.name}:{o.category}:{short_scope(o.scope)}"
                per_op[key] = per_op.get(key, 0.0) + o.dur * share
            busy = union((o.start, o.start + o.dur) for o in ops)
            for g in gaps(busy, lo, hi):
                gaps_out.append((attribute(g, trace.host), g[1] - g[0]))
    return {
        "device_ops": [[k, v] for k, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v] for k, v in
                      sorted(gaps_out, key=lambda kv: -kv[1])[:10]],
    }


def host_to_device(trace: Trace, phase: str) -> tuple:
    """(transfers, bytes) per traced step of a phase, from the runtime's own
    transfer events: what went from the host to the device."""
    steps = trace.phases[phase]["steps"]
    lo, hi = steps[0].start, steps[-1].end
    inside = [b for t, b in trace.transfers if lo <= t < hi]
    return len(inside) / len(steps), sum(inside) / len(steps)


def account(trace: Trace, phase: str) -> dict:
    """Per traced step of a phase, in ms: the step on the host's clock, the
    sum of device-operation times, the device's idle time, and what is left:
    step - (operations + idle), which is minus the time in which operations
    overlapped."""
    ph = trace.phases[phase]
    n, nd = len(ph["steps"]), max(len(trace.devices), 1)
    lo, hi = ph["steps"][0].start, ph["steps"][-1].end
    ops_s = busy_s = 0.0
    for ops in phase_ops(trace, phase).values():
        ops_s += sum(o.dur for o in ops)
        busy_s += total(clip(union((o.start, o.start + o.dur) for o in ops), lo, hi))
    step = (hi - lo) / n
    ops_ms, idle_ms = 1e3 * ops_s / nd / n, 1e3 * (step - busy_s / nd / n)
    return {"step_ms": 1e3 * step, "ops_ms": ops_ms, "idle_ms": idle_ms,
            "remainder_ms": 1e3 * step - ops_ms - idle_ms}


def short_scope(scope: str) -> str:
    return "/".join(scope.rstrip(":").split("/")[-3:])
