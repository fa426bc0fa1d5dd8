"""Host time per step inside the benchmark's own ``TraceAnnotation`` of the
given name, over the traced steps of a phase, in ms (profiler's clock)."""


def reduce(run, params):
    ph = run.trace.phases.get(params["phase"])
    if not ph or not ph["steps"]:
        return None
    lo, hi = ph["steps"][0].start, ph["steps"][-1].end
    spans = [s for s in run.trace.host
             if s.name == params["span"] and lo <= s.start < hi]
    if not spans:
        return None
    return 1e3 * sum(s.dur for s in spans) / len(ph["steps"])
