"""Share of the traced steps of a phase in which no operation ran on the
device: 100 x (1 - union of the device-op intervals / the steps' span),
averaged over the devices."""

from benchmark import xtrace


def reduce(run, params):
    ph = run.trace.phases.get(params["phase"])
    if not ph or not ph["steps"] or not run.trace.devices:
        return None
    lo, hi = ph["steps"][0].start, ph["steps"][-1].end
    shares = []
    for ops in xtrace.phase_ops(run.trace, params["phase"]).values():
        busy = xtrace.union((o.start, o.start + o.dur) for o in ops)
        shares.append(1.0 - xtrace.total(xtrace.clip(busy, lo, hi)) / (hi - lo))
    return 100.0 * sum(shares) / len(shares)
