"""Time per step of the matched (collective) operations during which no
other operation runs on the same device, in ms: the part of the exchange
that compute does not hide."""

from benchmark import xtrace
from benchmark.reducers import scope_time


def reduce(run, params):
    found = scope_time.matched_ops(run, params)
    if found is None or not any(m for m, _ in found.values()):
        return None
    steps = len(run.trace.phases[params["phase"]]["steps"])
    out = 0.0
    for matched, ops in found.values():
        ids = {id(o) for o in matched}
        mine = xtrace.union((o.start, o.start + o.dur) for o in matched)
        rest = xtrace.union((o.start, o.start + o.dur) for o in ops
                            if id(o) not in ids)
        out += xtrace.uncovered(mine, rest)
    return 1e3 * out / len(found) / steps
