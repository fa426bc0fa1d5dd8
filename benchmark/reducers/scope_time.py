"""Device time per step of the operations whose named-scope path, HLO name
or category matches, over the traced steps of a phase, averaged over the
devices, in ms.

params: ``phase``; ``match``: regexes, any of which has to be found in
``"<scope> <name> <category>"``; ``unless``: regexes that exclude."""

import re

from benchmark import xtrace


def matcher(params):
    inc = [re.compile(p) for p in params["match"]]
    exc = [re.compile(p) for p in params.get("unless", [])]

    def hit(op):
        text = f"{op.scope} {op.name} {op.category}"
        return any(r.search(text) for r in inc) and not any(
            r.search(text) for r in exc)

    return hit


def matched_ops(run, params):
    """device -> (matched ops, all ops) in the phase's traced steps."""
    ph = run.trace.phases.get(params["phase"])
    if not ph or not ph["steps"] or not run.trace.devices:
        return None
    hit = matcher(params)
    return {d: ([o for o in ops if hit(o)], ops)
            for d, ops in xtrace.phase_ops(run.trace, params["phase"]).items()}


def reduce(run, params):
    found = matched_ops(run, params)
    if found is None or not any(m for m, _ in found.values()):
        return None
    steps = len(run.trace.phases[params["phase"]]["steps"])
    return 1e3 * sum(o.dur for m, _ in found.values() for o in m) \
        / len(found) / steps
