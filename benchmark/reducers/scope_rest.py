"""Device time per step of every operation that none of the sibling metrics
in ``others`` matches (their ``layer_metrics/<name>.json`` give the
patterns), in ms: 'everything else on the device'."""

import json
import os

from benchmark import xtrace
from benchmark.reducers import scope_time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reduce(run, params):
    ph = run.trace.phases.get(params["phase"])
    if not ph or not ph["steps"] or not run.trace.devices:
        return None
    hits = []
    for name in params["others"]:
        with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
            hits.append(scope_time.matcher(json.load(f)["params"]))
    by_dev = xtrace.phase_ops(run.trace, params["phase"])
    rest = sum(o.dur for ops in by_dev.values() for o in ops
               if not any(h(o) for h in hits))
    return 1e3 * rest / len(by_dev) / len(ph["steps"])
