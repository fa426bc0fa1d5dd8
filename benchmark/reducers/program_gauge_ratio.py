"""100 x one gauge of the program's metrics registry
(``dgraph_tpu.obs.metrics.default_registry``) over one of its counters, in %:
a level the program kept (a run's maximum, set where the work happens) against
a size it counted once (``params``: ``gauge``, ``counter``), as
``program_counter_ratio`` reads two counters. Where the program keeps no such
gauge or counter (the parent of the PR that added them), or the counter is 0
(nothing of the kind was built), there is nothing to read and the metric is
left out of the line."""


def reduce(run, params):
    from dgraph_tpu.obs.metrics import default_registry

    snap = default_registry.snapshot()
    level = snap.get("gauges", {}).get(params["gauge"])
    size = snap.get("counters", {}).get(params["counter"])
    if level is None or not size:
        return None
    run.say(f"program gauge: {params['gauge']}={level:.0f} "
            f"{params['counter']}={size:.0f}")
    return 100.0 * level / size
