"""100 x one counter of the program's metrics registry
(``dgraph_tpu.obs.metrics.default_registry``) over another, in %: a share
counted where the work happens (``params``: ``numerator``, ``denominator``).
Counters named ``<numerator>.<part>`` are printed beside it, part by part.
Where the program keeps no such counters (the parent of the PR that added
them), or the denominator is 0 (nothing of the kind was built), there is
nothing to read and the metric is left out of the line."""


def reduce(run, params):
    from dgraph_tpu.obs.metrics import default_registry

    counters = default_registry.snapshot()["counters"]
    num, den = params["numerator"], params["denominator"]
    if not counters.get(den):
        return None
    parts = sorted(k[len(den) + 1:] for k in counters if k.startswith(den + "."))
    run.say(f"program counters: {num}={counters.get(num, 0.0):.0f} "
            f"{den}={counters[den]:.0f}" + "".join(
                f" {p}={counters.get(f'{num}.{p}', 0.0):.0f}/"
                f"{counters[f'{den}.{p}']:.0f}" for p in parts))
    return 100.0 * counters.get(num, 0.0) / counters[den]
