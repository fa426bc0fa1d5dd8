"""Median host-clock time of the traced steps of a phase, in ms: for a step
that only traced runs drive (each ends in ``block_until_ready``)."""

import statistics


def reduce(run, params):
    times = run.step_times.get(params["phase"])
    if not times:
        return None
    return 1e3 * statistics.median(times)
