"""A kernel's share of its roofline, in %: the least time the chip could
take for the work (operations or bytes from ``opsbytes.py``, over the
published peak) over the time the matched operations took in the trace.

params: ``phase``, ``match``/``unless`` (as ``scope_time``), ``work`` (a
module of ``benchmark/work``, given the cell's shapes and the number of
matched calls per step and device), ``peak`` (a key of ``peaks.json``) and
``peak_unit`` (what one unit of the peak is, in operations or bytes per
second). A share over 100 % means the work is counted too high or the time
leaves part of it out: the run fails rather than print it."""

from benchmark import opsbytes
from benchmark.reducers import scope_time


def reduce(run, params):
    found = scope_time.matched_ops(run, params)
    if found is None or not any(m for m, _ in found.values()):
        return None
    steps = len(run.trace.phases[params["phase"]]["steps"])
    n_dev = len(found)
    secs = sum(o.dur for m, _ in found.values() for o in m) / n_dev / steps
    calls = sum(len(m) for m, _ in found.values()) / n_dev / steps
    work = opsbytes.work(params["work"], run.info, calls)
    peak = opsbytes.device_peaks(run.device_kind)[params["peak"]] \
        * params["peak_unit"]
    share = 100.0 * (work / peak) / secs
    run.say(f"roofline {params['work']}: work={work:.6g} calls/step={calls:g} "
            f"least={work / peak * 1e3:.4f} ms measured={secs * 1e3:.4f} ms "
            f"share={share:.3f} % of {params['peak']}")
    if share > 100.0:
        raise AssertionError(
            f"roofline share {share:.1f} % > 100 %: {params['work']} counts too "
            f"much or the matched time leaves out part of the work")
    return share
