"""Seconds the program itself recorded for its once-per-launch stages
(``dgraph_tpu.obs.spans.stage``: always on, timed where the work happens),
summed over the stage names in ``params["stages"]``. The plain references
import nothing of the program, so the table holds the program's work alone.
A program without the table (the parent of the PR that added it), or one
that ran none of the named stages, gives nothing and the metric is left out
of the line."""


def reduce(run, params):
    from dgraph_tpu.obs import spans

    totals = getattr(spans, "stage_totals", dict)()
    found = [totals[name] for name in params["stages"] if name in totals]
    if not found:
        return None
    run.say("program stages: " + " ".join(
        f"{name}={totals[name]['total_s']:.4f}s/{totals[name]['count']}"
        for name in params["stages"] if name in totals))
    return sum(row["total_s"] for row in found)
