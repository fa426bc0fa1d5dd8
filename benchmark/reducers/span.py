"""A set-up span of the run, in seconds (source: program_span)."""


def reduce(run, params):
    return run.spans.get(params["span"])
