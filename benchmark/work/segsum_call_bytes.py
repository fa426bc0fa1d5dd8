"""Least HBM bytes of segment-sum kernel calls, after ``bench.py``'s
analytic-minimum model (each edge-row tensor counted once where it is
produced or consumed), taken per kernel call instead of per epoch."""


def work(info: dict, calls: float) -> float:
    """``calls`` segment-sum kernel calls on one device: each reads an
    [e_pad, col_block] tile of messages and the e_pad segment ids once and
    writes an [n_pad, col_block] result once. Weights, the fused bias and the
    float32 accumulator are extra traffic a better kernel could avoid, so
    they are not counted as needed."""
    cb = min(info["hidden"], info.get("col_block", 128))
    b = info["compute_bytes"]
    return calls * ((info["e_pad"] + info["n_pad"]) * cb * b + info["e_pad"] * 4)
