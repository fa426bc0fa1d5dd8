"""Useful projection FLOPs of the gated short convolutions in one train step."""


def work(info: dict, calls: float = 0) -> float:
    """A conv operator's two products, ``W_in`` (d -> 3d) and ``W_out``
    (d -> d): twice tokens x (3 d^2 + d^2) forward, times three (forward, and
    the two products of each in the backward pass), over the conv layers
    only. What ``remat`` computes a second time is not counted, so a
    rematerialised step cannot pass three quarters of the roofline by this
    count."""
    d = info["hidden"]
    return 3 * 2.0 * info["seq_len"] * 4 * d * d * info["layers_conv"]
