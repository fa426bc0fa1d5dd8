"""Useful attention FLOPs of one looped-LM train step."""


def work(info: dict, calls: float = 0) -> float:
    """Causal attention of one layer application: ``q k^T`` and ``p v`` are
    ``2 T^2 H D`` each over the whole square, half of which the mask keeps:
    ``2 T^2 H D`` forward. The backward pass needs twice the forward's
    (dq, dk, dv and the scores again), so three forwards a step for each of
    the ``layers x loop_steps`` applications. What ``remat`` and the backward
    kernels compute a second time is not useful work and is not counted."""
    T, H, D = info["seq_len"], info["heads"], info["head_dim"]
    return 3 * 2.0 * T * T * H * D * info["layers"] * info["loop_steps"]
