"""Useful projection and MLP FLOPs of one looped-LM train step."""


def layer_weights(info: dict) -> int:
    """Matmul weights of one decoder layer: q, k, v, o and the gated MLP."""
    d, D = info["hidden"], info["head_dim"]
    return (2 * d * info["heads"] * D + 2 * d * info["kv_heads"] * D
            + 3 * d * info["intermediate"])


def work(info: dict, calls: float = 0) -> float:
    """Twice tokens x weights for every layer application of the forward
    pass (``layers x loop_steps``: the same weights, used again every pass),
    times three (forward, and the two products of the backward pass). What
    ``remat`` computes a second time is not counted, so a rematerialised step
    cannot pass three quarters of the roofline by this count. The head and
    the gate belong to the exit loss, not here."""
    return 3 * 2.0 * info["seq_len"] * layer_weights(info) \
        * info["layers"] * info["loop_steps"]
