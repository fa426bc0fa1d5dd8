"""Useful attention FLOPs of one train step of differential attention under
a window and full."""


def work(info: dict, calls: float = 0) -> float:
    """Every softmax map (``heads`` a layer: two a query pair) takes, for each
    (query, key) pair its mask allows, ``2 D`` operations for the logit and
    ``2 * 2 D`` for the value of ``[v1 ; v2]``; three forwards a step. The
    pairs: ``T (T + 1) / 2`` a full-causal layer (the self layer that keeps
    its keys, the cross layers), ``w (w + 1) / 2 + (T - w) w`` a windowed one.
    Pairs inside a visited tile that the mask refuses, and what ``remat``
    computes a second time, are not counted."""
    T, H, D = info["seq_len"], info["heads"], info["head_dim"]
    w = min(info["window"], T)
    pairs = info["layers_full"] * (T * (T + 1) // 2) \
        + info["layers_window"] * (w * (w + 1) // 2 + (T - w) * w)
    return 3.0 * pairs * H * (2 * D + 2 * 2 * D)
