"""Useful attention FLOPs of one train step of a stack whose plain
grouped-query layers attend under a window or the whole causal prefix, each
under its own mask."""


def weights(info: dict) -> int:
    """One layer's attention: ``W_q`` and ``W_o`` d x H D, ``W_k`` and
    ``W_v`` d x Hkv D."""
    d, D = info["hidden"], info["head_dim"]
    return 2 * d * D * (info["heads"] + info["kv_heads"])


def pairs(info: dict) -> tuple:
    """(query, key) pairs one (full, windowed) layer's mask allows: ``T (T +
    1) / 2`` and ``w (w + 1) / 2 + (T - w) w``."""
    T = info["seq_len"]
    w = min(info["window"], T)
    return T * (T + 1) // 2, w * (w + 1) // 2 + (T - w) * w


def work(info: dict, calls: float = 0) -> float:
    """Every query head takes, for each pair its layer's mask allows, ``2 D``
    operations for the logit and ``2 D`` for the value: ``4 H D`` a pair;
    three forwards a step, as ``phi4_attn_flops`` counts them. Pairs inside
    a visited tile that the mask refuses, and what ``remat`` computes a
    second time, are not counted; grouped KV heads change bytes, not
    FLOPs."""
    full, window = pairs(info)
    allowed = info["layers_full"] * full + info["layers_window"] * window
    return 3.0 * allowed * 4 * info["heads"] * info["head_dim"]
