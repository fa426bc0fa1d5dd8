"""Useful attention FLOPs of one train step of a stack in which only some
layers attend."""


def work(info: dict, calls: float = 0) -> float:
    """``looplm_attn_flops``'s count (causal: ``2 T^2 H D`` forward a layer,
    three forwards a step; grouped KV heads change bytes, not FLOPs) over the
    ATTENTION layers only: the cell's ``layers`` counts the convolutions
    too."""
    T, H, D = info["seq_len"], info["heads"], info["head_dim"]
    return 3 * 2.0 * T * T * H * D * info["layers_attention"] \
        * info["loop_steps"]
