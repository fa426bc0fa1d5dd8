"""Useful FLOPs of the held experts' grouped products in one train step of a
stack whose experts have no gate projection."""


def weights(info: dict) -> int:
    """One routed expert: ``W_up`` d -> F and ``W_down`` F -> d."""
    return 2 * info["hidden"] * info["expert_width"]


def work(info: dict, calls: float = 0) -> float:
    """``lfm2_moe_flops``'s count with TWO products a routed row (up, down:
    ``2 d F`` each; the ungated squared-ReLU expert), times three, over the
    expert layers only. The rows are those the PROGRAM counted as routed to
    this chip's experts (``moe.rows_here`` over ``moe.rows_routed`` of the
    same steps). The shared expert is dense, not a grouped product: not
    counted here. A program that keeps no such counters has run no expert
    layer: nothing to count."""
    from dgraph_tpu.obs.metrics import default_registry

    c = default_registry.snapshot()["counters"]
    if not c.get("moe.rows_routed"):
        return 0.0
    routed_a_step = info["seq_len"] * info["experts_per_token"] \
        * info["layers_expert_ffn"] * info["loop_steps"]
    rows_here = routed_a_step * c["moe.rows_here"] / c["moe.rows_routed"]
    return 3 * 2.0 * rows_here * weights(info)
