"""Useful FLOPs of the held experts' grouped products in one train step of a
stack in which only some layers have experts."""


def work(info: dict, calls: float = 0) -> float:
    """``sdar_moe_flops``'s count (three products a routed row, ``2 d F``
    each, times three; the rows are those the PROGRAM counted as routed to
    this chip's experts, ``moe.rows_here`` over ``moe.rows_routed`` of the
    same steps) over the EXPERT layers only: the leading dense layer routes
    nothing. A program that keeps no such counters has run no expert layer:
    nothing to count."""
    from dgraph_tpu.obs.metrics import default_registry

    c = default_registry.snapshot()["counters"]
    if not c.get("moe.rows_routed"):
        return 0.0
    routed_a_step = info["seq_len"] * info["experts_per_token"] \
        * info["layers_expert_ffn"] * info["loop_steps"]
    rows_here = routed_a_step * c["moe.rows_here"] / c["moe.rows_routed"]
    return 3 * 3 * 2.0 * rows_here * info["hidden"] * info["expert_width"]
