"""Useful FLOPs of the held experts' grouped products in one train step."""


def work(info: dict, calls: float = 0) -> float:
    """Three products a routed row (gate, up, down), ``2 d F`` each, times
    three (forward, and the two products of each in the backward pass); what
    ``remat`` computes a second time, and the rows a tile holds beyond an
    expert's own, are not counted. The rows are those the PROGRAM counted as
    routed to this chip's experts (``moe.rows_here``, summed over the layers
    and the steps, over ``moe.rows_routed`` of the same steps), not the
    expected share. A program that keeps no such counters has run no expert
    layer: nothing to count."""
    from dgraph_tpu.obs.metrics import default_registry

    c = default_registry.snapshot()["counters"]
    if not c.get("moe.rows_routed"):
        return 0.0
    routed_a_step = info["rows"] * info["experts_per_token"] * info["layers"]
    rows_here = routed_a_step * c["moe.rows_here"] / c["moe.rows_routed"]
    return 3 * 3 * 2.0 * rows_here * info["hidden"] * info["expert_width"]
