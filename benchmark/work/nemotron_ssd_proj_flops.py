"""Useful projection FLOPs of the Mamba-2 mixers in one train step."""


def weights(info: dict) -> int:
    """A mixer's two projections: ``W_in`` d -> ``z | x B C | dt`` (``2 H P +
    2 G N + H`` columns) and ``W_out`` ``H P`` -> d."""
    d, inner = info["hidden"], info["ssd_heads"] * info["ssd_head_dim"]
    cols = 2 * inner + 2 * info["ssd_groups"] * info["ssd_state"] \
        + info["ssd_heads"]
    return d * cols + inner * d


def work(info: dict, calls: float = 0) -> float:
    """Twice tokens x weights forward, times three (forward, and the two
    products of each in the backward pass). What ``remat`` computes a second
    time is not counted."""
    return 3 * 2.0 * info["seq_len"] * weights(info) * info["layers_ssd"]
