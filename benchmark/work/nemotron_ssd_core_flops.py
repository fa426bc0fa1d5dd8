"""Useful FLOPs of the chunked Mamba-2 recurrence's products in one train
step."""


def work(info: dict, calls: float = 0) -> float:
    """Forward, a token: the pairs ``j <= i`` of its chunk (``(L + 1) / 2`` on
    average) at ``2 N`` a group for ``C_i . B_j`` and ``2 P`` a head for the
    masked-decayed matrix times ``x``; ``2 P N`` a head into the chunk's
    state (``B^T x``) and ``2 P N`` a head for the start state's read-out.
    Times three (forward, and the two products of each in the backward
    pass). The pairs above the diagonal, the decays' elementwise work, the
    ``T / L`` hops between chunks and what ``remat`` computes a second time
    are not counted."""
    H, P = info["ssd_heads"], info["ssd_head_dim"]
    G, N, L = info["ssd_groups"], info["ssd_state"], info["ssd_chunk"]
    L = min(L, info["seq_len"])
    a_token = (L + 1) / 2 * 2 * (G * N + H * P) + 2 * 2 * H * P * N
    return 3.0 * info["seq_len"] * a_token * info["layers_ssd"]
