"""Useful projection FLOPs of the state-space mixers and the gated memory
units in one train step."""


def work(info: dict, calls: float = 0) -> float:
    """A state-space mixer's four products (``W_in`` d -> 2C, ``W_x`` C -> R
    + 2N, ``W_dt`` R -> C, ``W_out`` C -> d) and a memory unit's two (d -> C,
    C -> d): twice tokens x weights forward, times three (forward, and the
    two products of each in the backward pass). What ``remat`` computes a
    second time is not counted."""
    d, C = info["hidden"], info["ssm_inner"]
    N, R = info["ssm_state"], info["ssm_dt_rank"]
    ssm = 2 * d * C + C * (R + 2 * N) + R * C + C * d
    gmu = 2 * d * C
    return 3 * 2.0 * info["seq_len"] * (
        ssm * info["layers_ssm"] + gmu * info["layers_gmu"])
