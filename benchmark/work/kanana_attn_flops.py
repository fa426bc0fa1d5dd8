"""Useful attention FLOPs of one train step of a stack that attends through
a latent: q.k heads of one size on value heads of another, one key head a
query head, under the causal mask."""


def pairs(info: dict) -> int:
    """(query, key) pairs the causal mask allows: ``T (T + 1) / 2``."""
    T = info["seq_len"]
    return T * (T + 1) // 2


def work(info: dict, calls: float = 0) -> float:
    """A layer's forward takes, a head and allowed pair, ``2 Dqk`` operations
    for the score and ``2 Dv`` for the value; its backward three products of
    ``Dqk`` (the score again, dq, dk) and two of ``Dv`` (dp, dv); under
    ``remat`` the forward runs a second time, and is counted a second time.
    ``Dqk`` is the MODEL's q.k head (192) even where the kernels multiply
    zero-padded ones (256): padding lowers the share and cannot raise it.
    Pairs inside a visited tile that the mask refuses, and the scores the
    two backward kernels each compute for themselves, are not counted."""
    qk, v = info["qk_head_dim"], info["v_head_dim"]
    forwards = 2 if info["remat"] else 1
    a_pair = 2.0 * (forwards * (qk + v) + 3 * qk + 2 * v)
    return a_pair * pairs(info) * info["heads"] * info["layers_attention"] \
        * info["loop_steps"]
