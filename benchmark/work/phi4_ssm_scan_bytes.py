"""Least HBM bytes of the selective scans in one train step."""


def work(info: dict, calls: float = 0) -> float:
    """What ONE fused pass a direction would have to move, in ``[T, C]``
    streams (C = ``ssm_inner``) of the types the program moves them in:
    forward reads ``u`` (compute dtype) and ``Delta`` (float32) and writes
    ``y`` (float32); backward reads ``u``, ``Delta`` and ``y``'s cotangent
    and writes ``u``'s (compute dtype) and ``Delta``'s (float32): three
    compute-dtype and five float32 streams a state-space layer. ``B``, ``C``
    (``[T, N]``), their cotangents, ``dA`` and ``dD`` are noise beside them.
    What ``remat`` reads a second time, the ``[T, N, C]`` states and every
    pass of a chunked form over them are not needed and not counted: a plain
    chunked form reads low by this count."""
    stream = info["seq_len"] * info["ssm_inner"]
    return stream * (3.0 * info["compute_bytes"] + 5.0 * 4) * info["layers_ssm"]
