"""Least HBM bytes of the gated short convolutions' gate-and-tap passes in
one train step."""


def work(info: dict, calls: float = 0) -> float:
    """What ONE fused pass a direction would have to move, in ``[T, d]``
    streams of the compute dtype: forward reads B, C, x~ and writes
    ``C * conv(B * x~)`` (4 streams); backward reads B, C, x~ and the
    result's cotangent and writes the three cotangents (7 streams). The taps
    (K x d) and the halo rows are noise beside them. Over the conv layers
    only; what ``remat`` reads a second time, and every intermediate an
    unfused form writes and reads back (y, z, the shifted copies), is not
    needed and not counted: a plain form reads low by this count."""
    stream = info["seq_len"] * info["hidden"] * info["compute_bytes"]
    return 11.0 * stream * info["layers_conv"]
