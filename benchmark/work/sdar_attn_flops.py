"""Useful attention FLOPs of one block-diffusion train step."""


def work(info: dict, calls: float = 0) -> float:
    """Attention over the ``2L`` rows ``[xt ; x0]`` under the block-diffusion
    mask: ``L (L + block)`` of the ``4 L^2`` pairs are allowed, and ``q k^T``
    and ``p v`` are ``2 H D`` each a pair: ``4 L (L + block) H D`` forward a
    layer (the query heads count; grouped KV heads change bytes, not FLOPs).
    The backward pass needs twice the forward's, so three forwards a step a
    layer. What ``remat`` and the backward kernels compute a second time, and
    the masked pairs inside a visited tile, are not useful work and are not
    counted."""
    L, B = info["seq_len"], info["block_length"]
    return 3 * 4.0 * L * (L + B) * info["heads"] * info["head_dim"] \
        * info["layers"] * info["loop_steps"]
