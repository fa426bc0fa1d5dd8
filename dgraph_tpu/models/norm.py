"""Normalisation layers: distributed BatchNorm over vertex-sharded
activations, and the token-local RMSNorm of the sequence models.

Reference parity: ``experiments/OGB-LSC/distributed_layers.py:22-207``
(DistributedBatchNorm1D): mean/var all-reduced across ranks with a custom
fwd/bwd. In JAX the psum is differentiable, so no hand-written backward is
needed; masking excludes padded vertices from the statistics (the reference
has no padding so it divides by global count directly,
``distributed_layers.py:29-68``).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


class DistributedBatchNorm(nn.Module):
    """``recompute=True`` is the reference's DistributedBN_with_Recompute
    (``distributed_layers.py:77-107``): the backward saves only the raw
    input plus the [F]-sized stats and REMATERIALIZES the normalized
    tensor, instead of keeping the [n_pad, F] x_hat residual alive
    through the whole backward. Here that is ``jax.checkpoint`` with a
    nothing-saved policy around the pure-local normalization — the stats
    collectives stay OUTSIDE the remat region (like the reference, which
    reuses forward's mean/var in backward), so recompute adds zero extra
    communication."""

    comm: Any
    momentum: float = 0.9
    epsilon: float = 1e-5
    use_running_average: bool = False
    recompute: bool = False

    @nn.compact
    def __call__(
        self,
        x: jax.Array,  # [n_pad, F] per-shard
        mask: Optional[jax.Array] = None,  # [n_pad] 1.0 for real vertices
        use_running_average: Optional[bool] = None,
    ) -> jax.Array:
        use_ra = (
            use_running_average
            if use_running_average is not None
            else self.use_running_average
        )
        F = x.shape[-1]
        ra_mean = self.variable("batch_stats", "mean", lambda: jnp.zeros(F))
        ra_var = self.variable("batch_stats", "var", lambda: jnp.ones(F))
        scale = self.param("scale", nn.initializers.ones, (F,))
        bias = self.param("bias", nn.initializers.zeros, (F,))

        if use_ra:
            mean, var = ra_mean.value, ra_var.value
        else:
            if mask is None:
                mask = jnp.ones(x.shape[0], x.dtype)
            m = mask[:, None]
            count = self.comm.all_reduce_sum(mask.sum())
            mean = self.comm.all_reduce_sum((x * m).sum(0)) / jnp.maximum(count, 1.0)
            var = self.comm.all_reduce_sum(((x - mean) ** 2 * m).sum(0)) / jnp.maximum(
                count, 1.0
            )
            if not self.is_initializing():
                ra_mean.value = self.momentum * ra_mean.value + (1 - self.momentum) * mean
                ra_var.value = self.momentum * ra_var.value + (1 - self.momentum) * var

        eps = self.epsilon

        def _normalize(x, mean, var, scale, bias):
            return scale * (x - mean) * jax.lax.rsqrt(var + eps) + bias

        if self.recompute:
            # save NOTHING from inside the region: backward recomputes the
            # normalization from (x, mean, var, scale, bias), all of which
            # the surrounding graph already keeps
            _normalize = jax.checkpoint(
                _normalize, policy=jax.checkpoint_policies.nothing_saveable)
        return _normalize(x, mean, var, scale, bias)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis (Zhang &
    Sennrich 2019), no bias and no mean subtraction. Token-local, so it
    needs no communicator. The statistic and the scaling are computed in
    float32 whatever ``dtype`` is; the result is cast to ``dtype`` (None:
    the input's). Scope ``dgraph.lm.norm`` (only the sequence LM uses it)."""

    epsilon: float = 1e-6
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        with jax.named_scope("dgraph.lm.norm"):
            xf = x.astype(jnp.float32)
            var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
            y = xf * jax.lax.rsqrt(var + self.epsilon) \
                * scale.astype(jnp.float32)
            return y.astype(self.dtype or x.dtype)
