"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights itself, from ``--seed``, in the shape of the
program's parameter tree (``jax.eval_shape`` of its ``init``), and hands the
same tree to the program and to the plain reference: neither takes anything
the other has made. Leaves are float32, the type the program keeps them in.

By the leaf's name: ``kernel`` ~ N(0, gain^2/fan_in) (gain 1 is flax's
default scale; a configuration whose layers sum unnormalised messages states
a smaller ``kernel_gain``, so that activations neither grow with depth nor
saturate the softmax),
``bias`` ~ 0.02 N(0, 1), ``scale`` (LayerNorm) = 1 + 0.02 N(0, 1), so no
leaf is inert in the first gradients.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def seeded_params(shapes, seed: int, sharding=None, kernel_gain: float = 1.0):
    """``shapes``: a pytree of ShapeDtypeStructs. Returns the filled tree."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        keys = jax.random.split(key, len(paths))
        out = []
        for k, (path, s) in zip(keys, paths):
            kind = leaf_name(path).rsplit("/", 1)[-1]
            n = jax.random.normal(k, s.shape, jnp.float32)
            if kind == "kernel":
                leaf = n * kernel_gain * (1.0 / s.shape[0]) ** 0.5
            elif kind == "scale":
                leaf = 1.0 + 0.02 * n
            else:
                leaf = 0.02 * n
            out.append(leaf.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make, out_shardings=sharding)(jax.random.key(seed))


def leaf_norms(tree, other=None) -> dict:
    """{leaf name: ||leaf|| (or ||leaf - other leaf||)}, as floats. One small
    jitted reduction over state that already exists; the model is not run."""

    @jax.jit
    def norms(a, b):
        if b is None:
            return jax.tree.map(
                lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), a)
        return jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)

    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(norms(tree, other)))
    return {leaf_name(p): float(v) for p, v in flat}
