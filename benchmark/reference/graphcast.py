"""Plain reference for the ``graphcast_small`` configuration.

Float32 ``jax.numpy`` at matmul precision ``highest``, on the benchmark's own
copy of the graphs (``graphcast_graph.py``), in the caller's numbering; AdamW
and the three-phase schedule written out. Nothing of ``dgraph_tpu`` is
imported; the weights are the ones the benchmark made from the seed.

Equations (Lam et al. 2023, as ``dgraph_tpu/models/graphcast/model.py``
states them): five embedders ``MLP(2 layers, SiLU) -> LayerNorm``; an edge
block ``e' = e + LN(W2 silu(We e + b + (Ws x_src)[src] + (Wd x_dst)[dst]))``;
a node block ``x' = x + MLP([x, sum of incoming e])``; encoder grid->mesh,
``processor_layers`` edge+node blocks on the multimesh, decoder mesh->grid,
a 2-layer head whose output is added to the input channels. The loss is the
squared error summed over channels, averaged over grid points.

The processor's layers run under ``lax.scan`` over their stacked weights, and
every block is rematerialised in the backward pass, so that the float32
program compiles quickly and fits the chip the program has just left.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gcn import quantiser

B1, B2, EPS = 0.9, 0.999, 1e-8
LN_EPS = 1e-6


def three_phase(step, peak, warmup, decay, floor=3e-7):
    if step < warmup:
        return peak * step / warmup
    t = step - warmup
    if t < decay:
        alpha = floor / peak
        return peak * ((1 - alpha) * 0.5 * (1 + math.cos(math.pi * t / decay)) + alpha)
    return floor


def dense(p, x, q):
    y = q(x) @ q(p["kernel"])
    return y + p["bias"] if "bias" in p else y


def layer_norm(p, x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def mlp(p, x, q):
    n = sum(k.startswith("Dense_") for k in p)
    for i in range(n):
        x = dense(p[f"Dense_{i}"], x, q)
        if i < n - 1:
            x = jax.nn.silu(x)
    return layer_norm(p["LayerNorm_0"], x) if "LayerNorm_0" in p else x


def edge_block(p, e, x_src, x_dst, src, dst, q):
    hs = dense(p["src_proj"], x_src, q)
    hd = dense(p["dst_proj"], x_dst, q)
    h = q(jax.nn.silu(dense(p["edge_proj"], e, q) + q(hs)[src] + q(hd)[dst]))
    return e + mlp(p["MLP_0"], h, q)


def node_block(p, x_dst, e, dst, q):
    agg = jnp.zeros((x_dst.shape[0], e.shape[1]), e.dtype).at[dst].add(q(e))
    return x_dst + mlp(p["MLP_0"], jnp.concatenate([x_dst, agg], -1), q)


def forward(params, x, g, layers, q):
    p = params["params"]
    ck = jax.checkpoint
    grid = ck(lambda pp, a: mlp(pp, a, q))(
        p["embed_grid"], jnp.concatenate([x, g["grid_node_static"]], -1))
    m = mlp(p["embed_mesh"], g["mesh_node_static"], q)
    e_mesh = mlp(p["embed_mesh_edges"], g["mesh_edge_static"], q)
    e_g2m = ck(lambda pp, a: mlp(pp, a, q))(
        p["embed_g2m_edges"], g["g2m_edge_static"])
    e_m2g = ck(lambda pp, a: mlp(pp, a, q))(
        p["embed_m2g_edges"], g["m2g_edge_static"])

    s, d = g["g2m_edges"]
    e_g2m = ck(lambda pp, e, a, b: edge_block(pp, e, a, b, s, d, q))(
        p["enc_edge"], e_g2m, grid, m)
    m = node_block(p["enc_node"], m, e_g2m, d, q)
    grid = grid + ck(lambda pp, a: mlp(pp, a, q))(p["enc_grid_mlp"], grid)

    ms, md = g["mesh_edges"]
    stack = lambda prefix: jax.tree.map(
        lambda *xs: jnp.stack(xs), *[p[f"{prefix}_{i}"] for i in range(layers)])

    @jax.checkpoint
    def layer(carry, lp):
        e, mm = carry
        e = edge_block(lp[0], e, mm, mm, ms, md, q)
        mm = node_block(lp[1], mm, e, md, q)
        return (e, mm), None

    (e_mesh, m), _ = jax.lax.scan(
        layer, (e_mesh, m), (stack("proc_edge"), stack("proc_node")))

    s, d = g["m2g_edges"]
    e_m2g = ck(lambda pp, e, a, b: edge_block(pp, e, a, b, s, d, q))(
        p["dec_edge"], e_m2g, m, grid)
    grid = ck(lambda pp, a, e: node_block(pp, a, e, d, q))(
        p["dec_node"], grid, e_m2g)
    return x + mlp(p["head"], grid, q)


@functools.lru_cache(maxsize=None)
def program(layers: int, wd: float, precision: str):
    """The jitted AdamW step, made once per setting."""
    q = quantiser(precision)

    @jax.jit
    def step(p, mu, nu, t, lr, x, y, g):
        def lf(pp):
            pred = forward(pp, x, g, layers, q)
            return ((pred - y) ** 2).sum(-1).mean()

        loss, grad = jax.value_and_grad(lf)(p)
        mu = jax.tree.map(lambda m, a: B1 * m + (1 - B1) * a, mu, grad)
        nu = jax.tree.map(lambda v, a: B2 * v + (1 - B2) * a * a, nu, grad)
        c1, c2 = 1 - B1 ** t, 1 - B2 ** t
        p = jax.tree.map(
            lambda a, m, v: a - lr * (
                (m / c1) / (jnp.sqrt(v / c2) + EPS) + wd * a), p, mu, nu)
        return p, mu, nu, loss, grad

    return step


@functools.lru_cache(maxsize=2)
def graph_on_device(mesh_level: int, num_lat: int, num_lon: int):
    from benchmark.reference import graphcast_graph

    graph = graphcast_graph.build(mesh_level, num_lat, num_lon)
    return {k: jnp.asarray(v) for k, v in graph.items()
            if isinstance(v, np.ndarray)}


def follow(params0, fields, size, precision="float32") -> dict:
    """One AdamW step per (x, y) of ``fields`` from ``params0``: each step's
    loss, the first gradient's and the total update's norm per leaf."""
    from benchmark.weights import leaf_norms

    step = program(size["processor_layers"], size["weight_decay"], precision)
    with jax.default_matmul_precision("highest"):
        g = graph_on_device(size["mesh_level"], size["num_lat"], size["num_lon"])
        p0 = jax.tree.map(jnp.asarray, params0)
        p, mu, nu = p0, jax.tree.map(jnp.zeros_like, p0), jax.tree.map(
            jnp.zeros_like, p0)
        out = {"loss": []}
        for k, (x, y) in enumerate(fields):
            lr = three_phase(k, size["peak_lr"], size["warmup_steps"],
                             size["decay_steps"])
            p, mu, nu, loss, grad = step(
                p, mu, nu, float(k + 1), lr, jnp.asarray(x), jnp.asarray(y), g)
            out["loss"].append(float(loss))
            if k == 0:
                out["grad"], out["grad_norm"] = grad, leaf_norms(grad)
        out["delta_norm"] = leaf_norms(p, p0)
    return out
