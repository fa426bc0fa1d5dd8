"""Plain reference for the GCN configurations (``gcn_arxiv``,
``gcn_papers100m``).

Float32 ``jax.numpy`` at matmul precision ``highest``: ``x[src]``,
``.at[dst].add``, dense layers, softmax cross-entropy, Adam written out. No
Pallas, no plan, no ``Communicator``; nothing of ``dgraph_tpu`` is imported.
It works on the caller's edge list and vertex order, and takes the weights
the benchmark made from the seed (a nested dict under the program's names).

Layer equations (``experiments/OGB/GCN.py`` of the source):
``m_e = relu(W_s h[src_e] + b + W_d h[dst_e]) * w_e``,
``h'_v = sum over edges into v of m_e``, with the symmetric norm
``w_e = 1 / sqrt(deg[src_e] deg[dst_e])``, ``deg`` = number of edge ends at a
vertex, or with no ``w_e`` at all where the configuration states no norm;
after ``num_layers`` of these a dense head gives the logits.

``precision`` below float32 is the control: every matmul operand and every
edge message is rounded to that type in the forward pass (per-tensor scaled
for float8) and the arithmetic stays float32 — the least a lower-precision
program would lose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8
EDGE_BLOCK_ELEMENTS = 1 << 27  # one edge-level float32 tensor: 512 MiB at most


def edge_blocks(src, dst, w, num_nodes: int, width: int):
    """The edge list cut into equal blocks, so that edge-level work fits one
    chip: [blocks, rows] arrays with rows x width <= ``EDGE_BLOCK_ELEMENTS``
    (5 blocks at arxiv's 2.3 M edges, 19 at 9.7 M). The last block is padded
    with edges into vertex ``num_nodes``, which the scatter drops."""
    rows = max(1, EDGE_BLOCK_ELEMENTS // width)
    blocks = -(-len(src) // rows)
    rows = -(-len(src) // blocks)
    pad = blocks * rows - len(src)
    cut = lambda a, fill: np.concatenate(
        [a, np.full(pad, fill, a.dtype)]).reshape(blocks, rows)
    return (cut(src, 0), cut(dst, num_nodes),
            None if w is None else cut(w, 0.0))


def quantiser(precision: str):
    """Rounds a tensor to ``precision`` in the forward pass and lets the
    gradient through unchanged (straight-through): cotangents rounded to
    float8 without a scale of their own underflow to zero, which no
    lower-precision program worth the name would do."""
    if precision == "float32":
        return lambda a: a
    if precision == "bfloat16":
        # not astype there and back: XLA on the TPU drops that pair
        # (float32 - bfloat16 reference read 1e-6 on the chip, PR 25)
        rounded = lambda a: jax.lax.reduce_precision(a, 8, 7)
    elif precision == "float8":
        def rounded(a):  # per-tensor scale to float8_e4m3's range
            scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
            return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return lambda a: a + jax.lax.stop_gradient(rounded(a) - a)


def symmetric_norm(src, dst, num_nodes):
    deg = np.bincount(src, minlength=num_nodes) + np.bincount(dst, minlength=num_nodes)
    deg = np.maximum(deg, 1).astype(np.float64)
    return (1.0 / np.sqrt(deg[src] * deg[dst])).astype(np.float32)


def forward(params, x, edges, num_layers, q):
    """``edges``: (src, dst, w or None) as ``edge_blocks`` cuts them."""
    p = params["params"]
    num_nodes = x.shape[0]
    h = x
    for i in range(num_layers):
        lp = p[f"GraphConvLayer_{i}"]
        hs = q(h) @ q(lp["src_proj"]["kernel"]) + lp["src_proj"]["bias"]
        hd = q(h) @ q(lp["dst_proj"]["kernel"])

        @jax.checkpoint
        def edge_stage(hs_, hd_, block):
            src, dst, w = block
            m = q(jax.nn.relu(
                q(hs_)[src] + q(hd_)[jnp.minimum(dst, num_nodes - 1)]))
            if w is not None:
                m = m * w[:, None]
            return jnp.zeros_like(hd_).at[dst].add(m, mode="drop")

        # one block of edges after another (a loop on the device, forward and
        # backward), summed: blocks side by side would each hold their
        # [rows, width] tensors at once
        h, _ = jax.lax.scan(
            lambda acc, block: (acc + edge_stage(hs, hd, block), None),
            jnp.zeros_like(hd), edges)
    head = p["Dense_0"]
    return q(h) @ q(head["kernel"]) + head["bias"]


def loss_and_accuracy(logits, y, mask):
    logp = jax.nn.log_softmax(logits)
    ll = jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    count = jnp.maximum(mask.sum(), 1.0)
    hits = ((jnp.argmax(logits, -1) == y) * mask).sum()
    return -(ll * mask).sum() / count, hits / count


@functools.lru_cache(maxsize=None)
def programs(num_layers: int, lr: float, precision: str):
    """(step, evaluate), jitted once per setting. The graph goes in as
    arguments: closed over, it would be baked into the program as constants."""
    q = quantiser(precision)

    @jax.jit
    def step(p, mu, nu, t, data, mask):
        x_, y_, edges = data

        def lf(pp):
            return loss_and_accuracy(
                forward(pp, x_, edges, num_layers, q), y_, mask)[0]

        loss, g = jax.value_and_grad(lf)(p)
        mu = jax.tree.map(lambda m, a: B1 * m + (1 - B1) * a, mu, g)
        nu = jax.tree.map(lambda v, a: B2 * v + (1 - B2) * a * a, nu, g)
        c1, c2 = 1 - B1 ** t, 1 - B2 ** t
        p = jax.tree.map(
            lambda a, m, v: a - lr * (m / c1) / (jnp.sqrt(v / c2) + EPS),
            p, mu, nu)
        return p, mu, nu, loss, g

    @jax.jit
    def evaluate(p, data, mask):
        x_, y_, edges = data
        return loss_and_accuracy(
            forward(p, x_, edges, num_layers, q), y_, mask)

    return step, evaluate


def follow(params0, edge_index, x, y, masks, size, steps=3,
           precision="float32") -> dict:
    """Train ``steps`` Adam steps from ``params0``; returns each step's loss,
    the first gradient's and the total update's norm per leaf, and the
    validation loss and accuracy at the end."""
    from benchmark.weights import leaf_norms

    src = np.asarray(edge_index[0], np.int32)
    dst = np.asarray(edge_index[1], np.int32)
    w = symmetric_norm(src, dst, x.shape[0]) if size["symmetric_norm"] else None
    step, evaluate = programs(
        size["num_layers"], size["learning_rate"], precision)
    edges = edge_blocks(src, dst, w, x.shape[0], size["hidden"])
    with jax.default_matmul_precision("highest"):
        data = (jnp.asarray(x), jnp.asarray(y, jnp.int32),
                jax.tree.map(jnp.asarray, edges))
        m_tr = jnp.asarray(masks["train"], jnp.float32)
        m_va = jnp.asarray(masks["val"], jnp.float32)
        p0 = jax.tree.map(jnp.asarray, params0)
        p = p0
        mu = jax.tree.map(jnp.zeros_like, p)
        nu = jax.tree.map(jnp.zeros_like, p)
        out = {"loss": []}
        for t in range(1, steps + 1):
            p, mu, nu, loss, g = step(p, mu, nu, float(t), data, m_tr)
            out["loss"].append(float(loss))
            if t == 1:
                out["grad"], out["grad_norm"] = g, leaf_norms(g)
        out["delta_norm"] = leaf_norms(p, p0)
        ev_loss, ev_acc = evaluate(p, data, m_va)
        out["eval_loss"], out["eval_accuracy"] = float(ev_loss), float(ev_acc)
    return out
