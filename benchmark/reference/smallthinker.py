"""Plain reference for the ``smallthinker_21b_a3b`` configuration: a sparse-
expert decoder whose router reads the layer's INPUT, before the norm and
before attention, whose experts are ReGLU with no shared expert beside them,
and whose attention is one full causal layer WITHOUT positions among three
windowed layers WITH rotary positions (PowerInfer SmallThinker-21BA3B-
Instruct); this chip's share of its first four layers.

Float32 ``jax.numpy`` at matmul precision ``highest``; no kernel, no flax,
nothing of ``dgraph_tpu`` imported. It takes the weights the benchmark made
from the seed (a nested dict under the program's names: each run of equal
layers, ``layers_<run>``, with its leaves stacked on a leading axis, the held
experts on a second) and the same token batches, and follows AdamW for a few
steps.

Equations (d hidden; H query heads on Hkv KV heads of D; E experts of width F
of which ``moe_num_primary_experts`` are held here, k a token; window w):

- layer with input ``x``: ``r = W_r x`` over all E, float32, of the stream
  itself (NO norm before the router); the k largest logits are chosen; gates
  ``g = softmax`` over the k chosen logits; ``h = x + Attn(RMSNorm_1(x))``;
  ``y = h + sum over the chosen experts THAT ARE HELD HERE (ids
  first_expert ...) of g_e W_down,e (relu(W_gate,e u) * W_up,e u)``, ``u =
  RMSNorm_2(h)``: what the absent experts would add is left out, and that
  partial sum goes on. Pre-norm only, eps 1e-6, no bias, no q/k norm, no
  shared expert;
- ``Attn``: q, k, v projections as ``[T, H or Hkv, D]``, query head j reads
  KV head ``j // (H / Hkv)``; layout 1 (windowed): rotary embedding
  (rotate-half) on q and k at the token's position, theta ``rope_theta``,
  then exact softmax(``q k^T / sqrt(D)``) over the keys j with ``i - w < j
  <= i`` (the query's own position and the w - 1 before it); layout 0
  (full): NO positional encoding, exact causal softmax over the whole
  prefix; ``W_o``;
- loss: the mean over the T - 1 scored positions of ``CE(W_head RMSNorm_f(h)
  [i], token i + 1)``, the head untied.

Departures from the published model, each an ``assumed`` line of the
configuration too: the config gives every number; the forms are PowerInfer's
``modeling_smallthinker.py`` and the SmallThinker report as known without a
network (the router's input being the un-normed stream, the softmax over the
chosen logits, the window counting the query's own position); "primary"
experts are the only routed experts (the report's neuron-level sparsity
inside an expert is ``relu``'s zeros and adds no leaf); this chip holds a
share of the 64 experts and of the vocabulary; no router auxiliary loss; no
dropout, no clipping; AdamW with a linear warm-up.

So that it fits the chip the program has just left: every layer under
recomputation; attention a block of queries at a time with the mask written
out per block (all T keys: nothing of the window's band is skipped); the held
experts in a plain loop (every expert over every row, times the gate, which
is 0 where the row did not choose it); the cross-entropy in blocks. That
changes no arithmetic.

``precision`` below float32 is the control: every matmul operand of the
projections, the experts, attention and the head is rounded to that type in
the forward pass and the arithmetic stays float32. The router stays float32,
as the configuration states it for the program too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gcn import quantiser
from benchmark.reference.lfm2 import hashable, layer_runs
from benchmark.reference.looplm import (
    EPS_ADAM, cross_entropy, rms_norm, rotary)

QUERY_BLOCK = 256  # [H, block, T] float32 scores at a time


def masked_attention(q, k, v, window: int, qz):
    """softmax(q k^T / sqrt(D) + mask) v, a block of queries at a time; q
    ``[T, H, D]`` on k, v ``[T, Hkv, D]``; key s is seen from query t iff
    ``s <= t`` and (``window`` > 0) ``t - s < window``."""
    T, H, D = q.shape
    Hkv = k.shape[1]
    block = min(QUERY_BLOCK, T)
    while T % block:
        block //= 2
    kq, vq = qz(k), qz(v)
    keys = jnp.arange(T)[None, :]

    @jax.checkpoint
    def one(args):
        qb, start = args
        rows = (start + jnp.arange(block))[:, None]
        seen = keys <= rows
        if window:
            seen = seen & (rows - keys < window)
        qg = qz(qb).reshape(block, Hkv, H // Hkv, D)
        s = jnp.einsum("tkgd,skd->kgts", qg, kq) / np.sqrt(D)
        a = qz(jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1))
        return jnp.einsum("kgts,skd->tkgd", a, vq).reshape(block, H, D)

    out = jax.lax.map(one, (q.reshape(T // block, block, H, D),
                            jnp.arange(0, T, block)))
    return out.reshape(T, H, D)


def attention(p, x, positions, windowed: bool, size, qz):
    """``W_o Attn(x)``: windowed with rotary positions, or full without."""
    H, Hkv, D = (size["num_attention_heads"], size["num_key_value_heads"],
                 size["head_dim"])
    T = x.shape[0]
    mm = lambda a, w: qz(a) @ qz(w["kernel"])
    q = mm(x, p["q_proj"]).reshape(T, H, D)
    k = mm(x, p["k_proj"]).reshape(T, Hkv, D)
    v = mm(x, p["v_proj"]).reshape(T, Hkv, D)
    if windowed:
        q, k = (rotary(t, positions, size["rope_theta"]) for t in (q, k))
    a = masked_attention(
        q, k, v, size["sliding_window_size"] if windowed else 0, qz)
    return mm(a.reshape(T, H * D), p["o_proj"])


def route(x, router, k: int):
    """(gates [T, k], experts [T, k]) over ALL experts, float32: the k
    largest logits, a softmax over those k."""
    top, experts = jax.lax.top_k(x @ router, k)
    return jax.nn.softmax(top, -1), experts


def held_experts(u, gates, experts, p, first: int, qz):
    """sum over the held experts e of gate_e * W_down,e (relu(W_gate,e u) *
    W_up,e u): every held expert over every row, a plain loop; gate_e is 0
    where the row did not choose e."""
    uq = qz(u)

    @jax.checkpoint
    def one(acc, args):
        e, wg, wu, wd = args
        gate = jnp.where(experts == first + e, gates, 0.0).sum(-1)
        mid = jax.nn.relu(uq @ qz(wg)) * (uq @ qz(wu))
        return acc + gate[:, None] * (qz(mid) @ qz(wd)), None

    n = p["gate_proj"]["kernel"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(n), p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
        p["down_proj"]["kernel"]))
    return out


def layer(windowed: bool, p, x, positions, size, qz):
    """One decoder layer; ``p``: this layer's leaves (no leading axis).
    Returns (y, the experts each row chose). The router reads ``x``, the
    layer's input, before the norm and before attention."""
    eps, e = size["rms_norm_eps"], p["experts"]
    gates, experts = route(x, e["router"]["kernel"],
                           size["moe_num_active_primary_experts"])
    h = x + attention(p, rms_norm(p["norm_attn_in"]["scale"], x, eps),
                      positions, windowed, size, qz)
    u = rms_norm(p["norm_mlp_in"]["scale"], h, eps)
    return h + held_experts(u, gates, experts, e, size["first_expert"],
                            qz), experts


def hidden_states(params, tokens, size, qz):
    """(the final norm's output [T, d], the experts every row chose in each
    layer [layers, T, k])."""
    p = params["params"]
    positions = jnp.arange(tokens.shape[0])
    h = p["embed"]["embedding"][tokens]
    chosen = []
    # the layouts' value a layer: 1 windowed with positions, 0 full without
    for i, (flag, n) in enumerate(layer_runs(size["layout"])):
        run, windowed = p["stack"][f"layers_{i}"], bool(flag)
        for j in range(n):
            lp = jax.tree.map(lambda a: a[j], run)
            h, c = jax.checkpoint(
                lambda lp, h, windowed=windowed: layer(
                    windowed, lp, h, positions, size, qz))(lp, h)
            chosen.append(c)
    return rms_norm(p["stack"]["norm_f"]["scale"], h,
                    size["rms_norm_eps"]), jnp.stack(chosen)


def logits(params, h):
    """The untied head, float32 (the tests')."""
    return h @ params["params"]["head"]["kernel"]


def loss_fn(params, tokens, size, qz):
    h, chosen = hidden_states(params, tokens, size, qz)
    targets = jnp.concatenate([tokens[1:], tokens[:1]])  # the last: unscored
    ce = cross_entropy(params["params"]["head"]["kernel"], h[None], targets,
                       qz)[0]
    return ce[:-1].mean(), chosen


@functools.lru_cache(maxsize=None)
def program(size_items: tuple, precision: str):
    """(the jitted gradient, the jitted AdamW update), made once per setting.
    The update works in place (its inputs are donated), leaf by leaf."""
    size = dict(size_items)
    qz = quantiser(precision)
    b1, b2, wd = size["beta1"], size["beta2"], size["weight_decay"]

    grad = jax.jit(jax.value_and_grad(
        lambda p, tokens: loss_fn(p, tokens, size, qz), has_aux=True))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, mu, nu, g, t, lr):
        mu = jax.tree.map(lambda m, a: b1 * m + (1 - b1) * a, mu, g)
        nu = jax.tree.map(lambda v, a: b2 * v + (1 - b2) * a * a, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(
            lambda a, m, v: a - lr * (
                (m / c1) / (jnp.sqrt(v / c2) + EPS_ADAM) + wd * a), p, mu, nu)
        return p, mu, nu

    return grad, update


def follow(params0, batches, size, precision="float32") -> dict:
    """One AdamW step per token batch from ``params0`` (host arrays): each
    step's loss, the first gradient (host arrays) and its norm per leaf, the
    total update's norm per leaf, and the experts every row chose in the
    first step (``chosen`` [layers, T, k], host)."""
    from benchmark.weights import leaf_norms

    grad_fn, update = program(hashable(size), precision)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(jnp.array, params0)  # copies: the update donates
        mu = jax.tree.map(jnp.zeros_like, p)
        nu = jax.tree.map(jnp.zeros_like, p)
        out = {"loss": []}
        for k, tokens in enumerate(batches):
            (loss, chosen), g = grad_fn(p, jnp.asarray(tokens))
            out["loss"].append(float(loss))
            if k == 0:
                out["grad_norm"] = leaf_norms(g)
                out["grad"] = jax.device_get(g)  # off the device: 4 B a weight
                out["chosen"] = np.asarray(chosen)
            lr = size["learning_rate"] * min(1.0, (k + 1) / size["warmup_steps"])
            p, mu, nu = update(p, mu, nu, g, float(k + 1), lr)
            del g
        out["delta_norm"] = leaf_norms(p, params0)
    return out
