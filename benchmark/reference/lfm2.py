"""Plain reference for the ``lfm2_8b_a1b`` configuration: a decoder of gated
short convolutions among attention layers, with sigmoid-routed sparse experts
after a leading dense layer; this chip's share of it.

Float32 ``jax.numpy`` at matmul precision ``highest``; no kernel, no flax,
nothing of ``dgraph_tpu`` imported. It takes the weights the benchmark made
from the seed (a nested dict under the program's names: each run of equal
layers, ``layers_<run>``, with its leaves stacked on a leading axis, the held
experts on a second) and the same token batches, and follows AdamW for a few
steps.

Equations (LFM2-8B-A1B, ``model_type`` lfm2_moe; d hidden, H query heads on
Hkv KV heads of D = d / H, K the convolution's kernel, E experts of width F of
which ``num_experts`` are held here, k a token):

- every layer (pre-norm): ``h <- h + Op(RMSNorm(h))``,
  ``h <- h + FFN(RMSNorm(h))``; one RMSNorm before the head; the head is the
  embedding transposed;
- ``Op``, conv layer: ``(B, C, x~) = split3(W_in x)``; ``y = B * x~``;
  ``z_t = sum_{j<K} w_j * y_{t-(K-1)+j}``, the terms before the sequence's
  start zero (written below as K shifted copies); ``Op = W_out (C * z)``;
- ``Op``, attention layer: q, k, v projections as ``[T, H or Hkv, D]``, RMSNorm
  over each head's D with a learned gain on q and on k, rotary embedding
  (rotate-half) at the token's position, query head j reads KV head
  ``j // (H / Hkv)`` (K and V repeated), exact causal softmax, ``W_o``;
- ``FFN``, dense layer: ``W_down(silu(W_gate u) * W_up u)``;
- ``FFN``, expert layer: ``s = sigmoid(W_r u)`` over all E; the k largest of
  ``s + b`` are chosen (``b``: the selection bias, which takes no gradient and
  no update); gates ``g_e = scale * s_e / (sum of the chosen s + 1e-6)``; the
  result is the sum over the chosen experts THAT ARE HELD HERE (ids
  ``first_expert ...``) of ``g_e W_down,e (silu(W_gate,e u) * W_up,e u)``:
  what the absent experts would add is left out, and that partial sum goes on;
- loss: the mean over the T - 1 scored positions of
  ``CE(E RMSNorm_f(h)[i], token i + 1)``.

Departures from the published model, each an ``assumed`` line of the
configuration too: the layer equations are LiquidAI's released modelling code
as known without a network (the config gives widths, kinds, counts, eps,
theta, ``norm_topk_prob``, ``use_expert_bias``, ``routed_scaling_factor``);
the selection bias is held fixed; no router auxiliary loss; this chip holds 8
of the 32 experts and a quarter of the vocabulary; no dropout, no clipping;
AdamW with a linear warm-up.

So that it fits the chip the program has just left: attention runs in blocks
of queries, the held experts in a plain loop (every expert over every row,
times the gate, which is 0 where the row did not choose it), the
cross-entropy in blocks, every layer under recomputation.

``precision`` below float32 is the control: every matmul operand of the
projections, the attention, the experts and the head is rounded to that type
in the forward pass and the arithmetic stays float32. The router and the
convolution's gates and taps stay float32, as the configuration states them
for the program too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gcn import quantiser
from benchmark.reference.looplm import (
    EPS_ADAM, causal_attention, cross_entropy, rms_norm, rotary)

GATE_EPS = 1e-6  # added to the chosen scores' sum
FROZEN = ("select_bias",)  # leaves the optimizer leaves alone, by name


def short_conv(y, taps):
    """``z_t = sum_j taps[j] * y_{t-(K-1)+j}``: K shifted copies of ``y``
    ``[T, d]``, zeros shifted in at the sequence's start; ``taps`` ``[K, d]``."""
    K, T = taps.shape[0], y.shape[0]
    z = jnp.zeros_like(y)
    for j in range(K):
        back = K - 1 - j  # tap j reads the row `back` steps before
        shifted = y if back == 0 else jnp.concatenate(
            [jnp.zeros((back, y.shape[1]), y.dtype), y[:T - back]])
        z = z + taps[j] * shifted
    return z


def conv_operator(p, x, qz):
    """``W_out (C * conv(B * x~))``; ``p``: in_proj, conv, out_proj."""
    b, c, xt = jnp.split(qz(x) @ qz(p["in_proj"]["kernel"]), 3, axis=-1)
    z = short_conv(b * xt, p["conv"]["kernel"])
    return qz(c * z) @ qz(p["out_proj"]["kernel"])


def attention_operator(p, x, positions, size, qz):
    H, Hkv, D = (size["num_attention_heads"], size["num_key_value_heads"],
                 size["head_dim"])
    eps, theta = size["norm_eps"], size["rope_theta"]
    T = x.shape[0]
    mm = lambda a, w: qz(a) @ qz(w["kernel"])
    q = rms_norm(p["q_norm"]["scale"], mm(x, p["q_proj"]).reshape(T, H, D), eps)
    k = rms_norm(p["k_norm"]["scale"], mm(x, p["k_proj"]).reshape(T, Hkv, D), eps)
    v = mm(x, p["v_proj"]).reshape(T, Hkv, D)
    q, k = rotary(q, positions, theta), rotary(k, positions, theta)
    k, v = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))
    return mm(causal_attention(q, k, v, qz).reshape(T, H * D), p["o_proj"])


def route(u, router, bias, k: int, scale: float):
    """(gates [T, k], experts [T, k]) over ALL experts, float32: sigmoid
    scores, the choice by ``score + bias``, the gates the scores alone."""
    s = jax.nn.sigmoid(u @ router)
    _, experts = jax.lax.top_k(s + bias, k)
    gates = jnp.take_along_axis(s, experts, -1)
    return scale * gates / (gates.sum(-1, keepdims=True) + GATE_EPS), experts


def held_experts(x, gates, experts, p, first: int, qz):
    """sum over the held experts e of gate_e(x) * FFN_e(x): every held expert
    over every row, a plain loop; gate_e is 0 where the row did not choose e."""
    xq = qz(x)

    @jax.checkpoint
    def one(acc, args):
        e, wg, wu, wd = args
        gate = jnp.where(experts == first + e, gates, 0.0).sum(-1)
        hmid = jax.nn.silu(xq @ qz(wg)) * (xq @ qz(wu))
        return acc + gate[:, None] * (qz(hmid) @ qz(wd)), None

    n = p["gate_proj"]["kernel"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(n), p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
        p["down_proj"]["kernel"]))
    return out


def layer(kind: str, p, h, positions, size, qz):
    """One decoder layer of ``kind`` (``"<conv|attn>+<dense|experts>"``);
    ``p``: this layer's leaves (no leading axis). Returns (h, the experts
    each row chose, or None for a dense FFN)."""
    mixer, ffn = kind.split("+")
    eps = size["norm_eps"]
    if mixer == "conv":
        h = h + conv_operator(
            p["conv"], rms_norm(p["norm_conv_in"]["scale"], h, eps), qz)
    else:
        h = h + attention_operator(
            p, rms_norm(p["norm_attn_in"]["scale"], h, eps), positions, size, qz)
    u = rms_norm(p["norm_mlp_in"]["scale"], h, eps)
    if ffn == "dense":
        mm = lambda a, w: qz(a) @ qz(w["kernel"])
        m = mm(jax.nn.silu(mm(u, p["gate_proj"])) * mm(u, p["up_proj"]),
               p["down_proj"])
        return h + m, None
    e = p["experts"]
    gates, experts = route(u, e["router"]["kernel"], e["select_bias"],
                           size["num_experts_per_tok"],
                           size["routed_scaling_factor"])
    return h + held_experts(u, gates, experts, e, size["first_expert"],
                            qz), experts


def layer_runs(pattern) -> list:
    """[(kind, length)] of the runs of consecutive equal kinds."""
    runs = []
    for kind in pattern:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return [tuple(r) for r in runs]


def hidden_states(params, tokens, size, qz):
    """(the final norm's output [T, d], the experts every row chose in each
    expert layer [expert layers, T, k])."""
    p = params["params"]
    positions = jnp.arange(tokens.shape[0])
    h = p["embed"]["embedding"][tokens]
    chosen = []
    for i, (kind, n) in enumerate(layer_runs(size["layer_pattern"])):
        run = p["stack"][f"layers_{i}"]
        for j in range(n):
            lp = jax.tree.map(lambda a: a[j], run)
            h, c = jax.checkpoint(
                lambda lp, h, kind=kind: layer(kind, lp, h, positions, size, qz)
            )(lp, h)
            if c is not None:
                chosen.append(c)
    return rms_norm(p["stack"]["norm_f"]["scale"], h,
                    size["norm_eps"]), jnp.stack(chosen)


def loss_fn(params, tokens, size, qz):
    h, chosen = hidden_states(params, tokens, size, qz)
    targets = jnp.concatenate([tokens[1:], tokens[:1]])  # the last: unscored
    # the tied head: the embedding, transposed
    ce = cross_entropy(params["params"]["embed"]["embedding"].T, h[None],
                       targets, qz)[0]
    return ce[:-1].mean(), chosen


def hashable(size: dict) -> tuple:
    """``size`` as a cache key: lists become tuples, nested groups go."""
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in size.items()
        if not isinstance(v, dict)))


@functools.lru_cache(maxsize=None)
def program(size_items: tuple, precision: str):
    """(the jitted gradient, the jitted AdamW update), made once per setting.
    The update works in place (its inputs are donated), leaf by leaf, and
    leaves the ``FROZEN`` leaves as they are."""
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

        def step(path, a, m, v):
            if path[-1].key in FROZEN:
                return a
            return a - lr * ((m / c1) / (jnp.sqrt(v / c2) + EPS_ADAM) + wd * a)

        return jax.tree_util.tree_map_with_path(step, p, mu, nu), mu, nu

    return grad, update


def follow(params0, batches, size, precision="float32") -> dict:
    """One AdamW step per token batch from ``params0`` (host arrays): each
    step's loss, the first gradient (host arrays) and its norm per leaf, the
    total update's norm per leaf, and the experts every row chose in the
    first step (``chosen`` [expert layers, T, k], host)."""
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
