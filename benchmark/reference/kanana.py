"""Plain reference for the ``kanana2_30b_a3b`` configuration: a sparse-expert
decoder that attends through a LATENT with a decoupled rotary key (multi-head
latent attention; ``model_type`` deepseek_v3; kakaocorp
kanana-2-30b-a3b-instruct-2601): one leading dense layer, then expert layers
of sigmoid-routed SwiGLU experts beside one shared expert; this chip's share
of its first five layers.

Float32 ``jax.numpy`` at matmul precision ``highest``; no kernel, no flax,
nothing of ``dgraph_tpu`` imported. It takes the weights the benchmark made
from the seed (a nested dict under the program's names: each run of equal
layers, ``layers_<run>``, with its leaves stacked on a leading axis, the held
experts on a second) and the same token batches, and follows AdamW for a few
steps.

Equations (d hidden; H heads; a q.k head is Dn dimensions without positions
and Dr rotated ones, a value head Dv; the latent R wide; F the dense width; E
experts of width Fe of which ``n_routed_experts`` are held here, k a token; a
shared expert of width Fs; no bias anywhere; RMSNorm with a learned gain and
eps 1e-6, pre-norm only):

- attention on x: ``x^ = RMSNorm_1(x)``; ``q = W_q x^`` as ``[T, H, Dn +
  Dr]``, each head ``[q_nope ; q_rope]`` (``q_lora_rank`` null: no query
  latent); ``[c ; k_r] = W_kva x^`` of sizes ``R | Dr``; ``c~ = RMSNorm_kv(c)``
  with a gain of R; ``[k_nope,h ; v_h] = W_kvb c~`` as ``[T, H, Dn + Dv]``;
  the rotary embedding at the token's position, theta ``rope_theta``, over
  the Dr dimensions of ``q_rope,h`` and of ``k_r``, pairs ``(2i, 2i + 1)``
  (``rope_interleave``); ``k_r`` is ONE head, read by all H: ``k_h =
  [k_nope,h ; k_r]``; ``a_h = softmax(q_h k_h^T / sqrt(Dn + Dr)) v_h`` over
  the causal prefix; ``h = x + W_o [a_1 ... a_H]`` (``H Dv -> d``);
- the leading dense layer: ``y = h + W_down(silu(W_gate u) * W_up u)``, ``u =
  RMSNorm_2(h)``, width F;
- an expert layer: ``s = sigmoid(W_r u)`` over all E, float32; the k largest
  of ``s + b`` are chosen (``b``: the selection bias, which takes no gradient
  and no update; ``n_group`` = ``topk_group`` = 1: no group limit); gates
  ``g_e = scale * s_e / (sum of the chosen s + 1e-20)``; ``y = h + (sum over
  the chosen experts THAT ARE HELD HERE, ids first_expert ..., of g_e
  W_down,e (silu(W_gate,e u) * W_up,e u)) + W_down,s (silu(W_gate,s u) *
  W_up,s u)``: what the absent experts would add is left out, the shared
  expert (the published two, one MLP of their summed width) is whole, and
  that sum goes on;
- loss: the mean over the T - 1 scored positions of ``CE(W_head RMSNorm_f(h)
  [i], token i + 1)``, the head untied.

Departures from the published model, each an ``assumed`` line of the
configuration too: the config gives every number, the forms are HF's
``modeling_deepseek_v3.py`` as known without a network; the selection bias is
held fixed and there is no router auxiliary loss; this chip holds a share of
the 128 experts and of the vocabulary; one packed stream without a document
mask; no dropout, no clipping; AdamW with a linear warm-up; the weights'
law.

So that it fits the chip the program has just left: every layer under
recomputation; attention a block of queries at a time over a ``[H, block,
T]`` score; the held experts in a plain loop (every held expert over every
row, times the gate, which is 0 where the row did not choose it); the
cross-entropy in blocks. That changes no arithmetic.

``precision`` below float32 is the control: every matmul operand of the
projections, the latent's two, the experts, the shared expert, attention and
the head is rounded to that type in the forward pass and the arithmetic stays
float32. The router stays float32, as the configuration states it for the
program too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gcn import quantiser
from benchmark.reference.lfm2 import hashable
from benchmark.reference.looplm import EPS_ADAM, cross_entropy, rms_norm

GATE_EPS = 1e-20  # added to the chosen scores' sum
QUERY_BLOCK = 256  # [H, block, T] float32 scores at a time
FROZEN = ("select_bias",)  # leaves the optimizer leaves alone, by name


def rotary_pairs(x, positions, theta):
    """x ``[T, H, D]``; the pairs ``(2i, 2i + 1)`` rotate by ``position *
    theta^(-2i / D)``: ``x cos + partner(x) sin`` with ``partner(x)[2i] =
    -x[2i + 1]``, ``partner(x)[2i + 1] = x[2i]``, a fixed signed permutation
    written as a ``[D, D]`` matrix of 0 and +-1 (exact at precision
    ``highest``; a reshape of the lanes into ``[D / 2, 2]`` costs a TPU 64
    times the tensor)."""
    D = x.shape[-1]
    pair = jnp.arange(D) // 2
    inv_freq = theta ** (-pair.astype(jnp.float32) * 2.0 / D)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    even = np.arange(0, D, 2)
    swap = np.zeros((D, D), np.float32)
    swap[even + 1, even], swap[even, even + 1] = -1.0, 1.0
    return x * jnp.cos(ang)[:, None, :] + (x @ swap) * jnp.sin(ang)[:, None, :]


def causal_attention(q, k, v, qz):
    """softmax(q k^T / sqrt(Dqk)) v with a causal mask, a block of queries at
    a time; q, k ``[T, H, Dqk]`` on v ``[T, H, Dv]``."""
    T, H, D = q.shape
    block = min(QUERY_BLOCK, T)
    while T % block:
        block //= 2
    kq, vq = qz(k), qz(v)

    @jax.checkpoint
    def one(args):
        qb, start = args
        s = jnp.einsum("thd,shd->hts", qz(qb), kq) / np.sqrt(D)
        rows = start + jnp.arange(block)
        s = jnp.where(jnp.arange(T)[None, None, :] <= rows[None, :, None],
                      s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", qz(jax.nn.softmax(s, -1)), vq)

    out = jax.lax.map(one, (q.reshape(T // block, block, H, D),
                            jnp.arange(0, T, block)))
    return out.reshape(T, H, v.shape[-1])


def latent_keys_values(p, x, positions, size, qz):
    """(k ``[T, H, Dn + Dr]``, v ``[T, H, Dv]``) of the normed stream ``x``:
    down to the latent and the one rotary key, the latent's norm, up to a
    head's keys and values, the rotated key beside every head's."""
    H, R = size["num_attention_heads"], size["kv_lora_rank"]
    Dn, Dv = size["qk_nope_head_dim"], size["v_head_dim"]
    T = x.shape[0]
    mm = lambda a, w: qz(a) @ qz(w["kernel"])
    c, k_r = jnp.split(mm(x, p["kv_a_proj"]), [R], axis=-1)
    c = rms_norm(p["kv_a_norm"]["scale"], c, size["rms_norm_eps"])
    k_nope, v = jnp.split(mm(c, p["kv_b_proj"]).reshape(T, H, Dn + Dv), [Dn],
                          axis=-1)
    k_r = rotary_pairs(k_r[:, None, :], positions, size["rope_theta"])
    return jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (T, H, k_r.shape[-1]))], -1), v


def attention(p, x, positions, size, qz):
    """``W_o Attn(x)`` through the latent; ``x`` the normed stream."""
    H, Dn = size["num_attention_heads"], size["qk_nope_head_dim"]
    Dr, Dv = size["qk_rope_head_dim"], size["v_head_dim"]
    T = x.shape[0]
    mm = lambda a, w: qz(a) @ qz(w["kernel"])
    q = mm(x, p["q_proj"]).reshape(T, H, Dn + Dr)
    q = jnp.concatenate([q[..., :Dn], rotary_pairs(
        q[..., Dn:], positions, size["rope_theta"])], -1)
    k, v = latent_keys_values(p, x, positions, size, qz)
    return mm(causal_attention(q, k, v, qz).reshape(T, H * Dv), p["o_proj"])


def swiglu(x, gate, up, down, qz):
    """``W_down (silu(W_gate x) * W_up x)`` on every row."""
    xq = qz(x)
    return qz(jax.nn.silu(xq @ qz(gate)) * (xq @ qz(up))) @ qz(down)


def route(u, router, bias, k: int, scale: float):
    """(gates [T, k], experts [T, k]) over ALL experts, float32: sigmoid
    scores, the choice by ``score + bias``, the gates the scores alone."""
    s = jax.nn.sigmoid(u @ router)
    _, experts = jax.lax.top_k(s + bias, k)
    gates = jnp.take_along_axis(s, experts, -1)
    return scale * gates / (gates.sum(-1, keepdims=True) + GATE_EPS), experts


def held_experts(u, gates, experts, p, first: int, qz):
    """sum over the held experts e of gate_e * W_down,e (silu(W_gate,e u) *
    W_up,e u): every held expert over every row, a plain loop; gate_e is 0
    where the row did not choose e."""

    @jax.checkpoint
    def one(acc, args):
        e, wg, wu, wd = args
        gate = jnp.where(experts == first + e, gates, 0.0).sum(-1)
        return acc + gate[:, None] * swiglu(u, wg, wu, wd, qz), None

    n = p["gate_proj"]["kernel"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(n), p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
        p["down_proj"]["kernel"]))
    return out


def shared_expert(u, p, qz):
    return swiglu(u, p["shared_gate_proj"]["kernel"],
                  p["shared_up_proj"]["kernel"],
                  p["shared_down_proj"]["kernel"], qz)


def expert_layer(p, u, size, qz):
    """(the held experts' part + the shared expert, the experts each row
    chose); ``p``: the ``experts`` leaves."""
    gates, experts = route(u, p["router"]["kernel"], p["select_bias"],
                           size["num_experts_per_tok"],
                           size["routed_scaling_factor"])
    return held_experts(u, gates, experts, p, size["first_expert"], qz) \
        + shared_expert(u, p, qz), experts


def layer(p, x, positions, size, qz):
    """One decoder layer; ``p``: this layer's leaves (no leading axis): an
    expert layer where it has ``experts``, else the dense one. Returns (y,
    the experts each row chose, or None). Each half is recomputed on its own
    in the backward pass, so that the two halves' float32 intermediates are
    never live together."""
    eps = size["rms_norm_eps"]

    @jax.checkpoint
    def mixer(p, x):
        return x + attention(p, rms_norm(p["norm_attn_in"]["scale"], x, eps),
                             positions, size, qz)

    @jax.checkpoint
    def ffn(p, h):
        u = rms_norm(p["norm_mlp_in"]["scale"], h, eps)
        if "experts" in p:
            m, chosen = expert_layer(p["experts"], u, size, qz)
            return h + m, chosen
        return h + swiglu(u, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                          p["down_proj"]["kernel"], qz), None

    return ffn(p, mixer(p, x))


def hidden_states(params, tokens, size, qz):
    """(the final norm's output [T, d], the experts every row chose in each
    expert layer [expert layers, T, k]): the run of ``first_k_dense_replace``
    dense layers (``layers_0``), then the run of expert layers
    (``layers_1``), each a scan over its stacked leaves (a Python loop over
    slices of them would hold a zero-padded copy of the run's whole gradient
    a layer)."""
    p = params["params"]
    positions = jnp.arange(tokens.shape[0])
    h = p["embed"]["embedding"][tokens]
    chosen = None
    for run in ("layers_0", "layers_1"):
        h, c = jax.lax.scan(
            lambda h, lp: layer(lp, h, positions, size, qz), h,
            p["stack"][run])
        chosen = c if c is not None else chosen
    return rms_norm(p["stack"]["norm_f"]["scale"], h,
                    size["rms_norm_eps"]), chosen


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
