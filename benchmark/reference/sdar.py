"""Plain reference for the ``sdar_30b_a3b`` configuration: a sparse-expert
decoder trained by block diffusion, this chip's share of it.

Float32 ``jax.numpy`` at matmul precision ``highest``; no kernel, no flax,
nothing of ``dgraph_tpu`` imported. It takes the weights the benchmark made
from the seed (a nested dict under the program's names: the layers' leaves
stacked on a leading axis, the held experts on a second) and the same
batches ``(tokens, masked, weight)``, and follows AdamW for a few steps.

Equations (SDAR-30B-A3B-Chat, a Qwen3-MoE decoder; d hidden, H query heads on
Hkv KV heads of D, E experts of width F of which ``num_experts`` are held
here, k a token):

- layer (pre-norm): ``h <- h + Attn(RMSNorm(h))``, ``h <- h + MoE(RMSNorm(h))``;
- ``Attn``: q, k, v projections as ``[T, H or Hkv, D]``; RMSNorm over each
  head's D with a learned gain on q and on k; rotary embedding (rotate-half)
  at the row's position IN THE SEQUENCE (row i of the noised copy and row i of
  the clean copy both carry position i); query head j reads KV head
  ``j // (H / Hkv)``; softmax(q k^T / sqrt(D) + mask) v; output projection;
- ``MoE``: ``p = softmax(W_r x)`` over all E; the k largest; gates
  ``g_e = p_e / sum over the k chosen``; the result is the sum over the chosen
  experts THAT ARE HELD HERE (ids ``first_expert ...``) of
  ``g_e W_down,e (silu(W_gate,e x) * W_up,e x)``: what the absent experts
  would add is left out, and that partial sum goes on;
- the mask over the 2L rows ``[xt ; x0]`` (blocks of ``block_length``): row q
  may attend row k iff both are in xt and in the same block, or q is in xt, k
  in x0 and block(k) < block(q), or both are in x0 and block(k) <= block(q);
- loss ``= (1 / L) sum_i masked_i weight_i CE(W_head RMSNorm_f(h)[xt row i],
  x0_i)``, no shift, ``weight_i = 1 / t_b`` of the token's block.

Departures from the published model, each an ``assumed`` line of the
configuration too: the per-head q/k norms, the block length, the objective,
the noise schedule and the mask token's id are not in the ``config.json``;
this chip holds 16 of the 128 experts and an eighth of the vocabulary; no
router auxiliary loss; no dropout, no clipping; AdamW with a linear warm-up.

So that it fits the chip the program has just left: attention runs in blocks
of queries with the mask written out per block, the held experts in a plain
loop (every expert over every row, times the gate, which is 0 where the row
did not choose it), the cross-entropy in blocks, the layers under
``lax.scan`` with recomputation.

``precision`` below float32 is the control: every matmul operand of the
projections, the attention and the experts is rounded to that type in the
forward pass and the arithmetic stays float32. The router stays float32,
as the configuration states it for the program too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gcn import quantiser
from benchmark.reference.looplm import EPS_ADAM, rms_norm, rotary

QUERY_BLOCK = 256  # [H, block, 2L] float32 scores at a time
LOSS_BLOCK = 1024  # [block, vocab] float32 logits at a time


def allowed_pairs(q_rows, k_rows, L: int, block: int):
    """The block-diffusion mask, written out: ``[len(q_rows), len(k_rows)]``
    bool for rows of ``[xt ; x0]``."""
    q_clean, k_clean = q_rows >= L, k_rows >= L
    qb = jnp.where(q_clean, q_rows - L, q_rows) // block
    kb = jnp.where(k_clean, k_rows - L, k_rows) // block
    qc, kc = q_clean[:, None], k_clean[None, :]
    qb, kb = qb[:, None], kb[None, :]
    return (~qc & ~kc & (qb == kb)) | (~qc & kc & (kb < qb)) \
        | (qc & kc & (kb <= qb))


def masked_attention(q, k, v, L, block, qz):
    """softmax(q k^T / sqrt(D) + mask) v, a block of queries at a time;
    q [T, H, D] on k, v [T, Hkv, D], T = 2L."""
    T, H, D = q.shape
    Hkv = k.shape[1]
    qb_rows = min(QUERY_BLOCK, T)
    while T % qb_rows:
        qb_rows //= 2
    kq, vq = qz(k), qz(v)
    cols = jnp.arange(T)

    @jax.checkpoint
    def one(args):
        qb, start = args
        rows = start + jnp.arange(qb_rows)
        qg = qz(qb).reshape(qb_rows, Hkv, H // Hkv, D)
        s = jnp.einsum("tkgd,skd->kgts", qg, kq) / np.sqrt(D)
        s = jnp.where(allowed_pairs(rows, cols, L, block)[None, None], s,
                      -jnp.inf)
        o = jnp.einsum("kgts,skd->tkgd", qz(jax.nn.softmax(s, -1)), vq)
        return o.reshape(qb_rows, H, D)

    out = jax.lax.map(one, (q.reshape(T // qb_rows, qb_rows, H, D),
                            jnp.arange(0, T, qb_rows)))
    return out.reshape(T, H, D)


def route(x, router, k: int, norm: bool):
    """(gates [T, k], experts [T, k]) over ALL experts, float32."""
    p = jax.nn.softmax(x @ router, -1)
    gates, experts = jax.lax.top_k(p, k)
    if norm:
        gates = gates / gates.sum(-1, keepdims=True)
    return gates, experts


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


def layer(p, h, positions, L, size, qz):
    """One decoder layer; ``p``: this layer's leaves (no leading axis).
    Returns (h, the experts each row chose)."""
    H, Hkv, D = (size["num_attention_heads"], size["num_key_value_heads"],
                 size["head_dim"])
    eps, theta = size["rms_norm_eps"], size["rope_theta"]
    T = h.shape[0]
    mm = lambda x, w: qz(x) @ qz(w["kernel"])
    x = rms_norm(p["norm_attn_in"]["scale"], h, eps)
    q = rms_norm(p["q_norm"]["scale"], mm(x, p["q_proj"]).reshape(T, H, D), eps)
    k = rms_norm(p["k_norm"]["scale"], mm(x, p["k_proj"]).reshape(T, Hkv, D), eps)
    v = mm(x, p["v_proj"]).reshape(T, Hkv, D)
    q, k = rotary(q, positions, theta), rotary(k, positions, theta)
    a = masked_attention(q, k, v, L, size["block_length"], qz)
    h = h + mm(a.reshape(T, H * D), p["o_proj"])
    u = rms_norm(p["norm_mlp_in"]["scale"], h, eps)
    gates, experts = route(u, p["experts"]["router"]["kernel"],
                           size["num_experts_per_tok"], size["norm_topk_prob"])
    h = h + held_experts(u, gates, experts, p["experts"],
                         size["first_expert"], qz)
    return h, experts


def hidden_states(params, rows, L, size, qz):
    """(the final norm's output [2L, d], chosen experts [layers, 2L, k])."""
    p = params["params"]
    positions = jnp.tile(jnp.arange(L), 2)

    @jax.checkpoint
    def apply_layer(h, lp):
        return layer(lp, h, positions, L, size, qz)

    h, chosen = jax.lax.scan(apply_layer, p["embed"]["embedding"][rows],
                             p["stack"]["layers"])
    return rms_norm(p["stack"]["norm_f"]["scale"], h,
                    size["rms_norm_eps"]), chosen


def cross_entropy(head, h, targets, qz):
    """-log softmax(W_head h)[target] per position, in blocks."""
    T, d = h.shape
    block = min(LOSS_BLOCK, T)
    while T % block:
        block //= 2
    wq = qz(head)

    @jax.checkpoint
    def one(args):
        hb, tgt = args
        logits = qz(hb) @ wq
        return jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, tgt[:, None], -1)[:, 0]

    return jax.lax.map(one, (h.reshape(T // block, block, d),
                             targets.reshape(T // block, block))).reshape(T)


def loss_fn(params, batch, size, qz):
    tokens, masked, weight = batch
    L = tokens.shape[0]
    xt = jnp.where(masked, size["mask_token_id"], tokens)
    h, chosen = hidden_states(params, jnp.concatenate([xt, tokens]), L, size, qz)
    ce = cross_entropy(params["params"]["head"]["kernel"], h[:L], tokens, qz)
    return jnp.where(masked, weight * ce, 0.0).sum() / L, chosen


@functools.lru_cache(maxsize=None)
def program(size_items: tuple, precision: str):
    """(the jitted gradient, the jitted AdamW update), made once per setting.
    The update works in place (its inputs are donated), leaf by leaf."""
    size = dict(size_items)
    qz = quantiser(precision)
    b1, b2, wd = size["beta1"], size["beta2"], size["weight_decay"]

    grad = jax.jit(jax.value_and_grad(
        lambda p, batch: loss_fn(p, batch, size, qz), has_aux=True))

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
    """One AdamW step per batch from ``params0`` (host arrays): each step's
    loss, the first gradient (host arrays) and its norm per leaf, the total
    update's norm per leaf, and the experts every row chose in the first
    step (``chosen`` [layers, 2L, k], host)."""
    from benchmark.weights import leaf_norms

    grad_fn, update = program(tuple(sorted(
        (k, v) for k, v in size.items() if not isinstance(v, (list, dict)))),
        precision)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(jnp.array, params0)  # copies: the update donates
        mu = jax.tree.map(jnp.zeros_like, p)
        nu = jax.tree.map(jnp.zeros_like, p)
        out = {"loss": []}
        for k, batch in enumerate(batches):
            (loss, chosen), g = grad_fn(p, tuple(jnp.asarray(a) for a in batch))
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
