"""Plain reference for the ``ouro_2p6b`` configuration: a looped decoder LM.

Float32 ``jax.numpy`` at matmul precision ``highest``; no kernel, no flax,
nothing of ``dgraph_tpu`` imported. It takes the weights the benchmark made
from the seed (a nested dict under the program's names: the layers' leaves
stacked on a leading axis) and the same token batches, and follows AdamW for a
few steps.

Equations (Ouro, "Scaling Latent Reasoning via Looped Language Models",
ByteDance 2025; d hidden, H heads of D, F intermediate, R = loop_steps):

- layer: ``a = Attn(RMSNorm1(h))``, ``h <- h + RMSNorm2(a)``,
  ``m = W_down(silu(W_gate u) * W_up u)``, ``u = RMSNorm3(h)``,
  ``h <- h + RMSNorm4(m)``;
- ``Attn``: q, k, v projections as ``[T, H, D]``, rotary embedding on q and k
  at the token's position (rotate-half pairs ``(i, i + D/2)``), exact causal
  softmax(``q k^T / sqrt(D)``) v, output projection;
- the loop: ``h_0 = E[tokens]``; ``h_t = RMSNorm_f(Stack(h_{t-1}))`` with the
  same stack every pass; ``logits_t = W_head h_t``;
  ``lambda_t = sigmoid(w_g . h_t + b_g)``;
- exit distribution: ``p_t = lambda_t prod_{j<t}(1 - lambda_j)`` for t < R,
  ``p_R = prod_{j<R}(1 - lambda_j)``;
- loss: mean over the T - 1 scored positions of
  ``sum_t p_t CE(logits_t, next token) - beta H(p)``.

Departures from the published model, each an ``assumed`` line of the
configuration too:
- the config gives the widths, ``total_ut_steps``, eps and theta; the
  placement of the four norms, the final norm at the end of every pass (its
  output fed to the next), the gate's form, the loss with its beta, and the
  absence of biases are from the paper and ``modeling_ouro.py`` as known here;
- one packed stream without a document mask; synthetic token ids;
- no dropout, no gradient clipping; AdamW with a linear warm-up of the
  learning rate (step k of the run takes (k + 1) / warmup_steps of the peak).

So that it fits the chip the program has just left: attention runs in blocks
of queries, the cross-entropy in blocks of positions, and the passes and the
layer applications under ``lax.scan`` with recomputation.

``precision`` below float32 is the control: every matmul operand (weights,
activations, q, k, v, attention weights) is rounded to that type in the
forward pass and the arithmetic stays float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gcn import quantiser

EPS_ADAM = 1e-8
QUERY_BLOCK = 512  # [H, block, T] float32 scores at a time
LOSS_BLOCK = 1024  # [block, vocab] float32 logits at a time


def rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary(x, positions, theta):
    """x [T, H, D]; pairs (i, i + D/2) rotate by position * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def causal_attention(q, k, v, qz):
    """softmax(q k^T / sqrt(D)) v with a causal mask, a block of queries at a
    time; q, k, v [T, H, D]."""
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
    return out.reshape(T, H, D)


def layer(p, h, positions, size, qz):
    """One decoder layer; ``p``: this layer's leaves (no leading axis)."""
    H, D, eps = size["num_attention_heads"], size["head_dim"], size["rms_norm_eps"]
    Hkv = size["num_key_value_heads"]
    T = h.shape[0]
    mm = lambda x, w: qz(x) @ qz(w["kernel"])
    x = rms_norm(p["norm_attn_in"]["scale"], h, eps)
    q = rotary(mm(x, p["q_proj"]).reshape(T, H, D), positions, size["rope_theta"])
    k = rotary(mm(x, p["k_proj"]).reshape(T, Hkv, D), positions, size["rope_theta"])
    v = mm(x, p["v_proj"]).reshape(T, Hkv, D)
    if Hkv != H:
        k, v = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))
    a = mm(causal_attention(q, k, v, qz).reshape(T, H * D), p["o_proj"])
    h = h + rms_norm(p["norm_attn_out"]["scale"], a, eps)
    u = rms_norm(p["norm_mlp_in"]["scale"], h, eps)
    m = mm(jax.nn.silu(mm(u, p["gate_proj"])) * mm(u, p["up_proj"]),
           p["down_proj"])
    return h + rms_norm(p["norm_mlp_out"]["scale"], m, eps)


def hidden_states(params, tokens, size, qz):
    """The exit state of every pass, [R, T, d]."""
    p = params["params"]
    positions = jnp.arange(tokens.shape[0])
    stack = p["stack"]

    @jax.checkpoint
    def apply_layer(h, lp):
        return layer(lp, h, positions, size, qz), None

    @jax.checkpoint  # a pass keeps only its input; its layers are redone
    def one_pass(h, _):
        h, _ = jax.lax.scan(apply_layer, h, stack["layers"])
        h = rms_norm(stack["norm_f"]["scale"], h, size["rms_norm_eps"])
        return h, h

    h0 = p["embed"]["embedding"][tokens]
    _, hs = jax.lax.scan(one_pass, h0, None, length=size["total_ut_steps"])
    return hs


def cross_entropy(head, hs, targets, qz):
    """ce [R, T]: -log softmax(W_head h)[target], a block of positions at a
    time."""
    R, T, d = hs.shape
    block = min(LOSS_BLOCK, T)
    while T % block:
        block //= 2
    wq = qz(head)

    @jax.checkpoint
    def one(args):
        h, tgt = args
        logits = qz(h) @ wq
        return jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, tgt[:, None], -1)[:, 0]

    nb = T // block
    ce = jax.lax.map(one, (hs.reshape(R * nb, block, d),
                           jnp.tile(targets.reshape(nb, block), (R, 1))))
    return ce.reshape(R, T)


def exit_probabilities(lam):
    """p [R, T] from the gates of the first R - 1 passes, lam [R - 1, T]."""
    stay = jnp.cumprod(1.0 - lam, axis=0)  # prod_{j<=t}
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]], 0)
    return jnp.concatenate([lam * before, stay[-1:]], 0)


def loss_fn(params, tokens, size, qz):
    p = params["params"]
    hs = hidden_states(params, tokens, size, qz)
    targets = jnp.concatenate([tokens[1:], tokens[:1]])  # the last: unscored
    if size["total_ut_steps"] > 1 and size["exit_gate"]:
        ce = cross_entropy(p["head"]["kernel"], hs, targets, qz)
        lam = jax.nn.sigmoid(
            (qz(hs[:-1]) @ qz(p["gate"]["kernel"]))[..., 0] + p["gate"]["bias"])
        prob = exit_probabilities(lam)
        entropy = -(prob * jnp.log(jnp.maximum(prob, 1e-37))).sum(0)
        per_pos = (prob * ce).sum(0) - size["exit_beta"] * entropy
    else:
        per_pos = cross_entropy(p["head"]["kernel"], hs[-1:], targets, qz)[0]
    return per_pos[:-1].mean()


@functools.lru_cache(maxsize=None)
def program(size_items: tuple, precision: str):
    """(the jitted gradient, the jitted AdamW update), made once per setting.
    The update works in place (its inputs are donated), leaf by leaf."""
    size = dict(size_items)
    qz = quantiser(precision)
    b1, b2, wd = size["beta1"], size["beta2"], size["weight_decay"]

    grad = jax.jit(jax.value_and_grad(
        lambda p, tokens: loss_fn(p, tokens, size, qz)))

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
    total update's norm per leaf."""
    from benchmark.weights import leaf_norms

    grad_fn, update = program(tuple(sorted(
        (k, v) for k, v in size.items() if not isinstance(v, (list, dict)))),
        precision)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(jnp.array, params0)  # copies: the update donates
        mu = jax.tree.map(jnp.zeros_like, p)
        nu = jax.tree.map(jnp.zeros_like, p)
        out = {"loss": []}
        for k, tokens in enumerate(batches):
            loss, g = grad_fn(p, jnp.asarray(tokens))
            out["loss"].append(float(loss))
            if k == 0:
                out["grad_norm"] = leaf_norms(g)
                out["grad"] = jax.device_get(g)  # off the device: 4 B a weight
            lr = size["learning_rate"] * min(1.0, (k + 1) / size["warmup_steps"])
            p, mu, nu = update(p, mu, nu, g, float(k + 1), lr)
            del g
        out["delta_norm"] = leaf_norms(p, params0)
    return out
