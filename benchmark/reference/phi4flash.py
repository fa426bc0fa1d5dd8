"""Plain reference for the ``phi4_mini_flash`` configuration: a
decoder-hybrid-decoder (SambaY with differential attention; Ren et al. 2025,
``model_type`` phi4flash): selective state-space layers, differential
attention under a window and full, gated memory units and cross-attention
that read what one earlier layer kept; this chip's six layers of it.

Float32 ``jax.numpy`` at matmul precision ``highest``; no kernel, no flax,
nothing of ``dgraph_tpu`` imported. It takes the weights the benchmark made
from the seed (a nested dict under the program's names: each run of equal
layers, ``layers_<run>``, with its leaves stacked on a leading axis) and the
same token batches, and follows AdamW for a few steps.

Equations (d hidden, H query heads on Hkv KV heads of D = d / H, F the MLP's
width, C = expand d channels of N states, K taps, rank R; l the layer's
PUBLISHED index, ``first_layer`` + its place here):

- every layer: ``h <- h + Mix(LN(h))``, ``h <- h + MLP(LN'(h))``; ``LN`` is
  LayerNorm with gain and bias; one more before the head; the head is the
  embedding transposed; no positional encoding.
  ``MLP(x) = W_2 (silu(g) * y)``, ``(g, y) = split2(W_1 x)``, no bias;
- ``ssm`` (``ssm_keep``): ``(u, z) = split2(W_in x)``; ``u <- silu(conv_K(u)
  + b)`` (depthwise, causal, zeros before the start); ``(r, B_t, C_t) =
  split(W_x u)``; ``Delta_t = softplus(W_dt r_t + b_dt)``; ``A = -exp(A_log)``;
  ``s_t[c, n] = exp(Delta_t[c] A[c, n]) s_{t-1}[c, n] + Delta_t[c] B_t[n]
  u_t[c]`` from ``s_{-1} = 0``; ``y_t[c] = sum_n C_t[n] s_t[c, n] + D[c]
  u_t[c]``; ``Mix = W_out (y * silu(z))``; ``ssm_keep`` keeps ``m = y``;
- ``diff_win`` / ``diff_keep``: ``q, k, v = split(W_qkv x + b)``; the heads
  pair up, adjacent ones: query pair i is ``(q1, q2) = (head 2i, head 2i +
  1)``, KV pair j likewise ``(k1, k2)``, ``(v1, v2)``; query pair i reads KV
  pair ``i // (H / Hkv)``. ``A_a = softmax(q_a k_a^T / sqrt(D) + mask)``;
  ``o = (1 - l0) RMSNorm_2D([A_1 v1 ; A_1 v2] - lam [A_2 v1 ; A_2 v2])``
  (four softmax-times-value products a pair; one gain vector of 2D);
  ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + l0``, ``l0 = 0.8 - 0.6 exp(-0.3
  l)``; ``Mix = W_o concat_i(o_i) + b``. ``diff_win``: key s is seen from
  query t iff ``t - window < s <= t``; ``diff_keep``: iff ``s <= t``, and the
  layer keeps its ``k, v``;
- ``gmu``: ``Mix = W_2 (silu(W_1 x) * m)``, ``m`` the kept one;
- ``cross``: ``q = W_q x + b`` only; differential attention as above, full
  causal, against the kept ``k, v``; its own lambdas, sub-norm and ``W_o``;
- loss: the mean over the T - 1 scored positions of ``CE(E LN_f(h)[i], token
  i + 1)``.

So that it fits the chip beside its own 16 B a parameter: every layer under
recomputation; the scan time step by time step (``lax.scan``) in blocks of
steps under recomputation, its state written ``[N, C]`` (the same numbers;
``[C, 16]`` would pad 16 to the 128 lanes in every saved block); attention a
block of queries at a time; the MLP a block of rows at a time; the
cross-entropy in blocks. That changes no arithmetic.

``precision`` below float32 is the control: every matmul operand of the
projections (the mixers', the MLPs', ``W_x``, ``W_dt``), of attention and of
the head is rounded to that type in the forward pass and the arithmetic stays
float32. The convolution, the step size and the recurrence stay float32, as
the configuration states them for the program too.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gcn import quantiser
from benchmark.reference.lfm2 import hashable, layer_runs, short_conv
from benchmark.reference.looplm import EPS_ADAM, cross_entropy

QUERY_BLOCK = 512  # queries of one [H, block, T] block of logits
ROW_BLOCK = 2048  # rows of one block of the MLP
STEP_BLOCK = 256  # time steps of one recomputed block of the scan
SUBLN_EPS = 1e-5


def layer_norm(p, x, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def divisor(T: int, most: int) -> int:
    block = min(most, T)
    while T % block:
        block //= 2
    return block


def mlp(p, x, qz):
    """``W_2 (silu(g) * y)``, ``(g, y) = split2(W_1 x)``, a block of rows at
    a time."""
    w1, w2 = qz(p["gate_up_proj"]["kernel"]), qz(p["down_proj"]["kernel"])
    block = divisor(x.shape[0], ROW_BLOCK)

    @jax.checkpoint
    def one(xb):
        g, y = jnp.split(qz(xb) @ w1, 2, axis=-1)
        return qz(jax.nn.silu(g) * y) @ w2

    return jax.lax.map(one, x.reshape(-1, block, x.shape[1])).reshape(x.shape)


def selective_scan(u, delta, A, B, Cm, D):
    """``y [T, C]`` of the recurrence, one time step after the other; the
    state is ``[N, C]`` (``A`` comes as ``[C, N]``)."""
    T, C = u.shape
    At = A.T
    block = divisor(T, STEP_BLOCK)

    def step(s, x):
        u_t, d_t, B_t, C_t = x
        s = jnp.exp(d_t[None, :] * At) * s + B_t[:, None] * (d_t * u_t)[None, :]
        return s, (C_t[:, None] * s).sum(0)

    @jax.checkpoint
    def steps(s, xs):
        return jax.lax.scan(step, s, xs)

    blocks = lambda x: x.reshape(T // block, block, x.shape[1])
    _, y = jax.lax.scan(steps, jnp.zeros_like(At),
                        (blocks(u), blocks(delta), blocks(B), blocks(Cm)))
    return y.reshape(T, C) + D * u


def state_space(p, x, size, qz):
    """(``W_out (y * silu(z))``, ``y``); ``p``: the ``ssm`` leaves."""
    N, R = size["d_state"], size["dt_rank"]
    mm = lambda a, w: qz(a) @ qz(w["kernel"])
    u, z = jnp.split(mm(x, p["in_proj"]), 2, axis=-1)
    u = jax.nn.silu(short_conv(u, p["conv"]["kernel"]) + p["conv_bias"])
    r, B, Cm = jnp.split(mm(u, p["x_proj"]), [R, R + N], axis=-1)
    delta = jax.nn.softplus(mm(r, p["dt_proj"]) + p["dt_bias"])
    y = selective_scan(u, delta, -jnp.exp(p["A_log"]), B, Cm, p["D"])
    return mm(y * jax.nn.silu(z), p["out_proj"]), y


def softmax_maps(q, k, vs, window, qz):
    """``[softmax(q k^T / sqrt(D) + mask) v for v in vs]``, a block of queries
    at a time; q, k ``[T, H, D]``, each v ``[T, H, Dv]``; key s is seen from
    query t iff ``s <= t`` and (``window`` given) ``t - s < window``."""
    T, H, D = q.shape
    block = divisor(T, QUERY_BLOCK)
    kq, vq = qz(k), [qz(v) for v in vs]
    keys = jnp.arange(T)[None, None, :]

    @jax.checkpoint
    def one(args):
        qb, start = args
        s = jnp.einsum("thd,shd->hts", qz(qb), kq) / np.sqrt(D)
        rows = (start + jnp.arange(block))[None, :, None]
        seen = keys <= rows
        if window:
            seen = seen & (rows - keys < window)
        a = qz(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1))
        return [jnp.einsum("hts,shd->thd", a, v) for v in vq]

    outs = jax.lax.map(one, (q.reshape(T // block, block, H, D),
                             jnp.arange(0, T, block)))
    return [o.reshape((T,) + o.shape[2:]) for o in outs]


def differential_attention(p, x, kept, depth, window, size, qz):
    """(``W_o concat(o) + b``, the layer's ``(k, v)``); ``kept``: another
    layer's ``(k, v)`` for a cross layer (then only q is projected)."""
    H, Hkv = size["num_attention_heads"], size["num_key_value_heads"]
    D, T = size["hidden_size"] // H, x.shape[0]
    mm = lambda a, w: qz(a) @ qz(w["kernel"]) + w["bias"]
    if kept is None:
        q, k, v = jnp.split(mm(x, p["qkv_proj"]),
                            [H * D, (H + Hkv) * D], axis=-1)
        k, v = k.reshape(T, Hkv // 2, 2, D), v.reshape(T, Hkv // 2, 2, D)
    else:
        q = mm(x, p["q_proj"])
        k, v = kept
    q = q.reshape(T, H // 2, 2, D)
    wide = lambda t, a: jnp.repeat(t[:, :, a], H // Hkv, axis=1)  # per q pair
    o1 = softmax_maps(q[:, :, 0], wide(k, 0), [wide(v, 0), wide(v, 1)],
                      window, qz)
    o2 = softmax_maps(q[:, :, 1], wide(k, 1), [wide(v, 0), wide(v, 1)],
                      window, qz)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0
    o = jnp.concatenate(o1, -1) - lam * jnp.concatenate(o2, -1)  # [T, H/2, 2D]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + SUBLN_EPS) \
        * p["subln"]["scale"] * (1.0 - lam0)
    return mm(o.reshape(T, H * D), p["o_proj"]), (k, v)


def layer(kind: str, p, h, kept, depth, size, qz):
    """One decoder layer of ``kind``; ``p``: this layer's leaves (no leading
    axis); ``kept``: {"m": ..., "kv": ...} of the layers before. Returns (h,
    what this layer keeps: {} or one entry)."""
    mixer = kind.split("+")[0]
    eps = size["layer_norm_eps"]
    keeps = {}
    if mixer in ("ssm", "ssm_keep"):
        a, y = state_space(p["ssm"], layer_norm(p["norm_ssm_in"], h, eps),
                           size, qz)
        if mixer == "ssm_keep":
            keeps = {"m": y}
    elif mixer == "gmu":
        x = layer_norm(p["norm_gmu_in"], h, eps)
        g = jax.nn.silu(qz(x) @ qz(p["gmu"]["in_proj"]["kernel"])) * kept["m"]
        a = qz(g) @ qz(p["gmu"]["out_proj"]["kernel"])
    else:
        a, kv = differential_attention(
            p, layer_norm(p["norm_attn_in"], h, eps),
            kept["kv"] if mixer == "cross" else None, depth,
            size["sliding_window"] if mixer == "diff_win" else 0, size, qz)
        if mixer == "diff_keep":
            keeps = {"kv": kv}
    h = h + a
    return h + mlp(p, layer_norm(p["norm_mlp_in"], h, eps), qz), keeps


def hidden_states(params, tokens, size, qz):
    """The final norm's output [T, d]."""
    p = params["params"]
    h = p["embed"]["embedding"][tokens]
    kept, depth = {}, size["first_layer"]
    for i, (kind, n) in enumerate(layer_runs(size["layer_pattern"])):
        run = p["stack"][f"layers_{i}"]
        for j in range(n):
            lp = jax.tree.map(lambda a: a[j], run)
            h, keeps = jax.checkpoint(
                lambda lp, h, kept, kind=kind, depth=depth: layer(
                    kind, lp, h, kept, depth, size, qz))(lp, h, kept)
            kept = {**kept, **keeps}
            depth += 1
    return layer_norm(p["stack"]["norm_f"], h, size["layer_norm_eps"])


def logits(params, h):
    """The tied head, float32: ``h E^T`` (the vocabulary-share test's)."""
    return h @ params["params"]["embed"]["embedding"].T


def loss_fn(params, tokens, size, qz):
    h = hidden_states(params, tokens, size, qz)
    targets = jnp.concatenate([tokens[1:], tokens[:1]])  # the last: unscored
    ce = cross_entropy(params["params"]["embed"]["embedding"].T, h[None],
                       targets, qz)[0]
    return ce[:-1].mean()


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

    grad_fn, update = program(hashable(size), precision)
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
