"""Plain reference for the ``nemotron3_nano_30b_a3b`` configuration: a hybrid
decoder whose every layer is ONE residual half: a Mamba-2 mixer, an expert
layer (sigmoid-routed squared-ReLU experts beside one shared expert) or
grouped-query attention (NVIDIA Nemotron-H, ``model_type`` nemotron_h); this
chip's share of its first nine layers.

Float32 ``jax.numpy`` at matmul precision ``highest``; no kernel, no flax,
nothing of ``dgraph_tpu`` imported. It takes the weights the benchmark made
from the seed (a nested dict under the program's names: each run of equal
layers, ``layers_<run>``, with its leaves stacked on a leading axis, the held
experts on a second) and the same token batches, and follows AdamW for a few
steps.

Equations (d hidden; Mamba-2 with H heads of P channels, G groups of N
states, K taps; E experts of width F of which ``n_routed_experts`` are held
here, k a token, a shared expert of width Fs; Ha query heads on Hkv KV heads
of Da):

- every layer: ``h <- h + Mix(RMSNorm(h))``, eps 1e-5, no bias in any
  projection; one RMSNorm before the untied head; no positional encoding;
- ``M``: ``(z, xBC, dt) = split(W_in u)`` of sizes ``H P | H P + 2 G N | H``;
  ``xBC <- silu(conv_K(xBC) + b)`` (depthwise, causal, zeros before the
  start); ``(x, B, C) = split(xBC)``, ``x`` as ``[T, H, P]``, ``B``, ``C`` as
  ``[T, G, N]``, head h reads group ``h // (H / G)``; ``dt = softplus(dt +
  dt_bias)``; ``a_t = exp(dt_t A)``, ``A = -exp(A_log)`` a head; ``S_t = a_t
  S_{t-1} + dt_t x_t B_t^T`` from ``S_{-1} = 0`` (a ``[P, N]`` state a head);
  ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm_{H P / G}(y * silu(z)) * g`` (the
  norm over each of the G groups of channels, after the gate); ``Mix = W_out
  y``. The recurrence runs STEP BY STEP over time (``lax.scan`` over
  ``S_t``), not in the chunked form the program takes;
- ``E``: ``s = sigmoid(W_r u)`` over all E; the k largest of ``s + b`` are
  chosen (``b``: the selection bias, which takes no gradient and no update;
  ``n_group`` 1: no group limit); gates ``g_e = scale * s_e / (sum of the
  chosen s + 1e-20)``; ``Mix = (sum over the chosen experts THAT ARE HELD
  HERE, ids first_expert ..., of g_e W_down,e relu(W_up,e u)^2) + W_down,s
  relu(W_up,s u)^2``: what the absent experts would add is left out, the
  shared expert is whole, and that sum goes on;
- ``*``: q, k, v projections as ``[T, Ha or Hkv, Da]``, query head j reads KV
  head ``j // (Ha / Hkv)``, exact causal softmax at ``1 / sqrt(Da)``, ``W_o``;
- loss: the mean over the T - 1 scored positions of ``CE(W_head RMSNorm_f(h)
  [i], token i + 1)``.

Departures from the published model, each an ``assumed`` line of the
configuration too: the layer equations are NVIDIA's released modelling code
and ``mamba_ssm``'s Mamba-2 as known without a network (the config gives every
number); no positional encoding (the family's description; the config's rope
keys are read by no layer); the selection bias is held fixed, no router
auxiliary loss; this chip holds 8 of the 128 experts and an eighth of the
vocabulary; ``A_log``, ``D``, ``dt_bias`` seeded as Mamba-2 initialises them;
weight decay on every leaf but the bias; no dropout, no clipping; AdamW with a
linear warm-up.

So that it fits the chip the program has just left: every layer under
recomputation; the recurrence in blocks of time steps under recomputation
(unblocked, a layer's 8192 states of ``[64, 64, 128]`` are 17 GB); attention a
block of queries at a time; the held experts in a plain loop (every expert
over every row, times the gate, which is 0 where the row did not choose it);
the cross-entropy in blocks. That changes no arithmetic.

``precision`` below float32 is the control: every matmul operand of the
projections (``W_in``, ``W_out``, q, k, v, o), of the experts and the shared
expert, of attention and of the head is rounded to that type in the forward
pass, and so are ``x``, ``B`` and ``C`` where they enter the recurrence (the
operands of the program's products inside a chunk); the arithmetic stays
float32. The router, the convolution, the step size and the recurrence's
decays and states stay float32, as the configuration states them for the
program too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gcn import quantiser
from benchmark.reference.lfm2 import hashable, short_conv
from benchmark.reference.looplm import (
    EPS_ADAM, causal_attention, cross_entropy, rms_norm)

GATE_EPS = 1e-20  # added to the chosen scores' sum
STEP_BLOCK = 256  # time steps of one recomputed block of the recurrence
FROZEN = ("select_bias",)  # leaves the optimizer leaves alone, by name


def recurrence(x, dt, A, B, Cm, D):
    """``y [T, H, P]`` of ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t + D x_t``, one time step after the other; ``B``, ``Cm``
    ``[T, G, N]``, head h reading group ``h // (H / G)``."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    block = STEP_BLOCK
    while T % block:
        block //= 2

    def step(S, args):  # S [G, H / G, P, N]
        x_t, dt_t, B_t, C_t = args
        S = jnp.exp(dt_t * A).reshape(G, -1, 1, 1) * S \
            + (dt_t[:, None] * x_t).reshape(G, -1, P, 1) * B_t[:, None, None, :]
        return S, (S * C_t[:, None, None, :]).sum(-1).reshape(H, P)

    @jax.checkpoint
    def steps(S, xs):
        return jax.lax.scan(step, S, xs)

    blocks = lambda a: a.reshape((T // block, block) + a.shape[1:])
    _, y = jax.lax.scan(steps, jnp.zeros((G, H // G, P, N), x.dtype),
                        (blocks(x), blocks(dt), blocks(B), blocks(Cm)))
    return y.reshape(T, H, P) + D[:, None] * x


def mamba2(p, u, size, qz):
    """``W_out (RMSNorm_groups(y * silu(z)) * g)``; ``p``: the ``ssd``
    leaves."""
    H, P = size["mamba_num_heads"], size["mamba_head_dim"]
    G, N, T = size["n_groups"], size["ssm_state_size"], u.shape[0]
    inner = H * P
    mm = lambda a, w: qz(a) @ qz(w["kernel"])
    z, xbc, dt = jnp.split(mm(u, p["in_proj"]),
                           [inner, 2 * inner + 2 * G * N], axis=-1)
    xbc = jax.nn.silu(short_conv(xbc, p["conv"]["kernel"]) + p["conv_bias"])
    x, B, Cm = jnp.split(xbc, [inner, inner + G * N], axis=-1)
    y = recurrence(
        qz(x).reshape(T, H, P), jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["A_log"]), qz(B).reshape(T, G, N), qz(Cm).reshape(T, G, N),
        p["D"])
    g = (y.reshape(T, inner) * jax.nn.silu(z)).reshape(T, G, inner // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + size["layer_norm_epsilon"])
    return mm(g.reshape(T, inner) * p["norm"]["scale"], p["out_proj"])


def attention(p, x, size, qz):
    H, Hkv, D = (size["num_attention_heads"], size["num_key_value_heads"],
                 size["head_dim"])
    T = x.shape[0]
    mm = lambda a, w: qz(a) @ qz(w["kernel"])
    q = mm(x, p["q_proj"]).reshape(T, H, D)
    k, v = (jnp.repeat(mm(x, p[n]).reshape(T, Hkv, D), H // Hkv, axis=1)
            for n in ("k_proj", "v_proj"))
    return mm(causal_attention(q, k, v, qz).reshape(T, H * D), p["o_proj"])


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def route(u, router, bias, k: int, scale: float):
    """(gates [T, k], experts [T, k]) over ALL experts, float32: sigmoid
    scores, the choice by ``score + bias``, the gates the scores alone."""
    s = jax.nn.sigmoid(u @ router)
    _, experts = jax.lax.top_k(s + bias, k)
    gates = jnp.take_along_axis(s, experts, -1)
    return scale * gates / (gates.sum(-1, keepdims=True) + GATE_EPS), experts


def held_experts(x, gates, experts, p, first: int, qz):
    """sum over the held experts e of gate_e(x) * W_down,e relu(W_up,e x)^2:
    every held expert over every row, a plain loop; gate_e is 0 where the row
    did not choose e."""
    xq = qz(x)

    @jax.checkpoint
    def one(acc, args):
        e, wu, wd = args
        gate = jnp.where(experts == first + e, gates, 0.0).sum(-1)
        return acc + gate[:, None] * (qz(relu2(xq @ qz(wu))) @ qz(wd)), None

    n = p["up_proj"]["kernel"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(n), p["up_proj"]["kernel"], p["down_proj"]["kernel"]))
    return out


def shared_expert(x, p, qz):
    """``W_down,s relu(W_up,s x)^2`` on every row."""
    mid = relu2(qz(x) @ qz(p["shared_up_proj"]["kernel"]))
    return qz(mid) @ qz(p["shared_down_proj"]["kernel"])


def expert_layer(p, u, size, qz):
    """(the held experts' part + the shared expert, the experts each row
    chose); ``p``: the ``experts`` leaves."""
    gates, experts = route(u, p["router"]["kernel"], p["select_bias"],
                           size["num_experts_per_tok"],
                           size["routed_scaling_factor"])
    return held_experts(u, gates, experts, p, size["first_expert"], qz) \
        + shared_expert(u, p, qz), experts


def layer(letter: str, p, h, size, qz):
    """One layer of the pattern's ``letter``; ``p``: this layer's leaves (no
    leading axis). Returns (h, the experts each row chose, or None)."""
    eps = size["layer_norm_epsilon"]
    if letter == "M":
        return h + mamba2(p["ssd"], rms_norm(p["norm_ssd_in"]["scale"], h,
                                             eps), size, qz), None
    if letter == "*":
        return h + attention(p, rms_norm(p["norm_attn_in"]["scale"], h, eps),
                             size, qz), None
    m, chosen = expert_layer(
        p["experts"], rms_norm(p["norm_mlp_in"]["scale"], h, eps), size, qz)
    return h + m, chosen


def hidden_states(params, tokens, size, qz):
    """(the final norm's output [T, d], the experts every row chose in each
    expert layer [expert layers, T, k]). The pattern alternates, so every
    layer is a run of one: ``layers_<i>`` with a leading axis of 1."""
    p = params["params"]
    h = p["embed"]["embedding"][tokens]
    chosen = []
    for i, letter in enumerate(size["hybrid_override_pattern"]):
        lp = jax.tree.map(lambda a: a[0], p["stack"][f"layers_{i}"])
        h, c = jax.checkpoint(
            lambda lp, h, letter=letter: layer(letter, lp, h, size, qz))(lp, h)
        if c is not None:
            chosen.append(c)
    return rms_norm(p["stack"]["norm_f"]["scale"], h,
                    size["layer_norm_epsilon"]), jnp.stack(chosen)


def logits(params, h):
    """The untied head, float32 (the vocabulary-share test's)."""
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
