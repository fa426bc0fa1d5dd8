"""Looped decoder LM over a sequence-sharded mesh axis.

One stack of ``num_layers`` decoder layers, its parameters held once (stacked
on a leading axis, applied under ``nn.scan``), is passed over ``loop_steps``
times with the same parameters (a second ``nn.scan`` with the parameters
broadcast): the looped language model of "Scaling Latent Reasoning via Looped
Language Models" (ByteDance, 2025; Ouro). ``loop_steps=1`` with
``exit_gate=False`` is a plain decoder with sandwich norms, so the loop is a
parameter of one model and the parameter tree does not depend on it.

Equations (d hidden, H heads of ``head_dim``, F intermediate, no bias):

- layer: ``a = Attn(RMSNorm1(h))``, ``h <- h + RMSNorm2(a)``,
  ``m = W_down(silu(W_gate u) * W_up u)`` with ``u = RMSNorm3(h)``,
  ``h <- h + RMSNorm4(m)``;
- ``Attn``: ``q, k, v = W_q x, W_k x, W_v x`` as ``[T, H, head_dim]``, rotary
  embedding on q and k at the token's GLOBAL position (rotate-half pairs
  ``(i, i + head_dim/2)``), exact causal softmax(``q k^T / sqrt(head_dim)``)
  ``v`` through the comm facade's ``seq_attention`` (dense or the Mosaic
  kernels on one device, ring or Ulysses over the graph axis), ``W_o``;
- the loop: ``h0 = E[tokens]``; for t = 1..R: ``h_t = RMSNorm_f(Stack(h_{t-1}))``;
  ``logits_t = W_head h_t``; exit gate ``lambda_t = sigmoid(w_g . h_t + b_g)``.

The same layer, by build-time fields, is a sparse-expert block-diffusion
decoder's (SDAR-30B-A3B, a Qwen3-MoE layer: ``sandwich_norm=False``,
``qk_norm=True``, ``experts=HeldExperts(...)``, ``block_length > 0``):

- pre-norm only: ``h <- h + Attn(RMSNorm1(h))``, ``h <- h + MoE(RMSNorm3(h))``;
- ``Attn``: RMSNorm over each head's ``head_dim`` with a learned gain on q and
  on k before the rotary embedding; ``num_kv_heads < num_heads`` KV heads read
  where they lie (no repeat); with ``block_length`` the ``2L`` rows are the
  noised and the clean copy of one sequence (row i of either carries position
  i) under ``BlockDiffusionMask(L, block_length)`` in ``causal``'s place;
- ``MoE``: ``p = softmax(W_r x)`` over all ``n_total`` experts in float32
  (the router reads the norm's float32 output), the ``k`` largest, gates
  renormalised over all chosen; this chip adds its ``n_held`` experts' part
  (:func:`dgraph_tpu.parallel.expert.held_experts_ffn`) and leaves the rest
  out.

A stack may hold layers of MORE THAN ONE KIND (``LoopLM.pattern``, a tuple of
kinds in stack order; LFM2-8B-A1B's decoder): a kind is ``"<mixer>+<ffn>"`` with
mixer ``attn`` (above) or ``conv`` and ffn ``dense`` (the gated MLP of width
``intermediate``) or ``experts`` (``experts``). Consecutive layers of one kind
are one ``nn.scan`` over stacked parameters (``layers_0``, ``layers_1``, ...
in stack order), unequal ones follow each other. No pattern: one scan,
``layers``, every layer of the kind the other fields give.

- ``conv``, the gated short convolution (kernel K = ``conv_kernel``):
  ``(B, C, x~) = split3(W_in x)``, each ``[T, d]``; ``y = B * x~``;
  ``z_t = sum_{j<K} w_j * y_{t-(K-1)+j}`` (depthwise, causal, zero before the
  sequence's start, one weight a channel and tap, no bias); ``Conv(x) =
  W_out (C * z)``. Token-local but for a ``K - 1``-row halo: over a sharded
  sequence a shard takes the last ``K - 1`` rows of ``y`` from the rank
  before it (one ``ppermute``; the first rank gets zeros);
- an expert layer's router may be the sigmoid form with a selection bias
  (``HeldExperts.score``, ``.select_bias``: :func:`route_topk`); the bias is a
  leaf of the parameter tree that takes no gradient, and the trainer's step
  leaves it alone (``FROZEN_LEAVES``: a masked update);
- ``tie_head``: ``logits(h) = h E^T`` with ``E`` the embedding, in the compute
  dtype with a float32 result; no ``head`` leaf.

Further mixers, of a decoder-hybrid-decoder (Phi-4-mini-flash-reasoning:
SambaY with differential attention; Ren et al. 2025), and what goes with them
(``norm="layer"``: LayerNorm with gain and bias; ``attn_bias``; ``fused_mlp``:
``(g, y) = split2(W_1 u)``, ``W_2 (silu(g) * y)``; ``rope_theta=None``: no
positional encoding):

- ``ssm`` / ``ssm_keep``, the selective state-space layer (Mamba-1;
  ``ssm``: :class:`StateSpace`, C = ``inner`` channels, N states, K taps,
  rank R): ``(u, z) = split2(W_in x)``; ``u <- silu(conv_K(u) + b)``
  (depthwise, causal, the ``K - 1``-row halo as ``conv``'s);
  ``(d, B_t, C_t) = split(W_x u)``; ``Delta = softplus(W_dt d + b_dt)``;
  ``A = -exp(A_log)``; ``y`` = the selective scan of
  :mod:`dgraph_tpu.ops.selective_scan` (float32 inside, the state crossing a
  sharded sequence rank by rank); ``Mix = W_out (y * silu(z))``.
  ``ssm_keep`` also KEEPS ``m = y`` for the layers after it;
- ``gmu``, the gated memory unit: ``Mix = W_2 (silu(W_1 x) * m)`` with ``m``
  what the last ``ssm_keep`` layer before it kept;
- ``diff_win`` / ``diff_keep`` / ``cross``, differential attention (Ye et al.
  2024): the H query and Hkv key heads of D are H/2 query pairs ``(q1, q2)``
  on Hkv/2 key pairs ``(k1, k2)`` (adjacent heads pair), the value heads pair
  into ``V = [v1 ; v2]`` of 2D; ``A_i = softmax(q_i k_i^T / sqrt(D) + mask)``;
  ``o = (1 - l0) RMSNorm_2D(A_1 V - l A_2 V)``, ``l = exp(lq1 . lk1) -
  exp(lq2 . lk2) + l0``, ``l0 = 0.8 - 0.6 exp(-0.3 depth)`` at the layer's
  PUBLISHED depth (``first_depth`` + its place in the stack); so every softmax
  map has a q.k head of D and a v head of 2D, and the H maps go through ONE
  ``seq_attention`` call as H query heads on Hkv key heads with the values
  repeated. ``diff_win``: under a causal window of ``window`` keys;
  ``diff_keep``: full causal, and the layer KEEPS its ``(k, v)``; ``cross``:
  projects ``q`` only and attends, full causal, the ``(k, v)`` the last
  ``diff_keep`` layer kept.

What a layer kept reaches its readers as a loop-invariant input of their scan
(never a carry: nothing is copied per layer), inside one pass over the stack.

A kind may have ONE half: ``"<mixer>+none"`` is a mixer alone, ``"none+<ffn>"``
a feed-forward part alone (``h <- h + Part(Norm(h))``, once). That is the
layer of a hybrid Mamba-2 / expert decoder (NVIDIA Nemotron-H, ``model_type``
nemotron_h; ``ssd+none``, ``none+experts``, ``attn+none``, RMSNorm at eps 1e-5,
no bias, no positional encoding, an untied head), with:

- ``ssd``, the Mamba-2 mixer (``ssd``: :class:`Mamba2Mixer`, H heads of P, G
  groups of N states, K taps): ``(z, xBC, dt) = split(W_in u)`` of sizes ``H P
  | H P + 2 G N | H``; ``xBC <- silu(conv_K(xBC) + b)`` (depthwise, causal,
  zeros before the start, the ``K - 1``-row halo as ``conv``'s); ``(x, B, C)
  = split(xBC)``, ``x`` as ``[T, H, P]``, ``B``, ``C`` as ``[T, G, N]``, head
  h reading group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)``; ``a_t =
  exp(dt_t A)``, ``A = -exp(A_log)`` a head; ``S_t = a_t S_{t-1} + dt_t x_t
  B_t^T`` from ``S_{-1} = 0``, ``y_t = S_t C_t + D x_t``
  (:mod:`dgraph_tpu.ops.ssd`: chunked, float32 decays and states, the state
  crossing a sharded sequence rank by rank); ``y <- RMSNorm_{H P / G}(y *
  silu(z)) * g`` (the norm over each of the G groups of channels, AFTER the
  gate); ``Mix = W_out y``;
- an expert of the ungated form (``HeldExperts.form = "relu2"``): ``W_down,e
  relu(W_up,e u)^2``, two products and no gate leaf; and ONE SHARED expert
  beside the routed ones (``HeldExperts.shared_width``): ``FFN(u) = (sum over
  the chosen experts held here) + W_down,s relu(W_up,s u)^2`` in the experts'
  form, a dense FFN on every token, which every chip of a deployment computes
  alike (a sum over shares counts it once).

A sparse-expert decoder whose ROUTER READS THE LAYER'S INPUT, with plain
grouped-query attention under a window beside full layers without positions
(PowerInfer SmallThinker; kinds ``attn+experts`` and ``attn_win+experts``,
pre-norm RMSNorm at eps 1e-6, no bias, no q/k norm, no shared expert, an
untied head):

- layer with input ``x``: ``r = W_r x`` over all ``n_total`` experts, float32,
  of the stream ITSELF (no norm before the router: ``HeldExperts.router_reads
  = "layer_input"``); the ``k`` largest logits are chosen; gates ``g =
  softmax`` over the ``k`` chosen logits (= the softmax over all divided by
  the chosen ones' sum: :func:`route_topk` with renormalisation); ``h = x +
  Attn(RMSNorm1(x))``; ``y = h + sum over the chosen experts held here of g_e
  W_down,e (relu(W_gate,e u) * W_up,e u)``, ``u = RMSNorm2(h)`` (ReGLU:
  ``HeldExperts.form = "gated_relu"``). The layer routes at its top and hands
  the gates and the chosen experts past the mixer to the experts' half, so
  the router's gradient joins the residual at the layer's input; what the
  placement is FOR (experts fetched, or an exchange issued, while attention
  runs) exists only where something is fetched or exchanged (ROADMAP R9);
- ``attn_win``, plain attention under a window: ``q, k, v`` as ``attn``'s,
  the rotary embedding on q and k, then exact softmax over the keys ``j``
  with ``i - window < j <= i`` (the query's own position and the ``window -
  1`` before it: ``WindowMask``), scope ``dgraph.lm.attn_win`` around the
  call; and positions BY KIND: with ``full_attn_rope=False`` an ``attn``
  layer of the same stack takes NO positional encoding and attends the whole
  causal prefix, while the windowed layers take the table (``rope_theta=None``
  keeps meaning that no layer has positions).

Attention through a LATENT with a decoupled rotary key (multi-head latent
attention, the ``deepseek_v3`` layer; kinds ``attn_mla+dense`` and
``attn_mla+experts``; pre-norm RMSNorm, no bias, an untied head):

- ``attn_mla`` (``mla``: :class:`LatentAttention`, which has the equations):
  queries straight from the stream, keys and values up-projected a head from
  a normed ``kv_rank``-wide latent, the rotary embedding over ``rope_dim`` of
  a q.k head's ``nope_dim + rope_dim`` dimensions in the PUBLISHED pairs
  ``(2i, 2i + 1)`` (:func:`apply_rotary_pairs`; the table is built for
  ``rope_dim``, not ``head_dim``), ONE rotated key that every head reads,
  exact causal softmax at ``1 / sqrt(nope_dim + rope_dim)`` through
  ``seq_attention`` with value heads of ``v_head_dim`` (where one device
  holds the sequence). Keys and values are materialised a head for the
  kernel, the form the published code trains with: no absorbed products, no
  cache;
- its expert layers are what is there: the sigmoid router with a selection
  bias and a gate scale, gated-SiLU experts, and beside them ONE shared
  expert of the same gated form (the published ``n_shared_experts`` experts
  are one MLP of their summed width).

Everything but attention, the convolutions' halo and the scan's state is
token-local, so those are the only communication. Parameters are float32;
matmuls run in ``config.resolve_compute_dtype(dtype)``; norms, the rotary embedding and the
logits are float32. The exit-distribution loss over the passes lives with the
trainer (:mod:`dgraph_tpu.train.lm`), which applies :meth:`LoopLM.logits`
in blocks of positions.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dgraph_tpu.models.norm import RMSNorm

# matmul in the compute dtype, result in float32 (the logits and the gate
# feed a softmax / sigmoid whose statistics are float32)
_dot_f32_out = functools.partial(
    jax.lax.dot_general, preferred_element_type=jnp.float32)


@jax.named_scope("dgraph.lm.rotary")
def rotary_tables(positions: jax.Array, head_dim: int, theta: float):
    """(cos, sin), each ``[T, head_dim / 2]`` float32, of the angles
    ``position * theta^(-2i / head_dim)``. ``positions`` are GLOBAL token
    positions, so a sequence shard passes ``rank * T_loc + arange(T_loc)``."""
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / head_dim)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


@jax.named_scope("dgraph.lm.rotary")
def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate-half rotary embedding of ``x`` ``[T, H, D]``: the pairs are
    ``(i, i + D/2)``. Float32 arithmetic, result in ``x``'s dtype."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


@jax.named_scope("dgraph.lm.rotary")
def apply_rotary_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """The rotary embedding of ``x`` ``[T, H, D]`` over ADJACENT pairs ``(2i,
    2i + 1)`` (the published ``rope_interleave`` form), ``cos``, ``sin`` ``[T,
    D / 2]``. Each element takes its partner from the lane beside it (two
    rolls and a select: no reshape of the lanes into ``[D / 2, 2]``, which a
    TPU would tile at two of 128 lanes). Float32 arithmetic, result in
    ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    c, s = (jnp.repeat(t, 2, axis=-1)[:, None, :] for t in (cos, sin))
    even = jnp.arange(x.shape[-1]) % 2 == 0
    partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                        jnp.roll(xf, 1, axis=-1))
    return (xf * c + partner * s).astype(x.dtype)


def _under_norm_scope(module):
    """``module``'s application under ``dgraph.lm.norm``, for a norm that
    opens no scope of its own (``nn.LayerNorm``; :class:`RMSNorm` does)."""
    return jax.named_scope("dgraph.lm.norm")(module.__call__)


@dataclasses.dataclass(frozen=True)
class HeldExperts:
    """The chip's share of a sparse-expert FFN: ``n_held`` of ``n_total``
    routed experts of width ``width`` (ids ``first_held ...``), ``k`` a
    token with their gates renormalised over the ``k`` chosen. ``rows``
    bounds the buffer of rows routed here (None: the worst case, which can
    drop nothing); a row past it is dropped and counted; ``ladder`` False
    takes no smaller rung under it (``parallel.expert.buffer_ladder``: every
    layer-step then costs the one buffer's rows, whatever was routed here;
    every rung computes the same sums). Independent of
    the objective: a causal model takes expert layers as a block-diffusion
    one does. The router's form (``parallel.expert.route_topk``): ``score``
    ``"softmax"`` or ``"sigmoid"``; ``select_bias``: a ``[n_total]`` leaf
    ``select_bias`` added to the scores for the choice only (no gradient, no
    optimizer update); ``gate_eps`` added to the chosen gates' sum (None: the
    softmax form's guard); ``gate_scale`` on the normalised gates. ``form``:
    an expert is ``"gated_silu"`` or ``"gated_relu"`` (ReGLU; leaves
    ``gate_proj``, ``up_proj``, ``down_proj``) or ``"relu2"`` (``W_down
    relu(W_up x)^2``: no ``gate_proj``); ``shared_width`` > 0: one shared
    expert of that width and the same form on every token, added to the held
    part (leaves ``shared_up_proj``, ``shared_down_proj`` and, gated,
    ``shared_gate_proj``); every router form goes with every expert form (a
    gated-SiLU shared expert beside the sigmoid router with its bias and gate
    scale is the ``deepseek_v3`` layer's). ``router_reads``, the router's place
    (``ROUTER_READS``): ``"ffn_input"``, what the experts multiply, the
    float32 output of the norm before them; or ``"layer_input"``, the
    residual stream as it reaches the layer, un-normed, before the mixer
    (SmallThinker's: the layer then routes at its top and hands the gates
    and the chosen experts past the mixer to the experts)."""

    n_total: int
    n_held: int
    k: int
    width: int
    first_held: int = 0
    rows: Optional[int] = None
    score: str = "softmax"
    select_bias: bool = False
    gate_eps: Optional[float] = None
    gate_scale: float = 1.0
    form: str = "gated_silu"
    shared_width: int = 0
    router_reads: str = "ffn_input"
    ladder: bool = True


# Leaves of the parameter tree, by name, that are buffers: no gradient
# reaches them and the trainer's step zeroes their update (train/lm.py).
FROZEN_LEAVES = ("select_bias",)
# What an expert layer's router reads (``HeldExperts.router_reads``).
ROUTER_READS = ("ffn_input", "layer_input")


class _Kernel(nn.Module):
    """A stack of expert kernels ``[n, fan_in, fan_out]`` under the leaf name
    every kernel has."""

    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel", nn.initializers.lecun_normal(batch_axis=(0,)),
            self.shape)


class HeldExpertsFFN(nn.Module):
    """``(norm's float32 output [T, d]) -> (the held experts' part [T, d]
    float32, stats)``; scope ``dgraph.lm.moe`` with ``router``, ``routes``,
    ``dispatch``, ``experts``, ``combine`` and, with a shared expert,
    ``shared`` (a dense FFN on this shard's own tokens, added after
    ``combine``). One routing block for both places of the router: called
    with no ``routes`` the layer routes from the tensor its experts multiply;
    ``route_only`` routes from whatever it is handed and returns ``(gates
    [T, k] float32, experts [T, k] int32)``, for a caller that hands them
    back as ``routes`` with the experts' input later."""

    spec: HeldExperts
    comm: Any
    dtype: Any = None

    @nn.compact
    def __call__(self, x32, routes=None, route_only: bool = False):
        from dgraph_tpu.parallel.expert import held_experts_ffn, route_topk

        sp, d = self.spec, x32.shape[-1]
        with jax.named_scope("dgraph.lm.moe"):
            if routes is None:
                with jax.named_scope("router"):
                    # float32 end to end: the k-th and (k+1)-th probabilities
                    # of a row can lie within a bf16 rounding of each other
                    logits = nn.Dense(
                        sp.n_total, use_bias=False, dtype=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST,
                        name="router")(x32)
                    bias = self.param(
                        "select_bias", nn.initializers.zeros,
                        (sp.n_total,)) if sp.select_bias else None
                    routes = route_topk(
                        logits, sp.k, score=sp.score, select_bias=bias,
                        eps=sp.gate_eps, scale=sp.gate_scale)
                    # for a caller that asks (mutable=["intermediates"]):
                    # which experts each row chose, to set beside a
                    # reference's
                    self.sow("intermediates", "chosen", routes[1])
            if route_only:
                return routes
            gates, experts = routes
            x = x32.astype(self.dtype)
            gated = sp.form != "relu2"
            out, stats = held_experts_ffn(
                x, gates, experts,
                _Kernel((sp.n_held, d, sp.width), name="gate_proj")()
                if gated else None,
                _Kernel((sp.n_held, d, sp.width), name="up_proj")(),
                _Kernel((sp.n_held, sp.width, d), name="down_proj")(),
                n_total=sp.n_total if sp.ladder else None,
                first_held=sp.first_held, rows=sp.rows,
                axis_name=self.comm.graph_axis, form=sp.form)
            if sp.shared_width:
                with jax.named_scope("shared"):
                    dense = functools.partial(
                        nn.Dense, use_bias=False, dtype=self.dtype)
                    u = dense(sp.shared_width, name="shared_up_proj")(x)
                    if sp.form == "relu2":
                        mid = jnp.square(nn.relu(u))
                    else:
                        act = nn.relu if sp.form == "gated_relu" else nn.silu
                        mid = act(dense(sp.shared_width,
                                        name="shared_gate_proj")(x)) * u
                    out = out + dense(d, name="shared_down_proj")(mid)
            return out, stats


def previous_rows(y: jax.Array, n: int, comm) -> jax.Array:
    """The ``n`` rows before this shard's first: the last ``n`` rows of the
    rank before it (one ``ppermute`` over the graph axis; its transpose hands
    the cotangent back), zeros on the first rank and on one device."""
    if comm is None or comm.graph_axis is None:
        return jnp.zeros((n,) + y.shape[1:], y.dtype)
    if y.shape[0] < n:
        raise ValueError(f"a shard of {y.shape[0]} rows has no {n}-row halo")
    world = comm.get_world_size()
    # no pair ends at rank 0: ppermute leaves zeros there
    return jax.lax.ppermute(y[-n:], comm.graph_axis,
                            [(i, i + 1) for i in range(world - 1)])


def causal_taps(y: jax.Array, taps: jax.Array, comm) -> jax.Array:
    """``z_t = sum_j taps[j] * y_{t-(K-1)+j}`` for ``taps`` ``[K, d]``: a
    depthwise causal convolution whose rows before the shard's first are the
    rank before's (:func:`previous_rows`; zeros at the sequence's start)."""
    K, n = taps.shape[0], y.shape[0]
    rows = jnp.concatenate([previous_rows(y, K - 1, comm), y]) if K > 1 else y
    return sum(taps[j] * rows[j:j + n] for j in range(K))


class _Taps(nn.Module):
    """The taps ``[K, d]`` of a depthwise convolution under the leaf name
    every kernel has (fan-in K)."""

    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel", nn.initializers.normal(self.shape[0] ** -0.5),
            self.shape)


class GatedShortConv(nn.Module):
    """``x [T_loc, d] -> W_out (C * conv_K(B * x~))`` (module docstring);
    scope ``dgraph.lm.conv`` with ``in_proj``, ``gate_conv``, ``out_proj``.
    The two products run in the compute dtype; both gates and the taps' sum
    in float32 (one fused pass over the three streams: the arithmetic's width
    costs no HBM byte), the result rounded once for ``out_proj``."""

    kernel_size: int
    comm: Any
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        d, K = x.shape[-1], self.kernel_size
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        with jax.named_scope("dgraph.lm.conv"):
            with jax.named_scope("in_proj"):
                bcx = dense(3 * d, name="in_proj")(x)
            with jax.named_scope("gate_conv"):
                b, c, xt = (t.astype(jnp.float32)
                            for t in jnp.split(bcx, 3, axis=-1))
                y = b * xt
                taps = _Taps((K, d), name="conv")().astype(jnp.float32)
                g = (c * causal_taps(y, taps, self.comm)).astype(bcx.dtype)
            with jax.named_scope("out_proj"):
                return dense(d, name="out_proj")(g)


@dataclasses.dataclass(frozen=True)
class StateSpace:
    """The sizes of a selective state-space mixer: ``inner`` channels, ``state``
    states a channel, ``conv`` taps of the causal convolution before the scan,
    the rank ``dt_rank`` of the step size's projection, and the scan's
    ``chunk`` (None: ``ops.selective_scan.SCAN_CHUNK``)."""

    inner: int
    state: int = 16
    conv: int = 4
    dt_rank: int = 1
    chunk: Optional[int] = None


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log(1 .. N)`` a channel: Mamba's own (decays from e^-dt to e^-N dt)."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


def _dt_bias_init(key, shape, dtype=jnp.float32, lo=1e-3, hi=0.1):
    """The inverse softplus of a log-uniform step size in ``[lo, hi]``."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(hi) - math.log(lo)) + math.log(lo))
    return dt + jnp.log(-jnp.expm1(-dt))


class SelectiveSSM(nn.Module):
    """``x [T_loc, d] -> (W_out (y * silu(z)), y)`` (module docstring); scope
    ``dgraph.lm.ssm`` with ``in_proj``, ``conv``, ``dt_bc``, ``scan``,
    ``gate``, ``out_proj``. The products run in the compute dtype (``W_x``'s
    and ``W_dt``'s with a float32 result); the taps' sum, the step size, the
    scan and the gate in float32; ``u``, ``y`` as kept and the gate's result
    are compute-dtype streams."""

    spec: StateSpace
    comm: Any
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        from dgraph_tpu.ops.selective_scan import SCAN_CHUNK, scan_sequence

        sp, d = self.spec, x.shape[-1]
        C, N, K, R = sp.inner, sp.state, sp.conv, sp.dt_rank
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        wide = functools.partial(dense, dot_general=_dot_f32_out)
        with jax.named_scope("dgraph.lm.ssm"):
            with jax.named_scope("in_proj"):
                u, z = jnp.split(dense(2 * C, name="in_proj")(x), 2, axis=-1)
            with jax.named_scope("conv"):
                taps = _Taps((K, C), name="conv")().astype(jnp.float32)
                bias = self.param("conv_bias", nn.initializers.zeros, (C,))
                u = nn.silu(causal_taps(u.astype(jnp.float32), taps, self.comm)
                            + bias).astype(u.dtype)
            with jax.named_scope("dt_bc"):
                step, B, Cm = jnp.split(
                    wide(R + 2 * N, name="x_proj")(u), [R, R + N], axis=-1)
                delta = nn.softplus(
                    wide(C, name="dt_proj")(step.astype(u.dtype))
                    + self.param("dt_bias", _dt_bias_init, (C,)))
                A = -jnp.exp(self.param("A_log", _a_log_init, (C, N)))
                D = self.param("D", nn.initializers.ones, (C,))
            with jax.named_scope("scan"):
                y = scan_sequence(u, delta, A, B, Cm, D, self.comm,
                                  chunk=sp.chunk or SCAN_CHUNK)
            with jax.named_scope("gate"):
                g = (y * nn.silu(z.astype(jnp.float32))).astype(u.dtype)
            with jax.named_scope("out_proj"):
                return dense(d, name="out_proj")(g), y.astype(u.dtype)


@dataclasses.dataclass(frozen=True)
class Mamba2Mixer:
    """The sizes of a Mamba-2 mixer: ``heads`` heads of ``head_dim`` channels
    (``heads * head_dim`` inner channels), ``groups`` groups of B and C of
    ``state`` states each, ``conv`` taps of the causal convolution over ``x |
    B | C``, and the ``chunk`` of the chunked recurrence (None:
    ``ops.ssd.SSD_CHUNK``)."""

    heads: int
    head_dim: int
    groups: int = 8
    state: int = 128
    conv: int = 4
    chunk: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """The sizes of attention through a latent (the mixer kind ``attn_mla``;
    kinds ``attn_mla+dense``, ``attn_mla+experts``): keys and values are
    up-projected from a ``kv_rank``-wide normed latent; a q.k head is
    ``nope_dim`` dimensions without positions and ``rope_dim`` rotated ones,
    the rotated key ONE head shared by all; a value head is ``v_head_dim``.
    Queries come straight from the stream: a query latent has no path yet.

    With H heads, on the normed stream x (leaves ``q_proj``, ``kv_a_proj``,
    ``kv_a_norm``, ``kv_b_proj``, ``o_proj``; no bias): ``q = W_q x`` as
    ``[H, nope_dim + rope_dim]``; ``[c ; k_r] = W_kva x`` of sizes ``kv_rank |
    rope_dim``; ``[k_nope,h ; v_h] = W_kvb RMSNorm(c)`` as ``[H, nope_dim +
    v_head_dim]``; the rotary embedding over the ``rope_dim`` dimensions of
    each query head and of ``k_r``, pairs ``(2i, 2i + 1)``; ``k_h = [k_nope,h
    ; k_r]``; ``a_h = softmax(q_h k_h^T / sqrt(nope_dim + rope_dim)) v_h``
    over the causal prefix; ``W_o [a_1 ... a_H]``. Scopes ``dgraph.lm.mla_down``
    (``W_kva`` and the latent's norm) and ``dgraph.lm.mla_up`` (``W_kvb``, the
    rotary key, its broadcast over the heads and the concatenation); the
    attention core is ``seq_attention``'s own ``dgraph.comm.seq_attention``."""

    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.nope_dim + self.rope_dim


def _a_log_heads_init(key, shape, dtype=jnp.float32, lo=1.0, hi=16.0):
    """``log U(lo, hi)`` a head: Mamba-2's own (``A_init_range``)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, lo, hi))


class _Gain(nn.Module):
    """A gain vector under the leaf name every norm's has."""

    size: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, (self.size,))


class SSDMixer(nn.Module):
    """``x [T_loc, d] -> W_out (RMSNorm_groups(y * silu(z)) * g)`` (module
    docstring); scope ``dgraph.lm.ssd`` with ``in_proj``, ``conv``, ``chunk``
    and ``state`` (:mod:`dgraph_tpu.ops.ssd`'s), ``norm``, ``out_proj``. The
    two projections run in the compute dtype (``dt`` is read from ``W_in``'s
    result in that type, as the published kernels do under autocast); the
    taps' sum, the step size, the decays and states of the recurrence, the
    gate and the grouped norm in float32; ``x``, ``B``, ``C`` and the norm's
    result are compute-dtype streams."""

    spec: Mamba2Mixer
    comm: Any
    dtype: Any = None
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        from dgraph_tpu.ops.ssd import SSD_CHUNK, ssd_sequence

        sp, d, n = self.spec, x.shape[-1], x.shape[0]
        H, Pd, G, N, K = sp.heads, sp.head_dim, sp.groups, sp.state, sp.conv
        inner, wide = H * Pd, H * Pd + 2 * G * N
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        with jax.named_scope("dgraph.lm.ssd"):
            with jax.named_scope("in_proj"):
                z, xbc, step = jnp.split(
                    dense(inner + wide + H, name="in_proj")(x),
                    [inner, inner + wide], axis=-1)
            with jax.named_scope("conv"):
                taps = _Taps((K, wide), name="conv")().astype(jnp.float32)
                bias = self.param("conv_bias", nn.initializers.zeros, (wide,))
                xbc = nn.silu(causal_taps(xbc.astype(jnp.float32), taps,
                                          self.comm) + bias).astype(xbc.dtype)
            xs, B, Cm = jnp.split(xbc, [inner, inner + G * N], axis=-1)
            delta = nn.softplus(step.astype(jnp.float32) + self.param(
                "dt_bias", _dt_bias_init, (H,)))
            A = -jnp.exp(self.param("A_log", _a_log_heads_init, (H,)))
            D = self.param("D", nn.initializers.ones, (H,))
            y = ssd_sequence(
                xs.reshape(n, H, Pd), delta, A, B.reshape(n, G, N),
                Cm.reshape(n, G, N), D, self.comm, chunk=sp.chunk or SSD_CHUNK)
            with jax.named_scope("norm"):
                g = (y.reshape(n, inner) * nn.silu(z.astype(jnp.float32))
                     ).reshape(n, G, inner // G)
                g = g * jax.lax.rsqrt(
                    jnp.mean(g * g, -1, keepdims=True) + self.eps)
                g = (g.reshape(n, inner) * _Gain(inner, name="norm")()
                     ).astype(xbc.dtype)
            with jax.named_scope("out_proj"):
                return dense(d, name="out_proj")(g)


class GatedMemoryUnit(nn.Module):
    """``(x [T_loc, d], m [T_loc, C]) -> W_2 (silu(W_1 x) * m)``; scope
    ``dgraph.lm.gmu``. The gate in float32 (one fused pass over two
    compute-dtype streams)."""

    dtype: Any = None

    @nn.compact
    def __call__(self, x, m):
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        with jax.named_scope("dgraph.lm.gmu"):
            g = nn.silu(dense(m.shape[-1], name="in_proj")(x).astype(
                jnp.float32)) * m.astype(jnp.float32)
            return dense(x.shape[-1], name="out_proj")(g.astype(m.dtype))


LAYER_MIXERS = ("attn", "conv", "ssm", "ssm_keep", "gmu", "diff_win",
                "diff_keep", "cross", "ssd", "none", "attn_win", "attn_mla")
LAYER_FFNS = ("dense", "experts", "none")  # "none": the half is not there
DIFF_MIXERS = ("diff_win", "diff_keep", "cross")  # differential attention
ATTENDING = ("attn", "attn_win", "attn_mla") + DIFF_MIXERS
WINDOWED = ("diff_win", "attn_win")  # attend under ``window`` keys
KEEPS = {"ssm_keep": "m", "diff_keep": "kv"}  # mixer -> what it keeps
READS = {"gmu": "m", "cross": "kv"}  # mixer -> what it reads of the kept


def split_kind(kind: str):
    """``"conv+experts" -> ("conv", "experts")``; a layer of one half spells
    the other ``none`` (``"ssd+none"``, ``"none+experts"``); raises on an
    unknown kind and on a layer of no half."""
    mixer, _, ffn = kind.partition("+")
    if mixer not in LAYER_MIXERS or ffn not in LAYER_FFNS \
            or mixer == ffn == "none":
        raise ValueError(
            f"layer kind {kind!r}: '<mixer>+<ffn>' with mixer in "
            f"{LAYER_MIXERS} and ffn in {LAYER_FFNS}, not both none")
    return mixer, ffn


class LoopLMLayer(nn.Module):
    """One decoder layer; ``(h, rope) -> (h, stats)``, the signature
    ``nn.scan`` wants of a body (``stats``: the expert layer's counts, None
    for a dense FFN). Sandwich norms and a gated MLP by default (Ouro's);
    ``sandwich_norm=False``, ``qk_norm``, ``experts``, ``block_length`` give
    the pre-norm sparse-expert block-diffusion layer; ``mixer="conv"`` puts
    the gated short convolution in attention's place (module docstring);
    ``mixer="none"`` / ``has_ffn=False`` leave a half out. A
    mixer that reads what an earlier layer kept (``READS``) takes it as a
    third argument; one that keeps something (``KEEPS``) returns ``(h,
    (stats, kept))``."""

    hidden: int
    num_heads: int
    head_dim: int
    intermediate: int
    comm: Any  # _BaseComm: seq_attention routes dense/flash/ring/ulysses
    num_kv_heads: Optional[int] = None  # None = num_heads
    rms_eps: float = 1e-6
    dtype: Any = None
    attn_impl: str = "ring"
    sandwich_norm: bool = True
    qk_norm: bool = False
    experts: Optional[HeldExperts] = None  # None: the gated MLP
    block_length: int = 0  # > 0: rows [xt ; x0] under the block-diffusion mask
    mixer: str = "attn"  # one of LAYER_MIXERS
    conv_kernel: int = 3
    norm: str = "rms"  # or "layer": LayerNorm with gain and bias
    attn_bias: bool = False  # a bias on attention's projections
    fused_mlp: bool = False  # one W_1 for the gate and the value
    window: int = 0  # keys a query of a "diff_win" / "attn_win" layer sees
    ssm: Optional[StateSpace] = None
    depth: int = 0  # the published index of the layer (differential attention)
    ssd: Optional[Mamba2Mixer] = None
    has_ffn: bool = True  # False: the mixer alone ("<mixer>+none")
    full_attn_rope: bool = True  # False: an "attn" layer takes no positions
    mla: Optional[LatentAttention] = None  # the sizes of an "attn_mla" layer

    @nn.compact
    def __call__(self, h, rope, kept=None):  # [T_loc, hidden], (cos, sin)
        from dgraph_tpu import config as _cfg

        dt = _cfg.resolve_compute_dtype(self.dtype)
        dense = functools.partial(nn.Dense, use_bias=False, dtype=dt)
        if self.norm == "rms":
            norm = functools.partial(RMSNorm, epsilon=self.rms_eps, dtype=dt)
        else:
            norm = lambda name: _under_norm_scope(nn.LayerNorm(
                epsilon=self.rms_eps, dtype=dt, name=name))
        post = (lambda name: norm(name=name)) if self.sandwich_norm \
            else (lambda name: lambda y: y.astype(h.dtype))
        keep = experts = routes = None
        if self.experts is not None and self.has_ffn:
            experts = HeldExpertsFFN(self.experts, self.comm, dt,
                                     name="experts")
            if self.experts.router_reads == "layer_input":
                # the stream itself, un-normed, before the mixer: float32
                # as the router is end to end
                routes = experts(h.astype(jnp.float32), route_only=True)
        if self.mixer == "conv":
            a = GatedShortConv(self.conv_kernel, self.comm, dt, name="conv")(
                norm(name="norm_conv_in")(h))
            h = h + post("norm_conv_out")(a)
        elif self.mixer in ("ssm", "ssm_keep"):
            a, keep = SelectiveSSM(self.ssm, self.comm, dt, name="ssm")(
                norm(name="norm_ssm_in")(h))
            h = h + post("norm_ssm_out")(a)
        elif self.mixer == "gmu":
            a = GatedMemoryUnit(dt, name="gmu")(norm(name="norm_gmu_in")(h),
                                                kept)
            h = h + post("norm_gmu_out")(a)
        elif self.mixer == "ssd":
            a = SSDMixer(self.ssd, self.comm, dt, self.rms_eps, name="ssd")(
                norm(name="norm_ssd_in")(h))
            h = h + post("norm_ssd_out")(a)
        elif self.mixer in DIFF_MIXERS:
            h, keep = self.differ(h, kept, dt, dense, norm, post)
        elif self.mixer == "attn_mla":
            h = self.attend_latent(h, rope, dense, norm, post)
        elif self.mixer != "none":
            if self.mixer == "attn" and not self.full_attn_rope:
                rope = None
            h = self.attend(h, rope, dense, norm, post)
        h, stats = self.ffn(h, dense, norm, post, experts, routes) \
            if self.has_ffn else (h, None)
        return h, ((stats, keep) if self.mixer in KEEPS else stats)

    def attend(self, h, rope, dense, norm, post):
        H, D = self.num_heads, self.head_dim
        Hkv = self.num_kv_heads or H
        if H % Hkv:
            raise ValueError(f"heads {H} not divisible by kv heads {Hkv}")
        n = h.shape[0]
        x = norm(name="norm_attn_in")(h)
        q = dense(H * D, name="q_proj")(x).reshape(n, H, D)
        k = dense(Hkv * D, name="k_proj")(x).reshape(n, Hkv, D)
        v = dense(Hkv * D, name="v_proj")(x).reshape(n, Hkv, D)
        if self.qk_norm:  # over each head's D, one gain vector for all heads
            q, k = norm(name="q_norm")(q), norm(name="k_norm")(k)
        if rope is not None:
            q, k = apply_rotary(q, *rope), apply_rotary(k, *rope)
        if self.block_length:
            from dgraph_tpu.parallel.sequence import BlockDiffusionMask

            a = self.comm.seq_attention(
                q, k, v, impl=self.attn_impl,
                mask=BlockDiffusionMask(n // 2, self.block_length))
        elif self.mixer == "attn_win":
            from dgraph_tpu.parallel.sequence import WindowMask

            # a scope of its own around the call, so that a trace tells the
            # windowed layers' attention from the full layers'
            with jax.named_scope("dgraph.lm.attn_win"):
                a = self.comm.seq_attention(
                    q, k, v, impl=self.attn_impl,
                    mask=WindowMask(n, self.window))
        else:
            a = self.comm.seq_attention(q, k, v, causal=True,
                                        impl=self.attn_impl)
        a = dense(self.hidden, name="o_proj")(a.reshape(n, H * D))
        return h + post("norm_attn_out")(a)

    def attend_latent(self, h, rope, dense, norm, post):
        """Attention through a latent (:class:`LatentAttention`): ``h + W_o
        a``. ``rope`` is the table for ``rope_dim``. The H keys and values
        are written out a head for ``seq_attention``, the one rotated key
        broadcast over the heads."""
        sp, H, n = self.mla, self.num_heads, h.shape[0]
        Dn, Dr, Dv = sp.nope_dim, sp.rope_dim, sp.v_head_dim
        x = norm(name="norm_attn_in")(h)
        q = dense(H * (Dn + Dr), name="q_proj")(x).reshape(n, H, Dn + Dr)
        # the slice of the rotary part and the concatenation that puts it
        # back are what a rotary over ``Dr`` of a head's dimensions costs
        # beyond the rolls
        with jax.named_scope("dgraph.lm.rotary"):
            q = jnp.concatenate(
                [q[..., :Dn], apply_rotary_pairs(q[..., Dn:], *rope)], axis=-1)
        with jax.named_scope("dgraph.lm.mla_down"):
            c, k_r = jnp.split(dense(sp.kv_rank + Dr, name="kv_a_proj")(x),
                               [sp.kv_rank], axis=-1)
            c = norm(name="kv_a_norm")(c)
        with jax.named_scope("dgraph.lm.mla_up"):
            k_nope, v = jnp.split(
                dense(H * (Dn + Dv), name="kv_b_proj")(c).reshape(
                    n, H, Dn + Dv), [Dn], axis=-1)
            k_r = apply_rotary_pairs(k_r[:, None, :], *rope)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_r, (n, H, Dr))], axis=-1)
        a = self.comm.seq_attention(q, k, v, causal=True, impl=self.attn_impl)
        a = dense(self.hidden, name="o_proj")(a.reshape(n, H * Dv))
        return h + post("norm_attn_out")(a)

    def differ(self, h, kept, dt, dense, norm, post):
        """Differential attention (module docstring): ``(h + W_o o, the
        layer's (k, v))``. The H softmax maps are one ``seq_attention`` call:
        H query heads of D on Hkv key heads (map i of a key pair's queries
        side by side on key head i of the pair), values ``[v1 ; v2]`` of 2D
        once a key head. The difference, its norm and the scale are float32
        under the scope ``dgraph.lm.diff``."""
        from dgraph_tpu.parallel.sequence import WindowMask

        H, D = self.num_heads, self.head_dim
        Hkv = self.num_kv_heads or H
        if H % Hkv or Hkv % 2:
            raise ValueError(f"differential attention pairs the heads: {H} "
                             f"query heads on {Hkv} kv heads")
        g, n = H // Hkv, h.shape[0]
        biased = functools.partial(dense, use_bias=self.attn_bias)
        x = norm(name="norm_attn_in")(h)
        if self.mixer == "cross":
            q = biased(H * D, name="q_proj")(x)
            k, v = kept
        else:
            q, k, v = jnp.split(
                biased((H + 2 * Hkv) * D, name="qkv_proj")(x),
                [H * D, (H + Hkv) * D], axis=-1)
            k, v = k.reshape(n, Hkv, D), v.reshape(n, Hkv // 2, 2 * D)
        # query heads (pair, map) -> (key pair, map, pair of the key pair)
        q = q.reshape(n, Hkv // 2, g, 2, D).swapaxes(2, 3).reshape(n, H, D)
        windowed = self.mixer == "diff_win"
        a = self.comm.seq_attention(
            q, k, jnp.repeat(v, 2, axis=1), causal=not windowed,
            mask=WindowMask(n, self.window) if windowed else None,
            impl=self.attn_impl)
        with jax.named_scope("dgraph.lm.diff"):
            lam0 = 0.8 - 0.6 * math.exp(-0.3 * self.depth)
            vec = lambda name: self.param(
                name, nn.initializers.normal(0.1), (D,))
            lam = jnp.exp(jnp.sum(vec("lambda_q1") * vec("lambda_k1"))) \
                - jnp.exp(jnp.sum(vec("lambda_q2") * vec("lambda_k2"))) + lam0
            a = a.reshape(n, Hkv // 2, 2, g, 2 * D).astype(jnp.float32)
            o = RMSNorm(epsilon=self.rms_eps, dtype=jnp.float32,
                        name="subln")(a[:, :, 0] - lam * a[:, :, 1])
            o = (o * (1.0 - lam0)).astype(dt).reshape(n, H * D)
        a = biased(self.hidden, name="o_proj")(o)
        return h + post("norm_attn_out")(a), (
            (k, v) if self.mixer == "diff_keep" else None)

    def ffn(self, h, dense, norm, post, experts, routes):
        if experts is not None:
            u32 = RMSNorm(epsilon=self.rms_eps, dtype=jnp.float32,
                          name="norm_mlp_in")(h)
            m, stats = experts(u32, routes)
            return h + post("norm_mlp_out")(m), stats
        u = norm(name="norm_mlp_in")(h)
        if self.fused_mlp:
            g, y = jnp.split(dense(2 * self.intermediate,
                                   name="gate_up_proj")(u), 2, axis=-1)
            m = nn.silu(g) * y
        else:
            m = nn.silu(dense(self.intermediate, name="gate_proj")(u)) \
                * dense(self.intermediate, name="up_proj")(u)
        m = dense(self.hidden, name="down_proj")(m)
        return h + post("norm_mlp_out")(m), None


def layer_runs(pattern) -> list:
    """``[(kind, length)]``: the runs of consecutive equal kinds of a
    pattern, in stack order."""
    runs = []
    for kind in pattern:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return [tuple(r) for r in runs]


class LoopPass(nn.Module):
    """One pass over the stack: ``num_layers`` layers under ``nn.scan``
    (parameters stacked on axis 0, each application rematerialised where
    ``remat``), then the final norm. ``(h, rope) -> (h_t, h_t)``: the carry
    of the loop and the pass's exit state (with expert layers, ``(h_t,
    stats [expert layers, 6])`` in the second place). With a ``pattern`` of
    kinds each run of equal kinds is such a scan (``layers_<run>``), one
    after the other; ``layer``'s ``experts`` go to the expert layers only.
    What a ``KEEPS`` layer kept goes to the ``READS`` layers after it as a
    loop-invariant input of their scan; a differential-attention layer is
    told its published depth, ``first_depth`` + its place in the stack."""

    num_layers: int
    layer: dict  # LoopLMLayer's fields
    remat: bool = True
    pattern: Optional[tuple] = None
    first_depth: int = 0

    @nn.compact
    def __call__(self, h, rope):
        with jax.named_scope("dgraph.lm.loop_pass"):
            def scan(barrier=False, **kw):
                cls = LoopLMLayer
                if self.remat:  # inside a scan: no barrier against CSE
                    cls = nn.remat(cls, prevent_cse=barrier)
                return nn.scan(
                    cls, variable_axes={"params": 0, "intermediates": 0},
                    split_rngs={"params": True},
                    **{"in_axes": nn.broadcast, **kw})

            if self.pattern is None:
                h, stats = scan(length=self.num_layers)(
                    **self.layer, name="layers")(h, rope)
            else:
                counted, kept, depth = [], {}, self.first_depth
                for i, (kind, n) in enumerate(layer_runs(self.pattern)):
                    mixer, ffn = split_kind(kind)
                    fields = dict(
                        self.layer, mixer=mixer, has_ffn=ffn != "none",
                        experts=self.layer["experts"] if ffn == "experts"
                        else None)
                    if mixer in DIFF_MIXERS or mixer in KEEPS:
                        if n > 1:
                            raise ValueError(
                                f"a run of {n} {kind!r} layers: a layer that "
                                f"keeps, or whose lambda depends on its "
                                f"depth, is a run of its own")
                        fields["depth"] = depth
                    # a run of ONE layer is a one-trip loop, which XLA
                    # inlines: without the barrier the recomputation is then
                    # merged with the forward pass and nothing is
                    # rematerialised (the six-kind stack compiled for a v5e at
                    # T 8192: 15.84 GB so, 11.44 GB with the barrier). The
                    # runs of the kinds that were there keep their program.
                    barrier = n == 1 and (mixer not in ("attn", "conv")
                                          or ffn == "none")
                    if mixer in READS:
                        if READS[mixer] not in kept:
                            raise ValueError(
                                f"a {kind!r} layer reads what a layer before "
                                f"it kept, and none did")
                        h, st = scan(barrier, length=n, in_axes=(
                            nn.broadcast, nn.broadcast))(
                                **fields, name=f"layers_{i}")(
                                    h, rope, kept[READS[mixer]])
                    else:
                        h, st = scan(barrier, length=n)(
                            **fields, name=f"layers_{i}")(h, rope)
                    if mixer in KEEPS:  # the run's one layer's
                        st, keep = st
                        kept[KEEPS[mixer]] = jax.tree.map(
                            lambda a: a[0], keep)
                    if st is not None:
                        counted.append(st)
                    depth += n
                stats = jnp.concatenate(counted) if counted else None
            # rematerialised too: its float32 internals would otherwise be
            # saved once a pass
            rms = self.layer.get("norm", "rms") == "rms"
            norm_cls = RMSNorm if rms else nn.LayerNorm
            norm_f = nn.remat(norm_cls) if self.remat else norm_cls
            norm_f = norm_f(epsilon=self.layer["rms_eps"], dtype=h.dtype,
                            name="norm_f")
            h = (norm_f if rms else _under_norm_scope(norm_f))(h)
        return h, (h if stats is None else (h, stats))


class LoopLM(nn.Module):
    """Token ids in, the exit state of every pass out.

    ``hidden(tokens, positions) -> [loop_steps, T_loc, hidden]``;
    ``logits(h) -> [..., vocab]`` float32 (the untied head; with ``tie_head``
    the embedding transposed);
    ``gate_logit(h) -> [...]`` float32 (``exit_gate`` only). Calling the
    module gives ``(logits of every pass, gate logits or None)`` whole: for
    ``init`` and for small sequences; a trainer applies the head in blocks.
    """

    vocab: int
    hidden_size: int
    num_layers: int
    num_heads: int
    head_dim: int
    intermediate: int
    comm: Any = None
    num_kv_heads: Optional[int] = None
    loop_steps: int = 1
    exit_gate: bool = False
    rms_eps: float = 1e-6
    rope_theta: Optional[float] = 10000.0  # None: no positional encoding
    dtype: Any = None
    attn_impl: str = "ring"
    remat: bool = True
    sandwich_norm: bool = True
    qk_norm: bool = False
    experts: Optional[HeldExperts] = None
    block_length: int = 0  # > 0: trained by block diffusion (train/lm.py)
    mask_token: Optional[int] = None  # the id a noised token is replaced by
    pattern: Optional[tuple] = None  # kinds "<mixer>+<ffn>", in stack order
    conv_kernel: int = 3
    tie_head: bool = False
    norm: str = "rms"  # or "layer"
    attn_bias: bool = False
    fused_mlp: bool = False
    window: int = 0
    ssm: Optional[StateSpace] = None
    first_depth: int = 0  # the published index of the stack's first layer
    ssd: Optional[Mamba2Mixer] = None
    # False: the full-attention ("attn") layers carry no positional encoding
    # and only the windowed ("attn_win") ones take the rotary table
    full_attn_rope: bool = True
    mla: Optional[LatentAttention] = None  # the "attn_mla" layers' sizes

    def layer_kinds(self) -> tuple:
        """The kind of each of the ``num_layers`` layers, in stack order."""
        if self.pattern is not None:
            return tuple(self.pattern)
        ffn = "dense" if self.experts is None else "experts"
        return (f"attn+{ffn}",) * self.num_layers

    def setup(self):
        from dgraph_tpu import config as _cfg

        if self.pattern is not None:
            ffns = {split_kind(kind)[1] for kind in self.pattern}
            if len(self.pattern) != self.num_layers:
                raise ValueError(f"a pattern of {len(self.pattern)} kinds for "
                                 f"{self.num_layers} layers")
            if ("experts" in ffns) != (self.experts is not None):
                raise ValueError("expert layers in the pattern and `experts` "
                                 "go together")
            mixers = {split_kind(kind)[0] for kind in self.pattern}
            if mixers & {"ssm", "ssm_keep"} and self.ssm is None:
                raise ValueError("state-space layers in the pattern need "
                                 "`ssm`, their sizes")
            if "ssd" in mixers and self.ssd is None:
                raise ValueError("Mamba-2 layers in the pattern need `ssd`, "
                                 "their sizes")
            if mixers & set(WINDOWED) and self.window < 1:
                raise ValueError("windowed layers in the pattern need "
                                 "`window`")
            if "attn_win" in mixers and self.block_length:
                raise ValueError("a windowed layer under block diffusion: "
                                 "one structured mask a layer")
            if "attn_mla" in mixers:
                if self.mla is None:
                    raise ValueError("latent-attention layers in the pattern "
                                     "need `mla`, their sizes")
                if len(mixers & set(ATTENDING)) > 1 or self.block_length \
                        or self.rope_theta is None:
                    raise ValueError(
                        "a stack attends at ONE pair of head sizes under one "
                        "rotary table: latent attention beside another "
                        "attending mixer, under block diffusion or without "
                        "positions has no path")
        if self.experts is not None \
                and self.experts.router_reads not in ROUTER_READS:
            raise ValueError(f"router_reads {self.experts.router_reads!r}: "
                             f"one of {ROUTER_READS}")
        dt = _cfg.resolve_compute_dtype(self.dtype)
        self.embed = nn.Embed(self.vocab, self.hidden_size, dtype=dt)
        layer = dict(
            hidden=self.hidden_size, num_heads=self.num_heads,
            head_dim=self.head_dim, intermediate=self.intermediate,
            comm=self.comm, num_kv_heads=self.num_kv_heads,
            rms_eps=self.rms_eps, dtype=self.dtype, attn_impl=self.attn_impl,
            sandwich_norm=self.sandwich_norm, qk_norm=self.qk_norm,
            experts=self.experts, block_length=self.block_length,
            conv_kernel=self.conv_kernel, norm=self.norm,
            attn_bias=self.attn_bias, fused_mlp=self.fused_mlp,
            window=self.window, ssm=self.ssm, ssd=self.ssd,
            full_attn_rope=self.full_attn_rope, mla=self.mla)
        # the same parameters every pass: broadcast, not split
        loop = nn.scan(
            LoopPass, variable_broadcast="params",
            variable_axes={"intermediates": 0},
            split_rngs={"params": False}, in_axes=nn.broadcast,
            length=self.loop_steps)
        self.stack = loop(self.num_layers, layer, self.remat, self.pattern,
                          self.first_depth)
        if not self.tie_head:
            self.head = nn.Dense(self.vocab, use_bias=False, dtype=dt,
                                 dot_general=_dot_f32_out)
        if self.exit_gate:
            self.gate = nn.Dense(1, dtype=dt, dot_general=_dot_f32_out)

    def hidden(self, tokens, positions):  # [T_loc] int32 each
        rope = None if self.rope_theta is None else rotary_tables(
            positions, self.rotary_dim(), self.rope_theta)
        with jax.named_scope("dgraph.lm.embed"):
            h0 = self.embed(tokens)
        # the loop over the passes under the passes' own scope: what stacks
        # their exit states (and what ``nn.scan`` computes from the loop's
        # broadcast inputs alone, ahead of a run of layers) is the stream's
        with jax.named_scope("dgraph.lm.loop_pass"):
            _, hs = self.stack(h0, rope)
        # [loop_steps, T_loc, hidden]; with expert layers also their counts,
        # [loop_steps, num_layers, 6] (parallel.expert.HELD_STATS)
        return hs

    def latent(self) -> Optional[LatentAttention]:
        """The latent-attention sizes, where the stack has such layers."""
        attends = any(k.startswith("attn_mla+") for k in self.layer_kinds())
        return self.mla if attends else None

    def rotary_dim(self) -> int:
        """The dimensions of a head the rotary table is built for: the
        latent mixer's ``rope_dim``, else the whole ``head_dim``."""
        sp = self.latent()
        return self.head_dim if sp is None else sp.rope_dim

    def attention_mask(self, seq_len: int):
        """The structured mask the layers attend under for a sequence of
        ``seq_len`` tokens (None: causal)."""
        if not self.block_length:
            return None
        from dgraph_tpu.parallel.sequence import BlockDiffusionMask

        return BlockDiffusionMask(seq_len, self.block_length)

    def attention_masks(self, seq_len: int):
        """Of a stack with differential attention or a windowed layer, the
        mask of each attending layer in stack order (``WindowMask`` or
        ``CausalMask`` objects); None for every other stack (one mask:
        :meth:`attention_mask`)."""
        from dgraph_tpu.parallel.sequence import CausalMask, WindowMask

        mixers = [split_kind(kind)[0] for kind in self.layer_kinds()]
        if not set(mixers) & set(DIFF_MIXERS + WINDOWED):
            return None
        return [WindowMask(seq_len, self.window) if m in WINDOWED
                else CausalMask(seq_len) for m in mixers if m in ATTENDING]

    def logits(self, h):
        with jax.named_scope("dgraph.lm.head"):
            if self.tie_head:  # h E^T, compute-dtype operands, float32 result
                dt = self.embed.dtype
                return _dot_f32_out(
                    h.astype(dt), self.embed.embedding.astype(dt),
                    (((h.ndim - 1,), (1,)), ((), ())))
            return self.head(h).astype(jnp.float32)

    def gate_logit(self, h):
        with jax.named_scope("dgraph.lm.exit_gate"):
            return self.gate(h).astype(jnp.float32)[..., 0]

    def __call__(self, tokens, positions):
        hs = self.hidden(tokens, positions)
        if self.experts is not None:
            hs, _ = hs  # the expert layers' counts go with the trainer's step
        return self.logits(hs), (
            self.gate_logit(hs) if self.exit_gate else None)
