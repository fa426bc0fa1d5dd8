"""Trainer for causal sequence LMs over a sequence-sharded mesh axis.

The sequence counterpart of :mod:`dgraph_tpu.train.loop`: set-up
(:func:`lm_setup`: mesh-placed parameters, optimizer state, the attention
implementation chosen after the chip's self-check), one jitted train step and
one eval step (:func:`make_lm_train_step`, :func:`make_lm_eval_step`), and
the host-fed loop (:class:`LMTrainer`, :func:`fit_lm`): one host batch of
token ids goes to the device per step, the step is dispatched, the host
blocks on the loss.

One packed sequence of ``seq_len`` tokens is a step. It is sharded over the
graph axis in contiguous blocks (rank ``r`` holds positions
``[r T/W, (r+1) T/W)``); the trainer owns the global positions, and every one
of the ``T - 1`` next-token predictions is scored for any world size: a
shard's last position predicts its right neighbour's first token, fetched by
``ppermute``.

The loss is the exit-distribution objective of a looped LM
(:class:`~dgraph_tpu.models.looplm.LoopLM`): with pass ``t``'s logits and
exit gate ``lambda_t``,

    p_t = lambda_t prod_{j<t} (1 - lambda_j)   (t < R),
    p_R = prod_{j<R} (1 - lambda_j),
    loss = mean over positions of  sum_t p_t CE(logits_t, next) - beta H(p).

With one pass, or the gate off (then the last pass exits), it is the plain
next-token cross-entropy. The head is applied per exit step in blocks of
positions, so no ``[R, T, vocab]`` tensor is ever live, and where the loss is
differentiated each block's pull-back is taken in the same loop, while its
logits are there (:func:`weighted_cross_entropy`). A model that returns
logits directly
(:class:`~dgraph_tpu.models.transformer.SeqTransformerLM`, whose MoE blocks
also sow an auxiliary loss) goes through the same step as one pass.

A model with ``block_length > 0`` is trained by block diffusion instead
(:func:`block_diffusion_loss_sum`): a batch is ``(tokens, masked, weight)``,
the stack runs over the ``2L`` rows ``[xt ; x0]`` under the block-diffusion
mask, and the loss is the masked-token cross-entropy on the noised rows,
weighted per block by ``1 / t_b``, over ``L``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.extend.core import Var
from jax.sharding import NamedSharding, PartitionSpec as P

from dgraph_tpu.comm.mesh import GRAPH_AXIS, make_graph_mesh, tree_size
from dgraph_tpu.obs.metrics import StepMetrics, default_registry
from dgraph_tpu.train.loop import init_opt_state

# the dense oracle materialises [H, T, T] float32 logits; past this size the
# trainer refuses it instead of falling back to it in silence
DENSE_LOGITS_LIMIT_BYTES = 1 << 30
# one block of float32 logits [block, vocab] the exit loss holds at a time
LOSS_BLOCK_BYTES = 1 << 28
# tokens a shard of model.init's probe sequence (no shape depends on it)
INIT_PROBE_TOKENS = 128


def lm_comm(world_size: int):
    """The communicator of a sequence LM: ``single`` on one device (its
    ``seq_attention`` is the dense oracle or the Mosaic kernels), ``tpu`` over
    the graph axis otherwise (ring or Ulysses)."""
    from dgraph_tpu.comm import Communicator

    if world_size == 1:
        return Communicator.init_process_group("single")
    return Communicator.init_process_group("tpu", world_size=world_size)


def lm_mesh(world_size: int, devices=None):
    return make_graph_mesh(ranks_per_graph=world_size, devices=devices)


def resolve_attention(comm, attn_impl: str, t_local: int, num_heads: int,
                      head_dim: int, mask=None, group: int = 1,
                      v_head_dim: Optional[int] = None,
                      dtype=jnp.float32) -> str:
    """Decide, before anything is traced, which attention implementation
    ``comm.seq_attention`` will run for a stack's calls (plain causal ones,
    or under a structured ``mask``, with ``group`` query heads a KV head and
    streams of ``dtype``), and say which: 'splash', 'flash', 'dense', 'ring',
    'ulysses+splash', 'ulysses+flash' or 'ulysses+dense'. 'splash': the
    splash kernels, which run every structured mask, and a plain causal call
    under the causal mask at a head that is no multiple of 128 lanes (with
    values of ``v_head_dim``, where that is another size) and at any head
    wherever the one backward kernel takes the shape
    (``sequence._causal_splash``); 'flash': the library's flash kernels, for
    the causal shapes that are left.

    Wherever a device holds a full-sequence view the Mosaic kernels are
    engaged only after ``flash_attention_selfcheck`` passed on this chip for
    the kernels the resolved route runs, and for no others (the flag is then
    pinned, which is what the single-comm site asks for).
    A dense path whose ``[H, T, T]`` float32 logits would pass
    ``DENSE_LOGITS_LIMIT_BYTES`` raises: at such a size the oracle is not a
    fall-back."""
    from dgraph_tpu import config as cfg
    from dgraph_tpu.parallel import sequence as seq

    world = comm.get_world_size()
    if mask is not None and comm.graph_axis is not None:
        raise NotImplementedError(
            f"the {mask.name} mask runs where one device holds the whole "
            f"sequence; world size {world} shards it (ROADMAP R11)")
    if comm.graph_axis is not None and attn_impl == "ring":
        return "ring"
    if comm.graph_axis is None:
        t_full, heads, prefix = t_local, num_heads, ""
    else:  # ulysses: the full sequence, a share of the heads, K and V
        # repeated to them
        t_full, heads, prefix, group = (
            t_local * world, num_heads // world, "ulysses+", 1)
    if cfg.flash_attention_enabled():
        cfg.set_flags(use_flash_attention=seq.flash_attention_selfcheck(
            mask, group, head_dim, v_head_dim, rows=t_full, dtype=dtype))
    view = jax.ShapeDtypeStruct((t_full, heads, head_dim), dtype)
    if seq._flash_applicable(view, require_pinned=comm.graph_axis is None,
                             mask=mask, group=group, v_head_dim=v_head_dim,
                             causal=mask is None):
        return prefix + ("splash" if mask is not None or seq._causal_splash(
            t_full, head_dim, v_head_dim or head_dim, group, dtype)
            else "flash")
    if heads * t_full * t_full * 4 > DENSE_LOGITS_LIMIT_BYTES:
        raise RuntimeError(
            f"attention over T={t_full} with {heads} heads would materialise "
            f"{heads * t_full * t_full * 4 / 1e9:.1f} GB of logits in the dense "
            f"oracle, and no Mosaic kernel is engaged (backend "
            f"{jax.default_backend()!r}, use_flash_attention="
            f"{cfg.use_flash_attention!r}, self-checks latched: flash "
            f"{seq._flash_verified}, splash {sorted(seq._splash_verified, key=str)}); "
            f"shard the sequence (ring) or repair the kernel")
    return prefix + "dense"


# --- the loss -----------------------------------------------------------------

def next_token_targets(tokens: jax.Array, comm, seq_len: int):
    """(targets, valid) for this shard's tokens ``[T_loc]``: position ``i``
    predicts token ``i + 1``; the shard's last position takes the right
    neighbour's first token, and the globally last position is not scored."""
    t_loc = tokens.shape[0]
    if comm.graph_axis is None:
        nxt, offset = tokens[:1], 0
    else:
        world = comm.get_world_size()
        left = [(i, (i - 1) % world) for i in range(world)]
        nxt = lax.ppermute(tokens[:1], comm.graph_axis, left)
        offset = lax.axis_index(comm.graph_axis) * t_loc
    targets = jnp.concatenate([tokens[1:], nxt])
    valid = offset + jnp.arange(t_loc) < seq_len - 1
    return targets, valid


def exit_distribution(gate_logits: jax.Array) -> jax.Array:
    """log p ``[R, ...]`` of the exit step, from the gate logits of the first
    ``R - 1`` passes (``[R - 1, ...]``): ``log p_t = log lambda_t +
    sum_{j<t} log(1 - lambda_j)``, and the last pass takes what is left."""
    g = gate_logits.astype(jnp.float32)
    log_stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)  # sum_{j<=t}
    before = jnp.concatenate([jnp.zeros_like(g[:1]), log_stay[:-1]], axis=0)
    return jnp.concatenate(
        [jax.nn.log_sigmoid(g) + before, log_stay[-1:]], axis=0)


def exit_weights(gate_logits: jax.Array, beta: float):
    """(``p`` ``[R, ...]``, ``-beta H(p)`` ``[...]``) from the gate logits of
    the first ``R - 1`` passes: the weight each pass's cross-entropy carries
    and the entropy term of the per-position loss ``sum_t p_t ce_t - beta
    H(p)`` (``H`` the entropy of the exit distribution)."""
    logp = exit_distribution(gate_logits)
    p = jnp.exp(logp)
    return p, beta * (p * logp).sum(0)


def loss_block_size(t_local: int, vocab: int) -> int:
    """The largest divisor of ``t_local`` whose float32 logits block
    ``[block, vocab]`` stays within ``LOSS_BLOCK_BYTES``."""
    most = max(1, LOSS_BLOCK_BYTES // (4 * vocab))
    return max(b for b in range(1, t_local + 1)
               if t_local % b == 0 and b <= most)


def _block_cross_entropy(head_fn, head, h, tgt):
    """``logsumexp(logits) - logits[tgt]`` ``[block]`` of one block's float32
    logits ``head_fn(h, *head)`` ``[block, vocab]``."""
    logits = head_fn(h, *head)
    with jax.named_scope("dgraph.lm.cross_entropy"):
        lse = jax.nn.logsumexp(logits, axis=-1)
        with jax.named_scope("target_logit"):
            hit = jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
        return lse - hit


def _varying_like(x, like):
    """``x`` varying over the manual axes ``like`` varies over (inside
    ``shard_map``; nothing outside): where this cast of a parameter is
    transposed, the shards' partial cotangents are summed."""
    axes = jax.typeof(like).vma - jax.typeof(x).vma
    return lax.pcast(x, tuple(axes), to="varying")


def _loss_blocks(hs, targets, w, block):
    """The R x T/block blocks of (``hs`` ``[R, T, d]``, ``targets`` ``[T]``,
    ``w`` ``[R, T]``), the loops' leading axis."""
    R, T, d = hs.shape
    nb = T // block
    return (hs.reshape(R * nb, block, d),
            jnp.tile(targets.reshape(nb, block), (R, 1)),
            w.reshape(R * nb, block))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _weighted_ce(head_fn, block, head, hs, targets, w):
    h, tgt, _ = _loss_blocks(hs, targets, w, block)
    ce = lax.map(lambda a: _block_cross_entropy(head_fn, head, *a), (h, tgt))
    return (w * ce.reshape(w.shape)).sum()


def _weighted_ce_fwd(head_fn, block, head, hs, targets, w):
    """One pass over the blocks: a block's logits give its cross-entropy and,
    pulled back from its weights, its share of the head leaves' cotangent
    (summed in float32, last block first as a scan's transpose sums it) and
    the cotangent of its exit states. The loss is linear in all three, so
    the backward pass only scales them."""

    def one(acc, args):
        h, tgt, wb = args
        ce, pull = jax.vjp(
            lambda head, h: _block_cross_entropy(head_fn, head, h, tgt),
            head, h)
        d_head, dh = pull(wb)
        return [a + g.astype(a.dtype) for a, g in zip(acc, d_head)], (ce, dh)

    d_head, (ce, dh) = lax.scan(
        one, [_varying_like(jnp.zeros(a.shape, jnp.float32), a) for a in head],
        _loss_blocks(hs, targets, w, block), reverse=True)
    ce = ce.reshape(w.shape)
    d_head = [g.astype(a.dtype) for g, a in zip(d_head, head)]
    return (w * ce).sum(), (d_head, dh.reshape(hs.shape), ce)


def _weighted_ce_bwd(head_fn, block, res, c):
    d_head, dh, ce = res
    return ([(c * g).astype(g.dtype) for g in d_head],
            (c * dh).astype(dh.dtype), None, c * ce)


_weighted_ce.defvjp(_weighted_ce_fwd, _weighted_ce_bwd)


def weighted_cross_entropy(logits_fn: Callable, hs: jax.Array,
                           targets: jax.Array, w: jax.Array,
                           block: int) -> jax.Array:
    """``sum(w * ce)`` with ``ce[t, i] = logsumexp(logits_fn(hs[t, i])) -
    logits_fn(hs[t, i])[targets[i]]`` for ``hs`` ``[R, T, d]`` and weights
    ``w`` ``[R, T]``, one ``[block, vocab]`` logits tensor at a time
    (``lax.map`` over the R x T/block blocks). Equal to the direct
    computation. Differentiated, the one loop also takes each block's
    pull-back while its logits are there (three head products a block, no
    recomputation); the array leaves ``logits_fn`` closes over (the head's
    kernel, or the tied embedding) are found by ``jax.closure_convert`` and
    are the only parameters that get a cotangent here."""
    # traced on a block that varies over no axis, so that the converted
    # function holds no cast of its own and takes the leaves cast here
    head_fn, head = jax.closure_convert(
        logits_fn, jnp.zeros((block, hs.shape[-1]), hs.dtype))
    head = [_varying_like(a, hs) for a in head]
    return _weighted_ce(head_fn, block, head, hs, targets, w)


def _is_looped(model) -> bool:
    return hasattr(model, "hidden") and hasattr(model, "loop_steps")


def _has_experts(model) -> bool:
    return getattr(model, "experts", None) is not None


def hidden_states(model, params, tokens, positions):
    """(the exit states ``[passes, T, d]`` of a looped model, its expert
    layers' counts ``[passes, layers, 6]`` or None for dense FFNs)."""
    out = model.apply(params, tokens, positions, method="hidden")
    return out if _has_experts(model) else (out, None)


def expert_counts(stats: jax.Array) -> jax.Array:
    """One step's counts, int32 ``[6]`` (``parallel.expert.HELD_STATS``), of
    the expert layers' ``[passes, layers, 6]``: sums over the layers; the
    most rows one expert got and the most rows one layer put into its buffer
    are maxima."""
    from dgraph_tpu.parallel.expert import HELD_STATS_MAX

    stats = stats.reshape(-1, stats.shape[-1])
    most = jnp.asarray(HELD_STATS_MAX)
    return stats.sum(0).at[most].set(stats[:, most].max(0))


def local_loss_sum(model, params, tokens, comm, *, seq_len: int,
                   beta: float = 0.0, loss_block: Optional[int] = None):
    """(sum over this shard's scored positions of the per-position loss,
    the model's own auxiliary loss, its expert layers' counts or None): per
    shard, inside ``shard_map`` where the communicator has an axis."""
    t_loc = tokens.shape[0]
    rank = 0 if comm.graph_axis is None else lax.axis_index(comm.graph_axis)
    positions = rank * t_loc + jnp.arange(t_loc, dtype=jnp.int32)
    targets, valid = next_token_targets(tokens, comm, seq_len)
    if _is_looped(model):
        hs, stats = hidden_states(model, params, tokens, positions)
        gated = model.exit_gate and model.loop_steps > 1
        if not gated:
            hs = hs[-1:]  # the last pass exits with certainty
        with jax.named_scope("dgraph.lm.exit_loss"):
            scored = valid.astype(jnp.float32)
            w, total = scored[None], 0.0
            if gated:
                gates = model.apply(params, hs[:-1], method="gate_logit")
                p, entropy = exit_weights(gates, beta)
                w, total = p * scored, (scored * entropy).sum()
            total += weighted_cross_entropy(
                lambda h: model.apply(params, h, method="logits"),
                hs, targets, w,
                loss_block or loss_block_size(t_loc, model.vocab))
        return total, 0.0, stats
    aux = 0.0
    if getattr(model, "moe_k", 0) > 0:
        logits, mut = model.apply(params, tokens, positions,
                                  mutable=["losses"])
        aux = sum(jnp.sum(v) for v in jax.tree.leaves(mut))
    else:
        logits = model.apply(params, tokens, positions)
    with jax.named_scope("dgraph.lm.exit_loss"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        per_pos = -jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0]
    return jnp.where(valid, per_pos, 0.0).sum(), aux, None


def _is_block_diffusion(model) -> bool:
    return bool(getattr(model, "block_length", 0))


def block_diffusion_loss_sum(model, params, batch, comm, *,
                             loss_block: Optional[int] = None):
    """(sum over the noised tokens of ``weight * CE(logits(xt row i),
    x0_i)``, the expert layers' counts or None) for one sequence
    ``batch = (tokens [L] int32, masked [L] bool, weight [L] float32)``,
    noised on the host with the batch: ``xt`` is ``tokens`` with the masked
    positions replaced by ``model.mask_token``, and ``weight`` is ``1 / t_b``
    of the token's block. The stack runs once over the ``2L`` rows
    ``[xt ; x0]`` (row i of either copy at position i) under
    ``BlockDiffusionMask(L, model.block_length)``; the head and the
    cross-entropy (no shift) run over the ``L`` noised rows only, in blocks."""
    tokens, masked, weight = batch  # (a sharded sequence: seq_attention refuses)
    L = tokens.shape[0]
    rows = jnp.concatenate(
        [jnp.where(masked, jnp.int32(model.mask_token), tokens), tokens])
    positions = jnp.tile(jnp.arange(L, dtype=jnp.int32), 2)
    hs, stats = hidden_states(model, params, rows, positions)
    with jax.named_scope("dgraph.lm.exit_loss"):
        w = jnp.where(masked, weight.astype(jnp.float32), 0.0)
        total = weighted_cross_entropy(
            lambda h: model.apply(params, h, method="logits"),
            hs[-1:, :L], tokens, w[None],
            loss_block or loss_block_size(L, model.vocab))
    return total, stats


def make_lm_loss(model, mesh, comm, *, seq_len: int, beta: float = 0.0,
                 loss_block: Optional[int] = None, aux_weight: float = 0.0,
                 param_specs=None):
    """``(params, tokens [T]) -> loss``: the mean over the ``T - 1`` scored
    positions, under ``shard_map`` where the sequence is sharded; for a
    block-diffusion model ``(params, (tokens, masked, weight)) -> loss``, the
    weighted sum over ``T``. A model with expert layers (``model.experts``)
    gives ``(loss, expert_counts)``, whatever its objective."""
    counted = _has_experts(model)
    if counted and comm.graph_axis is not None:
        raise NotImplementedError(
            "a LoopLM's expert layers over a mesh axis need their kernels "
            "sharded over it and their counts summed; the trainer holds one "
            "rank's share of the experts today (ROADMAP R9)")

    def body(params, batch):
        if _is_block_diffusion(model):
            total, stats = block_diffusion_loss_sum(
                model, params, batch, comm, loss_block=loss_block)
            loss = total / seq_len
        else:
            total, aux, stats = local_loss_sum(
                model, params, batch, comm, seq_len=seq_len, beta=beta,
                loss_block=loss_block)
            if comm.graph_axis is not None:
                total = lax.psum(total, comm.graph_axis)
            loss = total / (seq_len - 1) + aux_weight * aux
        return (loss, expert_counts(stats)) if counted else loss

    if comm.graph_axis is None:
        return body
    from dgraph_tpu.comm.collectives import shard_map_checks

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P() if param_specs is None else param_specs,
                  P(comm.graph_axis)),
        out_specs=P(),
        **shard_map_checks(relax="the neighbour-token ppermute and the MoE "
                                 "psum_scatter are replicated by construction"),
    )


def _leave_buffers_alone(updates):
    """Zero the update of every leaf that is a buffer by name
    (``models.looplm.FROZEN_LEAVES``: an expert router's selection bias): no
    gradient reaches it, and neither does weight decay. A masked update; the
    optimizer's state keeps the leaf (its moments stay zero). A tree without
    such a leaf passes through as it is."""
    from dgraph_tpu.models.looplm import FROZEN_LEAVES

    return jax.tree_util.tree_map_with_path(
        lambda path, u: jnp.zeros_like(u)
        if getattr(path[-1], "key", None) in FROZEN_LEAVES else u, updates)


def _probe_rows(model, tokens):
    """(rows, positions) of a probe sequence ``tokens`` as the model takes
    them; a block-diffusion model's rows are two copies."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    if _is_block_diffusion(model):
        tokens, positions = jnp.tile(tokens, 2), jnp.tile(positions, 2)
    return tokens, positions


def _outside_the_stack(model, params) -> list:
    """Per leaf of ``params``, whether the stack (``hidden``) leaves it
    unread: an untied head, the exit gate. Such a leaf's gradient is whole
    once the exit loss is differentiated, before the layers' backward pass
    starts. Read off a trace of ``hidden`` itself on a short probe sequence
    (which leaves it reads depends on the length no more than a parameter's
    shape does), not off a leaf's name."""
    jaxpr = jax.make_jaxpr(lambda p: hidden_states(model, p, *_probe_rows(
        model, jnp.zeros((INIT_PROBE_TOKENS,), jnp.int32)))[0])(params).jaxpr
    read = {v for eqn in jaxpr.eqns for v in eqn.invars
            if isinstance(v, Var)}
    return [v not in read for v in jaxpr.invars]


def _gradients_read(update: Callable, grads, *rest) -> list:
    """For each output leaf of ``update(grads, *rest)``, the set of
    ``grads``' leaves (by position) it is computed from; an equation that
    holds a jaxpr of its own counts as reading all its inputs for all its
    outputs."""
    jaxpr = jax.make_jaxpr(update)(grads, *rest).jaxpr
    reads = {v: {i} for i, v in enumerate(
        jaxpr.invars[:len(jax.tree.leaves(grads))])}
    none = frozenset()
    for eqn in jaxpr.eqns:
        got = none.union(*(reads.get(v, none) for v in eqn.invars
                           if isinstance(v, Var)))
        reads.update((v, got) for v in eqn.outvars)
    return [reads.get(v, none) if isinstance(v, Var) else none
            for v in jaxpr.outvars]


def make_lm_train_step(model, optimizer: optax.GradientTransformation, mesh,
                       comm, *, seq_len: int, beta: float = 0.0,
                       loss_block: Optional[int] = None,
                       aux_weight: float = 0.0, param_specs=None,
                       donate: bool = True, step_metrics: bool = False):
    """Jitted ``(params, opt_state, tokens [T]) -> (params, opt_state,
    StepMetrics)``. ``step_metrics`` (a build-time constant) adds the global
    gradient norm.

    The leaves the stack does not read (an untied head: its float32
    gradient, the size of the head, comes out of the exit loss's loop) are
    updated BEFORE the layers' backward pass, where the optimizer updates
    them from their own gradients alone (AdamW does; a clip by the global
    norm does not, and then nothing is done ahead): their gradients are
    pulled back first, the updated leaves are tied to the seed of the
    pull-back proper by one ``optimization_barrier``, and the compiler,
    which otherwise schedules every update after the last gradient, has to
    finish with those gradients before the layers' backward pass, whose
    temporaries then take their place. The values are those of one update
    of the whole tree."""
    loss_fn = make_lm_loss(
        model, mesh, comm, seq_len=seq_len, beta=beta, loss_block=loss_block,
        aux_weight=aux_weight, param_specs=param_specs)

    counted = _has_experts(model)

    def update(grads, opt_state, params):
        with jax.named_scope("dgraph.lm.optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(
                params, _leave_buffers_alone(updates)), opt_state

    def updated_ahead(pull, seed, opt_state, params):
        """(the seed tied to the leaves updated ahead, those leaves by their
        position among ``update``'s outputs), or the seed and nothing."""
        if comm.graph_axis is not None or not _is_looped(model):
            return seed, {}
        ahead = {i for i, out in enumerate(_outside_the_stack(model, params))
                 if out}
        reads = ahead and _gradients_read(update, params, opt_state, params)
        if not ahead or any(r & ahead and not r <= ahead for r in reads):
            return seed, {}
        (first,) = pull(seed)  # all but the leaves ahead is dead code
        flat, tree = jax.tree.flatten(first)
        with jax.named_scope("dgraph.lm.optimizer"):
            first = tree.unflatten([g if i in ahead else jnp.zeros_like(g)
                                    for i, g in enumerate(flat)])
            new = jax.tree.leaves(update(first, opt_state, params))
            return lax.optimization_barrier(
                (seed, {i: a for i, (a, r) in enumerate(zip(new, reads))
                        if r & ahead}))

    def lm_train_step(params, opt_state, tokens):
        out = jax.vjp(lambda p: loss_fn(p, tokens), params, has_aux=counted)
        loss, pull, counts = out if counted else (*out, None)
        seed, ahead = updated_ahead(
            pull, jnp.ones_like(loss), opt_state, params)
        (grads,) = pull(seed)
        gn = optax.global_norm(grads) if step_metrics else None
        new, tree = jax.tree.flatten(update(grads, opt_state, params))
        params, opt_state = tree.unflatten(
            [ahead.get(i, a) for i, a in enumerate(new)])
        return params, opt_state, StepMetrics(
            loss=loss, grad_norm=gn, moe_rows=counts)

    return jax.jit(lm_train_step, donate_argnums=(0, 1) if donate else ())


def make_lm_eval_step(model, mesh, comm, *, seq_len: int, beta: float = 0.0,
                      loss_block: Optional[int] = None, param_specs=None):
    """Jitted ``(params, tokens [T]) -> loss``: the forward pass and the
    training objective, no gradient."""
    loss_fn = make_lm_loss(
        model, mesh, comm, seq_len=seq_len, beta=beta, loss_block=loss_block,
        param_specs=param_specs)
    if _has_experts(model):
        return jax.jit(lambda params, batch: loss_fn(params, batch)[0])
    return jax.jit(loss_fn)


# --- set-up -------------------------------------------------------------------

def init_lm_params(model, mesh, comm, seed: int = 0, *,
                   param_specs_fn: Optional[Callable] = None):
    """``model.init`` on a short probe sequence (no parameter's shape
    depends on the sequence length), under ``shard_map`` where the
    communicator has an axis. Returns (params, param_specs or None):
    ``param_specs_fn(shapes)`` derives the per-leaf specs of leaves that are
    sharded over the graph axis (MoE experts)."""
    from dgraph_tpu.obs import spans

    world = comm.get_world_size()

    def init(tokens):
        return model.init(jax.random.key(seed), *_probe_rows(model, tokens))

    probe = jnp.zeros((INIT_PROBE_TOKENS * world,), jnp.int32)
    specs = None
    with spans.stage("setup.init_params") as st, jax.set_mesh(mesh):
        if comm.graph_axis is None:
            params = jax.jit(init)(probe)
        else:
            from dgraph_tpu.comm.collectives import shard_map_checks

            def sharded(out_specs):
                return jax.shard_map(
                    init, mesh=mesh, in_specs=P(comm.graph_axis),
                    out_specs=out_specs,
                    **shard_map_checks(relax="init outputs replicated (or "
                                             "per-expert) by construction"))

            if param_specs_fn is not None:
                specs = param_specs_fn(jax.eval_shape(sharded(P()), probe))
            params = jax.jit(sharded(P() if specs is None else specs))(probe)
        st.annotate(**tree_size(params))
    return params, specs


def layers_by_kind(kinds) -> dict:
    """How many of a stack's layers are of which kind (``models.looplm``'s
    ``"<mixer>+<ffn>"``): ``conv``, ``attention`` (every attending mixer),
    ``dense_ffn``, ``expert_ffn``, and, where the stack has any, ``ssm``,
    ``gmu``, ``window`` (attention under a window, differential or plain),
    ``attn_win`` (the plain ones of those), ``cross`` (attention
    on another layer's keys and values), ``ssd`` (Mamba-2 mixers) and the
    layers of one half: ``mixer_only`` (``"<mixer>+none"``) and
    ``experts_only`` (``"none+experts"``), and ``attn_mla`` (attention
    through a latent; among ``attention`` too)."""
    from dgraph_tpu.models.looplm import ATTENDING, WINDOWED, split_kind

    mixers = [split_kind(k)[0] for k in kinds]
    count = lambda *names: sum(m in names for m in mixers)
    out = {"conv": count("conv"), "attention": count(*ATTENDING),
           "dense_ffn": sum(k.endswith("+dense") for k in kinds),
           "expert_ffn": sum(k.endswith("+experts") for k in kinds)}
    more = {"ssm": count("ssm", "ssm_keep"), "gmu": count("gmu"),
            "window": count(*WINDOWED), "attn_win": count("attn_win"),
            "cross": count("cross"), "ssd": count("ssd"),
            "attn_mla": count("attn_mla"),
            "mixer_only": sum(k.endswith("+none") for k in kinds),
            "experts_only": sum(k == "none+experts" for k in kinds)}
    out.update({k: n for k, n in more.items() if n})
    return out


@dataclasses.dataclass
class LMTrainer:
    """What one launch holds, and the loop's step: host batch -> device,
    the jitted train step, the host blocks on the loss."""

    model: Any
    mesh: Any
    comm: Any
    seq_len: int
    params: Any
    opt_state: Any
    train_step: Callable
    eval_step: Callable
    startup: dict  # what ran: attention implementation, sizes
    steps_done: int = 0
    # the run's maxima so far, by stat: the most rows one expert got in one
    # layer, the most rows one layer put into its buffer
    expert_rows_max: dict = dataclasses.field(default_factory=dict)

    def feed(self, tokens):
        """One host batch onto the mesh, sharded over the graph axis: ``[T]``
        token ids, or for a block-diffusion model the three ``[T]`` arrays
        ``(tokens, masked, weight)``."""
        return jax.device_put(
            tokens, NamedSharding(self.mesh, P(GRAPH_AXIS)))

    def step(self, tokens) -> StepMetrics:
        """One step of the loop. Host-boundary spans (never inside the jitted
        step), one attribute read each while tracing is off."""
        from dgraph_tpu.obs import spans

        with spans.span("train.step", step=self.steps_done):
            with spans.span("host_feed"):
                toks = self.feed(tokens)
            with spans.span("step_dispatch"):
                self.params, self.opt_state, sm = self.train_step(
                    self.params, self.opt_state, toks)
            with spans.span("block"):
                jax.block_until_ready(sm.loss)
        if sm.moe_rows is not None:  # came back with the loss: no new sync
            self._count_expert_rows(np.asarray(sm.moe_rows))
        self.steps_done += 1
        return sm

    def _count_expert_rows(self, rows: np.ndarray) -> None:
        """The step's expert-layer counts into the registry: ``moe.rows_*``
        (``parallel.expert.HELD_STATS``) beside the rows routed anywhere and
        the rows the layers' buffers would hold at their last rung (what
        ``moe.rows_buffered``, the rungs taken, is a share of); the
        ``rows_max_*`` are maxima over the steps too: gauges."""
        from dgraph_tpu.parallel.expert import HELD_STATS, HELD_STATS_MAX

        default_registry.counter("moe.rows_routed", self.startup["moe_routes"])
        default_registry.counter("moe.buffer_rows_offered",
                                 self.startup["moe_buffer_rows_a_step"])
        for i, (name, v) in enumerate(zip(HELD_STATS, rows)):
            if i in HELD_STATS_MAX:
                most = max(self.expert_rows_max.get(name, 0), int(v))
                self.expert_rows_max[name] = most
                default_registry.gauge(f"moe.{name}", most)
            else:
                default_registry.counter(f"moe.{name}", float(v))

    def evaluate(self, tokens) -> jax.Array:
        """The training objective on one batch, forward only."""
        from dgraph_tpu.obs import spans

        with spans.span("train.eval", step=self.steps_done):
            with spans.span("host_feed"):
                toks = self.feed(tokens)
            with spans.span("step_dispatch"):
                loss = self.eval_step(self.params, toks)
            with spans.span("block"):
                jax.block_until_ready(loss)
        return loss


def lm_setup(model, optimizer: optax.GradientTransformation, mesh, comm, *,
             seq_len: int, seed: int = 0, beta: float = 0.0,
             loss_block: Optional[int] = None, aux_weight: float = 0.0,
             param_specs_fn: Optional[Callable] = None, donate: bool = True,
             step_metrics: bool = False, params=None) -> LMTrainer:
    """Everything a launch does once: choose the attention implementation
    (after the chip's self-check), initialise the parameters (``params``
    given: those instead, placed on the mesh) and the optimizer state on the
    mesh, build the steps. Stages ``setup.init_params`` (or ``setup.place``)
    and ``setup.init_opt_state``; counters ``lm.*`` (the layers by kind:
    ``lm.layers.conv / .attention / .dense_ffn / .expert_ffn``, and where the
    stack has them ``.ssm / .gmu / .window / .cross`` with ``lm.ssm.state /
    .inner / .chunk``, ``lm.attention.window / .v_head_dim``, ``.attn_win``
    with ``lm.attention.nope_layers`` (full-attention layers that take no
    positions beside windowed ones that do), ``.ssd / .mixer_only /
    .experts_only`` with ``lm.ssd.heads / .groups / .state / .chunk``;
    ``moe.shared_width`` beside the ``moe.*`` of a shared expert,
    ``moe.route_ahead_layers`` where the router reads its layer's input;
    ``.attn_mla`` with ``lm.attention.qk_head_dim / .v_head_dim / .kv_rank /
    .rope_dim``: the latent layers' q.k and value head sizes, which the
    attention implementation is resolved and self-checked at, and the
    latent's and the rotary key's widths); a
    stack of several masks (attention under a window and full, differential
    or plain) has each self-checked, and ``attn.mask_pairs / .tile_pairs``
    summed over them."""
    world = comm.get_world_size()
    if seq_len % world:
        raise ValueError(f"seq_len {seq_len} does not divide by world {world}")
    heads = getattr(model, "num_heads", 1)
    head_dim = getattr(model, "head_dim", None) or model.latent // heads
    mask = model.attention_mask(seq_len) \
        if hasattr(model, "attention_mask") else None
    kinds = model.layer_kinds() if hasattr(model, "layer_kinds") else None
    by_kind = None if kinds is None else layers_by_kind(kinds)
    masks = model.attention_masks(seq_len) \
        if hasattr(model, "attention_masks") else None
    group = heads // (getattr(model, "num_kv_heads", None) or heads)
    from dgraph_tpu import config as cfg

    # what the layers' q, k, v stream in (the kernels' VMEM is counted in it)
    dtype = jnp.dtype(cfg.resolve_compute_dtype(
        getattr(model, "dtype", None)) or jnp.float32)
    v_head_dim = None
    latent = model.latent() if hasattr(model, "latent") else None
    if latent is not None:  # one key and one value head a query head
        head_dim, v_head_dim, group = (
            latent.qk_head_dim, latent.v_head_dim, 1)
    if by_kind is not None and not by_kind["attention"]:
        attention = "none"  # a stack of convolutions attends nowhere
    elif masks:  # a mask a layer: each distinct one is resolved, in order
        from dgraph_tpu.models.looplm import DIFF_MIXERS, split_kind
        from dgraph_tpu.parallel.sequence import CausalMask

        # differential attention: a map's values are [v1 ; v2]
        v_head_dim = 2 * head_dim if any(
            split_kind(k)[0] in DIFF_MIXERS for k in kinds) else None
        attention = "/".join(dict.fromkeys(
            resolve_attention(
                comm, model.attn_impl, seq_len // world, heads, head_dim,
                None if isinstance(m, CausalMask) else m, group,
                v_head_dim=v_head_dim, dtype=dtype)
            for m in dict.fromkeys(masks)))
    else:
        attention = resolve_attention(
            comm, model.attn_impl,
            seq_len // world if mask is None else mask.rows, heads, head_dim,
            mask, group, v_head_dim=v_head_dim, dtype=dtype)
    specs = None
    if params is None:
        params, specs = init_lm_params(
            model, mesh, comm, seed, param_specs_fn=param_specs_fn)
    else:  # a caller's own (a checkpoint's): onto the mesh, replicated
        from dgraph_tpu.obs import spans

        with spans.stage("setup.place", **tree_size(params)):
            params = jax.device_put(params, NamedSharding(mesh, P()))
    opt_state = init_opt_state(optimizer, params, mesh)
    loops = getattr(model, "loop_steps", 1)
    startup = {
        "attention": attention, "world_size": world, "seq_len": seq_len,
        "layers_held": model.num_layers, "loop_steps": loops,
        "layer_applications": model.num_layers * loops,
        "parameters": sum(int(np.prod(a.shape))
                          for a in jax.tree.leaves(params)),
    }
    for name in ("layers_held", "loop_steps", "layer_applications"):
        default_registry.counter(f"lm.{name}", startup[name])
    default_registry.counter("lm.tokens_per_step", seq_len)
    default_registry.counter(f"lm.attention.{attention}")
    expert_layers = model.num_layers
    if by_kind is not None:
        startup["layers_by_kind"] = by_kind
        for kind, n in by_kind.items():
            default_registry.counter(f"lm.layers.{kind}", n)
        default_registry.counter("lm.attention.head_dim", head_dim)
        if by_kind["conv"]:
            default_registry.counter("lm.conv.kernel_size", model.conv_kernel)
        if by_kind.get("ssm"):
            from dgraph_tpu.ops.selective_scan import SCAN_CHUNK

            default_registry.counter("lm.ssm.state", model.ssm.state)
            default_registry.counter("lm.ssm.inner", model.ssm.inner)
            default_registry.counter("lm.ssm.chunk",
                                     model.ssm.chunk or SCAN_CHUNK)
        if by_kind.get("ssd"):
            from dgraph_tpu.ops.ssd import SSD_CHUNK

            for name in ("heads", "groups", "state"):
                default_registry.counter(f"lm.ssd.{name}",
                                         getattr(model.ssd, name))
            default_registry.counter("lm.ssd.chunk",
                                     model.ssd.chunk or SSD_CHUNK)
        if by_kind.get("window"):
            default_registry.counter("lm.attention.window", model.window)
        if v_head_dim:
            default_registry.counter("lm.attention.v_head_dim", v_head_dim)
        if latent is not None:
            startup["latent_attention"] = {
                "qk_head_dim": head_dim, "kv_rank": latent.kv_rank,
                "rope_dim": latent.rope_dim}
            for name, v in startup["latent_attention"].items():
                default_registry.counter(f"lm.attention.{name}", v)
        if by_kind.get("attn_win") and not model.full_attn_rope:
            nope = sum(k.startswith("attn+") for k in kinds)
            startup["nope_layers"] = nope
            default_registry.counter("lm.attention.nope_layers", nope)
        expert_layers = by_kind["expert_ffn"]
    if mask is not None:
        masks = [mask]
    if masks:  # pairs the masks allow / pairs in the tiles visited
        from dgraph_tpu.parallel.sequence import flash_tile

        rows = masks[0].rows  # a kernel path skips whole tiles, the dense
        # oracle none (a stack may take the flash kernels under its causal
        # mask, at a shape past the one backward kernel's budget, and the
        # splash kernels under its window)
        tile = flash_tile(rows) if set(attention.split("/")) <= {
            "splash", "flash"} else rows
        startup.update(
            attention_mask="+".join(dict.fromkeys(m.name for m in masks)),
            mask_pairs=sum(m.pairs() for m in masks),
            tile_pairs=sum(m.tile_pairs(tile) for m in masks))
        default_registry.counter("attn.mask_pairs", startup["mask_pairs"])
        default_registry.counter("attn.tile_pairs", startup["tile_pairs"])
    experts = getattr(model, "experts", None)
    if experts is not None:
        rows = (mask.rows if mask is not None else seq_len)
        from dgraph_tpu.parallel.expert import buffer_rows

        startup.update(
            experts_held=experts.n_held, experts_total=experts.n_total,
            moe_routes=rows * experts.k * expert_layers * loops,
            moe_buffer_rows=buffer_rows(  # one layer's, at its last rung
                rows, experts.k, experts.n_held, experts.rows))
        startup["moe_buffer_rows_a_step"] = (
            startup["moe_buffer_rows"] * expert_layers * loops)
        default_registry.counter("moe.experts_held", experts.n_held)
        default_registry.counter("moe.experts_total", experts.n_total)
        default_registry.counter("moe.buffer_rows",
                                 startup["moe_buffer_rows"])
        if experts.shared_width:
            startup["moe_shared_width"] = experts.shared_width
            default_registry.counter("moe.shared_width", experts.shared_width)
        if experts.router_reads == "layer_input":
            startup["moe_route_ahead_layers"] = expert_layers
            default_registry.counter("moe.route_ahead_layers", expert_layers)
    kw = dict(seq_len=seq_len, beta=beta, loss_block=loss_block,
              param_specs=specs)
    return LMTrainer(
        model=model, mesh=mesh, comm=comm, seq_len=seq_len, params=params,
        opt_state=opt_state, startup=startup,
        train_step=make_lm_train_step(
            model, optimizer, mesh, comm, aux_weight=aux_weight,
            donate=donate, step_metrics=step_metrics, **kw),
        eval_step=make_lm_eval_step(model, mesh, comm, **kw))


def fit_lm(model, optimizer: optax.GradientTransformation,
           batches: Iterable[np.ndarray], *, seq_len: int,
           world_size: int = 1, steps: int, seed: int = 0,
           log: Optional[Callable[[dict], None]] = None, log_every: int = 0,
           **setup):
    """The training driver: ``lm_setup`` then ``steps`` steps of
    :meth:`LMTrainer.step` over ``batches`` (host arrays ``[seq_len]`` of
    token ids). ``model`` was built with ``lm_comm(world_size)``. Returns
    (trainer, history of per-step records)."""
    import time

    mesh = lm_mesh(world_size)
    trainer = lm_setup(model, optimizer, mesh, model.comm, seq_len=seq_len,
                       seed=seed, **setup)
    if log is not None:
        log({"kind": "lm_startup", **trainer.startup})
    history = []
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        for i, tokens in zip(range(steps), batches):
            sm = trainer.step(tokens)
            rec = sm.record(
                step=i, seq_len=seq_len, world=world_size,
                ms_per_step=(time.perf_counter() - t0) / (i + 1) * 1e3)
            history.append(rec)
            if log is not None and log_every and (
                    i % log_every == 0 or i == steps - 1):
                log(rec)
    return trainer, history
