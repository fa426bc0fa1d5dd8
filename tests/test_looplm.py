"""The looped LM (models/looplm.py) and its trainer (train/lm.py) on the CPU
at tiny sizes, seeded weights: against the benchmark's plain reference, the
loop against the stack applied by hand, the sequence-sharded model against
one device, and the blockwise exit loss against the direct one."""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from dgraph_tpu.models.looplm import LoopLM, apply_rotary, rotary_tables
from dgraph_tpu.models.norm import RMSNorm
from dgraph_tpu.train import lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # the benchmark's reference, builder and work counts
    sys.path.insert(0, ROOT)
T, V = 64, 97
SIZE = {  # the reference's keys (the configuration's names)
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 8, "intermediate_size": 48, "vocab_size": V,
    "num_hidden_layers": 3, "total_ut_steps": 4, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "exit_gate": True, "exit_beta": 0.1,
    "learning_rate": 3e-4, "warmup_steps": 2000, "beta1": 0.9, "beta2": 0.95, "weight_decay": 0.1,
}


def build(comm, loop_steps=4, exit_gate=True, attn_impl="ring", dtype=None,
          remat=True, kv_heads=4, **kw):
    return LoopLM(
        vocab=V, hidden_size=32, num_layers=3, num_heads=4, head_dim=8,
        intermediate=48, comm=comm, num_kv_heads=kv_heads,
        loop_steps=loop_steps, exit_gate=exit_gate, rms_eps=1e-6,
        rope_theta=1e6, dtype=dtype, attn_impl=attn_impl, remat=remat, **kw)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, V, T), jnp.int32)


def seeded_for(model, tokens):
    """Seeded weights in the program's tree (no leaf inert: norm gains off 1,
    a gate bias), as the benchmark makes them."""
    from benchmark.builders.looplm import seeded_lm_params

    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), tokens, jnp.arange(T)))
    return seeded_lm_params(shapes, 11, None)


@pytest.fixture(scope="module")
def seeded(tokens):
    return seeded_for(build(lm.lm_comm(1)), tokens)


@pytest.fixture(scope="module")
def reference():
    from benchmark.reference import looplm

    return looplm


def single_loss(model, params, tokens, **kw):
    total, _, _ = lm.local_loss_sum(
        model, params, tokens, model.comm, seq_len=T, **kw)
    return total / (T - 1)


# --- against the plain reference ------------------------------------------------
# float32 on both sides. The program sums in another order (flax Dense, the
# blockwise loss, log-space exit distribution against the reference's
# products), so agreement is to float32 rounding through 12 layer
# applications: 2e-5 relative on logits of order 1, 1e-5 on the loss, and
# 2e-4 of a leaf's norm on its gradient (sums over 64 positions x 4 uses).

def test_each_pass_logits_match_reference(seeded, tokens, reference):
    model = build(lm.lm_comm(1))
    logits, gates = model.apply(seeded, tokens, jnp.arange(T))
    with jax.default_matmul_precision("highest"):
        hs = reference.hidden_states(seeded, tokens, SIZE, lambda a: a)
        want = hs @ seeded["params"]["head"]["kernel"]
        lam = jax.nn.sigmoid(
            (hs @ seeded["params"]["gate"]["kernel"])[..., 0]
            + seeded["params"]["gate"]["bias"])
    assert logits.shape == (4, T, V)
    for t in range(4):
        np.testing.assert_allclose(logits[t], want[t], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(jax.nn.sigmoid(gates), lam, rtol=2e-5, atol=2e-6)


def test_exit_distribution_matches_reference(reference):
    g = jnp.asarray(np.random.default_rng(1).normal(size=(3, 50)) * 3, jnp.float32)
    p = jnp.exp(lm.exit_distribution(g))
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        p, reference.exit_probabilities(jax.nn.sigmoid(g)), rtol=1e-5, atol=1e-7)


def test_loss_and_every_gradient_leaf_match_reference(seeded, tokens, reference):
    model = build(lm.lm_comm(1))
    loss, grads = jax.value_and_grad(
        lambda p: single_loss(model, p, tokens, beta=0.1))(seeded)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(
            lambda p: reference.loss_fn(p, tokens, SIZE, lambda a: a))(seeded)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    ref = dict(jax.tree_util.tree_flatten_with_path(want_g)[0])
    assert len(flat) == len(ref) == 16
    for path, g in flat:
        scale = float(jnp.linalg.norm(ref[path]))
        assert scale > 0, path  # no leaf is inert
        assert float(jnp.linalg.norm(g - ref[path])) <= 2e-4 * scale, path


def test_reference_follows_adamw_like_the_trainer(seeded, tokens, reference):
    """Three steps of the trainer (float32 compute) against the reference's
    follow(): the losses and the update's norm per leaf."""
    model = build(lm.lm_comm(1))
    opt = optax.adamw(lambda c: 3e-4 * jnp.minimum(1.0, (c + 1) / 2000),
                      b1=0.9, b2=0.95, weight_decay=0.1)
    step = lm.make_lm_train_step(model, opt, None, model.comm, seq_len=T,
                                 beta=0.1, donate=False)
    params, state, losses = seeded, opt.init(seeded), []
    for _ in range(3):
        params, state, sm = step(params, state, tokens)
        losses.append(float(sm.loss))
    got = reference.follow(jax.device_get(seeded), [np.asarray(tokens)] * 3, SIZE)
    np.testing.assert_allclose(losses, got["loss"], rtol=2e-5)
    delta = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b)), params, seeded)
    for (path, d) in jax.tree_util.tree_flatten_with_path(delta)[0]:
        name = "/".join(str(k.key) for k in path)
        # Adam's first steps are sign-like: a leaf's update norm is
        # ~ sum of the warm-up's rates x sqrt(size), equal on both sides
        # to 1e-3 (the float32 rounding of p + update, ~1e-7 / 3e-7 a leaf)
        np.testing.assert_allclose(d, got["delta_norm"][name], rtol=1e-3)


# --- the leaves the stack does not read are updated ahead -------------------------

def _equations(jaxpr, primitive: str) -> list:
    """Every equation of that primitive, in sub-jaxprs too."""
    from dgraph_tpu.analysis.trace import walk_eqns

    found = []
    walk_eqns(jaxpr, lambda eqn: eqn.primitive.name.startswith(primitive)
              and found.append(eqn))
    return found


def _adamw():
    return optax.adamw(lambda c: 3e-4 * jnp.minimum(1.0, (c + 1) / 2000),
                       b1=0.9, b2=0.95, weight_decay=0.1)


# (the model, the optimizer, the shapes tied to the seed of the pull-back):
# an untied head's kernel and the gate's two leaves with their two moments
# each; under a tied head the gate's alone; under a clip by the global norm,
# which reads every gradient for every update, nothing
HEAD, GATE = [(32, V)] * 3, [(1,)] * 3 + [(32, 1)] * 3
AHEAD_CASES = {
    "untied": (dict(), _adamw, sorted(HEAD + GATE)),
    "tied": (dict(tie_head=True), _adamw, sorted(GATE)),
    "clipped": (dict(), lambda: optax.chain(
        optax.clip_by_global_norm(1.0), _adamw()), None),
}


@pytest.mark.parametrize("case", list(AHEAD_CASES))
def test_leaves_outside_the_stack_are_updated_ahead(tokens, case):
    """The train step ties the updated leaves that the stack does not read
    (and their moments) to the seed of the layers' pull-back with ONE barrier,
    where the optimizer updates a leaf from its own gradient alone; what the
    step returns is one update of the whole tree either way."""
    kw, make_opt, tied = AHEAD_CASES[case]
    model, opt = build(lm.lm_comm(1), **kw), make_opt()
    params = seeded_for(model, tokens)
    state = opt.init(params)
    step = lm.make_lm_train_step(model, opt, None, model.comm, seq_len=T,
                                 beta=0.1, loss_block=16, donate=False)
    found = _equations(jax.make_jaxpr(step)(params, state, tokens).jaxpr,
                       "optimization_barrier")
    if tied is None:
        assert not found
    else:
        (barrier,) = found
        assert sorted(v.aval.shape for v in barrier.invars
                      if v.aval.shape) == tied
    outside = lm._outside_the_stack(model, params)
    names = {"/".join(k.key for k in path[1:]) for (path, _), out in zip(
        jax.tree_util.tree_flatten_with_path(params)[0], outside) if out}
    assert names == {"gate/bias", "gate/kernel"} | (
        set() if kw else {"head/kernel"})

    loss, grads = jax.value_and_grad(lm.make_lm_loss(
        model, None, model.comm, seq_len=T, beta=0.1, loss_block=16))(
            params, tokens)
    updates, want_state = opt.update(grads, state, params)
    want = (optax.apply_updates(params, updates), want_state)
    got_params, got_state, sm = step(params, state, tokens)
    np.testing.assert_allclose(sm.loss, loss, rtol=1e-6)
    flat = jax.tree_util.tree_flatten_with_path((got_params, got_state))[0]
    for (path, a), b in zip(flat, jax.tree.leaves(want)):
        # (jitted against eager: float32 rounding of the gradients)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-8,
                                   err_msg=str(path))


# --- the loop is a parameter of one model ---------------------------------------

@pytest.mark.parametrize("R", [1, 2, 4])
def test_parameter_tree_does_not_depend_on_loop_steps(R, tokens):
    one = build(lm.lm_comm(1), loop_steps=1).init(
        jax.random.key(3), tokens, jnp.arange(T))
    many = build(lm.lm_comm(1), loop_steps=R).init(
        jax.random.key(3), tokens, jnp.arange(T))
    assert jax.tree.structure(one) == jax.tree.structure(many)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(many)):
        assert a.shape == b.shape
    count = sum(a.size for a in jax.tree.leaves(many))
    per_layer = 4 * 32 * 32 + 3 * 32 * 48 + 4 * 32
    assert count == 3 * per_layer + 32 + 2 * V * 32 + 32 + 1


@pytest.mark.parametrize("remat", [True, False])
def test_loop_equals_the_stack_applied_by_hand(seeded, tokens, remat):
    """R passes = the loop_steps=1 model's stack (with its final norm)
    applied R times to its own output, the same parameters each time."""
    comm = lm.lm_comm(1)
    looped = build(comm, loop_steps=4, remat=remat)
    hs = looped.apply(seeded, tokens, jnp.arange(T), method="hidden")
    single = build(comm, loop_steps=1, remat=remat)
    rope = rotary_tables(jnp.arange(T), 8, 1e6)

    def one_pass(h):
        return single.apply(seeded, h, rope,
                            method=lambda m, h_, r: m.stack(h_, r)[1][0])

    h = seeded["params"]["embed"]["embedding"][tokens]
    for t in range(4):
        h = one_pass(h)
        np.testing.assert_allclose(hs[t], h, rtol=1e-5, atol=1e-5)


def test_one_pass_without_gate_is_plain_next_token_cross_entropy(seeded, tokens):
    model = build(lm.lm_comm(1), loop_steps=1, exit_gate=False)
    params = {"params": {k: v for k, v in seeded["params"].items() if k != "gate"}}
    logits, gates = model.apply(params, tokens, jnp.arange(T))
    assert gates is None and logits.shape == (1, T, V)
    logp = jax.nn.log_softmax(logits[0, :-1])
    want = -jnp.take_along_axis(logp, tokens[1:, None], 1).mean()
    np.testing.assert_allclose(single_loss(model, params, tokens), want, rtol=1e-6)


def test_gate_off_scores_the_last_pass_only(seeded, tokens):
    model = build(lm.lm_comm(1), loop_steps=4, exit_gate=False)
    params = {"params": {k: v for k, v in seeded["params"].items() if k != "gate"}}
    logits, _ = model.apply(params, tokens, jnp.arange(T))
    logp = jax.nn.log_softmax(logits[-1, :-1])
    want = -jnp.take_along_axis(logp, tokens[1:, None], 1).mean()
    np.testing.assert_allclose(single_loss(model, params, tokens), want, rtol=1e-6)


# --- the blockwise exit loss ------------------------------------------------------

def direct_loss(model, p, batch, beta):
    """The objective from the whole ``[R, T, vocab]`` logits at once, for
    plain ``jax.grad``: the gated exit loss, the last pass's next-token
    cross-entropy, or block diffusion's weighted masked-token one."""
    log_softmax_at = lambda logits, tgt: -jnp.take_along_axis(
        jax.nn.log_softmax(logits), tgt[..., None], -1)[..., 0]
    if model.block_length:
        tokens, masked, weight = batch
        rows = jnp.concatenate([jnp.where(masked, model.mask_token, tokens),
                                tokens])
        hs = model.apply(p, rows, jnp.tile(jnp.arange(T), 2), method="hidden")
        ce = log_softmax_at(model.apply(p, hs[-1, :T], method="logits"), tokens)
        return jnp.where(masked, weight * ce, 0.0).sum() / T
    logits, gates = model.apply(p, batch, jnp.arange(T))
    ce = log_softmax_at(logits, jnp.concatenate([batch[1:], batch[:1]])[None])
    if gates is None or model.loop_steps == 1:
        return ce[-1, :-1].mean()
    lam = jax.nn.sigmoid(gates[:-1])
    stay = jnp.cumprod(1 - lam, 0)
    prob = jnp.concatenate(
        [lam * jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]]), stay[-1:]])
    per = (prob * ce).sum(0) + beta * (prob * jnp.log(prob)).sum(0)
    return per[:-1].mean()


# (the model, the loss block, the traced scalar the loss is multiplied by):
# gated with four exits / the last pass alone (gate off, or one pass), an
# untied head / the embedding's transpose, next-token / block-diffusion
# weights, one block / several
GATED, TIED = dict(), dict(tie_head=True)
LAST = dict(exit_gate=False)
DIFFUSION = dict(loop_steps=1, exit_gate=False, block_length=8,
                 mask_token=V - 1)
EXIT_LOSS_CASES = {
    "gated-untied-64": (GATED, T, 1.0),
    "gated-untied-16": (GATED, 16, 1.0),
    "gated-untied-8": (GATED, 8, 1.0),
    "gated-tied-16": (TIED, 16, 1.0),
    "gated-tied-8-scaled": (TIED, 8, 0.37),
    "last-untied-16": (LAST, 16, 1.0),
    "one_pass-tied-64": (dict(LAST, loop_steps=1, tie_head=True), T, 1.0),
    "diffusion-untied-8-scaled": (DIFFUSION, 8, -2.5),
    "diffusion-tied-64": (dict(DIFFUSION, tie_head=True), T, 1.0),
}


@pytest.mark.parametrize("case", list(EXIT_LOSS_CASES))
def test_blockwise_exit_loss_equals_direct(tokens, case):
    """The head applied in blocks, each block's pull-back taken in the loss's
    own loop, gives the loss and EVERY gradient leaf (head or embedding, gate,
    the stack's parameters through the exit states) of the direct computation
    over ``[R, T, vocab]``; times a traced scalar, so that the backward rule's
    incoming cotangent is not 1."""
    kw, block, scale = EXIT_LOSS_CASES[case]
    model = build(lm.lm_comm(1), **kw)
    params = seeded_for(model, tokens)
    if model.block_length:
        from benchmark.builders.sdar import noised_batch

        batch = tuple(jnp.asarray(a) for a in noised_batch(
            np.random.default_rng(3), T, V - 1, 1.0, 8, 1e-3))
    else:
        batch = tokens
    loss_fn = lm.make_lm_loss(model, None, model.comm, seq_len=T, beta=0.1,
                              loss_block=block)
    (want, want_g), (got, got_g) = (
        jax.value_and_grad(lambda p, s: s * f(p))(params, jnp.float32(scale))
        for f in (lambda p: direct_loss(model, p, batch, 0.1),
                  lambda p: loss_fn(p, batch)))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    flat = jax.tree_util.tree_flatten_with_path(got_g)[0]
    assert len(flat) == len(jax.tree.leaves(params))
    for (path, a), b in zip(flat, jax.tree.leaves(want_g)):
        assert float(jnp.abs(b).max()) > 0, path  # no leaf is inert
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-7 * abs(scale), err_msg=str(path))


def _vocab_products(jaxpr, loops=()):
    """(dot_general with the vocabulary as a dimension of an operand or of
    its result, the scan / while equations it lies in) of a jaxpr and of
    every jaxpr its equations hold."""
    for eqn in jaxpr.eqns:
        shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
        if eqn.primitive.name == "dot_general" and any(V in s for s in shapes):
            yield eqn, loops
        inner = loops + (eqn,) if eqn.primitive.name in ("scan", "while") \
            else loops
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _vocab_products(sub, inner)


@pytest.mark.parametrize("tie_head", [False, True], ids=["untied", "tied"])
def test_one_loop_applies_the_head_three_times_a_block(tokens, tie_head):
    """The counter that says the mechanism engaged. Differentiated, the head
    (the only products with the vocabulary as a dimension) runs inside ONE
    loop, three products a block (logits, their pull-back to the exit states
    and to the head), and nowhere else; the eval step's loop holds the one
    product and carries no cotangent; and of the parameters only the leaf
    that ``logits`` reads crosses into the loss's own derivative rule."""
    model = build(lm.lm_comm(1), tie_head=tie_head)
    params = seeded_for(model, tokens)
    kw = dict(seq_len=T, beta=0.1, loss_block=16)
    loss = lm.make_lm_loss(model, None, model.comm, **kw)

    found = list(_vocab_products(
        jax.make_jaxpr(jax.value_and_grad(loss))(params, tokens).jaxpr))
    assert len(found) == 3
    assert all(len(loops) == 1 for _, loops in found)
    (loop,) = {id(loops[0]): loops[0] for _, loops in found}.values()
    assert loop.params["length"] == 4 * T // 16  # every block of every exit
    head = (V, 32) if tie_head else (32, V)
    carried = loop.invars[loop.params["num_consts"]:][:loop.params["num_carry"]]
    assert [(v.aval.shape, v.aval.dtype) for v in carried] == [
        (head, jnp.float32)]  # the head's cotangent, summed over the blocks

    step = lm.make_lm_eval_step(model, None, model.comm, **kw)
    ((_, (loop,)),) = _vocab_products(
        jax.make_jaxpr(step)(params, tokens).jaxpr)
    assert loop.params["num_carry"] == 0

    (rule,) = [e for e in _equations(
        jax.make_jaxpr(loss)(params, tokens).jaxpr, "custom_vjp_call")
        if any(V in v.aval.shape for v in e.invars)]
    # the head's leaf, the exit states [R, T, d], the targets, the weights
    assert sorted(v.aval.shape for v in rule.invars) == sorted(
        [head, (4, T, 32), (T,), (4, T)])


def test_loss_block_size_divides_and_fits():
    assert lm.loss_block_size(8192, 49152) == 1024  # 201 MB of logits
    assert lm.loss_block_size(64, 97) == 64
    assert lm.loss_block_size(96, 1 << 22) == 16  # 16 x 4 Mi x 4 B = 256 MiB


# --- sequence-sharded = single device -------------------------------------------

def sharded(world, impl, seeded, tokens, **kw):
    devs = jax.devices()
    if len(devs) < world:
        pytest.skip(f"need {world} devices")
    mesh, comm = lm.lm_mesh(world, devs), lm.lm_comm(world)
    model = build(comm, attn_impl=impl, **kw)

    def hidden(p, tk):
        t_loc = tk.shape[0]
        pos = jax.lax.axis_index("graph") * t_loc + jnp.arange(t_loc)
        hs = model.apply(p, tk, pos, method="hidden")
        return model.apply(p, hs, method="logits")

    with jax.set_mesh(mesh):
        logits = jax.jit(jax.shard_map(
            hidden, mesh=mesh, in_specs=(P(), P("graph")),
            out_specs=P(None, "graph")))(seeded, tokens)
        loss, grads = jax.jit(jax.value_and_grad(lm.make_lm_loss(
            model, mesh, comm, seq_len=T, beta=0.1)))(seeded, tokens)
    return logits, loss, grads


@pytest.mark.parametrize("world,impl,tie_head", [
    (4, "ring", False), (8, "ring", False), (4, "ulysses", False),
    (4, "ring", True)], ids=["4-ring", "8-ring", "4-ulysses", "4-ring-tied"])
def test_sharded_model_matches_single_device(
        world, impl, tie_head, seeded, tokens, compiled_fresh):
    """Logits of every pass (rotary positions from the shard's global offset),
    the loss over all T - 1 positions, and every gradient leaf (summed over
    shards AND over the four uses of each weight; the head's, which the exit
    loss takes a shard at a time in its own loop, and under a tied head the
    embedding's, which sums the lookup's and the head's). Compiled fresh: the
    eight-device CPU executable loaded back from the persistent compilation
    cache aborts the process or gives a wrong loss."""
    model = build(lm.lm_comm(1), tie_head=tie_head)
    if tie_head:
        seeded = seeded_for(model, tokens)
    want_logits, _ = model.apply(seeded, tokens, jnp.arange(T))
    want, want_g = jax.value_and_grad(
        lambda p: single_loss(model, p, tokens, beta=0.1))(seeded)
    logits, loss, grads = sharded(world, impl, seeded, tokens,
                                  tie_head=tie_head)
    # online-softmax blocks sum in another order: float32 rounding
    np.testing.assert_allclose(logits, want_logits, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_g)):
        assert float(jnp.linalg.norm(a - b)) <= 5e-4 * float(jnp.linalg.norm(b))


def test_wrong_positions_change_the_logits(seeded, tokens):
    """The rotary embedding is live: local positions on a shard (the fault
    the global offset prevents) would not give these logits."""
    model = build(lm.lm_comm(1))
    a, _ = model.apply(seeded, tokens, jnp.arange(T))
    b, _ = model.apply(seeded, tokens, jnp.arange(T) % 16)
    assert float(jnp.abs(a - b).max()) > 1e-2


def test_next_token_targets_single():
    toks = jnp.arange(10, dtype=jnp.int32) + 5
    tgt, valid = lm.next_token_targets(toks, lm.lm_comm(1), 10)
    assert tgt[:-1].tolist() == list(range(6, 15))
    assert valid.tolist() == [True] * 9 + [False]


# --- pieces -------------------------------------------------------------------------

def test_rms_norm():
    x = jnp.asarray(np.random.default_rng(2).normal(size=(5, 16)), jnp.float32)
    m = RMSNorm(epsilon=1e-6)
    p = m.init(jax.random.key(0), x)
    assert list(p["params"]) == ["scale"]  # no bias
    y = m.apply(p, x)
    np.testing.assert_allclose(jnp.sqrt((y * y).mean(-1)), 1.0, rtol=1e-4)
    assert RMSNorm(dtype=jnp.bfloat16).apply(p, x).dtype == jnp.bfloat16


def test_rotary_is_a_rotation_of_relative_position():
    rng = np.random.default_rng(3)
    q, k = (jnp.asarray(rng.normal(size=(1, 1, 8)), jnp.float32) for _ in range(2))

    def score(i, j):
        cq, sq = rotary_tables(jnp.asarray([i]), 8, 1e4)
        ck, sk = rotary_tables(jnp.asarray([j]), 8, 1e4)
        return float((apply_rotary(q, cq, sq) * apply_rotary(k, ck, sk)).sum())

    np.testing.assert_allclose(score(7, 3), score(104, 100), rtol=1e-4)
    np.testing.assert_allclose(score(0, 0), float((q * k).sum()), rtol=1e-6)
    c, s = rotary_tables(jnp.asarray([5]), 8, 1e4)
    np.testing.assert_allclose(
        jnp.linalg.norm(apply_rotary(q, c, s)), jnp.linalg.norm(q), rtol=1e-6)


def test_grouped_query_heads_share_keys(tokens):
    model = build(lm.lm_comm(1), kv_heads=2)
    p = model.init(jax.random.key(0), tokens, jnp.arange(T))
    assert p["params"]["stack"]["layers"]["k_proj"]["kernel"].shape == (3, 32, 16)
    logits, _ = model.apply(p, tokens, jnp.arange(T))
    assert bool(jnp.isfinite(logits).all())


def test_bf16_compute_keeps_f32_params_and_logits(seeded, tokens):
    model = build(lm.lm_comm(1), dtype=jnp.bfloat16)
    logits, gates = model.apply(seeded, tokens, jnp.arange(T))
    assert logits.dtype == gates.dtype == jnp.float32
    hs = model.apply(seeded, tokens, jnp.arange(T), method="hidden")
    assert hs.dtype == jnp.bfloat16
    want, _ = build(lm.lm_comm(1)).apply(seeded, tokens, jnp.arange(T))
    assert float(jnp.abs(logits - want).max()) < 0.25  # bf16, 12 applications


def test_resolve_attention_names_and_refuses_a_large_dense():
    single = lm.lm_comm(1)
    assert lm.resolve_attention(single, "ring", 256, 4, 16) == "dense"
    assert lm.resolve_attention(lm.lm_comm(4), "ring", 2048, 16, 128) == "ring"
    assert lm.resolve_attention(lm.lm_comm(4), "ulysses", 64, 4, 16) \
        == "ulysses+dense"
    with pytest.raises(RuntimeError, match="dense"):
        lm.resolve_attention(single, "ring", 8192, 16, 128)  # 4.3 GB of logits


def test_fit_lm_trains_and_records_what_ran(tokens):
    from dgraph_tpu.obs import spans
    from dgraph_tpu.obs.metrics import default_registry

    default_registry.reset()
    model = build(lm.lm_comm(1))
    seen = []
    batches = (np.asarray(tokens) for _ in range(40))
    trainer, history = lm.fit_lm(
        model, optax.adam(3e-3), batches, seq_len=T, world_size=1, steps=30,
        beta=0.1, log=seen.append, log_every=10)
    assert history[-1]["loss"] < history[0]["loss"] - 0.5
    assert seen[0]["kind"] == "lm_startup" and seen[0]["attention"] == "dense"
    assert seen[0]["layer_applications"] == 12 and trainer.steps_done == 30
    counters = default_registry.snapshot()["counters"]
    assert counters["lm.layers_held"] == 3
    assert counters["lm.layer_applications"] == 12
    assert counters["lm.loop_steps"] == 4 and counters["lm.tokens_per_step"] == T
    assert counters["lm.attention.dense"] == 1
    stages = spans.stage_totals()
    assert {"setup.init_params", "setup.init_opt_state"} <= set(stages)
    assert float(trainer.evaluate(np.asarray(tokens))) < history[0]["loss"]


def test_trainer_spans_are_the_loops_own(tokens):
    from dgraph_tpu.obs import spans

    model = build(lm.lm_comm(1), loop_steps=1, exit_gate=False)
    trainer = lm.lm_setup(model, optax.sgd(1e-2), lm.lm_mesh(1), model.comm,
                          seq_len=T)
    trainer.step(np.asarray(tokens))  # compiles: compile.* spans stay out
    seen = []
    spans.enable(sink=seen.append)
    try:
        trainer.step(np.asarray(tokens))
    finally:
        spans.disable()
    seen = [r for r in seen if not r["name"].startswith("compile.")]
    names = [r["name"] for r in seen]
    assert names == ["host_feed", "step_dispatch", "block", "train.step"]
    parent = seen[-1]["span"]
    assert all(r["parent"] == parent for r in seen[:3])


def test_given_params_are_placed(seeded, tokens):
    from dgraph_tpu.obs import spans

    before = spans.stage_totals().get("setup.place", {"count": 0})["count"]
    model = build(lm.lm_comm(1))
    trainer = lm.lm_setup(model, optax.sgd(1e-2), lm.lm_mesh(1), model.comm,
                          seq_len=T, params=jax.device_get(seeded), beta=0.1)
    assert spans.stage_totals()["setup.place"]["count"] == before + 1
    assert np.isfinite(float(trainer.step(np.asarray(tokens)).loss))


# --- the benchmark's configuration and work counts --------------------------------

def test_configuration_holds_every_published_number():
    with open(os.path.join(ROOT, "benchmark", "configs", "ouro_2p6b.json")) as f:
        cfg = json.load(f)
    published = {
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-6, "rope_theta": 1000000, "total_ut_steps": 4,
        "early_exit_threshold": 1, "vocab_size": 49152}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == 48
    assert len(cfg["layer_types"]) == 48 and cfg["tie_word_embeddings"] is False
    # what is run is what is published, but for the depth
    for k in ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "intermediate_size", "vocab_size", "rms_norm_eps",
              "rope_theta", "total_ut_steps", "num_hidden_layers"):
        assert cfg["sizes"][k] == cfg[k], k
    assert 4 <= cfg["sizes"]["num_hidden_layers"] <= 8
    assert cfg["source"].startswith("https://huggingface.co/ByteDance/Ouro-2.6B")


def test_work_counts_by_hand():
    from benchmark import opsbytes

    info = {"seq_len": 8192, "heads": 16, "kv_heads": 16, "head_dim": 128,
            "hidden": 2048, "intermediate": 5632, "layers": 6, "loop_steps": 4}
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632  # 51.4 M
    assert opsbytes.work("looplm_dense_flops", info, 0) \
        == 3 * 2 * 8192 * per_layer * 24
    assert opsbytes.work("looplm_attn_flops", info, 0) \
        == 3 * 2 * 8192 * 8192 * 16 * 128 * 24
    tiny = dict(info, seq_len=4, heads=2, kv_heads=1, head_dim=3, hidden=5,
                intermediate=7, layers=1, loop_steps=2)
    assert opsbytes.work("looplm_dense_flops", tiny, 0) \
        == 6 * 4 * (2 * 5 * 6 + 2 * 5 * 3 + 3 * 5 * 7) * 2
    assert opsbytes.work("looplm_attn_flops", tiny, 0) == 6 * 16 * 2 * 3 * 2


def test_zipf_tokens_cover_the_vocabulary_by_rank():
    from benchmark.builders.looplm import zipf_tokens

    ids = zipf_tokens(np.random.default_rng(2**31 + 5), 200_000, 1000, 1.0)
    assert ids.dtype == np.int32 and ids.min() == 0 and ids.max() <= 999
    counts = np.bincount(ids, minlength=1000)
    # P(0) / P(9) = 10 under exponent 1
    assert 8.5 < counts[0] / counts[9] < 11.5
    assert (counts[500:] > 0).mean() > 0.95  # the tail is drawn too
    again = zipf_tokens(np.random.default_rng(2**31 + 5), 200_000, 1000, 1.0)
    assert (ids == again).all()
