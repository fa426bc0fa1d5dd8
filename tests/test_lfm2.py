"""A decoder of several layer kinds (``models/looplm.py``'s pattern stack: the
gated short convolution among attention layers, a dense FFN beside held
experts, a tied head), the sigmoid router with a selection bias
(``parallel/expert.py::route_topk``) and attention at a head narrower than the
lanes (``parallel/sequence.py``) on the CPU at tiny sizes, seeded weights:
against the benchmark's plain reference (``benchmark/reference/lfm2.py``), the
shares of an expert layer against the uncut layer, the convolution's halo over
a sharded sequence, and the programs of the configurations that were there
(by hash)."""

import hashlib
import json
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from dgraph_tpu.models import looplm
from dgraph_tpu.models.looplm import HeldExperts, LoopLM
from dgraph_tpu.parallel import expert as ex
from dgraph_tpu.parallel import sequence as seq
from dgraph_tpu.train import lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
T, V = 96, 97
PATTERN = ("conv+dense", "attn+experts", "conv+experts", "conv+experts")
SIZE = {  # the reference's keys (the configuration's names)
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_experts_total": 16, "num_experts": 4, "first_expert": 0,
    "num_experts_per_tok": 2, "routed_scaling_factor": 1, "vocab_size": V,
    "layer_pattern": list(PATTERN), "norm_eps": 1e-5, "rope_theta": 1e6,
    "learning_rate": 3e-4, "warmup_steps": 2000, "beta1": 0.9, "beta2": 0.95,
    "weight_decay": 0.1,
}


def build(comm, dtype=None, pattern=PATTERN, select_bias=True, tie_head=True):
    return LoopLM(
        vocab=V, hidden_size=32, num_layers=len(pattern), pattern=pattern,
        conv_kernel=3, tie_head=tie_head, num_heads=4, head_dim=8,
        intermediate=48, comm=comm, num_kv_heads=2, rms_eps=1e-5,
        rope_theta=1e6, dtype=dtype, sandwich_norm=False, qk_norm=True,
        experts=HeldExperts(16, 4, 2, 16, score="sigmoid",
                            select_bias=select_bias, gate_eps=1e-6))


@pytest.fixture(scope="module")
def tokens():
    from benchmark.builders.looplm import zipf_tokens

    return jnp.asarray(zipf_tokens(np.random.default_rng(3), T, V, 1.0))


@pytest.fixture(scope="module")
def seeded():
    from benchmark.builders.looplm import seeded_lm_params

    model = build(lm.lm_comm(1))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros(T, jnp.int32), jnp.arange(T)))
    params = seeded_lm_params(shapes, 11, None)
    # a bias large enough to move many rows' choice
    for run in ("layers_1", "layers_2"):
        params["params"]["stack"][run]["experts"]["select_bias"] *= 10.0
    params["params"]["embed"]["embedding"] *= 32 ** -0.5
    return params


@pytest.fixture(scope="module")
def reference():
    from benchmark.reference import lfm2

    return lfm2


def leaves(tree):
    return {"/".join(str(k.key) for k in path): a
            for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


# --- against the plain reference ------------------------------------------------

def test_loss_and_every_gradient_leaf_match_reference(seeded, tokens, reference):
    model = build(lm.lm_comm(1))
    loss_fn = lm.make_lm_loss(model, None, model.comm, seq_len=T)
    (loss, counts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        seeded, tokens)
    with jax.default_matmul_precision("highest"):
        (want, chosen), want_g = jax.value_and_grad(
            lambda p: reference.loss_fn(p, tokens, SIZE, lambda a: a),
            has_aux=True)(seeded)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    got, ref = leaves(grads), leaves(want_g)
    assert set(got) == set(ref) and len(ref) == 33
    assert "params/head/kernel" not in ref  # the head is the embedding
    for name, g in got.items():
        scale = float(jnp.linalg.norm(ref[name]))
        if name.endswith("select_bias"):
            assert scale == 0 and float(jnp.abs(g).max()) == 0, name
            continue
        assert scale > 0, name  # no other leaf is inert
        assert float(jnp.linalg.norm(g - ref[name])) <= 3e-4 * scale, name
    # the program's counts are the reference's routing, counted
    assert chosen.shape == (3, T, 2)
    assert int(counts[0]) == int((np.asarray(chosen) < 4).sum())
    assert int(counts[2]) == 0


def test_reference_follows_adamw_like_the_trainer(seeded, tokens, reference):
    """Three steps through ``LMTrainer.step``; the selection bias takes no
    update, from the gradient or from the weight decay."""
    model = build(lm.lm_comm(1))
    opt = optax.adamw(lambda c: 3e-4 * jnp.minimum(1.0, (c + 1) / 2000),
                      b1=0.9, b2=0.95, weight_decay=0.1)
    trainer = lm.lm_setup(model, opt, lm.lm_mesh(1), model.comm, seq_len=T,
                          params=jax.tree.map(jnp.array, seeded), donate=False)
    assert trainer.startup["layers_by_kind"] == {
        "conv": 3, "attention": 1, "dense_ffn": 1, "expert_ffn": 3}
    assert trainer.startup["moe_routes"] == T * 2 * 3
    losses = [float(trainer.step(np.asarray(tokens)).loss) for _ in range(3)]
    got = reference.follow(jax.device_get(seeded), [np.asarray(tokens)] * 3, SIZE)
    np.testing.assert_allclose(losses, got["loss"], rtol=3e-5)
    delta = leaves(jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b)), trainer.params, seeded))
    for name, d in delta.items():
        if name.endswith("select_bias"):
            assert d == 0 and got["delta_norm"][name] == 0, name
        else:
            np.testing.assert_allclose(d, got["delta_norm"][name], rtol=2e-3)


def test_program_against_reference_under_the_tiny_limits():
    """The cell's own comparison at its tiny preset (bf16 compute), as the
    harness makes it: loss, first gradient, three-step update."""
    from benchmark import run as harness

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    name = "lfm2_8b_a1b.seq16k"
    _, config, traffic = harness.find_cell(bench, name)
    cell = harness.build_cell(config, traffic, 2**31 + 34, jax.devices()[:1], True)
    with cell.context():
        got, _, _ = harness.first_steps(cell, harness.CompileWatch())
    cell.release()
    assert cell.rows_dropped == 0 and cell.chosen.shape == (4, 128, 2)
    rows = harness.compare(got, cell.reference(harness.CHECK_STEPS),
                           harness.cell_limits(name, True))
    assert all(ok for _, _, _, ok in rows), rows
    assert {n for n, _, lim, _ in rows if lim is not None} \
        == {"loss_gap", "delta_norm_gap", "grad_diff_gap"}


# --- the router's second form -------------------------------------------------------

def test_sigmoid_router_by_hand():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((3, 32)).astype(np.float32)
    bias = (0.5 * rng.standard_normal(32)).astype(np.float32)
    gates, experts = ex.route_topk(
        jnp.asarray(logits), 4, score="sigmoid", select_bias=jnp.asarray(bias),
        eps=1e-6, scale=2.0)
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    for t in range(3):
        want = np.argsort(-(s[t] + bias))[:4]
        assert list(np.asarray(experts[t])) == list(want)
        np.testing.assert_allclose(
            gates[t], 2.0 * s[t, want] / (s[t, want].sum() + 1e-6), rtol=1e-6)
    # the default is the softmax form, its guard and no scale
    g0, e0 = ex.route_topk(jnp.asarray(logits), 4)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, -1)[:, :4]
    assert (np.asarray(e0) == top).all()
    np.testing.assert_allclose(
        g0, np.take_along_axis(p, top, -1)
        / np.take_along_axis(p, top, -1).sum(-1, keepdims=True), rtol=1e-5)
    with pytest.raises(ValueError, match="unknown router score"):
        ex.route_topk(jnp.asarray(logits), 4, score="tanh")


def test_selection_bias_moves_the_choice_and_not_the_gate():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    bias = jnp.asarray(0.5 * rng.standard_normal(32), jnp.float32)
    route = lambda b: ex.route_topk(logits, 4, score="sigmoid", select_bias=b,
                                    eps=1e-6)
    g0, e0 = route(jnp.zeros(32))
    g1, e1 = route(bias)
    assert (np.asarray(e0) != np.asarray(e1)).any()  # the choice moved
    s = jax.nn.sigmoid(logits)
    picked = jnp.take_along_axis(s, e1, -1)  # the gates are the bare scores
    np.testing.assert_allclose(g1, picked / (picked.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-6)
    # no gradient reaches the bias; the logits' gradient flows
    d_bias = jax.grad(lambda b: (route(b)[0] ** 2).sum())(bias)
    assert float(jnp.abs(d_bias).max()) == 0.0
    d_logits = jax.grad(lambda x: (ex.route_topk(
        x, 4, score="sigmoid", select_bias=bias, eps=1e-6)[0] ** 2).sum())(logits)
    assert float(jnp.abs(d_logits).max()) > 0.0


def test_the_shares_partial_results_add_up_to_the_uncut_layer(reference):
    """16 experts in 4 shares of 4 (experts 0-3, 4-7, 8-11, 12-15): each share
    routes over all 16 with the whole bias, normalises over all chosen, and
    adds its own experts' part; the four parts sum to the uncut reference
    layer's output, and each part is the reference's with that share held."""
    rng = np.random.default_rng(0)
    d, f, E, k = 32, 16, 16, 2
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, E)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.standard_normal(E), jnp.float32)
    W = {n: {"kernel": jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)}
         for n, s in (("gate_proj", (E, d, f)), ("up_proj", (E, d, f)),
                      ("down_proj", (E, f, d)))}
    gates, experts = ex.route_topk(x @ router, k, score="sigmoid",
                                   select_bias=bias, eps=1e-6)
    with jax.default_matmul_precision("highest"):
        g_ref, e_ref = reference.route(x, router, bias, k, 1)
        whole = reference.held_experts(x, g_ref, e_ref, W, 0, lambda a: a)
    assert (np.asarray(experts) == np.asarray(e_ref)).all()
    total, here = 0.0, 0
    for s in range(4):
        share = {n: {"kernel": w["kernel"][4 * s:4 * s + 4]} for n, w in W.items()}
        part, stats = ex.held_experts_ffn(
            x, gates, experts, *(share[n]["kernel"] for n in (
                "gate_proj", "up_proj", "down_proj")), first_held=4 * s)
        with jax.default_matmul_precision("highest"):
            want = reference.held_experts(x, g_ref, e_ref, share, 4 * s,
                                          lambda a: a)
        np.testing.assert_allclose(part, want, rtol=1e-4, atol=1e-5)
        assert 0 < float(jnp.abs(part).max())
        total, here = total + part, here + int(stats[0])
    assert here == T * k  # every route lands in exactly one share
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)


def test_the_trainer_leaves_the_selection_bias_alone(seeded, tokens):
    """Weight decay alone would move it: the step masks its update."""
    model = build(lm.lm_comm(1))
    opt = optax.adamw(1e-2, weight_decay=0.5)
    step = lm.make_lm_train_step(model, opt, None, model.comm, seq_len=T,
                                 donate=False)
    params, state, _ = step(seeded, opt.init(seeded), tokens)
    before, after = leaves(seeded), leaves(params)
    for name in before:
        same = bool((before[name] == after[name]).all())
        assert same == name.endswith("select_bias"), name
    assert float(jnp.abs(leaves(state[0].mu)[
        "params/stack/layers_1/experts/select_bias"]).max()) == 0.0


# --- the gated short convolution -------------------------------------------------------

def conv_module(comm):
    return looplm.GatedShortConv(3, comm, jnp.float32)


def test_short_convolution_is_causal_and_the_reference_s(reference):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    mod = conv_module(None)
    params = mod.init(jax.random.key(1), x)
    assert {k: v.shape for k, v in leaves(params).items()} == {
        "params/in_proj/kernel": (16, 48), "params/conv/kernel": (3, 16),
        "params/out_proj/kernel": (16, 16)}
    y = mod.apply(params, x)
    with jax.default_matmul_precision("highest"):
        want = reference.conv_operator(params["params"], x, lambda a: a)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    # output t does not move when input t + 1 does; output t + 1 and t + 2 do
    t = 17
    later = x.at[t + 1].add(1.0)
    y2 = mod.apply(params, later)
    np.testing.assert_array_equal(np.asarray(y[:t + 1]), np.asarray(y2[:t + 1]))
    for row in (t + 1, t + 2, t + 3):
        assert float(jnp.abs(y2[row] - y[row]).max()) > 0
    np.testing.assert_array_equal(np.asarray(y[t + 4:]), np.asarray(y2[t + 4:]))
    # by hand, one channel: z_t = w0 y_{t-2} + w1 y_{t-1} + w2 y_t
    yy = rng.standard_normal((6, 1)).astype(np.float32)
    w = np.array([[2.0], [3.0], [5.0]], np.float32)
    z = np.asarray(reference.short_conv(jnp.asarray(yy), jnp.asarray(w)))[:, 0]
    pad = np.concatenate([[0.0, 0.0], yy[:, 0]])
    np.testing.assert_allclose(
        z, [2 * pad[i] + 3 * pad[i + 1] + 5 * pad[i + 2] for i in range(6)],
        rtol=1e-6)


def test_short_convolution_sharded_over_four_ranks_equals_one():
    """The 2-row halo: forward, and the gradients to the input and to every
    parameter, W = 4 against W = 1."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    comm4 = lm.lm_comm(4)
    mesh = lm.lm_mesh(4, jax.devices()[:4])
    params = conv_module(None).init(jax.random.key(1), x)

    def loss1(params, x):
        return (conv_module(None).apply(params, x) * w).sum()

    def body(params, x, w):
        y = conv_module(comm4).apply(params, x)
        return y, jax.lax.psum((y * w).sum(), comm4.graph_axis)

    from dgraph_tpu.comm.collectives import shard_map_checks

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(comm4.graph_axis), P(comm4.graph_axis)),
        out_specs=(P(comm4.graph_axis), P()),
        **shard_map_checks(relax="test: the halo's ppermute"))
    with jax.set_mesh(mesh):
        y4, _ = jax.jit(sharded)(params, x, w)
        g4 = jax.jit(jax.grad(lambda p, x: sharded(p, x, w)[1],
                              argnums=(0, 1)))(params, x)
    np.testing.assert_allclose(y4, conv_module(None).apply(params, x),
                               rtol=1e-5, atol=1e-6)
    g1 = jax.grad(loss1, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(g4), jax.tree.leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    # the first rank's halo is zeros, the others' their neighbour's last rows
    halo = jax.shard_map(
        lambda y: looplm.previous_rows(y, 2, comm4), mesh=mesh,
        in_specs=P(comm4.graph_axis), out_specs=P(comm4.graph_axis))
    with jax.set_mesh(mesh):
        got = np.asarray(jax.jit(halo)(x)).reshape(4, 2, 16)
    assert (got[0] == 0).all()
    for r in (1, 2, 3):
        np.testing.assert_array_equal(got[r], np.asarray(x[16 * r - 2:16 * r]))
    with pytest.raises(ValueError, match="no 2-row halo"):
        looplm.previous_rows(x[:1], 2, comm4)


def test_a_stack_of_kinds_over_a_sharded_sequence_equals_one_device():
    """conv and attention layers with dense FFNs, W = 4 (ring attention and
    the convolution's halo) against W = 1: the loss and every gradient."""
    from benchmark.builders.looplm import seeded_lm_params, zipf_tokens

    pattern = ("conv+dense", "attn+dense", "conv+dense")

    def model_of(comm):
        return LoopLM(
            vocab=V, hidden_size=32, num_layers=3, pattern=pattern,
            tie_head=True, num_heads=4, head_dim=8, intermediate=48, comm=comm,
            num_kv_heads=2, rms_eps=1e-5, dtype=jnp.float32,
            sandwich_norm=False, qk_norm=True)

    one = model_of(lm.lm_comm(1))
    shapes = jax.eval_shape(lambda: one.init(
        jax.random.key(0), jnp.zeros(64, jnp.int32), jnp.arange(64)))
    params = seeded_lm_params(shapes, 5, None)
    assert sorted(params["params"]["stack"]) == [
        "layers_0", "layers_1", "layers_2", "norm_f"]
    toks = jnp.asarray(zipf_tokens(np.random.default_rng(6), 64, V, 1.0))
    l1, g1 = jax.value_and_grad(lm.make_lm_loss(
        one, None, one.comm, seq_len=64))(params, toks)
    comm4 = lm.lm_comm(4)
    mesh = lm.lm_mesh(4, jax.devices()[:4])
    with jax.set_mesh(mesh):
        l4, g4 = jax.jit(jax.value_and_grad(lm.make_lm_loss(
            model_of(comm4), mesh, comm4, seq_len=64)))(params, toks)
    np.testing.assert_allclose(l4, l1, rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g4)[0],
                            jax.tree.leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6, err_msg=str(path))


# --- the stack of kinds ---------------------------------------------------------------------

def test_runs_of_equal_kinds_are_scanned_and_unequal_ones_follow():
    assert looplm.layer_runs(PATTERN) == [
        ("conv+dense", 1), ("attn+experts", 1), ("conv+experts", 2)]
    model = build(lm.lm_comm(1))
    shapes = leaves(jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros(T, jnp.int32), jnp.arange(T))))
    shape = lambda n: shapes["params/stack/" + n].shape
    assert shape("layers_0/conv/in_proj/kernel") == (1, 32, 96)
    assert shape("layers_0/gate_proj/kernel") == (1, 32, 48)
    assert shape("layers_1/q_proj/kernel") == (1, 32, 32)
    assert shape("layers_1/experts/select_bias") == (1, 16)
    assert shape("layers_2/conv/conv/kernel") == (2, 3, 32)
    assert shape("layers_2/experts/gate_proj/kernel") == (2, 4, 32, 16)
    assert not any("attn" in n or "q_proj" in n for n in shapes
                   if "/layers_2/" in n or "/layers_0/" in n)
    assert "params/head/kernel" not in shapes
    assert model.layer_kinds() == PATTERN
    with pytest.raises(ValueError, match="layer kind 'rnn\\+dense'"):
        looplm.split_kind("rnn+dense")
    bad = build(lm.lm_comm(1), pattern=("conv+dense", "attn+dense"))
    with pytest.raises(ValueError, match="go together"):
        bad.init(jax.random.key(0), jnp.zeros(8, jnp.int32), jnp.arange(8))


def earlier_tiny():
    """(name, model, seq_len, loss keywords, batch) of the three
    configurations that were there before a fourth, at their tiny presets."""
    comm = lm.lm_comm(1)
    with open(os.path.join(ROOT, "benchmark", "configs", "ouro_2p6b.json")) as f:
        o = json.load(f)["tiny"]
    with open(os.path.join(ROOT, "benchmark", "configs", "sdar_30b_a3b.json")) as f:
        s = json.load(f)["tiny"]
    ouro = LoopLM(
        vocab=o["vocab_size"], hidden_size=o["hidden_size"],
        num_layers=o["num_hidden_layers"], num_heads=o["num_attention_heads"],
        num_kv_heads=o["num_key_value_heads"], head_dim=o["head_dim"],
        intermediate=o["intermediate_size"], comm=comm,
        loop_steps=o["total_ut_steps"], exit_gate=o["exit_gate"],
        rms_eps=o["rms_norm_eps"], rope_theta=float(o["rope_theta"]),
        dtype=jnp.dtype(o["compute_dtype"]), remat=o["remat"])
    sdar = LoopLM(
        vocab=s["vocab_size"], hidden_size=s["hidden_size"],
        num_layers=s["num_hidden_layers"], num_heads=s["num_attention_heads"],
        num_kv_heads=s["num_key_value_heads"], head_dim=s["head_dim"],
        intermediate=0, comm=comm, loop_steps=1, exit_gate=False,
        rms_eps=s["rms_norm_eps"], rope_theta=float(s["rope_theta"]),
        dtype=jnp.dtype(s["compute_dtype"]), remat=s["remat"],
        sandwich_norm=False, qk_norm=True,
        experts=HeldExperts(
            n_total=s["num_experts_total"], n_held=s["num_experts"],
            k=s["num_experts_per_tok"], width=s["moe_intermediate_size"],
            first_held=s["first_expert"], rows=s["moe_buffer_rows"]),
        block_length=s["block_length"], mask_token=s["mask_token_id"])
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2_8b_a1b.json")) as f:
        l = json.load(f)["tiny"]
    lfm2 = LoopLM(
        vocab=l["vocab_size"], hidden_size=l["hidden_size"],
        num_layers=len(l["layer_pattern"]), pattern=tuple(l["layer_pattern"]),
        conv_kernel=l["conv_L_cache"], tie_head=True,
        num_heads=l["num_attention_heads"],
        num_kv_heads=l["num_key_value_heads"], head_dim=l["head_dim"],
        intermediate=l["intermediate_size"], comm=comm, loop_steps=1,
        exit_gate=False, rms_eps=l["norm_eps"],
        rope_theta=float(l["rope_theta"]),
        dtype=jnp.dtype(l["compute_dtype"]), remat=l["remat"],
        sandwich_norm=False, qk_norm=True,
        experts=HeldExperts(
            n_total=l["num_experts_total"], n_held=l["num_experts"],
            k=l["num_experts_per_tok"], width=l["moe_intermediate_size"],
            first_held=l["first_expert"], rows=l["moe_buffer_rows"],
            score="sigmoid", select_bias=True, gate_eps=1e-6,
            gate_scale=float(l["routed_scaling_factor"])))
    T_o, T_s, T_l = o["seq_len"], s["seq_len"], l["seq_len"]
    return [
        ("ouro", ouro, T_o, dict(beta=o["exit_beta"]), jnp.zeros(T_o, jnp.int32)),
        ("sdar", sdar, T_s, {}, (jnp.zeros(T_s, jnp.int32), jnp.zeros(T_s, bool),
                                 jnp.ones(T_s, jnp.float32))),
        ("lfm2", lfm2, T_l, {}, jnp.zeros(T_l, jnp.int32))]


# sha256[:16] of (the parameter tree's shapes, the train step's jaxpr) at
# commit e47b801 (PR 33), before a pattern, a router form or a second head
# size existed: no pattern given = that program. SDAR's jaxpr is that one
# with PR 36's fifth count in every expert layer's stats (``rows_max_layer``;
# 9e32b1d66760a135 until then); its tree and both of Ouro's are e47b801's.
# "lfm2": this file's own pattern stack at its tiny preset at commit f4c1a93
# (PR 37), before a state-space, memory-unit or differential mixer existed.
# Since PR 43 both expert programs carry a sixth count (``rows_buffered``),
# lay a buffer out from the routes sorted once and cast the experts' kernels
# once, before the buffer's size is chosen (f89e56a41ea5fa01 and
# 088bbebae7c5f993 until then); SDAR's 1024-row buffer has a 512-row rung
# under it, LFM2's tiny one has none. Since PR 48 the three steps take the
# exit loss's pull-back in the loss's own loop and update the leaves the stack
# does not read ahead of the layers' backward pass (36ed7e9025061d24,
# b61c27e0ebabf8a1 and ce364c776caa8326 until then); the trees are as before.
PARENT_PROGRAMS = {
    "ouro": ("001bbafd6c891dd1", "b7cf35c8e108289b"),
    "sdar": ("7aabb5340b3078f4", "d09893f82bff312d"),
    "lfm2": ("43de925768624568", "ff3307ec30b19b32"),
}


@pytest.mark.parametrize("which", [0, 1, 2], ids=["ouro", "sdar", "lfm2"])
def test_no_pattern_is_the_parents_program(which):
    name, model, seq_len, kw, batch = earlier_tiny()[which]
    opt = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    mesh = lm.lm_mesh(1)
    tr = lm.lm_setup(model, opt, mesh, model.comm, seq_len=seq_len, seed=0, **kw)
    tree = str(jax.tree.map(lambda a: (a.shape, str(a.dtype)), tr.params))
    with jax.set_mesh(mesh):
        jaxpr = str(jax.make_jaxpr(
            lambda p, o, b: tr.train_step.__wrapped__(p, o, b))(
                tr.params, tr.opt_state, batch))
    jaxpr = re.sub(r"0x[0-9a-f]+", "0x", jaxpr)
    digest = lambda s: hashlib.sha256(s.encode()).hexdigest()[:16]
    assert (digest(tree), digest(jaxpr)) == PARENT_PROGRAMS[name]
    assert model.layer_kinds() == (model.pattern or (
        ("attn+dense",) if name == "ouro" else ("attn+experts",))
        * model.num_layers)


def test_tied_heads_gradient_is_the_sum_of_both_uses(seeded, tokens):
    """d loss / d E = (through the lookup) + (through the head): taken apart
    by giving the head a copy of the embedding."""
    model = build(lm.lm_comm(1))

    def loss(params, head_copy):
        positions = jnp.arange(T, dtype=jnp.int32)
        hs, _ = lm.hidden_states(model, params, tokens, positions)
        logits = hs[-1] @ head_copy.T
        targets, valid = lm.next_token_targets(tokens, model.comm, T)
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, targets[:, None], -1)[:, 0]
        return jnp.where(valid, ce, 0.0).sum() / (T - 1)

    E = seeded["params"]["embed"]["embedding"]
    g_lookup, g_head = jax.grad(loss, argnums=(0, 1))(seeded, E)
    tied = jax.grad(lambda p: lm.make_lm_loss(
        model, None, model.comm, seq_len=T)(p, tokens)[0])(seeded)
    want = g_lookup["params"]["embed"]["embedding"] + g_head
    assert float(jnp.linalg.norm(g_head)) > 0
    assert float(jnp.linalg.norm(g_lookup["params"]["embed"]["embedding"])) > 0
    np.testing.assert_allclose(tied["params"]["embed"]["embedding"], want,
                               rtol=1e-4, atol=1e-7)
    # logits(h) = h E^T, float32 out of compute-dtype operands
    bf = build(lm.lm_comm(1), dtype=jnp.bfloat16)
    h = jnp.asarray(np.random.default_rng(0).standard_normal((5, 32)), jnp.bfloat16)
    got = bf.apply(seeded, h, method="logits")
    assert got.dtype == jnp.float32 and got.shape == (5, V)
    np.testing.assert_allclose(
        got, h.astype(jnp.float32) @ E.astype(jnp.bfloat16).astype(jnp.float32).T,
        rtol=1e-5, atol=1e-6)


# --- attention at a head narrower than the lanes -----------------------------------------------

def test_narrow_grouped_heads_match_the_oracle_in_interpret_mode():
    """The kernel path itself at D = 64, 4 query heads a KV head, under the
    causal mask object (Mosaic interpreter on the CPU): forward and the three
    gradients; passing latches that head size and grouping, and no other."""
    assert seq._splash_selfcheck(seq.CausalMask(0), 4, interpret=True,
                                 head_dim=64)
    assert ("causal", 4, 64) in seq._splash_verified
    assert ("causal", 4, 128) not in seq._splash_verified
    assert not seq.flash_attention_selfcheck(group=4, head_dim=64)  # off-TPU
    mask = seq.CausalMask(6)
    assert mask.rows == 6 and mask.over(12) == seq.CausalMask(12)
    ids = np.arange(6)
    assert (np.asarray(mask.allowed(ids[:, None], ids[None, :]))
            == np.tril(np.ones((6, 6), bool))).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_one_kernel_backward_at_a_narrow_head(splash_backward, dtype):
    """ISSUE 50 at this configuration's grouping and head (causal, 4 query
    heads a KV head, heads of 64: half a lane tile): dq, dk, dv of the one
    backward kernel against the dense oracle's and the library's two
    kernels' (``conftest.py::splash_backward``)."""
    splash_backward(seq.CausalMask(512), 4, 64, 64, jnp.dtype(dtype))


def test_a_narrow_head_engages_only_after_its_own_selfcheck(monkeypatch):
    from dgraph_tpu import config as cfg

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(cfg, "use_flash_attention", True)
    monkeypatch.setattr(seq, "_splash_verified", set())
    q = jnp.zeros((256, 8, 64))
    ok = lambda x, **kw: seq._flash_applicable(
        x, require_pinned=True, **{"group": 4, "causal": True, **kw})
    assert ok(q) is False
    seq._splash_verified.add(("causal", 4, 64))
    assert ok(q) is True
    assert ok(q, group=2) is False
    assert ok(jnp.zeros((250, 8, 64))) is False
    # its kernel path is causal and takes no kv_mask
    assert ok(q, causal=False) is False
    assert ok(q, kv_mask=jnp.ones(256)) is False
    # a structured mask has its own check, at its own head size
    bd = seq.BlockDiffusionMask(128, 4)
    assert ok(q, causal=False, mask=bd) is False
    seq._splash_verified.add(("block_diffusion", 4, 128))
    assert ok(q, causal=False, mask=bd) is False
    assert ok(jnp.zeros((256, 8, 128)), causal=False, mask=bd) is True
    # causal heads of 128 have a check of their own too (ISSUE 52), and the
    # library's flash kernels theirs
    assert ok(jnp.zeros((256, 8, 128))) is False
    monkeypatch.setattr(seq, "_flash_verified", True)
    assert ok(jnp.zeros((256, 8, 128))) is True
    with pytest.raises(NotImplementedError, match="causal"):
        seq._flash_dense(q, q[:, :2], q[:, :2], causal=False, scale=None,
                         kv_mask=None)


# --- the benchmark's configuration and work counts --------------------------------

def test_configuration_holds_every_published_number():
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2_8b_a1b.json")) as f:
        cfg = json.load(f)
    kinds = ["conv", "conv", "full_attention", "conv", "conv", "conv",
             "full_attention", "conv", "conv", "conv", "full_attention", "conv",
             "conv", "conv", "full_attention", "conv", "conv", "conv",
             "full_attention", "conv", "conv", "full_attention", "conv", "conv"]
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "layer_types": kinds,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-5, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 24, "num_dense_layers": 2,
                                "num_experts": 32, "vocab_size": 65536}
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 8, 65536 // 4)
    size = cfg["sizes"]
    for k in ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "intermediate_size", "moe_intermediate_size",
              "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
              "routed_scaling_factor", "conv_L_cache", "vocab_size", "norm_eps",
              "rope_theta", "num_hidden_layers", "num_experts"):
        assert size[k] == cfg[k], k
    assert size["head_dim"] == 2048 // 32 == 64
    assert size["num_experts_total"] == 32 and size["first_expert"] == 0
    # published layers 1-5: a whole period, the dense layers counted once
    want = [("conv" if t == "conv" else "attn") + ("+dense" if i < 2 else "+experts")
            for i, t in enumerate(kinds)][1:6]
    assert size["layer_pattern"] == want == [
        "conv+dense", "attn+experts", "conv+experts", "conv+experts",
        "conv+experts"]
    assert size["moe_buffer_rows"] is None  # the worst case: nothing dropped
    assert "4 expert-parallel chips" in cfg["deployment"]
    assert cfg["why_layers"] and len(cfg["assumed"]) >= 10
    tiny = cfg["tiny"]
    assert tiny["layer_pattern"] == size["layer_pattern"]
    assert set(tiny) - {"seq_len"} == set(size)


def test_work_counts_by_hand(monkeypatch):
    from benchmark import opsbytes
    from dgraph_tpu.obs import metrics

    info = {"seq_len": 16384, "heads": 32, "head_dim": 64, "hidden": 2048,
            "expert_width": 1792, "experts_per_token": 4, "layers": 5,
            "layers_conv": 4, "layers_attention": 1, "layers_expert_ffn": 4,
            "loop_steps": 1, "compute_bytes": 2}
    assert opsbytes.work("lfm2_conv_flops", info, 0) \
        == 3 * 2 * 16384 * (3 * 2048 ** 2 + 2048 ** 2) * 4
    assert opsbytes.work("lfm2_conv_gate_bytes", info, 0) \
        == (4 + 7) * 16384 * 2048 * 2 * 4
    assert opsbytes.work("lfm2_attn_flops", info, 0) \
        == 3 * 2 * 16384 ** 2 * 32 * 64
    reg = metrics.Metrics()
    monkeypatch.setattr(metrics, "default_registry", reg)
    assert opsbytes.work("lfm2_moe_flops", info, 0) == 0.0  # nothing counted
    reg.counter("moe.rows_routed", 3 * 16384 * 4 * 4)  # three steps
    reg.counter("moe.rows_here", 3 * 70000)
    assert opsbytes.work("lfm2_moe_flops", info, 0) \
        == pytest.approx(3 * 3 * 2 * 70000 * 2048 * 1792)


def test_setup_counts_the_layers_by_kind(monkeypatch):
    from dgraph_tpu.obs import metrics

    reg = metrics.Metrics()
    monkeypatch.setattr(metrics, "default_registry", reg)
    monkeypatch.setattr(lm, "default_registry", reg)
    model = build(lm.lm_comm(1))
    lm.lm_setup(model, optax.sgd(0.1), lm.lm_mesh(1), model.comm, seq_len=T)
    c = reg.snapshot()["counters"]
    assert (c["lm.layers.conv"], c["lm.layers.attention"],
            c["lm.layers.dense_ffn"], c["lm.layers.expert_ffn"]) == (3, 1, 1, 3)
    assert c["lm.conv.kernel_size"] == 3 and c["lm.attention.head_dim"] == 8
    assert c["lm.attention.dense"] == 1 and c["lm.layers_held"] == 4
