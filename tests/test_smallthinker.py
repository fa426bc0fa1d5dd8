"""A sparse-expert decoder whose router reads the layer's INPUT, before the
norm and before attention (``HeldExperts.router_reads = "layer_input"``), with
ReGLU experts (``parallel/expert.py``'s form ``gated_relu``) and no shared
expert, one full attention layer without positions among windowed layers with
rotary positions (``models/looplm.py``'s kinds ``attn+experts`` and
``attn_win+experts``, ``LoopLM.full_attn_rope``), on the CPU at tiny sizes,
seeded weights: against the benchmark's plain reference
(``benchmark/reference/smallthinker.py``), each layer kind alone, the router's
place (a test that fails if it is moved after attention), the shares of an
expert layer against the uncut layer, the third expert form against a plain
loop over experts, the splash kernels at seven query heads a KV head. (That
every rung of the buffer's ladder is the one buffer when the routes are handed
past the mixer is ``tests/test_expert_buffer.py``'s; that the configurations that were
there still trace to their parents' programs is ``tests/test_lfm2.py``'s and
``tests/test_pallas_scan.py``'s hash tests, and Nemotron's below.)"""

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

from dgraph_tpu.models import looplm
from dgraph_tpu.models.looplm import HeldExperts
from dgraph_tpu.parallel import expert as ex
from dgraph_tpu.parallel import sequence as seq
from dgraph_tpu.train import lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CELL = "smallthinker_21b_a3b.seq16k"
PATTERN = ("attn+experts",) + ("attn_win+experts",) * 3
IDENT = lambda a: a


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def size():
    return dict(config()["tiny"], compute_dtype="float32")


@pytest.fixture(scope="module")
def reference():
    from benchmark.reference import smallthinker

    return smallthinker


def build(size, comm=None):
    from benchmark.builders.smallthinker import model_of

    return model_of(size, comm or lm.lm_comm(1))


@pytest.fixture(scope="module")
def seeded(size):
    from benchmark.builders.looplm import seeded_lm_params

    T = size["seq_len"]
    shapes = jax.eval_shape(lambda: build(size).init(
        jax.random.key(0), jnp.zeros(T, jnp.int32), jnp.arange(T)))
    return seeded_lm_params(shapes, 11, None)


@pytest.fixture(scope="module")
def tokens(size):
    from benchmark.builders.looplm import zipf_tokens

    return jnp.asarray(zipf_tokens(np.random.default_rng(3), size["seq_len"],
                                   size["vocab_size"], 1.0))


def leaves(tree):
    return {"/".join(str(k.key) for k in path): a
            for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


def one_layer(seeded, run, j=0):
    """The leaves of layer ``j`` of run ``run`` without their leading axis."""
    return jax.tree.map(lambda a: a[j], seeded["params"]["stack"][run])


def a_layer(size, mixer, **fields):
    """One ``LoopLMLayer`` of the tiny preset, float32."""
    model = build(size)
    return looplm.LoopLMLayer(
        hidden=size["hidden_size"], num_heads=size["num_attention_heads"],
        head_dim=size["head_dim"], intermediate=0, comm=lm.lm_comm(1),
        num_kv_heads=size["num_key_value_heads"], rms_eps=1e-6,
        dtype=jnp.float32, sandwich_norm=False, mixer=mixer,
        window=size["sliding_window_size"],
        **{"experts": model.experts, "full_attn_rope": False, **fields})


# --- against the plain reference ------------------------------------------------

def test_logits_loss_and_every_gradient_leaf_match_reference(
        size, seeded, tokens, reference):
    model, T = build(size), size["seq_len"]
    assert model.layer_kinds() == PATTERN
    assert looplm.layer_runs(PATTERN) == [("attn+experts", 1),
                                          ("attn_win+experts", 3)]
    got_logits, _ = model.apply(seeded, tokens, jnp.arange(T))
    loss_fn = lm.make_lm_loss(model, None, model.comm, seq_len=T)
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(seeded, tokens)
    with jax.default_matmul_precision("highest"):
        h, chosen = reference.hidden_states(seeded, tokens, size, IDENT)
        (want, _), want_g = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, tokens, size, IDENT),
            has_aux=True))(seeded)
        np.testing.assert_allclose(got_logits[0], reference.logits(seeded, h),
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    got, ref = leaves(grads), leaves(want_g)
    assert set(got) == set(ref) and len(ref) == 23
    assert "params/head/kernel" in ref  # untied
    assert not any("shared" in name or "select_bias" in name for name in ref)
    assert sum("gate_proj" in name for name in ref) == 2  # gated, both runs
    for name, g in got.items():
        scale = float(jnp.linalg.norm(ref[name]))
        assert scale > 0, name  # no leaf is inert
        assert float(jnp.linalg.norm(g - ref[name])) <= 3e-4 * scale, name
    # the rows this share's experts got, of the 4 layers' T k routes
    k = size["moe_num_active_primary_experts"]
    assert chosen.shape == (4, T, k)
    held = size["moe_num_primary_experts"]
    assert int(counts[0]) == int((np.asarray(chosen) < held).sum())
    assert int(counts[2]) == 0  # none dropped


def test_reference_follows_adamw_like_the_trainer(size, seeded, tokens,
                                                  reference):
    model, T = build(size), size["seq_len"]
    opt = optax.adamw(lambda c: 3e-4 * jnp.minimum(1.0, (c + 1) / 2000),
                      b1=0.9, b2=0.95, weight_decay=0.1)
    trainer = lm.lm_setup(model, opt, lm.lm_mesh(1), model.comm, seq_len=T,
                          params=jax.tree.map(jnp.array, seeded), donate=False)
    st = trainer.startup
    assert st["layers_by_kind"] == {
        "conv": 0, "attention": 4, "dense_ffn": 0, "expert_ffn": 4,
        "window": 3, "attn_win": 3}
    assert st["attention"] == "dense" and st["nope_layers"] == 1
    assert st["attention_mask"] == "causal+window"
    assert st["moe_route_ahead_layers"] == 4 and st["moe_routes"] == 4 * T * 2
    w = size["sliding_window_size"]
    assert st["mask_pairs"] == T * (T + 1) // 2 + 3 * (
        w * (w + 1) // 2 + (T - w) * w)
    losses = [float(trainer.step(np.asarray(tokens)).loss) for _ in range(3)]
    got = reference.follow(jax.device_get(seeded), [np.asarray(tokens)] * 3, size)
    np.testing.assert_allclose(losses, got["loss"], rtol=3e-5)
    delta = leaves(jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b)), trainer.params, seeded))
    for name, d in delta.items():
        np.testing.assert_allclose(d, got["delta_norm"][name], rtol=2e-3,
                                   err_msg=name)


def test_program_against_reference_under_the_tiny_limits():
    """The cell's own comparison at its tiny preset (bf16 compute), as the
    harness makes it: loss, first gradient (fetched leaf by leaf to the
    host), three-step update."""
    from benchmark import run as harness

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    _, cfg, traffic = harness.find_cell(bench, CELL)
    cell = harness.build_cell(cfg, traffic, 2**31 + 46, jax.devices()[:1], True)
    with cell.context():
        got, _, _ = harness.first_steps(cell, harness.CompileWatch())
    assert all(isinstance(g, np.ndarray) for g in jax.tree.leaves(got["grad"]))
    cell.release()
    assert cell.rows_dropped == 0
    rows = harness.compare(got, cell.reference(harness.CHECK_STEPS),
                           harness.cell_limits(CELL, True))
    assert all(ok for _, _, _, ok in rows), rows
    assert {n for n, _, lim, _ in rows if lim is not None} \
        == {"loss_gap", "delta_norm_gap", "grad_diff_gap"}


# --- each layer kind alone ----------------------------------------------------------

@pytest.fixture(scope="module")
def stream(size):
    """A residual stream ``[T, d]`` and the rotary table of its positions."""
    T, d = size["seq_len"], size["hidden_size"]
    x = jnp.asarray(np.random.default_rng(7).standard_normal((T, d)),
                    jnp.float32)
    return x, looplm.rotary_tables(jnp.arange(T), size["head_dim"],
                                   float(size["rope_theta"]))


def test_the_windowed_layer_alone_matches_the_reference(size, seeded,
                                                        reference, stream):
    x, rope = stream
    p = one_layer(seeded, "layers_1", 1)
    with jax.default_matmul_precision("highest"):
        got, stats = a_layer(size, "attn_win").apply({"params": p}, x, rope)
        want, chosen = reference.layer(True, p, x, jnp.arange(x.shape[0]),
                                       size, IDENT)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert int(stats[0]) == int((np.asarray(chosen) < 4).sum())
    # the window binds: the full mask over the same leaves gives another result
    with jax.default_matmul_precision("highest"):
        full, _ = a_layer(size, "attn", full_attn_rope=True).apply(
            {"params": p}, x, rope)
    assert float(jnp.abs(full - got)[size["sliding_window_size"]:].max()) > 1e-3
    np.testing.assert_allclose(  # ... and not before the window is full
        full[:size["sliding_window_size"]],
        got[:size["sliding_window_size"]], rtol=2e-4, atol=2e-5)


def test_the_full_layer_alone_takes_no_positions(size, seeded, reference,
                                                 stream):
    x, rope = stream
    p = one_layer(seeded, "layers_0")
    assert sorted(p) == ["experts", "k_proj", "norm_attn_in", "norm_mlp_in",
                         "o_proj", "q_proj", "v_proj"]
    assert sorted(p["experts"]) == ["down_proj", "gate_proj", "router",
                                    "up_proj"]
    d = size["hidden_size"]
    assert p["q_proj"]["kernel"].shape == (d, 4 * 16)  # H D = hidden here
    assert p["experts"]["router"]["kernel"].shape == (d, 16)  # every column
    with jax.default_matmul_precision("highest"):
        got, _ = a_layer(size, "attn").apply({"params": p}, x, rope)
        want, _ = reference.layer(False, p, x, jnp.arange(x.shape[0]), size,
                                  IDENT)
        with_positions, _ = a_layer(size, "attn", full_attn_rope=True).apply(
            {"params": p}, x, rope)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(with_positions - got).max()) > 1e-3


def test_a_sequence_shorter_than_the_window_is_the_full_layer_with_positions(
        size, seeded, stream):
    x, rope = stream
    n = size["sliding_window_size"] - 8
    x, rope = x[:n], tuple(t[:n] for t in rope)
    p = one_layer(seeded, "layers_1", 2)
    with jax.default_matmul_precision("highest"):
        windowed, _ = a_layer(size, "attn_win").apply({"params": p}, x, rope)
        full, _ = a_layer(size, "attn", full_attn_rope=True).apply(
            {"params": p}, x, rope)
    np.testing.assert_allclose(windowed, full, rtol=1e-5, atol=1e-6)


# --- the router's place -------------------------------------------------------------

def test_the_router_reads_the_layers_input(size, seeded, tokens, reference):
    """With attention's ``o_proj`` a nonzero seeded kernel, ``h`` differs
    from ``x``: the chosen experts are the reference's top-k of ``W_r x`` and
    differ, on some row, from the top-k of ``W_r RMSNorm_2(h)``; the router
    kernel's gradient is the reference's (it joins the residual at the
    layer's input). Fails if the router is moved after attention."""
    model, T = build(size), size["seq_len"]
    k = size["moe_num_active_primary_experts"]
    for run in ("layers_0", "layers_1"):
        assert float(jnp.abs(
            seeded["params"]["stack"][run]["o_proj"]["kernel"]).min()) > 0
    _, got = model.apply(seeded, tokens, jnp.arange(T), method="hidden",
                         mutable=["intermediates"])
    runs = got["intermediates"]["stack"]
    program = np.concatenate([np.asarray(jax.tree.leaves(runs[r])[0][0])
                              for r in ("layers_0", "layers_1")])
    with jax.default_matmul_precision("highest"):
        _, chosen = reference.hidden_states(seeded, tokens, size, IDENT)
    np.testing.assert_array_equal(np.sort(program, -1),
                                  np.sort(np.asarray(chosen), -1))
    # the router AFTER attention would have chosen otherwise
    p = one_layer(seeded, "layers_0")
    x = seeded["params"]["embed"]["embedding"][tokens]
    with jax.default_matmul_precision("highest"):
        h = x + reference.attention(
            p, reference.rms_norm(p["norm_attn_in"]["scale"], x, 1e-6),
            jnp.arange(T), False, size, IDENT)
        u = reference.rms_norm(p["norm_mlp_in"]["scale"], h, 1e-6)
        _, after = reference.route(u, p["experts"]["router"]["kernel"], k)
        _, ahead = reference.route(x, p["experts"]["router"]["kernel"], k)
    np.testing.assert_array_equal(np.sort(program[0], -1),
                                  np.sort(np.asarray(ahead), -1))
    moved = (np.sort(np.asarray(after), -1) != np.sort(program[0], -1)).any(-1)
    assert moved.mean() > 0.2  # most rows of a seeded stack
    # the gates too: softmax over the chosen logits of the un-normed stream
    spec = model.experts
    gates, _ = looplm.HeldExpertsFFN(spec, lm.lm_comm(1), jnp.float32).apply(
        {"params": p["experts"]}, x, route_only=True)
    with jax.default_matmul_precision("highest"):
        want_gates, _ = reference.route(x, p["experts"]["router"]["kernel"], k)
    np.testing.assert_allclose(gates, want_gates, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)


def test_the_router_kernels_gradient_is_the_references(size, seeded, tokens,
                                                       reference):
    model, T = build(size), size["seq_len"]
    loss_fn = lm.make_lm_loss(model, None, model.comm, seq_len=T)
    grads = jax.jit(jax.grad(lambda p: loss_fn(p, tokens)[0]))(seeded)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(
            lambda p: reference.loss_fn(p, tokens, size, IDENT)[0]))(seeded)
        # a reference whose router reads RMSNorm_2(h): another gradient

        def moved(p, x, positions, windowed=False):
            eps, e = 1e-6, p["experts"]
            h = x + reference.attention(
                p, reference.rms_norm(p["norm_attn_in"]["scale"], x, eps),
                positions, windowed, size, IDENT)
            u = reference.rms_norm(p["norm_mlp_in"]["scale"], h, eps)
            gates, experts = reference.route(u, e["router"]["kernel"], 2)
            return h + reference.held_experts(u, gates, experts, e, 0, IDENT)

        x = seeded["params"]["embed"]["embedding"][tokens]
        w = jnp.asarray(np.random.default_rng(5).standard_normal(x.shape),
                        jnp.float32)
        p0 = one_layer(seeded, "layers_0")
        g_moved = jax.grad(lambda p: (moved(p, x, jnp.arange(T)) * w).sum())(p0)
        g_ref = jax.grad(lambda p: (reference.layer(
            False, p, x, jnp.arange(T), size, IDENT)[0] * w).sum())(p0)
        g_got = jax.grad(lambda p: (a_layer(size, "attn").apply(
            {"params": p}, x, None)[0] * w).sum())(p0)
    for run in ("layers_0", "layers_1"):
        a = grads["params"]["stack"][run]["experts"]["router"]["kernel"]
        b = want["params"]["stack"][run]["experts"]["router"]["kernel"]
        assert float(jnp.linalg.norm(a - b)) <= 3e-4 * float(jnp.linalg.norm(b))
    router = lambda g: g["experts"]["router"]["kernel"]
    scale = float(jnp.linalg.norm(router(g_ref)))
    assert float(jnp.linalg.norm(router(g_got) - router(g_ref))) <= 3e-4 * scale
    assert float(jnp.linalg.norm(router(g_got) - router(g_moved))) > 0.3 * scale


def test_both_places_are_one_routine_and_the_experts_input_is_the_default():
    """``router_reads="ffn_input"`` (every accepted configuration): the layer
    hands the experts' module no routes and it routes from what it
    multiplies; the same leaves under ``"layer_input"`` route from the
    stream, so the two layers differ exactly by the routes."""
    rng = np.random.default_rng(2)
    T, d, f, E, k = 64, 32, 16, 8, 2
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    spec = HeldExperts(E, 4, k, f, form="gated_relu")
    assert spec.router_reads == "ffn_input"
    assert looplm.ROUTER_READS == ("ffn_input", "layer_input")
    mod = looplm.HeldExpertsFFN(spec, lm.lm_comm(1), jnp.float32)
    params = mod.init(jax.random.key(0), x)
    out, stats = mod.apply(params, x)
    routes = mod.apply(params, x, route_only=True)
    handed, stats2 = mod.apply(params, x, routes)
    np.testing.assert_allclose(out, handed, rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(stats, stats2)
    # routes from another tensor: another result from the same leaves
    other = mod.apply(params, x[::-1], route_only=True)
    elsewhere, _ = mod.apply(params, x, other)
    assert float(jnp.abs(elsewhere - out).max()) > 1e-3
    with pytest.raises(ValueError, match="router_reads"):
        looplm.LoopLM(
            vocab=32, hidden_size=d, num_layers=1, num_heads=2, head_dim=16,
            intermediate=0, comm=lm.lm_comm(1), sandwich_norm=False,
            experts=HeldExperts(E, 4, k, f, router_reads="after_attention")
        ).init(jax.random.key(0), jnp.zeros(8, jnp.int32), jnp.arange(8))


# --- the shares add up --------------------------------------------------------------

@pytest.mark.parametrize("shares", [4, 8])
def test_the_shares_add_up_to_the_uncut_layer(shares, reference):
    """16 experts in 4 shares of 4 (8 of 2): each share routes over all 16
    FROM THE LAYER'S INPUT, takes the softmax over its row's chosen logits,
    and adds its own experts' part; nothing is computed alike by every chip
    (no shared expert), so the shares' parts add up to the uncut reference
    layer's expert half, and every route lands in exactly one share."""
    rng = np.random.default_rng(0)
    T, d, f, E, k = 96, 32, 16, 16, 2
    n = E // shares
    x, u = (jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
            for _ in range(2))  # the router's input and the experts'
    mat = lambda *s: {"kernel": jnp.asarray(
        rng.standard_normal(s) * 0.2, jnp.float32)}
    whole = {"router": mat(d, E), "gate_proj": mat(E, d, f),
             "up_proj": mat(E, d, f), "down_proj": mat(E, f, d)}
    with jax.default_matmul_precision("highest"):
        gates, experts = reference.route(x, whole["router"]["kernel"], k)
        uncut = reference.held_experts(u, gates, experts, whole, 0, IDENT)
        total, here = 0.0, 0
        for s in range(shares):
            share = dict(whole, **{name: {"kernel": whole[name]["kernel"][
                n * s:n * (s + 1)]} for name in ("gate_proj", "up_proj",
                                                 "down_proj")})
            spec = HeldExperts(E, n, k, f, first_held=n * s,
                               form="gated_relu", router_reads="layer_input")
            mod = looplm.HeldExpertsFFN(spec, lm.lm_comm(1), jnp.float32)
            routes = mod.apply({"params": share}, x, route_only=True)
            out, stats = mod.apply({"params": share}, u, routes)
            want = reference.held_experts(u, gates, experts, share, n * s,
                                          IDENT)
            np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
            assert 0 < float(jnp.abs(out).max())
            total, here = total + out, here + int(stats[0])
    assert here == T * k
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)


# --- the third expert form ----------------------------------------------------------

def test_gated_relu_is_a_plain_loop_over_experts():
    rng = np.random.default_rng(1)
    T, d, f, E, k = 80, 24, 16, 6, 2
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((E, d, f)) * 0.3, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((E, f, d)) * 0.3, jnp.float32)
    gates, experts = ex.route_topk(
        jnp.asarray(rng.standard_normal((T, E)), jnp.float32), k)

    def loop(x, gates, wg, wu, wd):
        out = 0.0
        for e in range(E):
            gate = jnp.where(experts == e, gates, 0.0).sum(-1)
            out = out + gate[:, None] * (
                (jax.nn.relu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
        return out

    def layer(x, gates, wg, wu, wd):
        return ex.held_experts_ffn(x, gates, experts, wg, wu, wd,
                                   form="gated_relu", n_total=E)[0]

    cot = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    both = lambda f: jax.value_and_grad(
        lambda *a: (f(*a) * cot).sum(), argnums=(0, 1, 2, 3, 4))(
            x, gates, wg, wu, wd)
    (got, got_g), (want, want_g) = both(layer), both(loop)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)
    # relu, not silu: the gated SiLU form over the same leaves differs
    silu = ex.held_experts_ffn(x, gates, experts, wg, wu, wd, n_total=E)[0]
    assert float(jnp.abs(silu - layer(x, gates, wg, wu, wd)).max()) > 1e-2
    assert ex.EXPERT_FORMS == ("gated_silu", "relu2", "gated_relu")
    with pytest.raises(ValueError, match="a gate kernel"):
        ex.held_experts_ffn(x, gates, experts, None, wu, wd,
                            form="gated_relu")


# --- the kernels' self-checks at this grouping ------------------------------------

def test_seven_query_heads_a_kv_head_under_the_window_in_interpret_mode():
    """The splash kernels at 7 query heads a KV head, D 128, under a window
    (Mosaic interpreter on the CPU): forward and the three gradients against
    the dense oracle; passing latches that mask kind and grouping, no other."""
    assert seq._splash_selfcheck(seq.WindowMask(0, 512), 7, interpret=True)
    assert ("window", 7, 128) in seq._splash_verified
    assert ("window", 8, 128) not in seq._splash_verified
    # the flash path's repeat: KV head j // 7 serves query head j
    k = jnp.arange(4.0)[None, :, None] * jnp.ones((3, 4, 2))
    q = jnp.zeros((3, 28, 2))
    rk, _ = seq.repeat_kv(q, k, k)
    np.testing.assert_array_equal(rk[0, :, 0], np.arange(28) // 7)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_one_kernel_backward_at_seven_query_heads_a_kv_head(
        splash_backward, dtype):
    """ISSUE 50 at this configuration's grouping (7 query heads a KV head,
    heads of 128, under a window): dk and dv are summed over the seven heads
    and every query tile inside the kernel, in float32; against the dense
    oracle's and the library's two kernels' gradients
    (``conftest.py::splash_backward``)."""
    splash_backward(seq.WindowMask(512, 256), 7, 128, 128, jnp.dtype(dtype))


def test_setup_resolves_a_mask_a_layer_and_counts_the_new_kind(size,
                                                               monkeypatch):
    from dgraph_tpu.obs import metrics

    reg = metrics.Metrics()
    monkeypatch.setattr(metrics, "default_registry", reg)
    monkeypatch.setattr(lm, "default_registry", reg)
    asked = []
    real = lm.resolve_attention
    monkeypatch.setattr(lm, "resolve_attention", lambda *a, **kw: (
        asked.append((a[5], a[6], kw)), real(*a, **kw))[1])
    model, T = build(size), size["seq_len"]
    masks = model.attention_masks(T)
    assert [m.name for m in masks] == ["causal", "window", "window", "window"]
    assert masks[1] == seq.WindowMask(T, size["sliding_window_size"])
    lm.lm_setup(model, optax.sgd(0.1), lm.lm_mesh(1), model.comm, seq_len=T)
    # the full mask (as causal) and the window, once each, at this grouping
    # and with the values' head the keys' (no differential attention here)
    kw = {"v_head_dim": None, "dtype": jnp.dtype("float32")}
    assert asked == [(None, 2, kw), (masks[1], 2, kw)]
    c = reg.snapshot()["counters"]
    assert (c["lm.layers.attention"], c["lm.layers.attn_win"],
            c["lm.layers.window"], c["lm.layers.expert_ffn"]) == (4, 3, 3, 4)
    assert c["lm.attention.window"] == size["sliding_window_size"]
    assert c["lm.attention.nope_layers"] == 1
    assert c["moe.route_ahead_layers"] == 4 and c["moe.experts_held"] == 4
    assert "lm.attention.v_head_dim" not in c and "moe.shared_width" not in c
    assert c["attn.mask_pairs"] == sum(m.pairs() for m in masks)
    assert c["attn.tile_pairs"] == 4 * T * T  # the dense oracle skips nothing
    # a windowed kind needs its window, and takes no block-diffusion mask
    with pytest.raises(ValueError, match="window"):
        model.clone(window=0).init(jax.random.key(0), jnp.zeros(8, jnp.int32),
                                   jnp.arange(8))
    assert "attn_win" in looplm.LAYER_MIXERS and "attn_win" in looplm.ATTENDING
    assert looplm.split_kind("attn_win+experts") == ("attn_win", "experts")


# --- the accepted configurations keep their programs ----------------------------------

# sha256[:16] of (the parameter tree's shapes, the train step's jaxpr) of
# nemotron3_nano_30b_a3b's tiny preset at commit fcf560a (PR 45), before the
# router had a second place or the experts a third form; its exit loss is one
# loop and its head is updated ahead since PR 48 (7d1fdbe65e014ea2 until then).
PARENT_NEMOTRON = ("8a02a3a4d266aa23", "9a67bb9c73094a18")


def test_nemotrons_tiny_preset_is_the_parents_program():
    from benchmark.builders.nemotron_h import model_of

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3_nano_30b_a3b.json")) as f:
        tiny = json.load(f)["tiny"]
    model, T = model_of(tiny, lm.lm_comm(1)), tiny["seq_len"]
    opt = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    mesh = lm.lm_mesh(1)
    tr = lm.lm_setup(model, opt, mesh, model.comm, seq_len=T, seed=0)
    tree = str(jax.tree.map(lambda a: (a.shape, str(a.dtype)), tr.params))
    with jax.set_mesh(mesh):
        jaxpr = str(jax.make_jaxpr(
            lambda p, o, b: tr.train_step.__wrapped__(p, o, b))(
                tr.params, tr.opt_state, jnp.zeros(T, jnp.int32)))
    jaxpr = re.sub(r"0x[0-9a-f]+", "0x", jaxpr)
    digest = lambda s: hashlib.sha256(s.encode()).hexdigest()[:16]
    assert (digest(tree), digest(jaxpr)) == PARENT_NEMOTRON


# --- the configuration -----------------------------------------------------------------

def test_configuration_holds_every_published_number():
    cfg = config()
    layout = [0, 1, 1, 1] * 13
    published = {
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_layout": layout, "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": layout,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
    }
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "moe_num_primary_experts": 64,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"]) == (4, 8, 18992)
    assert cfg["vocab_size"] * 8 == 151936  # an eighth, the floor
    assert set(cfg) - set(published) == {
        "name", "builder", "reference", "source", "paper", "num_hidden_layers",
        "moe_num_primary_experts", "vocab_size", "published", "reduced",
        "deployment", "why_layers", "sizes", "tiny", "assumed", "correct"}
    assert "8 expert-parallel chips" in cfg["deployment"]
    assert "16.49" in cfg["why_layers"] and "11.83" in cfg["why_layers"]
    s = cfg["sizes"]
    # no width is cut: the sizes the cell runs are the published ones
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_ffn_hidden_size", "sliding_window_size",
                "moe_num_active_primary_experts", "rope_theta",
                "rms_norm_eps"):
        assert s[key] == cfg[key], key
    assert (s["moe_num_primary_experts_total"], s["moe_num_primary_experts"],
            s["first_expert"]) == (64, 8, 0)
    assert s["layout"] == cfg["rope_layout"][:4] \
        == cfg["sliding_window_layout"][:4] == [0, 1, 1, 1]
    assert s["moe_buffer_rows"] is None
    # no rung under the worst case: a window's steps route 1.0-5 x the even
    # router's rows here by seed and by step, astride any rung (PERF.md)
    from dgraph_tpu.parallel.expert import buffer_ladder
    assert s["moe_buffer_ladder"] is False
    assert buffer_ladder(16384, 6, 8, None) == (98304,)
    assert buffer_ladder(16384, 6, 8, 64) == (18432, 36864, 98304)
    t = cfg["tiny"]
    assert (t["hidden_size"], t["num_attention_heads"],
            t["num_key_value_heads"], t["head_dim"]) == (64, 4, 2, 16)
    assert (t["sliding_window_size"], t["seq_len"]) == (32, 128)
    assert (t["moe_num_primary_experts"], t["moe_num_primary_experts_total"],
            t["moe_num_active_primary_experts"], t["layout"]) \
        == (4, 16, 2, [0, 1, 1, 1])
    # the parameter count of the cut, by the issue's arithmetic
    d = 2560
    attn = 2 * d * 28 * 128 + 2 * d * 4 * 128
    assert attn == 20971520 and 3 * d * 768 == 5898240
    layer = attn + d * 64 + 8 * 3 * d * 768 + 2 * d
    total = 4 * layer + 2 * 18992 * d + d
    assert total == 370547200
    assert abs(total * 16 / 1e9 - 5.93) < 0.005
    # the program's model at these sizes holds exactly that
    model = build(s)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros(128, jnp.int32), jnp.arange(128)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == total
    assert model.pattern == PATTERN and not model.full_attn_rope
    assert model.experts == HeldExperts(
        64, 8, 6, 768, form="gated_relu", router_reads="layer_input",
        ladder=False)


def test_work_counts_by_hand():
    from benchmark import opsbytes
    from benchmark.work import smallthinker_attn_flops

    info = {"seq_len": 16384, "hidden": 2560, "heads": 28, "kv_heads": 4,
            "head_dim": 128, "window": 4096, "expert_width": 768,
            "experts_per_token": 6, "layers_full": 1, "layers_window": 3}
    assert smallthinker_attn_flops.weights(info) == 20971520
    assert 3 * info["hidden"] * info["expert_width"] == 5898240
    assert smallthinker_attn_flops.pairs(info) == (134225920, 58722304)
    assert seq.CausalMask(16384).pairs() == 134225920
    assert seq.WindowMask(16384, 4096).pairs() == 58722304
    assert opsbytes.work("smallthinker_attn_flops", info, 0) \
        == 3 * (134225920 + 3 * 58722304) * 4 * 3584
