"""A hybrid decoder whose every layer is ONE residual half (``models/looplm.py``'s
kinds ``ssd+none``, ``none+experts``, ``attn+none``: Mamba-2 mixers, held
squared-ReLU experts beside one shared expert, grouped-query attention), the
chunked Mamba-2 recurrence (``ops/ssd.py``) and the ungated expert form
(``parallel/expert.py``) on the CPU at tiny sizes, seeded weights: against the
benchmark's plain reference (``benchmark/reference/nemotron_h.py``), the
chunked operator against the diagonal selective scan and the recurrence by
hand, the shares of an expert layer with the shared expert counted once
against the uncut layer, the mixer over a sharded sequence. (That the
configurations that were there still trace to their parents' programs is
``tests/test_lfm2.py``'s and ``tests/test_pallas_scan.py``'s hash tests.)"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from dgraph_tpu.models import looplm
from dgraph_tpu.models.looplm import HeldExperts, LoopLM, Mamba2Mixer
from dgraph_tpu.ops import selective_scan as ss
from dgraph_tpu.ops import ssd as ssd_op
from dgraph_tpu.parallel import expert as ex
from dgraph_tpu.train import lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CELL = "nemotron3_nano_30b_a3b.seq8k"
PATTERN = ["ssd+none", "none+experts", "ssd+none", "none+experts", "ssd+none",
           "attn+none", "none+experts", "ssd+none", "none+experts"]


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3_nano_30b_a3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def size():
    return dict(config()["tiny"], compute_dtype="float32")


@pytest.fixture(scope="module")
def reference():
    from benchmark.reference import nemotron_h

    return nemotron_h


def build(size, comm=None):
    from benchmark.builders.nemotron_h import model_of

    return model_of(size, comm or lm.lm_comm(1))


@pytest.fixture(scope="module")
def seeded(size):
    from benchmark.builders.nemotron_h import seeded_params

    T = size["seq_len"]
    shapes = jax.eval_shape(lambda: build(size).init(
        jax.random.key(0), jnp.zeros(T, jnp.int32), jnp.arange(T)))
    params = seeded_params(shapes, 11, None, size)
    # a bias large enough to move many rows' choice
    for run in ("layers_1", "layers_6"):
        params["params"]["stack"][run]["experts"]["select_bias"] *= 10.0
    return params


@pytest.fixture(scope="module")
def tokens(size):
    from benchmark.builders.looplm import zipf_tokens

    return jnp.asarray(zipf_tokens(np.random.default_rng(3), size["seq_len"],
                                   size["vocab_size"], 1.0))


def leaves(tree):
    return {"/".join(str(k.key) for k in path): a
            for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


def one_layer(seeded, run):
    """The leaves of one-layer run ``run`` without their leading axis."""
    return jax.tree.map(lambda a: a[0], seeded["params"]["stack"][run])


# --- against the plain reference ------------------------------------------------

def test_logits_loss_and_every_gradient_leaf_match_reference(
        size, seeded, tokens, reference):
    model, T = build(size), size["seq_len"]
    assert model.layer_kinds() == tuple(PATTERN)
    got_logits, _ = model.apply(seeded, tokens, jnp.arange(T))
    loss_fn = lm.make_lm_loss(model, None, model.comm, seq_len=T)
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(seeded, tokens)
    with jax.default_matmul_precision("highest"):
        h, chosen = reference.hidden_states(seeded, tokens, size, lambda a: a)
        (want, _), want_g = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, tokens, size, lambda a: a),
            has_aux=True))(seeded)
        np.testing.assert_allclose(got_logits[0], reference.logits(seeded, h),
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    got, ref = leaves(grads), leaves(want_g)
    assert set(got) == set(ref) and len(ref) == 72
    assert "params/head/kernel" in ref  # untied
    assert not any("gate_proj" in name for name in ref)  # no gate projection
    for name, g in got.items():
        scale = float(jnp.linalg.norm(ref[name]))
        if name.endswith("select_bias"):  # a buffer: no gradient, either side
            assert scale == 0 == float(jnp.linalg.norm(g)), name
            continue
        assert scale > 0, name  # no other leaf is inert
        assert float(jnp.linalg.norm(g - ref[name])) <= 3e-4 * scale, name
    # the rows this share's experts got, of the 4 layers' T k routes
    assert chosen.shape == (4, T, size["num_experts_per_tok"])
    held = size["n_routed_experts"]
    assert int(counts[0]) == int((np.asarray(chosen) < held).sum())
    assert int(counts[2]) == 0  # none dropped
    # what the seed decides beside the normals
    st = seeded["params"]["stack"]["layers_0"]["ssd"]
    a = np.exp(np.asarray(st["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 1
    assert (np.asarray(st["D"]) == 1).all()
    step = np.asarray(jax.nn.softplus(st["dt_bias"]))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001


def test_reference_follows_adamw_like_the_trainer(size, seeded, tokens,
                                                  reference):
    """Three steps through ``LMTrainer.step``, every decayed leaf; the
    selection bias stays where it was on both sides."""
    model, T = build(size), size["seq_len"]
    opt = optax.adamw(lambda c: 3e-4 * jnp.minimum(1.0, (c + 1) / 2000),
                      b1=0.9, b2=0.95, weight_decay=0.1)
    trainer = lm.lm_setup(model, opt, lm.lm_mesh(1), model.comm, seq_len=T,
                          params=jax.tree.map(jnp.array, seeded), donate=False)
    assert trainer.startup["layers_by_kind"] == {
        "conv": 0, "attention": 1, "dense_ffn": 0, "expert_ffn": 4, "ssd": 4,
        "mixer_only": 5, "experts_only": 4}
    assert trainer.startup["attention"] == "dense"
    assert trainer.startup["moe_shared_width"] == 64
    assert trainer.startup["moe_routes"] == 4 * T * 2
    losses = [float(trainer.step(np.asarray(tokens)).loss) for _ in range(3)]
    got = reference.follow(jax.device_get(seeded), [np.asarray(tokens)] * 3, size)
    np.testing.assert_allclose(losses, got["loss"], rtol=3e-5)
    delta = leaves(jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b)), trainer.params, seeded))
    for name, d in delta.items():
        if name.endswith("select_bias"):
            assert d == 0 == got["delta_norm"][name], name
        else:
            np.testing.assert_allclose(d, got["delta_norm"][name], rtol=2e-3,
                                       err_msg=name)


def test_program_against_reference_under_the_tiny_limits():
    """The cell's own comparison at its tiny preset (bf16 compute), as the
    harness makes it: loss, first gradient (fetched leaf by leaf to the
    host), three-step update."""
    from benchmark import run as harness

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    _, cfg, traffic = harness.find_cell(bench, CELL)
    cell = harness.build_cell(cfg, traffic, 2**31 + 42, jax.devices()[:1], True)
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


# --- each part alone --------------------------------------------------------------

def test_each_mixer_alone_matches_the_reference(size, seeded, reference):
    """The Mamba-2 mixer, the expert layer (held part + shared expert) and
    grouped-query attention, each as its module against the reference's
    function on the same leaves."""
    T, d = size["seq_len"], size["hidden_size"]
    u = jnp.asarray(np.random.default_rng(7).standard_normal((T, d)),
                    jnp.float32)
    ident = lambda a: a
    model, comm = build(size), lm.lm_comm(1)
    with jax.default_matmul_precision("highest"):
        # M: published layer 0
        p = one_layer(seeded, "layers_0")["ssd"]
        got = looplm.SSDMixer(model.ssd, comm, jnp.float32, 1e-5).apply(
            {"params": p}, u)
        np.testing.assert_allclose(got, reference.mamba2(p, u, size, ident),
                                   rtol=2e-4, atol=2e-5)
        assert sorted(p) == ["A_log", "D", "conv", "conv_bias", "dt_bias",
                             "in_proj", "norm", "out_proj"]
        H, Pd = size["mamba_num_heads"], size["mamba_head_dim"]
        G, N = size["n_groups"], size["ssm_state_size"]
        assert p["in_proj"]["kernel"].shape == (d, 2 * H * Pd + 2 * G * N + H)
        assert p["conv"]["kernel"].shape == (4, H * Pd + 2 * G * N)
        assert p["norm"]["scale"].shape == (H * Pd,) and p["A_log"].shape == (H,)
        # E: published layer 1
        p = one_layer(seeded, "layers_1")["experts"]
        got, stats = looplm.HeldExpertsFFN(
            model.experts, comm, jnp.float32).apply({"params": p}, u)
        want, chosen = reference.expert_layer(p, u, size, ident)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        assert int(stats[0]) == int((np.asarray(chosen) < 4).sum())
        assert sorted(p) == ["down_proj", "router", "select_bias",
                             "shared_down_proj", "shared_up_proj", "up_proj"]
        assert p["router"]["kernel"].shape == (d, 16)  # every column
        # *: published layer 5; 4 query heads of 32 on 2 KV heads: H D != d
        p = one_layer(seeded, "layers_5")
        layer = looplm.LoopLMLayer(
            hidden=d, num_heads=4, head_dim=32, intermediate=0, comm=comm,
            num_kv_heads=2, rms_eps=1e-5, dtype=jnp.float32,
            sandwich_norm=False, has_ffn=False)
        got, _ = layer.apply({"params": p}, u, None)
        want = u + reference.attention(p, reference.rms_norm(
            p["norm_attn_in"]["scale"], u, 1e-5), size, ident)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        assert sorted(p) == ["k_proj", "norm_attn_in", "o_proj", "q_proj",
                             "v_proj"]
        assert p["q_proj"]["kernel"].shape == (d, 128) and d == 64


# --- the chunked recurrence -------------------------------------------------------

def ssd_inputs(T=37, H=4, Pd=3, G=2, N=5, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(T, H, Pd), jax.nn.softplus(f(T, H)), -jnp.exp(f(H)), f(T, G, N),
            f(T, G, N), f(H), f(H, Pd, N))


def recurrence_by_hand(x, dt, A, B, Cm, D, s0):
    K, s, ys = x.shape[1] // B.shape[1], s0, []
    for t in range(x.shape[0]):
        Bh, Ch = jnp.repeat(B[t], K, 0), jnp.repeat(Cm[t], K, 0)
        s = jnp.exp(dt[t] * A)[:, None, None] * s \
            + (dt[t][:, None] * x[t])[:, :, None] * Bh[:, None, :]
        ys.append(jnp.einsum("hpn,hn->hp", s, Ch) + D[:, None] * x[t])
    return jnp.stack(ys), s


def weighted(f, w, w2):
    def of(*a):
        y, last = f(*a)
        return (y * w).sum() + (last * w2).sum()
    return of


@pytest.fixture(scope="module")
def by_hand():
    """(inputs, weights, the loop by hand's value and every cotangent)."""
    args = ssd_inputs()
    w, w2 = ssd_inputs(seed=1)[0], ssd_inputs(seed=1)[6]
    return args, w, w2, jax.jit(jax.value_and_grad(
        weighted(recurrence_by_hand, w, w2), tuple(range(7))))(*args)


@pytest.mark.parametrize("chunk", [1, 5, 8, 37, 64],
                         ids=lambda c: f"chunk{c}")
def test_chunked_recurrence_is_the_loop_by_hand(chunk, by_hand):
    """Forward and every cotangent from a NONZERO start state (the start
    state's and through the last state too), for chunks that do and do not
    divide T = 37: a sequence that is no multiple of the chunk is padded
    with steps of dt = 0."""
    args, w, w2, want = by_hand
    got = jax.jit(jax.value_and_grad(weighted(
        lambda *a: ssd_op.ssd(*a, chunk=chunk), w, w2), tuple(range(7))))(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)


def test_chunked_recurrence_is_the_diagonal_selective_scan():
    """Two independent routes to one recurrence: the parameters broadcast to
    the diagonal form (``A`` a head -> ``[H P, N]``, ``B``, ``C`` a group ->
    one ``[T, N]`` a group of heads, ``dt`` a head -> a channel) through
    ``ops.selective_scan``, a group at a time; forward and both gradients."""
    T, H, Pd, G, N = 48, 4, 3, 2, 5
    x, dt, A, B, Cm, D, s0 = ssd_inputs(T, H, Pd, G, N, seed=2)
    w = ssd_inputs(T, H, Pd, G, N, seed=3)[0]
    K = H // G

    def diagonal(x, dt, A, B, Cm, D, s0):
        ys, lasts = [], []
        for g in range(G):
            hs = slice(g * K, (g + 1) * K)
            wide = lambda a: jnp.repeat(a, Pd, axis=-1)  # a head -> channels
            y, last = ss.selective_scan(
                x[:, hs].reshape(T, K * Pd), wide(dt[:, hs]),
                jnp.broadcast_to(wide(A[hs])[:, None], (K * Pd, N)),
                B[:, g], Cm[:, g], wide(D[hs]), s0[hs].reshape(K * Pd, N),
                chunk=16)
            ys.append(y.reshape(T, K, Pd))
            lasts.append(last.reshape(K, Pd, N))
        return jnp.concatenate(ys, 1), jnp.concatenate(lasts, 0)

    every = tuple(range(7))
    w2 = ssd_inputs(T, H, Pd, G, N, seed=3)[6]
    want = jax.jit(jax.value_and_grad(weighted(diagonal, w, w2), every))(
        x, dt, A, B, Cm, D, s0)
    got = jax.jit(jax.value_and_grad(weighted(
        lambda *a: ssd_op.ssd(*a, chunk=16), w, w2), every))(
        x, dt, A, B, Cm, D, s0)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)


def test_recurrence_takes_low_precision_streams_and_keeps_float32_inside():
    x, dt, A, B, Cm, D, _ = ssd_inputs(T=64)
    y32, _ = ssd_op.ssd(x, dt, A, B, Cm, D, chunk=16)
    xb, Bb, Cb = (t.astype(jnp.bfloat16) for t in (x, B, Cm))
    y, last = ssd_op.ssd(xb, dt, A, Bb, Cb, D, chunk=16)
    assert y.dtype == jnp.float32 and last.dtype == jnp.float32
    # the products' operands are bfloat16: a rounding each, no more
    np.testing.assert_allclose(y, y32, rtol=0.05, atol=0.05)
    assert float(jnp.abs(y - y32).max()) > 0
    g = jax.grad(lambda x_: ssd_op.ssd(x_, dt, A, Bb, Cb, D,
                                       chunk=16)[0].sum())(xb)
    assert g.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="groups"):
        ssd_op.ssd(x[:, :3], dt[:, :3], A[:3], B, Cm, D[:3])
    # decays of 60 a step over a chunk: exp only of differences <= 0
    y, last = ssd_op.ssd(x, 60.0 * jnp.ones_like(dt), A - 1.0, B, Cm, D,
                         chunk=16)
    assert bool(jnp.isfinite(y).all() and jnp.isfinite(last).all())
    grads = jax.grad(lambda dt_: ssd_op.ssd(
        x, dt_, A - 1.0, B, Cm, D, chunk=16)[0].sum())(60.0 * jnp.ones_like(dt))
    assert bool(jnp.isfinite(grads).all())


def sharded(fn, mesh, comm, n_in):
    from dgraph_tpu.comm.collectives import shard_map_checks

    return jax.shard_map(
        fn, mesh=mesh, in_specs=(P(),) + (P(comm.graph_axis),) * n_in,
        out_specs=(P(comm.graph_axis), P()),
        **shard_map_checks(relax="test: the halo's ppermute and the "
                                 "recurrence's gathered states"))


def test_mixer_sharded_over_four_ranks_equals_one():
    """The Mamba-2 mixer (in_proj, the four-tap convolution with its halo,
    the chunked recurrence with its state crossing the ranks, the gated
    grouped norm, out_proj) over a sequence sharded on 4 virtual devices
    against one device: the result and every gradient."""
    spec = Mamba2Mixer(heads=4, head_dim=8, groups=2, state=4, conv=4, chunk=8)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    one = looplm.SSDMixer(spec, None, jnp.float32)
    params = one.init(jax.random.key(1), x)
    comm4, mesh = lm.lm_comm(4), lm.lm_mesh(4, jax.devices()[:4])
    four = looplm.SSDMixer(spec, comm4, jnp.float32)

    def body(params, x, w):
        out = four.apply(params, x)
        return out, jax.lax.psum((out * w).sum(), comm4.graph_axis)

    f4 = sharded(body, mesh, comm4, 2)
    with jax.set_mesh(mesh):
        out4, _ = jax.jit(f4)(params, x, w)
        g4 = jax.jit(jax.grad(lambda p, x: f4(p, x, w)[1], (0, 1)))(params, x)
    np.testing.assert_allclose(out4, one.apply(params, x), rtol=1e-4,
                               atol=1e-5)
    g1 = jax.grad(lambda p, x: (one.apply(p, x) * w).sum(), (0, 1))(params, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g4)[0],
                            jax.tree.leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5, err_msg=str(path))
    assert float(jnp.abs(g1[0]["params"]["A_log"]).max()) > 0


def test_a_stack_of_mixers_over_a_sharded_sequence_equals_one_device(
        size, compiled_fresh):
    """Mamba-2 and attention layers of one half each through the trainer's
    loss on 4 virtual devices (ring attention, the halo, the state) against
    one: loss and gradients. (Expert layers over an axis are ROADMAP R9.)
    Compiled fresh: its multi-device CPU executable loaded back from the
    persistent compilation cache aborts the process."""
    T, V = 32, 97
    pattern = ("ssd+none", "attn+none")

    def model(comm):
        return LoopLM(
            vocab=V, hidden_size=32, num_layers=2, pattern=pattern,
            num_heads=4, head_dim=16, intermediate=0, comm=comm,
            num_kv_heads=2, rms_eps=1e-5, rope_theta=None, dtype=jnp.float32,
            sandwich_norm=False,
            ssd=Mamba2Mixer(heads=4, head_dim=16, groups=2, state=4, chunk=4))

    tokens = jnp.asarray(np.random.default_rng(5).integers(0, V, T), jnp.int32)
    one = model(lm.lm_comm(1))
    params = one.init(jax.random.key(2), tokens, jnp.arange(T))
    want, want_g = jax.value_and_grad(
        lm.make_lm_loss(one, None, one.comm, seq_len=T))(params, tokens)
    comm4, mesh = lm.lm_comm(4), lm.lm_mesh(4, jax.devices()[:4])
    with jax.set_mesh(mesh):
        got, got_g = jax.jit(jax.value_and_grad(lm.make_lm_loss(
            model(comm4), mesh, comm4, seq_len=T)))(params, tokens)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got_g)[0],
                            jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6, err_msg=str(path))


# --- the expert layer's forms and shares -------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once(
        reference):
    """16 experts in 4 shares of 4: each share routes over all 16 with the
    whole bias, normalises over all chosen, and adds its own experts' part
    AND the shared expert (every chip computes that alike). The four routed
    parts plus the shared expert counted ONCE equal the uncut reference
    layer; each share's output is the reference's with that share held."""
    rng = np.random.default_rng(0)
    T, d, f, fs, E, k = 96, 32, 16, 24, 16, 2
    size = {"num_experts_per_tok": k, "routed_scaling_factor": 2.5}
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    mat = lambda *s: {"kernel": jnp.asarray(
        rng.standard_normal(s) * 0.2, jnp.float32)}
    whole = {"router": mat(d, E), "up_proj": mat(E, d, f),
             "down_proj": mat(E, f, d), "shared_up_proj": mat(d, fs),
             "shared_down_proj": mat(fs, d),
             "select_bias": jnp.asarray(0.3 * rng.standard_normal(E),
                                        jnp.float32)}
    ident = lambda a: a
    with jax.default_matmul_precision("highest"):
        uncut, chosen = reference.expert_layer(
            whole, x, dict(size, first_expert=0), ident)
        shared = reference.shared_expert(x, whole, ident)
        routed_total, here = 0.0, 0
        for s in range(4):
            share = dict(whole, **{n: {"kernel": whole[n]["kernel"][
                4 * s:4 * s + 4]} for n in ("up_proj", "down_proj")})
            spec = HeldExperts(E, 4, k, f, first_held=4 * s, score="sigmoid",
                               select_bias=True, gate_eps=1e-20,
                               gate_scale=2.5, form="relu2", shared_width=fs)
            out, stats = looplm.HeldExpertsFFN(
                spec, lm.lm_comm(1), jnp.float32).apply({"params": share}, x)
            want, _ = reference.expert_layer(
                share, x, dict(size, first_expert=4 * s), ident)
            np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
            routed = out - shared  # what only this share can add
            assert 0 < float(jnp.abs(routed).max())
            routed_total, here = routed_total + routed, here + int(stats[0])
    assert here == T * k  # every route lands in exactly one share
    np.testing.assert_allclose(routed_total + shared, uncut, rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.abs(shared).max()) > 0
    # counted four times the sum would be off by three shared experts
    assert float(jnp.abs(routed_total + 4 * shared - uncut).max()) > 0.1


def test_the_ungated_form_takes_two_products_and_leaves_the_gated_one_alone():
    rng = np.random.default_rng(1)
    T, d, f, E, k = 64, 16, 8, 8, 2
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((4, d, f)) * 0.3, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((4, f, d)) * 0.3, jnp.float32)
    gates, experts = ex.route_topk(
        jnp.asarray(rng.standard_normal((T, E)), jnp.float32), k,
        score="sigmoid", eps=1e-20, scale=2.5)
    # the gated form: the parent's call and the spelled form, one program
    as_was = jax.make_jaxpr(lambda *a: ex.held_experts_ffn(*a))(
        x, gates, experts, wg, wu, wd)
    spelled = jax.make_jaxpr(lambda *a: ex.held_experts_ffn(
        *a, form="gated_silu"))(x, gates, experts, wg, wu, wd)
    assert str(as_was) == str(spelled)
    assert str(as_was).count("= ragged_dot_general[") == 3
    relu2 = jax.make_jaxpr(lambda x, g, e, u, d_: ex.held_experts_ffn(
        x, g, e, None, u, d_, form="relu2"))(x, gates, experts, wu, wd)
    assert str(relu2).count("= ragged_dot_general[") == 2
    out, stats = ex.held_experts_ffn(x, gates, experts, None, wu, wd,
                                     form="relu2")
    want = np.zeros((T, d), np.float32)
    for t in range(T):
        for c in range(k):
            e = int(experts[t, c])
            if e < 4:
                want[t] += float(gates[t, c]) * (np.square(np.maximum(
                    np.asarray(x[t]) @ np.asarray(wu[e]), 0)) @ np.asarray(wd[e]))
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    assert int(stats[0]) == int((np.asarray(experts) < 4).sum())
    for bad in (dict(form="relu2", w_gate=wg), dict(form="gated_silu",
                                                    w_gate=None),
                dict(form="gelu", w_gate=wg)):
        with pytest.raises(ValueError, match="expert form"):
            ex.held_experts_ffn(x, gates, experts, bad["w_gate"], wu, wd,
                                form=bad["form"])
    # a gated model's parameter tree has no shared leaves, an ungated one no gate
    gated = looplm.HeldExpertsFFN(HeldExperts(E, 4, k, f), lm.lm_comm(1),
                                  jnp.float32).init(jax.random.key(0), x)
    assert sorted(gated["params"]) == ["down_proj", "gate_proj", "router",
                                       "up_proj"]
    both = looplm.HeldExpertsFFN(
        HeldExperts(E, 4, k, f, shared_width=12), lm.lm_comm(1),
        jnp.float32).init(jax.random.key(0), x)
    assert sorted(both["params"]) == [
        "down_proj", "gate_proj", "router", "shared_down_proj",
        "shared_gate_proj", "shared_up_proj", "up_proj"]


def test_a_buffer_the_row_tile_does_not_divide_takes_a_smaller_tile():
    """6 routes a token over the 128-token probe of ``model.init``: 768 rows,
    which the 512-row tile does not divide (the kernel pads no tile)."""
    assert ex.grouped_tile_rows(768) == 256
    assert ex.grouped_tile_rows(49152) == ex.GROUPED_TILE_ROWS == 512
    assert ex.grouped_tile_rows(256) == 256 and ex.grouped_tile_rows(96) == 96
    assert ex._grouped_tiling(768, 2688, 1856) == (256, 128, 1856)


# --- the kinds of one half ----------------------------------------------------------

def test_kinds_of_one_half_are_spelled_none(size):
    assert looplm.split_kind("ssd+none") == ("ssd", "none")
    assert looplm.split_kind("none+experts") == ("none", "experts")
    assert looplm.split_kind("attn+none") == ("attn", "none")
    for bad in ("none+none", "ssd", "experts", "ssd+", "+experts", "M"):
        with pytest.raises(ValueError, match="layer kind"):
            looplm.split_kind(bad)
    assert lm.layers_by_kind(PATTERN) == {
        "conv": 0, "attention": 1, "dense_ffn": 0, "expert_ffn": 4, "ssd": 4,
        "mixer_only": 5, "experts_only": 4}
    # the kinds that were there count as they did
    assert lm.layers_by_kind(["conv+dense", "attn+experts"]) == {
        "conv": 1, "attention": 1, "dense_ffn": 1, "expert_ffn": 1}
    with pytest.raises(ValueError, match="need `ssd`"):
        LoopLM(vocab=8, hidden_size=8, num_layers=1, pattern=("ssd+none",),
               num_heads=1, head_dim=8, intermediate=0,
               comm=lm.lm_comm(1)).init(
            jax.random.key(0), jnp.zeros(8, jnp.int32), jnp.arange(8))
    # every layer is a run of its own, one half each: no leaf of the other
    model = build(size)
    T = size["seq_len"]
    tree = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros(T, jnp.int32), jnp.arange(T)))
    stack = tree["params"]["stack"]
    assert sorted(stack) == [f"layers_{i}" for i in range(9)] + ["norm_f"]
    assert sorted(stack["layers_0"]) == ["norm_ssd_in", "ssd"]
    assert sorted(stack["layers_1"]) == ["experts", "norm_mlp_in"]
    assert stack["layers_1"]["experts"]["up_proj"]["kernel"].shape \
        == (1, 4, 64, 32)


def test_a_one_layer_run_of_one_half_is_rematerialised_behind_the_barrier(
        size, seeded, tokens):
    """ROADMAP D19: a run of one layer is a one-trip loop that XLA inlines;
    without the barrier the recomputation merges with the forward pass. The
    new kinds' runs carry it (``prevent_cse`` on the layer's checkpoint),
    attention alone in its layer too; the accepted kinds' runs do not."""
    model = build(size)
    loss = lm.make_lm_loss(model, None, model.comm, seq_len=size["seq_len"])
    text = str(jax.make_jaxpr(jax.grad(lambda p: loss(p, tokens)[0]))(seeded))
    assert text.count("prevent_cse=True") >= 9


def test_setup_counts_the_new_kinds(size, monkeypatch):
    from dgraph_tpu.obs import metrics

    reg = metrics.Metrics()
    monkeypatch.setattr(metrics, "default_registry", reg)
    monkeypatch.setattr(lm, "default_registry", reg)
    model = build(size)
    lm.lm_setup(model, optax.sgd(0.1), lm.lm_mesh(1), model.comm,
                seq_len=size["seq_len"])
    c = reg.snapshot()["counters"]
    assert (c["lm.layers.ssd"], c["lm.layers.experts_only"],
            c["lm.layers.mixer_only"], c["lm.layers.attention"],
            c["lm.layers.expert_ffn"]) == (4, 4, 5, 1, 4)
    assert (c["lm.ssd.heads"], c["lm.ssd.groups"], c["lm.ssd.state"],
            c["lm.ssd.chunk"]) == (8, 2, 8, 32)
    assert c["moe.shared_width"] == 64 and c["moe.experts_held"] == 4
    assert c["lm.attention.head_dim"] == 32 and c["lm.attention.dense"] == 1
    assert "lm.ssm.state" not in c  # the Mamba-1 mixer's, not this one's


# --- the configuration -----------------------------------------------------------------

def test_configuration_holds_every_published_number():
    cfg = config()
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    }
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "n_routed_experts": 128, "vocab_size": 131072}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (9, 8, 16384)
    assert set(cfg) - set(published) == {
        "name", "builder", "reference", "source", "paper", "num_hidden_layers",
        "n_routed_experts", "vocab_size", "published", "reduced", "deployment",
        "why_layers", "sizes", "tiny", "assumed", "correct"}
    assert "16 expert-parallel chips" in cfg["deployment"]
    s = cfg["sizes"]
    # no width is cut: the sizes the cell runs are the published ones
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
                "ssm_state_size", "conv_kernel", "chunk_size",
                "moe_intermediate_size", "moe_shared_expert_intermediate_size",
                "num_experts_per_tok", "routed_scaling_factor",
                "mlp_hidden_act", "layer_norm_epsilon"):
        assert s[key] == cfg[key], key
    assert s["n_routed_experts_total"] == 128 and s["n_routed_experts"] == 8
    assert s["hybrid_override_pattern"] \
        == cfg["hybrid_override_pattern"][:9] == "MEMEM*EME"
    t = cfg["tiny"]
    assert t["hybrid_override_pattern"] == "MEMEM*EME" and t["n_groups"] >= 2
    assert (t["n_routed_experts"], t["n_routed_experts_total"],
            t["num_experts_per_tok"]) == (4, 16, 2)
    assert t["moe_shared_expert_intermediate_size"] > 0
    # the parameter count of the cut, by the issue's arithmetic
    d, inner = 2688, 64 * 64
    mixer = d * (2 * inner + 2 * 8 * 128 + 64) + inner * d \
        + 4 * 6144 + 6144 + 3 * 64 + inner + d
    attn = d * (32 + 2 + 2) * 128 + 32 * 128 * d + d
    outside = 2 * d * 3712 + d * 128 + 128 + d
    total = 4 * mixer + attn + 4 * (outside + 8 * 2 * d * 1856) \
        + 2 * 16384 * d + d
    assert total == 666963456
    assert abs(total * 16 / 1e9 - 10.67) < 0.005


def test_work_counts_by_hand():
    from benchmark import opsbytes
    from benchmark.work import nemotron_moe_flops, nemotron_ssd_proj_flops

    info = {"seq_len": 8192, "hidden": 2688, "heads": 32, "head_dim": 128,
            "ssd_heads": 64, "ssd_head_dim": 64, "ssd_groups": 8,
            "ssd_state": 128, "ssd_chunk": 128, "expert_width": 1856,
            "experts_per_token": 6, "layers_ssd": 4, "layers_attention": 1,
            "layers_expert_ffn": 4, "loop_steps": 1}
    assert nemotron_ssd_proj_flops.weights(info) == 2688 * 10304 + 4096 * 2688
    assert round(4 * nemotron_ssd_proj_flops.weights(info) / 1e6, 1) == 154.8
    assert opsbytes.work("nemotron_ssd_proj_flops", info, 0) \
        == 3 * 2 * 8192 * 4 * (2688 * 10304 + 4096 * 2688)
    assert round(nemotron_moe_flops.weights(info) / 1e6, 2) == 9.98
    core = opsbytes.work("nemotron_ssd_core_flops", info, 0)
    assert core == 3 * 4 * 8192 * (64.5 * 2 * (1024 + 4096)
                                   + 2 * 2 * 64 * 64 * 128)
    assert opsbytes.work("nemotron_attn_flops", info, 0) \
        == 3 * 2 * 8192 * 8192 * 32 * 128
