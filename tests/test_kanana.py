"""A sparse-expert decoder that attends through a LATENT with a decoupled
rotary key (``models/looplm.py``'s mixer kind ``attn_mla``,
:class:`LatentAttention`: q.k heads of ``nope_dim + rope_dim`` on value heads
of ``v_head_dim``, the rotated key one head that all read), one leading dense
layer and then sigmoid-routed SwiGLU experts beside a shared expert
(kanana-2-30b-a3b, ``model_type`` deepseek_v3), on the CPU at tiny sizes,
seeded weights: against the benchmark's plain reference
(``benchmark/reference/kanana.py``), the mixer alone against a direct
transcription of its equations, the shared rotary key, the shares of an expert
layer against the uncut layer, the splash kernels at a q.k head off the lanes
on a value head of another size, what ``lm_setup`` resolves and counts, the
configuration's numbers and the attention's work by hand."""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from dgraph_tpu.models import looplm
from dgraph_tpu.models.looplm import HeldExperts, LatentAttention
from dgraph_tpu.parallel import sequence as seq
from dgraph_tpu.train import lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CELL = "kanana2_30b_a3b.seq16k"
PATTERN = ("attn_mla+dense",) + ("attn_mla+experts",) * 2
IDENT = lambda a: a


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kanana2_30b_a3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def size():
    return dict(config()["tiny"], compute_dtype="float32")


@pytest.fixture(scope="module")
def reference():
    from benchmark.reference import kanana

    return kanana


def build(size, comm=None):
    from benchmark.builders.kanana import model_of

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


def a_layer(size, **fields):
    """One ``LoopLMLayer`` of the tiny preset, float32."""
    model = build(size)
    return looplm.LoopLMLayer(
        hidden=size["hidden_size"], num_heads=size["num_attention_heads"],
        head_dim=size["qk_head_dim"], intermediate=size["intermediate_size"],
        comm=lm.lm_comm(1), rms_eps=1e-6, dtype=jnp.float32,
        sandwich_norm=False, mixer="attn_mla",
        **{"mla": model.mla, "experts": model.experts, **fields})


# --- against the plain reference ------------------------------------------------

def test_logits_loss_and_every_gradient_leaf_match_reference(
        size, seeded, tokens, reference):
    model, T = build(size), size["seq_len"]
    assert model.layer_kinds() == PATTERN
    assert looplm.layer_runs(PATTERN) == [("attn_mla+dense", 1),
                                          ("attn_mla+experts", 2)]
    got_logits, _ = model.apply(seeded, tokens, jnp.arange(T))
    loss_fn = lm.make_lm_loss(model, None, model.comm, seq_len=T)
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(seeded, tokens)
    with jax.default_matmul_precision("highest"):
        h, chosen = reference.hidden_states(seeded, tokens, size, IDENT)
        (want, _), want_g = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, tokens, size, IDENT),
            has_aux=True))(seeded)
        # float32 on both sides: what is left is the order of the sums (the
        # program's grouped products and its blocked loss against plain
        # loops), 1e-5 of a logit of order 1
        np.testing.assert_allclose(got_logits[0], reference.logits(seeded, h),
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    got, ref = leaves(grads), leaves(want_g)
    # embed, head, norm_f; the dense layer's 10 leaves; an expert layer's 7
    # outside its experts and 8 inside (the bias among them)
    assert set(got) == set(ref) and len(ref) == 3 + 10 + 15
    assert "params/head/kernel" in ref  # untied
    frozen = [n for n in ref if n.endswith("select_bias")]
    assert frozen == ["params/stack/layers_1/experts/select_bias"]
    for name, g in got.items():
        scale = float(jnp.linalg.norm(ref[name]))
        if name in frozen:  # steers the choice only: no gradient reaches it
            assert scale == 0 and float(jnp.abs(g).max()) == 0
            continue
        assert scale > 0, name  # no leaf is inert
        # the same float32 sums in another order; a bfloat16 run of this
        # float32 configuration reads 1e-2 here
        assert float(jnp.linalg.norm(g - ref[name])) <= 3e-4 * scale, name
    k = size["num_experts_per_tok"]
    assert chosen.shape == (2, T, k)
    held = size["n_routed_experts"]
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
        "conv": 0, "attention": 3, "dense_ffn": 1, "expert_ffn": 2,
        "attn_mla": 3}
    assert st["attention"] == "dense"
    assert st["latent_attention"] == {
        "qk_head_dim": 24, "kv_rank": 32, "rope_dim": 8}
    assert st["moe_routes"] == 2 * T * 2 and st["moe_shared_width"] == 64
    losses = [float(trainer.step(np.asarray(tokens)).loss) for _ in range(3)]
    got = reference.follow(jax.device_get(seeded), [np.asarray(tokens)] * 3, size)
    # three float32 steps; AdamW's first steps divide by sqrt(v) ~ |g|, so a
    # leaf's change is lr-sized whatever its gradient's size and follows the
    # gradient's SIGN pattern: 2e-3 of its norm holds the order of the sums
    np.testing.assert_allclose(losses, got["loss"], rtol=3e-5)
    delta = leaves(jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b)), trainer.params, seeded))
    for name, d in delta.items():
        np.testing.assert_allclose(d, got["delta_norm"][name], rtol=2e-3,
                                   atol=1e-12, err_msg=name)
    # the selection bias is a buffer: the step leaves it as it was
    assert delta["params/stack/layers_1/experts/select_bias"] == 0


def test_program_against_reference_under_the_tiny_limits():
    """The cell's own comparison at its tiny preset (bf16 compute), as the
    harness makes it: loss, first gradient (fetched leaf by leaf to the
    host), three-step update."""
    from benchmark import run as harness

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    _, cfg, traffic = harness.find_cell(bench, CELL)
    cell = harness.build_cell(cfg, traffic, 2**31 + 49, jax.devices()[:1], True)
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


# --- the latent mixer alone -----------------------------------------------------------

def transcription(p, x, sp, H, theta):
    """``W_o Attn(x)`` by the module docstring's equations, one head and one
    query row at a time, in numpy float64: nothing of the program or of the
    reference."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)
    T = x.shape[0]
    Dn, Dr, Dv, R = sp.nope_dim, sp.rope_dim, sp.v_head_dim, sp.kv_rank

    def rotate(vec, pos):  # the published pairs (2i, 2i + 1)
        out = vec.copy()
        for i in range(Dr // 2):
            ang = pos * theta ** (-2.0 * i / Dr)
            a, b = vec[2 * i], vec[2 * i + 1]
            out[2 * i] = a * np.cos(ang) - b * np.sin(ang)
            out[2 * i + 1] = b * np.cos(ang) + a * np.sin(ang)
        return out

    q = (x @ p["q_proj"]["kernel"]).reshape(T, H, Dn + Dr)
    ckr = x @ p["kv_a_proj"]["kernel"]
    c, k_r = ckr[:, :R], ckr[:, R:]
    c = c / np.sqrt((c * c).mean(-1, keepdims=True) + 1e-6) \
        * p["kv_a_norm"]["scale"]
    kv = (c @ p["kv_b_proj"]["kernel"]).reshape(T, H, Dn + Dv)
    k_r = np.stack([rotate(k_r[t], t) for t in range(T)])
    out = np.zeros((T, H, Dv))
    for h in range(H):
        qh = np.stack([np.concatenate(
            [q[t, h, :Dn], rotate(q[t, h, Dn:], t)]) for t in range(T)])
        kh = np.concatenate([kv[:, h, :Dn], k_r], -1)  # the ONE rotary key
        for t in range(T):
            s = qh[t] @ kh[:t + 1].T / np.sqrt(Dn + Dr)
            w = np.exp(s - s.max())
            out[t, h] = (w / w.sum()) @ kv[:t + 1, h, Dn:]
    return out.reshape(T, H * Dv) @ p["o_proj"]["kernel"]


def test_the_latent_mixer_alone_is_its_equations(size, seeded, reference):
    """Three head sizes that all differ (16 without positions + 8 rotated on
    values of 32), a rotary table of ``rope_dim``, the softmax scale of the
    whole q.k head."""
    sp, H, T = build(size).mla, size["num_attention_heads"], 48
    assert (sp.nope_dim, sp.rope_dim, sp.v_head_dim, sp.kv_rank) \
        == (16, 8, 32, 32) and sp.qk_head_dim == 24
    x = jnp.asarray(np.random.default_rng(7).standard_normal(
        (T, size["hidden_size"])), jnp.float32)
    rope = looplm.rotary_tables(jnp.arange(T), sp.rope_dim,
                                float(size["rope_theta"]))
    assert rope[0].shape == (T, sp.rope_dim // 2)
    p = one_layer(seeded, "layers_0")
    assert sorted(p) == ["down_proj", "gate_proj", "kv_a_norm", "kv_a_proj",
                         "kv_b_proj", "norm_attn_in", "norm_mlp_in", "o_proj",
                         "q_proj", "up_proj"]
    d = size["hidden_size"]
    assert p["q_proj"]["kernel"].shape == (d, H * 24)
    assert p["kv_a_proj"]["kernel"].shape == (d, 32 + 8)
    assert p["kv_a_norm"]["scale"].shape == (32,)
    assert p["kv_b_proj"]["kernel"].shape == (32, H * (16 + 32))
    assert p["o_proj"]["kernel"].shape == (H * 32, d)
    ident = dict(p, norm_attn_in={"scale": jnp.ones(d)})  # x is the normed x
    mixer = a_layer(size, has_ffn=False)
    with jax.default_matmul_precision("highest"):
        got, _ = mixer.apply({"params": ident}, x, rope)
    want = transcription(p, x / np.sqrt((np.asarray(x, np.float64) ** 2).mean(
        -1, keepdims=True) + 1e-6), sp, H, float(size["rope_theta"]))
    # float32 against float64: 1e-6 of outputs of order 1
    np.testing.assert_allclose(got - x, want, rtol=2e-4, atol=2e-5)
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    with jax.default_matmul_precision("highest"):
        ref = reference.attention(p, normed, jnp.arange(T), size, IDENT)
    np.testing.assert_allclose(ref, want, rtol=2e-4, atol=2e-5)


def test_adjacent_pairs_are_not_the_half_pairs(size):
    """``apply_rotary_pairs`` rotates ``(2i, 2i + 1)``; ``apply_rotary``'s
    ``(i, i + D / 2)`` equals it under ONE fixed permutation of the columns
    (which is why a checkpoint's W_q and W_kva columns must not be
    permuted for this program)."""
    T, D = 16, 8
    x = jnp.asarray(np.random.default_rng(1).standard_normal((T, 3, D)),
                    jnp.float32)
    rope = looplm.rotary_tables(jnp.arange(T), D, 1e6)
    pairs = looplm.apply_rotary_pairs(x, *rope)
    halves = looplm.apply_rotary(x, *rope)
    assert float(jnp.abs(pairs - halves)[1:].max()) > 1e-2
    perm = np.concatenate([np.arange(0, D, 2), np.arange(1, D, 2)])
    np.testing.assert_allclose(
        looplm.apply_rotary(x[..., perm], *rope), pairs[..., perm],
        rtol=1e-6, atol=1e-6)
    # a rotation: norms of each pair are kept, position 0 is the identity
    np.testing.assert_allclose(pairs[0], x[0], rtol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(pairs, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_all_heads_read_the_one_rotary_key(size, seeded):
    """Perturbing the rotary key's columns of ``W_kva`` (its last ``rope_dim``)
    moves EVERY head's output; perturbing one head's columns of ``W_kvb``
    moves that head alone."""
    sp, H, T = build(size).mla, size["num_attention_heads"], 32
    x = jnp.asarray(np.random.default_rng(5).standard_normal(
        (T, size["hidden_size"])), jnp.float32)
    rope = looplm.rotary_tables(jnp.arange(T), sp.rope_dim, 1e6)
    p = one_layer(seeded, "layers_0")
    # W_o as the identity on the heads' concatenation, so a head's output is
    # a block of columns of the result
    eye = jnp.eye(H * sp.v_head_dim, size["hidden_size"])
    p = dict(p, o_proj={"kernel": eye})
    mixer = a_layer(size, has_ffn=False)
    run = lambda q: mixer.apply({"params": q}, x, rope)[0] - x
    base = run(p)
    kva = p["kv_a_proj"]["kernel"]
    moved = run(dict(p, kv_a_proj={"kernel": kva.at[:, sp.kv_rank:].add(0.5)}))
    kept = min(H * sp.v_head_dim, size["hidden_size"]) // sp.v_head_dim
    by_head = jnp.abs(moved - base)[:, :kept * sp.v_head_dim].reshape(
        T, kept, sp.v_head_dim).max((0, 2))
    assert kept >= 2 and float(by_head.min()) > 1e-4
    kvb = p["kv_b_proj"]["kernel"]  # columns: head-major [H, Dn + Dv]
    width = sp.nope_dim + sp.v_head_dim
    one = run(dict(p, kv_b_proj={"kernel": kvb.at[:, width:2 * width].add(
        0.5)}))
    by_head = jnp.abs(one - base)[:, :kept * sp.v_head_dim].reshape(
        T, kept, sp.v_head_dim).max((0, 2))
    assert float(by_head[1]) > 1e-4
    assert float(jnp.delete(by_head, 1).max()) == 0


# --- the shares add up --------------------------------------------------------------

@pytest.mark.parametrize("shares", [8, 16])
def test_the_shares_add_up_to_the_uncut_layer(shares, reference):
    """One expert layer whole (attention through the latent, 32 experts, a
    shared expert) in 8 shares of 4 experts (16 of 2): each share routes over
    all 32 with the sigmoid router and its bias, and gives ``h + its own
    experts' part + the shared expert``; attention, the residual and the
    shared expert are computed alike by every chip, so they count ONCE: the
    shares' outputs less ``shares - 1`` times that common part add up to the
    uncut reference layer, and every route lands in exactly one share."""
    rng = np.random.default_rng(0)
    T, d, H, E, k, f = 64, 32, 2, 32, 3, 16
    n = E // shares
    sp = LatentAttention(kv_rank=16, nope_dim=8, rope_dim=4, v_head_dim=12)
    size = {"num_attention_heads": H, "kv_lora_rank": 16,
            "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 12,
            "rope_theta": 1e6, "rms_norm_eps": 1e-6, "num_experts_per_tok": k,
            "routed_scaling_factor": 2.448, "first_expert": 0}
    mat = lambda *s: {"kernel": jnp.asarray(
        rng.standard_normal(s) * s[-2] ** -0.5, jnp.float32)}
    gain = lambda m: {"scale": jnp.asarray(
        1 + 0.1 * rng.standard_normal(m), jnp.float32)}
    experts = {"router": mat(d, E), "select_bias": jnp.asarray(
        0.1 * rng.standard_normal(E), jnp.float32),
        "gate_proj": mat(E, d, f), "up_proj": mat(E, d, f),
        "down_proj": mat(E, f, d), "shared_gate_proj": mat(d, 2 * f),
        "shared_up_proj": mat(d, 2 * f), "shared_down_proj": mat(2 * f, d)}
    whole = {"norm_attn_in": gain(d), "q_proj": mat(d, H * 12),
             "kv_a_proj": mat(d, 16 + 4), "kv_a_norm": gain(16),
             "kv_b_proj": mat(16, H * 20), "o_proj": mat(H * 12, d),
             "norm_mlp_in": gain(d), "experts": experts}
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    rope = looplm.rotary_tables(jnp.arange(T), 4, 1e6)
    with jax.default_matmul_precision("highest"):
        uncut, chosen = reference.layer(whole, x, jnp.arange(T), size, IDENT)
        h = x + reference.attention(
            whole, reference.rms_norm(whole["norm_attn_in"]["scale"], x, 1e-6),
            jnp.arange(T), size, IDENT)
        common = h + reference.shared_expert(
            reference.rms_norm(whole["norm_mlp_in"]["scale"], h, 1e-6),
            experts, IDENT)
        total, here, busy = 0.0, 0, 0
        for s in range(shares):
            held = {name: {"kernel": experts[name]["kernel"][n * s:n * (s + 1)]}
                    for name in ("gate_proj", "up_proj", "down_proj")}
            spec = HeldExperts(
                E, n, k, f, first_held=n * s, score="sigmoid",
                select_bias=True, gate_eps=1e-20, gate_scale=2.448,
                form="gated_silu", shared_width=2 * f)
            layer = looplm.LoopLMLayer(
                hidden=d, num_heads=H, head_dim=12, intermediate=0,
                comm=lm.lm_comm(1), dtype=jnp.float32, sandwich_norm=False,
                mixer="attn_mla", mla=sp, experts=spec)
            out, stats = layer.apply(
                {"params": dict(whole, experts=dict(experts, **held))}, x, rope)
            part = out - common  # this share's own experts' part
            busy += float(jnp.abs(part).max()) > 0
            total, here = total + part, here + int(stats[0])
    assert chosen.shape == (T, k) and here == T * k and busy > shares // 2
    np.testing.assert_allclose(common + total, uncut, rtol=2e-4, atol=2e-5)
    # ... and it is no small part that the shares carry
    assert float(jnp.abs(uncut - common).max()) > 0.1


# --- the kernels at a q.k head off the lanes on a value head of another size -------

def test_a_qk_head_off_the_lanes_on_another_value_head_in_interpret_mode():
    """The splash kernels at one key head a query head, q.k heads of 24 on
    value heads of 32 (24 is no multiple of the lanes, as 192 is not), under
    the causal mask (Mosaic interpreter on the CPU): forward and the three
    gradients against the dense oracle; passing latches that pair of sizes
    and no other."""
    assert seq._splash_selfcheck(seq.CausalMask(0), 1, interpret=True,
                                 head_dim=24, v_head_dim=32)
    assert ("causal", 1, (24, 32)) in seq._splash_verified
    assert ("causal", 1, 24) not in seq._splash_verified
    assert seq._head_key(192, 128) == (192, 128)
    # which columns of a kv tile go through the softmax at a time, by the
    # q.k head: narrower than the lanes, or not
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)

    block = lambda D: seq._splash_block_sizes(sk, 16384, D).block_kv_compute
    assert (block(128), block(64), block(192)) == (
        seq.SPLASH_KV_COMPUTE, seq.SPLASH_KV_COMPUTE_NARROW,
        seq.SPLASH_KV_COMPUTE)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_one_kernel_backward_at_a_qk_head_of_24_on_values_of_32(splash_backward, dtype):
    """ISSUE 50 at this configuration's grouping and head (tiny: one key head
    a query head, 24 | 32 as 192 | 128 is, causal): dq, dk, dv of the one
    backward kernel against the dense oracle's and the library's two
    kernels' (``conftest.py::splash_backward``)."""
    splash_backward(seq.CausalMask(512), 1, 24, 32, jnp.dtype(dtype))


def test_the_one_kernel_backward_at_the_cells_head_tile_and_chunk():
    """The one backward kernel as the cell runs it but for the rows: q.k
    heads of 192 on value heads of 128, tiles of 1024 rows
    (``FLASH_BLOCK``) taken 256 keys at a time (``pallas_attention.CHUNK``:
    four chunks a visit, which the 128-row tiles of the other cases do not
    reach), three tiles a side under the causal mask; float32, against the
    dense oracle."""
    from dgraph_tpu.ops import pallas_attention

    T, D, Dv = 3072, 192, 128
    assert (seq.flash_tile(T), pallas_attention.CHUNK) == (1024, 256)
    rng = np.random.default_rng(3)
    q, k = (jnp.asarray(rng.standard_normal((T, 1, D)), jnp.float32)
            for _ in range(2))
    v, w = (jnp.asarray(rng.standard_normal((T, 1, Dv)), jnp.float32)
            for _ in range(2))
    mask = seq.CausalMask(T)

    def grads(attend):
        return jax.grad(lambda *a: (attend(*a) * w).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda *a: seq._splash_dense(*a, mask=mask, scale=None,
                                             interpret=True))
    want = grads(lambda *a: seq.dense_attention(*a, mask=mask))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_zero_padded_q_and_k_give_the_same_attention():
    """What ``scripts/splash_head_sweep.py`` measured against the head as it
    is was the SAME attention: zero columns add nothing to a score while the
    softmax scale stays the unpadded head's."""
    rng = np.random.default_rng(2)
    T, H, D = 256, 2, 136
    q, k = (jnp.asarray(rng.standard_normal((T, H, D)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((T, H, 32)), jnp.float32)
    widen = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, -D % 128)))
    attend = lambda q, k: seq._splash_dense(
        q, k, v, mask=seq.CausalMask(T), scale=D ** -0.5, interpret=True)
    want = seq.dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(attend(q, k), want, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(attend(widen(q), widen(k)), want,
                               rtol=2e-3, atol=2e-3)


# --- what the launch resolves and counts -------------------------------------------

def test_setup_resolves_at_the_mixers_head_sizes_and_counts_them(size,
                                                                 monkeypatch):
    from dgraph_tpu.obs import metrics

    reg = metrics.Metrics()
    monkeypatch.setattr(metrics, "default_registry", reg)
    monkeypatch.setattr(lm, "default_registry", reg)
    asked = []
    real = lm.resolve_attention
    monkeypatch.setattr(lm, "resolve_attention", lambda *a, **kw: (
        asked.append((a[3:], kw)), real(*a, **kw))[1])
    model, T = build(size), size["seq_len"]
    assert model.attention_masks(T) is None and model.attention_mask(T) is None
    assert model.rotary_dim() == 8 and model.latent() == model.mla
    lm.lm_setup(model, optax.sgd(0.1), lm.lm_mesh(1), model.comm, seq_len=T)
    # 4 heads at the mixer's q.k head of 24 (NOT model.head_dim's), one key
    # head a query head, values of 32
    # ... and streams of the model's type (what the kernels' VMEM is counted in)
    assert asked == [((4, 24, None, 1), {"v_head_dim": 32,
                                         "dtype": jnp.dtype("float32")})]
    c = reg.snapshot()["counters"]
    assert (c["lm.layers.attention"], c["lm.layers.attn_mla"],
            c["lm.layers.dense_ffn"], c["lm.layers.expert_ffn"]) == (3, 3, 1, 2)
    assert (c["lm.attention.qk_head_dim"], c["lm.attention.v_head_dim"],
            c["lm.attention.kv_rank"], c["lm.attention.rope_dim"]) \
        == (24, 32, 32, 8)
    assert c["moe.shared_width"] == 64 and c["moe.experts_held"] == 4
    # a latent layer needs its sizes and shares a stack with no other
    # attending mixer
    init = lambda m: m.init(jax.random.key(0), jnp.zeros(8, jnp.int32),
                            jnp.arange(8))
    with pytest.raises(ValueError, match="mla"):
        init(model.clone(mla=None))
    with pytest.raises(ValueError, match="ONE pair of head sizes"):
        init(model.clone(pattern=("attn+dense",) + PATTERN[1:]))
    assert "attn_mla" in looplm.LAYER_MIXERS and "attn_mla" in looplm.ATTENDING
    assert looplm.split_kind("attn_mla+experts") == ("attn_mla", "experts")
    # a stack without latent layers has none of this
    assert looplm.LoopLM(vocab=8, hidden_size=8, num_layers=1, num_heads=1,
                         head_dim=8, intermediate=8).latent() is None


def test_the_ring_refuses_unequal_head_sizes_by_name():
    """``comm.seq_attention`` over a sharded sequence carries one head size
    (ROADMAP R10): values of another size are refused, by name, before any
    collective is traced."""
    comm = lm.lm_comm(2)
    q = jnp.zeros((8, 2, 24))
    v = jnp.zeros((8, 2, 32))
    for impl in ("ring", "ulysses"):
        with pytest.raises(NotImplementedError, match="one head size"):
            comm.seq_attention(q, q, v, causal=True, impl=impl)


# --- the configuration -----------------------------------------------------------------

# the catalog row's ``config`` (model-configs/architectures.jsonl), whole
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}


def test_configuration_holds_every_published_number():
    cfg = config()
    assert cfg["source"] == ("https://huggingface.co/kakaocorp/kanana-2-30b-"
                             "a3b-instruct-2601/blob/main/config.json")
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]} \
        == {"num_hidden_layers": 48, "n_routed_experts": 128,
            "vocab_size": 128256}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 8, 16032)
    assert cfg["vocab_size"] * 8 == 128256  # an eighth, the floor
    assert set(cfg) - set(PUBLISHED) == {
        "name", "builder", "reference", "source", "paper", "published",
        "reduced", "deployment", "why_layers", "sizes", "tiny", "assumed",
        "correct"}
    assert "16 expert-parallel chips" in cfg["deployment"]
    assert "15.58" in cfg["why_layers"] and "12.00" in cfg["why_layers"]
    s = cfg["sizes"]
    # no width is cut: the sizes the cell runs are the published ones
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "qk_nope_head_dim", "qk_rope_head_dim", "qk_head_dim",
                "v_head_dim", "kv_lora_rank", "q_lora_rank",
                "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "n_shared_experts",
                "routed_scaling_factor", "rope_theta", "rope_interleave",
                "rms_norm_eps", "first_k_dense_replace", "scoring_func",
                "topk_method", "n_group", "topk_group", "norm_topk_prob"):
        assert s[key] == cfg[key], key
    assert (s["n_routed_experts_total"], s["n_routed_experts"],
            s["first_expert"], s["moe_buffer_rows"]) == (128, 8, 0, None)
    t = cfg["tiny"]
    assert (t["qk_nope_head_dim"], t["qk_rope_head_dim"], t["v_head_dim"],
            t["kv_lora_rank"]) == (16, 8, 32, 32)
    # the parameter count of the cut, by the issue's arithmetic
    d, H = 2048, 32
    attn = d * H * 192 + d * (512 + 64) + 512 + 512 * H * 256 + H * 128 * d
    assert round(attn / 1e6, 2) == 26.35
    expert, shared = 3 * d * 768, 3 * d * 1536
    dense_layer = attn + 3 * d * 6144 + 2 * d
    assert round(dense_layer / 1e6, 2) == 64.10
    expert_layer = lambda held: attn + held * expert + shared \
        + d * 128 + 128 + 2 * d
    assert round(expert_layer(16) / 1e6, 2) == 111.55  # cut A
    assert round(expert_layer(8) / 1e6, 2) == 73.80  # cut B, taken
    whole = dense_layer + 47 * expert_layer(128) + 2 * 128256 * d + d
    assert round(whole / 1e9, 2) == 30.67
    total = dense_layer + 4 * expert_layer(8) + 2 * 16032 * d + d
    assert total == 424961024 and abs(total * 16 / 1e9 - 6.80) < 0.005
    # the program's model at these sizes holds exactly that
    model = build(s)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros(128, jnp.int32), jnp.arange(128)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == total
    assert model.pattern == ("attn_mla+dense",) + ("attn_mla+experts",) * 4
    assert model.mla == LatentAttention(512, 128, 64, 128)
    assert model.experts == HeldExperts(
        128, 8, 6, 768, score="sigmoid", select_bias=True, gate_eps=1e-20,
        gate_scale=2.448, form="gated_silu", shared_width=1536)


def test_attention_work_by_hand():
    """P causal pairs, H heads: a forward ``2 P H (192 + 128)``, the backward
    ``2 P H (3 x 192 + 2 x 128)``, the forward twice under remat; the padded
    form changes nothing (the model's 192 is counted, not the kernels'
    256)."""
    from benchmark import opsbytes
    from benchmark.work import kanana_attn_flops

    info = {"seq_len": 8, "heads": 3, "qk_head_dim": 192, "v_head_dim": 128,
            "layers_attention": 2, "loop_steps": 1, "remat": True}
    P = 8 * 9 // 2
    assert kanana_attn_flops.pairs(info) == P == seq.CausalMask(8).pairs()
    fwd, bwd = 2 * P * 3 * (192 + 128), 2 * P * 3 * (3 * 192 + 2 * 128)
    assert opsbytes.work("kanana_attn_flops", info, 0) == 2 * (2 * fwd + bwd)
    assert opsbytes.work("kanana_attn_flops", dict(info, remat=False), 7) \
        == 2 * (fwd + bwd)
    assert opsbytes.work("kanana_attn_flops",
                         dict(info, qk_padded_to=256), 0) == 2 * (2 * fwd + bwd)
    # the cell's: 12.65 TFLOP a layer at 16 384 tokens, 63.2 a step
    cell = dict(info, seq_len=16384, heads=32, layers_attention=5)
    assert kanana_attn_flops.pairs(cell) == 134225920
    assert round(opsbytes.work("kanana_attn_flops", cell, 0) / 5e12, 2) == 12.65
