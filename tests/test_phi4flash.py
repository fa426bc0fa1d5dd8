"""A decoder-hybrid-decoder (``models/looplm.py``'s kinds ``ssm``, ``ssm_keep``,
``diff_win``, ``diff_keep``, ``gmu``, ``cross``: selective state-space layers,
differential attention under a window and full, a gated memory unit and
cross-attention that read what one earlier layer kept), the chunked selective
scan (``ops/selective_scan.py``) and the window mask with a v head of its own
(``parallel/sequence.py``) on the CPU at tiny sizes, seeded weights: against
the benchmark's plain reference (``benchmark/reference/phi4flash.py``), the
scan by hand, the scan and the convolution over a sharded sequence, the
vocabulary's shares against the uncut head. (That the configurations that were
there still trace to their parents' programs is ``tests/test_lfm2.py``'s
``test_no_pattern_is_the_parents_program``, now with the several-kinds
pattern as a third case.)"""

import json
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from dgraph_tpu.models import looplm
from dgraph_tpu.models.looplm import LoopLM, StateSpace
from dgraph_tpu.ops import selective_scan as ss
from dgraph_tpu.parallel import sequence as seq
from dgraph_tpu.train import lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CELL = "phi4_mini_flash.seq8k"
PATTERN = ["ssm+dense", "diff_win+dense", "ssm_keep+dense", "diff_keep+dense",
           "gmu+dense", "cross+dense"]


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi4_mini_flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def size():
    return dict(config()["tiny"], compute_dtype="float32")


@pytest.fixture(scope="module")
def reference():
    from benchmark.reference import phi4flash

    return phi4flash


def build(size, comm=None):
    from benchmark.builders.phi4flash import model_of

    return model_of(size, comm or lm.lm_comm(1))


@pytest.fixture(scope="module")
def seeded(size):
    from benchmark.builders.phi4flash import seeded_params

    T = size["seq_len"]
    shapes = jax.eval_shape(lambda: build(size).init(
        jax.random.key(0), jnp.zeros(T, jnp.int32), jnp.arange(T)))
    return seeded_params(shapes, 11, None, size["hidden_size"])


@pytest.fixture(scope="module")
def tokens(size):
    from benchmark.builders.looplm import zipf_tokens

    return jnp.asarray(zipf_tokens(np.random.default_rng(3), size["seq_len"],
                                   size["vocab_size"], 1.0))


def leaves(tree):
    return {"/".join(str(k.key) for k in path): a
            for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


# --- against the plain reference ------------------------------------------------

def test_loss_and_every_gradient_leaf_match_reference(size, seeded, tokens,
                                                      reference):
    model = build(size)
    loss_fn = lm.make_lm_loss(model, None, model.comm, seq_len=size["seq_len"])
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(seeded, tokens)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, tokens, size, lambda a: a)))(seeded)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    got, ref = leaves(grads), leaves(want_g)
    assert set(got) == set(ref) and len(ref) == 86
    assert "params/head/kernel" not in ref  # the head is the embedding
    for name, g in got.items():
        scale = float(jnp.linalg.norm(ref[name]))
        assert scale > 0, name  # no leaf is inert
        assert float(jnp.linalg.norm(g - ref[name])) <= 3e-4 * scale, name
    # what the seed decides beside the normals
    st = seeded["params"]["stack"]
    np.testing.assert_allclose(
        st["layers_0"]["ssm"]["A_log"][0, 5], np.log(np.arange(1, 5)), rtol=1e-6)
    assert (np.asarray(st["layers_2"]["ssm"]["D"]) == 1).all()
    step = np.asarray(jax.nn.softplus(st["layers_0"]["ssm"]["dt_bias"]))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    assert 0.05 < float(jnp.std(st["layers_1"]["lambda_q1"])) < 0.2


def test_reference_follows_adamw_like_the_trainer(size, seeded, tokens,
                                                  reference):
    """Three steps through ``LMTrainer.step``, every decayed leaf."""
    model, T = build(size), size["seq_len"]
    opt = optax.adamw(lambda c: 3e-4 * jnp.minimum(1.0, (c + 1) / 2000),
                      b1=0.9, b2=0.95, weight_decay=0.1)
    trainer = lm.lm_setup(model, opt, lm.lm_mesh(1), model.comm, seq_len=T,
                          params=jax.tree.map(jnp.array, seeded), donate=False)
    assert trainer.startup["layers_by_kind"] == {
        "conv": 0, "attention": 3, "dense_ffn": 6, "expert_ffn": 0, "ssm": 2,
        "gmu": 1, "window": 1, "cross": 1}
    assert trainer.startup["attention"] == "dense"
    assert trainer.startup["attention_mask"] == "window+causal"
    losses = [float(trainer.step(np.asarray(tokens)).loss) for _ in range(3)]
    got = reference.follow(jax.device_get(seeded), [np.asarray(tokens)] * 3, size)
    np.testing.assert_allclose(losses, got["loss"], rtol=3e-5)
    delta = leaves(jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b)), trainer.params, seeded))
    for name, d in delta.items():
        # the key bias' gradient is zero but for rounding (softmax does not
        # see a constant added to every key's logit), and AdamW makes a
        # full-size update of whatever noise each side has
        tol = 0.5 if name.endswith("qkv_proj/bias") else 2e-3
        np.testing.assert_allclose(d, got["delta_norm"][name], rtol=tol,
                                   err_msg=name)


def test_program_against_reference_under_the_tiny_limits():
    """The cell's own comparison at its tiny preset (bf16 compute), as the
    harness makes it: loss, first gradient (fetched leaf by leaf to the
    host), three-step update."""
    from benchmark import run as harness

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    _, cfg, traffic = harness.find_cell(bench, CELL)
    cell = harness.build_cell(cfg, traffic, 2**31 + 39, jax.devices()[:1], True)
    with cell.context():
        got, _, _ = harness.first_steps(cell, harness.CompileWatch())
    assert all(isinstance(g, np.ndarray) for g in jax.tree.leaves(got["grad"]))
    cell.release()
    rows = harness.compare(got, cell.reference(harness.CHECK_STEPS),
                           harness.cell_limits(CELL, True))
    assert all(ok for _, _, _, ok in rows), rows
    assert {n for n, _, lim, _ in rows if lim is not None} \
        == {"loss_gap", "delta_norm_gap", "grad_diff_gap"}


# --- the selective scan --------------------------------------------------------

def scan_inputs(T=37, C=8, N=4, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(T, C), jax.nn.softplus(f(T, C)), -jnp.exp(f(C, N)), f(T, N),
            f(T, N), f(C), f(C, N))


def scan_by_hand(u, delta, A, B, Cm, D, s0):
    s, ys = s0, []
    for t in range(u.shape[0]):
        s = jnp.exp(delta[t][:, None] * A) * s \
            + delta[t][:, None] * B[t][None] * u[t][:, None]
        ys.append((s * Cm[t][None]).sum(1) + D * u[t])
    return jnp.stack(ys), s


@pytest.mark.parametrize("chunk", [1, 5, 8, 37, 64],
                         ids=lambda c: f"chunk{c}")
def test_chunked_scan_is_the_loop_by_hand(chunk):
    """Forward and every cotangent (the start state's and through the last
    state too), for chunks that do and do not divide T = 37."""
    args = scan_inputs()
    w, w2 = scan_inputs(seed=1)[0], scan_inputs(seed=1)[6]

    def loss(f):
        def of(*a):
            y, last = f(*a)
            return (y * w).sum() + (last * w2).sum()
        return of

    every = tuple(range(7))
    want = jax.value_and_grad(loss(scan_by_hand), every)(*args)
    got = jax.value_and_grad(loss(lambda *a: ss.selective_scan(
        *a, chunk=chunk)), every)(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)


def test_scan_takes_low_precision_streams_and_keeps_float32_inside():
    u, delta, A, B, Cm, D, _ = scan_inputs(T=64)
    y32, _ = ss.selective_scan(u, delta, A, B, Cm, D, chunk=16)
    ub = u.astype(jnp.bfloat16)
    y, last = ss.selective_scan(ub, delta, A, B, Cm, D, chunk=16)
    assert y.dtype == jnp.float32 and last.dtype == jnp.float32
    want, _ = scan_by_hand(ub.astype(jnp.float32), delta, A, B, Cm, D,
                           jnp.zeros_like(A))
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(y - y32).max()) > 0  # the stream was rounded, once
    g = jax.grad(lambda u_: ss.selective_scan(
        u_, delta, A, B, Cm, D, chunk=16)[0].sum())(ub)
    assert g.dtype == jnp.bfloat16


def sharded(fn, mesh, comm, n_in):
    from dgraph_tpu.comm.collectives import shard_map_checks

    return jax.shard_map(
        fn, mesh=mesh, in_specs=(P(),) + (P(comm.graph_axis),) * n_in,
        out_specs=(P(comm.graph_axis), P()),
        **shard_map_checks(relax="test: the halo's ppermute and the scan's "
                                 "gathered states"))


def test_scan_and_conv_sharded_over_four_ranks_equal_one():
    """The state-space mixer (in_proj, the four-tap convolution with its halo,
    the scan with its state crossing the ranks, gate, out_proj) over a
    sequence sharded on 4 virtual devices against one device: the result,
    what it keeps, and every gradient."""
    spec = StateSpace(inner=32, state=4, conv=4, dt_rank=2, chunk=8)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    one = looplm.SelectiveSSM(spec, None, jnp.float32)
    params = one.init(jax.random.key(1), x)
    comm4, mesh = lm.lm_comm(4), lm.lm_mesh(4, jax.devices()[:4])
    four = looplm.SelectiveSSM(spec, comm4, jnp.float32)

    def body(params, x, w):
        out, kept = four.apply(params, x)
        return jnp.concatenate([out, kept], -1), jax.lax.psum(
            (out * w).sum() + kept.sum(), comm4.graph_axis)

    f4 = sharded(body, mesh, comm4, 2)
    with jax.set_mesh(mesh):
        both, _ = jax.jit(f4)(params, x, w)
        g4 = jax.jit(jax.grad(lambda p, x: f4(p, x, w)[1], (0, 1)))(params, x)
    out1, kept1 = one.apply(params, x)
    np.testing.assert_allclose(both[:, :16], out1, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(both[:, 16:], kept1, rtol=1e-4, atol=1e-5)
    g1 = jax.grad(lambda p, x: (one.apply(p, x)[0] * w).sum()
                  + one.apply(p, x)[1].sum(), (0, 1))(params, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g4)[0],
                            jax.tree.leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5, err_msg=str(path))
    assert sorted(params["params"]) == [
        "A_log", "D", "conv", "conv_bias", "dt_bias", "dt_proj", "in_proj",
        "out_proj", "x_proj"]


# --- the window mask, the unequal head sizes ------------------------------------------

def test_window_mask_at_its_edges():
    m = seq.WindowMask(16, 4)
    ids = np.arange(16)
    dense = np.asarray(m.allowed(ids[:, None], ids[None, :]))
    assert dense[9, 9] and dense[9, 6] and not dense[9, 5] and not dense[9, 10]
    assert dense[0].sum() == 1 and dense[2].sum() == 3 and dense[3].sum() == 4
    assert (dense.sum(1)[3:] == 4).all()  # itself and the 3 before it
    assert m.pairs() == dense.sum() == 10 + 12 * 4
    # tiles of 4: the diagonal and the one below it
    assert m.tile_pairs(4) == (4 + 3) * 16
    assert m.tile_pairs(16) == 256 and seq.WindowMask(16, 1).tile_pairs(4) == 64
    assert seq.WindowMask(16, 5).tile_pairs(4) == (4 + 3) * 16
    assert seq.WindowMask(16, 6).tile_pairs(4) == (4 + 3 + 2) * 16
    c = seq.CausalMask(16)
    assert c.pairs() == 136 and c.tile_pairs(4) == 10 * 16
    assert m.over(64) == seq.WindowMask(64, 4)
    assert seq.WindowMask(0, 512).over(512) == seq.WindowMask(512, 64)
    with pytest.raises(ValueError, match="a window of 0"):
        seq.WindowMask(16, 0)
    # a window as long as the sequence is causal attention
    q, k, v = (jnp.asarray(np.random.default_rng(i).standard_normal(s), jnp.float32)
               for i, s in enumerate([(16, 4, 8), (16, 2, 8), (16, 2, 12)]))
    np.testing.assert_allclose(
        seq.dense_attention(q, k, v, mask=seq.WindowMask(16, 16)),
        seq.dense_attention(q, k, v, causal=True), rtol=1e-6)
    assert seq.dense_attention(q, k, v, mask=m).shape == (16, 4, 12)
    comm4 = lm.lm_comm(4)
    with pytest.raises(NotImplementedError, match="R11"):
        comm4.seq_attention(q, k, v, mask=m)
    with pytest.raises(NotImplementedError, match="R10"):
        comm4.seq_attention(q, k, v, causal=True)


@pytest.mark.parametrize("kind", ["window", "causal"])
def test_splash_at_qk_64_v_128_matches_the_oracle_in_interpret_mode(kind):
    """The kernel path itself at a q.k head of 64 beside a v head of 128, two
    query heads a key head (Mosaic interpreter on the CPU): forward and the
    three gradients; passing latches that mask, grouping and pair of sizes,
    and no other."""
    mask = seq.WindowMask(0, 512) if kind == "window" else seq.CausalMask(0)
    assert seq._splash_selfcheck(mask, 2, interpret=True, head_dim=64,
                                 v_head_dim=128)
    assert (kind, 2, (64, 128)) in seq._splash_verified
    assert (kind, 2, 64) not in seq._splash_verified
    assert not seq.flash_attention_selfcheck(mask, 2, 64, 128)  # off-TPU


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["window", "causal"])
def test_the_one_kernel_backward_at_qk_64_v_128(splash_backward, kind, dtype):
    """ISSUE 50 under both of this stack's masks (2 query heads a key head,
    q.k heads of 64 on value heads of 128): dq, dk, dv of the one backward
    kernel against the dense oracle's and the library's two kernels'; a
    window of two tiles, so that a query tile sees a whole tile, cut tiles on
    both edges of the band and skipped ones on both sides
    (``conftest.py::splash_backward``)."""
    mask = seq.WindowMask(512, 256) if kind == "window" \
        else seq.CausalMask(512)
    splash_backward(mask, 2, 64, 128, jnp.dtype(dtype))


def test_unequal_heads_engage_only_after_their_own_selfcheck(monkeypatch):
    from dgraph_tpu import config as cfg

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(cfg, "use_flash_attention", True)
    monkeypatch.setattr(seq, "_splash_verified", {("causal", 2, 64)})
    q = jnp.zeros((256, 8, 64))
    win = seq.WindowMask(256, 32)
    ok = lambda mask=None, **kw: seq._flash_applicable(
        q, require_pinned=True, group=2, mask=mask, causal=mask is None, **kw)
    assert ok() is True and ok(v_head_dim=64) is True
    assert ok(v_head_dim=128) is False and ok(mask=win, v_head_dim=128) is False
    seq._splash_verified.add(("window", 2, (64, 128)))
    assert ok(mask=win, v_head_dim=128) is True and ok(v_head_dim=128) is False
    assert ok(mask=win) is False


# --- differential attention, the layers that read what was kept -------------------------

def test_differential_attention_by_hand_for_one_pair(size, seeded):
    """Layer 17's mixer (``diff_keep``: full causal) for query pair 0, by hand
    from the layer's leaves, lambda_init at the published index."""
    run = jax.tree.map(lambda a: a[0], seeded["params"]["stack"]["layers_3"])
    d, H, Hkv, D = 64, 4, 2, 16
    T = 24
    h = jnp.asarray(np.random.default_rng(2).standard_normal((T, d)), jnp.float32)
    layer = looplm.LoopLMLayer(
        hidden=d, num_heads=H, head_dim=D, intermediate=128,
        comm=lm.lm_comm(1), num_kv_heads=Hkv, rms_eps=1e-5, dtype=jnp.float32,
        sandwich_norm=False, mixer="diff_keep", norm="layer", attn_bias=True,
        fused_mlp=True, depth=17)

    def mixer_only(params, h):
        """The layer with its MLP's second product zeroed: h + Mix(LN(h))."""
        p = dict(params, down_proj={
            "kernel": jnp.zeros_like(params["down_proj"]["kernel"])})
        out, (stats, keep) = layer.apply({"params": p}, h, None)
        assert stats is None
        return out, keep

    out, (k_kept, v_kept) = mixer_only(run, h)
    # by hand
    x = h - h.mean(-1, keepdims=True)
    x = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) \
        * run["norm_attn_in"]["scale"] + run["norm_attn_in"]["bias"]
    qkv = x @ run["qkv_proj"]["kernel"] + run["qkv_proj"]["bias"]
    q, k, v = qkv[:, :64], qkv[:, 64:96], qkv[:, 96:]
    q1, q2 = q[:, 0:16], q[:, 16:32]  # pair 0 = heads 0 and 1
    k1, k2 = k[:, 0:16], k[:, 16:32]  # kv pair 0
    V = v[:, 0:32]  # [v1 ; v2]
    causal = np.tril(np.ones((T, T), bool))
    soft = lambda a, b: jax.nn.softmax(
        jnp.where(causal, a @ b.T / 4.0, -jnp.inf), -1)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * 17)
    lam = jnp.exp(run["lambda_q1"] @ run["lambda_k1"]) \
        - jnp.exp(run["lambda_q2"] @ run["lambda_k2"]) + lam0
    o = soft(q1, k1) @ V - lam * (soft(q2, k2) @ V)
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + 1e-5) \
        * run["subln"]["scale"] * (1 - lam0)
    # the whole layer's result, with pair 0's slice of W_o's input replaced by
    # zeros, differs from it by exactly pair 0's part
    w_o = run["o_proj"]["kernel"]
    part = o @ w_o[:32]

    def without_pair0(params, h):
        p = dict(params, o_proj=dict(params["o_proj"],
                                     kernel=w_o.at[:32].set(0.0)))
        return mixer_only(p, h)[0]

    np.testing.assert_allclose(out - without_pair0(run, h), part,
                               rtol=2e-4, atol=2e-6)
    assert k_kept.shape == (T, 2, 16) and v_kept.shape == (T, 1, 32)
    np.testing.assert_allclose(k_kept.reshape(T, 32), k, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(v_kept.reshape(T, 32), v, rtol=1e-5, atol=1e-6)
    assert abs(lam0 - 0.79634) < 1e-4


def test_later_layers_read_what_was_kept_and_hand_its_gradient_back(
        size, seeded, tokens):
    """Layers 18-19 read layer 16's m and layer 17's k, v: the gradient of
    layer 16's W_x changes when layer 18's W_2 does, layer 17's W_qkv's (its
    k, v columns) when layer 19's W_o does; the layers before the keepers are
    reached through the residual stream alone."""
    model = build(size)
    loss_fn = jax.jit(jax.grad(lm.make_lm_loss(
        model, None, model.comm, seq_len=size["seq_len"])))
    base = loss_fn(seeded, tokens)["params"]["stack"]

    def without(run, *path):
        p = jax.tree.map(lambda a: a, seeded)
        node = p["params"]["stack"][run]
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = jnp.zeros_like(node[path[-1]])
        return loss_fn(p, tokens)["params"]["stack"]

    cut = without("layers_4", "gmu", "out_proj", "kernel")  # layer 18's W_2
    a = base["layers_2"]["ssm"]["x_proj"]["kernel"]
    b = cut["layers_2"]["ssm"]["x_proj"]["kernel"]
    assert float(jnp.linalg.norm(a - b)) > 1e-3 * float(jnp.linalg.norm(a))
    # with the memory unit's output cut, its input projection gets nothing
    assert float(jnp.abs(cut["layers_4"]["gmu"]["in_proj"]["kernel"]).max()) == 0
    cut = without("layers_5", "o_proj", "kernel")  # layer 19's W_o
    kv = slice(64, 128)  # the k and v columns of layer 17's W_qkv
    a = base["layers_3"]["qkv_proj"]["kernel"][..., kv]
    b = cut["layers_3"]["qkv_proj"]["kernel"][..., kv]
    assert float(jnp.linalg.norm(a - b)) > 1e-3 * float(jnp.linalg.norm(a))
    assert float(jnp.abs(cut["layers_5"]["q_proj"]["kernel"]).max()) == 0


def test_a_stack_says_what_it_cannot_run(size):
    comm = lm.lm_comm(1)
    small = dict(vocab=32, hidden_size=16, num_heads=4, head_dim=4,
                 intermediate=16, comm=comm, num_kv_heads=2, sandwich_norm=False,
                 norm="layer", rope_theta=None, tie_head=True, window=4,
                 ssm=StateSpace(inner=32, state=2, conv=4, dt_rank=1))
    toks, pos = jnp.zeros(8, jnp.int32), jnp.arange(8)

    def init(pattern, **kw):
        return LoopLM(num_layers=len(pattern), pattern=pattern,
                      **{**small, **kw}).init(jax.random.key(0), toks, pos)

    with pytest.raises(ValueError, match="none did"):
        init(("ssm+dense", "gmu+dense"))
    with pytest.raises(ValueError, match="none did"):
        init(("diff_win+dense", "cross+dense"))
    with pytest.raises(ValueError, match="a run of its own"):
        init(("diff_win+dense", "diff_win+dense"))
    with pytest.raises(ValueError, match="need `ssm`"):
        init(("ssm+dense",), ssm=None)
    with pytest.raises(ValueError, match="need `window`"):
        init(("diff_win+dense",), window=0)
    with pytest.raises(ValueError, match="layer kind 'mamba2\\+dense'"):
        looplm.split_kind("mamba2+dense")
    # two plain state-space layers ARE one scanned run; no rotary table is made
    tree = leaves(init(("ssm+dense", "ssm+dense", "ssm_keep+dense", "gmu+dense")))
    assert tree["params/stack/layers_0/ssm/A_log"].shape == (2, 32, 2)
    assert tree["params/stack/layers_2/gmu/in_proj/kernel"].shape == (1, 16, 32)
    model = build(size)
    assert model.layer_kinds() == tuple(PATTERN)
    masks = model.attention_masks(128)
    assert masks == [seq.WindowMask(128, 8), seq.CausalMask(128),
                     seq.CausalMask(128)]
    assert lm.layers_by_kind(["conv+dense", "attn+experts"]) == {
        "conv": 1, "attention": 1, "dense_ffn": 1, "expert_ffn": 1}


# --- the chip's share of the vocabulary ----------------------------------------------------

def test_the_vocabulary_slices_side_by_side_are_the_uncut_head(
        size, seeded, tokens, reference):
    """The embedding and head divided by rows over 8 chips: the eight slices'
    logits side by side equal the uncut tied head's, and the loss over a slice
    is the uncut cross-entropy restricted to that slice's rows."""
    model = build(size)
    E = seeded["params"]["embed"]["embedding"]  # the uncut one: 512 rows
    V, share = E.shape[0], E.shape[0] // 8
    h = jnp.asarray(np.random.default_rng(5).standard_normal(
        (size["seq_len"], size["hidden_size"])), jnp.float32)
    whole = model.apply(seeded, h, method="logits")
    np.testing.assert_allclose(whole, reference.logits(seeded, h), rtol=1e-5,
                               atol=1e-6)
    parts = []
    for r in range(8):
        cut = dict(size, vocab_size=share)
        p = {"params": dict(seeded["params"], embed={
            "embedding": E[r * share:(r + 1) * share]})}
        parts.append(build(cut).apply(p, h, method="logits"))
    np.testing.assert_allclose(jnp.concatenate(parts, -1), whole, rtol=1e-6,
                               atol=1e-6)
    # a slice's loss: ids from the slice, logits and softmax over the slice
    ids = tokens % share
    cut = dict(size, vocab_size=share)
    p0 = {"params": dict(seeded["params"], embed={"embedding": E[:share]})}
    got = lm.make_lm_loss(build(cut), None, lm.lm_comm(1),
                          seq_len=size["seq_len"])(p0, ids)
    hs = build(cut).apply(p0, ids, jnp.arange(ids.shape[0]), method="hidden")[0]
    full = model.apply(seeded, hs, method="logits")  # all 512 columns
    restricted = jax.nn.log_softmax(full[:, :share], -1)  # the uncut head's
    want = -jnp.take_along_axis(restricted[:-1], ids[1:, None], -1).mean()
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert V == 512 and share == 64


def test_configuration_holds_every_published_number():
    cfg = config()
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-5,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 32, "vocab_size": 200064}
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (6, 200064 // 8)
    size = cfg["sizes"]
    for k in ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "intermediate_size", "layer_norm_eps", "sliding_window",
              "mb_per_layer", "vocab_size", "num_hidden_layers",
              "tie_word_embeddings", "mlp_bias", "lm_head_bias"):
        assert size[k] == cfg[k], k
    assert size["head_dim"] == 2560 // 40 == 64
    assert (size["d_inner"], size["d_state"], size["d_conv"], size["expand"],
            size["dt_rank"]) == (5120, 16, 4, 2, math.ceil(2560 / 16))
    # published layers 14-19, by the released code's rule
    def kind(l):
        if l < 16 or l == 16:
            if l % 2 == 0:
                return "ssm_keep" if l == 16 else "ssm"
            return "diff_win"
        if l == 17:
            return "diff_keep"
        return "gmu" if l % 2 == 0 else "cross"

    assert size["first_layer"] == 14
    assert size["layer_pattern"] == [kind(l) + "+dense" for l in range(14, 20)] \
        == PATTERN
    assert "8 chips" in cfg["deployment"] and "11.15 GB" in cfg["why_layers"]
    assert len(cfg["assumed"]) >= 12
    tiny = cfg["tiny"]
    assert tiny["layer_pattern"] == size["layer_pattern"]
    assert set(tiny) - {"seq_len"} == set(size)
    assert (tiny["hidden_size"], tiny["num_attention_heads"],
            tiny["num_key_value_heads"], tiny["head_dim"], tiny["d_state"],
            tiny["sliding_window"], tiny["seq_len"], tiny["vocab_size"]) \
        == (64, 4, 2, 16, 4, 8, 128, 512)
    # the real size's parameter count, from shapes alone
    model = build(dict(size, compute_dtype="bfloat16"))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros(128, jnp.int32), jnp.arange(128)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == 697_094_272 and round(n * 16 / 1e9, 2) == 11.15


def test_work_counts_by_hand():
    from benchmark import opsbytes

    info = {"seq_len": 8192, "heads": 40, "head_dim": 64, "hidden": 2560,
            "window": 512, "ssm_inner": 5120, "ssm_state": 16,
            "ssm_dt_rank": 160, "layers_ssm": 2, "layers_gmu": 1,
            "layers_window": 1, "layers_full": 2, "compute_bytes": 2}
    assert opsbytes.work("phi4_ssm_scan_bytes", info, 0) \
        == 8192 * 5120 * (3 * 2 + 5 * 4) * 2
    ssm = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert opsbytes.work("phi4_ssm_proj_flops", info, 0) \
        == 3 * 2 * 8192 * (2 * ssm + 2 * 2560 * 5120)
    pairs = 2 * (8192 * 8193 // 2) + (512 * 513 // 2 + (8192 - 512) * 512)
    assert opsbytes.work("phi4_attn_flops", info, 0) \
        == 3 * pairs * 40 * (2 * 64 + 2 * 128)
    assert pairs == 2 * seq.CausalMask(8192).pairs() \
        + seq.WindowMask(8192, 512).pairs()


def test_setup_counts_the_new_kinds(size, monkeypatch):
    from dgraph_tpu.obs import metrics

    reg = metrics.Metrics()
    monkeypatch.setattr(metrics, "default_registry", reg)
    monkeypatch.setattr(lm, "default_registry", reg)
    model = build(size)
    lm.lm_setup(model, optax.sgd(0.1), lm.lm_mesh(1), model.comm,
                seq_len=size["seq_len"])
    c = reg.snapshot()["counters"]
    assert (c["lm.layers.ssm"], c["lm.layers.gmu"], c["lm.layers.window"],
            c["lm.layers.cross"], c["lm.layers.attention"]) == (2, 1, 1, 1, 3)
    assert (c["lm.ssm.state"], c["lm.ssm.inner"], c["lm.ssm.chunk"]) \
        == (4, 128, 32)
    assert c["lm.attention.window"] == 8 and c["lm.attention.v_head_dim"] == 32
    assert c["lm.attention.head_dim"] == 16 and c["lm.attention.dense"] == 1
    T = size["seq_len"]
    assert c["attn.mask_pairs"] == seq.WindowMask(T, 8).pairs() \
        + 2 * seq.CausalMask(T).pairs()
    assert c["attn.tile_pairs"] == 3 * T * T  # the dense oracle: one tile
