"""The sparse-expert block-diffusion LM (``models/looplm.py`` with held
experts, ``parallel/expert.py::held_experts_ffn``, the block-diffusion mask of
``parallel/sequence.py``, the second objective of ``train/lm.py``) on the CPU
at tiny sizes, seeded weights: against the benchmark's plain reference
(``benchmark/reference/sdar.py``), the shares of an expert layer against the
uncut layer, the mask against a hand-written matrix, the kernel path's mask
and head grouping in interpret mode."""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from dgraph_tpu.models.looplm import HeldExperts, LoopLM
from dgraph_tpu.parallel import expert as ex
from dgraph_tpu.parallel import sequence as seq
from dgraph_tpu.train import lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
L, V, B = 64, 97, 4
SIZE = {  # the reference's keys (the configuration's names)
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "moe_intermediate_size": 16, "num_experts_total": 16,
    "num_experts": 4, "first_expert": 0, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "vocab_size": V, "mask_token_id": V - 1,
    "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
    "block_length": B, "learning_rate": 3e-4, "warmup_steps": 2000,
    "beta1": 0.9, "beta2": 0.95, "weight_decay": 0.1,
}


def build(comm, dtype=None, n_held=4, first=0, rows=None, attn_impl="ring",
          block_length=B):
    return LoopLM(
        vocab=V, hidden_size=32, num_layers=2, num_heads=4, head_dim=8,
        intermediate=0, comm=comm, num_kv_heads=2, loop_steps=1,
        exit_gate=False, rms_eps=1e-6, rope_theta=1e6, dtype=dtype,
        attn_impl=attn_impl, sandwich_norm=False, qk_norm=True,
        experts=HeldExperts(16, n_held, 4, 16, first_held=first, rows=rows),
        block_length=block_length, mask_token=V - 1)


@pytest.fixture(scope="module")
def batch():
    from benchmark.builders.sdar import noised_batch

    return noised_batch(np.random.default_rng(3), L, V - 1, 1.0, B, 1e-3)


@pytest.fixture(scope="module")
def seeded():
    from benchmark.builders.looplm import seeded_lm_params

    model = build(lm.lm_comm(1))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros(2 * L, jnp.int32),
        jnp.tile(jnp.arange(L), 2)))
    return seeded_lm_params(shapes, 11, None)


@pytest.fixture(scope="module")
def reference():
    from benchmark.reference import sdar

    return sdar


def program_loss(model, params, batch):
    total, stats = lm.block_diffusion_loss_sum(
        model, params, tuple(jnp.asarray(a) for a in batch), model.comm)
    return total / L, stats


# --- against the plain reference ------------------------------------------------

def test_loss_and_every_gradient_leaf_match_reference(seeded, batch, reference):
    model = build(lm.lm_comm(1))
    (loss, stats), grads = jax.value_and_grad(
        lambda p: program_loss(model, p, batch), has_aux=True)(seeded)
    with jax.default_matmul_precision("highest"):
        (want, chosen), want_g = jax.value_and_grad(
            lambda p: reference.loss_fn(
                p, tuple(jnp.asarray(a) for a in batch), SIZE, lambda a: a),
            has_aux=True)(seeded)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    ref = dict(jax.tree_util.tree_flatten_with_path(want_g)[0])
    assert len(flat) == len(ref) == 15
    for path, g in flat:
        scale = float(jnp.linalg.norm(ref[path]))
        assert scale > 0, path  # no leaf is inert: the router learns too
        assert float(jnp.linalg.norm(g - ref[path])) <= 3e-4 * scale, path
    # the program's counts are the reference's routing, counted
    held = np.asarray(chosen) < 4
    assert stats.shape == (1, 2, 6)
    assert (np.asarray(stats)[0, :, 0] == held.sum((1, 2))).all()
    assert (np.asarray(stats)[0, :, 2] == 0).all()


def test_reference_follows_adamw_like_the_trainer(seeded, batch, reference):
    model = build(lm.lm_comm(1))
    opt = optax.adamw(lambda c: 3e-4 * jnp.minimum(1.0, (c + 1) / 2000),
                      b1=0.9, b2=0.95, weight_decay=0.1)
    step = lm.make_lm_train_step(model, opt, None, model.comm, seq_len=L,
                                 donate=False)
    params, state, losses = seeded, opt.init(seeded), []
    dev = tuple(jnp.asarray(a) for a in batch)
    for _ in range(3):
        params, state, sm = step(params, state, dev)
        losses.append(float(sm.loss))
    assert sm.moe_rows.shape == (6,) and int(sm.moe_rows[2]) == 0
    got = reference.follow(jax.device_get(seeded), [batch] * 3, SIZE)
    np.testing.assert_allclose(losses, got["loss"], rtol=3e-5)
    delta = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b)), params, seeded)
    for (path, d) in jax.tree_util.tree_flatten_with_path(delta)[0]:
        name = "/".join(str(k.key) for k in path)
        np.testing.assert_allclose(d, got["delta_norm"][name], rtol=2e-3)


def test_program_against_reference_under_the_tiny_limits():
    """The cell's own comparison at its tiny preset (bf16 compute), as the
    harness makes it: loss, first gradient, three-step update."""
    from benchmark import run as harness

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    _, config, traffic = harness.find_cell(bench, "sdar_30b_a3b.bd8k")
    cell = harness.build_cell(config, traffic, 2**31 + 32, jax.devices()[:1], True)
    with cell.context():
        got, _, _ = harness.first_steps(cell, harness.CompileWatch())
    cell.release()
    rows = harness.compare(got, cell.reference(harness.CHECK_STEPS),
                           harness.cell_limits("sdar_30b_a3b.bd8k", True))
    assert all(ok for _, _, _, ok in rows), rows
    assert {n for n, _, lim, _ in rows if lim is not None} \
        == {"loss_gap", "delta_norm_gap", "grad_diff_gap"}


# --- the chip's share of the expert layer -----------------------------------------

def layer_inputs(T=96, d=32, f=16, E=16, k=4, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, E)), jnp.float32)
    W = {n: {"kernel": jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)}
         for n, s in (("gate_proj", (E, d, f)), ("up_proj", (E, d, f)),
                      ("down_proj", (E, f, d)))}
    return x, router, W, k


def test_the_shares_partial_results_add_up_to_the_uncut_layer(reference):
    """16 experts in 4 shares of 4: each share routes over all 16, renormalises
    over all 4 chosen, and adds its own experts' part; the four parts sum to
    the uncut reference layer's output, and each part is the reference's with
    the same share held."""
    x, router, W, k = layer_inputs()
    gates, experts = ex.route_topk(x @ router, k)
    with jax.default_matmul_precision("highest"):
        g_ref, e_ref = reference.route(x, router, k, True)
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
    assert here == x.shape[0] * k  # every route lands in exactly one share
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    # the gates of a share do not sum to 1: they are normalised over ALL
    # chosen experts, held here or not
    held = np.asarray(experts) < 4
    assert 0.0 < float((np.asarray(gates) * held).sum(-1).mean()) < 0.9


def test_no_row_is_dropped_when_one_id_is_most_of_the_batch():
    """60 % of the tokens are one id, so their rows go to the same 4 experts:
    the dropless buffer takes them all; a bounded one counts what it cut."""
    model = build(lm.lm_comm(1))
    rng = np.random.default_rng(5)
    tokens = np.where(rng.random(L) < 0.6, 7, rng.integers(0, V - 1, L)).astype(np.int32)
    batch = (tokens, np.zeros(L, bool), np.ones(L, np.float32))
    params = model.init(jax.random.key(1), jnp.zeros(2 * L, jnp.int32),
                        jnp.tile(jnp.arange(L), 2))
    (_, stats) = program_loss(model, params, batch)
    stats = np.asarray(stats)[0]
    assert (stats[:, 2] == 0).all()
    # the hot id's rows pile on a few experts: far from an even load
    assert stats[0, 1] >= 0.6 * 2 * L or stats[0, 0] < 0.6 * 2 * L
    assert stats[:, 1].max() > 2 * (2 * L * 4 / 16)
    x, router, W, k = layer_inputs(T=64)
    x = x.at[:40].set(x[0])  # 40 identical rows
    gates, experts = ex.route_topk(x @ router, k)
    first = int(experts[0, 0]) // 4 * 4  # the share that holds their first choice
    kernels = [W[n]["kernel"][first:first + 4] for n in ("gate_proj", "up_proj", "down_proj")]
    full, st = ex.held_experts_ffn(x, gates, experts, *kernels, first_held=first)
    assert int(st[2]) == 0 and int(st[1]) >= 40
    cut, st_cut = ex.held_experts_ffn(x, gates, experts, *kernels,
                                      first_held=first, rows=32)
    assert int(st_cut[2]) == int(st[0]) - 32 > 0  # counted, not silent
    assert not np.allclose(cut, full)


def test_gradient_reaches_the_router_through_the_gates():
    x, router, W, k = layer_inputs()

    def out(router):
        gates, experts = ex.route_topk(x @ router, k)
        y, _ = ex.held_experts_ffn(x, gates, experts, *(
            W[n]["kernel"][:4] for n in ("gate_proj", "up_proj", "down_proj")))
        return (y ** 2).sum()

    g = jax.grad(out)(router)
    assert float(jnp.abs(g).max()) > 0
    eps = 1e-3 * jnp.sign(g)  # a finite difference along the gradient's sign
    np.testing.assert_allclose(
        (out(router + eps) - out(router - eps)) / 2, (g * eps).sum(), rtol=2e-2)


def test_grouped_matmul_kernel_path_in_interpret_mode():
    """``megablox.gmm`` (the TPU path) against ``lax.ragged_dot`` (the CPU
    path), forward and both gradients, rows past the groups masked."""
    rng = np.random.default_rng(2)
    sizes = jnp.asarray([100, 0, 156, 37], jnp.int32)
    lhs = jnp.asarray(rng.standard_normal((512, 128)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((4, 128, 256)), jnp.float32)
    live = (jnp.arange(512) < 293)[:, None]
    f = lambda interpret: lambda a, b: (jnp.where(live, ex.grouped_matmul(
        a, b, sizes, interpret=interpret), 0) ** 2).sum()
    got, want = (jax.grad(f(i), (0, 1))(lhs, rhs) for i in (True, False))
    # the kernel writes no row past the groups, in either direction: the
    # layer reads none (its gathers select the routed rows)
    np.testing.assert_allclose(got[0][:293], want[0][:293], rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-4, atol=2e-3)


def test_expert_layer_over_a_mesh_axis_is_the_whole_layer(reference):
    """W = 4 ranks, 4 experts each, tokens sharded: the ranks' parts are
    summed over the axis and the result is the uncut layer's."""
    from jax.sharding import Mesh, PartitionSpec as P

    x, router, W, k = layer_inputs(T=64)
    mesh = Mesh(np.array(jax.devices()[:4]), ("graph",))

    def body(x, wg, wu, wd):
        gates, experts = ex.route_topk(x @ router, k)
        y, stats = ex.held_experts_ffn(x, gates, experts, wg, wu, wd,
                                       axis_name="graph")
        return y, stats[None]

    y, stats = jax.shard_map(
        body, mesh=mesh, in_specs=P("graph"), out_specs=P("graph"),
        check_vma=False)(x, *(W[n]["kernel"] for n in ("gate_proj", "up_proj", "down_proj")))
    with jax.default_matmul_precision("highest"):
        whole = reference.held_experts(
            x, *reference.route(x, router, k, True), W, 0, lambda a: a)
    np.testing.assert_allclose(y, whole, rtol=1e-4, atol=1e-5)
    assert int(stats[:, 0].sum()) == 64 * k and int(stats[:, 2].sum()) == 0


# --- the structured mask --------------------------------------------------------

def hand_mask(L, B):
    m = np.zeros((2 * L, 2 * L), bool)
    for q in range(2 * L):
        for k in range(2 * L):
            qb, kb = (q % L) // B, (k % L) // B
            if q < L and k < L:
                m[q, k] = qb == kb
            elif q < L:
                m[q, k] = kb < qb
            elif k >= L:
                m[q, k] = kb <= qb
    return m


@pytest.mark.parametrize("L_,B_", [(16, 4), (18, 3), (64, 4)])
def test_mask_allows_L_times_L_plus_block_pairs(L_, B_):
    mask = seq.BlockDiffusionMask(L_, B_)
    want = hand_mask(L_, B_)
    assert (mask.dense() == want).all()
    assert want.sum() == mask.pairs() == L_ * (L_ + B_)
    assert want.any(1).all()  # no row without a key
    assert not want[L_:, :L_].any()  # never from x0 to xt


def test_dense_oracle_honours_the_mask_exactly():
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((32, 2, 8)), jnp.float32)
               for _ in range(3))
    got = seq.dense_attention(q, k, v, mask=seq.BlockDiffusionMask(16, 4))
    s = np.einsum("thd,shd->hts", q, k) / np.sqrt(8)
    s = np.where(hand_mask(16, 4)[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hts,shd->thd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="block_diffusion"):
        seq.dense_attention(q, k, v, causal=True,
                            mask=seq.BlockDiffusionMask(16, 4))


def test_kernel_paths_mask_object_is_the_same_matrix():
    """What the splash kernels are handed: every tile of the computed mask
    equals the hand-written matrix, the tiles it would visit are those with
    an allowed pair, and the tile counts at the cell's size are 80 of 256."""
    mask = seq.BlockDiffusionMask(16, 4)
    heads = seq._splash_mask(mask, 3)
    want = hand_mask(16, 4)
    assert heads.shape == (3, 32, 32)
    for m in heads.masks:
        assert (np.asarray(m[:, :]) == want).all()
        assert (np.asarray(m[8:16, 16:32]) == want[8:16, 16:32]).all()
    tiles = mask.tile_map(8)
    assert (tiles == want.reshape(4, 8, 4, 8).any((1, 3))).all()
    assert mask.tile_pairs(8) == tiles.sum() * 64
    big = seq.BlockDiffusionMask(8192, 4)
    assert big.tile_map(1024).sum() == 80 and big.tile_map(1024).size == 256
    assert big.pairs() == 8192 * 8196


def test_splash_kernels_match_the_oracle_in_interpret_mode():
    """The kernel path itself (Mosaic interpreter on the CPU): forward and
    the three gradients under the mask, 2 query heads a KV head, tiles of
    128 of which some are whole, some partial, some skipped."""
    assert seq._splash_selfcheck(seq.BlockDiffusionMask(256, 4), 2,
                                 interpret=True)
    assert ("block_diffusion", 2, 128) in seq._splash_verified
    assert not seq.flash_attention_selfcheck(seq.BlockDiffusionMask(256, 4), 2)  # off-TPU


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_one_kernel_backward_under_the_block_diffusion_mask(splash_backward, dtype):
    """ISSUE 50 under this configuration's mask: dq, dk, dv of the one
    backward kernel against the dense oracle's and the library's two
    kernels', 2 query heads a KV head at heads of 128; a noised query tile's
    visits are not one run of key tiles (``conftest.py::splash_backward``)."""
    splash_backward(seq.BlockDiffusionMask(256, 4), 2, 128, 128, jnp.dtype(dtype))


@pytest.mark.parametrize("rows,head_dim,v_head_dim", [
    (16384, 192, 128), (16384, 128, 128), (16384, 64, 64), (8192, 64, 128)],
    ids=["kanana2_30b_a3b", "sdar_30b_a3b-smallthinker_21b_a3b",
         "lfm2_8b_a1b", "phi4_mini_flash"])
def test_the_one_kernel_backward_is_taken_by_shape(monkeypatch, rows,
                                                   head_dim, v_head_dim):
    """The route is a predicate on shapes (ISSUE 50): the five splash cells'
    attention shapes take the one kernel on a TPU (or interpreted), a KV
    head whose resident blocks and float32 accumulators pass the VMEM
    budget does not, nor rows that are no whole lane-tile tiles, nor
    anything off a TPU: those keep the library's two kernels."""
    from dgraph_tpu.ops import pallas_attention

    taken = lambda rows, **kw: seq._one_kernel_backward(
        rows, head_dim, v_head_dim, jnp.bfloat16, **kw)
    assert not taken(rows)  # the CPU
    assert taken(rows, interpret=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert taken(rows) and not taken(4 * rows) and not taken(rows + 64)
    held = pallas_attention.vmem_bytes(rows, head_dim, v_head_dim, 2,
                                       seq.flash_tile(rows))
    # K, V, dk, dv (double-buffered blocks) and the two float32 accumulators
    # of a head at whole lane tiles: 12 bytes a row and lane, and the rest
    wide = -(-head_dim // 128) * 128 + -(-v_head_dim // 128) * 128
    assert 12 * rows * wide < held <= pallas_attention.VMEM_BUDGET
    assert held < 12 * rows * wide + (24 << 20)


def test_grouped_query_heads_equal_repeated_keys_and_values():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((32, 8, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((32, 2, 8)), jnp.float32)
            for _ in range(2))
    rep = lambda t: jnp.repeat(t, 4, axis=1)
    mask = seq.BlockDiffusionMask(16, 4)
    comm = lm.lm_comm(1)
    for kw in ({"causal": True}, {"mask": mask}):
        np.testing.assert_allclose(
            comm.seq_attention(q, k, v, **kw),
            comm.seq_attention(q, rep(k), rep(v), **kw), rtol=1e-6)
    q128, k128, v128 = (jnp.asarray(rng.standard_normal((256, h, 128)), jnp.float32)
                        for h in (4, 2, 2))
    m128 = seq.BlockDiffusionMask(128, 4)
    np.testing.assert_allclose(  # the kernel reads KV heads where they lie
        seq._splash_dense(q128, k128, v128, mask=m128, scale=None, interpret=True),
        seq.dense_attention(q128, jnp.repeat(k128, 2, 1), jnp.repeat(v128, 2, 1),
                            mask=m128), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_ring_and_ulysses_refuse_the_mask_by_name(impl):
    from dgraph_tpu.comm import Communicator

    comm = Communicator.init_process_group("tpu", world_size=4)
    q = jnp.zeros((8, 4, 8))
    with pytest.raises(NotImplementedError, match=f"{impl} attention has no block_diffusion"):
        comm.seq_attention(q, q, q, impl=impl, mask=seq.BlockDiffusionMask(16, 4))
    with pytest.raises(NotImplementedError, match="block_diffusion"):
        lm.resolve_attention(comm, impl, 8, 4, 8, seq.BlockDiffusionMask(16, 4), 1)
    with pytest.raises(ValueError, match="causal"):
        lm.lm_comm(1).seq_attention(q, q, q, causal=True,
                                    mask=seq.BlockDiffusionMask(4, 4))


# --- the trainer ----------------------------------------------------------------

def test_trainer_takes_the_three_array_batch_and_counts(batch):
    from dgraph_tpu.obs.metrics import default_registry

    default_registry.reset()
    comm, mesh = lm.lm_comm(1), lm.lm_mesh(1)
    tr = lm.lm_setup(build(comm), optax.adamw(1e-3), mesh, comm, seq_len=L)
    assert tr.startup["attention"] == "dense"
    assert tr.startup["attention_mask"] == "block_diffusion"
    assert tr.startup["mask_pairs"] == L * (L + B)
    assert tr.startup["tile_pairs"] == 4 * L * L  # the oracle's one tile
    with jax.set_mesh(mesh):
        first = float(tr.step(batch).loss)
        for _ in range(5):
            sm = tr.step(batch)
        assert float(sm.loss) < first
        assert abs(float(tr.evaluate(batch)) - float(tr.step(batch).loss)) < 1e-5
    c = default_registry.snapshot()["counters"]
    assert c["moe.experts_held"] == 4 and c["moe.experts_total"] == 16
    assert c["moe.rows_routed"] == 7 * 2 * L * 4 * 2  # steps x rows x k x layers
    assert 0 < c["moe.rows_here"] < c["moe.rows_routed"]
    assert c["moe.rows_dropped"] == 0 and c["moe.rows_tiled"] >= c["moe.rows_here"]
    assert c["attn.mask_pairs"] == L * (L + B)
    assert default_registry.snapshot()["gauges"]["moe.rows_max_expert"] > 0


def test_expert_layers_do_not_depend_on_the_objective(batch):
    """``experts`` and ``block_length`` are independent fields: a causal
    next-token model with expert layers trains through the same trainer, its
    counts come back with the loss, and its loss is the next-token
    cross-entropy of the model's own logits."""
    from dgraph_tpu.obs.metrics import default_registry

    default_registry.reset()
    comm, mesh = lm.lm_comm(1), lm.lm_mesh(1)
    model = build(comm, n_held=16, block_length=0)
    tr = lm.lm_setup(model, optax.adamw(1e-3), mesh, comm, seq_len=L)
    assert "attention_mask" not in tr.startup
    tokens = batch[0]
    with jax.set_mesh(mesh):
        logits, _ = model.apply(tr.params, jnp.asarray(tokens), jnp.arange(L))
        want = -jnp.take_along_axis(
            jax.nn.log_softmax(logits[0, :-1]), jnp.asarray(tokens)[1:, None],
            axis=1).mean()
        np.testing.assert_allclose(tr.evaluate(tokens), want, rtol=1e-5)
        first = tr.step(tokens)
        np.testing.assert_allclose(first.loss, want, rtol=1e-5)
        for _ in range(5):
            sm = tr.step(tokens)
    assert float(sm.loss) < float(first.loss)
    # every expert is held: every route lands here, none is dropped
    assert (int(sm.moe_rows[0]), int(sm.moe_rows[2])) == (L * 4 * 2, 0)
    c = default_registry.snapshot()["counters"]
    assert c["moe.rows_here"] == c["moe.rows_routed"] == 6 * L * 4 * 2


def test_trainer_refuses_expert_layers_over_a_mesh_axis():
    comm, mesh = lm.lm_comm(4), lm.lm_mesh(4)
    with pytest.raises(NotImplementedError, match="over a mesh axis"):
        lm.lm_setup(build(comm, block_length=0), optax.adamw(1e-3), mesh,
                    comm, seq_len=L)


def test_unmasked_tokens_and_the_clean_copy_carry_no_loss(seeded, batch):
    model = build(lm.lm_comm(1))
    tokens, masked, weight = batch
    base, _ = program_loss(model, seeded, batch)
    none, _ = program_loss(model, seeded, (tokens, np.zeros(L, bool), weight))
    assert float(none) == 0.0 and float(base) > 0
    double, _ = program_loss(model, seeded, (tokens, masked, 2 * weight))
    np.testing.assert_allclose(double, 2 * base, rtol=1e-6)
    # a noised block's prediction does not see its own clean tokens: changing
    # the clean id of a masked position moves only its target
    i = int(np.flatnonzero(masked)[-1])
    later = tokens.copy()
    later[i] = (later[i] + 1) % (V - 1)
    hs = lambda t: model.apply(seeded, jnp.concatenate(
        [jnp.where(masked, V - 1, t), t]), jnp.tile(jnp.arange(L), 2),
        method="hidden")[0][0]
    a, b = hs(jnp.asarray(tokens)), hs(jnp.asarray(later))
    blk = i // B
    np.testing.assert_allclose(a[:(blk + 1) * B], b[:(blk + 1) * B], atol=1e-6)


# --- the benchmark's configuration and work counts --------------------------------

def test_configuration_holds_every_published_number():
    with open(os.path.join(ROOT, "benchmark", "configs", "sdar_30b_a3b.json")) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-6, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    assert cfg["num_experts"] == 16 and cfg["vocab_size"] == 151936 // 8
    size = cfg["sizes"]
    for k in ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "moe_intermediate_size", "num_experts_per_tok",
              "norm_topk_prob", "vocab_size", "rms_norm_eps", "rope_theta",
              "num_hidden_layers", "num_experts"):
        assert size[k] == cfg[k], k
    assert size["num_experts_total"] == 128 and size["first_expert"] == 0
    assert 4 <= size["num_hidden_layers"] <= 6 and size["num_experts"] >= 8
    assert size["mask_token_id"] == size["vocab_size"] - 1
    assert "8 expert-parallel chips" in cfg["deployment"]
    assert cfg["why_layers"] and cfg["assumed"]


def test_work_counts_by_hand(monkeypatch):
    from benchmark import opsbytes
    from dgraph_tpu.obs import metrics

    info = {"seq_len": 8192, "rows": 16384, "block_length": 4, "heads": 32,
            "head_dim": 128, "hidden": 2048, "expert_width": 768,
            "experts_per_token": 8, "layers": 4, "loop_steps": 1}
    assert opsbytes.work("sdar_attn_flops", info, 0) \
        == 3 * 4 * 8192 * 8196 * 32 * 128 * 4
    reg = metrics.Metrics()
    monkeypatch.setattr(metrics, "default_registry", reg)
    assert opsbytes.work("sdar_moe_flops", info, 0) == 0.0  # nothing counted
    reg.counter("moe.rows_routed", 3 * 16384 * 8 * 4)  # three steps
    reg.counter("moe.rows_here", 3 * 70000)
    assert opsbytes.work("sdar_moe_flops", info, 0) \
        == pytest.approx(3 * 3 * 2 * 70000 * 2048 * 768)
