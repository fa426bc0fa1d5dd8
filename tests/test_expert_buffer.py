"""The expert layer's row buffer as a run-time choice among a few static
sizes (``parallel/expert.py::buffer_ladder``, ``_laddered``): whatever rung a
layer takes, its output, every gradient and its counts are the one buffer's at
the same bound; the ladder's own derivative holds nothing of a rung not taken;
a ladder of one rung is the one buffer's program."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dgraph_tpu.parallel import expert as ex

# T tokens choose K of N experts; experts FIRST, FIRST + 1 are held. With the
# row tile cut to 8 the ladder is (24, 48, 128): 1.5 x and 3 x the expected
# 16 rows, then the worst case T * min(K, held) (T * K = 256 routes are
# sorted whatever the rung, so no shape below is 128 rows by accident).
T, K, N, HELD, FIRST, D, F = 64, 4, 32, 2, 4, 16, 24
LADDER = (24, 48, 128)
FORMS = ("gated_silu", "relu2", "gated_relu")


@pytest.fixture
def small_tile(monkeypatch):
    monkeypatch.setattr(ex, "GROUPED_TILE_ROWS", 8)
    assert ex.buffer_ladder(T, K, HELD, N) == LADDER


def chosen(rows_here: int) -> jnp.ndarray:
    """``[T, K]`` distinct expert ids a token of which exactly ``rows_here``
    are held ones: two a token, then one, then none."""
    out = np.zeros((T, K), np.int32)
    away = [e for e in range(N) if not FIRST <= e < FIRST + HELD]
    for t in range(T):
        held = min(HELD, max(rows_here - HELD * t, 0))
        rest = [away[(3 * t + j) % len(away)] for j in range(K - held)]
        out[t] = np.random.default_rng(t).permutation(
            [FIRST + j for j in range(held)] + rest)
    assert (np.sort(out, 1)[:, 1:] != np.sort(out, 1)[:, :-1]).all()
    assert ((out >= FIRST) & (out < FIRST + HELD)).sum() == rows_here
    return jnp.asarray(out)


def inputs(form):
    r = np.random.default_rng(0)
    x = jnp.asarray(r.standard_normal((T, D)), jnp.float32)
    gates = jnp.asarray(r.random((T, K)) + 0.1, jnp.float32)
    wu, wg = (jnp.asarray(r.standard_normal((HELD, D, F)) * 0.3, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(r.standard_normal((HELD, F, D)) * 0.3, jnp.float32)
    cot = jnp.asarray(r.standard_normal((T, D)), jnp.float32)
    return x, gates, (None if form == "relu2" else wg, wu, wd), cot


def layer(form, experts, n_total, rows=None):
    """(out, stats, gradients to x, gates and the kernels) of one layer."""
    x, gates, kernels, cot = inputs(form)

    def f(x, gates, kernels):
        out, stats = ex.held_experts_ffn(
            x, gates, experts, *kernels, form=form, n_total=n_total,
            first_held=FIRST, rows=rows)
        return (out * cot).sum(), (out, stats)

    (_, (out, stats)), grads = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2), has_aux=True))(x, gates, kernels)
    return out, np.asarray(stats), grads


def same(got, want, rtol=1e-4):
    """Counts to the unit; floats at the tolerance ``test_sdar.py`` sets the
    cut buffer against the reference at (a rung lays the same rows out in the
    same order, but XLA's CPU products block a 24-row and a 128-row buffer
    differently: the last bits of a float32 sum move)."""
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype.kind in "iub":
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=rtol, atol=1e-5)


# --- the ladder -----------------------------------------------------------------

@pytest.mark.parametrize("shape,want", [
    # tokens, k, held, total, the caller's bound
    ((8192, 6, 8, 128, None), (4608, 9216, 49152)),  # nemotron3_nano_30b_a3b
    ((16384, 4, 8, 32, None), (24576, 49152, 65536)),  # lfm2_8b_a1b
    ((16384, 8, 16, 128, 65536), (24576, 49152, 65536)),  # sdar_30b_a3b
    ((16384, 8, 16, 128, 40000), (24576, 40000)),  # a bound under a rung
    ((16384, 8, 16, 128, 20000), (20000,)),  # ... and under the first
    ((16384, 8, 128, 128, None), (131072,)),  # every expert held
    ((16384, 4, 8, None, None), (65536,)),  # the total not told
    ((128, 2, 1, 8, None), (128,)),  # moe_apply at a test's size
    ((8192, 2, 1, 8, None), (3072, 6144, 8192)),  # ... and at a real one
    ((8192, 1, 1, 2, None), (6144, 8192)),  # 3 x is past the worst case
    ((8192, 1, 1, 1, None), (8192,)),  # ... and 1.5 x
], ids=["nemotron", "lfm2", "sdar", "bound_mid", "bound_low", "all_held",
        "untold", "moe_small", "moe", "moe_two", "moe_one"])
def test_ladder_is_a_function_of_the_shapes(shape, want):
    tokens, k, held, total, rows = shape
    got = ex.buffer_ladder(tokens, k, held, total, rows)
    assert got == want and got[-1] == ex.buffer_rows(tokens, k, held, rows)
    assert all(r % ex.GROUPED_TILE_ROWS == 0 for r in got[:-1])


def test_one_factor_is_two_rungs(monkeypatch):
    """What PR 43 also measured (the switch takes any number of rungs): the
    first rung alone."""
    monkeypatch.setattr(ex, "LADDER_FACTORS", (1.5,))
    assert ex.buffer_ladder(8192, 6, 8, 128) == (4608, 49152)
    assert ex.buffer_ladder(16384, 4, 8, 32) == (24576, 65536)
    layer("relu2", chosen(10), N)  # traces: a two-way switch


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("rows_here,rows,rung", [
    (10, None, 24), (100, None, 128), (70, 64, 64)])
def test_a_model_without_the_ladder_runs_the_one_buffer(
        small_tile, form, rows_here, rows, rung):
    """``HeldExperts.ladder`` False (smallthinker_21b_a3b's cell: its layers'
    rows lie astride any rung, by seed and by step): the experts' module
    does not tell the layer the total, so the layer traces the one buffer's
    program, no conditional, whatever was routed here; the laddered module
    beside it takes a rung and computes the same."""
    from dgraph_tpu.models import looplm
    from dgraph_tpu.train import lm

    x, gates, kernels, cot = inputs(form)
    experts = chosen(rows_here)
    names = ("up_proj", "down_proj") if form == "relu2" \
        else ("gate_proj", "up_proj", "down_proj")

    def f(x, gates, kernels, ladder):
        mod = looplm.HeldExpertsFFN(looplm.HeldExperts(
            N, HELD, K, F, first_held=FIRST, rows=rows, form=form,
            ladder=ladder), lm.lm_comm(1), jnp.float32)
        out, stats = mod.apply({"params": {
            n: {"kernel": w} for n, w in zip(names, kernels[-len(names):])}},
            x, (gates, experts))
        return (out * cot).sum(), (out, stats)

    def run(ladder):
        g = jax.value_and_grad(functools.partial(f, ladder=ladder),
                               argnums=(0, 1, 2), has_aux=True)
        (_, (out, stats)), grads = jax.jit(g)(x, gates, kernels)
        conds = [n for n, _, _ in _walk(jax.make_jaxpr(g)(
            x, gates, kernels).jaxpr, False, []) if n == "cond"]
        return out, np.asarray(stats), grads, conds

    out, stats, grads, conds = run(False)
    assert not conds and stats[5] == (rows or LADDER[-1])
    want_out, want_stats, want_grads, want_conds = run(True)
    assert want_conds and want_stats[5] == rung
    same(stats[:5], want_stats[:5])
    same(out, want_out)
    same(grads, want_grads)


# --- every rung is the one buffer ---------------------------------------------

@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("rows_here,rows,rung", [
    (0, None, 24), (10, None, 24), (24, None, 24),  # ... to the edge
    (25, None, 48), (48, None, 48),
    (49, None, 128), (100, None, 128), (128, None, 128),
    (64, 64, 64), (70, 64, 64),  # a caller's bound: six rows dropped
    (30, 40, 40),  # a bound under the second rung: ladder (24, 40)
])
def test_rung_taken_is_the_one_buffer(small_tile, form, rows_here, rows, rung):
    experts = chosen(rows_here)
    out, stats, grads = layer(form, experts, N, rows)
    _rung_is_the_one_buffer(experts, out, stats, grads, form, rows_here, rows,
                            rung)


@pytest.mark.parametrize("rows_here,rows,rung", [
    (10, None, 24), (48, None, 48), (100, None, 128), (70, 64, 64)])
def test_rung_taken_under_handed_routes_is_the_one_buffer(
        small_tile, rows_here, rows, rung):
    """The router's other place (``HeldExperts.router_reads =
    "layer_input"``): the layer hands the experts' module the routes it took
    before the mixer, from another tensor than the one the experts multiply,
    and every rung is still the one buffer."""
    from dgraph_tpu.models import looplm
    from dgraph_tpu.train import lm

    x, gates, kernels, cot = inputs("gated_relu")
    experts = chosen(rows_here)
    mod = looplm.HeldExpertsFFN(looplm.HeldExperts(
        N, HELD, K, F, first_held=FIRST, rows=rows, form="gated_relu",
        router_reads="layer_input"), lm.lm_comm(1), jnp.float32)
    names = ("gate_proj", "up_proj", "down_proj")

    def f(x, gates, kernels):
        out, stats = mod.apply({"params": {
            n: {"kernel": w} for n, w in zip(names, kernels)}},
            x, (gates, experts))  # no router leaf: the routes are handed in
        return (out * cot).sum(), (out, stats)

    (_, (out, stats)), grads = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2), has_aux=True))(x, gates, kernels)
    _rung_is_the_one_buffer(experts, out, np.asarray(stats), grads,
                            "gated_relu", rows_here, rows, rung)


def _rung_is_the_one_buffer(experts, out, stats, grads, form, rows_here, rows,
                            rung):
    want_out, want_stats, want_grads = layer(form, experts, None, rows)
    assert stats[5] == rung and want_stats[5] == (rows or LADDER[-1])
    same(stats[:5], want_stats[:5])
    last = rows or LADDER[-1]
    assert stats[0] == rows_here and stats[2] == max(rows_here - last, 0)
    assert stats[4] == min(rows_here, last)
    same(out, want_out)
    same(grads, want_grads)
    if rows_here:
        assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grads))


def test_dropped_rows_change_the_result_and_are_counted(small_tile):
    experts = chosen(70)
    cut, stats, _ = layer("gated_silu", experts, N, 64)
    full, stats_full, _ = layer("gated_silu", experts, N)
    assert stats[2] == 6 and stats_full[2] == 0
    assert not np.allclose(cut, full)


# --- under the layers' scan and remat -------------------------------------------

class _Layer(nn.Module):
    form: str
    n_total: object

    @nn.compact
    def __call__(self, h, routed):
        gates, experts = routed
        def kernel(name, *shape):
            return self.param(name, nn.initializers.normal(0.3), shape)

        kernels = (None if self.form == "relu2" else kernel("gate", HELD, D, F),
                   kernel("up", HELD, D, F), kernel("down", HELD, F, D))
        out, stats = ex.held_experts_ffn(
            h, gates, experts, *kernels, form=self.form,
            n_total=self.n_total, first_held=FIRST)
        return h + out, stats


@pytest.mark.parametrize("form", FORMS)
def test_scanned_rematerialised_layers_take_their_own_rungs(small_tile, form):
    """Three stacked layers under ``nn.scan`` of ``nn.remat``, routed 10, 30
    and 100 rows: each takes its own rung, and the loss and every gradient
    are those of the stack with one buffer a layer."""
    rows_here = (10, 30, 100)
    x, gates, _, cot = inputs(form)
    routed = (jnp.stack([gates * (i + 1) / 3 for i in range(3)]),
              jnp.stack([chosen(n) for n in rows_here]))

    def stack(n_total):
        return nn.scan(
            nn.remat(_Layer, prevent_cse=False), variable_axes={"params": 0},
            split_rngs={"params": True}, in_axes=0, length=3)(form, n_total)

    params = stack(None).init(jax.random.key(0), x, routed)

    def run(n_total):
        def loss(params, x, gates):
            h, stats = stack(n_total).apply(params, x, (gates, routed[1]))
            return (h * cot).sum(), stats

        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(params, x, routed[0])

    (loss, stats), grads = run(N)
    (want_loss, want_stats), want_grads = run(None)
    stats = np.asarray(stats)
    assert tuple(stats[:, 5]) == (24, 48, 128)
    assert tuple(stats[:, 0]) == rows_here
    same(stats[:, :5], np.asarray(want_stats)[:, :5])
    same(loss, want_loss)
    same(grads, want_grads, rtol=5e-4)  # three layers deep in float32


# --- over a mesh axis, the ranks on different rungs -----------------------------

@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("rows,rungs,dropped", [
    (None, (96, 48, 48, 48), 0),  # rank 0 is routed 60 rows, rank 2 its edge
    (56, (56, 48, 48, 48), 4),  # ... and past a caller's bound
], ids=["free", "bound"])
def test_ranks_of_an_axis_take_different_rungs(small_tile, form, rows, rungs,
                                               dropped):
    """Four ranks, two experts each of 8, sixteen tokens a rank: every rank
    gathers all 64 tokens, rank 0 is routed 60 rows, rank 1 20, rank 2 48 and
    rank 3 none. The collectives lie outside the switch, so the program ends,
    and the result is the one-buffer layer's."""
    from jax.sharding import Mesh, PartitionSpec as P

    total = 8
    assert ex.buffer_ladder(T, 2, HELD, total, rows)[:-1] == (48, 96)[
        :2 if rows is None else 1]
    experts = jnp.asarray(np.concatenate([
        np.tile([0, 1], (30, 1)), np.tile([2, 3], (10, 1)),
        np.tile([4, 5], (24, 1))]), jnp.int32)
    x, gates, kernels, cot = inputs(form)
    gates = gates[:, :2]
    per_rank = [None if w is None else jnp.tile(w, (4, 1, 1)) * jnp.repeat(
        jnp.arange(1.0, 5.0), HELD)[:, None, None] for w in kernels]
    mesh = Mesh(np.array(jax.devices()[:4]), ("graph",))

    def run(n_total):
        def body(x, gates, experts, cot, *kernels):
            if form == "relu2":
                kernels = (None, *kernels)
            out, stats = ex.held_experts_ffn(
                x, gates, experts, *kernels, form=form, n_total=n_total,
                rows=rows, axis_name="graph")
            return jax.lax.psum((out * cot).sum(), "graph"), stats[None]

        def loss(x, gates, kernels):
            got, stats = jax.shard_map(
                body, mesh=mesh, in_specs=P("graph"),
                out_specs=(P(), P("graph")), check_vma=False)(
                    x, gates, experts, cot, *kernels)
            return got, stats

        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(
                x, gates, [w for w in per_rank if w is not None])

    (loss, stats), grads = run(total)
    (want_loss, want_stats), want_grads = run(None)
    stats = np.asarray(stats)
    assert tuple(stats[:, 0]) == (60, 20, 48, 0)
    assert tuple(stats[:, 5]) == rungs and stats[:, 2].sum() == dropped
    same(stats[:, :5], np.asarray(want_stats)[:, :5])
    same(loss, want_loss)
    same(grads, want_grads)


def test_moe_apply_takes_a_rung_on_every_rank(small_tile):
    """One expert a rank of four, k = 1: 64 tokens, 16 expected a rank, so
    the ladder is (24, 48, 64); a router that sends 40 of them to rank 0
    puts the ranks on different rungs, and the mixture is the dense one."""
    assert ex.buffer_ladder(T, 1, 1, 4) == (24, 48, 64)
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("graph",))
    x, _, _, _ = inputs("relu2")
    logits = jnp.asarray(np.random.default_rng(1).standard_normal((T, 4)),
                         jnp.float32).at[:40, 0].add(20.0)
    w = jnp.asarray(np.random.default_rng(2).standard_normal((4, D, D)) * 0.3,
                    jnp.float32)

    def mixture(w):
        return jax.shard_map(
            lambda x, lg, w: ex.moe_apply(
                x, lg, lambda p, z: jnp.tanh(z @ p), w[0], "graph"),
            mesh=mesh, in_specs=P("graph"), out_specs=P("graph"),
            check_vma=False)(x, logits, w)

    assert "cond" in str(jax.make_jaxpr(mixture)(w))
    got, grad = jax.jit(jax.value_and_grad(lambda w: (mixture(w) ** 2).sum()))(w)
    probs = jax.nn.softmax(logits, -1)
    top = probs.argmax(-1)

    def dense(w):
        y = jnp.tanh(jnp.einsum("td,tde->te", x, w[top]))
        return ((y * probs.max(-1)[:, None]) ** 2).sum()

    want, want_grad = jax.value_and_grad(dense)(w)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-4, atol=1e-5)


# --- what the programs hold -----------------------------------------------------

def _walk(jaxpr, in_last, found):
    """Every equation's result shapes, with whether it lies inside the LAST
    branch of a conditional."""
    for eqn in jaxpr.eqns:
        found.append((eqn.primitive.name, in_last,
                      [getattr(v.aval, "shape", ()) for v in eqn.outvars]))
        if eqn.primitive.name == "cond":
            branches = eqn.params["branches"]
            for i, b in enumerate(branches):
                _walk(b.jaxpr, in_last or i == len(branches) - 1, found)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _walk(sub, in_last, found)
    return found


@pytest.mark.parametrize("form", FORMS)
def test_one_rung_traces_no_conditional(form):
    """All experts held, the total not told, or a small batch: one rung, the
    one buffer's program, equation for equation."""
    x, gates, kernels, _ = inputs(form)
    experts = chosen(40)

    def program(n_total, held=HELD):
        ks = [None if w is None else jnp.tile(w, (held // HELD, 1, 1))
              for w in kernels]
        return jax.make_jaxpr(jax.grad(lambda x: ex.held_experts_ffn(
            x, gates, experts, *ks, form=form, n_total=n_total,
            first_held=FIRST)[0].sum()))(x)

    # the real tile: 1.5 x 16 rows round up to 512, past the 128-row buffer
    assert ex.buffer_ladder(T, K, HELD, N) == (128,)
    untold, small, all_held = program(None), program(N), program(N, N)
    assert str(untold) == str(small)
    for jaxpr in (untold, small, all_held):
        assert not any(name == "cond" for name, _, _ in _walk(
            jaxpr.jaxpr, False, []))


@pytest.mark.parametrize("form", FORMS)
def test_backward_holds_no_worst_case_buffer_outside_the_last_branch(
        small_tile, form):
    """JAX's own derivative of a conditional returns every branch's
    residuals, the branches not taken filled with zeros at the WORST-CASE
    shape. The ladder's derivative differentiates the rung taken under its
    own switch: no ``[128, ...]`` value outside a last branch, forward and
    backward one conditional each, three branches."""
    x, gates, kernels, cot = inputs(form)
    experts = chosen(40)

    def loss(x, gates, kernels):
        out, _ = ex.held_experts_ffn(
            x, gates, experts, *kernels, form=form, n_total=N,
            first_held=FIRST)
        return (out * cot).sum()

    jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2)))(
        x, gates, kernels)
    found = _walk(jaxpr.jaxpr, False, [])
    conds = [f for f in found if f[0] == "cond"]
    # the forward, its rematerialised copy (dead code: its result is not
    # read, XLA drops it) and the backward
    assert 2 <= len(conds) <= 3
    wide = [(name, shapes) for name, in_last, shapes in found if not in_last
            for s in shapes if s and s[0] == LADDER[-1]]
    assert not wide, wide
    rows = {s[0] for _, in_last, shapes in found if not in_last
            for s in shapes if len(s) == 2 and s[1] in (D, F)}
    assert {24, 48} <= rows  # the lower rungs' buffers are there
    inside = {s[0] for _, in_last, shapes in found if in_last
              for s in shapes if len(s) == 2 and s[1] in (D, F)}
    assert LADDER[-1] in inside


# --- the counter and its metric ------------------------------------------------

def test_trainer_counts_the_rungs_taken_beside_the_rows_offered():
    """``moe.rows_buffered`` (the rungs the layers took, summed) over
    ``moe.buffer_rows_offered`` (the last rung x layers x steps) is what
    ``moe_buffer_used_pct.fed`` reads; at this model's size the ladder has
    one rung, so it reads 100."""
    import json
    import os

    from test_sdar import L, build
    from benchmark.builders.sdar import noised_batch
    from benchmark.reducers import program_counter_ratio
    from dgraph_tpu.obs.metrics import default_registry
    from dgraph_tpu.train import lm

    assert ex.HELD_STATS[5] == "rows_buffered"
    assert 5 not in ex.HELD_STATS_MAX
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "layer_metrics",
                           "moe_buffer_used_pct.fed.json")) as f:
        spec = json.load(f)
    assert spec["params"] == {"numerator": "moe.rows_buffered",
                              "denominator": "moe.buffer_rows_offered"}
    said = []
    run = type("Run", (), {"say": staticmethod(said.append)})
    default_registry.reset()
    assert program_counter_ratio.reduce(run, spec["params"]) is None

    comm, mesh = lm.lm_comm(1), lm.lm_mesh(1)
    tr = lm.lm_setup(build(comm), optax.adamw(1e-3), mesh, comm, seq_len=L,
                     seed=0, donate=False)
    batch = noised_batch(np.random.default_rng(3), L, 96, 1.0, 4, 1e-3)
    with jax.set_mesh(mesh):
        for _ in range(2):
            sm = tr.step(batch)
    assert np.asarray(sm.moe_rows).shape == (len(ex.HELD_STATS),)
    c = default_registry.snapshot()["counters"]
    one = tr.startup["moe_buffer_rows"]
    assert one == 2 * L * 4 and tr.startup["moe_buffer_rows_a_step"] == 2 * one
    assert c["moe.buffer_rows_offered"] == 2 * 2 * one  # layers x steps
    assert c["moe.rows_buffered"] == c["moe.buffer_rows_offered"]
    assert int(sm.moe_rows[5]) == 2 * one
    assert program_counter_ratio.reduce(run, spec["params"]) == 100.0
    assert "moe.rows_buffered=" in said[-1]
    default_registry.reset()


def test_trainer_reads_under_100_where_a_lower_rung_holds_the_rows(small_tile):
    """The same model with the row tile cut so that its 512-row buffer has
    rungs of 192 and 384 under it: the layers take them and the share falls
    under 100; nothing is dropped."""
    from test_sdar import L, build
    from benchmark.builders.sdar import noised_batch
    from dgraph_tpu.obs.metrics import default_registry
    from dgraph_tpu.train import lm

    assert ex.buffer_ladder(2 * L, 4, 4, 16) == (192, 384, 512)
    default_registry.reset()
    comm, mesh = lm.lm_comm(1), lm.lm_mesh(1)
    tr = lm.lm_setup(build(comm), optax.adamw(1e-3), mesh, comm, seq_len=L,
                     seed=0, donate=False)
    batch = noised_batch(np.random.default_rng(3), L, 96, 1.0, 4, 1e-3)
    with jax.set_mesh(mesh):
        sm = tr.step(batch)
    rows = np.asarray(sm.moe_rows)
    c = default_registry.snapshot()["counters"]
    assert rows[2] == 0 and rows[0] <= rows[5] < c["moe.buffer_rows_offered"]
    assert rows[5] in (2 * 192, 192 + 384, 2 * 384)
    assert c["moe.rows_buffered"] == rows[5]
    default_registry.reset()
