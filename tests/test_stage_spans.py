"""The stages, spans and counters the program records where its work
happens (docs/observability.md has the table of names): set-up stages of
``DistributedGraph.from_global``, placement and init; ``fit()``'s
``train.step`` with its children; the plan's grid and halo counters against
a hand count; the compile counters. CPU, tiny sizes."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dgraph_tpu.obs import spans
from dgraph_tpu.obs.metrics import default_registry


@pytest.fixture(autouse=True)
def _tracing_off():
    spans.disable()
    yield
    spans.disable()


def stage_delta(fn):
    """(fn's result, {stage: (count, seconds) it added to the table})."""
    before = spans.stage_totals()
    out = fn()
    delta = {}
    for name, row in spans.stage_totals().items():
        was = before.get(name, {"count": 0, "total_s": 0.0})
        if row["count"] != was["count"]:
            delta[name] = (row["count"] - was["count"],
                           row["total_s"] - was["total_s"])
    return out, delta


def counter_delta(fn, prefix):
    """(fn's result, what it added to the registry's counters under prefix)."""
    before = default_registry.snapshot()["counters"]
    out = fn()
    after = default_registry.snapshot()["counters"]
    return out, {k: v - before.get(k, 0.0) for k, v in after.items()
                 if k.startswith(prefix) and v != before.get(k, 0.0)}


def small_graph(world_size=2, nodes=600, edges=3000, seed=0):
    from dgraph_tpu.data import DistributedGraph
    from dgraph_tpu.data.synthetic import random_edges

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((nodes, 8), dtype=np.float32)
    y = rng.integers(0, 4, nodes)
    split = rng.random(nodes)
    return DistributedGraph.from_global(
        random_edges(nodes, edges, seed=seed), x, y,
        {"train": split < 0.6, "val": split >= 0.6},
        world_size=world_size, add_symmetric_norm=True,
        plan_cache_dir="", tune="off")


def test_from_global_leaves_its_three_stages_inside_the_call():
    t0 = time.perf_counter()
    g, delta = stage_delta(small_graph)
    whole = time.perf_counter() - t0
    assert {"setup.partition", "setup.plan", "setup.shard"} <= set(delta)
    for name in ("setup.partition", "setup.plan", "setup.shard"):
        count, secs = delta[name]
        assert count == 1 and secs > 0, name
    parts = sum(delta[n][1] for n in
                ("setup.partition", "setup.plan", "setup.shard"))
    assert parts <= whole  # the stages lie inside the call, one after another
    assert g.plan.world_size == 2


def test_stage_spans_carry_the_sizes_of_the_work():
    recs = []
    spans.enable(sink=recs.append)
    small_graph()
    spans.disable()
    by_name = {r["name"]: r for r in recs}
    for name in ("setup.partition", "setup.plan", "setup.shard"):
        assert by_name[name]["attrs"]["world_size"] == 2, name
        assert by_name[name]["attrs"]["num_edges"] > 0, name
    assert by_name["setup.partition"]["attrs"]["num_nodes"] == 600
    assert by_name["setup.partition"]["attrs"]["method"] == "rcm"


def test_graphcast_plans_count_as_plan_stages():
    from dgraph_tpu.models.graphcast import build_graphcast_graphs

    _, delta = stage_delta(lambda: build_graphcast_graphs(1, 6, 12, 1))
    assert delta["setup.plan"][0] == 3  # mesh, grid->mesh, mesh->grid


def test_graphcast_build_is_covered_by_its_stages():
    """Mesh generation, partition, the three plans and the static features
    are the whole call: what an outside timer around it read, by part."""
    from dgraph_tpu.models.graphcast import build_graphcast_graphs

    recs = []
    spans.enable(sink=recs.append)
    t0 = time.perf_counter()
    _, delta = stage_delta(lambda: build_graphcast_graphs(2, 19, 36, 2))
    whole = time.perf_counter() - t0
    spans.disable()
    assert {n: c for n, (c, _) in delta.items()} == {
        "setup.graph_gen": 1, "setup.partition": 1, "setup.plan": 3,
        "setup.shard": 1}
    parts = sum(secs for _, secs in delta.values())
    assert 0.8 * whole <= parts <= whole
    gen = next(r for r in recs if r["name"] == "setup.graph_gen")
    assert gen["attrs"]["num_nodes"] > 0 and gen["attrs"]["num_edges"] > 0


def fit_small(epochs, **kw):
    from dgraph_tpu.comm import Communicator, make_graph_mesh
    from dgraph_tpu.models import GCN
    from dgraph_tpu.train.loop import fit

    g = small_graph()
    mesh = make_graph_mesh(ranks_per_graph=2, devices=jax.devices()[:2])
    comm = Communicator.init_process_group("tpu", world_size=2)
    model = GCN(8, 4, comm=comm, num_layers=2)
    return fit(model, g, mesh, num_epochs=epochs, **kw)


def test_fit_places_and_inits_under_stages_and_steps_under_train_step():
    recs = []
    spans.enable(sink=recs.append)
    (_, history), delta = stage_delta(lambda: fit_small(3, log_every=2))
    spans.disable()
    # plan + two batches placed; one init of each kind
    assert delta["setup.place"][0] == 3
    assert delta["setup.init_params"][0] == 1
    assert delta["setup.init_opt_state"][0] == 1
    place = [r for r in recs if r["name"] == "setup.place"]
    assert all(r["attrs"]["bytes"] > 0 and r["attrs"]["leaves"] > 0
               for r in place)
    init = next(r for r in recs if r["name"] == "setup.init_params")
    assert init["attrs"]["leaves"] > 0 and init["attrs"]["bytes"] > 0

    steps = [r for r in recs if r["name"] == "train.step"]
    assert [r["attrs"]["epoch"] for r in steps] == [0, 1, 2]
    assert not [r for r in recs if r["name"] == "train.epoch"]
    by_parent = {}
    for r in recs:
        by_parent.setdefault(r["parent"], []).append(r)
    for step in steps:
        kids = by_parent[step["span"]]
        assert [k["name"] for k in kids] == ["step_dispatch", "block"]
        end = step["ts_unix"] + step["dur_ms"] / 1e3
        for k in kids:  # covered: the step ends after its last child
            assert k["ts_unix"] >= step["ts_unix"] - 1e-4
            assert k["ts_unix"] + k["dur_ms"] / 1e3 <= end + 1e-3
        assert step["dur_ms"] >= sum(k["dur_ms"] for k in kids) - 0.01
    # records arrive as spans close: the step's after the block that
    # fetches the loss, so the step ends with the loss on the host
    names = [r["name"] for r in recs
             if r["name"] in ("train.step", "step_dispatch", "block")
             and r["parent"] in {None} | {s["span"] for s in steps}]
    assert names[:3] == ["step_dispatch", "block", "train.step"]
    assert all(isinstance(h["loss"], float) for h in history)
    evals = [r for r in recs if r["name"] == "train.eval"]
    assert [r["attrs"]["epoch"] for r in evals] == [0, 2]
    assert [k["name"] for k in by_parent[evals[0]["span"]]] == [
        "step_dispatch", "block"]
    assert "val_loss" in history[0] and "val_loss" not in history[1]


def test_a_poisoned_epoch_places_its_batch_outside_the_place_stage():
    """setup.place is once a launch: chaos poisoning an epoch's features
    puts them on the mesh without passing through the stage again."""
    from dgraph_tpu import chaos

    chaos.arm("grads=poison@1:count=2")
    try:
        (_, history), delta = stage_delta(
            lambda: fit_small(3, nonfinite_guard=True))
    finally:
        chaos.disarm()
    assert delta["setup.place"][0] == 3
    assert len(history) == 3


def test_fit_block_span_holds_the_loss_fetch():
    """No span of fit() closes before the value it names is on the host:
    the float() of the loss sits inside the ``block`` span, lexically."""
    import ast
    import inspect
    import textwrap

    from dgraph_tpu.train import loop

    tree = ast.parse(textwrap.dedent(inspect.getsource(loop.fit)))
    fetched_in = []
    for node in ast.walk(tree):
        if isinstance(node, ast.With):
            call = node.items[0].context_expr
            if (isinstance(call, ast.Call) and call.args
                    and isinstance(call.args[0], ast.Constant)):
                inside = ast.dump(node)
                if "float" in inside and "'loss'" in inside:
                    fetched_in.append(call.args[0].value)
    assert "block" in fetched_in and "train.step" in fetched_in
    assert "step_dispatch" not in fetched_in


def test_fit_reports_a_recompile_after_its_second_epoch(monkeypatch):
    """train.recompile is written once when compile.count rises after the
    second epoch, and not when it stays."""
    from dgraph_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    recs = []
    spans.enable(sink=recs.append)
    fit_small(4)
    assert not [r for r in recs if r["name"] == "train.recompile"]

    from dgraph_tpu import chaos

    def fire(point, index=None, **_):
        if point == "grads" and index == 3:  # stands in for a retrace
            jax.jit(lambda a: a * 3 + 1)(jnp.arange(7.0))
        return False

    monkeypatch.setattr(chaos, "fire", fire)
    recs.clear()
    fit_small(4)
    spans.disable()
    (rec,) = [r for r in recs if r["name"] == "train.recompile"]
    assert rec["attrs"]["compiles"] >= 1 and rec["attrs"]["after_epoch"] == 2


def two_rank_edges(per_vertex_rank1: int):
    """Two ranks of 512 vertices, every edge inside its rank: rank 0 has 8
    edges a vertex (4096), rank 1 ``per_vertex_rank1`` a vertex, so rank 1's
    row of the padded [2, 4096] layout ends in padded edges."""
    n, cols = 512, []
    i = np.arange(n)
    for rank, per_vertex in ((0, 8), (1, per_vertex_rank1)):
        for j in range(per_vertex):
            cols.append(np.stack([rank * n + i, rank * n + (i + j) % n]))
    return np.concatenate(cols, axis=1), np.repeat(np.arange(2), n)


# hand counts at the plan's tiles (1024-edge chunks, 256-vertex blocks,
# 512 + 2 x 8 = 528 halo-side rows = 3 blocks a rank). Rank 0, every route:
# each block of 256 vertices holds 2048 edges = 2 chunks. Rank 1 with k
# edges a vertex has 4096 - 512 k padded edges, indexed n_pad on the owner
# side and, on the halo-sorted route, keyed 768: the first id past the
# three blocks (528 itself lies in the third), so they sort last and into
# no block:
#   halo_sort: only real edges count. k=4: 1024 edges a block, block 0 is
#     chunk 0, block 1 chunk 1, block 2 (the halo rows) empty: [1, 1, 0].
#     k=2: 512 edges a block, both in chunk 0: [1, 1, 0]. Width 2 from
#     rank 0's [2, 2, 0] whatever k is: 6 rows x 2 = 12 steps for 4 + 2.
#     (Until format v11 the padding was keyed 0 and sorted into block 0:
#     [3, 1, 0] and [4, 1, 0], widths 3 and 4, 18 and 24 steps.)
#   scatter: the padded edges fall past the last block: k=4 [1, 1], k=2
#     [1, 1] (both blocks in chunk 0), width 2 from rank 0: 8 steps, 4 + 2.
#   gather_mv (4 chunks a rank x the widest span): k=2 puts all 512
#     vertices into chunk 0, which then spans 2 blocks: 16 steps, 4 + 5.
HAND_COUNTS = {
    8: {"halo_sort": (12, 8), "scatter": (8, 8), "gather_mv": (8, 8)},
    4: {"halo_sort": (12, 6), "scatter": (8, 6), "gather_mv": (8, 8)},
    2: {"halo_sort": (12, 6), "scatter": (8, 6), "gather_mv": (16, 9)},
}


@pytest.mark.parametrize("per_vertex", sorted(HAND_COUNTS))
def test_grid_counters_equal_a_hand_count(per_vertex):
    from dgraph_tpu.plan import build_edge_plan

    edges, part = two_rank_edges(per_vertex)
    (plan, _), got = counter_delta(
        lambda: build_edge_plan(edges, part, world_size=2), "plan.segsum")
    assert (plan.e_pad, plan.n_src_pad, plan.halo.s_pad) == (4096, 512, 8)
    want = HAND_COUNTS[per_vertex]
    for route, (steps, used) in want.items():
        assert got[f"plan.segsum_grid_steps.{route}"] == steps, route
        assert got[f"plan.segsum_used_chunks.{route}"] == used, route
    assert got["plan.segsum_grid_steps"] == sum(s for s, _ in want.values())
    assert got["plan.segsum_used_chunks"] == sum(u for _, u in want.values())
    # the counters are the hints' own arrays: the widths are the plan's
    assert plan.halo_sort_mc * 6 == want["halo_sort"][0]
    assert plan.scatter_mc * 4 == want["scatter"][0]
    assert plan.gather_mv * 8 == want["gather_mv"][0]


def test_padding_does_not_set_the_halo_sorted_routes_width():
    """The padded edges sort past the last block of the halo-sorted route,
    so its width is the widest block of real edges (rank 0's, the same for
    every k) and its fill falls only by the work the lighter rank lacks."""
    widths = {k: HAND_COUNTS[k]["halo_sort"][0] // 6 for k in HAND_COUNTS}
    assert widths == {8: 2, 4: 2, 2: 2}
    # the owner side always padded past its last block: same steps there
    assert {HAND_COUNTS[k]["scatter"][0] for k in HAND_COUNTS} == {8}

    def fill(route, c):
        steps, used = HAND_COUNTS[c][route]
        return used / steps

    assert fill("halo_sort", 8) == 8 / 12
    assert fill("halo_sort", 4) == fill("halo_sort", 2) == 6 / 12


def test_sharded_build_counts_the_same_grid():
    from dgraph_tpu.plan import build_edge_plan, build_plan_shards

    edges, part = two_rank_edges(2)
    _, mono = counter_delta(
        lambda: build_edge_plan(edges, part, world_size=2), "plan.segsum")

    def sharded(tmp):
        return build_plan_shards(edges, part, out_dir=tmp, world_size=2)

    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        (_, delta), got = counter_delta(
            lambda: stage_delta(lambda: sharded(tmp)), "plan.segsum")
    assert got == mono
    assert delta["setup.plan"][0] == 1


def test_a_repaired_shard_is_counted_at_the_plans_width():
    """A sharded build that rebuilds one rank counts that rank's rows at
    the plan's widths, which the shards it kept may have set."""
    import tempfile

    from dgraph_tpu.plan import build_plan_shards

    edges, part = two_rank_edges(2)
    with tempfile.TemporaryDirectory() as tmp:
        build_plan_shards(edges, part, out_dir=tmp, world_size=2)
        _, got = counter_delta(lambda: build_plan_shards(
            edges, part, out_dir=tmp, world_size=2, rebuild_ranks=(0,)),
            "plan.segsum")
    # rank 0 alone: 3 halo-side blocks, 2 owner blocks, 4 chunks, each at
    # the plan's widths (2, 2, 2: rank 1's chunk 0 spans both owner blocks,
    # which sets gather_mv; its padding sets nothing); its own counts are
    # 2 a block
    assert got["plan.segsum_grid_steps.halo_sort"] == 3 * 2
    assert got["plan.segsum_used_chunks.halo_sort"] == 4
    assert got["plan.segsum_grid_steps.scatter"] == 2 * 2
    assert got["plan.segsum_grid_steps.gather_mv"] == 4 * 2


def test_halo_wire_counters_follow_the_resolved_lowering():
    """plan.halo_real_rows / plan.halo_wire_rows are the quantities behind
    plan_efficiency's halo_wire_fill_* for the lowering that will run."""
    from dgraph_tpu.plan import halo_wire_rows, plan_efficiency

    g, got = counter_delta(lambda: small_graph(world_size=4), "plan.halo")
    eff = plan_efficiency(g.plan, g.layout)
    real = int(g.layout.halo_counts.sum())
    assert got["plan.halo_real_rows"] == real > 0
    assert got["plan.halo_wire_rows"] == halo_wire_rows(g.plan, eff["halo_impl"])
    fill = got["plan.halo_real_rows"] / got["plan.halo_wire_rows"]
    assert fill == pytest.approx(eff[f"halo_wire_fill_{eff['halo_impl']}"])
    W, S = 4, g.plan.halo.s_pad
    assert halo_wire_rows(g.plan, "all_to_all") == W * (W - 1) * S
    assert halo_wire_rows(g.plan, "ppermute") == len(g.plan.halo_deltas) * W * S
    assert halo_wire_rows(g.plan, "none") == 0


def test_no_counter_became_a_static_field_of_the_plan():
    """The plan's static hints make every unseen seed compile again; the
    counters are host numbers and add none."""
    import dataclasses

    from dgraph_tpu.plan import EdgePlan

    names = {f.name for f in dataclasses.fields(EdgePlan)}
    assert not {n for n in names
                if "grid" in n or "used" in n or "wire_rows" in n or "fill" in n}


def test_compile_counters_read_one_compile_then_none():
    from dgraph_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    enable_compile_cache()  # idempotent: the listeners are installed once
    x = jnp.arange(11.0)
    jax.block_until_ready(x)

    @jax.jit
    def f(a):
        return a * 2 + 1

    recs = []
    spans.enable(sink=recs.append)
    _, first = counter_delta(lambda: jax.block_until_ready(f(x)), "compile.")
    _, second = counter_delta(lambda: jax.block_until_ready(f(x)), "compile.")
    spans.disable()
    assert first["compile.count"] == 1
    assert first["compile.trace_s"] > 0 and first["compile.lower_s"] > 0
    assert first["compile.backend_s"] > 0
    assert second == {}
    from dgraph_tpu.utils.compile_cache import compile_totals

    totals = compile_totals()  # what fit() and the experiments' logs read
    assert totals["compile.count"] >= 1 and totals["compile.backend_s"] > 0
    assert all(k.startswith("compile.") for k in totals)
    backend = [r for r in recs if r["name"] == "compile.backend"]
    assert len(backend) == 1 and backend[0]["attrs"]["fun_name"] == "jit(f)"
    assert {r["name"] for r in recs} == {
        "compile.trace", "compile.lower", "compile.backend"}
