"""The device work below the layer scopes is named (ISSUE 36): the children
``rows`` / ``mask`` / ``slice`` of ``dgraph.local_take``, ``send_gather`` /
``wire`` / ``scatter_add`` / ``mask`` / ``concat`` of the ``dgraph.halo_*``
scopes under every lowering, ``routes`` beside the four of ``dgraph.lm.moe``;
the gauge ``moe.rows_max_layer`` with ``moe.buffer_rows``, the counter
``gather.bwd_chunks`` and the stage ``setup.attention_selfcheck``. The
benchmark's per-layer metrics (``benchmark/layer_metrics/gather_rows_ms.*``,
``halo_*_ms.train``, ``moe_*_ms.fed``, ...) match these names in a device
trace's operation paths, so each is looked for where a trace would carry it:
the location of an operation in the lowered text."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from dgraph_tpu import config as cfg
from dgraph_tpu import plan as pl
from dgraph_tpu.comm import Communicator, collectives
from dgraph_tpu.comm.mesh import make_graph_mesh, squeeze_plan
from dgraph_tpu.data import DistributedGraph, synthetic
from dgraph_tpu.obs import spans
from dgraph_tpu.obs.metrics import default_registry
from dgraph_tpu.ops import local as local_ops
from dgraph_tpu.train import lm

from test_sdar import build as build_sdar  # the tiny two-layer expert model
from test_take_scatter_bias_relu import _plan, _shard

TAKE_CHILDREN = ("rows", "mask", "slice")


def op_paths(lowered) -> list:
    """The named-scope path of every operation of a lowered program."""
    return re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))


def children(paths, parent: str, direction: str = "") -> set:
    """The scopes opened directly under ``parent`` (itself possibly wrapped
    in ``jvp(...)`` / ``transpose(...)``), on the paths of the backward
    (``"bwd"``: under a ``transpose(``), the forward (``"fwd"``) or all."""
    found = set()
    for p in paths:
        if direction and ("transpose(" in p) != (direction == "bwd"):
            continue
        for m in re.finditer(re.escape(parent) + r"\)*/([^/]+)/", p):
            found.add(m.group(1))
    return found


@pytest.fixture
def flags():
    saved = (cfg.halo_impl, cfg.tuned_halo_impl, cfg.use_flash_attention)
    yield
    cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1],
                  use_flash_attention=saved[2])


@pytest.fixture(scope="module")
def sbm():
    return synthetic.sbm_classification_graph(num_nodes=400, seed=1)


def gcn_step_paths(sbm, world: int, impl: str) -> list:
    from dgraph_tpu.models import GCN
    from dgraph_tpu.train.loop import make_train_step

    cfg.set_flags(halo_impl=impl)
    g = DistributedGraph.from_global(
        sbm["edge_index"], sbm["features"], sbm["labels"], sbm["masks"],
        world_size=world, partition_method="random", add_symmetric_norm=True)
    comm = (Communicator.init_process_group("tpu", world_size=world)
            if world > 1 else Communicator.init_process_group("single"))
    mesh = make_graph_mesh(ranks_per_graph=world, devices=jax.devices()[:world])
    model = GCN(hidden_features=8, out_features=4, comm=comm)
    plan = jax.tree.map(jnp.asarray, g.plan)
    batch = {"x": jnp.asarray(g.features), "y": jnp.asarray(g.labels),
             "mask": jnp.asarray(g.masks["train"]),
             "edge_weight": jnp.asarray(g.edge_weight)}
    single = GCN(hidden_features=8, out_features=4,
                 comm=Communicator.init_process_group("single"))
    params = jax.eval_shape(lambda: single.init(
        jax.random.key(0), batch["x"][0], jax.tree.map(lambda a: a[0], plan),
        batch["edge_weight"][0]))
    opt = optax.sgd(0.1)
    step = make_train_step(model, opt, mesh, plan, donate=False)
    with jax.set_mesh(mesh):
        return op_paths(step.lower(
            params, jax.eval_shape(opt.init, params), batch, plan))


@pytest.mark.parametrize("world, impl, exchange", [
    (1, "all_to_all", "dgraph.halo_exchange"),
    (2, "all_to_all", "dgraph.halo_exchange"),
    (2, "ppermute", "dgraph.halo_exchange"),
    (4, "overlap", "dgraph.halo_exchange_overlap"),
])
def test_a_gcn_train_step_names_the_work_under_its_scopes(
        flags, sbm, world, impl, exchange):
    """Forward and backward, at one device and over a mesh under each
    lowering: the row gathers of the local take (and no edge-mask multiply:
    the fused layer's is gone), the exchange's send gather, masks and (over a mesh) its
    collective, ``halo_extend``'s concatenate. A child's name travels with
    its transpose: the backward's operations carry the same children."""
    paths = gcn_step_paths(sbm, world, impl)
    fwd, bwd = "fwd", "bwd"
    wire = {"wire"} if world > 1 else set()
    assert {"send_gather", "mask"} | wire <= children(paths, exchange, fwd)
    if impl == "overlap":
        # the pinned VJP is the reverse rounds: their own scatter_add child
        assert {"scatter_add", "mask", "wire"} <= children(
            paths, exchange, bwd)
    else:
        # plain AD: the transposed send gather IS the scatter-add into the
        # owner's table, under the child's name
        assert {"send_gather", "mask"} | wire <= children(
            paths, exchange, bwd)
        assert any(re.search(r"send_gather/scatter-add$", p) for p in paths
                   if "transpose(" in p)
    if impl != "overlap":  # the overlap layer takes no concatenated table
        assert "concat" in children(paths, "dgraph.halo_extend", fwd)
        # the fused layer takes its rows unmasked (ISSUE 37): its
        # aggregation drops a padded edge by its id, so no edge-mask
        # multiply follows the gather, forward or transposed
        for direction in (fwd, bwd):
            assert children(
                paths, "dgraph.local_take", direction) == {"rows"}, direction
    else:
        assert "rows" in children(paths, "dgraph.boundary_take", fwd)
    # ``wire`` holds the collective and nothing else
    under_wire = {p.rsplit("/", 1)[1] for p in paths if "/wire/" in p}
    assert under_wire <= {"all_to_all", "ppermute"}, under_wire
    assert bool(under_wire) == (world > 1)


@pytest.mark.parametrize("impl", ["all_to_all", "ppermute"])
def test_halo_scatter_sum_names_its_scatter_add(flags, impl):
    """The halo-side scatter's reverse exchange: collective under ``wire``,
    receive mask under ``mask``, the sum into the owner's table under
    ``scatter_add``."""
    world = 2
    plan_np = _plan(world, seed=3, long_tail=False)
    mesh = make_graph_mesh(ranks_per_graph=world, devices=jax.devices()[:world])
    h = jnp.ones((world, world * plan_np.halo.s_pad, 8), jnp.float32)
    halo = jax.tree.map(jnp.asarray, plan_np.halo)

    def shard(h_, halo_):
        return collectives.halo_scatter_sum(
            h_[0], jax.tree.map(lambda a: a[0], halo_), plan_np.n_src_pad,
            "graph", deltas=plan_np.halo_deltas, impl=impl)[None]

    fn = jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(P("graph"), P("graph")),
        out_specs=P("graph")))
    with jax.set_mesh(mesh):
        paths = op_paths(fn.lower(h, halo))
    assert {"wire", "mask", "scatter_add"} <= children(
        paths, "dgraph.halo_scatter_sum")


def test_graphcast_edge_block_names_its_takes():
    from dgraph_tpu.models.graphcast.model import MeshEdgeBlock

    rng = np.random.default_rng(0)
    V, E, F = 48, 300, 12
    edges = np.stack([rng.integers(0, V, E), rng.integers(0, V, E)])
    plan, _ = pl.build_edge_plan(edges, np.zeros(V, np.int32), world_size=1)
    plan = squeeze_plan(jax.tree.map(jnp.asarray, plan))
    block = MeshEdgeBlock(latent=F, comm=Communicator.init_process_group("single"))
    x = jnp.ones((plan.n_src_pad, F), jnp.float32)
    e = jnp.ones((plan.e_pad, F), jnp.float32)
    params = jax.eval_shape(block.init, jax.random.key(0), e, x, x, plan)
    paths = op_paths(jax.jit(jax.grad(
        lambda p, e_, x_: block.apply(p, e_, x_, x_, plan).sum(),
        argnums=(0, 2))).lower(params, e, x))
    for direction in ("fwd", "bwd"):
        assert {"rows", "mask"} <= children(
            paths, "dgraph.local_take", direction), direction


def _engaged_grad_paths(weighted=True):
    """The fused GCN layer's gradient where the transposed route engages
    (the program's TPU branches, lowered on the CPU)."""
    plan_np = _plan(1, seed=1)
    plan = _shard(plan_np, 0)
    F = 256
    table = jnp.ones((plan_np.n_src_pad + plan_np.halo.s_pad, F), jnp.bfloat16)
    bias = jnp.ones((plan_np.n_dst_pad, F), jnp.bfloat16)
    w = jnp.ones((plan_np.e_pad,), jnp.float32) if weighted else None

    def loss(t, b, w_):
        return collectives.take_scatter_bias_relu(
            t, b, plan, "src", "dst", None, w_).astype(jnp.float32).sum()

    return op_paths(jax.jit(jax.grad(loss, (0, 1))).lower(table, bias, w))


def counted(*names):
    c = default_registry.snapshot()["counters"]
    return [c.get(n, 0) for n in names]


ROUTES = ("gather.bwd_chunks", "gather.bwd_transposed", "gather.bwd_permuted")


def test_every_operation_of_a_local_take_is_under_one_child_or_none(
        tpu_interpret, monkeypatch):
    """With the table taken in row parts and the backward on the transposed
    route, all three children occur; no operation's path holds two of them,
    and what is under none is what stays with the parent (the ids' shape
    changes, ``take_values``' split and concatenate)."""
    before = counted(*ROUTES)
    whole = _engaged_grad_paths()
    took = np.subtract(counted(*ROUTES), before)
    assert took.tolist() == [2, 2, 0]  # two chunks, both transposed
    whole_unweighted = _engaged_grad_paths(weighted=False)
    n_rows = _plan(1, seed=1).n_src_pad + _plan(1, seed=1).halo.s_pad
    monkeypatch.setattr(
        local_ops, "GATHER_TABLE_BYTES", -(-n_rows // 2) * 128 * 2)
    parted = _engaged_grad_paths()
    for paths in (whole, parted):
        under = [p for p in paths if "dgraph.local_take" in p]
        assert under
        for p in under:
            tail = p.split("dgraph.local_take", 1)[1].split("/")[1:-1]
            assert sum(s in TAKE_CHILDREN for s in tail) <= 1, p
    assert children(whole, "dgraph.local_take") >= {"rows", "mask", "slice"}
    # the part gathers' slices and select chain are the forward's
    fwd_parted = [p for p in parted if "transpose(" not in p]
    assert children(fwd_parted, "dgraph.local_take") >= set(TAKE_CHILDREN)
    # ``mask`` holds a select chain or ``take_values``' lane select, and no
    # edge-mask multiply (ISSUE 37): a whole table and no edge weight, none
    took_children = children(whole_unweighted, "dgraph.local_take")
    assert {"rows", "slice"} <= took_children and "mask" not in took_children
    assert "mask" not in children(
        [p for p in whole if "transpose(" not in p], "dgraph.local_take")


def test_local_take_called_directly_keeps_its_mask():
    """Only the fused layer's take leaves the edge-mask pass out:
    ``local_take`` called directly, as every other model calls it, lowers
    a ``mask`` child beside ``rows`` and hands back zero rows in the
    padded slots, where the unmasked take hands back table row 0."""
    plan = _shard(_plan(1, seed=1), 0)
    table = jnp.full((plan.n_src_pad + plan.halo.s_pad, 128), 3, jnp.bfloat16)
    padded = np.asarray(plan.edge_mask) == 0
    assert padded.any()
    for take, kids, fill in (
            (collectives.local_take, {"rows", "mask"}, 0.0),
            (collectives._local_take_unmasked, {"rows"}, 3.0)):
        fn = jax.jit(lambda t, take=take: take(t, plan, "src"))
        assert children(
            op_paths(fn.lower(table)), "dgraph.local_take") == kids
        rows = np.asarray(fn(table), np.float32)
        assert (rows[padded] == fill).all() and (rows[~padded] == 3.0).all()


def test_bwd_chunks_is_the_sum_of_the_two_routes(tpu_interpret):  # noqa: F811
    before = counted(*ROUTES)
    _engaged_grad_paths(weighted=False)  # transposed: the kernels run
    cfg_saved = cfg.use_pallas_fused_bwd
    try:
        cfg.set_flags(use_pallas_fused_bwd=False)
        _engaged_grad_paths(weighted=False)  # permuted: the ops' own VJPs
    finally:
        cfg.set_flags(use_pallas_fused_bwd=cfg_saved)
    chunks, transposed, permuted = np.subtract(counted(*ROUTES), before)
    assert (transposed, permuted) == (2, 2)
    assert chunks == transposed + permuted


def test_take_values_names_its_row_gathers_and_lane_select():
    values = jnp.arange(1024, dtype=jnp.float32)
    idx = jnp.arange(64, dtype=jnp.int32)
    paths = op_paths(jax.jit(local_ops.take_values).lower(values, idx))
    assert {p.split("/")[-2] for p in paths
            if p.split("/")[-2:-1] and p.split("/")[-2] in TAKE_CHILDREN} == {
        "rows", "mask", "slice"}


# --- the expert layer ------------------------------------------------------------

def test_an_expert_layer_names_routes_beside_its_four_scopes():
    from dgraph_tpu.models.looplm import HeldExperts, HeldExpertsFFN

    layer = HeldExpertsFFN(
        HeldExperts(16, 4, 4, 16), lm.lm_comm(1), dtype=jnp.float32)
    x = jnp.ones((32, 8), jnp.float32)
    params = jax.eval_shape(layer.init, jax.random.key(0), x)
    paths = op_paths(jax.jit(jax.value_and_grad(
        lambda p, x_: layer.apply(p, x_)[0].sum(), argnums=(0, 1))).lower(
            params, x))
    fwd = children(paths, "dgraph.lm.moe", "fwd")
    assert fwd >= {"router", "routes", "dispatch", "experts", "combine"}
    # the two sorts are under ``routes`` and nowhere else
    sorts = [p for p in paths if p.endswith("jit(argsort)")]
    assert sorts and all("/routes/" in p for p in sorts)
    # the integer routes take no gradient; the other four transpose
    assert children(paths, "dgraph.lm.moe", "bwd") >= {
        "router", "dispatch", "experts", "combine"}


def test_rows_max_layer_is_the_fullest_layers_buffer(flags):
    """A two-layer model whose buffer overflows: the step's fifth count is
    the most rows ONE layer put into its buffer (the other counts are sums
    over the layers), the trainer keeps the run's maximum as the gauge
    ``moe.rows_max_layer`` beside the counter ``moe.buffer_rows``, and
    ``rows_dropped`` is what it was: the rows routed here past each layer's
    buffer."""
    from test_sdar import L
    from benchmark.builders.looplm import seeded_lm_params
    from benchmark.builders.sdar import noised_batch
    from dgraph_tpu.parallel.expert import HELD_STATS

    assert HELD_STATS[4] == "rows_max_layer"
    batch = noised_batch(np.random.default_rng(3), L, 96, 1.0, 4, 1e-3)
    comm, mesh = lm.lm_comm(1), lm.lm_mesh(1)
    shapes = jax.eval_shape(lambda: build_sdar(comm).init(
        jax.random.key(0), jnp.zeros(2 * L, jnp.int32),
        jnp.tile(jnp.arange(L), 2)))
    seeded = seeded_lm_params(shapes, 11, None)
    dev = tuple(jnp.asarray(a) for a in batch)

    def layer_stats(model):
        return np.asarray(lm.block_diffusion_loss_sum(
            model, seeded, dev, comm)[1])[0]  # [layers, 6]

    free = layer_stats(build_sdar(comm))  # the worst-case buffer
    assert (free[:, 0] == free[:, 4]).all() and (free[:, 2] == 0).all()
    rows = int(free[:, 0].max()) - 8  # the fuller layer overflows
    held = layer_stats(build_sdar(comm, rows=rows))
    assert held[:, 4].max() == rows and held[:, 2].sum() > 0
    assert (held[:, 2] == np.maximum(held[:, 0] - rows, 0)).all()
    assert (held[:, 4] == np.minimum(held[:, 0], rows)).all()

    default_registry.reset()
    tr = lm.lm_setup(build_sdar(comm, rows=rows), optax.adamw(1e-3), mesh,
                     comm, seq_len=L, params=seeded, donate=False)
    with jax.set_mesh(mesh):
        sm = tr.step(batch)
    got = np.asarray(sm.moe_rows)
    assert got.shape == (6,)
    assert got[4] == held[:, 4].max() and got[4] < held[:, 4].sum()
    assert got[2] == held[:, 2].sum() and got[0] == held[:, 0].sum()
    snap = default_registry.snapshot()
    assert snap["gauges"]["moe.rows_max_layer"] == got[4]
    assert snap["counters"]["moe.buffer_rows"] == rows
    assert snap["counters"]["moe.rows_dropped"] == held[:, 2].sum()
    # the worst case is the buffer where none is given
    default_registry.reset()
    lm.lm_setup(build_sdar(comm), optax.adamw(1e-3), mesh, comm, seq_len=L,
                params=seeded)
    assert default_registry.snapshot()["counters"]["moe.buffer_rows"] \
        == 2 * L * 4


def test_the_attention_selfcheck_is_a_stage_with_the_tracer_off(flags):
    """``lm_setup`` asks for the self-check wherever flash attention is
    wanted; the call is an always-on stage (here, off a TPU, it ends at
    once and latches nothing)."""
    assert not spans.enabled()
    before = spans.stage_totals().get(
        "setup.attention_selfcheck", {"count": 0})["count"]
    cfg.set_flags(use_flash_attention=True)  # a TPU's default, pinned
    comm, mesh = lm.lm_comm(1), lm.lm_mesh(1)
    tr = lm.lm_setup(build_sdar(comm), optax.adamw(1e-3), mesh, comm,
                     seq_len=64)
    assert tr.startup["attention"] == "dense"  # the check did not pass
    row = spans.stage_totals()["setup.attention_selfcheck"]
    assert row["count"] == before + 1 and row["total_s"] >= 0
