"""A layer's column chunks run one after the other (ISSUE 31):
``collectives.map_vertex_chunks`` ties chunk j+1's table slices behind
chunk j's ``[N, chunk]`` result with an ``optimization_barrier``, in the
forward alone, so that each gather table is live through its own gather
only (what lets the TPU compiler place every table on chip). The tie is
an identity: every model branch that takes it gives the same bits with
and without it; GraphCast's edge block (an ``[E, chunk]`` result) does
not take it. The reader of a compiled module's placement is host-only.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dgraph_tpu import config as cfg
from dgraph_tpu import plan as pl
from dgraph_tpu.comm import Communicator, collectives
from dgraph_tpu.comm.mesh import (
    GRAPH_AXIS,
    make_graph_mesh,
    plan_in_specs,
    squeeze_plan,
)
from dgraph_tpu.obs.metrics import default_registry
from dgraph_tpu.plan import shard_vertex_data

W, V, E, F = 2, 48, 300, 12
COL_BLOCK = 4  # three chunks of the 12-wide tables: two ties a layer
TIES = F // COL_BLOCK - 1


@pytest.fixture
def flags():
    saved = (cfg.halo_impl, cfg.tuned_halo_impl, cfg.gather_col_block)
    cfg.set_flags(gather_col_block=COL_BLOCK)
    yield
    cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1],
                  gather_col_block=saved[2])


def _ties_counted() -> float:
    return default_registry.snapshot()["counters"].get(
        "gather.chunks_sequenced", 0)


def _layer(branch: str, comm):
    from dgraph_tpu.models.gcn import GraphConvLayer
    from dgraph_tpu.models.sage import SAGEConv

    if branch.startswith("sage"):
        return SAGEConv(out_features=F, comm=comm)
    return GraphConvLayer(out_features=F, comm=comm)


@pytest.mark.parametrize("branch", [
    "gcn_fused", "gcn_fused_overlap", "gcn_composed", "gcn_composed_overlap",
    "sage", "sage_overlap",
])
def test_sequenced_chunks_are_the_same_program_but_for_the_ties(
        rng, flags, monkeypatch, branch):
    part = np.sort(rng.integers(0, W, V)).astype(np.int32)
    edges = np.stack([rng.integers(0, V, E), rng.integers(0, V, E)])
    plan, layout = pl.build_edge_plan(edges, part, world_size=W, overlap=True)
    if "composed" in branch:
        # the composed GCN branches are what a plan that is not
        # homogeneous takes (the fused scatter is gated on it)
        plan = dataclasses.replace(plan, homogeneous=False)
    cfg.set_flags(
        halo_impl="overlap" if branch.endswith("overlap") else "all_to_all")
    mesh = make_graph_mesh(ranks_per_graph=W, devices=jax.devices()[:W])
    comm = Communicator.init_process_group("tpu", world_size=W)
    module = _layer(branch, comm)
    shard = lambda a: jnp.asarray(  # noqa: E731
        shard_vertex_data(a, layout.src_counts, plan.n_src_pad))
    xs = shard(rng.normal(size=(V, F)).astype(np.float32))
    ct = shard(rng.normal(size=(V, F)).astype(np.float32))
    plan_dev = jax.tree.map(jnp.asarray, plan)
    in_specs = (P(), P(GRAPH_AXIS), plan_in_specs(plan))

    def trace_and_run():
        # a fresh function each time: JAX caches a traced shard_map body
        # by the function's identity
        def forward(params, x_, p_):
            return module.apply(params, x_[0], squeeze_plan(p_))[None]

        apply = jax.shard_map(
            forward, mesh=mesh, in_specs=in_specs, out_specs=P(GRAPH_AXIS))
        before = _ties_counted()
        fwd_jaxpr = str(jax.make_jaxpr(apply)(params, xs, plan_dev))
        counted = _ties_counted() - before
        out, vjp = jax.vjp(lambda p_, x_: apply(p_, x_, plan_dev), params, xs)
        bwd_jaxpr = str(jax.make_jaxpr(vjp)(ct))
        return (counted, fwd_jaxpr.count("optimization_barrier"),
                bwd_jaxpr.count("optimization_barrier"),
                jax.jit(lambda: (out, vjp(ct)))())

    with jax.set_mesh(mesh):
        params = jax.jit(jax.shard_map(
            lambda x_, p_: module.init(
                jax.random.key(0), x_[0], squeeze_plan(p_)),
            mesh=mesh, in_specs=in_specs[1:], out_specs=P(),
            check_vma=False))(xs, plan_dev)
        *ties, sequenced = trace_and_run()
        assert ties == [TIES, TIES, 0]  # counted, forward's, backward's
        monkeypatch.setattr(
            collectives, "_run_after", lambda token, cols: cols)
        *ties, plain = trace_and_run()
        assert ties == [TIES, 0, 0]
    for a, b in zip(jax.tree.leaves(sequenced), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.abs(np.asarray(sequenced[0])).sum() > 0


def test_graphcast_edge_block_is_not_sequenced(rng, flags, monkeypatch):
    """Its chunks return ``[E, chunk]`` tensors that are concatenated: a
    tie would hold one behind the next. It stays on
    ``map_feature_chunks``, whose program is what it was."""
    from dgraph_tpu.models.graphcast.model import MeshEdgeBlock

    def no_tie(token, cols):
        raise AssertionError("the edge block took a tie")

    monkeypatch.setattr(collectives, "_run_after", no_tie)
    part = np.zeros(V, np.int32)
    edges = np.stack([rng.integers(0, V, E), rng.integers(0, V, E)])
    plan, _ = pl.build_edge_plan(edges, part, world_size=1)
    plan = squeeze_plan(jax.tree.map(jnp.asarray, plan))
    comm = Communicator.init_process_group("single")
    block = MeshEdgeBlock(latent=F, comm=comm)
    x = jnp.ones((plan.n_src_pad, F), jnp.float32)
    e = jnp.ones((plan.e_pad, F), jnp.float32)
    before = _ties_counted()
    params = jax.eval_shape(block.init, jax.random.key(0), e, x, x, plan)
    jaxpr = str(jax.make_jaxpr(block.apply)(params, e, x, x, plan))
    assert _ties_counted() == before
    assert "optimization_barrier" not in jaxpr


def test_map_vertex_chunks_one_chunk_is_a_plain_call(flags):
    x = jnp.arange(12.0).reshape(3, 4)
    before = _ties_counted()
    out = collectives.map_vertex_chunks(lambda t: t * 2, (x,))
    assert _ties_counted() == before
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) * 2)


def test_chunks_whose_tables_cannot_be_placed_stay_independent(
        flags, monkeypatch):
    """Ordering costs a mask fusion a chunk, so it is taken only where a
    table slice fits on-chip memory (``ON_CHIP_BYTES``): a larger one
    (``gcn_papers100m.w4``'s 207 MB) traces to the unordered program."""
    x = jnp.ones((8, 12), jnp.float32)  # slices of 8 x 4 x 4 bytes
    bias = jnp.ones((2, 12), jnp.float32)  # the largest slice decides

    def ties(limit):
        monkeypatch.setattr(collectives, "ON_CHIP_BYTES", limit)
        before = _ties_counted()
        jaxpr = str(jax.make_jaxpr(lambda t, b: collectives.map_vertex_chunks(
            lambda tc, bc: tc.sum(0, keepdims=True) + bc, (t, b)))(x, bias))
        return _ties_counted() - before, jaxpr.count("optimization_barrier")

    assert ties(8 * 4 * 4) == (TIES, TIES)
    assert ties(8 * 4 * 4 - 1) == (0, 0)
    # the real limit parts the two GCN cells' tables (rows x 128 bf16)
    monkeypatch.undo()
    assert 169352 * 128 * 2 <= collectives.ON_CHIP_BYTES < 809728 * 128 * 2


def test_run_after_differentiates_both_ways_and_gives_the_token_nothing():
    token, x = jnp.ones((3,)), jnp.arange(4.0)

    def f(token, x):
        (y,) = collectives._run_after(token, (x,))
        return jnp.sum(y * y)

    g_token, g_x = jax.grad(f, argnums=(0, 1))(token, x)
    np.testing.assert_array_equal(np.asarray(g_token), np.zeros(3))
    np.testing.assert_array_equal(np.asarray(g_x), 2 * np.asarray(x))
    _, tangent = jax.jvp(f, (token, x), (token, jnp.ones_like(x)))
    assert float(tangent) == float(jnp.sum(2 * x))


# ---------------------------------------------------------------------------
# the compiled module's reader (host-only: canned text)
# ---------------------------------------------------------------------------

_CANNED = """\
HloModule jit_step, is_scheduled=true

%fused_computation.clone (param_0.21: bf16[169352,128], param_1.39: s32[2332672]) -> bf16[2332672,128] {
  %param_0.21 = bf16[169352,128]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %param_1.39 = s32[2332672]{0:T(1024)S(1)} parameter(1)
  %transpose.49 = s32[2332672]{0:T(1024)} transpose(%param_1.39), dimensions={0}
  %gather.25 = bf16[2332672,128]{1,0:T(8,128)(2,1)} gather(%param_0.21, %transpose.49), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,128}, metadata={op_name="jit(step)/dgraph.local_take/gather" stack_frame_id=14}
  ROOT %reshape.87 = bf16[2332672,128]{1,0:T(8,128)(2,1)} reshape(%gather.25)
}

%fused_computation.3.clone (param_0.22: bf16[169352,128], param_1.41: s32[2332672]) -> bf16[2332672,128] {
  %param_0.22 = bf16[169352,128]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1.41 = s32[2332672]{0:T(1024)S(1)} parameter(1)
  ROOT %gather.26 = bf16[2332672,128]{1,0:T(8,128)(2,1)} gather(%param_0.22, %param_1.41), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,128}
}

%fused_computation.7 (param_0.5: f32[169344,40], param_1.2: s32[169344,2]) -> f32[169344] {
  %param_0.5 = f32[169344,40]{1,0:T(8,128)} parameter(0)
  %param_1.2 = s32[169344,2]{1,0:T(8,128)} parameter(1)
  ROOT %gather.24 = f32[169344]{0:T(1024)} gather(%param_0.5, %param_1.2), offset_dims={}, collapsed_slice_dims={0,1}, start_index_map={0,1}, index_vector_dim=1, slice_sizes={1,1}
}

ENTRY %main (p0: bf16[169352,128], p1: s32[2332672]) -> bf16[2332672,128] {
  %p0 = bf16[169352,128]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = s32[2332672]{0:T(1024)} parameter(1)
  %f0 = bf16[2332672,128]{1,0:T(8,128)(2,1)} fusion(%p0, %p1), kind=kCustom, calls=%fused_computation.clone
  ROOT %f1 = bf16[2332672,128]{1,0:T(8,128)(2,1)} fusion(%p0, %p1), kind=kCustom, calls=%fused_computation.3.clone
}
"""


def test_gather_table_placement_reads_the_memory_space_of_each_table():
    from dgraph_tpu.analysis.hlo import gather_table_placement, placement_line

    got = gather_table_placement(_CANNED)
    # the element gather (take_along_axis) is not a row gather
    assert [g["gather"] for g in got] == ["gather.25", "gather.26"]
    on_chip, in_hbm = got
    assert on_chip["memory_space"] == 1 and in_hbm["memory_space"] == 0
    assert on_chip["computation"] == "fused_computation.clone"
    assert on_chip["op_name"] == "jit(step)/dgraph.local_take/gather"
    for g in got:
        assert g["table_shape"] == (169352, 128)
        assert g["table_dtype"] == "bf16"
        assert g["table_bytes"] == 169352 * 128 * 2
        assert g["rows"] == 2332672
    assert placement_line(got) == (
        "gather tables on chip: 1 of 2; in HBM: bf16[169352,128] (43.4 MB)")
    assert placement_line(got[:1]) == "gather tables on chip: 1 of 1"
    assert gather_table_placement("HloModule empty\n") == []


def test_placement_line_names_an_edge_tensor_left_in_hbm():
    """What ``scripts/gather_placement.py`` prints for a train step. The
    fused GCN layer's backward (ISSUE 33) gathers from owner-side vertex
    tables, which are placed like the forward's; a backward that falls back
    to the permutation by ``halo_sort_perm`` gathers from an ``[E, C]`` edge
    tensor, which never is, and the line names it."""
    from dgraph_tpu.analysis.hlo import gather_table_placement, placement_line

    transposed = _CANNED.replace(
        'op_name="jit(step)/dgraph.local_take/gather"',
        'op_name="jit(step)/transpose(jvp(GCN))/GraphConvLayer_0/'
        'dgraph.local_take/gather"'
    ).replace("param_0.22 = bf16[169352,128]{1,0:T(8,128)(2,1)}",
              "param_0.22 = bf16[169352,128]{1,0:T(8,128)(2,1)S(1)}")
    got = gather_table_placement(transposed)
    assert got[0]["op_name"].startswith("jit(step)/transpose(jvp(GCN))")
    assert placement_line(got) == "gather tables on chip: 2 of 2"
    permuted = _CANNED.replace("param_0.22: bf16[169352,128]",
                               "param_0.22: bf16[2332672,128]").replace(
        "param_0.22 = bf16[169352,128]", "param_0.22 = bf16[2332672,128]")
    assert placement_line(gather_table_placement(permuted)) == (
        "gather tables on chip: 1 of 2; in HBM: bf16[2332672,128] (597.2 MB)")
