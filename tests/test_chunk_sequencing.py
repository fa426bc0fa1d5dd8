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
from dgraph_tpu.ops import local as local_ops
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


def _counted(name: str) -> float:
    return default_registry.snapshot()["counters"].get(name, 0)


def _ties_counted() -> float:
    return _counted("gather.chunks_sequenced")


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
            local_ops, "run_after", lambda token, cols: cols)
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

    monkeypatch.setattr(local_ops, "run_after", no_tie)
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


@pytest.mark.parametrize("limit_rows, tied", [
    (8, True),  # the slice fits whole
    (7, True),  # in two row parts of 4
    (3, True),  # in three of 3, 3 and 2
    (2, False),  # four parts: past ``MAX_ROW_PARTS``, cannot pay
])
def test_chunks_are_tied_where_their_tables_can_be_gathered_on_chip(
        flags, monkeypatch, limit_rows, tied):
    """Ordering costs a mask fusion a chunk, so it is taken only where a
    table slice can be gathered from on-chip memory: whole
    (``GATHER_TABLE_BYTES``) or in the row parts ``row_take`` takes it in
    (``gcn_papers100m.w4``'s 207 MB slices, in two). A slice that would
    need more than ``MAX_ROW_PARTS`` (an ``[E, chunk]`` edge tensor)
    traces to the unordered program."""
    x = jnp.ones((8, 12), jnp.float32)  # slices of 8 x 4 x 4 bytes
    bias = jnp.ones((2, 12), jnp.float32)  # the largest slice decides
    monkeypatch.setattr(local_ops, "GATHER_TABLE_BYTES", limit_rows * 4 * 4)
    before = _ties_counted()
    jaxpr = str(jax.make_jaxpr(lambda t, b: collectives.map_vertex_chunks(
        lambda tc, bc: tc.sum(0, keepdims=True) + bc, (t, b)))(x, bias))
    want = TIES if tied else 0
    assert (_ties_counted() - before,
            jaxpr.count("optimization_barrier")) == (want, want)


def test_the_size_rule_parts_the_benchmarks_tables():
    """Rows x 128 bf16 columns: both GCN cells' vertex tables and the fused
    backward's owner-side tables whole, ``gcn_papers100m.w4``'s
    halo-extended table in two, an ``[E, 128]`` edge tensor not at all."""
    assert local_ops.GATHER_TABLE_BYTES == 112 << 20
    parts = [local_ops.on_chip_row_parts(rows, 128 * 2)
             for rows in (169352, 166656, 809728, 2332672, 2561024)]
    assert parts == [1, 1, 2, 0, 0]
    # three parts are the most (the arithmetic is in the rule's docstring)
    assert local_ops.on_chip_row_parts(3 * (112 << 20) // 256, 256) == 3
    assert local_ops.on_chip_row_parts(3 * (112 << 20) // 256 + 1, 256) == 0


# ---------------------------------------------------------------------------
# a table too large for on-chip memory, taken in row parts (ISSUE 35)
# ---------------------------------------------------------------------------


def _boundary_ids(rng, n, k, count):
    """Ids on both sides of every part boundary, at both ends of the
    table, past it and before it (numpy's wrap), and random ones; an odd
    count."""
    rows = -(-n // k)
    edges = [b + d for b in range(0, n + rows, rows) for d in (-2, -1, 0, 1)]
    ids = np.concatenate([
        edges, [n - 1, n, n + 7, 2 * n, -1, -n, -n - 1],
        rng.integers(0, n, count)])
    return jnp.asarray(ids[: len(ids) // 2 * 2 + 1].astype(np.int32))


@pytest.mark.parametrize("oob", ["fill", "clamp"])
@pytest.mark.parametrize("k", [2, 3])
@pytest.mark.parametrize("n", [48, 53])  # divides by 2 and 3, and neither
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_gather_in_row_parts_is_the_whole_gather_to_the_bit(
        rng, monkeypatch, dtype, n, k, oob):
    x = jnp.asarray(rng.standard_normal((n, 8)), dtype)
    idx = _boundary_ids(rng, n, k, 40)
    row_bytes = 8 * x.dtype.itemsize
    monkeypatch.setattr(
        local_ops, "GATHER_TABLE_BYTES", -(-n // k) * row_bytes)
    assert local_ops.on_chip_row_parts(n, row_bytes) == k
    before = _counted("gather.row_parts")
    got = jax.jit(lambda x_, i_: local_ops.row_take(x_, i_, oob=oob))(x, idx)
    assert _counted("gather.row_parts") - before == k
    want = (jnp.take(x, idx, axis=0, mode="fill", fill_value=0)
            if oob == "fill" else x[idx])
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32))
    assert np.abs(np.asarray(got, np.float32)).sum() > 0


@pytest.mark.parametrize("oob", ["fill", "clamp"])
def test_a_table_that_fits_traces_the_unparted_gather(rng, oob):
    """Under the threshold ``row_take`` is the expression it was: the same
    jaxpr, no tie, no part counted (every cell but ``gcn_papers100m.w4``)."""
    x = jnp.asarray(rng.standard_normal((48, 8)), jnp.bfloat16)
    idx = _boundary_ids(rng, 48, 2, 40)

    def parent(x_, i_):
        if oob == "fill":
            return jnp.take(x_, i_, axis=0, mode="fill", fill_value=0)
        return x_[i_]

    before = _counted("gather.row_parts")
    got = str(jax.make_jaxpr(
        lambda x_, i_: local_ops.row_take(x_, i_, oob=oob))(x, idx))
    assert _counted("gather.row_parts") == before
    assert got == str(jax.make_jaxpr(parent)(x, idx))
    assert "optimization_barrier" not in got


@pytest.mark.parametrize("k", [2, 3])
def test_the_parts_are_cut_after_their_ties_and_selected_once(
        rng, monkeypatch, k):
    """Part p + 1 is a slice of the whole table tied behind part p's
    gather (k - 1 barriers, each with the whole table as an operand), and
    one select chain picks the part: k - 1 selects and the fill."""
    x = jnp.asarray(rng.standard_normal((48, 8)), jnp.float32)
    idx = _boundary_ids(rng, 48, k, 40)
    monkeypatch.setattr(local_ops, "GATHER_TABLE_BYTES", 48 // k * 8 * 4)
    jaxpr = jax.make_jaxpr(
        lambda x_, i_: local_ops.row_take(x_, i_, oob="fill"))(x, idx)
    ties = [e for e in jaxpr.jaxpr.eqns
            if e.primitive.name == "custom_jvp_call"]  # run_after
    assert len(ties) == k - 1 == str(jaxpr).count("optimization_barrier")
    for e in ties:
        assert [v.aval.shape for v in e.invars] == [(idx.shape[0], 8), (48, 8)]
    calls = [e.params["name"] for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "jit"]
    assert calls.count("_take") == k
    # the ids' wrap, k - 1 choices between parts, the fill
    assert calls.count("_where") == k + 1


@pytest.mark.parametrize("k", [2, 3])
def test_the_sort_routes_vjp_does_not_see_the_parts(rng, monkeypatch, k):
    """``take_rows_sort_route``'s gradient (gather by the permutation,
    sorted segment-sum) is the same to the bit whether its forward took
    the table whole or in parts; the tie is the forward's alone."""
    n, e = 53, 200
    x = jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)
    ids = rng.integers(0, n, e).astype(np.int32)
    perm = np.argsort(ids, kind="stable").astype(np.int32)
    ct = jnp.asarray(rng.standard_normal((e, 8)), jnp.float32)

    def grad():
        # a fresh function each time: jit caches a trace by identity
        def loss(x_):
            rows = local_ops.take_rows_sort_route(
                x_, jnp.asarray(ids), jnp.asarray(perm),
                jnp.asarray(ids[perm]), pallas_hints=(0, 0, 0))
            return (rows * ct).sum()

        jaxpr = str(jax.make_jaxpr(jax.grad(loss))(x))
        return jaxpr.count("optimization_barrier"), jax.jit(
            jax.value_and_grad(loss))(x)

    ties, whole = grad()
    assert ties == 0
    monkeypatch.setattr(local_ops, "GATHER_TABLE_BYTES", -(-n // k) * 8 * 4)
    ties, parted = grad()
    assert ties == k - 1  # the forward's; none came with the transpose
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(parted)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_after_differentiates_both_ways_and_gives_the_token_nothing():
    token, x = jnp.ones((3,)), jnp.arange(4.0)

    def f(token, x):
        (y,) = local_ops.run_after(token, (x,))
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
