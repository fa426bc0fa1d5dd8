"""Every halo lowering (all_to_all, ppermute neighbor rounds, overlap)
against the dense oracle at W = 8, forward and backward, gather and
halo-side scatter, on both sparse (ring) and dense (random) peer sets; and
the neighbor rounds against all_to_all at W = 2 and 4 on a mesh that has a
replica axis beside the graph axis."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dgraph_tpu import config as cfg
from dgraph_tpu import plan as pl
from dgraph_tpu.comm import collectives
from dgraph_tpu.comm.mesh import make_graph_mesh
from dgraph_tpu.plan import shard_edge_data, shard_vertex_data, unshard_vertex_data
from dgraph_tpu.testing import (
    dense_gather,
    dense_scatter_sum,
    spmd_apply,
    unshard_edge_data,
)


@pytest.fixture(params=["ppermute", "all_to_all", "overlap"])
def impl(request):
    old = cfg.halo_impl
    cfg.set_flags(halo_impl=request.param)
    yield request.param
    cfg.set_flags(halo_impl=old)


@pytest.fixture(params=["ring", "random"])
def case(request, rng, impl):
    W, V = 8, 96
    if request.param == "ring":
        # block-partition a ring graph: traffic only to rank+-1 -> sparse deltas
        src = np.arange(V)
        dst = (src + 1) % V
        edges = np.stack([np.concatenate([src, dst]), np.concatenate([dst, src])])
        part = np.sort(np.arange(V) * W // V).astype(np.int32)
    else:
        edges = rng.integers(0, V, size=(2, 600))
        part = np.sort(rng.integers(0, W, V)).astype(np.int32)
    # the overlap lowering needs the plan's interior/boundary split
    plan, layout = pl.build_edge_plan(
        edges, part, world_size=W, overlap=(impl == "overlap")
    )
    assert collectives.resolve_plan_impl(plan, "graph") == impl
    return edges, part, plan, layout, request.param


def test_ring_partition_has_sparse_deltas(rng):
    W, V = 8, 96
    src = np.arange(V)
    dst = (src + 1) % V
    edges = np.stack([np.concatenate([src, dst]), np.concatenate([dst, src])])
    part = np.sort(np.arange(V) * W // V).astype(np.int32)
    plan, _ = pl.build_edge_plan(edges, part, world_size=W)
    assert set(plan.halo_deltas) == {1, W - 1}


def test_gather_matches_dense(mesh8, case, impl, rng):
    edges, part, plan, layout, _ = case
    V, F = len(part), 6
    x = rng.normal(size=(V, F)).astype(np.float32)
    xs = shard_vertex_data(x, layout.src_counts, plan.n_src_pad)
    out = spmd_apply(mesh8, collectives.gather, plan, jnp.asarray(xs), static_args=("src", "graph"))
    got = unshard_edge_data(np.asarray(out), layout)
    np.testing.assert_allclose(got, dense_gather(x, edges, "src"), rtol=1e-6)


def test_scatter_to_halo_side_matches_dense(mesh8, case, impl, rng):
    edges, part, plan, layout, _ = case
    V, F = len(part), 4
    edata = rng.normal(size=(edges.shape[1], F)).astype(np.float32)
    ed = shard_edge_data(edata, layout, plan.e_pad)
    out = spmd_apply(mesh8, collectives.scatter_sum, plan, jnp.asarray(ed), static_args=("src", "graph"))
    got = unshard_vertex_data(np.asarray(out), layout.src_counts)
    np.testing.assert_allclose(
        got, dense_scatter_sum(edata, edges, "src", V), rtol=1e-5, atol=1e-5
    )


def test_gather_grad_matches_dense(mesh8, case, impl, rng):
    edges, part, plan, layout, _ = case
    V, F = len(part), 3
    x = rng.normal(size=(V, F)).astype(np.float32)
    xs = jnp.asarray(shard_vertex_data(x, layout.src_counts, plan.n_src_pad))
    ct = rng.normal(size=(edges.shape[1], F)).astype(np.float32)
    ct_sh = jnp.asarray(shard_edge_data(ct, layout, plan.e_pad))

    def loss_fn(xs_):
        out = spmd_apply(mesh8, collectives.gather, plan, xs_, static_args=("src", "graph"))
        return jnp.sum(out * ct_sh)

    with jax.set_mesh(mesh8):
        grad = jax.jit(jax.grad(loss_fn))(xs)
    got = unshard_vertex_data(np.asarray(grad), layout.src_counts)
    np.testing.assert_allclose(
        got, dense_scatter_sum(ct, edges, "src", V), rtol=1e-5, atol=1e-5
    )


def test_scatter_to_halo_side_grad_matches_dense(mesh8, case, impl, rng):
    """The gradient of the halo-side scatter_sum is the gather of the
    cotangent: d/d(edata_e) sum_v ct_v * out_v = ct[src(e)]."""
    edges, part, plan, layout, _ = case
    V, F = len(part), 3
    edata = rng.normal(size=(edges.shape[1], F)).astype(np.float32)
    ed = jnp.asarray(shard_edge_data(edata, layout, plan.e_pad))
    ct = rng.normal(size=(V, F)).astype(np.float32)
    ct_sh = jnp.asarray(shard_vertex_data(ct, layout.src_counts, plan.n_src_pad))

    def loss_fn(ed_):
        out = spmd_apply(mesh8, collectives.scatter_sum, plan, ed_, static_args=("src", "graph"))
        return jnp.sum(out * ct_sh)

    with jax.set_mesh(mesh8):
        grad = jax.jit(jax.grad(loss_fn))(ed)
    got = unshard_edge_data(np.asarray(grad), layout)
    np.testing.assert_allclose(got, dense_gather(ct, edges, "src"), rtol=1e-6)


# --- W = 2 and 4 under a replica axis: the rounds against all_to_all -------


@pytest.fixture(params=[2, 4])
def replica_case(request, rng):
    W = request.param
    V, E = (48, 300) if W == 2 else (96, 600)
    edges = rng.integers(0, V, size=(2, E))
    part = np.sort(rng.integers(0, W, V)).astype(np.int32)
    plan, layout = pl.build_edge_plan(edges, part, world_size=W)
    mesh = make_graph_mesh(ranks_per_graph=W, num_replicas=8 // W)
    assert dict(mesh.shape)["graph"] == W and mesh.size == 8
    old = cfg.halo_impl
    yield edges, part, plan, layout, mesh
    cfg.set_flags(halo_impl=old)


def _under_both(fn):
    """fn() under the neighbor rounds and under all_to_all."""
    out = {}
    for name in ("ppermute", "all_to_all"):
        cfg.set_flags(halo_impl=name)
        out[name] = np.asarray(fn())
    return out["ppermute"], out["all_to_all"]


def test_rounds_gather_equals_all_to_all_beside_replicas(replica_case, rng):
    edges, part, plan, layout, mesh = replica_case
    V, F = len(part), 6
    x = rng.normal(size=(V, F)).astype(np.float32)
    xs = jnp.asarray(shard_vertex_data(x, layout.src_counts, plan.n_src_pad))
    got, want = _under_both(lambda: spmd_apply(
        mesh, collectives.gather, plan, xs, static_args=("src", "graph")))
    # a received block is placed, never summed: the rounds move the same bits
    assert (got == want).all()
    np.testing.assert_allclose(
        unshard_edge_data(got, layout), dense_gather(x, edges, "src"),
        rtol=1e-6)


def test_rounds_gather_grad_equals_all_to_all_beside_replicas(
        replica_case, rng):
    edges, part, plan, layout, mesh = replica_case
    V, F = len(part), 3
    x = rng.normal(size=(V, F)).astype(np.float32)
    xs = jnp.asarray(shard_vertex_data(x, layout.src_counts, plan.n_src_pad))
    ct = rng.normal(size=(edges.shape[1], F)).astype(np.float32)
    ct_sh = jnp.asarray(shard_edge_data(ct, layout, plan.e_pad))

    def grad_once():
        def loss_fn(xs_):
            out = spmd_apply(mesh, collectives.gather, plan, xs_,
                             static_args=("src", "graph"))
            return jnp.sum(out * ct_sh)

        with jax.set_mesh(mesh):
            return jax.jit(jax.grad(loss_fn))(xs)

    got, want = _under_both(grad_once)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        unshard_vertex_data(got, layout.src_counts),
        dense_scatter_sum(ct, edges, "src", V), rtol=1e-5, atol=1e-5)


def test_rounds_scatter_sum_equals_all_to_all_beside_replicas(
        replica_case, rng):
    edges, part, plan, layout, mesh = replica_case
    V, F = len(part), 4
    edata = rng.normal(size=(edges.shape[1], F)).astype(np.float32)
    ed = jnp.asarray(shard_edge_data(edata, layout, plan.e_pad))
    got, want = _under_both(lambda: spmd_apply(
        mesh, collectives.scatter_sum, plan, ed, static_args=("src", "graph")))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        unshard_vertex_data(got, layout.src_counts),
        dense_scatter_sum(edata, edges, "src", V), rtol=1e-5, atol=1e-5)
