"""Interior/boundary edge split + compute–communication-overlap halo
lowering.

Parity strategy: the overlap lowering (double-buffered ppermute rounds +
split interior/boundary aggregation) must be BIT-IDENTICAL to the padded
all_to_all path, forward and backward, on the 2- and 4-shard synthetic
graphs — same reduction operands, same term order (the overlap schedule
changes WHEN things run, never what is summed). Plan-level invariants and
the footprint's overlapped-schedule pricing are host-only (no compiles).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dgraph_tpu import config as cfg
from dgraph_tpu import plan as pl
from dgraph_tpu.comm import collectives
from dgraph_tpu.comm.mesh import make_graph_mesh
from dgraph_tpu.plan import shard_edge_data, shard_vertex_data
from dgraph_tpu.testing import spmd_apply


@pytest.fixture
def impl_flags():
    saved = (cfg.halo_impl, cfg.tuned_halo_impl)
    yield
    cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1])


def _case(rng, W, V=48, E=300):
    part = np.sort(rng.integers(0, W, V)).astype(np.int32)
    edges = np.stack([rng.integers(0, V, E), rng.integers(0, V, E)])
    plan, layout = pl.build_edge_plan(edges, part, world_size=W, overlap=True)
    return edges, part, plan, layout


def _run_all(mesh, plan, xs, ed, ct_e, ct_v):
    """One jitted program per lowering: gather fwd+grad and halo-side
    scatter fwd+grad together (keeps the new-compile count low — the
    tier-1 budget rule)."""

    def everything(xs_, ed_):
        out_g = spmd_apply(
            mesh, collectives.gather, plan, xs_, static_args=("src", "graph")
        )
        g_g = jax.grad(
            lambda x: jnp.sum(
                spmd_apply(mesh, collectives.gather, plan, x,
                           static_args=("src", "graph")) * ct_e
            )
        )(xs_)
        out_s = spmd_apply(
            mesh, collectives.scatter_sum, plan, ed_,
            static_args=("src", "graph"),
        )
        g_s = jax.grad(
            lambda e: jnp.sum(
                spmd_apply(mesh, collectives.scatter_sum, plan, e,
                           static_args=("src", "graph")) * ct_v
            )
        )(ed_)
        return out_g, g_g, out_s, g_s

    with jax.set_mesh(mesh):
        return [np.asarray(a) for a in jax.jit(everything)(xs, ed)]


@pytest.mark.parametrize("W", [2, 4])
def test_overlap_bitwise_parity_with_all_to_all(rng, impl_flags, W):
    """halo_exchange_overlap / scatter_sum_overlap (through the gather and
    halo-side scatter they lower) against the all_to_all path, forward AND
    backward. Gather forward, gather gradient and scatter gradient are
    bit-identical at every W: the exchange moves rows and its transpose
    runs the same masked flat segment-sum over the same buffer. Scatter
    FORWARD is bit-identical at W = 2 only. Piece by piece (interior
    sum, slot partials, reverse rounds) the two paths agree to the bit;
    the last merge — a vertex's local partial plus one returned partial
    per delta — is folded into the segment-sum's accumulation differently
    by XLA once each op is compiled, so the partial sums over deltas are
    added in another order than the serial path's slots: the same terms,
    regrouped. With one delta there is one order; beyond, float32
    rounding differs (54–61 of 320 elements by ≤ 9.6e-7 on four seeds),
    bounded per vertex by the two-orderings bound ``2 u (n-1) sum|terms|``
    (u = 2^-24, n = that vertex's edge count), which is what W > 2 is
    held to. No blanket rtol: a dropped or doubled term is O(1)."""
    edges, part, plan, layout = _case(rng, W)
    V, F = len(part), 5
    xs = jnp.asarray(shard_vertex_data(
        rng.normal(size=(V, F)).astype(np.float32),
        layout.src_counts, plan.n_src_pad,
    ))
    ed_raw = rng.normal(size=(edges.shape[1], F)).astype(np.float32)
    ed = jnp.asarray(shard_edge_data(ed_raw, layout, plan.e_pad))
    ct_e = jnp.asarray(shard_edge_data(
        rng.normal(size=(edges.shape[1], F)).astype(np.float32),
        layout, plan.e_pad,
    ))
    ct_v = jnp.asarray(shard_vertex_data(
        rng.normal(size=(V, F)).astype(np.float32),
        layout.src_counts, plan.n_src_pad,
    ))
    mesh = make_graph_mesh(ranks_per_graph=W, devices=jax.devices()[:W])

    cfg.set_flags(halo_impl="overlap")
    got_ov = _run_all(mesh, plan, xs, ed, ct_e, ct_v)
    cfg.set_flags(halo_impl="all_to_all")
    got_a2a = _run_all(mesh, plan, xs, ed, ct_e, ct_v)
    for name, a, b in zip(
        ("gather fwd", "gather grad", "scatter fwd", "scatter grad"),
        got_ov, got_a2a,
    ):
        if name == "scatter fwd" and W > 2:
            abs_sum = np.zeros((V, F), np.float32)
            np.add.at(abs_sum, edges[0], np.abs(ed_raw))
            n_terms = np.bincount(edges[0], minlength=V)
            atol = shard_vertex_data(
                np.finfo(np.float32).eps
                * np.maximum(n_terms - 1, 0)[:, None] * abs_sum,
                layout.src_counts, plan.n_src_pad,
            )
            assert (np.abs(a - b) <= atol).all(), (
                f"{name} beyond the regrouping bound: "
                f"{np.abs(a - b).max()} vs {atol.max()}"
            )
            continue
        np.testing.assert_array_equal(a, b, err_msg=f"{name} not bit-identical")


def test_overlap_models_match_all_to_all(rng, impl_flags):
    """Model-level routing (GCN fused scatter_bias_relu_overlap + SAGE
    gather_scatter_overlap) agrees with the serial lowering — allclose,
    not bitwise: the interior/boundary split regroups the owner-side
    float accumulation."""
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm import Communicator
    from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan
    from dgraph_tpu.models.gcn import GraphConvLayer
    from dgraph_tpu.models.sage import SAGEConv

    W, V, E, F = 2, 48, 300, 8
    edges, part, plan, layout = _case(rng, W, V, E)
    mesh = make_graph_mesh(ranks_per_graph=W, devices=jax.devices()[:W])
    comm = Communicator.init_process_group("tpu", world_size=W)
    xs = jnp.asarray(shard_vertex_data(
        rng.normal(size=(V, F)).astype(np.float32),
        layout.src_counts, plan.n_src_pad,
    ))
    modules = [
        GraphConvLayer(out_features=8, comm=comm),  # fused bias+relu path
        SAGEConv(out_features=8, comm=comm),  # identity-message path
    ]

    def run(module, impl):
        cfg.set_flags(halo_impl=impl)

        def body(x_, p_):
            psq = squeeze_plan(p_)
            params = module.init(jax.random.key(0), x_[0], psq)
            return module.apply(params, x_[0], psq)[None]

        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(GRAPH_AXIS), plan_in_specs(plan)),
            out_specs=P(GRAPH_AXIS),
        )
        with jax.set_mesh(mesh):
            return np.asarray(jax.jit(f)(xs, jax.tree.map(jnp.asarray, plan)))

    for module in modules:
        a = run(module, "overlap")
        b = run(module, "all_to_all")
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# Host-only: plan invariants, resolution, footprint pricing (no compiles)
# ---------------------------------------------------------------------------


class TestOverlapPlan:
    def test_split_tiles_live_edges(self, rng):
        _, _, plan, _ = _case(rng, 4)
        ov = plan.overlap
        assert ov is not None
        counts = pl.interior_boundary_edge_counts(plan)
        np.testing.assert_array_equal(
            np.asarray(ov.num_interior), counts["interior_per_shard"]
        )
        np.testing.assert_array_equal(
            np.asarray(ov.num_interior) + np.asarray(ov.num_boundary),
            np.asarray(plan.num_edges),
        )
        pl.validate_plan(plan)  # all invariants hold on a fresh build

    def test_validate_rejects_corrupt_split(self, rng):
        _, _, plan, _ = _case(rng, 4)
        ov = plan.overlap
        # 1) interior referencing a halo slot
        bad_int = np.asarray(ov.side("interior", plan.halo_side)).copy()
        bad_int[0, 0] = plan.n_src_pad + 1  # halo slot on the halo side
        field = "int_src" if plan.halo_side == "src" else "int_dst"
        corrupt = dataclasses.replace(plan, overlap=dataclasses.replace(
            ov, **{field: bad_int}))
        with pytest.raises(ValueError, match="interior halo-side id"):
            pl.validate_plan(corrupt)
        # 2) boundary slot out of the halo buffer
        bfield = "bnd_src" if plan.halo_side == "src" else "bnd_dst"
        bad_bnd = np.asarray(getattr(ov, bfield)).copy()
        bad_bnd[0, 0] = plan.world_size * plan.halo.s_pad + 3
        corrupt = dataclasses.replace(plan, overlap=dataclasses.replace(
            ov, **{bfield: bad_bnd}))
        with pytest.raises(ValueError, match="boundary slot"):
            pl.validate_plan(corrupt)
        # 3) subset counts that no longer tile the edge set
        corrupt = dataclasses.replace(plan, overlap=dataclasses.replace(
            ov, num_interior=np.asarray(ov.num_interior) + 1))
        with pytest.raises(ValueError, match="int_mask count|tile"):
            pl.validate_plan(corrupt)

    def test_overlap_rejected_without_sorted_edges(self, rng):
        W, V = 2, 32
        part = np.sort(rng.integers(0, W, V)).astype(np.int32)
        edges = np.stack([rng.integers(0, V, 64), rng.integers(0, V, 64)])
        with pytest.raises(ValueError, match="overlap=True conflicts"):
            pl.build_edge_plan(
                edges, part, world_size=W, overlap=True, sort_edges=False
            )

    def test_env_pin_builds_spec_and_resolves(self, rng, impl_flags):
        W, V = 2, 32
        part = np.sort(rng.integers(0, W, V)).astype(np.int32)
        edges = np.stack([np.arange(V), (np.arange(V) + 1) % V])
        cfg.set_flags(halo_impl="overlap")
        plan, _ = pl.build_edge_plan(edges, part, world_size=W)  # auto
        assert plan.overlap is not None
        impl, source = pl.resolve_halo_impl(
            W, plan.halo_deltas, overlap_available=True)
        assert (impl, source) == ("overlap", "env")

    def test_resolution_degrades_without_spec(self, rng, impl_flags):
        """An 'overlap' pin on a plan with no split must fall back to a
        lowerable impl, never half-lower (mixed lowerings in one step)."""
        cfg.set_flags(halo_impl="overlap")
        # spec-less plan: overlap=False forces the split off despite the pin
        W, V = 2, 32
        part = np.sort(rng.integers(0, W, V)).astype(np.int32)
        edges = np.stack([np.arange(V), (np.arange(V) + 1) % V])
        plan, _ = pl.build_edge_plan(edges, part, world_size=W, overlap=False)
        assert plan.overlap is None
        impl, source = pl.resolve_halo_impl(
            W, plan.halo_deltas, overlap_available=False)
        assert impl in ("ppermute", "all_to_all")
        assert source == "heuristic"


def test_footprint_arxiv_4shard_overlap_bytes(impl_flags):
    """Acceptance pin: on the arxiv-shaped 4-shard synthetic workload the
    resolved overlap exchange carries strictly fewer collective bytes than
    the padded full-halo all_to_all operand, and the overlapped schedule's
    exposed time never exceeds the serial rounds it replaces."""
    from dgraph_tpu import partition as pt
    from dgraph_tpu.data.synthetic import arxiv_shaped_edges
    from dgraph_tpu.obs.footprint import plan_footprint

    edge_index, num_nodes = arxiv_shaped_edges(0)
    new_edges, ren = pt.partition_graph(
        edge_index, num_nodes, 4, method="block", seed=0
    )
    plan, _ = pl.build_edge_plan(
        new_edges, ren.partition, world_size=4, pad_multiple=128, overlap=True
    )
    cfg.set_flags(halo_impl="auto", tuned_halo_impl=None)
    fp = plan_footprint(plan, "bfloat16", 128)
    ex = fp["collectives"]["halo_exchange"]
    assert ex["impl"] == "overlap"  # spec present -> heuristic adopts it
    # boundary-only rounds vs the padded [W*S, F] full-halo block
    assert ex["operand_bytes_per_shard"] < ex["a2a_operand_bytes_per_shard"]
    assert ex["ici_bytes_per_shard"] == ex["operand_bytes_per_shard"]
    ov = ex["overlap"]
    assert ov["rounds"] == len(plan.halo_deltas)
    assert ov["exposed_us"] <= ov["serial_us"]
    assert ov["hidden_us"] >= 0
    split = fp["edge_split"]
    assert 0 < split["boundary_frac"] < 1
    assert split["interior_frac"] + split["boundary_frac"] == pytest.approx(1.0)
    assert (
        split["interior_total"] + split["boundary_total"]
        == int(np.asarray(plan.num_edges).sum())
    )
    # the activation dtype flows into the runtime-buffer accounting
    assert fp["plan_memory"]["dtype_bytes"] == 2
