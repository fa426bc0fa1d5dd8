"""Model tests: single-device (SingleComm) vs 8-way distributed (TpuComm)
logit equivalence — the strongest correctness statement: the distributed
model computes bit-for-bit (up to fp tolerance) the same function as the
dense one — plus end-to-end training convergence on a synthetic SBM task.

This mirrors the reference's dummy-communicator model tests
(``experiments/GraphCast/tests/test_single_model.py``) and the pattern that
the same layer code runs under real and fake backends (SURVEY.md §3.5).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from dgraph_tpu.comm import Communicator
from dgraph_tpu.data import DistributedGraph, synthetic
from dgraph_tpu.models import GCN, GAT, GraphSAGE
from dgraph_tpu.plan import unshard_vertex_data
from dgraph_tpu.testing import spmd_apply


@pytest.fixture(scope="module")
def sbm():
    return synthetic.sbm_classification_graph(num_nodes=400, seed=1)


def build_graphs(sbm, world):
    return DistributedGraph.from_global(
        sbm["edge_index"],
        sbm["features"],
        sbm["labels"],
        sbm["masks"],
        world_size=world,
        partition_method="random",  # stress cross-rank edges
        add_symmetric_norm=True,
    )


def to_original_order(x_sharded, g):
    """[W, n_pad, ...] -> [V, ...] in the ORIGINAL (pre-renumbering) ids."""
    xr = unshard_vertex_data(np.asarray(x_sharded), g.ren.counts)
    out = np.empty_like(xr)
    out[g.ren.inv] = xr
    return out


MODELS = {
    "gcn": lambda comm: GCN(hidden_features=32, out_features=4, comm=comm),
    "sage": lambda comm: GraphSAGE(hidden_features=32, out_features=4, comm=comm),
    "gat": lambda comm: GAT(hidden_features=16, out_features=4, comm=comm, num_heads=2),
}


@pytest.mark.parametrize("name", ["gcn", "sage", "gat"])
def test_distributed_matches_single_device(mesh8, sbm, name):
    g1 = build_graphs(sbm, 1)
    g8 = build_graphs(sbm, 8)

    comm1 = Communicator.init_process_group("single")
    comm8 = Communicator.init_process_group("tpu", world_size=8)
    model1, model8 = MODELS[name](comm1), MODELS[name](comm8)

    def args_for(g, shard=None):
        sel = (lambda a: jnp.asarray(a[shard])) if shard is not None else jnp.asarray
        plan = jax.tree.map(sel, g.plan)
        extra = ()
        if name == "gcn":
            extra = (sel(g.edge_weight),)
        return (sel(g.features), plan) + extra

    params = model1.init(jax.random.key(0), *args_for(g1, shard=0))

    out1 = model1.apply(params, *args_for(g1, shard=0))
    ref = to_original_order(np.asarray(out1)[None], g1)

    def fn8(x, *rest):
        plan_shard = rest[-1]
        extra = rest[:-1]
        return model8.apply(params, x, plan_shard, *extra)

    arrays = [jnp.asarray(g8.features)]
    static = ()
    if name == "gcn":
        arrays.append(jnp.asarray(g8.edge_weight))

    def fn(x, *rest):
        # rest = (*extra_arrays, plan_shard)
        extra, plan_shard = rest[:-1], rest[-1]
        return model8.apply(params, x, plan_shard, *extra)

    # spmd_apply passes (arrays..., plan, static...) — adapt ordering
    def body(x, *rest):
        plan_shard = rest[-1]
        extras = rest[:-1]
        return model8.apply(params, x, plan_shard, *extras)

    from dgraph_tpu.testing import spmd_apply as _apply

    def reordered(*a):
        # a = (x, [ew], plan)
        return body(*a)

    out8 = _apply(mesh8, reordered, g8.plan, *arrays)
    got = to_original_order(out8, g8)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_gcn_trains_on_sbm(mesh8, sbm):
    from dgraph_tpu.train.loop import fit

    g8 = build_graphs(sbm, 8)
    comm8 = Communicator.init_process_group("tpu", world_size=8)
    model = GCN(hidden_features=32, out_features=4, comm=comm8)
    params, history = fit(
        model, g8, mesh8, optimizer=optax.adam(5e-3), num_epochs=60
    )
    assert history[-1]["loss"] < history[0]["loss"] * 0.5
    assert history[-1]["acc"] > 0.75


def test_distributed_gradients_match_single_device(mesh8, sbm):
    """Full train-step gradient equivalence: psum'd distributed grads ==
    dense single-device grads (parity with test_NCCLCommPlan.py's backward
    checks, but end-to-end through the model)."""
    g1 = build_graphs(sbm, 1)
    g8 = build_graphs(sbm, 8)
    comm1 = Communicator.init_process_group("single")
    comm8 = Communicator.init_process_group("tpu", world_size=8)
    m1 = GCN(hidden_features=8, out_features=4, comm=comm1)
    m8 = GCN(hidden_features=8, out_features=4, comm=comm8)

    plan1 = jax.tree.map(lambda l: jnp.asarray(l[0]), g1.plan)
    params = m1.init(
        jax.random.key(0), jnp.asarray(g1.features[0]), plan1, jnp.asarray(g1.edge_weight[0])
    )

    def loss1(p):
        logits = m1.apply(p, jnp.asarray(g1.features[0]), plan1, jnp.asarray(g1.edge_weight[0]))
        logp = jax.nn.log_softmax(logits)
        y = jnp.asarray(g1.labels[0])
        mask = jnp.asarray(g1.masks["train"][0])
        ll = jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
        return -(ll * mask).sum() / mask.sum()

    dense_grads = jax.grad(loss1)(params)

    from dgraph_tpu.train.loop import make_train_step

    # one step with zero LR: metrics + grads path exercised; compare loss
    opt = optax.sgd(0.0)
    batch = {
        "x": jnp.asarray(g8.features),
        "y": jnp.asarray(g8.labels),
        "mask": jnp.asarray(g8.masks["train"]),
        "edge_weight": jnp.asarray(g8.edge_weight),
    }
    plan8 = jax.tree.map(jnp.asarray, g8.plan)
    step = make_train_step(m8, opt, mesh8, plan8, donate=False)
    with jax.set_mesh(mesh8):
        _, _, metrics = step(params, opt.init(params), batch, plan8)
    np.testing.assert_allclose(float(metrics["loss"]), float(loss1(params)), rtol=1e-4)

    # and the distributed grads themselves
    from jax.sharding import PartitionSpec as P
    from dgraph_tpu.comm.mesh import plan_in_specs, squeeze_plan, GRAPH_AXIS
    from dgraph_tpu.train.loop import masked_cross_entropy

    def shard_grads(params, batch, plan):
        plan_s = squeeze_plan(plan)
        b = jax.tree.map(lambda l: l[0], batch)

        def lf(p):
            logits = m8.apply(p, b["x"], plan_s, b["edge_weight"])
            return masked_cross_entropy(logits, b["y"], b["mask"], GRAPH_AXIS)

        # grad w.r.t. replicated params auto-psums across shards (vma)
        return jax.grad(lf)(params)

    batch_specs = jax.tree.map(lambda _: P(GRAPH_AXIS), batch)
    with jax.set_mesh(mesh8):
        dist_grads = jax.jit(
            jax.shard_map(
                shard_grads,
                mesh=mesh8,
                in_specs=(P(), batch_specs, plan_in_specs(plan8)),
                out_specs=P(),
            )
        )(params, batch, plan8)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-5),
        dist_grads,
        dense_grads,
    )


def test_multilabel_float_targets_train(mesh8, sbm):
    """ogbn-proteins-shaped path: float [V, C] multi-label targets survive
    DistributedGraph.from_global (no int cast) and train under the BCE loss
    (the reference handles proteins via a per-dataset num_classes table,
    ``ogbn_datasets.py:25-37``)."""
    from dgraph_tpu.train.loop import fit, masked_bce_multilabel

    rng = np.random.default_rng(3)
    C = 6
    multilabels = (rng.random((400, C)) < 0.3).astype(np.float32)
    g8 = DistributedGraph.from_global(
        sbm["edge_index"],
        sbm["features"],
        multilabels,
        sbm["masks"],
        world_size=8,
        partition_method="random",
        add_symmetric_norm=True,
    )
    assert g8.labels.dtype == np.float32 and g8.labels.shape[-1] == C
    comm8 = Communicator.init_process_group("tpu", world_size=8)
    model = GCN(hidden_features=16, out_features=C, comm=comm8)
    params, history = fit(
        model, g8, mesh8, optimizer=optax.adam(5e-3), num_epochs=15,
        loss_fn=masked_bce_multilabel,
    )
    assert history[-1]["loss"] < history[0]["loss"]
    assert np.isfinite(history[-1]["loss"])


def test_chunked_pipeline_one_exchange_per_layer(mesh8, sbm):
    """Structural pin for the feature-chunked edge pipeline: hidden width
    256 = 2 chunks per layer, but the halo all_to_all count must stay ONE
    per conv layer (comm.halo_extend hoists it out of the chunk loop) —
    chunking must never multiply collectives."""
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan

    g = build_graphs(sbm, 8)
    comm = Communicator.init_process_group("tpu", world_size=8)
    model = GCN(hidden_features=256, out_features=4, comm=comm, num_layers=2)
    plan = jax.tree.map(jnp.asarray, g.plan)
    x = jnp.asarray(g.features)
    ew = jnp.asarray(g.edge_weight)
    params = jax.eval_shape(
        lambda: jax.shard_map(
            lambda p_, x_, e_: model.init(jax.random.key(0), x_[0],
                                          squeeze_plan(p_), e_[0]),
            mesh=mesh8,
            in_specs=(plan_in_specs(plan), P(GRAPH_AXIS), P(GRAPH_AXIS)),
            out_specs=P(),
        )(plan, x, ew)
    )
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params)

    fwd = jax.shard_map(
        lambda pp, p_, x_, e_: model.apply(pp, x_[0], squeeze_plan(p_),
                                           e_[0])[None],
        mesh=mesh8,
        in_specs=(P(), plan_in_specs(plan), P(GRAPH_AXIS), P(GRAPH_AXIS)),
        out_specs=P(GRAPH_AXIS),
    )
    jaxpr = jax.make_jaxpr(fwd)(params, plan, x, ew)

    def count(j, name):
        n = 0
        for e in j.eqns:
            n += name in e.primitive.name
            for p in e.params.values():
                for item in (p if isinstance(p, (list, tuple)) else [p]):
                    if hasattr(item, "jaxpr"):
                        n += count(getattr(item.jaxpr, "jaxpr", item.jaxpr),
                                   name)
                    elif hasattr(item, "eqns"):
                        n += count(item, name)
        return n

    n_a2a = count(jaxpr.jaxpr, "all_to_all")
    n_pp = count(jaxpr.jaxpr, "ppermute")
    # 2 conv layers x 1 halo side each = EXACTLY 2 exchanges in the
    # forward (the stream side is the halo side; the bias side is local).
    # The random partition makes every peer pair live, so the halo cost
    # model deterministically picks all_to_all (ppermute must be absent —
    # a ppermute-lowered exchange would make the a2a count vacuous).
    assert n_pp == 0, f"unexpected ppermute lowering ({n_pp})"
    assert n_a2a == 2, f"chunking changed the collective count: {n_a2a}"


def test_gat_head_chunked_matches_single_device(mesh8, sbm):
    """GAT at H*D > gather_col_block (4 heads x 64 = 256) so the
    head-group-chunked attention path ENGAGES distributed (the default
    test configs are below the threshold and only cover the full-width
    path). Distributed chunked output must equal the single-device run."""
    from dgraph_tpu.testing import spmd_apply as _apply

    g1 = build_graphs(sbm, 1)
    g8 = build_graphs(sbm, 8)
    comm1 = Communicator.init_process_group("single")
    comm8 = Communicator.init_process_group("tpu", world_size=8)
    mk = lambda comm: GAT(hidden_features=64, out_features=4, comm=comm,
                          num_heads=4)
    model1, model8 = mk(comm1), mk(comm8)

    plan1 = jax.tree.map(lambda a: jnp.asarray(a[0]), g1.plan)
    params = model1.init(jax.random.key(0), jnp.asarray(g1.features[0]), plan1)
    ref = to_original_order(
        np.asarray(model1.apply(params, jnp.asarray(g1.features[0]),
                                plan1))[None], g1)

    out8 = _apply(
        mesh8,
        lambda x, plan_shard: model8.apply(params, x, plan_shard),
        g8.plan, jnp.asarray(g8.features),
    )
    np.testing.assert_allclose(to_original_order(out8, g8), ref,
                               rtol=2e-4, atol=2e-4)


def test_graph_transformer_chunked_local_matches_single(mesh8, sbm):
    """GraphTransformer at latent 256 > gather_col_block so the chunked
    local-branch path engages; distributed must equal single-device."""
    from dgraph_tpu.models import GraphTransformer
    from dgraph_tpu.testing import spmd_apply as _apply

    g1 = build_graphs(sbm, 1)
    g8 = build_graphs(sbm, 8)
    comm1 = Communicator.init_process_group("single")
    comm8 = Communicator.init_process_group("tpu", world_size=8)
    mk = lambda comm: GraphTransformer(latent=256, out_features=4, comm=comm,
                                       num_layers=1, num_heads=4)
    model1, model8 = mk(comm1), mk(comm8)

    plan1 = jax.tree.map(lambda a: jnp.asarray(a[0]), g1.plan)
    vm1 = jnp.asarray(g1.vertex_mask[0])
    params = model1.init(jax.random.key(0), jnp.asarray(g1.features[0]),
                         plan1, vm1)
    ref = to_original_order(
        np.asarray(model1.apply(params, jnp.asarray(g1.features[0]), plan1,
                                vm1))[None], g1)

    out8 = _apply(
        mesh8,
        lambda x, vm, plan_shard: model8.apply(params, x, plan_shard, vm),
        g8.plan, jnp.asarray(g8.features), jnp.asarray(g8.vertex_mask),
    )
    np.testing.assert_allclose(to_original_order(out8, g8), ref,
                               rtol=2e-3, atol=2e-3)
