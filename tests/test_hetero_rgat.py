"""Heterogeneous graph + RGAT tests: plan correctness per relation,
single-device vs 8-way logit equivalence (including distributed BatchNorm
statistics), and a short training run.

Mirrors the reference's OGB-LSC stack (``experiments/OGB-LSC``, SURVEY §2.5).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from dgraph_tpu.comm import Communicator
from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan
from dgraph_tpu.data.hetero import DistributedHeteroGraph, synthetic_mag
from dgraph_tpu.models import RGAT
from dgraph_tpu.plan import unshard_vertex_data


@pytest.fixture(scope="module")
def mag():
    return synthetic_mag(num_papers=200, num_authors=120, num_institutions=20, seed=2)


def build(mag, world):
    nf, rels, labels, masks = mag
    return DistributedHeteroGraph.from_global(
        nf, rels, world, labels=labels, masks=masks, partition_method="random"
    )


def to_orig(x_sharded, ren):
    xr = unshard_vertex_data(np.asarray(x_sharded), ren.counts)
    out = np.empty_like(xr)
    out[ren.inv] = xr
    return out


def hetero_in_specs(g):
    return (
        jax.tree.map(lambda _: P(GRAPH_AXIS), g.features),
        jax.tree.map(lambda _: P(GRAPH_AXIS), g.plans),
        jax.tree.map(lambda _: P(GRAPH_AXIS), g.vertex_masks),
    )


def hetero_args(g, shard=None):
    sel = (lambda a: jnp.asarray(a[shard])) if shard is not None else jnp.asarray
    feats = {t: sel(v) for t, v in g.features.items()}
    plans = {k: jax.tree.map(sel, p) for k, p in g.plans.items()}
    vmasks = {t: sel(v) for t, v in g.vertex_masks.items()}
    return feats, plans, vmasks


def test_relation_plans_cover_all_edges(mag):
    nf, rels, _, _ = mag
    g = build(mag, 4)
    for key, edges in rels.items():
        assert float(np.asarray(g.plans[key].edge_mask).sum()) == edges.shape[1]


@pytest.mark.parametrize("hidden,heads", [(16, 2), (64, 4)])
def test_rgat_distributed_matches_single(mesh8, mag, hidden, heads):
    # (64, 4): H*D = 256 > gather_col_block, so the head-group-chunked
    # attention path ENGAGES (the small config covers single-group)
    g1, g8 = build(mag, 1), build(mag, 8)
    rels = list(g8.plans)
    comm1 = Communicator.init_process_group("single")
    comm8 = Communicator.init_process_group("tpu", world_size=8)
    kw = dict(
        hidden_features=hidden, out_features=4, relations=rels, num_layers=2,
        num_heads=heads,
    )
    m1 = RGAT(comm=comm1, **kw)
    m8 = RGAT(comm=comm8, **kw)

    f1, p1, v1 = hetero_args(g1, shard=0)
    variables = m1.init(jax.random.key(0), f1, p1, v1, train=False)
    out1, _ = m1.apply(variables, f1, p1, v1, train=True, mutable=["batch_stats"])
    ref = to_orig(np.asarray(out1)[None], g1.renumberings["paper"])

    def body(feats, plans, vmasks):
        feats = {t: v[0] for t, v in feats.items()}
        plans = {k: squeeze_plan(p) for k, p in plans.items()}
        vmasks = {t: v[0] for t, v in vmasks.items()}
        out, _ = m8.apply(variables, feats, plans, vmasks, train=True, mutable=["batch_stats"])
        return out[None]

    f8, p8, v8 = hetero_args(g8)
    fn = jax.shard_map(
        body, mesh=mesh8, in_specs=hetero_in_specs(g8), out_specs=P(GRAPH_AXIS)
    )
    with jax.set_mesh(mesh8):
        out8 = jax.jit(fn)(f8, p8, v8)
    got = to_orig(out8, g8.renumberings["paper"])
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


def test_rgat_trains(mesh8, mag):
    g8 = build(mag, 8)
    rels = list(g8.plans)
    comm8 = Communicator.init_process_group("tpu", world_size=8)
    model = RGAT(
        hidden_features=16,
        out_features=4,
        comm=comm8,
        relations=rels,
        num_layers=2,
        use_batch_norm=False,
    )
    f8, p8, v8 = hetero_args(g8)
    y = jnp.asarray(g8.labels["paper"])
    mask = jnp.asarray(g8.masks[("paper", "train")])

    def init_body(feats, plans, vmasks):
        feats = {t: v[0] for t, v in feats.items()}
        plans = {k: squeeze_plan(p) for k, p in plans.items()}
        vmasks = {t: v[0] for t, v in vmasks.items()}
        return model.init(jax.random.key(0), feats, plans, vmasks)

    with jax.set_mesh(mesh8):
        params = jax.jit(
            jax.shard_map(
                init_body, mesh=mesh8, in_specs=hetero_in_specs(g8), out_specs=P()
            )
        )(f8, p8, v8)

    opt = optax.adam(5e-3)
    opt_state = opt.init(params)

    def train_body(params, feats, plans, vmasks, y, mask):
        feats = {t: v[0] for t, v in feats.items()}
        plans = {k: squeeze_plan(p) for k, p in plans.items()}
        vmasks = {t: v[0] for t, v in vmasks.items()}
        y_, m_ = y[0], mask[0]

        def lf(p):
            logits = model.apply(p, feats, plans, vmasks)
            logp = jax.nn.log_softmax(logits)
            ll = jnp.take_along_axis(logp, y_[:, None], axis=1)[:, 0]
            cnt = jax.lax.psum(m_.sum(), GRAPH_AXIS)
            return -(ll * m_).sum() / jnp.maximum(cnt, 1.0)

        loss, grads = jax.value_and_grad(lf)(params)
        return jax.lax.psum(loss, GRAPH_AXIS), grads

    in_specs = (P(),) + hetero_in_specs(g8) + (P(GRAPH_AXIS), P(GRAPH_AXIS))
    step_body = jax.shard_map(
        train_body, mesh=mesh8, in_specs=in_specs, out_specs=(P(), P())
    )

    @jax.jit
    def step(params, opt_state):
        loss, grads = step_body(params, f8, p8, v8, y, mask)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    with jax.set_mesh(mesh8):
        for _ in range(30):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses[::10]


def test_locality_partition_cuts_halo_volume():
    """Union-graph locality partitioning must reduce total deduped halo
    pairs vs random (VERDICT r1 #7: random hetero partition makes RGAT halo
    volume worst-case by construction) while keeping every type's per-rank
    balance within the padding slack."""
    from dgraph_tpu.data.hetero import DistributedHeteroGraph, synthetic_mag

    W = 4
    nf, rels, labels, masks = synthetic_mag(2000, 1200, 120, 8, 4, seed=2)

    def halo_pairs(g):
        return sum(int(l.halo_counts.sum()) for l in g.layouts.values())

    g_rand = DistributedHeteroGraph.from_global(
        nf, rels, W, labels=labels, masks=masks, partition_method="random"
    )
    g_loc = DistributedHeteroGraph.from_global(
        nf, rels, W, labels=labels, masks=masks, partition_method="multilevel"
    )
    hp_rand, hp_loc = halo_pairs(g_rand), halo_pairs(g_loc)
    assert hp_loc < 0.8 * hp_rand, (hp_loc, hp_rand)
    # per-type balance: padded size within slack of the ideal share
    for t, ren in g_loc.renumberings.items():
        V = len(ren.perm)
        assert ren.counts.max() <= int(np.ceil(V / W * 1.05)) + 1, (t, ren.counts)
