"""Heterogeneous RGAT training (the reference's ``experiments/OGB-LSC``:
RGAT on MAG240M or a degree-calibrated synthetic MAG-like graph).

The real MAG240M requires the ogb.lsc package + a 1.4TB download; like the
reference's ``SyntheticHeterogeneousDataset`` fallback
(``lsc_datasets/synthetic_dataset.py``), the default here is the synthetic
generator with the same relation structure (3 node types, 5 relations).
"""

from __future__ import annotations

import dataclasses
import time


@dataclasses.dataclass
class Config:
    """RGAT paper-classification training."""

    # MAG240M memmap directory (prepare_mag240m_memmap /
    # synthetic_mag240m_memmap layout); overrides the in-memory generator
    memmap_dir: str = ""
    num_papers: int = 5000
    num_authors: int = 3000
    num_institutions: int = 300
    feat_dim: int = 64
    num_classes: int = 8
    hidden: int = 64
    num_layers: int = 2
    num_heads: int = 2
    batch_norm: bool = True
    bn_recompute: bool = False  # remat the BN normalization in backward
    lr: float = 3e-3
    epochs: int = 60
    world_size: int = 0
    # 'multilevel' = union-graph locality partitioning (halo volume shrinks
    # with community structure); 'random' = the worst case
    partition_method: str = "multilevel"
    plan_cache: str = "cache/plans_rgat"  # "" disables
    log_path: str = "logs/rgat_mag.jsonl"
    # thread grad-norm through the jitted step + emit obs step records;
    # build-time flag (False = byte-identical un-instrumented step)
    step_metrics: bool = False


def main(cfg: Config):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm import Communicator, make_graph_mesh
    from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan
    from dgraph_tpu.data.hetero import DistributedHeteroGraph, synthetic_mag
    from dgraph_tpu.models import RGAT
    from dgraph_tpu.obs import startup_record
    from dgraph_tpu.obs.metrics import StepMetrics
    from dgraph_tpu.utils import ExperimentLog

    world = cfg.world_size or len(jax.devices())
    mesh = make_graph_mesh(ranks_per_graph=world)
    comm = Communicator.init_process_group("tpu", world_size=world)

    from dgraph_tpu.plan import plan_efficiency

    if cfg.memmap_dir:
        from dgraph_tpu.data.mag240m import load_mag240m_memmap

        nf, rels, labels, masks, meta = load_mag240m_memmap(cfg.memmap_dir)
        num_classes = meta["num_classes"]
    else:
        nf, rels, labels, masks = synthetic_mag(
            cfg.num_papers, cfg.num_authors, cfg.num_institutions,
            cfg.feat_dim, cfg.num_classes,
        )
        num_classes = cfg.num_classes
    t0 = time.perf_counter()
    g = DistributedHeteroGraph.from_global(
        nf, rels, world, labels=labels, masks=masks,
        partition_method=cfg.partition_method,
        plan_cache=cfg.plan_cache or None,
    )
    log = ExperimentLog(cfg.log_path)
    log.write(startup_record("experiments.rgat_mag"))
    # per-relation padding-efficiency + halo-volume telemetry (VERDICT r1
    # #7/#8): the numbers that decide all_to_all vs ppermute and quantify
    # what the locality partition bought
    for key, plan_r in g.plans.items():
        eff = plan_efficiency(plan_r, g.layouts[key])
        log.write({
            "relation": "-".join(key),
            "partition": cfg.partition_method,
            "halo_pairs": int(g.layouts[key].halo_counts.sum()),
            **{k: (round(v, 4) if isinstance(v, float) else v) for k, v in eff.items()},
        })
    log.write({"plan_build_s": round(time.perf_counter() - t0, 1)})

    model = RGAT(
        hidden_features=cfg.hidden,
        out_features=num_classes,
        comm=comm,
        relations=list(g.plans),
        num_layers=cfg.num_layers,
        num_heads=cfg.num_heads,
        use_batch_norm=cfg.batch_norm,
        bn_recompute=cfg.bn_recompute,
    )

    feats = {t: jnp.asarray(v) for t, v in g.features.items()}
    plans = {k: jax.tree.map(jnp.asarray, p) for k, p in g.plans.items()}
    vmasks = {t: jnp.asarray(v) for t, v in g.vertex_masks.items()}
    y = jnp.asarray(g.labels["paper"])
    mask = jnp.asarray(g.masks[("paper", "train")])

    feat_specs = jax.tree.map(lambda _: P(GRAPH_AXIS), feats)
    plan_specs = {k: plan_in_specs(p) for k, p in plans.items()}
    vm_specs = jax.tree.map(lambda _: P(GRAPH_AXIS), vmasks)

    def unshard(tree):
        feats_, plans_, vmasks_ = tree
        return (
            {t: v[0] for t, v in feats_.items()},
            {k: squeeze_plan(p) for k, p in plans_.items()},
            {t: v[0] for t, v in vmasks_.items()},
        )

    def init_body(feats_, plans_, vmasks_):
        f, p, v = unshard((feats_, plans_, vmasks_))
        return model.init(jax.random.key(0), f, p, v, train=False)

    with jax.set_mesh(mesh):
        variables = jax.jit(
            jax.shard_map(
                init_body,
                mesh=mesh,
                in_specs=(feat_specs, plan_specs, vm_specs),
                out_specs=P(),
            )
        )(feats, plans, vmasks)

    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    opt = optax.adam(cfg.lr)
    opt_state = opt.init(params)

    def train_body(params, batch_stats, feats_, plans_, vmasks_, y_, m_):
        f, p, v = unshard((feats_, plans_, vmasks_))
        yy, mm = y_[0], m_[0]

        def lf(pp):
            out, mut = model.apply(
                {"params": pp, "batch_stats": batch_stats},
                f, p, v, train=True, mutable=["batch_stats"],
            )
            logp = jax.nn.log_softmax(out)
            ll = jnp.take_along_axis(logp, yy[:, None], axis=1)[:, 0]
            cnt = jax.lax.psum(mm.sum(), GRAPH_AXIS)
            loss = -(ll * mm).sum() / jnp.maximum(cnt, 1.0)
            correct = ((jnp.argmax(out, -1) == yy) * mm).sum()
            return loss, (mut.get("batch_stats", {}), correct, cnt)

        (loss, (new_bs, correct, cnt)), grads = jax.value_and_grad(lf, has_aux=True)(params)
        acc = jax.lax.psum(correct, GRAPH_AXIS) / jnp.maximum(cnt, 1.0)
        return jax.lax.psum(loss, GRAPH_AXIS), acc, grads, new_bs

    body = jax.shard_map(
        train_body,
        mesh=mesh,
        in_specs=(P(), P(), feat_specs, plan_specs, vm_specs, P(GRAPH_AXIS), P(GRAPH_AXIS)),
        out_specs=(P(), P(), P(), P()),
    )

    @jax.jit
    def step(params, batch_stats, opt_state):
        loss, acc, grads, new_bs = body(params, batch_stats, feats, plans, vmasks, y, mask)
        # build-time flag: False traces the exact un-instrumented step
        gn = optax.global_norm(grads) if cfg.step_metrics else None
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_bs, opt_state, StepMetrics(loss=loss, accuracy=acc, grad_norm=gn)

    with jax.set_mesh(mesh):
        for epoch in range(cfg.epochs):
            t0 = time.perf_counter()
            params, batch_stats, opt_state, sm = step(params, batch_stats, opt_state)
            jax.block_until_ready(sm.loss)
            if epoch % 10 == 0 or epoch == cfg.epochs - 1:
                rec = sm.record(
                    step=epoch,
                    epoch_ms=round((time.perf_counter() - t0) * 1000, 2),
                )
                rec["epoch"] = epoch  # legacy key, kept for plot scripts
                log.write(rec)


if __name__ == "__main__":
    import os as _os, sys as _sys

    # direct-invocation support (repo not pip-installed): put the repo
    # root on sys.path so `python experiments/<script>.py` works
    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
