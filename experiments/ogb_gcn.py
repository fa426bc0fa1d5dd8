"""Full-graph node classification (the reference's ``experiments/OGB/main.py``).

Trains GCN / GraphSAGE / GAT on a partitioned graph over a TPU mesh, with
per-epoch timing, accuracy logs, and the set-up stage totals. Data: a
synthetic SBM graph by default (this environment has no ogb package / no
egress), or any ``.npz`` with edge_index/features/labels/train_mask/... via
``--data.path`` — the `ogbn-*` datasets exported to npz load unchanged.

Run (single host; mesh = all visible devices):
    python experiments/ogb_gcn.py --model gcn --epochs 100
    python experiments/ogb_gcn.py --data.num_nodes 100000 --world_size 4
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np


@dataclasses.dataclass
class DataConfig:
    path: Optional[str] = None  # npz with edge_index [2,E], features, labels, masks
    ogb_name: Optional[str] = None  # e.g. 'ogbn-arxiv' — needs the ogb
    # package, OR a raw download in the official layout under `root`
    # (data/ogb_raw.py parses it directly), OR path pointing at an
    # export_npz() artifact (data/ogbn.py)
    root: str = "dataset"  # where the ogb package / raw downloads live
    num_nodes: int = 5000  # synthetic SBM size when path is None
    num_classes: int = 8
    feat_dim: int = 64
    avg_degree: float = 10.0
    partition: str = "multilevel"  # METIS-shaped native partitioner


@dataclasses.dataclass
class Config:
    """Distributed full-graph GCN training."""

    model: str = "gcn"  # gcn | sage | gat | gt (GraphTransformer)
    hidden: int = 128
    num_layers: int = 2
    lr: float = 5e-3
    epochs: int = 100
    world_size: int = 0  # 0 = all devices
    log_path: str = "logs/ogb_gcn.jsonl"
    # thread grad-norm/mask-count through the jitted step (obs.metrics);
    # build-time flag — the default False keeps the timed step
    # byte-identical to the historical one so epoch_ms stays comparable
    # against recorded baselines (step records are emitted either way,
    # just without the in-step extras)
    step_metrics: bool = False
    data: DataConfig = dataclasses.field(default_factory=DataConfig)


def _num_classes(labels: np.ndarray) -> int:
    # multi-label float targets ([V, C], e.g. ogbn-proteins): C is the width
    if labels.ndim > 1:
        return int(labels.shape[1])
    return int(labels.max()) + 1


def _normalize_split_names(masks: dict) -> dict:
    """OGB says "valid"; the training loop's split name is "val"
    (DistributedGraph.batch falls back to ALL vertices on an unknown split
    — a silent eval-on-everything without this rename)."""
    if "valid" in masks and "val" not in masks:
        masks["val"] = masks.pop("valid")
    return masks


def load_data(cfg: DataConfig):
    if cfg.ogb_name:
        from dgraph_tpu.data import ogbn

        arrs = (
            ogbn.from_npz(cfg.path) if cfg.path
            else ogbn.load_ogb_arrays(cfg.ogb_name, root=cfg.root)
        )
        labels = np.asarray(arrs["labels"])
        masks = {
            k.removesuffix("_mask"): np.asarray(v)
            for k, v in arrs.items()
            if k.endswith("_mask")
        }
        return {
            "edge_index": np.asarray(arrs["edge_index"]),
            "features": np.asarray(arrs["features"]),
            "labels": labels,
            "masks": _normalize_split_names(masks),
            "num_classes": _num_classes(labels),
        }
    if cfg.path:
        z = np.load(cfg.path)
        masks = _normalize_split_names({
            k.removesuffix("_mask"): z[k] for k in z.files if k.endswith("_mask")
        })
        return {
            "edge_index": z["edge_index"],
            "features": z["features"],
            "labels": z["labels"],
            "masks": masks,
            "num_classes": _num_classes(np.asarray(z["labels"])),
        }
    from dgraph_tpu.data import synthetic

    return synthetic.sbm_classification_graph(
        num_nodes=cfg.num_nodes,
        num_classes=cfg.num_classes,
        feat_dim=cfg.feat_dim,
        avg_degree=cfg.avg_degree,
    )


def main(cfg: Config):
    import jax
    import optax

    from dgraph_tpu.comm import Communicator, make_graph_mesh
    from dgraph_tpu.comm.mesh import put_on_graph_axis
    from dgraph_tpu.data import DistributedGraph
    from dgraph_tpu.models import GAT, GCN, GraphSAGE, GraphTransformer
    from dgraph_tpu.train.loop import (
        init_opt_state,
        init_params,
        make_eval_step,
        make_train_step,
        masked_bce_multilabel,
        masked_cross_entropy,
        vmask_batch_args,
    )
    from dgraph_tpu.obs import plan_footprint, startup_record
    from dgraph_tpu.obs.metrics import step_record
    from dgraph_tpu.obs import spans
    from dgraph_tpu.utils.compile_cache import compile_totals
    from dgraph_tpu.utils import ExperimentLog

    world = cfg.world_size or len(jax.devices())
    mesh = make_graph_mesh(ranks_per_graph=world)
    comm = Communicator.init_process_group("tpu", world_size=world)
    log = ExperimentLog(cfg.log_path)
    log.write(startup_record("experiments.ogb_gcn"))
    data = load_data(cfg.data)

    g = DistributedGraph.from_global(
        data["edge_index"],
        data["features"],
        data["labels"],
        data["masks"],
        world_size=world,
        partition_method=cfg.data.partition,
        add_symmetric_norm=cfg.model == "gcn",
    )
    # static comm accounting BEFORE any device step: what will this plan
    # move per halo exchange, and how imbalanced is it?
    log.write({
        "kind": "plan_footprint",
        **plan_footprint(g.plan, feat_dim=int(data["features"].shape[1])),
    })

    C = data["num_classes"]
    if cfg.model == "gcn":
        model = GCN(cfg.hidden, C, comm=comm, num_layers=cfg.num_layers)
    elif cfg.model == "sage":
        model = GraphSAGE(cfg.hidden, C, comm=comm, num_layers=cfg.num_layers)
    elif cfg.model == "gat":
        model = GAT(cfg.hidden, C, comm=comm, num_layers=cfg.num_layers)
    elif cfg.model in ("gt", "graph_transformer"):
        model = GraphTransformer(cfg.hidden, C, comm=comm, num_layers=cfg.num_layers)
    else:
        raise SystemExit(f"unknown model {cfg.model}")
    bargs = vmask_batch_args if cfg.model in ("gt", "graph_transformer") else None

    plan = put_on_graph_axis(g.plan, mesh)

    def _batch(split):
        return put_on_graph_axis(
            dict(g.batch(split), y=g.labels, vmask=g.vertex_mask), mesh
        )

    batch_tr = _batch("train")
    batch_va = _batch("val")

    params = init_params(model, mesh, plan, batch_tr, batch_args=bargs)
    optimizer = optax.adam(cfg.lr)
    opt_state = init_opt_state(optimizer, params, mesh)
    loss_fn = (
        masked_bce_multilabel if np.asarray(g.labels).ndim > 2 else masked_cross_entropy
    )
    train_step = make_train_step(
        model, optimizer, mesh, plan, loss_fn=loss_fn, batch_args=bargs,
        step_metrics=cfg.step_metrics,
    )
    eval_step = make_eval_step(model, mesh, loss_fn=loss_fn, batch_args=bargs)

    epoch_times = []
    with jax.set_mesh(mesh):
        for epoch in range(cfg.epochs):
            t0 = time.perf_counter()
            params, opt_state, m = train_step(params, opt_state, batch_tr, plan)
            jax.block_until_ready(m["loss"])
            dt = (time.perf_counter() - t0) * 1000
            epoch_times.append(dt)
            rec = step_record(m, step=epoch, wall_ms=dt)
            rec["epoch"] = epoch  # legacy key, kept for plot scripts
            if epoch % 10 == 0 or epoch == cfg.epochs - 1:
                ev = eval_step(params, batch_va, plan)
                rec["val_acc"] = float(ev["accuracy"])
                rec["val_loss"] = float(ev["loss"])
            # one structured record per step — the obs metrics pipeline
            log.write(rec)
    # final held-out accuracy (the reference reports test accuracy for the
    # OGB runs; ~72% is the public GCN bar on real ogbn-arxiv)
    if "test" in g.masks:
        batch_te = _batch("test")
        with jax.set_mesh(mesh):
            te = eval_step(params, batch_te, plan)
        log.write({"test_acc": float(te["accuracy"]), "test_loss": float(te["loss"])})
    # avg excluding first (compile) epoch — the reference's convention
    # (experiments/OGB/main.py:129-221)
    log.write(
        {
            "avg_epoch_ms_excl_first": round(float(np.mean(epoch_times[1:])), 2),
            "stages": spans.stage_totals(),
            "compiles": compile_totals(),
        }
    )


if __name__ == "__main__":
    import os as _os, sys as _sys

    # direct-invocation support (repo not pip-installed): put the repo
    # root on sys.path so `python experiments/<script>.py` works
    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
