"""ogbn-papers100M-scale full-graph GCN (the reference's headline scale
target; BASELINE.md north star: papers100M epoch time on a v5p-32).

111M vertices / 1.6B edges don't fit one chip; the recipe here is the
framework's memory-scaling stack (SURVEY §7 step 9):
- vertices int32-renumbered, sharded over the full `graph` axis
- hash-keyed on-disk plan cache so the multi-hour plan build happens once
  (``train/checkpoint.cached_edge_plan``; reference pattern
  ``MAG240M_dataset.py:237-260``)
- remat (``jax.checkpoint``) on the conv layers to trade FLOPs for HBM
- bfloat16 compute

Data: ``--data_npz`` pointing at either a ``.npz`` archive (loaded eagerly)
or a DIRECTORY of ``edge_index.npy`` / ``features.npy`` / ``labels.npy`` /
``train_mask.npy`` files — the directory form is opened with
``np.load(..., mmap_mode="r")``, so shard materialization streams rows from
disk instead of first building a second in-RAM copy of the feature matrix.
Device placement streams per-device blocks (``shard_rows_to_device``), so
host residency during sharding is ONE device's ``[n_pad, F]`` block — the
stacked ``[W, n_pad, F]`` copy (~57 GB at real scale) never exists, and
multi-controller hosts materialize only their own devices' rows.
``--synthetic_scale`` gives a shape-matched power-law synthetic at a chosen
fraction of papers100M (use ``data/memmap.synthetic_papers_like`` +
``--data_npz <dir>`` to keep even the synthetic source on disk at large
fractions).

This script is single-controller; each run partitions and shards the full
graph host-side. For multi-controller pods,
``comm.multihost.process_local_shards`` picks which shards each host
should materialize.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional


@dataclasses.dataclass
class Config:
    """papers100M-scale full-graph GCN."""

    data_npz: Optional[str] = None
    synthetic_scale: float = 0.001  # fraction of papers100M (111M nodes)
    hidden: int = 256
    num_layers: int = 3
    lr: float = 1e-3
    epochs: int = 10
    world_size: int = 0
    bfloat16: bool = True
    remat: bool = True
    # partition/plan knobs — keep in lock-step with setup_comms.py (both
    # feed the plan-cache fingerprint; a mismatch silently misses the
    # offline-built cache and repeats the hours-long build)
    partition_method: str = "greedy_bfs"
    pad_multiple: int = 128
    plan_cache: str = "cache/plans"  # "" disables the on-disk plan cache
    log_path: str = "logs/papers100m.jsonl"
    # per-step obs records (grad-norm costs one global_norm at this scale,
    # so it is opt-in on the billion-edge path)
    step_metrics: bool = False
    # Build the partition + comm plan and stop (no features, no training).
    # The full-scale proof mode (VERDICT r1 #3): at synthetic_scale=1.0
    # (111M nodes / 1.6B edges) the features alone are 57 GB, but the plan
    # build is the scaling-critical artifact — this measures its wall time
    # and peak RSS the way the reference's offline per-rank plan precompute
    # would be measured (MAG240M_dataset.py:237-260).
    # NOTE: at synthetic_scale=1.0 prefer scripts/p100m_r5.sh — the
    # single-process flow stacks the edge list, sample, and plan
    # transients in one address space (OOM-killed at 130.7 GB on a 125 GB
    # host); the staged pipeline keeps each phase's peak standalone.
    plan_only: bool = False


def _peak_rss_gb() -> float:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


class _HostLog:
    """Append-JSONL writer that never touches JAX (ExperimentLog's
    is-lead check calls jax.process_index(), which initializes the
    accelerator backend — exactly what the offline plan-only flow must
    avoid: it runs where there may be no accelerator)."""

    def __init__(self, path: str):
        import os

        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self.path = path

    def write(self, rec: dict) -> None:
        import json

        with open(self.path, "a") as f:
            f.write(json.dumps(rec) + "\n")


def _plan_only(cfg: Config, world: int) -> None:
    """Partition + plan build only, with wall-time and peak-RSS telemetry.
    Memory discipline matters more than style here: references to the raw
    edge list are dropped as soon as the renumbered copy exists (each
    [2, E] int64 array is 26 GB at full papers100M scale)."""
    import gc

    import numpy as np

    log = _HostLog(cfg.log_path)
    from dgraph_tpu.obs import startup_record

    # snapshot_backend=False: this host-only flow must NEVER touch the
    # accelerator (an offline plan build needs none)
    log.write(startup_record(
        "experiments.papers100m_gcn.plan_only", snapshot_backend=False))

    from dgraph_tpu import partition as pt
    from dgraph_tpu.data.synthetic import power_law_graph
    from dgraph_tpu.plan import plan_memory_usage
    from dgraph_tpu.train.checkpoint import cached_edge_plan

    V = max(int(111_059_956 * cfg.synthetic_scale), 10_000)
    t0 = time.perf_counter()
    edge_index = power_law_graph(V, 14.5)
    t_gen = time.perf_counter() - t0
    E = int(edge_index.shape[1])
    log.write({"phase": "generate", "nodes": V, "edges": E,
               "wall_s": round(t_gen, 1), "peak_rss_gb": round(_peak_rss_gb(), 1)})

    t0 = time.perf_counter()
    new_edges, ren = pt.partition_graph(
        edge_index, V, world, method=cfg.partition_method
    )
    del edge_index
    gc.collect()
    t_part = time.perf_counter() - t0
    # directed edge cut on the renumbered list (native O(E) streaming count
    # when built; the VERDICT r4 #6 quality gate is cut <= 0.76)
    from dgraph_tpu import native as _native

    if _native.available():
        cut = _native.edge_cut_count(new_edges, ren.partition) / max(E, 1)
    else:
        cut = pt.edge_cut(new_edges, ren.partition)
    log.write({"phase": "partition", "method": cfg.partition_method,
               "wall_s": round(t_part, 1), "cut": round(float(cut), 4),
               "peak_rss_gb": round(_peak_rss_gb(), 1)})

    t0 = time.perf_counter()
    plan_np, layout = cached_edge_plan(
        cfg.plan_cache, new_edges, ren.partition, world_size=world,
        pad_multiple=cfg.pad_multiple,
    )
    t_plan = time.perf_counter() - t0
    mem = plan_memory_usage(plan_np, feature_dim=128)
    log.write({
        "phase": "plan_build", "wall_s": round(t_plan, 1),
        "peak_rss_gb": round(_peak_rss_gb(), 1),
        "e_pad": int(plan_np.e_pad), "s_pad": int(plan_np.halo.s_pad),
        "halo_pairs": int(layout.halo_counts.sum()),
        # unique (needer, vertex) pairs per edge — a DEDUPED halo-volume
        # measure (hub endpoints collapse), not the raw cross-edge fraction
        "halo_pair_fraction": round(
            float(layout.halo_counts.sum()) / max(E, 1), 4),
        "plan_bytes": {k: int(v) for k, v in mem.items()},
    })
    print(f"plan_only done: E={E} partition {t_part:.0f}s + plan {t_plan:.0f}s, "
          f"peak RSS {_peak_rss_gb():.1f} GB")


def main(cfg: Config):
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    from dgraph_tpu.comm import Communicator, make_graph_mesh
    from dgraph_tpu import partition as pt
    from dgraph_tpu.data import memmap as mm
    from dgraph_tpu.train.checkpoint import cached_edge_plan
    from dgraph_tpu.models import GCN
    from dgraph_tpu.train.loop import init_params, make_train_step
    from dgraph_tpu.obs import spans
    from dgraph_tpu.utils.compile_cache import compile_totals
    from dgraph_tpu.utils import ExperimentLog

    if cfg.plan_only:
        # host-only flow: never touch the accelerator backend (an offline
        # plan build needs none); world_size required
        if cfg.data_npz:
            raise SystemExit(
                "--plan_only works on the synthetic generator; for offline "
                "plan builds from real exports use experiments/setup_comms.py"
            )
        if not cfg.world_size:
            raise SystemExit("--plan_only requires an explicit --world_size")
        _plan_only(cfg, cfg.world_size)
        return

    from dgraph_tpu.obs import plan_footprint, startup_record
    from dgraph_tpu.obs.metrics import step_record

    world = cfg.world_size or len(jax.devices())
    mesh = make_graph_mesh(ranks_per_graph=world)
    comm = Communicator.init_process_group("tpu", world_size=world)
    log = ExperimentLog(cfg.log_path)
    log.write(startup_record("experiments.papers100m_gcn"))

    if cfg.data_npz:
        import os

        if os.path.isdir(cfg.data_npz):
            # directory of .npy files: true memmaps, nothing loaded eagerly
            z = mm.open_memmap_dataset(
                cfg.data_npz,
                names=("edge_index", "features", "labels", "train_mask"),
            )
        else:
            z = np.load(cfg.data_npz)  # .npz archive (eager)
        edge_index, feats = z["edge_index"], z["features"]
        labels = np.asarray(z["labels"]).squeeze()
        train_mask = z["train_mask"]
        # OGB papers100M labels are float with NaN on the ~98% unlabeled
        # nodes; map NaN -> class 0 (loss-masked by train_mask anyway).
        if np.issubdtype(labels.dtype, np.floating):
            labels = np.where(np.isnan(labels), 0, labels)
        labels = labels.astype(np.int64)
        C = int(labels.max()) + 1
    else:
        from dgraph_tpu.data.synthetic import power_law_graph

        V = max(int(111_059_956 * cfg.synthetic_scale), 10_000)
        F, C = 128, 172
        rng = np.random.default_rng(0)
        edge_index = power_law_graph(V, 14.5)  # papers100M avg degree ~14.5
        feats = rng.normal(size=(V, F)).astype(np.float32)
        labels = rng.integers(0, C, V).astype(np.int32)
        train_mask = rng.random(V) < 0.01
        log.write({"synthetic_nodes": V, "edges": int(edge_index.shape[1])})

    V = feats.shape[0]
    # the stage names DistributedGraph.from_global uses (this script streams
    # its features instead of calling it); setup.plan is inside the builder
    sizes = dict(num_nodes=int(V), num_edges=int(edge_index.shape[1]),
                 world_size=world, method=cfg.partition_method)
    with spans.stage("setup.partition", **sizes):
        new_edges, ren = pt.partition_graph(
            edge_index, V, world, method=cfg.partition_method
        )

    plan_np, layout = cached_edge_plan(
        cfg.plan_cache, new_edges, ren.partition, world_size=world,
        pad_multiple=cfg.pad_multiple,
    )
    n_pad = plan_np.n_src_pad
    # static comm accounting at the training dtype/width before sharding
    log.write({
        "kind": "plan_footprint",
        **plan_footprint(
            plan_np,
            "bfloat16" if cfg.bfloat16 else "float32",
            feat_dim=int(feats.shape[1]),
        ),
    })

    # blocks stream from the (possibly memmapped) source straight onto the
    # mesh, one device's rows at a time — neither feats[ren.inv] nor the
    # stacked [W, n_pad, F] copy ever exists host-side (~57 GB at real
    # papers100M scale); multi-controller hosts materialize only their own
    # devices' blocks
    with spans.stage("setup.shard", **sizes):
        x = mm.shard_rows_to_device(
            feats, ren.inv, ren.offsets, n_pad, mesh, dtype=np.float32
        )
        y = mm.shard_rows_to_device(
            labels, ren.inv, ren.offsets, n_pad, mesh, dtype=np.int32
        )
        m = mm.shard_rows_to_device(
            train_mask, ren.inv, ren.offsets, n_pad, mesh, dtype=np.float32
        )

    dtype = jnp.bfloat16 if cfg.bfloat16 else None
    if cfg.remat:
        import flax.linen as nn

        cls = nn.remat(GCN)
    else:
        cls = GCN
    model = cls(cfg.hidden, C, comm=comm, num_layers=cfg.num_layers, dtype=dtype)

    plan = jax.tree.map(jnp.asarray, plan_np)
    batch = {"x": x, "y": y, "mask": m}
    params = init_params(model, mesh, plan, batch)
    optimizer = optax.adam(cfg.lr)
    opt_state = optimizer.init(params)
    step = make_train_step(
        model, optimizer, mesh, plan, step_metrics=cfg.step_metrics
    )

    with jax.set_mesh(mesh):
        times = []
        for epoch in range(cfg.epochs):
            t0 = time.perf_counter()
            params, opt_state, metrics = step(params, opt_state, batch, plan)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            times.append(dt)
            rec = step_record(metrics, step=epoch, epoch_s=round(dt, 3))
            rec["epoch"] = epoch  # legacy key, kept for plot scripts
            log.write(rec)
    log.write(
        {
            "avg_epoch_s_excl_first": round(float(np.mean(times[1:])), 3) if len(times) > 1 else None,
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
