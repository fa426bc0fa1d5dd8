"""DistributedGraph: one vertex-partitioned graph, plan + sharded tensors.

Reference parity: ``DGraph/data/graph.py:24-268`` (DistributedGraph holding
features/edge_index/labels + rank maps with per-rank slicing accessors) and
``DGraph/data/preprocess.py`` (renumbering/edge sort). TPU-first: instead of
per-rank slicing accessors, everything is stored stacked ``[W, n_pad, ...]``
ready to place on the mesh with ``PartitionSpec('graph')``; masks replace the
reference's node-range arithmetic (``graph.py:224-259``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from dgraph_tpu import partition as pt
from dgraph_tpu.plan import (
    EdgePlan,
    EdgePlanLayout,
    shard_edge_data,
    shard_vertex_data,
)


@dataclasses.dataclass
class DistributedGraph:
    num_nodes: int
    num_edges: int
    world_size: int
    edge_index: np.ndarray  # [2, E] renumbered (contiguous per-rank blocks)
    ren: pt.Renumbering
    plan: EdgePlan
    layout: EdgePlanLayout
    features: np.ndarray  # [W, n_pad, F]
    # [W, n_pad] int32 class ids, or [W, n_pad, C] float32 multi-label
    # targets (ogbn-proteins); float inputs keep their dtype through
    # from_global for BCE losses
    labels: Optional[np.ndarray]
    masks: dict  # split name -> [W, n_pad] f32
    vertex_mask: np.ndarray  # [W, n_pad] f32: 1.0 for real vertices
    edge_weight: Optional[np.ndarray] = None  # [W, e_pad] f32
    # the adopted TuningRecord (dgraph_tpu.tune), or None when the
    # hard-coded defaults are in effect; serving/health artifacts read
    # tuning_record_id off this so perf numbers stay attributable
    tuning_record: Optional[object] = None

    @property
    def tuning_record_id(self) -> Optional[str]:
        return self.tuning_record.record_id if self.tuning_record else None

    @classmethod
    def from_global(
        cls,
        edge_index: np.ndarray,
        features: np.ndarray,
        labels: Optional[np.ndarray],
        masks: Optional[dict],
        world_size: int,
        *,
        partition_method: Optional[str] = None,
        edge_owner: str = "dst",
        add_symmetric_norm: bool = False,
        pad_multiple: Optional[int] = None,
        seed: int = 0,
        sample_frac: Optional[float] = None,
        edge_balance: Optional[float] = None,
        partition_kwargs: Optional[dict] = None,
        plan_cache_dir: str = "",
        tune: str = "auto",
    ) -> "DistributedGraph":
        """Partition + plan + shard one global graph.

        ``partition_method`` / ``pad_multiple`` left at None resolve
        through the tuning layer: with ``tune="auto"`` (default) a
        persisted :class:`~dgraph_tpu.tune.record.TuningRecord` matching
        this graph's signature (in ``plan_cache_dir`` or the default
        record dir; env ``DGRAPH_TUNE_RECORD`` pins/disables) supplies
        them, else the hard-coded defaults (``"rcm"`` / ``8``) apply.
        Explicit values always win — adoption never overrides a caller's
        stated choice. ``tune="off"`` skips the lookup entirely.

        ``sample_frac`` / ``edge_balance`` are the
        ``method="multilevel_sampled"`` quality knobs (ADVICE r5: the
        measured-good p100m blend — 0.35 sample fraction + edge-balance
        vertex weights — was previously reachable only from
        ``scripts/p100m_r5_stages.py``), forwarded to
        :func:`~dgraph_tpu.partition.partition_graph` (which rejects
        them for other methods) and folded into the plan-cache key so a
        re-blended partition can never warm-hit a plan built under
        different knobs.
        """
        if tune not in ("auto", "off"):
            raise ValueError(f"tune must be 'auto' or 'off', got {tune!r}")
        from dgraph_tpu import chaos

        chaos.fire("data.load")  # the partition/plan/shard host boundary
        num_nodes = features.shape[0]
        edge_index = np.asarray(edge_index)
        from dgraph_tpu.tune.record import (
            adopt_record,
            clear_adoption,
            lookup_record,
        )

        record = None
        if tune == "auto" and (partition_method is None or pad_multiple is None):
            from dgraph_tpu import config as _cfg
            from dgraph_tpu.tune.signature import graph_signature

            # dtype axis of the signature = the COMPUTE dtype the run will
            # use (a bfloat16-tuned record is a different workload from a
            # float32 one), not the storage dtype of the features array —
            # from_global casts those to f32 regardless
            sig = graph_signature(
                edge_index, num_nodes, world_size,
                dtype=_cfg.default_compute_dtype,
                feat_dim=features.shape[1] if features.ndim > 1 else 0,
            )
            record = lookup_record(sig, cache_dir=plan_cache_dir)
            if record is not None:
                tuned = adopt_record(record)
                if partition_method is None:
                    partition_method = tuned.get("partition_method")
                if pad_multiple is None:
                    pad_multiple = tuned.get("pad_multiple")
        if record is None:
            # no record adopted for THIS graph — whether the lookup missed,
            # tune="off", or explicit knobs skipped it entirely: reset the
            # process-global tuned flags so an earlier graph's adopted halo
            # lowering cannot leak onto this one (most-recent-wins)
            clear_adoption()
        if partition_method is None:
            partition_method = "rcm"
        if pad_multiple is None:
            pad_multiple = 8
        part_kwargs = dict(partition_kwargs or {})
        # explicit first-class knobs win over a duplicate in
        # partition_kwargs (the pre-plumbing spelling)
        if sample_frac is not None:
            part_kwargs["sample_frac"] = sample_frac
        if edge_balance is not None:
            part_kwargs["edge_balance"] = edge_balance
        from dgraph_tpu.obs import spans

        # the three always-on set-up stages of this call (obs.spans.stage):
        # setup.partition here, setup.plan inside plan.build_edge_plan,
        # setup.shard below
        sizes = dict(num_nodes=int(num_nodes),
                     num_edges=int(edge_index.shape[1]),
                     world_size=world_size, method=partition_method)
        with spans.stage("setup.partition", **sizes):
            new_edges, ren = pt.partition_graph(
                edge_index, num_nodes, world_size, method=partition_method,
                seed=seed, **part_kwargs,
            )
        # the on-disk plan cache (train/checkpoint.cached_edge_plan) resolves
        # a falsy dir to a plain build, so this is the one call site either way
        from dgraph_tpu.train.checkpoint import cached_edge_plan

        # an adopted record whose halo lowering is 'overlap' needs the plan
        # to CARRY the interior/boundary split — pass the intent explicitly
        # so the plan-cache fingerprint distinguishes spec-ful plans (None
        # keeps the builder's env/record auto-resolution for everyone else)
        overlap = True if (
            record is not None and record.config.get("halo_impl") == "overlap"
        ) else None
        # partition knobs ride the cache key (key_extra folds into the
        # fingerprint without reaching the plan builder): the partition
        # CONTENT is hashed too, so this is belt-and-braces against two
        # blends that happen to collide — and it makes the artifact name
        # self-describing for cache forensics
        key_extra = {"partition_method": partition_method}
        for k, v in part_kwargs.items():
            key_extra[f"part_{k}"] = v
        plan, layout = cached_edge_plan(
            plan_cache_dir,
            new_edges,
            ren.partition,
            world_size=world_size,
            edge_owner=edge_owner,
            pad_multiple=pad_multiple,
            overlap=overlap,
            key_extra=key_extra,
        )
        with spans.stage("setup.shard", **sizes):
            n_pad = plan.n_src_pad
            feats = shard_vertex_data(
                np.asarray(features)[ren.inv], ren.counts, n_pad
            ).astype(np.float32)
            if labels is not None:
                lab_arr = np.asarray(labels)
                # integer class ids -> int32; float arrays (e.g. ogbn-proteins'
                # [V, 112] multi-label targets) keep float32 for BCE losses
                lab_dtype = (
                    np.float32 if np.issubdtype(lab_arr.dtype, np.floating) else np.int32
                )
                lab = shard_vertex_data(
                    lab_arr[ren.inv].astype(lab_dtype), ren.counts, n_pad
                )
            else:
                lab = None
            m = {}
            if masks:
                for k, v in masks.items():
                    m[k] = shard_vertex_data(
                        np.asarray(v).astype(np.float32)[ren.inv], ren.counts, n_pad
                    )
            vmask = shard_vertex_data(
                np.ones(num_nodes, np.float32), ren.counts, n_pad
            )
            ew = None
            if add_symmetric_norm:
                ew = shard_edge_data(
                    symmetric_norm_weights(new_edges, num_nodes), layout, plan.e_pad
                )
        return cls(
            num_nodes=num_nodes,
            num_edges=edge_index.shape[1],
            world_size=world_size,
            edge_index=new_edges,
            ren=ren,
            plan=plan,
            layout=layout,
            features=feats,
            labels=lab,
            masks=m,
            vertex_mask=vmask,
            edge_weight=ew,
            tuning_record=record,
        )

    def batch(self, split: str) -> dict:
        """Pytree for the train/eval step: leaves have leading [W] axis."""
        out = {
            "x": self.features,
            "mask": self.masks[split] if split in self.masks else self.vertex_mask,
        }
        if self.labels is not None:
            out["y"] = self.labels
        if self.edge_weight is not None:
            out["edge_weight"] = self.edge_weight
        return out


def symmetric_norm_weights(edge_index: np.ndarray, num_nodes: int) -> np.ndarray:
    """Kipf-Welling GCN normalization 1/sqrt(d_src * d_dst) per edge."""
    src, dst = edge_index
    deg = np.zeros(num_nodes, np.float64)
    np.add.at(deg, src, 1.0)
    np.add.at(deg, dst, 1.0)
    deg = np.maximum(deg, 1.0)
    return (1.0 / np.sqrt(deg[src] * deg[dst])).astype(np.float32)
