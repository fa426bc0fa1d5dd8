"""Static comm-traffic accounting for :class:`~dgraph_tpu.plan.EdgePlan`.

The plan is fully static, so every byte a training step will move over ICI
— halo send/recv per shard, all_to_all operand volume, the gradient-sync
psum — is computable on the host before any device work, the way "The Big
Send-off" / array-redistribution work (PAPERS.md) plans collectives from
traffic tables. :func:`plan_footprint` walks a plan (plus feature width and
dtype) and reports:

- per-collective bytes: the useful (masked) halo payload, the padded
  operand each lowering actually carries (``all_to_all`` moves all
  ``W*S_pad`` rows per shard, live or not; ppermute rounds move
  ``len(halo_deltas)*S_pad``), and the remote (cross-chip) fraction;
- per-shard send/recv row counts and max/mean imbalance — the number that
  says whether one hub-heavy shard serializes the exchange;
- an analytic roofline: time lower bounds for the ICI wire and the HBM
  streams each collective implies, and which resource binds.

Byte conventions (pinned by tests/test_obs.py against the lowered HLO):

- ``operand_bytes_per_shard`` is the size of the array handed to the
  collective on ONE shard — what a Perfetto trace or HLO dump shows.
- ``ici_bytes_per_shard`` counts only rows that leave the chip: the
  all_to_all self-block stays local, so it is ``(W-1)/W`` of the operand;
  every ppermute round is fully remote.
- "real"/"useful" bytes count mask-live rows only (padding excluded).

CLI::

    python -m dgraph_tpu.obs.footprint --nodes 4096 --edges 16384 --world 8
    python -m dgraph_tpu.obs.footprint --arxiv          # the bench shape

prints the same report as JSON.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np

# v5e chip ceilings (bench.py uses the same HBM number). ICI: aggregate
# per-chip interconnect bandwidth; one direction of the 4-link torus is
# half, but collectives drive links bidirectionally, so the aggregate is
# the roofline's optimistic bound.
V5E_PEAK_HBM_GBPS = 819.0
V5E_ICI_GBPS = 200.0


def dtype_bytes(dtype) -> int:
    """Itemsize for numpy dtypes, jax dtypes, and the bf16 family names
    numpy doesn't know. (Canonical implementation lives in
    :func:`dgraph_tpu.plan.dtype_nbytes` — the base layer — so plan-side
    byte accounting never imports upward into obs.)"""
    from dgraph_tpu.plan import dtype_nbytes

    return dtype_nbytes(dtype)


def _imbalance(per_shard: np.ndarray) -> dict:
    per_shard = np.asarray(per_shard, dtype=np.float64)
    mean = float(per_shard.mean()) if per_shard.size else 0.0
    return {
        "max": float(per_shard.max(initial=0.0)),
        "mean": mean,
        "max_over_mean": float(per_shard.max(initial=0.0) / mean) if mean else 1.0,
    }


def plan_footprint(
    plan,
    dtype="float32",
    feat_dim: int = 128,
    *,
    param_count: int = 0,
    ici_gbps: float = V5E_ICI_GBPS,
    hbm_gbps: float = V5E_PEAK_HBM_GBPS,
) -> dict:
    """Static byte/imbalance/roofline report for one plan at one feature
    width. Pure host numpy — never touches a device. JSON-serializable.

    Args:
      plan: an :class:`~dgraph_tpu.plan.EdgePlan` (numpy or device leaves).
      dtype: activation dtype of the exchanged features.
      feat_dim: feature width F the exchange will run at.
      param_count: when > 0, also accounts the per-step gradient-sync psum
        (ring all-reduce volume) at f32.
    """
    from dgraph_tpu.plan import (
        interior_boundary_edge_counts,
        plan_memory_usage,
        resolve_halo_impl,
    )

    from dgraph_tpu.wire.spec import get_format, resolve_wire_format

    W, S = plan.world_size, plan.halo.s_pad
    b = dtype_bytes(dtype)
    F = int(feat_dim)
    row_bytes = F * b
    # wire rows are priced at the RESOLVED codec's encoded width (the
    # same ladder the runtime walks: env pin > tuned record > plan-
    # attached > fp32 identity); HBM-side quantities stay at the
    # activation row_bytes — only the collective operand is encoded.
    # With the fp32 identity wire_row_bytes == row_bytes and every
    # number below reproduces the pre-codec report exactly.
    wf_name, wf_source = resolve_wire_format(
        W, tuple(plan.halo_deltas),
        plan_format=getattr(plan, "wire_format", "fp32"),
    )
    wire_fmt = get_format(wf_name)
    wire_row_bytes = wire_fmt.wire_row_bytes(F, b)

    send_mask = np.asarray(plan.halo.send_mask) > 0  # [W, W, S]
    real_counts = send_mask.sum(axis=2).astype(np.int64)  # [sender, needer]
    send_rows = real_counts.sum(axis=1)  # [W]
    recv_rows = real_counts.sum(axis=0)  # [W]
    real_rows = int(real_counts.sum())
    n_deltas = len(plan.halo_deltas)
    # mirror the runtime's lowering choice (comm.collectives.
    # resolve_plan_impl): env pin > adopted tuning record > heuristic —
    # the report must account the lowering the run actually executes,
    # whoever chose it (incl. 'overlap' when the plan carries its split)
    overlap_available = getattr(plan, "overlap", None) is not None
    impl, impl_source = resolve_halo_impl(
        W, plan.halo_deltas, overlap_available=overlap_available,
        pair_rows=getattr(plan, "halo_pair_rows", ()),
    )
    edge_split = interior_boundary_edge_counts(plan)

    # one halo_exchange (the gather's comm leg); halo_scatter_sum (the
    # scatter's reverse leg / the exchange's transpose) moves the same.
    a2a_operand = W * S * wire_row_bytes  # [W, S, F_wire] per shard
    a2a_ici = (W - 1) * S * wire_row_bytes  # self block never leaves chip
    pp_operand = n_deltas * S * wire_row_bytes  # one [S, F_wire] per delta
    # the overlap lowering sends the same boundary-only round payloads as
    # ppermute — its win is SCHEDULING (exposed time), not wire bytes.
    wire_per_shard = {
        "all_to_all": a2a_ici, "ppermute": pp_operand, "overlap": pp_operand,
    }
    chosen_wire = wire_per_shard.get(impl, 0)
    real_bytes = real_rows * wire_row_bytes
    # analytic-min HBM streams per shard per exchange, LOWERING-AWARE:
    # the [W*S, F] halo output buffer is written either way, but only the
    # blocks the chosen lowering actually sends are gathered and read
    # (all_to_all pads every peer; ppermute/overlap touch live deltas
    # only; 'none' never gathers a send buffer at all).
    sent_blocks = {
        "all_to_all": W, "ppermute": n_deltas, "overlap": n_deltas,
    }.get(impl, 0)
    hbm_per_shard = (2 * sent_blocks + W) * S * row_bytes

    def _roofline(ici_bytes: float, hbm_bytes: float) -> dict:
        t_ici = ici_bytes / (ici_gbps * 1e3) if ici_gbps else 0.0  # us
        t_hbm = hbm_bytes / (hbm_gbps * 1e3) if hbm_gbps else 0.0
        return {
            "ici_us": round(t_ici, 3),
            "hbm_us": round(t_hbm, 3),
            "bound": "ici" if t_ici >= t_hbm else "hbm",
        }

    operand_by_impl = {
        "all_to_all": a2a_operand, "ppermute": pp_operand,
        "overlap": pp_operand,
    }
    exchange = {
        "impl": impl,
        "impl_source": impl_source,
        "wire_format": wf_name,
        "wire_format_source": wf_source,
        "wire_row_bytes": wire_row_bytes,
        "compression_ratio": round(wire_fmt.compression_ratio(F, b), 4),
        "operand_bytes_per_shard": operand_by_impl.get(impl, 0),
        "a2a_operand_bytes_per_shard": a2a_operand,
        "ici_bytes_per_shard": chosen_wire,
        "ici_bytes_total": chosen_wire * W,
        "real_bytes_total": real_bytes,
        # same ratio plan_efficiency reports as halo_wire_fill_* — derived
        # here from send_mask instead of layout.halo_counts because
        # footprint deliberately needs only the PLAN (cache-loaded plans
        # carry no EdgePlanLayout); equivalence is pinned by test_obs.py
        "wire_efficiency": round(real_bytes / (chosen_wire * W), 4)
        if chosen_wire
        else 1.0,
        "hbm_bytes_per_shard": hbm_per_shard,
        "roofline": _roofline(chosen_wire, hbm_per_shard),
    }
    if n_deltas:
        # overlapped-schedule pricing (arxiv 2112.01075 / 2504.18658
        # framing): the exchange runs as n_deltas boundary rounds with the
        # interior aggregation interleaved, so the EXPOSED cost per round
        # is max(round comm, its interior compute share), not their sum.
        # Interior compute is modeled as the 3 HBM streams of the
        # interior-edge rows one exchange leg drives (take write, read,
        # reduce write — the per-leg half of search.py's 6-stream model).
        int_rows_max = max(edge_split["interior_per_shard"] or [0])
        round_comm_us = (
            (S * wire_row_bytes) / (ici_gbps * 1e3) if ici_gbps else 0.0
        )
        interior_us = (
            3 * int_rows_max * row_bytes / (hbm_gbps * 1e3) if hbm_gbps else 0.0
        )
        per_round_int = interior_us / n_deltas
        exposed = n_deltas * max(round_comm_us, per_round_int)
        serial = n_deltas * round_comm_us + interior_us
        exchange["overlap"] = {
            "rounds": n_deltas,
            "round_comm_us": round(round_comm_us, 3),
            "interior_compute_us": round(interior_us, 3),
            "exposed_us": round(exposed, 3),
            "serial_us": round(serial, 3),
            "hidden_us": round(serial - exposed, 3),
        }
    psum = None
    if param_count:
        # ring all-reduce: each member sends 2*(W-1)/W of the payload
        # (reduce-scatter + all-gather), grads sync at f32
        grad_bytes = int(param_count) * 4
        per_shard = int(2 * grad_bytes * (W - 1) / max(W, 1))
        psum = {
            "param_count": int(param_count),
            "payload_bytes": grad_bytes,
            "ici_bytes_per_shard": per_shard,
            "ici_bytes_total": per_shard * W,
            "roofline": _roofline(per_shard, 2 * grad_bytes),
        }

    num_edges = np.asarray(plan.num_edges, dtype=np.int64)
    return {
        "world_size": W,
        "s_pad": int(S),
        "e_pad": int(plan.e_pad),
        "n_src_pad": int(plan.n_src_pad),
        "n_dst_pad": int(plan.n_dst_pad),
        "halo_side": plan.halo_side,
        "num_halo_deltas": n_deltas,
        "feat_dim": F,
        "dtype": getattr(dtype, "__name__", None) or str(dtype),
        "dtype_bytes": b,
        "halo": {
            "real_rows_total": real_rows,
            "real_bytes_total": real_bytes,
            "per_shard_send_rows": [int(v) for v in send_rows],
            "per_shard_recv_rows": [int(v) for v in recv_rows],
            "per_shard_send_bytes": [
                int(v) * wire_row_bytes for v in send_rows
            ],
            "per_shard_recv_bytes": [
                int(v) * wire_row_bytes for v in recv_rows
            ],
            "wire_bytes_per_shard": wire_per_shard,
            "active_peer_pairs": int((real_counts > 0).sum()),
        },
        "collectives": {
            "halo_exchange": exchange,
            # the scatter's remote leg is the exact transpose: same shapes
            "halo_scatter_sum": exchange,
            "psum_grad_sync": psum,
        },
        "imbalance": {
            "halo_send_rows": _imbalance(send_rows),
            "halo_recv_rows": _imbalance(recv_rows),
            "edges": _imbalance(num_edges),
        },
        "local_streams": {
            "edge_tensor_bytes": int(plan.e_pad) * row_bytes,
            "vertex_tensor_bytes": int(plan.n_src_pad) * row_bytes,
            "halo_buffer_bytes": W * S * row_bytes,
        },
        # interior/boundary live-edge split: the boundary fraction bounds
        # the collective payload, the interior fraction bounds how much
        # compute the overlap lowering can hide it behind
        "edge_split": edge_split,
        "overlap_available": overlap_available,
        # runtime-buffer accounting at the ACTUAL activation dtype (the
        # plan_memory_usage satellite: a bf16 run must not be billed f32)
        "plan_memory": plan_memory_usage(plan, F, dtype=dtype),
        "roofline_constants": {"ici_gbps": ici_gbps, "hbm_gbps": hbm_gbps},
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Config:
    """Static comm-footprint report for a (synthetic or cached) plan."""

    nodes: int = 4096
    edges: int = 16384  # directed edges before symmetrization
    symmetrize: bool = True
    arxiv: bool = False  # override nodes/edges with the bench's arxiv shape
    world: int = 8
    feat_dim: int = 128
    dtype: str = "float32"
    partition: str = "block"  # any dgraph_tpu.partition method
    pad_multiple: int = 128
    overlap: bool = False  # build the interior/boundary split and price
    # the overlapped schedule (False still follows an env/record pin)
    seed: int = 0
    param_count: int = 0  # >0: also account the grad-sync psum
    indent: int = 2  # 0 = one JSON line


def main(cfg: Config) -> dict:
    from dgraph_tpu import partition as pt
    from dgraph_tpu.plan import build_edge_plan

    from dgraph_tpu.data.synthetic import ARXIV_EDGES, ARXIV_NODES, random_edges

    if cfg.arxiv:
        cfg.nodes, cfg.edges = ARXIV_NODES, ARXIV_EDGES
    edge_index = random_edges(cfg.nodes, cfg.edges, cfg.seed, cfg.symmetrize)
    new_edges, ren = pt.partition_graph(
        edge_index, cfg.nodes, cfg.world, method=cfg.partition, seed=cfg.seed
    )
    plan, _ = build_edge_plan(
        new_edges, ren.partition, world_size=cfg.world,
        pad_multiple=cfg.pad_multiple, overlap=cfg.overlap or None,
    )
    report = plan_footprint(
        plan, cfg.dtype, cfg.feat_dim, param_count=cfg.param_count
    )
    print(json.dumps(report, indent=cfg.indent or None))
    return report


if __name__ == "__main__":
    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
