"""Two-phase configuration search.

Phase 1 (**analytic**, always): every candidate's plan is built host-side
and priced with :func:`dgraph_tpu.obs.footprint.plan_footprint`'s
byte/imbalance/roofline model — per-layer wire and HBM-stream time at the
workload's feature width and dtype. The padded-static-shape design makes
this honest: every shard executes ``e_pad`` edge slots whether they are
real or padding, so a skewed partition's cost shows up directly as a
bigger ``e_pad``, and hub-driven ``s_pad`` inflation as a bigger exchange
operand. No device is touched.

Phase 2 (**measured**, when ``budget_s > 0``): only the top-K analytic
survivors are timed, with the compile-inside-scan protocol ``bench.py``
uses (run n steps inside one jit, delta two scan lengths — per-call RPC
latency cancels). Non-finite timings are dropped before ranking — the
same NaN guard :mod:`dgraph_tpu.tune.adopt` applies to sweep rows (a
crashed compile must not be crowned winner because ``x < nan`` is always
False).

The result is a :class:`~dgraph_tpu.tune.record.TuningRecord`; every
candidate evaluation emits one ``kind="tune_trace"`` JSONL row through the
caller's :class:`~dgraph_tpu.utils.logging.ExperimentLog` and ticks the
:mod:`dgraph_tpu.obs.metrics` registry.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Optional, Sequence

import numpy as np

from dgraph_tpu.obs.footprint import (
    V5E_ICI_GBPS,
    V5E_PEAK_HBM_GBPS,
    dtype_bytes,
    plan_footprint,
)
from dgraph_tpu.plan import HALO_IMPLS
from dgraph_tpu.tune.record import TuningRecord
from dgraph_tpu.tune.signature import graph_signature
from dgraph_tpu.tune.space import (
    default_candidate,
    ladder_candidates,
    plan_candidates,
)

_logger = logging.getLogger("dgraph_tpu.tune")

# per-collective launch overhead (us) charged when choosing the halo
# lowering: a2a pays it once, ppermute pays it per live delta — this is
# what keeps "W-1 rounds of ppermute" from beating one all_to_all on
# dense peer sets purely on wire bytes
LAUNCH_US = 2.0

# serve-ladder proxy constants: one bucket == one AOT warmup compile
# (~seconds), amortized over a nominal request volume; padding waste costs
# a fraction of a nominal infer. Proxies, not measurements — the ladder
# choice only needs the *ordering* to be sane (few huge buckets vs many
# tiny ones), and both endpoints are dominated by these terms.
LADDER_COMPILE_US_PER_BUCKET = 300.0  # 3 s compile / 10k requests
LADDER_INFER_US = 1000.0


@dataclasses.dataclass
class SearchResult:
    record: TuningRecord
    trace: list
    ranked: list  # [(candidate_key, analytic_total_us)] best-first
    measured: dict  # candidate_key -> ms (finite only)


def candidate_cost(
    plan,
    *,
    feat_dim: int,
    dtype,
    ici_gbps: float = V5E_ICI_GBPS,
    hbm_gbps: float = V5E_PEAK_HBM_GBPS,
) -> dict:
    """Analytic per-layer cost (us) of one plan at one feature width,
    derived from the footprint report: the better of the two halo
    lowerings (wire + launch + exchange HBM streams, x2 for the gather
    and scatter legs) plus the padded local edge/vertex streams (the
    6-stream-per-layer accounting bench.py's roofline uses)."""
    fp = plan_footprint(plan, dtype, feat_dim, ici_gbps=ici_gbps, hbm_gbps=hbm_gbps)
    W, S = plan.world_size, plan.halo.s_pad
    row = feat_dim * dtype_bytes(dtype)
    n_d = fp["num_halo_deltas"]
    wire = fp["halo"]["wire_bytes_per_shard"]
    split = fp["edge_split"]

    def exch_bound(impl: str) -> float:
        sent_blocks = {"all_to_all": W, "ppermute": n_d}.get(impl, 0)
        launches = {"all_to_all": 1, "ppermute": n_d}.get(impl, 0)
        wire_us = wire.get(impl, 0) / (ici_gbps * 1e3) + launches * LAUNCH_US
        hbm_us = (2 * sent_blocks + W) * S * row / (hbm_gbps * 1e3)
        return max(wire_us, hbm_us)

    # the overlap lowering moves ppermute's boundary-only rounds but hides
    # them behind the interior-edge aggregation (3 HBM streams of interior
    # rows per exchange leg — the per-leg half of the 6-stream local
    # model), so its EXPOSED exchange cost is what serial rounds cost
    # minus what the interior work can absorb
    int_rows_max = max(split["interior_per_shard"] or [0])
    interior_leg_us = 3 * int_rows_max * row / (hbm_gbps * 1e3)
    overlap_exposed = 0.0
    if n_d:
        pp_us = exch_bound("ppermute")
        overlap_exposed = max(pp_us - interior_leg_us, 0.0)

    if n_d == 0:
        impl, exch_us = "none", 0.0
    else:
        bounds = {
            "all_to_all": exch_bound("all_to_all"),
            "ppermute": exch_bound("ppermute"),
            "overlap": overlap_exposed,
        }
        # stable tie-break preserving the pre-overlap semantics: ppermute
        # beats all_to_all on equal cost (as before), overlap — equal to
        # ppermute exactly when there is no interior work to hide behind
        # — only wins when it actually hides something. plan.HALO_IMPLS
        # with ppermute moved to the front IS that order.
        order = ("ppermute",) + tuple(k for k in HALO_IMPLS if k != "ppermute")
        impl = min(
            (k for k in order if k in bounds),
            key=lambda k: (bounds[k], order.index(k)),
        )
        exch_us = bounds[impl]

    # wire-format ranking (dgraph_tpu.wire): the codec changes only the
    # WIRE leg of the chosen lowering — decode accumulates at the
    # activation dtype, so HBM streams, launches and local work are
    # format-invariant. Re-price the winner's exchange bound with each
    # registered format's row width and keep the min; the ordering
    # tie-break prefers the less lossy format (fp32 first), so a lossy
    # codec never engages without STRICTLY beating the lossless wire —
    # e.g. an HBM-bound exchange ties every format and fp32 stands.
    from dgraph_tpu.wire.spec import (
        WIRE_FORMAT_NAMES,
        fp8_available,
        get_format,
    )

    exch_rep = fp["collectives"]["halo_exchange"]
    res_row = exch_rep["wire_row_bytes"]
    wire_rank: dict = {}
    wf_winner = "fp32"
    wire_operand_bytes = 0
    if n_d and res_row:
        launches_by = {"all_to_all": 1, "ppermute": n_d, "overlap": n_d}
        sent_by = {"all_to_all": W, "ppermute": n_d, "overlap": n_d}

        def _bound_at_wire_scale(scale: float) -> float:
            wire_us = (
                wire.get(impl, 0) * scale / (ici_gbps * 1e3)
                + launches_by[impl] * LAUNCH_US
            )
            hbm_us = (2 * sent_by[impl] + W) * S * row / (hbm_gbps * 1e3)
            bound = max(wire_us, hbm_us)
            if impl == "overlap":
                bound = max(bound - interior_leg_us, 0.0)
            return bound

        names = [
            n for n in WIRE_FORMAT_NAMES
            if n != "fp8" or fp8_available()
        ]
        b_act = dtype_bytes(dtype)
        for name in names:
            row_f = get_format(name).wire_row_bytes(feat_dim, b_act)
            wire_rank[name] = round(_bound_at_wire_scale(row_f / res_row), 3)
        wf_winner = min(
            names, key=lambda n: (wire_rank[n], names.index(n))
        )
        # byte-exact operand figure at the winner's width: the resolved
        # operand is rows * res_row, so recover rows first (exact) and
        # re-multiply — the wire_compile ledger gate is zero-tolerance
        rows = exch_rep["operand_bytes_per_shard"] // res_row
        wire_operand_bytes = rows * get_format(wf_winner).wire_row_bytes(
            feat_dim, b_act
        )

    local_us = 6 * (plan.e_pad + plan.n_dst_pad) * row / (hbm_gbps * 1e3)
    return {
        "total_us": round(2 * exch_us + local_us, 3),
        "exchange_us": round(exch_us, 3),
        "local_stream_us": round(local_us, 3),
        "halo_impl": impl,
        "e_pad": int(plan.e_pad),
        "s_pad": int(S),
        "num_halo_deltas": n_d,
        # overlap-knob pricing: both alternatives land in the trace so the
        # record's choice is auditable (overlap in {off, on} first-class)
        "overlap_exposed_us": round(overlap_exposed, 3),
        # wire-format ranking: every priced alternative lands in the
        # trace (auditable); the winner is what the record adopts
        "wire_format": wf_winner,
        "wire_formats_us": wire_rank,
        "wire_operand_bytes": int(wire_operand_bytes),
        "wire_compression_ratio": round(
            get_format(wf_winner).compression_ratio(
                feat_dim, dtype_bytes(dtype)
            ), 4,
        ),
        "interior_frac": split["interior_frac"],
        "boundary_frac": split["boundary_frac"],
        "wire_efficiency": fp["collectives"]["halo_exchange"]["wire_efficiency"],
        "edge_imbalance": fp["imbalance"]["edges"]["max_over_mean"],
    }


def ladder_cost(sizes: Sequence[int], max_request: int) -> float:
    """Proxy cost (us/request) of one bucket ladder under a uniform
    request-size distribution on [1, max_request]: amortized warmup
    compiles + relative padding waste."""
    import bisect

    sizes = sorted(sizes)
    n = np.arange(1, max_request + 1, dtype=np.float64)
    buckets = np.asarray(
        [sizes[bisect.bisect_left(sizes, int(v))] for v in n], np.float64
    )
    waste = float((buckets - n).sum() / n.sum())
    return len(sizes) * LADDER_COMPILE_US_PER_BUCKET + waste * LADDER_INFER_US


def choose_ladder(max_request: int) -> dict:
    """Best (min_bucket, growth) geometry for the workload's request
    ceiling; returns the BucketLadder.geometric kwargs plus its cost."""
    from dgraph_tpu.serve.bucketing import BucketLadder

    best = None
    for min_bucket, growth in ladder_candidates():
        mb = min(min_bucket, max_request)
        sizes = BucketLadder.geometric(mb, max(max_request, mb), growth).sizes
        cost = ladder_cost(sizes, max_request)
        if best is None or cost < best["cost_us"]:
            best = {
                "min_bucket": int(mb),
                "max_bucket": int(max(max_request, mb)),
                "growth": float(growth),
                "num_buckets": len(sizes),
                "cost_us": round(cost, 3),
            }
    return best


def _pallas_config(dtype, feat_dim: int, sweep_log: str) -> dict:
    """Scatter/tile choices from the on-chip sweep log, when one exists.
    The analytic model cannot rank Pallas against XLA (same bytes, different
    schedulers), so this dimension only ever comes from measurement. When
    the log holds verdicts at several feature widths, the one measured
    closest to this workload's ``feat_dim`` decides — a verdict from a
    4x-wider sweep can invert at narrow rows."""
    from dgraph_tpu.tune import adopt
    from dgraph_tpu.tune.signature import canonical_dtype

    report = adopt.sweep_report(sweep_log) if sweep_log else None
    if report is None:
        return {}
    out = {}
    short = {"bfloat16": "bf16", "float32": "f32"}.get(
        canonical_dtype(dtype), canonical_dtype(dtype)
    )
    scatter = [
        v for v in report["verdicts"]
        if v["flag"] == "use_pallas_scatter"
        and v["dtype"] in (short, canonical_dtype(dtype))
    ]
    if scatter:
        best = min(scatter, key=lambda v: abs((v["F"] or 0) - feat_dim))
        out["use_pallas_scatter"] = best["verdict"] == "PALLAS"
    if report["consensus"] is not None:
        be, bn = report["consensus"]
        out["scatter_block_e"] = int(be)
        out["scatter_block_n"] = int(bn)
    return out


def search(
    edge_index: np.ndarray,
    num_nodes: int,
    world_size: int,
    *,
    feat_dim: int = 128,
    dtype="float32",
    budget_s: float = 0.0,
    top_k: int = 3,
    methods: Optional[Sequence[str]] = None,
    pad_multiples: Optional[Sequence[int]] = None,
    measure_fn: Optional[Callable] = None,
    max_request: int = 1024,
    seed: int = 0,
    sweep_log: str = "logs/kernel_benchmarks.jsonl",
    log=None,
    registry=None,
) -> SearchResult:
    """Run the two-phase search and return the winning record.

    Args:
      edge_index: [2, E] global edges (any numbering — partitioning
        renumbers internally per candidate).
      budget_s: measured-phase wall budget in seconds; 0 = analytic only.
      measure_fn: ``(plan, feat_dim=..., dtype=..., seed=...) -> ms``;
        defaults to :func:`dgraph_tpu.tune.measure.measure_plan_ms` (only
        consulted when ``budget_s > 0``). Non-finite returns are dropped.
      log: an :class:`~dgraph_tpu.utils.logging.ExperimentLog` for the
        JSONL search trace (optional).
      registry: an :class:`~dgraph_tpu.obs.metrics.Metrics`; defaults to
        the obs default registry.
    """
    from dgraph_tpu import partition as pt
    from dgraph_tpu.plan import build_edge_plan
    from dgraph_tpu.obs.metrics import default_registry

    t_start = time.perf_counter()
    reg = registry if registry is not None else default_registry
    edge_index = np.asarray(edge_index)
    sig = graph_signature(
        edge_index, num_nodes, world_size, dtype=dtype, feat_dim=feat_dim
    )
    trace: list = []

    def emit(**row):
        rec = {"kind": "tune_trace", **row}
        trace.append(rec)
        if log is not None:
            log.write(rec)

    cands = plan_candidates(world_size, methods, pad_multiples)
    default = default_candidate(world_size)
    if default not in cands:
        # a restricted space must still price the baseline the record's
        # cost claim is made against
        cands.append(default)

    partitions: dict = {}  # method -> (new_edges, ren)
    evaluated: list = []  # (Candidate, cost dict, plan)

    for cand in cands:
        t0 = time.perf_counter()
        try:
            if cand.partition_method not in partitions:
                partitions[cand.partition_method] = pt.partition_graph(
                    edge_index, num_nodes, world_size,
                    method=cand.partition_method, seed=seed,
                )
            new_edges, ren = partitions[cand.partition_method]
            plan, _layout = build_edge_plan(
                new_edges, ren.partition, world_size=world_size,
                pad_multiple=cand.pad_multiple,
            )
        except (ValueError, ImportError) as e:
            # an un-lowerable knob combination (build_edge_plan's early
            # rejection) or a missing optional dep is a pruned branch of
            # the space, not a search failure
            emit(phase="analytic", candidate=cand.key, error=str(e))
            reg.counter("tune.candidates_rejected")
            continue
        cost = candidate_cost(plan, feat_dim=feat_dim, dtype=dtype)
        build_s = round(time.perf_counter() - t0, 3)
        emit(
            phase="analytic", candidate=cand.key,
            partition_method=cand.partition_method,
            pad_multiple=cand.pad_multiple, build_s=build_s, **cost,
        )
        reg.counter("tune.candidates_analytic")
        reg.histogram("tune.candidate_build_s", build_s)
        evaluated.append((cand, cost, plan))

    if not evaluated:
        raise ValueError(
            "tuning search evaluated zero candidates; every combination was "
            "rejected — check the methods/pad_multiples restrictions"
        )

    # default-first tie-break: equal-cost exotic candidates must not
    # displace the known-good baseline
    evaluated.sort(
        key=lambda r: (r[1]["total_us"], r[0] != default, r[0].key)
    )
    default_cost = next((c for cd, c, _ in evaluated if cd == default), None)
    if default_cost is None:
        # the default itself was rejected (e.g. rcm without scipy): the
        # winner stands in as the baseline so the record's cost claim
        # stays well-formed, and the trace says why
        default_cost = evaluated[0][1]
        emit(phase="analytic", candidate=default.key,
             note="default candidate rejected; winner used as baseline")

    # plans are dead weight after pricing except for the measured top-K:
    # at arxiv scale each one holds multi-MB index arrays, so drop the rest
    # before the measured phase instead of holding the whole space live
    keep_plans = top_k if budget_s > 0 else 0
    evaluated = [
        (cd, c, p if i < keep_plans else None)
        for i, (cd, c, p) in enumerate(evaluated)
    ]

    measured: dict = {}
    phase = "analytic"
    winner_cand, winner_cost, _winner_plan = evaluated[0]
    if budget_s > 0:
        if measure_fn is None:
            from dgraph_tpu.tune.measure import measure_plan_ms

            measure_fn = measure_plan_ms
        # the budget buys MEASUREMENT time: the clock starts here, not at
        # the top of the search — an expensive analytic phase must not
        # silently starve the phase the caller explicitly paid for
        deadline = time.perf_counter() + budget_s
        for cand, cost, plan in evaluated[:top_k]:
            if time.perf_counter() >= deadline:
                emit(phase="measured", candidate=cand.key,
                     skipped="budget_exhausted")
                break
            t0 = time.perf_counter()
            try:
                ms = float(
                    measure_fn(plan, feat_dim=feat_dim, dtype=dtype, seed=seed)
                )
            except Exception as e:  # noqa: BLE001 — one broken candidate
                # must not abort the phase
                emit(phase="measured", candidate=cand.key,
                     error=f"{type(e).__name__}: {e}")
                continue
            emit(
                phase="measured", candidate=cand.key, ms=ms,
                measure_s=round(time.perf_counter() - t0, 3),
            )
            reg.histogram("tune.measure_ms", ms)
            if ms == ms:  # NaN guard (see tune.adopt)
                measured[cand.key] = ms
        if measured:
            phase = "measured"
            winner_key = min(measured, key=measured.get)
            winner_cand, winner_cost, _winner_plan = next(
                r for r in evaluated if r[0].key == winner_key
            )

    config = {
        "partition_method": winner_cand.partition_method,
        "pad_multiple": int(winner_cand.pad_multiple),
        "edge_owner": "dst",
        "halo_impl": winner_cost["halo_impl"],
        "wire_format": winner_cost.get("wire_format", "fp32"),
        "serve": choose_ladder(min(max_request, num_nodes)),
    }
    config.update(_pallas_config(dtype, feat_dim, sweep_log))

    cost = {
        "winner_us": winner_cost["total_us"],
        "default_us": default_cost["total_us"],
        "unit": "analytic_us_per_layer",
        "candidates_evaluated": len(evaluated),
        "search_wall_s": round(time.perf_counter() - t_start, 3),
    }
    if winner_cand.key in measured:
        cost["measured_ms"] = round(measured[winner_cand.key], 4)
    record = TuningRecord.create(sig, config, cost, phase)
    emit(
        phase="result", record_id=record.record_id, winner=winner_cand.key,
        **cost,
    )
    if winner_cost.get("wire_operand_bytes"):
        # the winner's wire format joins the perf ledger: operand_bytes
        # lands in regress's byte-exact class, so a codec or pricing
        # change that alters what this workload ships on the wire goes
        # RED across commits (off unless DGRAPH_LEDGER_DIR set;
        # maybe_ingest swallows every failure)
        from dgraph_tpu.obs.ledger import maybe_ingest

        maybe_ingest(
            {
                "kind": "wire_compile",
                "workload": {
                    "world_size": world_size, "nodes": num_nodes,
                    "edges": int(edge_index.shape[1]),
                    "feat_dim": feat_dim,
                },
                "wire_format": winner_cost["wire_format"],
                "wire_format_source": "tune",
                "operand_bytes": winner_cost["wire_operand_bytes"],
                "compression_ratio": winner_cost["wire_compression_ratio"],
            },
            source="tune.search", default_on=False,
        )
    _logger.info(
        "tuning search done: winner=%s (%s us/layer vs default %s), phase=%s",
        winner_cand.key, winner_cost["total_us"], default_cost["total_us"],
        phase,
    )
    return SearchResult(
        record=record,
        trace=trace,
        ranked=[(cd.key, c["total_us"]) for cd, c, _ in evaluated],
        measured=measured,
    )
