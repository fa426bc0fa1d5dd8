"""Generate docs/reference.md from the package's docstrings.

The reference's docs site drives this page from mkdocstrings
(``mkdocs.yml`` + ``docs/reference.md`` ``:::`` directives); this repo
can't install mkdocs plugins (no egress), so the same content is emitted
as plain markdown by introspection — rerun after API changes:

    python scripts/gen_api_docs.py
"""

from __future__ import annotations

import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# (section title, module path, names; None = module __all__ or public defs)
SECTIONS = [
    ("Communicator", "dgraph_tpu.comm.communicator",
     ["Communicator", "TpuComm", "SingleComm"]),
    ("Differentiable collectives", "dgraph_tpu.comm.collectives", None),
    ("Device mesh", "dgraph_tpu.comm.mesh", None),
    ("Multi-host launch", "dgraph_tpu.comm.multihost", None),
    ("Elastic world membership", "dgraph_tpu.comm.membership",
     ["Membership", "RankLost", "MembershipChanged", "Straggler",
      "RankLostError", "DeadlineExceeded", "read_roster",
      "RANK_LOST_EXIT_CODE", "Joiner", "JoinRequest", "RankJoinError",
      "grant_join", "read_joins", "RANK_JOIN_EXIT_CODE"]),
    ("Communication plans", "dgraph_tpu.plan",
     ["CommPattern", "EdgePlan", "OverlapSpec", "build_edge_plan",
      "build_comm_pattern", "compute_comm_map", "validate_plan",
      "plan_memory_usage", "interior_boundary_edge_counts",
      "pick_halo_impl", "resolve_halo_impl", "halo_wire_rows",
      "halo_sort_route", "halo_sort_sentinel"]),
    ("Sharded plan builds (cache format v8)", "dgraph_tpu.plan",
     ["build_plan_shards", "build_edge_plan_sharded", "load_sharded_plan",
      "assemble_plan", "shard_nbytes_estimate", "reshard_vertex_data"]),
    ("Wire formats: registry & resolution", "dgraph_tpu.wire.spec",
     ["WireFormat", "get_format", "fp8_available", "resolve_wire_format",
      "np_encode", "np_decode", "np_roundtrip_bound",
      "np_encode_compensated", "delta_skip_rows", "WIRE_FORMAT_NAMES",
      "FP8_SCALE_BYTES"]),
    ("Wire formats: jax codecs", "dgraph_tpu.wire.codec",
     ["make_wire_transform", "make_wire_codec", "make_a2a_codec",
      "make_ppermute_codec", "encode_compensated", "fp8_jnp_ok"]),
    ("Plan shard IO & integrity", "dgraph_tpu.plan_shards",
     ["PlanShardWriter", "PlanManifestError", "PlanShardError",
      "PlanBuildMemoryExceeded", "read_manifest", "write_manifest",
      "atomic_write_json", "read_shard", "write_shard", "bad_shards",
      "payload_nbytes", "resolve_memory_budget"]),
    ("Partitioning", "dgraph_tpu.partition", None),
    ("Rank-local ops", "dgraph_tpu.ops.local", None),
    ("Pallas kernels", "dgraph_tpu.ops.pallas_segment",
     ["sorted_segment_sum", "sorted_segment_sum_bias_relu",
      "sorted_row_gather", "max_chunks_hint", "max_vblocks_hint",
      "block_chunk_counts", "chunk_vblock_spans"]),
    ("Selective scan", "dgraph_tpu.ops.selective_scan",
     ["selective_scan", "scan_sequence"]),
    ("Selective scan kernels", "dgraph_tpu.ops.pallas_scan",
     ["applies", "channel_block", "vmem_bytes", "fused_forward",
      "fused_backward"]),
    ("Chunked matrix-state recurrence (Mamba-2)", "dgraph_tpu.ops.ssd",
     ["ssd", "ssd_sequence"]),
    ("Chunked recurrence kernels", "dgraph_tpu.ops.pallas_ssd",
     ["applies", "vmem_bytes", "fused_forward", "fused_backward"]),
    ("Models", "dgraph_tpu.models", None),
    ("Sequence-LM layers", "dgraph_tpu.models.looplm",
     ["HeldExperts", "HeldExpertsFFN", "StateSpace", "Mamba2Mixer",
      "LatentAttention", "SSDMixer", "split_kind", "causal_taps",
      "previous_rows", "apply_rotary_pairs"]),
    ("GraphCast", "dgraph_tpu.models.graphcast", None),
    ("Tensor parallelism", "dgraph_tpu.parallel.tensor", None),
    ("Pipeline parallelism", "dgraph_tpu.parallel.pipeline", None),
    ("Sequence/context parallelism", "dgraph_tpu.parallel.sequence", None),
    ("Expert parallelism (MoE)", "dgraph_tpu.parallel.expert", None),
    ("Data layer", "dgraph_tpu.data", None),
    ("Training utilities", "dgraph_tpu.train.loop", None),
    ("Elastic / failure handling", "dgraph_tpu.train.elastic", None),
    ("Train supervisor", "dgraph_tpu.train.supervise",
     ["supervise", "supervise_group"]),
    ("Shrink-to-fit recovery", "dgraph_tpu.train.shrink",
     ["init_world", "shrink_world", "read_world", "write_world",
      "ShrinkError"]),
    ("Grow-to-fit expansion", "dgraph_tpu.train.grow",
     ["grow_world", "grant_joined", "grow_record", "GrowError"]),
    ("Non-finite step guard", "dgraph_tpu.train.guard",
     ["NonFiniteMonitor", "NonFiniteAbort"]),
    ("Chaos fault injection", "dgraph_tpu.chaos",
     ["ChaosFault", "Clause", "parse_spec", "fire", "arm", "disarm",
      "active_spec", "poison_array", "poison_pytree"]),
    ("Checkpointing", "dgraph_tpu.train.checkpoint", None),
    ("Serving: engine", "dgraph_tpu.serve.engine", ["ServeEngine"]),
    ("Serving: shape bucketing", "dgraph_tpu.serve.bucketing",
     ["BucketLadder", "pad_ids"]),
    ("Serving: micro-batching", "dgraph_tpu.serve.batcher", ["MicroBatcher"]),
    ("Serving: errors & health", "dgraph_tpu.serve.errors",
     ["ServeError", "RequestTooLarge", "QueueFull", "RequestTimeout",
      "EngineStopped", "QuotaExceeded", "TenantDegraded", "SwapRejected"]),
    ("Serving: health record", "dgraph_tpu.serve.health",
     ["serve_health_record"]),
    ("Serving: hot-swap rollover", "dgraph_tpu.serve.rollover",
     ["swap_params", "params_mismatch", "nonfinite_param_leaves"]),
    ("Serving: model registry", "dgraph_tpu.serve.registry",
     ["ModelRegistry"]),
    ("Serving: tenant isolation", "dgraph_tpu.serve.tenancy",
     ["TenantTable", "TenantQuota", "TokenBucket", "DEFAULT_TENANT"]),
    ("Serving: live graph deltas", "dgraph_tpu.serve.deltas",
     ["init_world", "append_delta", "replan", "load_generation",
      "build_engine", "read_world", "write_world", "assign_new_vertices",
      "staged_delta_paths", "DeltaError"]),
    ("Device timing of single ops", "dgraph_tpu.utils.timing", None),
    ("Compile cache", "dgraph_tpu.utils.compile_cache",
     ["enable_compile_cache", "compile_totals"]),
    ("Observability: comm footprint", "dgraph_tpu.obs.footprint",
     ["plan_footprint", "dtype_bytes"]),
    ("Observability: step metrics", "dgraph_tpu.obs.metrics",
     ["StepMetrics", "Metrics", "step_record"]),
    ("Observability: run health", "dgraph_tpu.obs.health",
     ["RunHealth", "classify_wedge", "startup_record"]),
    ("Observability: span tracing", "dgraph_tpu.obs.spans",
     ["Tracer", "Span", "span", "stage", "stage_totals", "record_span",
      "enable", "disable", "enabled", "current_span", "current_trace_id", "child_env", "read_spans",
      "export_perfetto"]),
    ("Observability: step-time attribution", "dgraph_tpu.obs.attribution",
     ["scan_delta_attribution", "multichip_family_table"]),
    ("Observability: perf-trajectory ledger", "dgraph_tpu.obs.ledger",
     ["normalize_record", "ingest", "maybe_ingest", "read_ledger",
      "backfill", "resolve_ledger_dir", "atomic_append_jsonl",
      "ledger_path", "LEDGER_SCHEMA_VERSION",
      "SERVE_HEALTH_SCHEMA_VERSION"]),
    ("Observability: drift sentinel", "dgraph_tpu.obs.regress",
     ["check_ledger", "metric_class", "baseline_stats",
      "dropped_tier_verdicts"]),
    ("Observability: trajectory report", "dgraph_tpu.obs.report",
     ["render_trajectory", "sparkline"]),
    ("Autotuning: signatures", "dgraph_tpu.tune.signature",
     ["graph_signature", "signature_key", "degree_histogram"]),
    ("Autotuning: records & adoption", "dgraph_tpu.tune.record",
     ["TuningRecord", "lookup_record", "adopt_record",
      "default_record_dir"]),
    ("Autotuning: search", "dgraph_tpu.tune.search",
     ["search", "candidate_cost", "choose_ladder", "SearchResult"]),
    ("Autotuning: measured phase", "dgraph_tpu.tune.measure",
     ["measure_plan_ms"]),
    ("Autotuning: kernel-sweep winners", "dgraph_tpu.tune.adopt",
     ["pick_winners", "sweep_report"]),
    ("Static analysis: trace auditor", "dgraph_tpu.analysis.trace",
     ["walk_eqns", "collect_collectives", "build_audit_workload",
      "audit_workload", "donation_unmatched", "schedule_drift_record"]),
    ("Static analysis: lowered-artifact auditor", "dgraph_tpu.analysis.hlo",
     ["lower_program", "collect_stablehlo", "audit_workload_hlo",
      "donation_entries", "hlo_drift_record", "COLLECTIVE_HLO_OPS"]),
    ("Static analysis: cross-rank SPMD divergence auditor",
     "dgraph_tpu.analysis.spmd",
     ["build_spmd_fixture", "build_shrink_fixture", "build_rank_workload",
      "rank_live_deltas", "canonical_module_text",
      "canonicalize_rank_modules", "collective_sequence",
      "resolution_agreement", "audit_plan_dir_spmd", "spmd_drift_record",
      "spmd_selftest"]),
    ("Static analysis: host concurrency & durability auditor",
     "dgraph_tpu.analysis.host",
     ["scan_module", "class_concurrency_findings", "build_lock_graph",
      "lock_order_findings", "durable_write_findings",
      "pointer_flip_findings", "chaos_coverage_findings",
      "run_host_audit", "host_selftest_failures"]),
    ("Static analysis: contract linter", "dgraph_tpu.analysis.lint",
     ["Finding", "Rule", "rule", "path_matcher", "lint_file", "run_lint"]),
    ("Config & flags", "dgraph_tpu.config", None),
]


def public_names(mod):
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    out = []
    for n, obj in vars(mod).items():
        if n.startswith("_"):
            continue
        if inspect.isclass(obj) or inspect.isfunction(obj):
            if getattr(obj, "__module__", None) == mod.__name__:
                out.append(n)
    return out


def scrub_addresses(text):
    """Default-value reprs embed object addresses (flax's _Sentinel, jax
    PjitFunction, custom_jvp...) in signatures AND dataclass
    auto-docstrings — scrub them or regeneration is nondeterministic."""
    import re

    return re.sub(r" at 0x[0-9a-fA-F]+", "", text)


def fmt_signature(name, obj):
    try:
        sig = str(inspect.signature(obj))
    except (TypeError, ValueError):
        sig = "(...)"
    return scrub_addresses(f"{name}{sig}")


def emit_obj(lines, name, obj, depth):
    head = "#" * depth
    kind = "class" if inspect.isclass(obj) else "function"
    lines.append(f"{head} `{fmt_signature(name, obj)}`\n")
    doc = inspect.getdoc(obj)
    if doc:
        lines.append(scrub_addresses(doc) + "\n")
    else:
        lines.append(f"*(undocumented {kind})*\n")
    if inspect.isclass(obj):
        for mn, m in sorted(vars(obj).items()):
            if mn.startswith("_") or not callable(m):
                continue
            mdoc = inspect.getdoc(m)
            if not mdoc:
                continue
            first = mdoc.splitlines()[0]
            lines.append(f"- **`{fmt_signature(mn, m)}`** — {first}\n")


def main():
    import importlib

    lines = [
        "# API reference\n",
        "*Generated by `scripts/gen_api_docs.py` — do not edit by hand.*\n",
    ]
    for title, modpath, names in SECTIONS:
        mod = importlib.import_module(modpath)
        lines.append(f"## {title}\n")
        mod_doc = inspect.getdoc(mod)
        if mod_doc:
            # first paragraph of the module docstring as the section intro
            lines.append(mod_doc.split("\n\n")[0] + "\n")
        lines.append(f"*Module: `{modpath}`*\n")
        for name in names or public_names(mod):
            obj = getattr(mod, name, None)
            if obj is None:
                raise SystemExit(f"{modpath}.{name} does not exist")
            if not (inspect.isclass(obj) or callable(obj)):
                lines.append(f"### `{name}`\n\n{type(obj).__name__} constant.\n")
                continue
            emit_obj(lines, name, obj, 3)
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "reference.md")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as fh:
        fh.write("\n".join(lines))
    print(f"wrote {out} ({sum(len(l) for l in lines)} chars, "
          f"{len(lines)} blocks)")


if __name__ == "__main__":
    main()
