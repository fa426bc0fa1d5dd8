"""Unified observability layer.

Three pillars, one import:

- :mod:`dgraph_tpu.obs.footprint` — static comm-traffic accounting: walk an
  :class:`~dgraph_tpu.plan.EdgePlan` and report per-collective bytes, shard
  imbalance, and an analytic ICI/HBM roofline before a single step runs.
  Also a CLI: ``python -m dgraph_tpu.obs.footprint``.
- :mod:`dgraph_tpu.obs.metrics` — runtime metrics: a host-side
  :class:`Metrics` registry (counters/gauges/histograms) and the
  :class:`StepMetrics` aux-pytree the jitted train step threads out
  (loss, grad-norm, mask counts), emitted as one structured JSONL record
  per step through :class:`~dgraph_tpu.utils.logging.ExperimentLog`.
- :mod:`dgraph_tpu.obs.health` — run/probe health diagnostics: the
  structured :class:`RunHealth` record (probe attempts, wall-times, backend
  state, wedge classification, topology snapshot) bench.py and the
  experiment CLIs embed in their artifacts, so a null benchmark is
  diagnosable from the JSON alone.
- :mod:`dgraph_tpu.obs.spans` — the flight recorder: hierarchical
  host-side spans with trace/span/parent ids shared across train, serve,
  and bench (and across process restarts), JSONL records, and a Perfetto
  (Chrome trace) exporter. One attribute read when disabled; never inside
  traced code (lint-enforced). Enabled context-managed spans also hold a
  ``jax.profiler.TraceAnnotation``, so they share the device trace's
  clock. Names the program records (docs/tracing.md has who reads each):

  ===============================================  ========  ================
  name                                             kind      read by
  ===============================================  ========  ================
  setup.partition / setup.plan / setup.shard       stage     partition_s,
                                                             plan_s, shard_s
  setup.place; setup.graph_gen (GraphCast's)       stage     operators
  setup.init_params / setup.init_opt_state         stage     init_s
  train.step, train.eval > step_dispatch, block    span      span_cost.py,
                                                             xtrace's names
  train.recompile; compile.trace/.lower/.backend   span      operators
  compile.count                                    counter   train.recompile
  compile.trace_s/.lower_s/.backend_s/             counter   the experiments'
  .cache_hits/.cache_misses                                  "compiles" log
  plan.segsum_grid_steps / plan.segsum_used_chunks counter   segsum_grid_
  (also per route)                                           fill_pct.*
  plan.halo_wire_rows / plan.halo_real_rows        counter   halo_wire_
                                                             fill_pct.train
  ===============================================  ========  ================

  Stages (``spans.stage``) are always on: their totals are in
  ``spans.stage_totals()`` with tracing off. Counters live in
  :data:`~dgraph_tpu.obs.metrics.default_registry`.
- :mod:`dgraph_tpu.obs.attribution` — CPU scan-delta step-time
  attribution: per-phase ``{interior, exchange, optimizer, other}``
  timing per halo lowering on the virtual-CPU backend — bench.py's
  non-null timing tier for wedged rounds.
"""

# spans is deliberately NOT imported here: `python -m dgraph_tpu.obs.spans`
# (the perfetto-export/selftest CLI) would otherwise execute the module
# twice — once via this package import, once as __main__ — leaving two
# default tracers in one process. Use `from dgraph_tpu.obs import spans`.
from dgraph_tpu.obs.footprint import plan_footprint
from dgraph_tpu.obs.health import RunHealth, classify_wedge, startup_record
from dgraph_tpu.obs.metrics import Metrics, StepMetrics, default_registry

__all__ = [
    "plan_footprint",
    "RunHealth",
    "classify_wedge",
    "startup_record",
    "Metrics",
    "StepMetrics",
    "default_registry",
]
