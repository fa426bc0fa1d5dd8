"""Static analysis: trace auditing + contract linting.

Five PRs in, the repo's hardest-won invariants existed only by convention:
the tuner auto-adopts configs priced by ``obs.footprint``'s analytic
schedule with nothing checking that the traced program actually emits that
schedule; PR 4 removed a mixed-lowering hazard (a config re-read inside a
traced function could hand the forward exchange and its transpose different
lowerings) that nothing prevented from regressing; and ``chaos`` /
``train.supervise`` / the standalone health loader stayed jax-free only by
hand-enforced discipline.  This package is the machine-checked backstop —
the analogue of DGraph's layered Communicator design (each layer's contract
checkable in isolation) and of "Memory-efficient array redistribution"
(PAPERS.md), which treats the emitted collective schedule as a verifiable
artifact rather than a hope:

- :mod:`dgraph_tpu.analysis.trace` — the **trace auditor**: abstractly
  traces (``jax.make_jaxpr`` / ``jax.eval_shape`` — zero XLA compiles) the
  train step, eval step, and serve bucket forward under each halo lowering
  and verifies the traced collective schedule against the one
  ``obs.footprint`` priced (op counts AND operand bytes — the numbers the
  tuner ranks on), plus single-lowering-per-program, no host callbacks,
  fp32 accumulation, and donation consumption.
- :mod:`dgraph_tpu.analysis.hlo` — the **lowered-artifact auditor**
  (ISSUE 12): one tier below the jaxpr, ``jit(...).lower()`` (StableHLO —
  never ``.compile()``) for every (program, halo lowering) pair and
  verifies the post-lowering schedule: collective kinds/counts/
  replica_groups vs the plan, operand bytes vs ``obs.footprint``, **no
  XLA-materialized collective the plan didn't schedule** (the accidental
  all-gather class), one transport family per program, and
  ``(params, opt_state)`` donation surviving lowering as donor/alias
  entries.
- :mod:`dgraph_tpu.analysis.spmd` — the **cross-rank SPMD divergence
  auditor** (ISSUE 13): every rank's train/eval/serve program lowered
  from that rank's plan-shard subset view under that rank's env, then
  proven identical — canonicalized module bytes, the program-order
  collective issue sequence (the deadlock detector: the NCCL/NVSHMEM
  class hangs, not errors, on schedule mismatch), per-rank live-delta
  symmetry, and tuned-record resolution agreement — across 2/4-shard
  worlds and both generations of a ``train/shrink.py`` transition.
- :mod:`dgraph_tpu.analysis.lint` — the **contract linter**: stdlib-``ast``
  rules over the source tree (jax-free modules, no config reads in traced
  bodies — pallas kernel bodies included, custom_vjp pairing, named_scope
  on collectives, shard_map check kwargs routed through
  ``shard_map_checks``, deterministic plan builds), with a small registry
  so new contracts are one rule away.

CLI::

    python -m dgraph_tpu.analysis              # lint + audit (all tiers)
    python -m dgraph_tpu.analysis --selftest   # compile-free tier-1 smoke

- :mod:`dgraph_tpu.analysis.host` — the **host-side concurrency &
  durability auditor** (the fifth tier, and the only one that audits the
  *host* program instead of the device program): stdlib-``ast`` race /
  deadlock / torn-write rules over the jax-free control plane — per-class
  guarded-field inference with out-of-lock access flagging (thread-escape
  aware), the inter-class lock-acquisition-order graph (cycles RED), the
  atomic-writer routing for durable artifacts, the pointer-flip-last CFG
  check on generation commits, and the bidirectional chaos-registry
  coverage drift check.

This module deliberately imports neither jax nor numpy at module level:
``lint`` and ``host`` are pure stdlib, and ``trace`` pulls jax in lazily
so the CLI can pin the platform/device-count env before any backend
decision is made.  Importing the package registers the host rules in
``lint.RULES`` (one registry: ``--list_rules``, the docs-catalog pin and
the ``# lint: allow(...)`` pragma cover all five tiers' rules).
"""

from __future__ import annotations

from dgraph_tpu.analysis import host  # noqa: F401  (registers host rules)

__all__ = ["hlo", "host", "kernel", "lint", "spmd", "trace"]
