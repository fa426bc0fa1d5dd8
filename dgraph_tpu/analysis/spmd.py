"""Cross-rank SPMD divergence auditor: prove every rank lowers the SAME
program, in the SAME collective order.

DGraph-style full-graph training is SPMD over a vertex-partitioned graph:
every rank must trace an *identical* program or the fine-grained halo
collectives deadlock — the NCCL/NVSHMEM backends of the reference HANG,
not error, on a schedule mismatch (PAPER.md L1/L2), and XLA's collectives
are no different.  The trace tier (:mod:`~dgraph_tpu.analysis.trace`) and
the HLO tier (:mod:`~dgraph_tpu.analysis.hlo`) verify ONE rank's program
against the plan; nothing until this module verified rank-vs-rank
agreement.  And the inputs each rank builds "the same" program from are
genuinely different per rank:

- the **plan-shard subset view** (PR 8): each host loads only its own
  shard (``load_sharded_plan(ranks=[r])`` + ``assemble_plan``) — the
  statics ride the shared manifest, but a build that derived a static
  from the local rows instead would diverge silently;
- the **environment**: ``$DGRAPH_RANK``
  (:data:`~dgraph_tpu.utils.env.RANK_ENV_VAR`), ``DGRAPH_CHAOS``
  ``rank=K`` clauses, per-host tuned-record resolution
  (:func:`~dgraph_tpu.plan.resolve_halo_impl`);
- **post-shrink generations** (PR 9): after a ``train/shrink.py``
  transition every survivor re-plans from the new generation's artifact.

GSPMD-style partitioners ("Automated SPMD partitioning", PAPERS.md)
*assume* program identity across shards as ground truth and never
re-check it; this tier machine-checks the assumption, lower-only
(``jit(...).lower()`` — zero XLA compiles, jit-cache counter enforced
like the HLO tier), before the multi-host campaign can hit the
divergence/hang class at 40-GB-plan scale.

Per (program, halo lowering), each rank's step is built and lowered **as
that rank would build it** — under that rank's env, from that rank's
shard-subset plan view — then three checks run:

(a) **module identity**: all W canonicalized StableHLO modules are
    byte/hash-identical.  Canonicalization strips location metadata
    (rendered with debug info off) and forgives exactly one benign
    divergence class: a line that differs across ranks *only* by an
    integer literal equal to each rank's own id (a rank-tag constant —
    e.g. a metrics field recording the rank) is rewritten with a
    ``«RANK»`` token.  The substitution is alignment-based (same line
    count required, applied only where ranks already differ, only when
    it makes the lines EQUAL), so it can never mask a structural
    difference.  On mismatch the failure names the first divergent op
    and its producing Python frame (from the debug locations of a
    second, debug-info render).

(b) **collective issue order**: the in-program-order sequence of
    collective ops (kind, channel id, replica_groups /
    source_target_pairs, operand bytes) agrees pairwise across ranks —
    the deadlock detector proper: an order-swapped or count-mismatched
    schedule is caught even when per-rank totals match.

(c) **n_deltas symmetry**: a rank whose shard sees fewer live halo
    deltas (it sends to fewer peers — exactly the PR 8 subset-view /
    PR 9 shrink hazard) would emit fewer ppermute rounds IF the program
    consulted the local view.  The auditor computes each rank's locally
    observable live-delta set and proves the asymmetry either absent
    (all sets equal) or program-invariant (sets differ but every rank's
    module is still identical — the program provably uses the manifest's
    global ``halo_deltas``).

Plus a **tuned-resolution agreement** check: each rank resolves its halo
lowering through :func:`~dgraph_tpu.plan.resolve_halo_impl` under its own
(simulated) adopted record; divergent resolution is reported before any
lowering — a rank-divergent tune record is a deadlock at step one.

The zero-filled completion of a rank's plan view is sound for lowering:
a rank never holds its peers' rows, lowering consumes only shapes +
statics, and a program whose *structure* depended on peer row values
would not be SPMD in the first place — that dependence is exactly what
the cross-rank comparison would surface.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
import re
import tempfile
from typing import Callable, Dict, List, Optional

from dgraph_tpu.analysis.hlo import (
    COLLECTIVE_HLO_OPS,
    _dense_2d,
    _elt_info,
    _jit_cache_entries,
    lower_program,
)
from dgraph_tpu.analysis.trace import HALO_IMPLS, AuditWorkload, PROGRAMS
from dgraph_tpu.utils.env import RANK_ENV_VAR

__all__ = [
    "build_spmd_fixture",
    "build_shrink_fixture",
    "build_rank_workload",
    "rank_live_deltas",
    "canonical_module_text",
    "canonicalize_rank_modules",
    "collective_sequence",
    "resolution_agreement",
    "audit_plan_dir_spmd",
    "spmd_drift_record",
    "spmd_selftest",
]

RANK_TOKEN = "«RANK»"

# statics a rank's plan view must agree on with every peer: one drifted
# value here changes traced round counts / operand shapes program-wide
_STATIC_FIELDS = (
    "world_size", "n_src_pad", "n_dst_pad", "e_pad", "halo_side",
    "homogeneous", "owner_sorted", "halo_deltas", "scatter_mc",
    "scatter_block_e", "scatter_block_n", "halo_sort_mc", "gather_mv",
    # the FULL-WORLD traffic matrix: a rank whose matrix drifted weighs
    # the heuristic differently and may resolve another lowering
    "halo_pair_rows",
    # the wire format attached at build time (dgraph_tpu.wire): a rank
    # whose format drifted encodes collective operands at a different
    # dtype/width — every exchange rendezvous disagrees on byte counts
    "wire_format",
)


@contextlib.contextmanager
def _rank_env(rank: int):
    """Simulate one rank's process env (``$DGRAPH_RANK``) for the
    duration of a build+lower — restored unconditionally."""
    old = os.environ.get(RANK_ENV_VAR)
    os.environ[RANK_ENV_VAR] = str(int(rank))
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(RANK_ENV_VAR, None)
        else:
            os.environ[RANK_ENV_VAR] = old


# ---------------------------------------------------------------------------
# fixtures: sharded plan artifacts (and a shrink run) for the audit
# ---------------------------------------------------------------------------


def _fixture_graph(world_size: int, num_nodes: int, num_edges: int,
                   seed: int):
    """The canonical audit graph (same construction as
    :func:`~dgraph_tpu.analysis.trace.build_audit_workload`, so the spmd
    tier audits the same workload shape the other tiers pin)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    part = np.sort(rng.integers(0, world_size, num_nodes)).astype(np.int32)
    edges = np.stack([
        rng.integers(0, num_nodes, num_edges),
        rng.integers(0, num_nodes, num_edges),
    ])
    return edges, part


def build_spmd_fixture(
    world_size: int,
    out_dir: str,
    *,
    num_nodes: int = 48,
    num_edges: int = 300,
    seed: int = 0,
) -> str:
    """Write the v8 sharded-plan artifact the cross-rank audit loads its
    per-rank views from (``overlap=True`` so all four halo lowerings are
    legal; no O(E) layout sidecar — per-rank loading never reads it)."""
    from dgraph_tpu.plan import build_plan_shards

    edges, part = _fixture_graph(world_size, num_nodes, num_edges, seed)
    build_plan_shards(
        edges, part, out_dir=out_dir, world_size=world_size, overlap=True,
        write_layout=False,
    )
    return out_dir


def build_shrink_fixture(
    run_dir: str,
    *,
    world_size: int = 3,
    num_nodes: int = 48,
    num_edges: int = 240,
    seed: int = 0,
) -> dict:
    """A real ``train/shrink.py`` W -> W-1 transition: init generation 0,
    make one checkpoint step durable on every rank (the consistent cut
    ``shrink_world`` requires), lose the last rank.  Returns the adopted
    world record; ``plan_dir(run_dir, g)`` for g in {0, 1} are the two
    generations the cross-rank audit then verifies."""
    import numpy as np

    from dgraph_tpu import plan_shards as ps
    from dgraph_tpu.train import shrink
    from dgraph_tpu.train.checkpoint import save_checkpoint

    edges, _ = _fixture_graph(world_size, num_nodes, num_edges, seed)
    shrink.init_world(
        run_dir, edges, num_nodes, world_size, seed=seed, overlap=True,
    )
    statics = ps.read_manifest(shrink.plan_dir(run_dir, 0))["statics"]
    n_pad = int(statics["n_dst_pad"])
    for r in range(world_size):
        save_checkpoint(
            shrink.rank_ckpt_dir(run_dir, 0, r),
            {"state": {"w": np.zeros((n_pad, 2), np.float32)}, "step": 0},
            0,
        )
    return shrink.shrink_world(run_dir, [world_size - 1])


# ---------------------------------------------------------------------------
# per-rank plan views and workloads
# ---------------------------------------------------------------------------


def _expand_rank_view(sub_plan, rank: int, world_size: int):
    """Zero-filled full-``[W]`` completion of one rank's subset plan view
    (leading axis 1 -> W, the rank's own row in its slot).  Shapes and
    statics are exactly what the rank knows; peer rows — which the rank
    never holds — are zeros, which lowering (shapes only) cannot see."""
    import numpy as np
    import jax

    def expand(leaf):
        arr = np.asarray(leaf)
        out = np.zeros((world_size,) + arr.shape[1:], arr.dtype)
        out[rank] = arr[0]
        return out

    return jax.tree.map(expand, sub_plan)


def rank_live_deltas(sub_plan, rank: int) -> tuple:
    """The live halo deltas OBSERVABLE from one rank's own shard: deltas
    ``(p - rank) % W`` for peers p this rank sends at least one real halo
    row to.  (Receive liveness lives in the peers' shards — exactly why a
    per-rank derivation of ``halo_deltas`` would be asymmetric.)"""
    import numpy as np

    W = int(sub_plan.world_size)
    mask = np.asarray(sub_plan.halo.send_mask)[0]  # [W, S]
    live = set()
    for p in range(W):
        if p != rank and mask[p].any():
            live.add((p - rank) % W)
    return tuple(sorted(live))


def _plan_statics(plan) -> dict:
    out = {k: getattr(plan, k) for k in _STATIC_FIELDS}
    out["s_pad"] = int(plan.halo.s_pad)
    out["halo_deltas"] = tuple(int(d) for d in plan.halo_deltas)
    out["overlap"] = plan.overlap is not None
    if plan.overlap is not None:
        out["e_int_pad"] = int(plan.overlap.e_int_pad)
        out["e_bnd_pad"] = int(plan.overlap.e_bnd_pad)
    return out


def build_rank_workload(
    plan_dir: str,
    rank: int,
    **workload_kwargs,
) -> AuditWorkload:
    """Build the audit workload **as rank ``rank`` would build it**: the
    plan comes from that rank's shard-subset view
    (``load_sharded_plan(ranks=[rank])`` -> :func:`~dgraph_tpu.plan.
    assemble_plan`), everything downstream (batch shapes, model init,
    optimizer state) is derived from that view's statics through the
    SAME scaffolding the other tiers audit
    (:func:`~dgraph_tpu.analysis.trace.workload_from_plan` — structural
    sameness, not parallel-edit sameness), and the whole build runs
    under that rank's env (``$DGRAPH_RANK``).  Abstract throughout:
    params/opt_state are ``eval_shape`` trees, the batch is zeros —
    nothing compiles, nothing touches a device buffer."""
    from dgraph_tpu.analysis.trace import workload_from_plan
    from dgraph_tpu.plan import load_sharded_plan

    with _rank_env(rank):
        sub, _ = load_sharded_plan(
            plan_dir, ranks=[rank], load_layout=False
        )
        plan = _expand_rank_view(sub, rank, int(sub.world_size))
        return workload_from_plan(plan, **workload_kwargs)


# ---------------------------------------------------------------------------
# canonicalization + ordered collective walk
# ---------------------------------------------------------------------------


def canonical_module_text(lowered) -> str:
    """The lowered StableHLO module rendered WITHOUT debug info (no
    ``loc(...)`` / ``#loc`` metadata — the only per-build noise in the
    asm) — the byte string the cross-rank identity check hashes."""
    module = lowered.compiler_ir(dialect="stablehlo")
    return module.operation.get_asm(enable_debug_info=False)


def _rank_id_sub(line: str, rank: int) -> str:
    """Rewrite standalone occurrences of ``rank``'s own integer id to the
    RANK token (word/float boundaries guarded: ``dense<1>`` rewrites,
    ``tensor<1x8xf32>``'s dim and ``1.000000e+00`` do not)."""
    return re.sub(
        rf"(?<![\w.]){rank}(?![\w.])", RANK_TOKEN, line
    )


def canonicalize_rank_modules(texts: Dict[int, str]) -> tuple:
    """Alignment-based benign-divergence canonicalization over per-rank
    module texts.  Returns ``(canonical: dict, rank_tag_lines: int)``.

    Only lines where ranks ALREADY differ are touched, and a line is
    rewritten only when substituting each rank's own id makes all ranks'
    lines EQUAL — a pure rank-tag constant.  Anything else (different op,
    different shape, different order, different count) survives verbatim
    and fails the identity check.  Modules with different line counts are
    returned unchanged: that is structural divergence by definition."""
    ranks = sorted(texts)
    lines = {r: texts[r].splitlines() for r in ranks}
    if len({len(v) for v in lines.values()}) != 1:
        return dict(texts), 0
    n = len(lines[ranks[0]])
    subs = 0
    for i in range(n):
        row = {r: lines[r][i] for r in ranks}
        if len(set(row.values())) == 1:
            continue
        cand = {r: _rank_id_sub(row[r], r) for r in ranks}
        if len(set(cand.values())) == 1 and cand[ranks[0]] != row[ranks[0]]:
            for r in ranks:
                lines[r][i] = cand[r]
            subs += 1
    return {r: "\n".join(lines[r]) for r in ranks}, subs


def _walk_ops(module):
    """Every op of a StableHLO module in PROGRAM ORDER (pre-order over
    regions/blocks) — the order XLA will issue collectives in."""

    def rec(op):
        yield op
        for region in op.regions:
            for block in region.blocks:
                for child in block.operations:
                    yield from rec(child.operation)

    yield from rec(module.operation)


def _tensor_info(t):
    import math

    from jaxlib.mlir import ir

    rt = ir.RankedTensorType(t)
    shape = tuple(int(s) for s in rt.shape)
    np_dtype, nbytes = _elt_info(str(rt.element_type))
    return shape, np_dtype, int(math.prod(shape)) * nbytes


def collective_sequence(lowered) -> List[dict]:
    """The module's collective ISSUE sequence, in program order: op kind,
    channel id, replica_groups / source_target_pairs, operand bytes.
    Two ranks whose sequences differ anywhere — order, kind, peers,
    payload — are a deadlock on real transports (each side waits for the
    other's next collective, which never comes)."""
    module = lowered.compiler_ir(dialect="stablehlo")
    seq = []
    for op in _walk_ops(module):
        name = op.name
        if not name.startswith("stablehlo."):
            continue
        kind = name[len("stablehlo."):]
        if kind not in COLLECTIVE_HLO_OPS or not op.operands:
            continue
        attrs = {name: op.attributes[name] for name in op.attributes}
        shape, np_dtype, nbytes = _tensor_info(op.operands[0].type)
        channel = attrs.get("channel_handle")
        m = re.search(r"handle\s*=\s*(\d+)", str(channel)) if channel else None
        seq.append({
            "op": kind,
            "shape": list(shape),
            "dtype": np_dtype,
            "bytes": nbytes,
            "channel_id": int(m.group(1)) if m else None,
            "replica_groups": _dense_2d(attrs.get("replica_groups")),
            "source_target_pairs": _dense_2d(
                attrs.get("source_target_pairs")
            ),
        })
    return seq


def _short_loc(loc: str) -> str:
    """Condense an MLIR callsite chain to ``scope @ file:line`` (the
    producing Python frame) — the full chain is pages long."""
    scope = re.match(r'loc\("([^"]+)"', loc)
    frame = re.search(r'"([^"<][^"]*)":(\d+):\d+', loc)
    out = scope.group(1) if scope else ""
    if frame:
        out += f" @ {frame.group(1)}:{frame.group(2)}"
    return out or loc[:160]


def _op_fingerprints(lowered) -> List[tuple]:
    """(op name, result types, attributes) per op in program order, plus
    the op's debug location — the divergence-naming walk (locations come
    from THIS render; the identity check's render has them stripped)."""
    module = lowered.compiler_ir(dialect="stablehlo")
    out = []
    for op in _walk_ops(module):
        attrs = tuple(sorted(
            (name, str(op.attributes[name])) for name in op.attributes
        ))
        results = tuple(str(r.type) for r in op.results)
        out.append((op.name, results, attrs, _short_loc(str(op.location))))
    return out


def _first_divergent_op(fp_a: list, fp_b: list, rank_a: int, rank_b: int):
    """First program-order op whose (name, results, attrs) fingerprint
    differs between two ranks' modules, with both producing frames."""
    for i, (a, b) in enumerate(zip(fp_a, fp_b)):
        if a[:3] != b[:3]:
            return (
                f"op #{i}: rank {rank_a} lowered {a[0]!r} "
                f"(from {a[3]}), rank {rank_b} lowered {b[0]!r} "
                f"(from {b[3]})"
            )
    if len(fp_a) != len(fp_b):
        i = min(len(fp_a), len(fp_b))
        longer, who = (fp_a, rank_a) if len(fp_a) > len(fp_b) else (fp_b, rank_b)
        return (
            f"op #{i}: rank {who} lowered {len(longer) - i} extra op(s), "
            f"first {longer[i][0]!r} (from {longer[i][3]})"
        )
    return "modules differ only in attribute/metadata text"


def _issue_key(entry: dict) -> tuple:
    """A collective's order-independent identity: everything except the
    channel id, which XLA assigns in ISSUE order — two ranks that swap
    two collectives also swap the channel numbering, so the swap must be
    recognized on the op's own parameters."""
    return tuple(
        (k, repr(v)) for k, v in sorted(entry.items()) if k != "channel_id"
    )


def _compare_sequences(seq0: list, seq_r: list, rank: int, label: str,
                       failures: list) -> None:
    """Pairwise collective-schedule agreement (rank 0 vs rank ``rank``):
    the deadlock detector proper."""
    if len(seq0) != len(seq_r):
        failures.append(
            f"[spmd:{label}] collective COUNT mismatch: rank 0 issues "
            f"{len(seq0)} collectives, rank {rank} issues {len(seq_r)} — "
            f"on a real transport the long side blocks forever on round "
            f"{min(len(seq0), len(seq_r))}"
        )
        return
    for i, (a, b) in enumerate(zip(seq0, seq_r)):
        if a == b:
            continue
        # a swap: the collective rank `rank` issues HERE, rank 0 issues
        # LATER (or vice versa) — same multiset, different order
        later = any(
            _issue_key(b) == _issue_key(seq0[j])
            for j in range(i + 1, len(seq0))
        ) or any(
            _issue_key(a) == _issue_key(seq_r[j])
            for j in range(i + 1, len(seq_r))
        )
        what = (
            "ORDER-swapped collective schedule"
            if later else "collective-parameter drift"
        )
        failures.append(
            f"[spmd:{label}] {what} at issue #{i}: rank 0 issues "
            f"{a['op']}(channel={a['channel_id']}, bytes={a['bytes']}, "
            f"pairs={a['source_target_pairs']}), rank {rank} issues "
            f"{b['op']}(channel={b['channel_id']}, bytes={b['bytes']}, "
            f"pairs={b['source_target_pairs']}) — mismatched peers "
            f"rendezvous on different collectives and deadlock"
        )
        return


# ---------------------------------------------------------------------------
# tuned-record resolution agreement
# ---------------------------------------------------------------------------


def resolution_agreement(
    world_size: int,
    halo_deltas: tuple,
    *,
    overlap_available: bool,
    pair_rows: tuple = (),
    rank_tuned: Optional[Dict[int, Optional[str]]] = None,
    plan_wire_format: str = "fp32",
    rank_tuned_wire: Optional[Dict[int, Optional[str]]] = None,
    failures: Optional[list] = None,
) -> dict:
    """Resolve the halo lowering AND the wire format PER RANK through
    the real :func:`~dgraph_tpu.plan.resolve_halo_impl` /
    :func:`~dgraph_tpu.wire.spec.resolve_wire_format` ladders, each rank
    under its own (simulated) adopted tuning record — divergent
    resolution means the ranks would not even agree on the transport
    family (or would encode collective operands at different widths), a
    deadlock before the first exchange.  Appends to ``failures`` and
    returns ``{rank: [impl, source, wire_format, wire_source]}``."""
    from dgraph_tpu import config as _cfg
    from dgraph_tpu.plan import resolve_halo_impl
    from dgraph_tpu.wire.spec import resolve_wire_format

    rank_tuned = rank_tuned or {}
    rank_tuned_wire = rank_tuned_wire or {}
    out = {}
    saved = (_cfg.halo_impl, _cfg.tuned_halo_impl,
             _cfg.wire_format, _cfg.tuned_wire_format)
    try:
        for r in range(world_size):
            with _rank_env(r):
                _cfg.set_flags(
                    halo_impl="auto", tuned_halo_impl=rank_tuned.get(r),
                    wire_format="auto",
                    tuned_wire_format=rank_tuned_wire.get(r),
                )
                impl, source = resolve_halo_impl(
                    world_size, tuple(halo_deltas),
                    overlap_available=overlap_available,
                    pair_rows=pair_rows,
                )
                wf, wf_source = resolve_wire_format(
                    world_size, tuple(halo_deltas),
                    plan_format=plan_wire_format,
                )
                out[r] = [impl, source, wf, wf_source]
    finally:
        _cfg.set_flags(
            halo_impl=saved[0], tuned_halo_impl=saved[1],
            wire_format=saved[2], tuned_wire_format=saved[3],
        )
    if failures is not None:
        if len({(v[0], v[1]) for v in out.values()}) > 1:
            failures.append(
                f"[spmd:resolution] ranks resolve DIFFERENT halo "
                f"lowerings: {out} — a rank-divergent tuned record (or "
                f"env pin) splits the transport family before the first "
                f"exchange"
            )
        if len({(v[2], v[3]) for v in out.values()}) > 1:
            failures.append(
                f"[spmd:resolution] ranks resolve DIFFERENT wire "
                f"formats: {out} — a rank-divergent tuned record (or "
                f"env pin) makes peers encode/decode collective operands "
                f"at different widths; every rendezvous disagrees on "
                f"byte counts"
            )
    return out


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------


def _call_builder(build: Callable, w: AuditWorkload, rank: int):
    """Program builders are rank-agnostic by default
    (:data:`~dgraph_tpu.analysis.trace.PROGRAMS`); mutant builders (the
    selftest's seeded divergences) take ``(w, rank)``."""
    import inspect

    params = inspect.signature(build).parameters
    if len(params) >= 2:
        return build(w, rank)
    return build(w)


def audit_plan_dir_spmd(
    plan_dir: str,
    *,
    impls=HALO_IMPLS,
    programs: Optional[dict] = None,
    rank_tuned: Optional[Dict[int, Optional[str]]] = None,
    rank_tuned_wire: Optional[Dict[int, Optional[str]]] = None,
    label: str = "",
    workload_kwargs: Optional[dict] = None,
) -> dict:
    """Run the full cross-rank audit over one sharded-plan artifact:
    build + lower every (program, halo lowering) pair per rank — each
    rank from its own shard-subset view, under its own env — and verify
    module identity (a), collective issue order (b), n_deltas symmetry
    (c), and tuned-resolution agreement.  Lower-only: the jit cache of
    every built program must stay empty (counter in the report, failure
    otherwise).  Returns a ``kind="spmd_audit"`` report dict (``ok`` +
    ``failures``; the caller decides whether to raise)."""
    from dgraph_tpu import config as _cfg
    from dgraph_tpu import plan_shards as ps
    from dgraph_tpu.plan import load_sharded_plan

    manifest = ps.read_manifest(plan_dir)
    W = int(manifest["world_size"])
    prefix = f"{label}/" if label else ""
    failures: list = []

    # per-rank plan views: statics agreement + locally observable deltas
    statics_by_rank, live_by_rank = {}, {}
    for r in range(W):
        with _rank_env(r):
            sub, _ = load_sharded_plan(plan_dir, ranks=[r], load_layout=False)
        statics_by_rank[r] = _plan_statics(sub)
        live_by_rank[r] = rank_live_deltas(sub, r)
    base = statics_by_rank[0]
    for r in range(1, W):
        if statics_by_rank[r] != base:
            diff = {
                k: (base[k], statics_by_rank[r][k])
                for k in base
                if statics_by_rank[r].get(k) != base[k]
            }
            failures.append(
                f"[spmd:{prefix}statics] rank {r}'s plan view disagrees "
                f"with rank 0 on {diff} — every traced shape/round count "
                f"downstream diverges"
            )
    halo_deltas = base["halo_deltas"]

    # tuned-record resolution agreement (each rank under its own record)
    resolution = resolution_agreement(
        W, halo_deltas, overlap_available=base.get("overlap", False),
        pair_rows=base.get("halo_pair_rows", ()),
        rank_tuned=rank_tuned,
        plan_wire_format=base.get("wire_format", "fp32"),
        rank_tuned_wire=rank_tuned_wire, failures=failures,
    )

    # per-rank workloads, built under each rank's env (skipped when the
    # caller asked for the static checks only, impls=())
    wk = dict(workload_kwargs or {})
    workloads = (
        {r: build_rank_workload(plan_dir, r, **wk) for r in range(W)}
        if impls else {}
    )

    program_records: list = []
    saved = (_cfg.halo_impl, _cfg.tuned_halo_impl)
    schedule_ok = True
    try:
        for impl in impls:
            _cfg.set_flags(halo_impl=impl, tuned_halo_impl=None)
            for plabel, build in (programs or PROGRAMS).items():
                tag = f"{prefix}{plabel}/{impl}"
                texts, seqs, lowereds, cache = {}, {}, {}, {}
                for r in range(W):
                    with _rank_env(r):
                        fn, args = _call_builder(build, workloads[r], r)
                        lowered = lower_program(fn, args)
                        texts[r] = canonical_module_text(lowered)
                        seqs[r] = collective_sequence(lowered)
                        lowereds[r] = lowered
                        cache[r] = _jit_cache_entries(fn)
                    if cache[r] is None:
                        failures.append(
                            f"[spmd:{tag}] rank {r}: jit-cache probe "
                            f"unavailable — the lower-only contract is "
                            f"unenforceable; update analysis for this jax "
                            f"version"
                        )
                    elif cache[r]:
                        failures.append(
                            f"[spmd:{tag}] rank {r}: jit cache holds "
                            f"{cache[r]} executable(s) after a lower-only "
                            f"audit — something compiled"
                        )

                canon, rank_tags = canonicalize_rank_modules(texts)
                hashes = {
                    r: hashlib.sha256(canon[r].encode()).hexdigest()[:16]
                    for r in canon
                }
                identical = len(set(hashes.values())) == 1
                if not identical:
                    fp0 = _op_fingerprints(lowereds[0])
                    for r in range(1, W):
                        if hashes[r] == hashes[0]:
                            continue
                        failures.append(
                            f"[spmd:{tag}] rank {r}'s canonicalized "
                            f"StableHLO differs from rank 0's "
                            f"({hashes[0]} vs {hashes[r]}); first "
                            f"divergence — "
                            + _first_divergent_op(
                                fp0, _op_fingerprints(lowereds[r]), 0, r
                            )
                        )
                        break  # one named divergence per pair is enough
                n_sched = len(failures)
                for r in range(1, W):
                    _compare_sequences(seqs[0], seqs[r], r, tag, failures)
                if len(failures) > n_sched or not identical:
                    schedule_ok = False
                program_records.append({
                    "program": plabel,
                    "impl": impl,
                    "module_hash": hashes,
                    "identical": identical,
                    "rank_tag_lines": rank_tags,
                    "num_collectives": len(seqs[0]),
                    "jit_cache_entries": cache,
                })
    finally:
        _cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1])

    # (c) n_deltas symmetry: absent, or proven program-invariant by the
    # very identity the modules just demonstrated. In static-only mode
    # (impls=() — nothing lowered) an asymmetric view is REPORTED but not
    # failed: there is no program evidence either way.
    sym = "symmetric"
    if len({live_by_rank[r] for r in live_by_rank}) > 1:
        if not program_records:
            sym = "asymmetric_not_lowered"
        elif schedule_ok:
            sym = "asymmetric_program_invariant"
        else:
            sym = "asymmetric"
            failures.append(
                f"[spmd:{prefix}n_deltas] per-rank live-delta views differ "
                f"({ {r: list(v) for r, v in live_by_rank.items()} }) AND "
                f"the lowered programs diverge — a rank that sees fewer "
                f"live deltas is emitting a different round schedule (the "
                f"rank-subset / shrink hazard)"
            )

    return {
        "kind": "spmd_audit",
        "plan_dir": plan_dir,
        "label": label,
        "world_size": W,
        "num_halo_deltas": len(halo_deltas),
        "halo_deltas": list(halo_deltas),
        "impls": list(impls),
        "programs": program_records,
        "statics_agree": not any("statics" in f for f in failures),
        "per_rank_live_deltas": {
            str(r): list(v) for r, v in live_by_rank.items()
        },
        "delta_symmetry": sym,
        "resolution": {str(r): v for r, v in resolution.items()},
        "failures": failures,
        "ok": not failures,
    }


# ---------------------------------------------------------------------------
# bench fallback record
# ---------------------------------------------------------------------------


def spmd_drift_record(
    world_size: int = 4, *, num_nodes: int = 1024, num_edges: int = 4096,
    feat_dim: int = 16, seed: int = 0,
) -> dict:
    """Compact cross-rank identity record for bench's no-healthy-chip
    fallback (ROADMAP item 5, FOURTH non-null tier beside
    ``schedule_drift``, ``cpu_scan_delta``, and ``hlo_drift``): the
    TRAIN step only, one row per halo lowering with the per-rank module
    hashes and the schedule-identity verdict — a wedged round still
    lands a non-null signal about whether the ranks would have agreed
    on a collective schedule at all."""
    from dgraph_tpu.analysis.trace import _train_program

    with tempfile.TemporaryDirectory(prefix="dgraph_spmd_drift_") as tmp:
        build_spmd_fixture(
            world_size, tmp, num_nodes=num_nodes, num_edges=num_edges,
            seed=seed,
        )
        report = audit_plan_dir_spmd(
            tmp, programs={"train_step": _train_program},
            workload_kwargs={"feat_dim": feat_dim},
        )
    per_impl = {
        rec["impl"]: {
            "identical": rec["identical"],
            "num_collectives": rec["num_collectives"],
            "rank_tag_lines": rec["rank_tag_lines"],
        }
        for rec in report["programs"]
    }
    return {
        "kind": "spmd_drift",
        "workload": {
            "world_size": world_size, "nodes": num_nodes,
            "edges": num_edges, "feat_dim": feat_dim, "seed": seed,
        },
        "num_halo_deltas": report["num_halo_deltas"],
        "delta_symmetry": report["delta_symmetry"],
        "train_step_by_impl": per_impl,
        "failures": report["failures"],
        "drift": not report["ok"],
    }


# ---------------------------------------------------------------------------
# seeded divergence mutants (the selftest's vacuity guards)
# ---------------------------------------------------------------------------


def mutant_dropped_round_program(w: AuditWorkload, rank: int):
    """Rank 1 drops the last live delta from its round schedule — the
    PR 8/9 hazard in its purest form.  Every other rank spins on the
    missing round's ``collective_permute`` forever on real transports;
    here it MUST turn both the module-identity and the issue-sequence
    checks red."""
    import jax
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm import collectives
    from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan

    deltas = tuple(w.plan_np.halo_deltas)
    my_deltas = deltas[:-1] if rank == 1 else deltas

    def stepish(xs, plan):
        def body(plan_, x):
            p = squeeze_plan(plan_)
            buf = collectives.halo_exchange(
                x[0], p.halo, GRAPH_AXIS, deltas=my_deltas, impl="ppermute",
            )
            return buf.sum()[None]

        return jax.shard_map(
            body, mesh=w.mesh,
            in_specs=(plan_in_specs(w.plan), P(GRAPH_AXIS)),
            out_specs=P(GRAPH_AXIS),
            **collectives.shard_map_checks(impl="ppermute"),
        )(plan, xs)

    return jax.jit(stepish), (w.batch["x"], w.plan)


def mutant_swapped_order_program(w: AuditWorkload, rank: int):
    """Two collectives, issued in RANK-DEPENDENT order (rank 1 swaps
    them) — per-rank totals match exactly, so only the issue-sequence
    comparison can catch it.  Needs >= 2 live deltas."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm.collectives import shard_map_checks
    from dgraph_tpu.comm.mesh import GRAPH_AXIS

    W = w.world_size
    deltas = tuple(w.plan_np.halo_deltas)
    if len(deltas) < 2:
        raise ValueError(
            f"the swapped-order mutant needs >= 2 live deltas (have "
            f"{deltas}); use a wider fixture"
        )
    order = deltas[:2] if rank != 1 else deltas[:2][::-1]

    def stepish(xs):
        def body(x):
            out = x[0]
            for d in order:
                perm = [(i, (i + d) % W) for i in range(W)]
                out = out + lax.ppermute(out, GRAPH_AXIS, perm)
            return out[None]

        return jax.shard_map(
            body, mesh=w.mesh, in_specs=(P(GRAPH_AXIS),),
            out_specs=P(GRAPH_AXIS),
            **shard_map_checks(relax="seeded spmd vacuity mutant"),
        )(xs)

    return jax.jit(stepish), (w.batch["x"],)


def benign_rank_tag_program(w: AuditWorkload, rank: int):
    """A rank-id CONSTANT folded into the module (a metrics tag — the
    one benign per-rank difference) alongside a normal collective: the
    canonicalizer must substitute it and the audit must stay GREEN."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm.collectives import shard_map_checks
    from dgraph_tpu.comm.mesh import GRAPH_AXIS

    W = w.world_size

    def stepish(xs):
        def body(x):
            out = x[0] + lax.ppermute(
                x[0], GRAPH_AXIS, [(i, (i + 1) % W) for i in range(W)]
            )
            return out[None], jnp.int32(rank)

        return jax.shard_map(
            body, mesh=w.mesh, in_specs=(P(GRAPH_AXIS),),
            out_specs=(P(GRAPH_AXIS), P()),
            **shard_map_checks(relax="rank tag replicated by construction"),
        )(xs)

    return jax.jit(stepish), (w.batch["x"],)


# ---------------------------------------------------------------------------
# selftest (the vacuity guards; __main__'s --selftest and the standalone
# CLI both run this)
# ---------------------------------------------------------------------------


def _check(failures: list, cond, msg: str) -> None:
    if not cond:
        failures.append(msg)


def spmd_selftest(log=None, *, seed: int = 0) -> dict:
    """The cross-rank audit's tier-1 registration: clean 2- AND 4-shard
    worlds across all four halo lowerings, one real shrink (W -> W-1)
    transition's both generations, and the seeded-divergence vacuity
    mutants (dropped round on rank 1, swapped two-collective order,
    rank-divergent tune record) that must each go RED — plus the benign
    rank-tag constant that must stay GREEN.  Zero XLA compiles
    throughout; every program's jit-cache counter rides the report."""
    from dgraph_tpu.analysis.trace import _train_program
    from dgraph_tpu.train import shrink as shr

    failures: list = []
    summary: dict = {"kind": "spmd_selftest"}
    with tempfile.TemporaryDirectory(prefix="dgraph_spmd_selftest_") as tmp:
        # clean cross-rank audits: every program, every lowering, W=2 and 4
        w4_dir = None
        for W in (2, 4):
            d = build_spmd_fixture(W, os.path.join(tmp, f"w{W}"), seed=seed)
            rep = audit_plan_dir_spmd(d, label=f"w{W}")
            if log is not None:
                log.write(rep)
            _check(
                failures, rep["ok"],
                f"{W}-shard cross-rank audit drifted: {rep['failures']}",
            )
            _check(
                failures, rep["num_halo_deltas"] >= 1,
                f"{W}-shard spmd fixture has no cross-rank traffic "
                f"(the identity checks would be vacuous)",
            )
            summary[f"w{W}"] = {
                "ok": rep["ok"],
                "delta_symmetry": rep["delta_symmetry"],
                "num_halo_deltas": rep["num_halo_deltas"],
                "programs_identical": all(
                    p["identical"] for p in rep["programs"]
                ),
                "jit_cache_entries": max(
                    (c or 0)
                    for p in rep["programs"]
                    for c in p["jit_cache_entries"].values()
                ),
            }
            if W == 4:
                w4_dir = d

        # one REAL shrink transition: audit both generations (train step,
        # all four lowerings) — the post-shrink world must re-agree
        rund = os.path.join(tmp, "shrink")
        world = build_shrink_fixture(rund, world_size=3, seed=seed)
        _check(
            failures, world["world_size"] == 2 and world["generation"] == 1,
            f"shrink fixture did not adopt a W-1 world: {world}",
        )
        for gen, wsz in ((0, 3), (1, 2)):
            rep = audit_plan_dir_spmd(
                shr.plan_dir(rund, gen),
                programs={"train_step": _train_program},
                label=f"shrink_g{gen}",
            )
            if log is not None:
                log.write(rep)
            _check(
                failures, rep["world_size"] == wsz,
                f"shrink generation {gen} plan is for world "
                f"{rep['world_size']}, expected {wsz}",
            )
            _check(
                failures, rep["ok"],
                f"post-shrink generation {gen} cross-rank audit drifted: "
                f"{rep['failures']}",
            )
            summary[f"shrink_g{gen}"] = {
                "ok": rep["ok"], "world_size": rep["world_size"],
                "delta_symmetry": rep["delta_symmetry"],
            }

        # vacuity mutants on the 4-shard fixture (>= 2 live deltas there)
        mutants = {}

        rep = audit_plan_dir_spmd(
            w4_dir, impls=("ppermute",),
            programs={"mutant_drop": mutant_dropped_round_program},
            label="mutant_drop",
        )
        mutants["dropped_round"] = not rep["ok"]
        _check(
            failures, not rep["ok"],
            "auditor accepted a rank-dependent branch that DROPS a "
            "ppermute round on rank 1",
        )
        _check(
            failures,
            any("COUNT mismatch" in f or "differs" in f
                for f in rep["failures"]),
            f"dropped-round divergence was red for the wrong reason: "
            f"{rep['failures'][:2]}",
        )

        rep = audit_plan_dir_spmd(
            w4_dir, impls=("ppermute",),
            programs={"mutant_swap": mutant_swapped_order_program},
            label="mutant_swap",
        )
        mutants["swapped_order"] = not rep["ok"]
        _check(
            failures, not rep["ok"],
            "auditor accepted a rank-dependent SWAP of two collectives "
            "(equal per-rank totals — the pure ordering deadlock)",
        )
        _check(
            failures,
            any("ORDER" in f for f in rep["failures"]),
            f"swapped-order divergence missed by the issue-sequence "
            f"comparator: {rep['failures'][:2]}",
        )

        # a rank-divergent adopted tuning record must fail resolution
        # agreement before anything lowers
        rep = audit_plan_dir_spmd(
            w4_dir, impls=(), programs={},
            rank_tuned={0: "all_to_all", 1: "ppermute"},
            label="mutant_tuned",
        )
        mutants["divergent_tune_record"] = not rep["ok"]
        _check(
            failures, not rep["ok"],
            "auditor accepted rank-divergent tuned-record resolution",
        )
        _check(
            failures,
            any("resolution" in f for f in rep["failures"]),
            f"divergent tune record was red for the wrong reason: "
            f"{rep['failures'][:2]}",
        )

        # a rank-divergent adopted WIRE-FORMAT record must likewise fail
        # resolution agreement before anything lowers (rank 1 encodes
        # bf16 while rank 0 sends fp32 — byte counts disagree at every
        # rendezvous)
        rep = audit_plan_dir_spmd(
            w4_dir, impls=(), programs={},
            rank_tuned_wire={0: None, 1: "bf16"},
            label="mutant_wire",
        )
        mutants["divergent_wire_record"] = not rep["ok"]
        _check(
            failures, not rep["ok"],
            "auditor accepted rank-divergent wire-format resolution",
        )
        _check(
            failures,
            any("wire" in f for f in rep["failures"]),
            f"divergent wire record was red for the wrong reason: "
            f"{rep['failures'][:2]}",
        )

        # the benign rank-tag constant must stay GREEN (canonicalized),
        # proving the identity check doesn't cry wolf on rank identity
        rep = audit_plan_dir_spmd(
            w4_dir, impls=("ppermute",),
            programs={"benign_tag": benign_rank_tag_program},
            label="benign_tag",
        )
        mutants["benign_rank_tag_green"] = rep["ok"]
        _check(
            failures, rep["ok"],
            f"canonicalization failed to forgive a benign rank-id "
            f"constant: {rep['failures'][:2]}",
        )
        _check(
            failures,
            any(p["rank_tag_lines"] > 0 for p in rep["programs"]),
            "benign rank-tag program embedded no rank constant — the "
            "canonicalization check is vacuous",
        )

        summary["mutants"] = mutants
    summary["failures"] = failures
    summary["ok"] = not failures
    return summary


# ---------------------------------------------------------------------------
# CLI (scripts/check.py runs this standalone; the package CLI embeds it)
# ---------------------------------------------------------------------------


def main(cfg) -> dict:
    import json

    from dgraph_tpu.obs.health import RunHealth
    from dgraph_tpu.utils import ExperimentLog

    health = RunHealth.begin("analysis.spmd.cli")
    log = ExperimentLog(cfg.log_path, echo=False)
    if cfg.selftest:
        out = spmd_selftest(log, seed=cfg.seed)
        failures = out["failures"]
    else:
        with tempfile.TemporaryDirectory(prefix="dgraph_spmd_") as tmp:
            build_spmd_fixture(cfg.world, tmp, seed=cfg.seed)
            out = audit_plan_dir_spmd(tmp)
        failures = out["failures"]
    out["run_health"] = health.finish(
        "; ".join(failures) if failures else None,
        wedge="stage_failure" if failures else None,
    )
    log.write(out)
    print(json.dumps(out, indent=cfg.indent or None))
    if failures:
        raise SystemExit("spmd audit FAILED: " + "; ".join(failures[:10]))
    return out


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    from dgraph_tpu.utils.cli import parse_config

    @dataclasses.dataclass
    class Config:
        """Cross-rank SPMD divergence auditor (``--selftest`` runs the
        2/4-shard + shrink-generation audits plus the seeded-divergence
        vacuity mutants; default audits a fresh ``--world`` fixture)."""

        selftest: bool = False
        world: int = 2
        seed: int = 0
        log_path: str = "logs/analysis.jsonl"
        indent: int = 0

    main(parse_config(Config))
