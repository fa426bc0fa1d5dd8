"""Trace auditor: verify the traced collective schedule against the one
``obs.footprint`` priced.

The tuner auto-adopts configs ranked by :func:`dgraph_tpu.obs.footprint.
plan_footprint`'s analytic schedule — collective op counts and operand byte
volumes computed on host from the plan alone.  Nothing, until this module,
checked that the program jax actually traces emits *that* schedule: a
lowering regression (a stray all_to_all on the ppermute path, a halo
exchange that silently upcast its operand, a second collective sneaking
into one leg) would leave the tuner ranking fiction.  "Memory-efficient
array redistribution" (PAPERS.md) treats the emitted collective schedule as
a verifiable artifact; this is that check for dgraph_tpu.

Everything here is ABSTRACT: programs are traced with ``jax.make_jaxpr`` /
``jax.eval_shape`` over ``ShapeDtypeStruct``/numpy operands — zero XLA
compiles, zero device buffers, so the audit runs in tier-1 and in the
bench's no-healthy-chip fallback at interactive speed.

Per (program, halo lowering) the auditor verifies:

- **schedule**: collective op counts and per-operand bytes match
  ``plan_footprint`` at the traced feature width/dtype (``all_to_all``
  operands == the padded ``[W, S, F]`` block; each ``ppermute`` round ==
  one ``[S, F]`` block; round count == ``legs * num_halo_deltas`` where
  ``legs`` is measured from the all_to_all-pinned trace of the same
  program);
- **single lowering**: exactly one halo-lowering family per traced
  program — the PR 4 mixed-lowering hazard, machine-checked;
- **no host callbacks** inside traced code;
- **fp32 accumulation**: no ``psum``-family collective runs on a
  sub-32-bit dtype (bf16 may ride the wire; reductions must not);
- **donation**: every donated buffer's (shape, dtype) is matched by an
  output — otherwise the donation is silently dropped and peak HBM grows
  by the full params+opt_state footprint.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional

from dgraph_tpu.plan import HALO_IMPLS  # the one list of lowerings

# psum family across jax versions: 'psum' (0.6+), 'psum2'/'pbroadcast'
# (0.4.x shard_map rewrite); pmean lowers through psum
PSUM_PRIMS = ("psum", "psum2", "psum_invariant", "pmean")
HALO_PRIMS = ("all_to_all", "ppermute")
CALLBACK_PRIMS = (
    "pure_callback", "io_callback", "debug_callback", "outside_call",
    "host_callback_call", "python_callback",
)


def walk_eqns(jaxpr, visit) -> None:
    """Call ``visit(eqn)`` on every eqn, recursing into sub-jaxprs
    (pjit/shard_map/custom_vjp/custom_jvp/scan/remat bodies). The ONE
    canonical traversal — the dtype-discipline tests and every collector
    below share it, so descent logic cannot drift between checks."""
    for eqn in jaxpr.eqns:
        visit(eqn)
        for p in eqn.params.values():
            for item in p if isinstance(p, (list, tuple)) else [p]:
                inner = getattr(item, "jaxpr", None)
                if inner is not None:
                    walk_eqns(getattr(inner, "jaxpr", inner), visit)
                elif hasattr(item, "eqns"):
                    walk_eqns(item, visit)


def aval_bytes(aval) -> int:
    from dgraph_tpu.plan import dtype_nbytes

    shape = tuple(getattr(aval, "shape", ()) or ())
    return int(math.prod(shape)) * dtype_nbytes(aval.dtype)


def collect_collectives(jaxpr) -> dict:
    """One pass over a (closed) jaxpr: every halo collective / psum /
    host-callback eqn with operand shapes, dtypes, and bytes."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out = {"all_to_all": [], "ppermute": [], "psum": [], "callbacks": []}

    def visit(eqn):
        name = eqn.primitive.name
        if name in HALO_PRIMS:
            key = name
        elif name in PSUM_PRIMS:
            key = "psum"
        elif name in CALLBACK_PRIMS:
            key = "callbacks"
        else:
            return
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            # scalars have shape () and still count (the loss psum is one);
            # only truly shapeless vars (tokens etc.) are skipped
            if aval is None or not hasattr(aval, "shape"):
                if key == "callbacks":
                    out[key].append({"primitive": name})
                continue
            out[key].append({
                "primitive": name,
                "shape": tuple(int(s) for s in aval.shape),
                "dtype": str(aval.dtype),
                "bytes": aval_bytes(aval),
            })

    walk_eqns(jaxpr, visit)
    return out


# ---------------------------------------------------------------------------
# audit workload: a small sharded GCN train/eval/serve triple
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AuditWorkload:
    """Everything needed to trace the three program kinds abstractly."""

    model: Any
    optimizer: Any
    mesh: Any
    plan: Any          # numpy-leaf EdgePlan (stacked [W] layout)
    plan_np: Any       # same object, kept for footprint accounting
    batch: dict        # numpy leaves, leading [W]
    params: Any        # ShapeDtypeStruct pytree
    opt_state: Any     # ShapeDtypeStruct pytree
    world_size: int
    feat_dim: int
    num_nodes: int
    serve_bucket: int = 8


def workload_from_plan(
    plan,
    *,
    feat_dim: int = 8,
    hidden: int = 16,
    num_classes: int = 4,
    num_layers: int = 2,
    seed: int = 0,
    compute_dtype: Optional[str] = "bfloat16",
    devices=None,
    batch: Optional[dict] = None,
    num_nodes: Optional[int] = None,
) -> AuditWorkload:
    """Scaffold the audit workload around an EXISTING ``[W]``-stacked
    plan: mesh, communicator, bf16-compute GCN, batch (zeros unless
    given — operand values never reach a lowered artifact), and abstract
    ``eval_shape`` params/opt_state.  The ONE builder
    :func:`build_audit_workload` and the cross-rank spmd tier's per-rank
    builds (:func:`dgraph_tpu.analysis.spmd.build_rank_workload`) both
    go through, so the tiers can never audit different workload shapes.
    Nothing here compiles and nothing touches a device buffer."""
    import numpy as np
    import jax
    import optax

    from dgraph_tpu.comm import Communicator
    from dgraph_tpu.comm.mesh import (
        GRAPH_AXIS, make_graph_mesh, plan_in_specs, squeeze_plan,
    )
    from dgraph_tpu.models import GCN
    from jax.sharding import PartitionSpec as P

    world_size = int(plan.world_size)
    if devices is None:
        devices = jax.devices()
    if len(devices) < world_size:
        raise ValueError(
            f"audit for world_size={world_size} needs that many "
            f"devices; have {len(devices)} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8 before jax's first "
            f"backend touch)"
        )
    mesh = make_graph_mesh(
        ranks_per_graph=world_size, devices=devices[:world_size]
    )
    comm = Communicator.init_process_group("tpu", world_size=world_size)
    dt = None
    if compute_dtype and compute_dtype not in ("float32", "f32"):
        import jax.numpy as jnp

        dt = {"bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
              "float16": jnp.float16}[compute_dtype]
    model = GCN(
        hidden_features=hidden, out_features=num_classes, comm=comm,
        num_layers=num_layers, dtype=dt,
    )
    n_pad = int(plan.n_src_pad)
    if batch is None:
        batch = {
            "x": np.zeros((world_size, n_pad, feat_dim), np.float32),
            "y": np.zeros((world_size, n_pad), np.int32),
            "mask": np.ones((world_size, n_pad), np.float32),
        }

    def init_body(b, p):
        ps = squeeze_plan(p)
        bb = jax.tree.map(lambda leaf: leaf[0], b)
        return model.init(jax.random.key(seed), bb["x"], ps)

    from dgraph_tpu.comm.collectives import shard_map_checks

    bspecs = jax.tree.map(lambda _: P(GRAPH_AXIS), batch)
    init_fn = jax.shard_map(
        init_body, mesh=mesh, in_specs=(bspecs, plan_in_specs(plan)),
        out_specs=P(),
        **shard_map_checks(relax="init outputs replicated by construction"),
    )
    params = jax.eval_shape(init_fn, batch, plan)
    optimizer = optax.adam(1e-2)
    opt_state = jax.eval_shape(optimizer.init, params)
    return AuditWorkload(
        model=model, optimizer=optimizer, mesh=mesh, plan=plan, plan_np=plan,
        batch=batch, params=params, opt_state=opt_state,
        world_size=world_size, feat_dim=feat_dim,
        num_nodes=num_nodes if num_nodes is not None
        else world_size * n_pad,
    )


def build_audit_workload(
    world_size: int = 2,
    *,
    num_nodes: int = 48,
    num_edges: int = 300,
    feat_dim: int = 8,
    hidden: int = 16,
    num_classes: int = 4,
    num_layers: int = 2,
    seed: int = 0,
    compute_dtype: Optional[str] = "bfloat16",
    devices=None,
) -> AuditWorkload:
    """Host-build the canonical audit workload: a ``world_size``-shard
    random graph (with the interior/boundary split, so all three lowerings
    are legal) and a bf16-compute GCN — bf16 makes the fp32-accumulation
    check bite.  No device arrays: params/opt_state are
    ``ShapeDtypeStruct`` trees from ``eval_shape`` and the batch is plain
    numpy, so tracing compiles nothing."""
    import numpy as np

    from dgraph_tpu import plan as pl

    rng = np.random.default_rng(seed)
    part = np.sort(rng.integers(0, world_size, num_nodes)).astype(np.int32)
    edges = np.stack([
        rng.integers(0, num_nodes, num_edges),
        rng.integers(0, num_nodes, num_edges),
    ])
    plan, layout = pl.build_edge_plan(
        edges, part, world_size=world_size, overlap=True
    )
    x = pl.shard_vertex_data(
        rng.normal(size=(num_nodes, feat_dim)).astype(np.float32),
        layout.src_counts, plan.n_src_pad,
    )
    batch = {
        "x": x,
        "y": np.zeros((world_size, plan.n_src_pad), np.int32),
        "mask": np.ones((world_size, plan.n_src_pad), np.float32),
    }
    return workload_from_plan(
        plan, feat_dim=feat_dim, hidden=hidden, num_classes=num_classes,
        num_layers=num_layers, seed=seed, compute_dtype=compute_dtype,
        devices=devices, batch=batch, num_nodes=num_nodes,
    )


# ---------------------------------------------------------------------------
# program builders (fresh per lowering: jit's trace cache would otherwise
# replay the first lowering it saw — exactly the class of staleness the
# auditor exists to expose)
# ---------------------------------------------------------------------------


def _train_program(w: AuditWorkload):
    from dgraph_tpu.train.loop import make_train_step

    step = make_train_step(w.model, w.optimizer, w.mesh, w.plan)
    return step, (w.params, w.opt_state, w.batch, w.plan)


def _eval_program(w: AuditWorkload):
    from dgraph_tpu.train.loop import make_eval_step

    step = make_eval_step(w.model, w.mesh)
    return step, (w.params, w.batch, w.plan)


def _serve_program(w: AuditWorkload):
    """The engine's per-bucket forward, built by the REAL
    :class:`~dgraph_tpu.serve.engine.ServeEngine` construction path (so
    serve semantics cannot drift from what is audited), traced with
    abstract operands."""
    import numpy as np
    import jax

    from dgraph_tpu.serve.bucketing import BucketLadder
    from dgraph_tpu.serve.engine import ServeEngine

    params_zero = jax.tree.map(
        lambda s: np.zeros(s.shape, s.dtype), w.params
    )
    engine = ServeEngine(
        w.model, w.mesh, w.plan, params_zero,
        {"x": w.batch["x"]},
        id_rank=np.zeros(w.num_nodes, np.int32),
        id_slot=np.zeros(w.num_nodes, np.int32),
        ladder=BucketLadder((w.serve_bucket,)),
    )
    fwd = engine._forwards[w.serve_bucket]
    idx = jax.ShapeDtypeStruct((w.serve_bucket,), np.int32)
    return fwd, (w.params, {"x": w.batch["x"]}, w.plan, idx, idx)


PROGRAMS = {
    "train_step": _train_program,
    "eval_step": _eval_program,
    "serve_forward": _serve_program,
}


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------


def _expected_bytes(plan, dtype: str, feat_dim: int) -> dict:
    """What obs.footprint prices for ONE exchange at this width/dtype:
    the padded all_to_all operand and the per-round ppermute block. Pulled
    from :func:`plan_footprint` itself (not re-derived) so the audit pins
    the exact numbers the tuner ranks on.

    ``dtype``/``feat_dim`` come from the TRACED collective operand. Under
    a non-identity wire format that operand is already encoded — bf16
    casts price themselves (footprint resolves the same format at the
    traced itemsize), but the fp8 operand is uint8 with the 4 scale lanes
    concatenated into its last axis, so the activation width is recovered
    before pricing (wire_row_bytes then reproduces the traced last-dim
    exactly)."""
    from dgraph_tpu.obs.footprint import plan_footprint
    from dgraph_tpu.wire.spec import FP8_SCALE_BYTES, resolve_wire_format

    wf, _src = resolve_wire_format(
        plan.world_size, tuple(plan.halo_deltas),
        plan_format=getattr(plan, "wire_format", "fp32"),
    )
    if wf == "fp8" and dtype == "uint8":
        feat_dim = feat_dim - FP8_SCALE_BYTES
    fp = plan_footprint(plan, dtype, feat_dim=feat_dim)
    ex = fp["collectives"]["halo_exchange"]
    n_deltas = fp["num_halo_deltas"]
    per_round = (
        fp["halo"]["wire_bytes_per_shard"]["ppermute"] // n_deltas
        if n_deltas else 0
    )
    return {
        "a2a_operand_bytes": ex["a2a_operand_bytes_per_shard"],
        "ppermute_round_bytes": per_round,
        "num_halo_deltas": n_deltas,
    }


def _audit_one_program(
    label: str, impl: str, fn: Callable, args: tuple, plan, failures: list,
) -> dict:
    """Trace one program under one pinned lowering and run the per-program
    checks; returns the program record (and appends to ``failures``)."""
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args)
    coll = collect_collectives(jaxpr)
    n_a2a, n_pp = len(coll["all_to_all"]), len(coll["ppermute"])

    def fail(msg):
        failures.append(f"[{label}/{impl}] {msg}")

    # exactly one halo-lowering family per traced program (PR 4 hazard)
    families_present = [f for f in HALO_PRIMS if coll[f]]
    if len(families_present) > 1:
        fail(
            f"mixed halo lowerings in ONE program: "
            + " + ".join(
                f"{len(coll[f])} {f}" for f in families_present
            )
            + " eqns (two legs of one op resolved differently)"
        )
    want_family = "all_to_all" if impl == "all_to_all" else "ppermute"
    for other in HALO_PRIMS:
        if other != want_family and coll[other]:
            fail(
                f"pinned lowering {impl!r} but the trace contains "
                f"{len(coll[other])} {other} eqn(s)"
            )
    if not coll[want_family]:
        fail(f"pinned lowering {impl!r} traced no {want_family} eqns at all")

    # operand bytes: every collective operand must be EXACTLY the block
    # obs.footprint prices at that operand's width/dtype
    byte_rows = []
    for rec in coll[want_family]:
        feat = rec["shape"][-1] if rec["shape"] else 0
        exp = _expected_bytes(plan, rec["dtype"], feat)
        want = {
            "all_to_all": exp["a2a_operand_bytes"],
            "ppermute": exp["ppermute_round_bytes"],
        }[want_family]
        byte_rows.append({
            "primitive": rec["primitive"], "shape": rec["shape"],
            "dtype": rec["dtype"], "traced_bytes": rec["bytes"],
            "footprint_bytes": want,
        })
        if rec["bytes"] != want:
            fail(
                f"{rec['primitive']} operand {rec['shape']} ({rec['dtype']})"
                f" carries {rec['bytes']} B; footprint prices {want} B — "
                f"the tuner is ranking a schedule the program does not emit"
            )

    # no host callbacks inside traced code
    if coll["callbacks"]:
        fail(
            f"host callback(s) inside the traced program: "
            f"{sorted({c['primitive'] for c in coll['callbacks']})}"
        )

    # fp32 accumulation: psum-family reductions must not run sub-32-bit
    narrow = [
        r for r in coll["psum"]
        if r["dtype"] in ("bfloat16", "float16")
    ]
    if narrow:
        fail(
            f"psum on a sub-32-bit dtype: "
            f"{[(r['shape'], r['dtype']) for r in narrow[:4]]} — fp32 "
            f"accumulation discipline broken"
        )

    return {
        "program": label,
        "impl": impl,
        "num_all_to_all": n_a2a,
        "num_ppermute": n_pp,
        "num_psum": len(coll["psum"]),
        "collective_operands": byte_rows,
    }


def donation_unmatched(fn, args, donated_tree) -> dict:
    """(shape, dtype) -> count of donated leaves with NO matching output
    leaf in ``jax.eval_shape(fn, *args)`` (abstract — never compiles).
    Empty dict == every donation can be honored."""
    import jax
    from collections import Counter

    out = jax.eval_shape(fn, *args)
    donated = Counter(
        (tuple(l.shape), str(l.dtype)) for l in jax.tree.leaves(donated_tree)
    )
    produced = Counter(
        (tuple(l.shape), str(l.dtype)) for l in jax.tree.leaves(out)
    )
    return {
        k: n - produced.get(k, 0)
        for k, n in donated.items()
        if n > produced.get(k, 0)
    }


def _audit_donation(w: AuditWorkload, failures: list) -> dict:
    """The train step donates (params, opt_state); every donated leaf's
    (shape, dtype) must be matched by an output leaf, or XLA drops the
    donation and peak HBM grows by the donated footprint."""
    import jax

    step, args = _train_program(w)
    unmatched = donation_unmatched(step, args, (w.params, w.opt_state))
    donated_count = len(jax.tree.leaves((w.params, w.opt_state)))
    if unmatched:
        failures.append(
            f"[train_step] donated buffers not consumed by any same-"
            f"shape/dtype output (donation silently dropped): "
            f"{dict(list(unmatched.items())[:4])}"
        )
    return {
        "donated_leaves": donated_count,
        "unmatched": [
            {"shape": list(k[0]), "dtype": k[1], "count": n}
            for k, n in unmatched.items()
        ],
    }


def audit_workload(
    w: AuditWorkload,
    impls=HALO_IMPLS,
    programs=None,
) -> dict:
    """Trace every (program, lowering) pair and verify the full contract.

    Returns an ``AuditReport`` dict (``kind="trace_audit"``); ``ok`` is
    False and ``failures`` names every drift.  The caller decides whether
    to raise (the CLI exits nonzero; bench's fallback just attaches it).
    """
    from dgraph_tpu import config as _cfg

    failures: list = []
    program_records = []
    legs: dict = {}
    saved = (_cfg.halo_impl, _cfg.tuned_halo_impl)
    try:
        for impl in impls:
            _cfg.set_flags(halo_impl=impl, tuned_halo_impl=None)
            for label, build in (programs or PROGRAMS).items():
                fn, args = build(w)
                rec = _audit_one_program(
                    label, impl, fn, args, w.plan_np, failures
                )
                program_records.append(rec)
                if impl == "all_to_all":
                    legs[label] = rec["num_all_to_all"]
    finally:
        _cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1])

    # cross-lowering count pin: the round-based lowerings must run exactly
    # legs * num_halo_deltas rounds, where legs is measured from the
    # all_to_all-pinned trace of the SAME program (model-agnostic: the
    # exchange-leg count is a property of the program, not the lowering)
    n_deltas = len(w.plan_np.halo_deltas)
    for rec in program_records:
        if rec["impl"] == "all_to_all" or rec["program"] not in legs:
            continue
        want = legs[rec["program"]] * n_deltas
        if rec["num_ppermute"] != want:
            failures.append(
                f"[{rec['program']}/{rec['impl']}] {rec['num_ppermute']} "
                f"ppermute rounds; expected legs({legs[rec['program']]}) * "
                f"num_halo_deltas({n_deltas}) = {want}"
            )

    donation = _audit_donation(w, failures)
    return {
        "kind": "trace_audit",
        "world_size": w.world_size,
        "num_nodes": w.num_nodes,
        "num_halo_deltas": n_deltas,
        "impls": list(impls),
        "exchange_legs": legs,
        "programs": program_records,
        "donation": donation,
        "failures": failures,
        "ok": not failures,
    }


def schedule_drift_record(
    world_size: int = 8, *, num_nodes: int = 4096, num_edges: int = 16384,
    feat_dim: int = 32, seed: int = 0,
) -> dict:
    """Compact footprint-vs-traced comparison for bench's no-healthy-chip
    fallback tier (ROADMAP item 5): one record per halo lowering with the
    traced and footprint-priced bytes, so a round that never reaches a
    chip still lands a non-null schedule-drift signal."""
    w = build_audit_workload(
        world_size, num_nodes=num_nodes, num_edges=num_edges,
        feat_dim=feat_dim, seed=seed,
    )
    report = audit_workload(w)
    per_impl = {}
    for rec in report["programs"]:
        if rec["program"] != "train_step":
            continue
        ops = rec["collective_operands"]
        per_impl[rec["impl"]] = {
            "collective_count": len(ops),
            "traced_bytes": sum(o["traced_bytes"] for o in ops),
            "footprint_bytes": sum(o["footprint_bytes"] for o in ops),
        }
    return {
        "kind": "schedule_drift",
        "workload": {
            "world_size": world_size, "nodes": num_nodes, "edges": num_edges,
            "feat_dim": feat_dim, "seed": seed,
        },
        "num_halo_deltas": report["num_halo_deltas"],
        "train_step_by_impl": per_impl,
        "failures": report["failures"],
        "drift": not report["ok"],
    }
