"""Lowered-artifact auditor: verify the StableHLO the programs actually
lower to — collective schedule, operand bytes, and donation survival.

The trace auditor (:mod:`dgraph_tpu.analysis.trace`) stops at the jaxpr:
it proves the *traced* program emits the collective schedule
``obs.footprint`` prices. But the artifact XLA compiles is one level
lower, and two things can change between jaxpr and StableHLO:

- **XLA-materialized collectives.** A wrong out-spec can make the
  partitioner insert a full ``all_gather`` that no jaxpr-level check sees
  (GC3 in PAPERS.md treats the compiled collective schedule as an
  artifact to verify, not hope about).
- **Donation.** ``donate_argnums`` is jit metadata at the jaxpr level;
  whether it survives is decided at lowering, where each honored donation
  becomes a ``jax.buffer_donor`` / ``tf.aliasing_output`` entry on a
  ``main`` argument. A dropped donation (an output shape drifted away
  from its donated input) costs the full params+opt_state footprint of
  peak HBM and raises no error anywhere.

So this tier lowers every (program, halo lowering) pair with
``jit(...).lower()`` — **lower-only, never ``.compile()``**: StableHLO
emission is a host-side MLIR build, zero XLA compiles, zero device
buffers (the rule ``tests/README.md`` documents) — and walks the module:

- collective op kinds/counts match the planned schedule (``all_to_all``
  count == exchange legs; ``collective_permute`` count == legs *
  num_halo_deltas);
- ``replica_groups`` / ``source_target_pairs`` are exactly the graph-axis
  groups / live-delta rings the plan schedules;
- per-operand bytes equal ``obs.footprint``'s pricing at the LOWERED
  width/dtype (the numbers the tuner ranks on, re-pinned below the
  jaxpr);
- **no collective the plan didn't schedule** — any other ``all_gather``
  / ``reduce_scatter`` / ``collective_broadcast``, or a second transport
  family in one program, is drift;
- no ``all_reduce`` on a sub-32-bit dtype (fp32 accumulation at the
  artifact level);
- donation survives lowering (donor-entry count == donated leaves, and
  every donor argument's type is covered by an output type, so XLA can
  actually alias it).

Everything here assumes the virtual-CPU backend the analysis CLI pins.

One reader below works a level further down, on the text of a COMPILED
module (``compiled.as_text()``, e.g. of a step compiled for a described
TPU as ``benchmark/tools/rehearse_w4.py`` does):
:func:`gather_table_placement` lists every row gather with the memory
space the compiler gave its table. Whether a table was placed on chip is
decided by the compiler's memory assignment and is in no jaxpr and no
StableHLO; it is worth 4.3 against 24.8 ms a gather (PERF.md, PR 31).
"""

from __future__ import annotations

import math
import re
from typing import Optional

from dgraph_tpu.analysis.trace import (
    HALO_IMPLS,
    PROGRAMS,
    _expected_bytes,
    build_audit_workload,
)

__all__ = [
    "collect_stablehlo",
    "lower_program",
    "audit_workload_hlo",
    "donation_entries",
    "hlo_drift_record",
    "gather_table_placement",
    "placement_line",
]

# StableHLO ops that move data across devices; anything here that the
# plan didn't schedule is drift
COLLECTIVE_HLO_OPS = (
    "all_to_all",
    "collective_permute",
    "all_gather",
    "all_reduce",
    "reduce_scatter",
    "collective_broadcast",
)

# MLIR element type -> (numpy-ish dtype name, itemsize)
_MLIR_DTYPES = {
    "f64": ("float64", 8), "f32": ("float32", 4),
    "bf16": ("bfloat16", 2), "f16": ("float16", 2),
    "i64": ("int64", 8), "i32": ("int32", 4),
    "i16": ("int16", 2), "i8": ("int8", 1), "i1": ("bool", 1),
    "ui64": ("uint64", 8), "ui32": ("uint32", 4), "ui8": ("uint8", 1),
    # fp8 wire payloads ride collectives bitcast to ui8, but the e4m3
    # element type itself can appear in surrounding compute
    "f8E4M3FN": ("float8_e4m3fn", 1),
}


def _elt_info(elt: str) -> tuple:
    return _MLIR_DTYPES.get(elt, (elt, 0))


def lower_program(fn, args):
    """``jit(...).lower`` the program — the ONE sanctioned way to produce
    the artifact this tier audits. ``fn`` must already be jitted (every
    registered program builder returns a jitted callable); the call never
    compiles and never touches a device buffer."""
    if not hasattr(fn, "lower"):
        raise TypeError(
            f"HLO audit needs a jitted program (got {type(fn).__name__}); "
            f"the registered builders return jit-wrapped steps precisely "
            f"so this tier can lower them without compiling"
        )
    return fn.lower(*args)


def _dense_2d(attr) -> Optional[list]:
    """DenseIntElementsAttr -> list of rows (replica_groups /
    source_target_pairs are always rank-2)."""
    from jaxlib.mlir import ir

    if attr is None:
        return None
    dense = ir.DenseIntElementsAttr(attr)
    shape = ir.ShapedType(dense.type).shape
    vals = list(dense)
    if len(shape) != 2:
        return [vals]
    it = iter(vals)
    return [[next(it) for _ in range(shape[1])] for _ in range(shape[0])]


def collect_stablehlo(lowered) -> dict:
    """One recursive walk over the lowered StableHLO module: every
    collective op (operand shape/dtype/bytes + replica_groups /
    source_target_pairs) and the ``main`` function's donation entries
    (``jax.buffer_donor`` / ``tf.aliasing_output`` argument attributes)
    and result types."""
    from jaxlib.mlir import ir

    module = lowered.compiler_ir(dialect="stablehlo")
    out = {k: [] for k in COLLECTIVE_HLO_OPS}
    donation = {"donor_args": [], "alias_args": 0, "result_types": []}

    def tensor_info(t):
        rt = ir.RankedTensorType(t)
        shape = tuple(int(s) for s in rt.shape)
        elt = str(rt.element_type)
        np_dtype, nbytes = _elt_info(elt)
        return shape, elt, np_dtype, int(math.prod(shape)) * nbytes

    def visit(op):
        name = op.name
        if name == "func.func":
            sym = ir.StringAttr(op.attributes["sym_name"]).value
            if sym == "main":
                ftype = ir.FunctionType(
                    ir.TypeAttr(op.attributes["function_type"]).value
                )
                donation["result_types"] = [
                    tensor_info(t)[:2] for t in ftype.results
                ]
                if "arg_attrs" in op.attributes:
                    args = ir.ArrayAttr(op.attributes["arg_attrs"])
                    for i, d in enumerate(args):
                        dd = ir.DictAttr(d)
                        if "tf.aliasing_output" in dd:
                            donation["alias_args"] += 1
                        elif "jax.buffer_donor" in dd:
                            donation["donor_args"].append(
                                tensor_info(ftype.inputs[i])[:2]
                            )
        elif name.startswith("stablehlo."):
            kind = name[len("stablehlo."):]
            if kind in out and op.operands:
                shape, elt, np_dtype, nbytes = tensor_info(
                    op.operands[0].type
                )
                attrs = {name: op.attributes[name] for name in op.attributes}
                out[kind].append({
                    "op": kind,
                    "shape": shape,
                    "dtype": np_dtype,
                    "elt": elt,
                    "bytes": nbytes,
                    "replica_groups": _dense_2d(attrs.get("replica_groups")),
                    "source_target_pairs": _dense_2d(
                        attrs.get("source_target_pairs")
                    ),
                })
        for region in op.regions:
            for block in region.blocks:
                for child in block.operations:
                    visit(child.operation)

    visit(module.operation)
    out["donation"] = donation
    return out


def donation_entries(lowered) -> dict:
    """Just the donation slice of :func:`collect_stablehlo` (for callers
    that only need the donor/alias census)."""
    return collect_stablehlo(lowered)["donation"]


# ---------------------------------------------------------------------------
# expected schedule (groups / pairs are in linearized mesh-device order)
# ---------------------------------------------------------------------------


def _mesh_dims(mesh) -> tuple:
    from dgraph_tpu.comm.mesh import GRAPH_AXIS

    shape = dict(mesh.shape)
    W = shape[GRAPH_AXIS]
    R = max(1, math.prod(s for a, s in shape.items() if a != GRAPH_AXIS))
    return R, W


def _graph_groups(R: int, W: int) -> list:
    return [[r * W + g for g in range(W)] for r in range(R)]


def _permute_pair_sets(R: int, W: int, deltas) -> dict:
    """frozenset of (src, tgt) pairs -> "d{delta}{fwd|rev}" label for every
    live delta in both put directions — a traced permute must match one."""
    sets = {}
    for d in deltas:
        for sign, tag in ((1, "fwd"), (-1, "rev")):
            pairs = frozenset(
                (r * W + i, r * W + ((i + sign * d) % W))
                for r in range(R)
                for i in range(W)
            )
            sets[pairs] = f"d{d}:{tag}"
    return sets


def _audit_one_lowering(
    label: str,
    impl: str,
    lowered,
    plan,
    mesh,
    failures: list,
    coll: Optional[dict] = None,
) -> dict:
    """Verify one program's lowered module against the planned schedule;
    returns the program record (and appends failures). Pass a
    pre-collected ``coll`` to share one module walk with the donation
    check."""
    coll = collect_stablehlo(lowered) if coll is None else coll
    R, W = _mesh_dims(mesh)
    deltas = tuple(plan.halo_deltas)
    n_deltas = len(deltas)
    S = plan.halo.s_pad
    groups = _graph_groups(R, W)
    pair_sets = _permute_pair_sets(R, W, deltas)

    def fail(msg):
        failures.append(f"[hlo:{label}/{impl}] {msg}")

    # no XLA-materialized collective the plan didn't schedule: no
    # lowering gathers, so every all_gather is one
    for rec in coll["all_gather"]:
        fail(
            f"unscheduled all_gather of {rec['shape']} ({rec['dtype']}, "
            f"{rec['bytes']} B) in the lowered module — XLA materialized a "
            f"collective the plan never scheduled (wrong out-spec?)"
        )
    for kind in ("reduce_scatter", "collective_broadcast"):
        for rec in coll[kind]:
            fail(
                f"unscheduled {kind} of {rec['shape']} ({rec['dtype']}) "
                f"in the lowered module"
            )

    # exactly one transport family per lowered program
    n_a2a = len(coll["all_to_all"])
    n_cp = len(coll["collective_permute"])
    counts = {"all_to_all": n_a2a, "ppermute": n_cp}
    families = [name for name, count in counts.items() if count]
    want_family = "all_to_all" if impl == "all_to_all" else "ppermute"
    if len(families) > 1:
        fail(
            "mixed transport families in ONE lowered program: "
            + " + ".join(families)
        )
    for fam, count in counts.items():
        if fam != want_family and count:
            fail(
                f"pinned lowering {impl!r} but the module contains {count} "
                f"{fam} op(s)"
            )
    if not counts[want_family]:
        fail(f"pinned lowering {impl!r} lowered no {want_family} ops at all")

    # per-operand bytes == obs.footprint's pricing at the LOWERED
    # width/dtype, and groups/pairs == the planned schedule
    operand_rows = []
    for rec in coll["all_to_all"]:
        F = rec["shape"][-1] if rec["shape"] else 0
        want = _expected_bytes(plan, rec["dtype"], F)["a2a_operand_bytes"]
        operand_rows.append({**{k: rec[k] for k in ("op", "shape", "dtype", "bytes")},
                             "footprint_bytes": want})
        if rec["bytes"] != want:
            fail(
                f"all_to_all operand {rec['shape']} ({rec['dtype']}) is "
                f"{rec['bytes']} B lowered; footprint prices {want} B"
            )
        if rec["replica_groups"] != groups:
            fail(
                f"all_to_all replica_groups {rec['replica_groups']} != "
                f"planned graph-axis groups {groups}"
            )
    for rec in coll["collective_permute"]:
        F = rec["shape"][-1] if rec["shape"] else 0
        want = _expected_bytes(plan, rec["dtype"], F)["ppermute_round_bytes"]
        operand_rows.append({
            **{k: rec[k] for k in ("op", "shape", "dtype", "bytes")},
            "footprint_bytes": want,
        })
        if rec["bytes"] != want:
            fail(
                f"collective_permute operand {rec['shape']} "
                f"({rec['dtype']}) is {rec['bytes']} B lowered; "
                f"footprint prices {want} B per round"
            )
        pairs = frozenset(map(tuple, rec["source_target_pairs"] or []))
        if pairs not in pair_sets:
            fail(
                f"collective_permute pairs {sorted(pairs)} match no "
                f"live delta ring of the plan (deltas={deltas}, W={W})"
            )
    # fp32 accumulation at the artifact level: reductions never run
    # sub-32-bit (bf16 may ride the wire; all_reduce must not)
    narrow = [
        r for r in coll["all_reduce"]
        if r["dtype"] in ("bfloat16", "float16")
    ]
    if narrow:
        fail(
            f"all_reduce on a sub-32-bit dtype in the lowered module: "
            f"{[(r['shape'], r['dtype']) for r in narrow[:4]]}"
        )

    return {
        "program": label,
        "impl": impl,
        "num_all_to_all": n_a2a,
        "num_collective_permute": n_cp,
        "num_all_reduce": len(coll["all_reduce"]),
        "collective_operands": operand_rows,
        "s_pad": int(S),
        "num_halo_deltas": n_deltas,
    }


def _donation_failures(don: dict, expected_donors: int, label: str,
                       failures: list) -> dict:
    """Donation must survive lowering: donor-entry count == donated
    leaves, and every donor argument's (shape, dtype) covered by an
    output — otherwise XLA drops the alias at compile time and peak HBM
    grows by the donated footprint. ``don`` is the donation slice of an
    already-collected module walk (:func:`donation_entries` /
    ``collect_stablehlo(...)["donation"]``) — callers that walked the
    module once don't pay a second recursive pass."""
    from collections import Counter

    declared = don["alias_args"] + len(don["donor_args"])
    rec = {
        "expected_donors": int(expected_donors),
        "donor_args": declared,
        "alias_args": don["alias_args"],
        "uncovered": [],
    }
    if declared != expected_donors:
        failures.append(
            f"[hlo:{label}] {declared} donation entrie(s) survived lowering;"
            f" {expected_donors} leaves were donated — donation dropped "
            f"before XLA ever saw it"
        )
    produced = Counter(don["result_types"])
    for t in don["donor_args"]:
        if produced.get(t, 0) > 0:
            produced[t] -= 1
        else:
            rec["uncovered"].append({"shape": list(t[0]), "elt": t[1]})
    if rec["uncovered"]:
        failures.append(
            f"[hlo:{label}] donated argument type(s) with no matching "
            f"output in the lowered module (XLA will drop the alias): "
            f"{rec['uncovered'][:4]}"
        )
    return rec


def _jit_cache_entries(fn) -> Optional[int]:
    """The jitted program's executable-cache size — MUST stay 0 across
    this tier (lower-only; a ``.compile()`` sneaking in shows up here and
    turns the audit red). Returns None when the probe itself is
    unavailable (jax moved the private ``_cache_size``) — the caller
    treats that as a FAILURE, not a pass: a contract that silently stops
    being checked is worse than one that loudly asks for an update."""
    cache_size = getattr(fn, "_cache_size", None)
    if not callable(cache_size):
        return None
    try:
        return int(cache_size())
    except Exception:
        return None


def audit_workload_hlo(
    w,
    impls=HALO_IMPLS,
    programs=None,
) -> dict:
    """Lower every (program, halo lowering) pair and verify the full
    post-lowering contract; returns a ``kind="hlo_audit"`` report dict
    (same caller contract as :func:`~dgraph_tpu.analysis.trace.
    audit_workload`: ``ok`` + ``failures``, the caller decides whether to
    raise)."""
    import jax

    from dgraph_tpu import config as _cfg

    failures: list = []
    program_records = []
    legs: dict = {}
    donation = None
    saved = (_cfg.halo_impl, _cfg.tuned_halo_impl)
    try:
        for impl in impls:
            _cfg.set_flags(halo_impl=impl, tuned_halo_impl=None)
            for label, build in (programs or PROGRAMS).items():
                fn, args = build(w)
                lowered = lower_program(fn, args)
                coll = collect_stablehlo(lowered)
                rec = _audit_one_lowering(
                    label, impl, lowered, w.plan_np, w.mesh, failures,
                    coll=coll,
                )
                rec["jit_cache_entries"] = _jit_cache_entries(fn)
                if rec["jit_cache_entries"] is None:
                    failures.append(
                        f"[hlo:{label}/{impl}] jit-cache probe unavailable "
                        f"(jax moved _cache_size?) — the lower-only "
                        f"contract is unenforceable; update analysis.hlo "
                        f"for this jax version"
                    )
                elif rec["jit_cache_entries"]:
                    failures.append(
                        f"[hlo:{label}/{impl}] jit cache holds "
                        f"{rec['jit_cache_entries']} executable(s) after a "
                        f"lower-only audit — something compiled"
                    )
                program_records.append(rec)
                if impl == "all_to_all":
                    legs[label] = rec["num_all_to_all"]
                    if label == "train_step":
                        donated = len(jax.tree.leaves((w.params, w.opt_state)))
                        donation = _donation_failures(
                            coll["donation"], donated, f"{label}/{impl}",
                            failures,
                        )
    finally:
        _cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1])

    # cross-lowering count pins, mirrored from the trace tier but against
    # the LOWERED ops: legs measured from the all_to_all-pinned module
    n_deltas = len(w.plan_np.halo_deltas)
    for rec in program_records:
        if rec["impl"] == "all_to_all" or rec["program"] not in legs:
            continue
        want = legs[rec["program"]] * n_deltas
        if rec["num_collective_permute"] != want:
            failures.append(
                f"[hlo:{rec['program']}/{rec['impl']}] "
                f"{rec['num_collective_permute']} collective_permutes "
                f"lowered; expected legs({legs[rec['program']]}) * "
                f"num_halo_deltas({n_deltas}) = {want}"
            )

    return {
        "kind": "hlo_audit",
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


def hlo_drift_record(
    world_size: int = 8, *, num_nodes: int = 4096, num_edges: int = 16384,
    feat_dim: int = 32, seed: int = 0,
) -> dict:
    """Compact lowered-schedule comparison for bench's no-healthy-chip
    fallback (ROADMAP item 5, third non-null tier beside
    ``schedule_drift`` and ``cpu_scan_delta``): the TRAIN step only, one
    row per halo lowering with lowered-vs-footprint bytes plus the
    donation census, so a wedged round still lands a non-null signal
    about the artifact XLA would have compiled."""
    from dgraph_tpu.analysis.trace import _train_program

    w = build_audit_workload(
        world_size, num_nodes=num_nodes, num_edges=num_edges,
        feat_dim=feat_dim, seed=seed,
    )
    report = audit_workload_hlo(w, programs={"train_step": _train_program})
    per_impl = {}
    for rec in report["programs"]:
        ops = rec["collective_operands"]
        per_impl[rec["impl"]] = {
            "collective_count": len(ops),
            "lowered_bytes": sum(o["bytes"] for o in ops),
            "footprint_bytes": sum(o["footprint_bytes"] for o in ops),
        }
    return {
        "kind": "hlo_drift",
        "workload": {
            "world_size": world_size, "nodes": num_nodes, "edges": num_edges,
            "feat_dim": feat_dim, "seed": seed,
        },
        "num_halo_deltas": report["num_halo_deltas"],
        "train_step_by_impl": per_impl,
        "donation": report["donation"],
        "failures": report["failures"],
        "drift": not report["ok"],
    }


# ---------------------------------------------------------------------------
# compiled-module reader: where each row gather's table lives
# ---------------------------------------------------------------------------

_HLO_ITEMSIZE = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
# `%name = dtype[dims]{layout} opcode(operands...), attrs` — the layout has
# no blank in it, so it ends at the first one
_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>\S+) = (?P<dtype>\w+)\[(?P<dims>[\d,]*)\]"
    r"(?P<layout>\{\S*\})? (?P<op>[\w-]+)\((?P<rest>.*)$"
)


def gather_table_placement(compiled_text: str) -> list:
    """Every row gather of a compiled module (``compiled.as_text()``): a
    ``gather`` that takes whole rows (``slice_sizes={1, C}``) of a 2-D
    ``[N, C]`` table. One dict a gather, in module order: ``gather`` and
    ``computation`` names, ``table_shape``, ``table_dtype``,
    ``table_bytes``, ``rows`` (taken), ``memory_space`` of the table as
    the gather's computation sees it (0 = HBM; on a TPU ``S(1)`` is
    on-chip memory) and ``op_name`` from the metadata. A fused gather's
    table is ``parameter(0)`` of its fused computation, and that
    parameter's layout carries the space the operand was assigned."""
    out = []
    computation, defs = None, {}
    for line in compiled_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and " -> " in stripped:
            head = stripped.split(" (", 1)[0].split()
            computation, defs = head[-1].lstrip("%"), {}
            continue
        m = _HLO_INSTR.match(line)
        if m is None:
            continue
        defs[m["name"]] = m
        if m["op"] != "gather":
            continue
        table = defs.get(m["rest"].split(",", 1)[0].strip().lstrip("%"))
        sizes = re.search(r"slice_sizes=\{([\d,]*)\}", m["rest"])
        if table is None or sizes is None:
            continue
        shape = tuple(int(d) for d in table["dims"].split(",") if d)
        if len(shape) != 2 or sizes[1] != f"1,{shape[1]}":
            continue
        space = re.search(r"S\((\d+)\)", table["layout"] or "")
        op_name = re.search(r'op_name="([^"]*)"', m["rest"])
        out.append({
            "gather": m["name"],
            "computation": computation,
            "table_shape": shape,
            "table_dtype": table["dtype"],
            "table_bytes": shape[0] * shape[1]
            * _HLO_ITEMSIZE.get(table["dtype"], 0),
            "rows": math.prod(int(d) for d in m["dims"].split(",") if d)
            // max(shape[1], 1),
            "memory_space": int(space[1]) if space else 0,
            "op_name": op_name[1] if op_name else "",
        })
    return out


def placement_line(gathers: list) -> str:
    """``gather tables on chip: k of n`` over ``gathers``, with the sizes
    of those left in HBM. An ``[E, C]`` edge tensor among them is a
    backward gather by ``halo_sort_perm``: it can never be placed, and
    since PR 33 the fused GCN layer's backward has none (it gathers from
    ``[n_owner_pad, C]`` vertex tables instead)."""
    left = [g for g in gathers if g["memory_space"] == 0]
    line = f"gather tables on chip: {len(gathers) - len(left)} of {len(gathers)}"
    if left:
        tables = sorted({(g["table_shape"], g["table_dtype"], g["table_bytes"])
                         for g in left})
        line += "; in HBM: " + ", ".join(
            f"{dt}[{r},{c}] ({nbytes / 1e6:.1f} MB)"
            for (r, c), dt, nbytes in tables)
    return line
