"""Lowered-artifact auditor (ISSUE 12).

Everything here is **lower-only**: programs reach StableHLO through
``jit(...).lower()`` — zero new XLA compiles (asserted explicitly via
the jit-cache counter below; the budget rule tests/README.md documents).
The clean-tree GREEN pins run the full 2- and 4-shard audits across
every halo lowering; the vacuity guards prove the tier still goes RED on
seeded drift — a seeded extra all-gather and a dropped donation, plus
the raw-``shard_map`` lint shape.
"""

import warnings

import pytest

from dgraph_tpu.analysis import hlo as H
from dgraph_tpu.analysis import lint as L


@pytest.fixture(scope="module")
def workload2():
    from dgraph_tpu.analysis.trace import build_audit_workload

    return build_audit_workload(2)


@pytest.fixture(scope="module")
def workload4():
    from dgraph_tpu.analysis.trace import build_audit_workload

    return build_audit_workload(4)


# ---------------------------------------------------------------------------
# clean-tree GREEN pins (2- and 4-shard, every lowering)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("world", [2, 4])
def test_hlo_audit_clean_green(world, workload2, workload4):
    """The lowered schedule of every (program, lowering) pair matches the
    plan: op kinds/counts, replica_groups/rings, byte-exact footprint
    pricing, one transport family, donation survival."""
    w = workload2 if world == 2 else workload4
    rep = H.audit_workload_hlo(w)
    assert rep["ok"], rep["failures"]
    assert set(rep["exchange_legs"]) == {
        "train_step", "eval_step", "serve_forward"
    }
    # byte-exact footprint cross-check at the HLO level, every operand
    rows = 0
    for p in rep["programs"]:
        for op in p["collective_operands"]:
            assert op["bytes"] == op["footprint_bytes"] > 0, (p, op)
            rows += 1
    assert rows > 0
    # donation survived lowering for the donating train step
    don = rep["donation"]
    assert don["donor_args"] + don["alias_args"] == don["expected_donors"]
    assert don["uncovered"] == []


def test_hlo_count_pins_mirror_trace_tier(workload2):
    """Cross-lowering count discipline at the artifact level: permutes ==
    legs * deltas."""
    rep = H.audit_workload_hlo(workload2)
    assert rep["ok"], rep["failures"]
    n_deltas = rep["num_halo_deltas"]
    by = {(p["program"], p["impl"]): p for p in rep["programs"]}
    for prog, legs in rep["exchange_legs"].items():
        assert by[(prog, "all_to_all")]["num_all_to_all"] == legs
        for impl in ("ppermute", "overlap"):
            assert by[(prog, impl)]["num_collective_permute"] == (
                legs * n_deltas
            )


def test_hlo_audit_is_lower_only(workload2):
    """Zero new XLA compiles: every program's jit cache must be EMPTY
    after a full audit — the counter the serve stack already trusts."""
    from dgraph_tpu.analysis.trace import PROGRAMS
    from dgraph_tpu import config as cfg

    rep = H.audit_workload_hlo(workload2)
    for p in rep["programs"]:
        assert p["jit_cache_entries"] == 0, p
    # and directly, on a freshly built program: lower() must not compile
    saved = (cfg.halo_impl, cfg.tuned_halo_impl)
    try:
        cfg.set_flags(halo_impl="all_to_all", tuned_halo_impl=None)
        fn, args = PROGRAMS["train_step"](workload2)
        H.lower_program(fn, args)
        assert fn._cache_size() == 0
    finally:
        cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1])


def test_the_unpinned_train_step_lowers_one_all_to_all_a_leg():
    """Nothing pinned, no record, a W = 4 plan built without the split
    (what every trainer and the W = 4 cell build): the lowered train step
    is the all_to_all one, an all_to_all an exchange leg and not one
    collective_permute."""
    import numpy as np

    from dgraph_tpu import config as cfg
    from dgraph_tpu import plan as pl
    from dgraph_tpu.analysis.trace import _train_program, workload_from_plan

    W, V = 4, 96
    rng = np.random.default_rng(0)
    part = np.sort(rng.integers(0, W, V)).astype(np.int32)
    plan, _ = pl.build_edge_plan(
        rng.integers(0, V, size=(2, 600)), part, world_size=W)
    assert plan.overlap is None and len(plan.halo_deltas) == W - 1
    w = workload_from_plan(plan, num_nodes=V)

    def lowered_under(pin):
        cfg.set_flags(halo_impl=pin, tuned_halo_impl=None)
        return H.lower_program(*_train_program(w))

    saved = (cfg.halo_impl, cfg.tuned_halo_impl)
    try:
        unpinned = lowered_under("auto")
        assert pl.resolve_halo_impl(
            W, plan.halo_deltas, pair_rows=plan.halo_pair_rows
        ) == ("all_to_all", "heuristic")
        pinned = lowered_under("all_to_all")
    finally:
        cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1])
    coll = H.collect_stablehlo(unpinned)
    legs = len(H.collect_stablehlo(pinned)["all_to_all"])
    assert legs > 0 and len(coll["all_to_all"]) == legs
    assert not coll["collective_permute"]
    assert unpinned.as_text() == pinned.as_text()


# ---------------------------------------------------------------------------
# vacuity guards: seeded drift must go RED
# ---------------------------------------------------------------------------


def test_seeded_extra_all_gather_goes_red(workload2):
    """An XLA-materialized all_gather the plan never scheduled must
    fail the HLO audit."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu import config as cfg
    from dgraph_tpu.analysis.trace import _train_program
    from dgraph_tpu.comm.collectives import shard_map_checks
    from dgraph_tpu.comm.mesh import GRAPH_AXIS

    w = workload2
    saved = (cfg.halo_impl, cfg.tuned_halo_impl)
    try:
        cfg.set_flags(halo_impl="all_to_all", tuned_halo_impl=None)
        fn, args = _train_program(w)

        def seeded(params, opt_state, batch, plan):
            out = fn(params, opt_state, batch, plan)
            extra = jax.shard_map(
                # each rank keeps its own copy of the gathered rows: the vma
                # checker is on, and all_gather's output counts as varying
                lambda x: lax.all_gather(x[0], GRAPH_AXIS)[None],
                mesh=w.mesh, in_specs=(P(GRAPH_AXIS),),
                out_specs=P(GRAPH_AXIS),
                **shard_map_checks(relax="seeded test mutant"),
            )(batch["x"])
            return out, extra

        failures = []
        H._audit_one_lowering(
            "seeded", "all_to_all",
            H.lower_program(jax.jit(seeded, donate_argnums=(0, 1)), args),
            w.plan_np, w.mesh, failures,
        )
        assert any("unscheduled all_gather" in f for f in failures), failures
    finally:
        cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1])


def test_dropped_donation_goes_red(workload2):
    """Both donation-drop shapes fail at the artifact level: donate=False
    (no donor entries survive lowering) and a metrics-only output (donors
    survive but no output type can cover them)."""
    import jax

    from dgraph_tpu import config as cfg
    from dgraph_tpu.analysis.trace import _train_program
    from dgraph_tpu.train.loop import make_train_step

    w = workload2
    donated = len(jax.tree.leaves((w.params, w.opt_state)))
    saved = (cfg.halo_impl, cfg.tuned_halo_impl)
    try:
        cfg.set_flags(halo_impl="all_to_all", tuned_halo_impl=None)
        fn, args = _train_program(w)
        nd = make_train_step(w.model, w.optimizer, w.mesh, w.plan,
                             donate=False)
        failures = []
        H._donation_failures(H.donation_entries(H.lower_program(nd, args)),
                             donated, "no-donate", failures)
        assert failures
        mo = jax.jit(lambda p, o, b, pl: fn(p, o, b, pl)[2],
                     donate_argnums=(0, 1))
        failures = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            H._donation_failures(
                H.donation_entries(H.lower_program(mo, args)), donated,
                "metrics-only", failures)
        assert failures
    finally:
        cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1])


def test_hlo_rejects_wrong_lowering_family(workload2):
    """Pin ppermute, audit the artifact as all_to_all -> RED."""
    from dgraph_tpu import config as cfg
    from dgraph_tpu.analysis.trace import _train_program

    saved = (cfg.halo_impl, cfg.tuned_halo_impl)
    try:
        cfg.set_flags(halo_impl="ppermute", tuned_halo_impl=None)
        fn, args = _train_program(workload2)
        failures = []
        H._audit_one_lowering(
            "t", "all_to_all", H.lower_program(fn, args),
            workload2.plan_np, workload2.mesh, failures,
        )
        assert failures
    finally:
        cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1])


# ---------------------------------------------------------------------------
# the no-unchecked-shard-map rule + pallas-kernel lint descent
# ---------------------------------------------------------------------------


def _run_rule(name, path, src):
    import ast

    tree = ast.parse(src)
    lines = src.splitlines()
    got = L.RULES[name].check(path, tree, lines)
    return [f for f in got if not L._suppressed(lines, f.line, f.rule)]


def test_raw_shard_map_site_flagged():
    """The two raw shapes this PR fixed (check_vma= kwarg and the blanket
    **RELAXED_CHECKS splat) fire; the routed spelling does not."""
    path = "dgraph_tpu/train/loop.py"
    bad_kwarg = (
        "import jax\n"
        "def build(body, mesh, specs):\n"
        "    return jax.shard_map(body, mesh=mesh, in_specs=specs,\n"
        "                         out_specs=specs, check_vma=False)\n"
    )
    bad_splat = (
        "import jax\n"
        "RELAXED_CHECKS = {'check_vma': False}\n"
        "def build(body, mesh, specs):\n"
        "    return jax.shard_map(body, mesh=mesh, in_specs=specs,\n"
        "                         out_specs=specs, **RELAXED_CHECKS)\n"
    )
    good = (
        "import jax\n"
        "from dgraph_tpu.comm.collectives import shard_map_checks\n"
        "def build(body, mesh, specs, plan):\n"
        "    return jax.shard_map(body, mesh=mesh, in_specs=specs,\n"
        "                         out_specs=specs,\n"
        "                         **shard_map_checks(plan, 'graph'))\n"
    )
    assert _run_rule("no-unchecked-shard-map", path, bad_kwarg)
    assert _run_rule("no-unchecked-shard-map", path, bad_splat)
    assert not _run_rule("no-unchecked-shard-map", path, good)


def test_lint_descends_into_pallas_kernels():
    """A config read (or span) inside a kernel handed to pallas_call via
    a functools.partial alias fires — the pre-ISSUE-12 blind spot."""
    path = "dgraph_tpu/ops/pallas_segment.py"
    bad = (
        "import functools\n"
        "from jax.experimental import pallas as pl\n"
        "from dgraph_tpu import config as _cfg\n"
        "def _kernel(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...] * (2 if _cfg.use_pallas_scatter else 1)\n"
        "def transport(x, shape):\n"
        "    kern = functools.partial(_kernel)\n"
        "    return pl.pallas_call(kern, out_shape=shape)(x)\n"
    )
    good = bad.replace(
        "def _kernel(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...] * (2 if _cfg.use_pallas_scatter else 1)\n",
        "def _kernel(x_ref, o_ref, *, scale):\n"
        "    o_ref[...] = x_ref[...] * scale\n",
    ).replace(
        "    kern = functools.partial(_kernel)\n",
        "    scale = 2 if _cfg.use_pallas_scatter else 1\n"
        "    kern = functools.partial(_kernel, scale=scale)\n",
    )
    assert _run_rule("no-config-read-in-trace", path, bad)
    assert not _run_rule("no-config-read-in-trace", path, good)
    span_bad = (
        "from jax.experimental import pallas as pl\n"
        "from dgraph_tpu.obs import spans\n"
        "def _kernel(x_ref, o_ref):\n"
        "    with spans.span('segsum.tile', stage='scatter'):\n"
        "        o_ref[...] = x_ref[...]\n"
        "def transport(x, shape):\n"
        "    return pl.pallas_call(_kernel, out_shape=shape)(x)\n"
    )
    assert _run_rule("no-span-in-trace", path, span_bad)


def test_shipped_tree_has_no_unchecked_shard_maps():
    """The clean-tree pin for the new rule: the five raw sites ISSUE 12
    fixed (train/loop.py init, the blanket RELAXED_CHECKS in
    parallel/sequence.py, and the two analysis-internal ones) stay
    fixed."""
    report = L.run_lint()
    raw = [f for f in report["findings"]
           if f["rule"] == "no-unchecked-shard-map"]
    assert raw == []


# ---------------------------------------------------------------------------
# bench fallback record
# ---------------------------------------------------------------------------


def test_hlo_drift_record_shape():
    """The third wedged-round fallback tier: non-null lowered-vs-priced
    bytes per lowering plus the donation census."""
    rec = H.hlo_drift_record(2, num_nodes=64, num_edges=256, feat_dim=8)
    assert rec["kind"] == "hlo_drift"
    assert rec["drift"] is False
    assert set(rec["train_step_by_impl"]) == set(H.HALO_IMPLS)
    for impl in H.HALO_IMPLS:
        row = rec["train_step_by_impl"][impl]
        assert row["lowered_bytes"] == row["footprint_bytes"] > 0
    don = rec["donation"]
    assert don["donor_args"] + don["alias_args"] == don["expected_donors"]
