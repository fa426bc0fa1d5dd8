"""Cross-rank SPMD divergence auditor (``analysis/spmd.py``): per-rank
lowered-module identity, collective issue order, n_deltas symmetry, and
the canonicalization that makes them sound.

The full matrix — 2- AND 4-shard worlds x all four halo lowerings x all
three programs, plus both generations of a real shrink transition and
every vacuity mutant — runs in the ``--selftest`` CLI registration
(``tests/test_analysis.py::test_analysis_selftest_cli``); the tests here
pin each mechanism individually on reduced shapes so a regression names
its own check.  Everything is lower-only: zero new XLA compiles
(tests/README.md), jit-cache counters asserted in the reports.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def plan_dir2(tmp_path_factory):
    from dgraph_tpu.analysis.spmd import build_spmd_fixture

    d = str(tmp_path_factory.mktemp("spmd") / "w2")
    return build_spmd_fixture(2, d)


@pytest.fixture(scope="module")
def plan_dir4(tmp_path_factory):
    from dgraph_tpu.analysis.spmd import build_spmd_fixture

    d = str(tmp_path_factory.mktemp("spmd") / "w4")
    return build_spmd_fixture(4, d)


# ---------------------------------------------------------------------------
# the clean contract: identical programs, identical order, empty jit cache
# ---------------------------------------------------------------------------


def test_clean_cross_rank_audit_is_green(plan_dir2):
    from dgraph_tpu.analysis.spmd import audit_plan_dir_spmd
    from dgraph_tpu.analysis.trace import _train_program

    rep = audit_plan_dir_spmd(
        plan_dir2, impls=("all_to_all", "ppermute"),
        programs={"train_step": _train_program},
    )
    assert rep["ok"], rep["failures"]
    assert rep["world_size"] == 2
    assert rep["num_halo_deltas"] >= 1
    for prec in rep["programs"]:
        assert prec["identical"], prec
        assert len(set(prec["module_hash"].values())) == 1
        assert prec["num_collectives"] > 0  # identity of empty would be vacuous
        # lower-only, per rank, asserted in the report
        assert all(c == 0 for c in prec["jit_cache_entries"].values()), prec
    assert rep["delta_symmetry"] == "symmetric"
    # every rank resolved the same lowering through the real ladder
    assert len({tuple(v) for v in rep["resolution"].values()}) == 1


def test_rank_views_see_their_own_live_deltas(plan_dir4):
    """rank_live_deltas reads the rank's OWN send mask — the locally
    observable half of the delta set the manifest globalizes."""
    from dgraph_tpu.analysis.spmd import rank_live_deltas
    from dgraph_tpu.plan import load_sharded_plan

    full, _ = load_sharded_plan(plan_dir4, load_layout=False)
    global_deltas = set(full.halo_deltas)
    for r in range(4):
        sub, _ = load_sharded_plan(plan_dir4, ranks=[r], load_layout=False)
        live = rank_live_deltas(sub, r)
        assert set(live) <= global_deltas, (r, live, global_deltas)


# ---------------------------------------------------------------------------
# seeded divergences (the deadlock classes) must go RED
# ---------------------------------------------------------------------------


def test_dropped_round_on_one_rank_goes_red(plan_dir4):
    from dgraph_tpu.analysis.spmd import (
        audit_plan_dir_spmd, mutant_dropped_round_program,
    )

    rep = audit_plan_dir_spmd(
        plan_dir4, impls=("ppermute",),
        programs={"mutant": mutant_dropped_round_program},
    )
    assert not rep["ok"]
    assert any(
        "COUNT mismatch" in f or "differs" in f for f in rep["failures"]
    ), rep["failures"]
    # the divergence names rank 1 (the seeded branch) against rank 0
    assert any("rank 1" in f for f in rep["failures"]), rep["failures"]


def test_swapped_collective_order_flagged_as_order(plan_dir4):
    """Equal per-rank totals, different order — only the issue-sequence
    comparator can catch this one."""
    from dgraph_tpu.analysis.spmd import (
        audit_plan_dir_spmd, mutant_swapped_order_program,
    )

    rep = audit_plan_dir_spmd(
        plan_dir4, impls=("ppermute",),
        programs={"mutant": mutant_swapped_order_program},
    )
    assert not rep["ok"]
    assert any("ORDER" in f for f in rep["failures"]), rep["failures"]
    assert not any("COUNT mismatch" in f for f in rep["failures"])


def test_rank_divergent_tune_record_goes_red(plan_dir4):
    """A per-host adopted TuningRecord that disagrees across ranks splits
    the transport family before the first exchange — caught at the
    resolution-agreement check, before anything lowers (impls=())."""
    from dgraph_tpu.analysis.spmd import audit_plan_dir_spmd

    rep = audit_plan_dir_spmd(
        plan_dir4, impls=(), programs={},
        rank_tuned={0: "all_to_all", 1: "ppermute"},
    )
    assert not rep["ok"]
    assert any("resolution" in f for f in rep["failures"]), rep["failures"]


def test_benign_rank_tag_constant_stays_green(plan_dir2):
    """A rank-id constant folded into the module (a metrics tag) is the
    one benign per-rank difference; the canonicalizer must substitute it
    — and must COUNT the substitution, so the check is provably
    non-vacuous."""
    from dgraph_tpu.analysis.spmd import (
        audit_plan_dir_spmd, benign_rank_tag_program,
    )

    rep = audit_plan_dir_spmd(
        plan_dir2, impls=("ppermute",),
        programs={"benign": benign_rank_tag_program},
    )
    assert rep["ok"], rep["failures"]
    assert all(p["rank_tag_lines"] > 0 for p in rep["programs"])


# ---------------------------------------------------------------------------
# canonicalization mechanics (pure text, no lowering)
# ---------------------------------------------------------------------------


def test_canonicalize_substitutes_only_pure_rank_tags():
    from dgraph_tpu.analysis.spmd import RANK_TOKEN, canonicalize_rank_modules

    # a pure rank-tag line is rewritten; rank 0's ubiquitous `0` literals
    # on SHARED lines are untouched
    texts = {
        0: "op_a dense<0> : tensor<i32>\nshared dense<0> : tensor<i32>",
        1: "op_a dense<1> : tensor<i32>\nshared dense<0> : tensor<i32>",
    }
    canon, subs = canonicalize_rank_modules(texts)
    assert subs == 1
    assert canon[0] == canon[1]
    assert RANK_TOKEN in canon[0].splitlines()[0]
    assert "dense<0>" in canon[0].splitlines()[1]  # shared line untouched

    # a structural difference on the same line survives verbatim
    texts = {
        0: "stablehlo.add %a, %b",
        1: "stablehlo.multiply %a, %b",
    }
    canon, subs = canonicalize_rank_modules(texts)
    assert subs == 0
    assert canon[0] != canon[1]

    # float rank-lookalikes are NOT substituted (boundary guard)
    texts = {
        0: "c = dense<0.000000e+00> : tensor<f32>",
        1: "c = dense<1.000000e+00> : tensor<f32>",
    }
    canon, subs = canonicalize_rank_modules(texts)
    assert subs == 0 and canon[0] != canon[1]

    # different line counts = structural divergence, returned unchanged
    texts = {0: "a\nb", 1: "a"}
    canon, subs = canonicalize_rank_modules(texts)
    assert subs == 0 and canon == texts


def test_rank_env_is_restored_after_audit(plan_dir2):
    from dgraph_tpu.analysis.spmd import audit_plan_dir_spmd
    from dgraph_tpu.utils.env import RANK_ENV_VAR

    os.environ[RANK_ENV_VAR] = "7"
    try:
        audit_plan_dir_spmd(plan_dir2, impls=(), programs={})
        assert os.environ[RANK_ENV_VAR] == "7"
    finally:
        os.environ.pop(RANK_ENV_VAR, None)


# ---------------------------------------------------------------------------
# bench fallback record (tier 4)
# ---------------------------------------------------------------------------


def test_spmd_drift_record_shape():
    from dgraph_tpu.analysis.spmd import spmd_drift_record

    rec = spmd_drift_record(2, num_nodes=64, num_edges=256, feat_dim=8)
    assert rec["kind"] == "spmd_drift"
    assert rec["drift"] is False
    assert rec["num_halo_deltas"] >= 1
    from dgraph_tpu.plan import HALO_IMPLS

    assert set(rec["train_step_by_impl"]) == set(HALO_IMPLS)
    for impl in HALO_IMPLS:
        row = rec["train_step_by_impl"][impl]
        assert row["identical"] is True
        assert row["num_collectives"] > 0


# ---------------------------------------------------------------------------
# shrink generations re-agree (the W -> W-1 path, reduced: one impl)
# ---------------------------------------------------------------------------


def test_shrink_generations_cross_rank_green(tmp_path):
    from dgraph_tpu.analysis.spmd import (
        audit_plan_dir_spmd, build_shrink_fixture,
    )
    from dgraph_tpu.analysis.trace import _train_program
    from dgraph_tpu.train import shrink as shr

    rund = str(tmp_path / "run")
    world = build_shrink_fixture(rund, world_size=3)
    assert world["generation"] == 1 and world["world_size"] == 2
    for gen, wsz in ((0, 3), (1, 2)):
        rep = audit_plan_dir_spmd(
            shr.plan_dir(rund, gen), impls=("ppermute",),
            programs={"train_step": _train_program}, label=f"g{gen}",
        )
        assert rep["world_size"] == wsz
        assert rep["ok"], (gen, rep["failures"])
