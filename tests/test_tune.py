"""Autotuner tests: signature stability, record round-trip + adoption
rules, analytic ranking, measured-phase NaN guard, knob rejection, halo
lowering override sources, and the tier-1 CLI smoke.

Everything here is host-side numpy plus one subprocess (the compile-free
``--selftest``): the tier-1 suite is compile-dominated and near its
budget, so no test in this file may trigger a fresh XLA compile.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from dgraph_tpu.data.synthetic import random_edges
from dgraph_tpu.tune import adopt as tune_adopt
from dgraph_tpu.tune.record import (
    TuningRecord,
    adopt_record,
    lookup_record,
    record_path,
)
from dgraph_tpu.tune.search import search
from dgraph_tpu.tune.signature import (
    degree_histogram,
    graph_signature,
    signature_key,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolated_tune_env(tmp_path, monkeypatch):
    """Point the default record dir at an empty tmp dir and clear any pin:
    a developer's real cache/plans records must not leak into assertions."""
    monkeypatch.setenv("DGRAPH_TUNE_DIR", str(tmp_path / "default_records"))
    monkeypatch.delenv("DGRAPH_TUNE_RECORD", raising=False)


@pytest.fixture(autouse=True)
def _reset_tuned_flags():
    from dgraph_tpu import config

    yield
    config.set_flags(tuned_halo_impl=None, tuning_record_id=None)


def _small_graph(seed=0, nodes=512, edges=2048):
    return random_edges(nodes, edges, seed=seed), nodes


# ---------------------------------------------------------------------------
# signatures
# ---------------------------------------------------------------------------


class TestSignature:
    def test_stable_across_calls(self):
        e, n = _small_graph()
        a = graph_signature(e, n, 4, dtype="bfloat16", feat_dim=64)
        b = graph_signature(e.copy(), n, 4, dtype="bfloat16", feat_dim=64)
        assert a == b
        assert signature_key(a) == signature_key(b)

    def test_renumbering_invariant(self):
        """Same graph under a vertex permutation and edge shuffle -> same
        signature (records must survive a re-load that renumbers)."""
        e, n = _small_graph()
        rng = np.random.default_rng(7)
        perm = rng.permutation(n)
        e2 = perm[e][:, rng.permutation(e.shape[1])]
        a = graph_signature(e, n, 2, dtype="float32", feat_dim=8)
        b = graph_signature(e2, n, 2, dtype="float32", feat_dim=8)
        assert a["degree_digest"] == b["degree_digest"]
        assert signature_key(a) == signature_key(b)

    def test_discriminates_workloads(self):
        e, n = _small_graph()
        base = graph_signature(e, n, 2, dtype="float32", feat_dim=8)
        keys = {
            signature_key(base),
            signature_key(graph_signature(e, n, 4, dtype="float32", feat_dim=8)),
            signature_key(graph_signature(e, n, 2, dtype="bfloat16", feat_dim=8)),
            signature_key(graph_signature(e, n, 2, dtype="float32", feat_dim=16)),
            signature_key(
                graph_signature(e[:, :-100], n, 2, dtype="float32", feat_dim=8)
            ),
        }
        assert len(keys) == 5

    def test_dtype_aliases_canonicalized(self):
        e, n = _small_graph()
        a = graph_signature(e, n, 2, dtype="bf16")
        b = graph_signature(e, n, 2, dtype="bfloat16")
        assert signature_key(a) == signature_key(b)

    def test_degree_histogram_counts(self):
        # star graph: hub degree n-1, leaves degree 1
        n = 9
        e = np.stack([np.zeros(n - 1, np.int64), np.arange(1, n)])
        hist = degree_histogram(e, n)
        assert hist.sum() == n
        assert hist[1] == n - 1  # leaves: degree 1 -> bucket 1
        assert hist[4] == 1  # hub: degree 8 -> bucket [8, 16)


# ---------------------------------------------------------------------------
# records: round-trip, lookup, adoption rules
# ---------------------------------------------------------------------------


def _make_record(sig):
    return TuningRecord.create(
        sig,
        {"partition_method": "rcm", "pad_multiple": 8,
         "halo_impl": "ppermute"},
        {"winner_us": 1.0, "default_us": 2.0},
        "analytic",
    )


class TestRecord:
    def test_roundtrip(self, tmp_path):
        e, n = _small_graph()
        sig = graph_signature(e, n, 2)
        rec = _make_record(sig)
        path = rec.save(str(tmp_path))
        assert path == record_path(str(tmp_path), sig)
        loaded = TuningRecord.load(path)
        assert loaded.record_id == rec.record_id
        assert loaded.config == rec.config
        assert loaded.signature == sig

    def test_validate_rejects_garbage(self):
        e, n = _small_graph()
        sig = graph_signature(e, n, 2)
        with pytest.raises(ValueError, match="phase"):
            TuningRecord.create(sig, {"pad_multiple": 8}, {"winner_us": 1}, "vibes")
        with pytest.raises(ValueError, match="pad_multiple"):
            TuningRecord.create(
                sig, {"pad_multiple": -3}, {"winner_us": 1}, "analytic"
            )
        with pytest.raises(ValueError, match="unknown config keys"):
            TuningRecord.create(
                sig, {"warp_speed": 9}, {"winner_us": 1}, "analytic"
            )
        # a partial or wrongly-typed serve dict must fail at validate time,
        # not as a KeyError/shape error deep in serving startup
        with pytest.raises(ValueError, match="serve config"):
            TuningRecord.create(
                sig, {"pad_multiple": 8, "serve": {"growth": 2.0}},
                {"winner_us": 1}, "analytic",
            )
        with pytest.raises(ValueError, match="serve config"):
            TuningRecord.create(
                sig,
                {"pad_multiple": 8,
                 "serve": {"min_bucket": 8.5, "max_bucket": 64,
                           "growth": 2.0}},
                {"winner_us": 1}, "analytic",
            )

    def test_lookup_hit_mismatch_and_corrupt(self, tmp_path):
        e, n = _small_graph()
        sig = graph_signature(e, n, 2)
        rec = _make_record(sig)
        rec.save(str(tmp_path))
        hit = lookup_record(sig, cache_dir=str(tmp_path))
        assert hit is not None and hit.record_id == rec.record_id
        # different workload -> miss (falls back to defaults, no error)
        other = graph_signature(e, n, 8)
        assert lookup_record(other, cache_dir=str(tmp_path)) is None
        # corrupt file -> logged miss, not a crash
        with open(record_path(str(tmp_path), sig), "w") as f:
            f.write("{truncated")
        assert lookup_record(sig, cache_dir=str(tmp_path)) is None

    def test_stored_signature_is_authoritative(self, tmp_path):
        """A record renamed onto another workload's key must not adopt."""
        e, n = _small_graph()
        sig = graph_signature(e, n, 2)
        other = graph_signature(e, n, 8)
        rec = _make_record(sig)
        os.makedirs(tmp_path, exist_ok=True)
        with open(record_path(str(tmp_path), other), "w") as f:
            json.dump(rec.to_dict(), f)
        assert lookup_record(other, cache_dir=str(tmp_path)) is None

    def test_env_pin_and_disable(self, tmp_path, monkeypatch):
        e, n = _small_graph()
        sig = graph_signature(e, n, 2)
        rec = _make_record(sig)
        path = rec.save(str(tmp_path))
        # disable beats an on-disk match
        monkeypatch.setenv("DGRAPH_TUNE_RECORD", "off")
        assert lookup_record(sig, cache_dir=str(tmp_path)) is None
        # pin adopts even for a non-matching signature (warned)
        monkeypatch.setenv("DGRAPH_TUNE_RECORD", path)
        other = graph_signature(e, n, 8)
        pinned = lookup_record(other, cache_dir="")
        assert pinned is not None and pinned.record_id == rec.record_id
        # unreadable pin degrades to disabled, not a crash
        monkeypatch.setenv("DGRAPH_TUNE_RECORD", str(tmp_path / "missing.json"))
        assert lookup_record(sig, cache_dir=str(tmp_path)) is None

    def test_adopt_sets_flags_and_returns_build_kwargs(self):
        from dgraph_tpu import config

        e, n = _small_graph()
        rec = _make_record(graph_signature(e, n, 2))
        kw = adopt_record(rec)
        assert kw == {"partition_method": "rcm", "pad_multiple": 8}
        assert config.tuned_halo_impl == "ppermute"
        assert config.tuning_record_id == rec.record_id


# ---------------------------------------------------------------------------
# halo lowering override sources
# ---------------------------------------------------------------------------


class TestResolveHaloImpl:
    def test_source_precedence(self):
        from dgraph_tpu import config
        from dgraph_tpu.plan import pick_halo_impl, resolve_halo_impl

        deltas = (1, 2, 3, 4, 5, 6, 7)
        saved = config.halo_impl
        try:
            config.set_flags(halo_impl="auto", tuned_halo_impl=None)
            impl, source = resolve_halo_impl(8, deltas)
            assert source == "heuristic"
            assert impl == pick_halo_impl(8, deltas)

            config.set_flags(tuned_halo_impl="ppermute")
            assert resolve_halo_impl(8, deltas) == ("ppermute", "record")

            # env/operator pin beats the record
            config.set_flags(halo_impl="all_to_all")
            assert resolve_halo_impl(8, deltas) == ("all_to_all", "env")

            # no traffic: nothing to choose, whatever the pins say
            assert resolve_halo_impl(8, ()) == ("none", "plan")
        finally:
            config.set_flags(halo_impl=saved, tuned_halo_impl=None)

    def test_plan_efficiency_reports_source(self):
        from dgraph_tpu import config
        from dgraph_tpu.plan import build_edge_plan, plan_efficiency

        e, n = _small_graph()
        from dgraph_tpu import partition as pt

        new_edges, ren = pt.partition_graph(e, n, 2, method="block")
        plan, layout = build_edge_plan(
            new_edges, ren.partition, world_size=2, pad_multiple=8
        )
        saved = config.halo_impl
        try:
            config.set_flags(halo_impl="auto", tuned_halo_impl=None)
            eff = plan_efficiency(plan, layout)
            assert eff["halo_impl_source"] == "heuristic"
            config.set_flags(tuned_halo_impl="all_to_all")
            eff = plan_efficiency(plan, layout)
            assert (eff["halo_impl"], eff["halo_impl_source"]) == (
                "all_to_all", "record",
            )
        finally:
            config.set_flags(halo_impl=saved, tuned_halo_impl=None)

    def test_footprint_reports_source(self):
        from dgraph_tpu import config
        from dgraph_tpu.obs.footprint import plan_footprint
        from dgraph_tpu.plan import build_edge_plan
        from dgraph_tpu import partition as pt

        e, n = _small_graph()
        new_edges, ren = pt.partition_graph(e, n, 2, method="block")
        plan, _ = build_edge_plan(
            new_edges, ren.partition, world_size=2, pad_multiple=8
        )
        saved = config.halo_impl
        try:
            config.set_flags(halo_impl="ppermute", tuned_halo_impl=None)
            fp = plan_footprint(plan, "float32", 8)
            ex = fp["collectives"]["halo_exchange"]
            assert (ex["impl"], ex["impl_source"]) == ("ppermute", "env")
        finally:
            config.set_flags(halo_impl=saved)


# ---------------------------------------------------------------------------
# build_edge_plan knob-compatibility rejection
# ---------------------------------------------------------------------------


class TestKnobRejection:
    def _build(self, nodes=512, edges=2048, **kw):
        from dgraph_tpu.plan import build_edge_plan

        e, n = _small_graph(nodes=nodes, edges=edges)
        part = np.minimum(np.arange(n) // (n // 2), 1).astype(np.int32)
        return build_edge_plan(e, part, world_size=2, **kw)

    def test_e_pad_vs_pad_multiple_named(self):
        with pytest.raises(ValueError) as ei:
            self._build(pad_multiple=8, e_pad=4098)
        assert "e_pad=4098" in str(ei.value)
        assert "pad_multiple=8" in str(ei.value)

    def test_kernel_scale_e_pad_vs_scatter_block_named(self):
        from dgraph_tpu.plan import SCATTER_BLOCK_E

        bad = SCATTER_BLOCK_E + 8  # pad_multiple-aligned but sub-block-off
        with pytest.raises(ValueError) as ei:
            self._build(pad_multiple=8, e_pad=bad)
        assert "scatter_block_e" in str(ei.value)

    def test_sub_block_e_pad_still_allowed(self):
        # hand-pinned tiny sub-block shapes (the test-plan idiom) must keep
        # working: below SCATTER_BLOCK_E the kernel alignment rule is off
        plan, _ = self._build(nodes=64, edges=128, pad_multiple=1, e_pad=300)
        assert plan.e_pad == 300

    def test_bad_pad_multiple_and_s_pad(self):
        with pytest.raises(ValueError, match="pad_multiple=0"):
            self._build(pad_multiple=0)
        with pytest.raises(ValueError, match="s_pad=9"):
            self._build(pad_multiple=8, s_pad=9)


# ---------------------------------------------------------------------------
# search: analytic ranking + measured phase (stubbed measure)
# ---------------------------------------------------------------------------


class TestSearch:
    def test_analytic_ranking_arxiv_shaped(self, tmp_path):
        """Scaled-down arxiv-shaped workload (uniform random, symmetrized):
        the analytic phase must rank every candidate with finite cost,
        best-first, and never place the winner above the defaults."""
        from dgraph_tpu.utils import ExperimentLog

        e, n = _small_graph(seed=3, nodes=1024, edges=4096)
        log = ExperimentLog(str(tmp_path / "trace.jsonl"), echo=False)
        result = search(
            e, n, 4, feat_dim=32, dtype="float32", budget_s=0.0,
            methods=("block", "random", "rcm"), pad_multiples=(8, 128),
            max_request=128, log=log, sweep_log="",
        )
        costs = [c for _, c in result.ranked]
        assert all(np.isfinite(c) and c > 0 for c in costs)
        assert costs == sorted(costs)
        assert result.record.phase == "analytic"
        assert (
            result.record.cost["winner_us"] <= result.record.cost["default_us"]
        )
        cfg = result.record.config
        assert cfg["partition_method"] in ("block", "random", "rcm")
        assert cfg["pad_multiple"] in (8, 128)
        assert cfg["halo_impl"] in ("none", "ppermute", "all_to_all", "overlap")
        assert cfg["serve"]["num_buckets"] >= 1
        # trace landed in the JSONL: one analytic row per candidate + result
        rows = [
            json.loads(l)
            for l in open(tmp_path / "trace.jsonl")
            if l.startswith("{")
        ]
        analytic = [r for r in rows if r.get("phase") == "analytic"]
        assert len(analytic) == 6  # 3 methods x 2 pads
        assert any(r.get("phase") == "result" for r in rows)

    def test_measured_phase_nan_guard(self):
        """A NaN measurement (crashed compile / timing noise) must never be
        crowned winner — the survivor with a finite time wins, and the
        record flips to phase='measured'."""
        e, n = _small_graph(seed=5)
        calls = []

        def fake_measure(plan, *, feat_dim, dtype, seed):
            calls.append(plan.e_pad)
            return float("nan") if len(calls) == 1 else 7.5

        result = search(
            e, n, 2, feat_dim=16, budget_s=60.0, top_k=2,
            measure_fn=fake_measure, methods=("block", "random"),
            pad_multiples=(8,), max_request=64, sweep_log="",
        )
        assert len(calls) == 2  # exactly top_k survivors timed
        assert result.record.phase == "measured"
        assert result.record.cost["measured_ms"] == 7.5
        # the winner is the candidate that measured finite, i.e. ranked #2
        assert result.record.config["partition_method"] == result.ranked[1][
            0
        ].split("/")[0]

    def test_measure_exception_is_contained(self):
        e, n = _small_graph(seed=6)

        def exploding_measure(plan, **kw):
            raise RuntimeError("mosaic went sideways")

        result = search(
            e, n, 2, feat_dim=16, budget_s=60.0, top_k=1,
            measure_fn=exploding_measure, methods=("block",),
            pad_multiples=(8,), max_request=64, sweep_log="",
        )
        # every measurement failed -> analytic ranking stands
        assert result.record.phase == "analytic"
        assert result.measured == {}

    def test_sweep_log_feeds_pallas_config(self, tmp_path):
        rows = [
            {"op": "segment_sum_pallas_default", "dtype": "bf16", "F": 128,
             "block_e": 1024, "block_n": 256, "ms": 2.0},
            {"op": "segment_sum_pallas_default", "dtype": "bf16", "F": 128,
             "block_e": 512, "block_n": 256, "ms": float("nan")},
            {"op": "segment_sum_xla", "dtype": "bf16", "F": 128, "ms": 3.0},
        ]
        path = tmp_path / "sweep.jsonl"
        with open(path, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
        e, n = _small_graph(seed=8)
        result = search(
            e, n, 2, feat_dim=16, dtype="bfloat16", methods=("block",),
            pad_multiples=(8,), max_request=64, sweep_log=str(path),
        )
        cfg = result.record.config
        assert cfg["use_pallas_scatter"] is True  # 2.0 < 3.0
        assert (cfg["scatter_block_e"], cfg["scatter_block_n"]) == (1024, 256)

    def test_sweep_verdict_picks_nearest_feat_dim(self, tmp_path):
        """Verdicts at several widths: the one measured closest to the
        workload's feat_dim decides (a wide-row PALLAS win must not flip
        a narrow workload)."""
        rows = [
            {"op": "segment_sum_xla", "dtype": "f32", "F": 64, "ms": 2.0},
            {"op": "segment_sum_pallas_highest", "dtype": "f32", "F": 64,
             "ms": 3.0},  # XLA wins at 64
            {"op": "segment_sum_xla", "dtype": "f32", "F": 256, "ms": 4.0},
            {"op": "segment_sum_pallas_highest", "dtype": "f32", "F": 256,
             "ms": 1.0},  # PALLAS wins at 256
        ]
        path = tmp_path / "sweep.jsonl"
        with open(path, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
        e, n = _small_graph(seed=9)
        result = search(
            e, n, 2, feat_dim=64, dtype="float32", methods=("block",),
            pad_multiples=(8,), max_request=64, sweep_log=str(path),
        )
        assert result.record.config["use_pallas_scatter"] is False

    def test_rejected_default_candidate_survives(self, monkeypatch):
        """The default candidate failing to build must not crash the
        search — the winner stands in as the cost baseline."""
        from dgraph_tpu import partition as pt

        real = pt.partition_graph

        def no_rcm(*a, **kw):
            if kw.get("method") == "rcm":
                raise ImportError("scipy unavailable (simulated)")
            return real(*a, **kw)

        monkeypatch.setattr(pt, "partition_graph", no_rcm)
        e, n = _small_graph(seed=10)
        result = search(
            e, n, 2, feat_dim=16, methods=("block", "rcm"),
            pad_multiples=(8,), max_request=64, sweep_log="",
        )
        assert result.record.cost["winner_us"] <= result.record.cost["default_us"]
        assert all(not k.startswith("rcm/") for k, _ in result.ranked)


# ---------------------------------------------------------------------------
# sweep winner-picking (folded from scripts/adopt_sweep.py)
# ---------------------------------------------------------------------------


class TestAdoptSweep:
    def test_nan_guard_in_winner_picking(self):
        rows = [
            {"op": "segment_sum_pallas_highest", "dtype": "f32", "F": 64,
             "block_e": 512, "block_n": 256, "ms": 4.0},
            # the NaN row would win a naive min() (x < nan is always False)
            {"op": "segment_sum_pallas_highest", "dtype": "f32", "F": 64,
             "block_e": 1024, "block_n": 256, "ms": float("nan")},
            {"op": "segment_sum_xla", "dtype": "f32", "F": 64, "ms": 3.0},
        ]
        report = tune_adopt.pick_winners(rows)
        key = ("segment_sum_pallas_highest", "f32", 64)
        assert report["winners"][key] == (512, 256)
        (v,) = report["verdicts"]
        assert v["verdict"] == "XLA"  # 4.0 (finite best) vs 3.0

    def test_thin_script_wrapper(self, tmp_path):
        """scripts/adopt_sweep.py keeps its CLI contract (and never imports
        the package / jax: it must run with the TPU lease in any state)."""
        rows = [
            {"op": "gather_sorted_pallas", "dtype": "bf16", "F": 32,
             "block_e": 512, "block_n": 256, "ms": 1.5},
            {"op": "gather_sorted_xla", "dtype": "bf16", "F": 32, "ms": 2.5},
        ]
        path = tmp_path / "kb.jsonl"
        with open(path, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
        out = subprocess.run(
            [sys.executable, "scripts/adopt_sweep.py", str(path)],
            capture_output=True, text=True, timeout=120, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert out.returncode == 0, out.stdout + out.stderr
        assert "WINNER block_e=512" in out.stdout
        assert "use_pallas_gather" in out.stdout and "PALLAS" in out.stdout


# ---------------------------------------------------------------------------
# adoption end-to-end: from_global + serve health attribution
# ---------------------------------------------------------------------------


class TestAdoption:
    def test_from_global_adopts_matching_record(self, tmp_path):
        e, n = _small_graph(seed=11, nodes=300, edges=1200)
        feats = np.random.default_rng(0).normal(size=(n, 12)).astype(np.float32)
        # signed with the compute dtype from_global will look up under
        sig = graph_signature(e, n, 2, dtype="float32", feat_dim=12)
        rec = TuningRecord.create(
            sig,
            {"partition_method": "block", "pad_multiple": 128},
            {"winner_us": 1.0, "default_us": 2.0},
            "analytic",
        )
        rec.save(str(tmp_path))

        from dgraph_tpu.data.graph import DistributedGraph

        g = DistributedGraph.from_global(
            e, feats, None, None, world_size=2, plan_cache_dir=str(tmp_path)
        )
        assert g.tuning_record_id == rec.record_id
        # the record's knobs actually reached the build: pad_multiple=128
        # pads 150 local vertices to 256 (the default 8 would give 152),
        # and the block partition keeps the original contiguous numbering
        assert g.plan.n_src_pad == 256
        from dgraph_tpu.partition import block_partition

        np.testing.assert_array_equal(
            g.ren.partition, block_partition(n, 2)
        )

        # explicit caller choices suppress the lookup entirely
        g2 = DistributedGraph.from_global(
            e, feats, None, None, world_size=2,
            partition_method="block", pad_multiple=8,
            plan_cache_dir=str(tmp_path),
        )
        assert g2.tuning_record is None and g2.tuning_record_id is None

    def test_lookup_miss_clears_prior_adoption(self, tmp_path):
        """A graph with no record must not inherit the previous graph's
        adopted halo lowering (process-global flag hygiene)."""
        from dgraph_tpu import config
        from dgraph_tpu.data.graph import DistributedGraph

        e, n = _small_graph(seed=13, nodes=200, edges=800)
        rec = _make_record(graph_signature(e, n, 2))  # halo_impl=ppermute
        adopt_record(rec)
        assert config.tuned_halo_impl == "ppermute"
        feats = np.zeros((n, 4), np.float32)
        g = DistributedGraph.from_global(
            e, feats, None, None, world_size=2, plan_cache_dir=str(tmp_path)
        )
        assert g.tuning_record is None
        assert config.tuned_halo_impl is None
        assert config.tuning_record_id is None

        # ... and likewise when the lookup is SKIPPED (explicit knobs /
        # tune="off"), not just when it misses
        adopt_record(rec)
        DistributedGraph.from_global(
            e, feats, None, None, world_size=2,
            partition_method="block", pad_multiple=8,
        )
        assert config.tuned_halo_impl is None
        adopt_record(rec)
        DistributedGraph.from_global(
            e, feats, None, None, world_size=2, tune="off",
        )
        assert config.tuned_halo_impl is None

    def test_invalid_tune_arg_raises(self):
        from dgraph_tpu.data.graph import DistributedGraph

        e, n = _small_graph(nodes=64, edges=128)
        with pytest.raises(ValueError, match="tune must be"):
            DistributedGraph.from_global(
                e, np.zeros((n, 4), np.float32), None, None, world_size=2,
                tune="on",
            )

    def test_serve_health_carries_record_id(self):
        from dgraph_tpu.obs.metrics import Metrics
        from dgraph_tpu.serve.bucketing import BucketLadder
        from dgraph_tpu.serve.health import serve_health_record

        class _StubEngine:
            ladder = BucketLadder((8, 16))
            num_nodes = 100
            warmup_s = 0.5
            registry = Metrics()
            tuning_record_id = "tune-deadbeef-v1"

            def recompiles_since_warmup(self):
                return 0

        rec = serve_health_record(_StubEngine())
        assert rec["tuning_record"] == "tune-deadbeef-v1"
        delattr(_StubEngine, "tuning_record_id")
        rec = serve_health_record(_StubEngine())
        assert rec["tuning_record"] is None


# ---------------------------------------------------------------------------
# CLI smoke (tier-1: the whole tuner pipeline on every run, compile-free)
# ---------------------------------------------------------------------------


def test_tune_selftest_cli(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "dgraph_tpu.tune", "--selftest", "true",
         "--log_path", str(tmp_path / "tune.jsonl")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["kind"] == "tune_selftest"
    assert rec["failures"] == []
    assert rec["cost"]["winner_us"] <= rec["cost"]["default_us"]
    assert rec["run_health"]["error"] is None
    # the JSONL artifact carries the search trace + the health record
    rows = [
        json.loads(l)
        for l in open(tmp_path / "tune.jsonl")
        if l.startswith("{")
    ]
    assert any(r.get("kind") == "tune_trace" for r in rows)
    assert any(r.get("kind") == "tune_selftest" for r in rows)
