"""Crash-safe sharded plan artifacts (cache format v8): streaming per-rank
builds, integrity manifests, resume, and fault-tolerant loaders.

The acceptance pins for ISSUE 8:

- a chaos-injected SIGTERM mid-build (``plan.write=sigterm@k`` — the
  deterministic stand-in for the OOM-killer's SIGKILL) leaves a resumable
  manifest, and the resumed build is **bit-identical** to an uninterrupted
  one (shard pickles compared by SHA-256);
- a single corrupt / truncated / missing shard is detected by checksum and
  rebuilt **alone** (the durable shards are not rewritten), logged with
  which shard triggered it;
- a memory-budget violation raises a structured
  :class:`~dgraph_tpu.plan_shards.PlanBuildMemoryExceeded` instead of
  getting OOM-killed (the r5 papers100M failure mode, ROADMAP item 3).

Everything here is host-side numpy + subprocess orchestration — zero new
XLA compiles (tier-1 budget is compile-dominated; tests/README.md).
"""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _graph(seed=0, n=48, e=300, w=4):
    """Deterministic tiny synthetic graph with contiguous per-rank blocks
    (reproducible across processes — the kill-and-resume worker rebuilds
    the same graph from the same seed)."""
    rng = np.random.default_rng(seed)
    part = np.sort(rng.integers(0, w, n)).astype(np.int64)
    edges = rng.integers(0, n, (2, e)).astype(np.int64)
    return edges, part, w


def _assert_plans_equal(a, b):
    import dataclasses

    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if va is None or vb is None:
            assert va is vb, f.name
        elif f.name == "halo":
            assert np.array_equal(va.send_idx, vb.send_idx), "halo.send_idx"
            assert np.array_equal(va.send_mask, vb.send_mask), "halo.send_mask"
            assert va.s_pad == vb.s_pad
        elif f.name == "overlap":
            for of in dataclasses.fields(va):
                assert np.array_equal(
                    np.asarray(getattr(va, of.name)),
                    np.asarray(getattr(vb, of.name)),
                ), f"overlap.{of.name}"
        elif isinstance(va, np.ndarray):
            assert np.array_equal(va, vb), f.name
        else:
            assert va == vb, f.name


def _shard_shas(plan_dir):
    import dgraph_tpu.plan_shards as ps

    man = ps.read_manifest(plan_dir)
    return {r: e["sha256"] for r, e in man["shards"].items()}


# ---------------------------------------------------------------------------
# bit-parity: streamed == monolithic
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("overlap,sort_route", [
    (False, False), (False, True), (True, False), (True, True),
])
def test_sharded_build_bit_identical_to_monolithic(
    tmp_path, overlap, sort_route
):
    from dgraph_tpu.plan import build_edge_plan, build_edge_plan_sharded

    edges, part, w = _graph()
    mono, mono_layout = build_edge_plan(
        edges, part, world_size=w, overlap=overlap, sort_route=sort_route,
        use_native=False,
    )
    plan, layout = build_edge_plan_sharded(
        edges, part, out_dir=str(tmp_path / "shards"), world_size=w,
        overlap=overlap, sort_route=sort_route, fingerprint="parity",
    )
    _assert_plans_equal(mono, plan)
    import dataclasses

    for f in dataclasses.fields(mono_layout):
        assert np.array_equal(
            np.asarray(getattr(mono_layout, f.name)),
            np.asarray(getattr(layout, f.name)),
        ), f.name


def test_native_core_rejected_in_streaming_mode(tmp_path):
    from dgraph_tpu.plan import build_plan_shards

    edges, part, w = _graph()
    with pytest.raises(ValueError, match="use_native"):
        build_plan_shards(
            edges, part, out_dir=str(tmp_path), world_size=w,
            use_native=True,
        )


# ---------------------------------------------------------------------------
# kill-and-resume (the acceptance pin): SIGTERM after 2 durable shards,
# resume from the manifest, bit-identical to an uninterrupted build
# ---------------------------------------------------------------------------

_BUILD_WORKER = """
import numpy as np
import sys
from dgraph_tpu.plan import build_plan_shards

rng = np.random.default_rng(0)
part = np.sort(rng.integers(0, 4, 48)).astype(np.int64)
edges = rng.integers(0, 48, (2, 300)).astype(np.int64)
build_plan_shards(
    edges, part, out_dir=sys.argv[1], world_size=4, fingerprint="killres",
)
print("BUILD_COMPLETE")
"""


def _run_build(out_dir, chaos=""):
    env = dict(os.environ)
    env["DGRAPH_CHAOS"] = chaos
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-c", _BUILD_WORKER, str(out_dir)],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )


def test_kill_and_resume_bit_identical(tmp_path):
    import dgraph_tpu.plan_shards as ps
    from dgraph_tpu.plan import load_sharded_plan

    killed = tmp_path / "killed"
    clean = tmp_path / "clean"

    # chaos plan.write=sigterm@2: the process dies BEFORE writing shard 2,
    # with shards 0 and 1 already durable in the manifest
    r = _run_build(killed, chaos="plan.write=sigterm@2")
    assert r.returncode == -signal.SIGTERM, (r.returncode, r.stderr[-500:])
    assert "BUILD_COMPLETE" not in r.stdout
    man = ps.read_manifest(str(killed))
    assert not man["complete"]
    assert sorted(man["shards"]) == ["0", "1"]

    # the durable shards must survive the resume UNTOUCHED (resumed, not
    # rebuilt): pin their inode mtimes across the second run
    durable = {
        r2: os.path.getmtime(os.path.join(str(killed), e["file"]))
        for r2, e in man["shards"].items()
    }

    r = _run_build(killed)  # no chaos: resume from the manifest
    assert r.returncode == 0, r.stderr[-800:]
    assert "BUILD_COMPLETE" in r.stdout
    man = ps.read_manifest(str(killed))
    assert man["complete"] and sorted(man["shards"]) == ["0", "1", "2", "3"]
    for r2, mtime in durable.items():
        path = os.path.join(str(killed), man["shards"][r2]["file"])
        assert os.path.getmtime(path) == mtime, f"shard {r2} was rewritten"

    # uninterrupted reference build: every shard pickle bit-identical
    r = _run_build(clean)
    assert r.returncode == 0, r.stderr[-800:]
    assert _shard_shas(str(killed)) == _shard_shas(str(clean))
    pk, _ = load_sharded_plan(str(killed))
    pc, _ = load_sharded_plan(str(clean))
    _assert_plans_equal(pk, pc)


def test_in_process_resume_skips_durable_shards(tmp_path):
    """Same resume contract without subprocesses: a build interrupted by a
    chaos raise at rank 2 resumes past ranks 0-1."""
    from dgraph_tpu import chaos
    from dgraph_tpu.plan import build_plan_shards
    import dgraph_tpu.plan_shards as ps

    edges, part, w = _graph()
    out = str(tmp_path / "shards")
    chaos.arm("plan.build_shard=raise@2")
    try:
        with pytest.raises(chaos.ChaosFault):
            build_plan_shards(
                edges, part, out_dir=out, world_size=w, fingerprint="res",
            )
    finally:
        chaos.reset()
    man = ps.read_manifest(out)
    assert sorted(man["shards"]) == ["0", "1"] and not man["complete"]
    mtimes = {
        r: os.path.getmtime(os.path.join(out, e["file"]))
        for r, e in man["shards"].items()
    }
    manifest = build_plan_shards(
        edges, part, out_dir=out, world_size=w, fingerprint="res",
    )
    assert manifest["complete"]
    for r, t in mtimes.items():
        path = os.path.join(out, manifest["shards"][r]["file"])
        assert os.path.getmtime(path) == t, f"shard {r} was rewritten"


# ---------------------------------------------------------------------------
# fault-tolerant loaders: single-shard repair, full rebuild only when the
# manifest itself is gone
# ---------------------------------------------------------------------------


def _cached(cache_dir, **kw):
    from dgraph_tpu.train.checkpoint import cached_edge_plan

    edges, part, w = _graph()
    return cached_edge_plan(str(cache_dir), edges, part, world_size=w, **kw)


def _plan_dir(cache_dir):
    (d,) = [
        os.path.join(str(cache_dir), x)
        for x in os.listdir(str(cache_dir)) if x.startswith("plan_")
    ]
    return d


def test_corrupt_shard_detected_and_rebuilt_alone(tmp_path, caplog):
    import dgraph_tpu.plan_shards as ps

    plan0, _ = _cached(tmp_path)
    d = _plan_dir(tmp_path)
    man = ps.read_manifest(d)
    victim = os.path.join(d, man["shards"]["2"]["file"])
    blob = bytearray(open(victim, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(victim, "wb").write(bytes(blob))
    others = {
        r: os.path.getmtime(os.path.join(d, e["file"]))
        for r, e in man["shards"].items() if r != "2"
    }

    with caplog.at_level("WARNING"):
        plan1, _ = _cached(tmp_path)
    _assert_plans_equal(plan0, plan1)
    # the log names the shard that triggered the repair...
    assert any(
        "shard 2" in rec.getMessage() for rec in caplog.records
    ), [r.getMessage() for r in caplog.records]
    # ...and the intact shards were not rewritten
    man = ps.read_manifest(d)
    for r, t in others.items():
        assert os.path.getmtime(os.path.join(d, man["shards"][r]["file"])) == t
    assert not ps.bad_shards(d, man)


def test_missing_shard_rebuilt_not_the_world(tmp_path, caplog):
    """A manifest that references shards deleted out from under it rebuilds
    the missing shards, not the world (the satellite fix)."""
    import dgraph_tpu.plan_shards as ps

    plan0, _ = _cached(tmp_path)
    d = _plan_dir(tmp_path)
    man = ps.read_manifest(d)
    os.unlink(os.path.join(d, man["shards"]["1"]["file"]))
    survivors = {
        r: os.path.getmtime(os.path.join(d, e["file"]))
        for r, e in man["shards"].items() if r != "1"
    }

    with caplog.at_level("WARNING"):
        plan1, _ = _cached(tmp_path)
    _assert_plans_equal(plan0, plan1)
    assert any(
        "shard 1" in rec.getMessage() for rec in caplog.records
    ), [r.getMessage() for r in caplog.records]
    man = ps.read_manifest(d)
    assert man["complete"] and not ps.bad_shards(d, man)
    for r, t in survivors.items():
        assert os.path.getmtime(os.path.join(d, man["shards"][r]["file"])) == t


def test_unreadable_manifest_degrades_to_full_rebuild(tmp_path):
    import dgraph_tpu.plan_shards as ps

    plan0, _ = _cached(tmp_path)
    d = _plan_dir(tmp_path)
    open(ps.manifest_path(d), "w").write("{ not json")
    plan1, _ = _cached(tmp_path)
    _assert_plans_equal(plan0, plan1)
    assert ps.read_manifest(d)["complete"]


def test_truncated_shard_detected_by_size(tmp_path):
    import dgraph_tpu.plan_shards as ps
    from dgraph_tpu.plan import load_sharded_plan

    _cached(tmp_path)
    d = _plan_dir(tmp_path)
    man = ps.read_manifest(d)
    victim = os.path.join(d, man["shards"]["0"]["file"])
    blob = open(victim, "rb").read()
    open(victim, "wb").write(blob[: len(blob) // 2])
    with pytest.raises(ps.PlanShardError) as ei:
        load_sharded_plan(d)
    assert ei.value.rank == 0
    assert ps.bad_shards(d, man) == {0: "truncated"}


def test_load_rank_subset_and_multihost_path(tmp_path):
    """Each-host-loads-its-shard: a rank subset's leading axis is
    len(ranks) while the statics still describe the full world."""
    from dgraph_tpu.comm.multihost import process_local_plan_shards
    from dgraph_tpu.plan import load_sharded_plan

    full, _ = _cached(tmp_path)
    d = _plan_dir(tmp_path)
    sub, layout = load_sharded_plan(d, ranks=[1, 3], load_layout=False)
    assert layout is None
    assert sub.src_index.shape[0] == 2
    assert sub.world_size == full.world_size == 4
    assert sub.e_pad == full.e_pad
    assert np.array_equal(sub.src_index[0], full.src_index[1])
    assert np.array_equal(sub.src_index[1], full.src_index[3])
    assert np.array_equal(sub.edge_mask[1], full.edge_mask[3])

    plan, ranks = process_local_plan_shards(d, ranks=[2])
    assert ranks == [2]
    assert np.array_equal(plan.dst_index[0], full.dst_index[2])


@pytest.mark.parametrize("w", [2, 4])
def test_rank_subset_view_bit_identical_to_full_world_slice(tmp_path, w):
    """The substrate assumption of the cross-rank SPMD auditor
    (``analysis.spmd``): ``assemble_plan(load_sharded_plan(ranks=[r]))``
    yields per-rank rows BIT-identical to slicing the full-world plan,
    for EVERY rank — a subset view that disagreed with the full world on
    any array row or any static would make per-rank program builds
    diverge by construction."""
    import dataclasses

    from dgraph_tpu.plan import build_plan_shards, load_sharded_plan

    edges, part, _ = _graph(seed=3, w=w)
    d = str(tmp_path / f"shards_w{w}")
    build_plan_shards(
        edges, part, out_dir=d, world_size=w, overlap=True,
        write_layout=False,
    )
    full, _ = load_sharded_plan(d, load_layout=False)

    def leaves(plan):
        out = {}
        for f in dataclasses.fields(plan):
            v = getattr(plan, f.name)
            if isinstance(v, np.ndarray):
                out[f.name] = v
        for f in dataclasses.fields(plan.halo):
            v = getattr(plan.halo, f.name)
            if isinstance(v, np.ndarray):
                out[f"halo.{f.name}"] = v
        if plan.overlap is not None:
            for f in dataclasses.fields(plan.overlap):
                v = getattr(plan.overlap, f.name)
                if isinstance(v, np.ndarray):
                    out[f"overlap.{f.name}"] = v
        return out

    full_leaves = leaves(full)
    assert full_leaves, "no array leaves found — the comparison is vacuous"
    for r in range(w):
        sub, layout = load_sharded_plan(d, ranks=[r], load_layout=False)
        assert layout is None
        sub_leaves = leaves(sub)
        assert set(sub_leaves) == set(full_leaves)
        for name, leaf in sub_leaves.items():
            assert leaf.shape[0] == 1, (r, name)
            assert leaf.dtype == full_leaves[name].dtype, (r, name)
            assert np.array_equal(leaf[0], full_leaves[name][r]), (r, name)
        # every static the program build consumes must describe the FULL
        # world, not the subset
        assert sub.world_size == full.world_size == w
        for field in ("n_src_pad", "n_dst_pad", "e_pad", "halo_side",
                      "homogeneous", "owner_sorted", "halo_deltas",
                      "scatter_mc", "scatter_block_e", "scatter_block_n"):
            assert getattr(sub, field) == getattr(full, field), (r, field)
        assert sub.halo.s_pad == full.halo.s_pad
        assert (sub.overlap is None) == (full.overlap is None)
        if sub.overlap is not None:
            assert sub.overlap.e_int_pad == full.overlap.e_int_pad
            assert sub.overlap.e_bnd_pad == full.overlap.e_bnd_pad


def test_write_layout_opt_out(tmp_path):
    """write_layout=False skips the O(E) layout sidecar entirely — at
    papers100M scale it pickles to ~25 GB and nothing in the per-host
    load path consumes it (the p100m plan stage runs this way)."""
    import dgraph_tpu.plan_shards as ps
    from dgraph_tpu.plan import build_plan_shards, load_sharded_plan

    edges, part, w = _graph()
    out = str(tmp_path / "shards")
    manifest = build_plan_shards(
        edges, part, out_dir=out, world_size=w, write_layout=False,
    )
    assert manifest["complete"] and manifest["layout"] is None
    assert not os.path.exists(os.path.join(out, ps.LAYOUT_NAME))
    plan, layout = load_sharded_plan(out, load_layout=False)
    assert layout is None and plan.world_size == w


def test_cached_edge_plan_rank_subset_skips_layout(tmp_path):
    """ranks=[...] is the per-host path: it must not read (or verify)
    the O(E) layout sidecar."""
    _cached(tmp_path)  # warm the cache (full build writes the layout)
    d = _plan_dir(tmp_path)
    layout_path = os.path.join(d, "layout.pkl")
    # corrupt the sidecar: a subset load that touched it would raise
    open(layout_path, "wb").write(b"garbage")
    plan, layout = _cached(tmp_path, ranks=[0, 2])
    assert layout is None
    assert plan.src_index.shape[0] == 2
    # a full-world load DOES verify it — and repairs via full rebuild
    plan_full, layout_full = _cached(tmp_path)
    assert layout_full is not None


def test_cached_edge_plan_write_layout_false_round_trips(tmp_path):
    """write_layout=False passed through cached_edge_plan must build,
    cache, and warm-load (plan, None) — not chase a sidecar that was
    never written."""
    from dgraph_tpu.train.checkpoint import cached_edge_plan

    edges, part, w = _graph()
    plan, layout = cached_edge_plan(
        str(tmp_path), edges, part, world_size=w, write_layout=False,
    )
    assert layout is None
    assert not os.path.exists(os.path.join(_plan_dir(tmp_path), "layout.pkl"))
    plan2, layout2 = cached_edge_plan(  # warm hit, no rebuild loop
        str(tmp_path), edges, part, world_size=w, write_layout=False,
    )
    assert layout2 is None
    _assert_plans_equal(plan, plan2)


def test_fresh_start_deletes_stale_artifact(tmp_path):
    """A fingerprint/statics mismatch discards stale progress AND deletes
    the orphaned shard/manifest files — orphaned tens-of-GB shards in a
    fixed out_dir are the r5 disk-exhaustion mode."""
    import dgraph_tpu.plan_shards as ps
    from dgraph_tpu.plan import build_plan_shards

    edges, part, w = _graph()
    out = str(tmp_path / "shards")
    build_plan_shards(edges, part, out_dir=out, world_size=w,
                      fingerprint="old")
    assert os.path.exists(os.path.join(out, ps.shard_filename(0)))
    w2 = ps.PlanShardWriter(out, fingerprint="new", world_size=w, statics={})
    assert not w2.done(0)
    assert not any(
        f.startswith("shard_") or f == ps.LAYOUT_NAME
        for f in os.listdir(out)
    ), os.listdir(out)
    assert not os.path.exists(ps.manifest_path(out))


def test_shards_stamped_with_the_last_format_are_rebuilt(tmp_path):
    """A finished artifact in a fixed out_dir whose manifest says format 12
    (an ``EdgePlan`` with one more static) is none of this build's
    progress: every shard is written again, the manifest says the
    format that wrote it, and the assembled plan is today's class."""
    import dgraph_tpu.plan_shards as ps
    from dgraph_tpu.plan import build_edge_plan, build_edge_plan_sharded
    from dgraph_tpu.train.checkpoint import PLAN_FORMAT_VERSION

    assert PLAN_FORMAT_VERSION == 13
    edges, part, w = _graph()
    out = str(tmp_path / "shards")
    build_edge_plan_sharded(edges, part, out_dir=out, world_size=w)
    man = ps.read_manifest(out)
    assert man["format_version"] == PLAN_FORMAT_VERSION and man["complete"]
    man.pop("manifest_sha256")
    ps.write_manifest(out, {**man, "format_version": 12})
    for e in man["shards"].values():  # a rebuilt shard is a new file
        os.utime(os.path.join(out, e["file"]), (1, 1))

    plan, _ = build_edge_plan_sharded(edges, part, out_dir=out, world_size=w)
    man2 = ps.read_manifest(out)
    assert man2["format_version"] == PLAN_FORMAT_VERSION and man2["complete"]
    for e in man2["shards"].values():
        assert os.path.getmtime(os.path.join(out, e["file"])) > 1, e["file"]
    assert not hasattr(plan, "halo_schedule")
    mono, _ = build_edge_plan(edges, part, world_size=w)
    _assert_plans_equal(mono, plan)


def test_a_plan_cached_under_the_last_format_is_not_loaded(
        tmp_path, monkeypatch):
    """The cache key holds the format: what format 12 cached lies under
    another name, so this tree builds its own and never unpickles it."""
    from dgraph_tpu.train import checkpoint

    monkeypatch.setattr(checkpoint, "PLAN_FORMAT_VERSION", 12)
    _cached(tmp_path)
    old = _plan_dir(tmp_path)
    monkeypatch.undo()
    plan, _ = _cached(tmp_path)
    dirs = sorted(
        x for x in os.listdir(str(tmp_path)) if x.startswith("plan_"))
    assert len(dirs) == 2 and os.path.basename(old) in dirs
    assert not hasattr(plan, "halo_schedule")


def test_cached_edge_plan_ignores_use_native(tmp_path, caplog):
    """The v8 cache always streams through the numpy core (the native
    core fills the whole [W, E_pad] stack); an explicit use_native=True
    from an old caller is ignored with a warning, not a crash."""
    from dgraph_tpu.train.checkpoint import cached_edge_plan

    edges, part, w = _graph()
    with caplog.at_level("WARNING", logger="dgraph_tpu.checkpoint"):
        plan, _ = cached_edge_plan(
            str(tmp_path), edges, part, world_size=w, use_native=True,
        )
    assert plan.world_size == w
    assert any(
        "use_native is ignored" in r.getMessage() for r in caplog.records
    )


def test_cached_edge_plan_ranks_requires_cache_dir():
    from dgraph_tpu.train.checkpoint import cached_edge_plan

    edges, part, w = _graph()
    with pytest.raises(ValueError, match="cache_dir"):
        cached_edge_plan("", edges, part, world_size=w, ranks=[0])


def test_default_fingerprint_is_content_bound(tmp_path):
    """fingerprint="" defaults to a streaming content hash of the build
    inputs: byte-identical inputs (in-RAM or memmap'd) share it, and a
    changed edge list gets a NEW fingerprint so a resumed manifest can
    never adopt the old build's shards even when statics coincide."""
    from dgraph_tpu.plan import build_plan_shards

    edges, part, w = _graph()
    d = str(tmp_path / "shards")
    m1 = build_plan_shards(edges, part, out_dir=d, world_size=w)
    assert m1["fingerprint"].startswith("content:")
    mm_path = tmp_path / "edges.npy"
    np.save(mm_path, edges)
    mm = np.load(mm_path, mmap_mode="r")
    m1b = build_plan_shards(mm, part, out_dir=d, world_size=w)
    assert m1b["fingerprint"] == m1["fingerprint"]
    # same edge multiset, different bytes: the writer must start fresh
    # (fingerprint mismatch), not adopt the previous build's shards
    edges2 = np.ascontiguousarray(edges[:, ::-1])
    m2 = build_plan_shards(edges2, part, out_dir=d, world_size=w)
    assert m2["fingerprint"] != m1["fingerprint"]
    assert m2["complete"]


def test_write_layout_not_in_cache_key(tmp_path):
    """write_layout is an artifact-shape knob, not a plan knob: both
    spellings must hash to ONE cache dir, with the missing sidecar
    self-healed on the first load that wants it — not a duplicate
    multi-GB artifact under a second key."""
    import glob

    from dgraph_tpu.train.checkpoint import cached_edge_plan

    edges, part, w = _graph()
    cached_edge_plan(
        str(tmp_path), edges, part, world_size=w, write_layout=False,
    )
    dirs = glob.glob(str(tmp_path / "plan_*"))
    assert len(dirs) == 1
    plan, layout = cached_edge_plan(str(tmp_path), edges, part, world_size=w)
    assert glob.glob(str(tmp_path / "plan_*")) == dirs
    assert layout is not None  # sidecar written on demand by the repair


def test_cached_edge_plan_no_cache_drops_artifact_kwargs():
    """A falsy cache_dir (the --plan_cache "" convention) builds without
    caching; write_layout describes the on-disk artifact and must not
    leak into build_edge_plan (which rejects it)."""
    from dgraph_tpu.train.checkpoint import cached_edge_plan

    edges, part, w = _graph()
    plan, layout = cached_edge_plan(
        "", edges, part, world_size=w, write_layout=False,
    )
    assert plan.world_size == w and layout is not None


def test_cached_edge_plan_verify_off_warm_hit_still_repairs(tmp_path):
    """verify=False skips the SHA pass on warm hits (the papers100M-scale
    load-cost knob) — but a truncated shard still fails to unpickle and
    takes the same single-shard repair path."""
    import glob

    import dgraph_tpu.plan_shards as ps
    from dgraph_tpu.train.checkpoint import cached_edge_plan

    edges, part, w = _graph()
    plan, _ = cached_edge_plan(str(tmp_path), edges, part, world_size=w)
    plan2, _ = cached_edge_plan(
        str(tmp_path), edges, part, world_size=w, verify=False,
    )
    _assert_plans_equal(plan, plan2)
    pdir = glob.glob(str(tmp_path / "plan_*"))[0]
    man = ps.read_manifest(pdir)
    shard = os.path.join(pdir, man["shards"]["1"]["file"])
    with open(shard, "r+b") as fh:
        fh.truncate(os.path.getsize(shard) // 2)
    plan3, _ = cached_edge_plan(
        str(tmp_path), edges, part, world_size=w, verify=False,
    )
    _assert_plans_equal(plan, plan3)


# ---------------------------------------------------------------------------
# memory budget: structured raise, never an OOM kill
# ---------------------------------------------------------------------------


def test_memory_budget_violation_raises_structured(tmp_path):
    from dgraph_tpu.plan import build_plan_shards
    from dgraph_tpu.plan_shards import PlanBuildMemoryExceeded

    edges, part, w = _graph()
    with pytest.raises(PlanBuildMemoryExceeded) as ei:
        build_plan_shards(
            edges, part, out_dir=str(tmp_path), world_size=w,
            memory_budget_bytes=1024,
        )
    rec = ei.value.record()
    assert rec["kind"] == "plan_build_memory_exceeded"
    assert rec["budget_bytes"] == 1024
    assert rec["needed_bytes"] > 1024
    # the upfront estimate fails BEFORE any shard is assembled
    assert rec["rank"] is None
    assert not os.path.exists(os.path.join(str(tmp_path), "shard_0000.pkl"))


def test_memory_budget_env_knob(tmp_path, monkeypatch):
    from dgraph_tpu.plan import build_plan_shards
    from dgraph_tpu.plan_shards import (
        MEMORY_BUDGET_ENV,
        PlanBuildMemoryExceeded,
    )

    edges, part, w = _graph()
    monkeypatch.setenv(MEMORY_BUDGET_ENV, "0.001")  # ~1 KiB
    with pytest.raises(PlanBuildMemoryExceeded):
        build_plan_shards(
            edges, part, out_dir=str(tmp_path), world_size=w,
        )
    monkeypatch.setenv(MEMORY_BUDGET_ENV, "64")  # plenty for the tiny graph
    manifest = build_plan_shards(
        edges, part, out_dir=str(tmp_path), world_size=w,
    )
    assert manifest["complete"]


def test_shard_nbytes_estimate_is_an_upper_bound(tmp_path):
    from dgraph_tpu.plan import build_plan_shards, shard_nbytes_estimate
    import dgraph_tpu.plan_shards as ps

    edges, part, w = _graph()
    manifest = build_plan_shards(
        edges, part, out_dir=str(tmp_path), world_size=w, overlap=True,
        sort_route=True,
    )
    est = shard_nbytes_estimate(manifest["statics"])
    for r in range(w):
        payload = ps.read_shard(
            str(tmp_path), r, manifest["shards"][str(r)]
        )
        assert ps.payload_nbytes(payload) <= est, r


# ---------------------------------------------------------------------------
# the standalone supervise twin (bench's wedge-surviving probe loop)
# ---------------------------------------------------------------------------


def test_supervise_standalone_twin_contract():
    """bench.py loads train/supervise.py by PATH with the spans/health
    twins pre-registered; the literal fallback constants in that branch
    must track the canonical package values."""
    import importlib.util

    from dgraph_tpu import chaos
    from dgraph_tpu.train import supervise as pkg
    from dgraph_tpu.train.elastic import WEDGED_EXIT_CODE

    def load(name, *rel):
        path = os.path.join(REPO, *rel)
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        return mod

    try:
        load("_dgraph_obs_health", "dgraph_tpu", "obs", "health.py")
        load("_dgraph_obs_spans", "dgraph_tpu", "obs", "spans.py")
        twin = load(
            "_dgraph_train_supervise", "dgraph_tpu", "train", "supervise.py"
        )
        from dgraph_tpu.comm.membership import RANK_LOST_EXIT_CODE

        assert twin.WEDGED_EXIT_CODE == WEDGED_EXIT_CODE == 17
        assert twin.ATTEMPT_ENV_VAR == chaos.ATTEMPT_ENV_VAR
        assert twin.RANK_ENV_VAR == chaos.RANK_ENV_VAR
        assert twin.RANK_LOST_EXIT_CODE == RANK_LOST_EXIT_CODE == 19
        assert pkg.WEDGED_EXIT_CODE == twin.WEDGED_EXIT_CODE
        # the constant's canonical home (dgraph_tpu/utils/env.py, jax-free
        # by lint contract): every consumer — chaos's rank=K matcher, the
        # supervisor's export, membership's rank_from_env, the twin's
        # literal fallback — must carry the SAME string
        from dgraph_tpu.comm import membership
        from dgraph_tpu.utils.env import RANK_ENV_VAR

        assert (
            RANK_ENV_VAR == chaos.RANK_ENV_VAR == pkg.RANK_ENV_VAR
            == membership.RANK_ENV_VAR == twin.RANK_ENV_VAR == "DGRAPH_RANK"
        )
        # the twin's supervise() runs end to end without the package
        lineage = twin.supervise(
            [sys.executable, "-c", "import sys; sys.exit(0)"],
            backoff_s=0.01,
        )
        assert lineage["final_exit_code"] == 0
        assert lineage["kind"] == "supervise_lineage"
    finally:
        for name in ("_dgraph_obs_health", "_dgraph_obs_spans",
                     "_dgraph_train_supervise"):
            sys.modules.pop(name, None)


# ---------------------------------------------------------------------------
# memmap helper: streamed renumbering
# ---------------------------------------------------------------------------


def test_renumber_edges_chunked_matches_in_ram(tmp_path):
    from dgraph_tpu.data.memmap import renumber_edges_chunked

    rng = np.random.default_rng(3)
    edges = rng.integers(0, 100, (2, 1000)).astype(np.int64)
    perm = rng.permutation(100).astype(np.int64)
    out_path = str(tmp_path / "renum.npy")
    got = renumber_edges_chunked(edges, perm, out_path, chunk_cols=128)
    assert isinstance(got, np.memmap)  # file-backed, reclaimable pages
    assert np.array_equal(np.asarray(got), perm[edges])
