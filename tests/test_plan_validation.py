"""plan_memory_usage + validate_plan (+ a papers100M-direction scale check)."""

import numpy as np
import pytest

from dgraph_tpu import plan as pl
from dgraph_tpu.plan import plan_memory_usage, validate_plan


def test_valid_plan_passes(rng):
    edges = rng.integers(0, 64, size=(2, 400))
    part = np.sort(rng.integers(0, 8, 64)).astype(np.int32)
    plan, _ = pl.build_edge_plan(edges, part, world_size=8)
    validate_plan(plan)  # no raise
    mem = plan_memory_usage(plan, feature_dim=128)
    assert mem["total_runtime_bytes"] > 0
    assert mem["halo_buffer_bytes"] == 8 * plan.halo.s_pad * 128 * 4


def test_corrupted_plan_caught(rng):
    import dataclasses

    edges = rng.integers(0, 64, size=(2, 400))
    part = np.sort(rng.integers(0, 8, 64)).astype(np.int32)
    plan, _ = pl.build_edge_plan(edges, part, world_size=8)
    bad_src = np.asarray(plan.src_index).copy()
    bad_src[0, 0] = 10_000_000
    bad = dataclasses.replace(plan, src_index=bad_src)
    with pytest.raises(ValueError, match="src_index"):
        validate_plan(bad)

    bad_send = np.asarray(plan.halo.send_mask).copy()
    bad_send[2, 2, 0] = 1.0  # self-send
    bad2 = dataclasses.replace(plan, halo=dataclasses.replace(plan.halo, send_mask=bad_send))
    with pytest.raises(ValueError, match="sends to itself"):
        validate_plan(bad2)


def _masked(plan, how):
    """``plan`` with ``edge_mask`` no longer the padding mask: a REAL edge
    masked out with its ids left in place (``real_edge``: what a hand-made
    "drop these edges" mask is), or a padded slot whose halo-side id is
    not 0 (``halo_id``)."""
    import dataclasses

    mask = np.asarray(plan.edge_mask).copy()
    src = np.asarray(plan.src_index).copy()
    rank = int(np.argmax(np.asarray(plan.num_edges) > 0))
    if how == "real_edge":
        mask[rank, np.flatnonzero(mask[rank] > 0)[0]] = 0
    else:
        assert plan.halo_side == "src"
        src[rank, np.flatnonzero(mask[rank] == 0)[-1]] = 1
    return dataclasses.replace(plan, edge_mask=mask, src_index=src)


@pytest.mark.parametrize("how,match", [
    ("real_edge", "owner-side index is not n_owner_pad"),
    ("halo_id", "halo-side index is not 0"),
])
@pytest.mark.parametrize("sort_route", [True, False])
def test_an_edge_mask_that_is_not_the_padding_mask_is_refused(
        rng, how, match, sort_route):
    """``edge_mask`` is the padding mask and nothing else: the fused GCN
    layer (``take_scatter_bias_relu``) never reads it and drops a padded
    edge by its ids, so a masked REAL edge would be aggregated there and
    dropped by every other model. ``validate_plan`` refuses such a plan,
    with or without the sorted route, under each of its two checks."""
    edges = rng.integers(0, 64, size=(2, 400))
    part = np.sort(rng.integers(0, 4, 64)).astype(np.int32)
    plan, _ = pl.build_edge_plan(
        edges, part, world_size=4, sort_route=sort_route)
    validate_plan(plan)
    with pytest.raises(ValueError, match=match):
        validate_plan(_masked(plan, how))


@pytest.mark.parametrize("how", ["real_edge", "halo_id"])
def test_a_cached_shard_with_a_masked_real_edge_does_not_load(
        rng, tmp_path, monkeypatch, how):
    """The same two checks where a plan ENTERS the program: a shard of the
    on-disk artifact edited by hand (or written by an older builder) is
    refused by ``assemble_plan``, so it cannot reach the fused layer
    unseen; the builders' shared tail ``_finalize_plan`` holds a fresh
    build to them too."""
    from dgraph_tpu import plan_shards as ps

    edges = rng.integers(0, 64, size=(2, 400))
    part = np.sort(rng.integers(0, 4, 64)).astype(np.int32)
    plan_dir = str(tmp_path / "plan")
    plan, _ = pl.build_edge_plan_sharded(
        edges, part, out_dir=plan_dir, world_size=4)
    validate_plan(plan)
    manifest = ps.read_manifest(plan_dir)
    payloads = {
        r: ps.read_shard(plan_dir, r, manifest["shards"][str(r)])
        for r in range(4)
    }
    pl.assemble_plan(manifest, payloads, list(range(4)))  # as built: loads
    bad = _masked(plan, how)
    for r in range(4):
        payloads[r] = dict(
            payloads[r], edge_mask=np.asarray(bad.edge_mask)[r],
            src_index=np.asarray(bad.src_index)[r])
    with pytest.raises(ValueError, match="invalid EdgePlan"):
        pl.assemble_plan(manifest, payloads, list(range(4)))
    with pytest.raises(ValueError, match="invalid EdgePlan"):
        pl._require_padding_mask(
            bad.src_index, bad.dst_index, bad.edge_mask, bad.halo_side,
            bad.n_src_pad, bad.n_dst_pad)
    seen = []
    monkeypatch.setattr(
        pl, "_padding_mask_errors", lambda *a: seen.append(a) or [])
    pl.build_edge_plan(edges, part, world_size=4)
    pl.build_edge_plan(edges, part, world_size=4, use_native=False)
    assert len(seen) == 2  # _finalize_plan, whichever core built the rows


@pytest.mark.slow
def test_scale_plan_build_5m_edges(rng):
    """papers100M-direction scale check: 500k vertices / 5M edges through
    partition + plan build + validation within test-tolerable time. (The
    real papers100M build, 111M/1.6B, is a batch job: same code path,
    native dedup, plan cache — SURVEY §7 hard-parts.)"""
    import time

    from dgraph_tpu import partition as pt
    from dgraph_tpu.data.synthetic import power_law_graph

    V, W = 500_000, 16
    edges = power_law_graph(V, 10.0, seed=1)
    t0 = time.time()
    part = pt.greedy_bfs_partition(edges, V, W)
    ren = pt.renumber_contiguous(part, W)
    new_edges = ren.perm[edges]
    plan, layout = pl.build_edge_plan(new_edges, ren.partition, world_size=W)
    dt = time.time() - t0
    validate_plan(plan)
    assert float(np.asarray(plan.edge_mask).sum()) == edges.shape[1]
    assert dt < 120, f"plan build too slow: {dt:.1f}s"


class TestSortRouteValidation:
    """validate_plan's halo-sort-route checks: a valid plan passes (real
    edges at their index, padded edges after them at the sentinel past the
    last vertex block); each corruption class (non-permutation,
    non-monotone, ids mismatch, a padded edge inside a block, a real edge
    at the sentinel) is rejected — stale/corrupt cached plans must
    rebuild, not silently feed the Pallas sorted kernels."""

    def _plan(self):
        rng = np.random.default_rng(3)
        V, E, W = 64, 400, 4
        edges = np.stack([rng.integers(0, V, E), rng.integers(0, V, E)])
        part = np.sort(rng.integers(0, W, V)).astype(np.int32)
        return pl.build_edge_plan(edges, part, world_size=W, edge_owner="dst")[0]

    def test_valid_plan_passes(self):
        plan = self._plan()
        validate_plan(plan)
        # every rank's row ends in padded edges: the rule is exercised
        assert (np.asarray(plan.num_edges) < plan.e_pad).all()

    @staticmethod
    def _sentinel(plan):
        n_rows = plan.n_src_pad + plan.world_size * plan.halo.s_pad
        return pl.halo_sort_sentinel(n_rows, plan.scatter_block_n)

    def test_masked_edge_inside_a_block_rejected(self):
        """The rule before format v11: a padded edge at sorted id 0."""
        import dataclasses

        plan = self._plan()
        halo_idx = np.asarray(plan.src_index)
        perm = np.argsort(halo_idx, axis=1, kind="stable").astype(np.int32)
        old_rule = dataclasses.replace(
            plan, halo_sort_perm=perm,
            halo_sorted_ids=np.take_along_axis(halo_idx, perm, axis=1))
        with pytest.raises(ValueError, match="this one is masked"):
            validate_plan(old_rule)
        # at the tail, monotone, but inside the last block: n_rows is no
        # multiple of the block, so n_rows itself is not past it
        n_rows = plan.n_src_pad + plan.world_size * plan.halo.s_pad
        assert n_rows < self._sentinel(plan)
        sids = np.asarray(plan.halo_sorted_ids).copy()
        sids[sids == self._sentinel(plan)] = n_rows
        bad = dataclasses.replace(plan, halo_sorted_ids=sids)
        with pytest.raises(ValueError, match="this one is masked"):
            validate_plan(bad)

    def test_real_edge_at_the_sentinel_rejected(self):
        import dataclasses

        plan = self._plan()
        sids = np.asarray(plan.halo_sorted_ids).copy()
        sids[0, int(plan.num_edges[0]) - 1] = self._sentinel(plan)
        bad = dataclasses.replace(plan, halo_sorted_ids=sids)
        with pytest.raises(ValueError, match="this one is real"):
            validate_plan(bad)

    def test_non_monotone_sorted_ids_rejected(self):
        import dataclasses

        plan = self._plan()
        bad = dataclasses.replace(
            plan,
            halo_sorted_ids=np.flip(np.asarray(plan.halo_sorted_ids), axis=1),
        )
        with pytest.raises(ValueError, match="not monotone"):
            validate_plan(bad)

    def test_non_permutation_rejected(self):
        import dataclasses

        plan = self._plan()
        perm = np.asarray(plan.halo_sort_perm).copy()
        perm[0, 0] = perm[0, 1]  # duplicate entry: not a permutation
        bad = dataclasses.replace(plan, halo_sort_perm=perm)
        with pytest.raises(ValueError, match="not a permutation"):
            validate_plan(bad)

    def test_owner_ids_out_of_step_with_the_route_rejected(self):
        """``halo_sorted_owner_ids`` is ``dst_index[halo_sort_perm]``
        (format v12): the fused GCN layer's backward gathers by it, so a
        row that is not the owner index in the route's order, or a route
        without it, must not load."""
        import dataclasses

        plan = self._plan()
        oids = np.asarray(plan.halo_sorted_owner_ids)
        np.testing.assert_array_equal(
            oids, np.take_along_axis(
                np.asarray(plan.dst_index), np.asarray(plan.halo_sort_perm),
                axis=1))
        # a padded edge keeps the owner side's out-of-range id
        assert (oids[:, -1] == plan.n_dst_pad).all()
        for bad_ids in (np.asarray(plan.dst_index), np.roll(oids, 1, axis=1)):
            bad = dataclasses.replace(plan, halo_sorted_owner_ids=bad_ids)
            with pytest.raises(
                    ValueError, match=r"owner_index\[halo_sort_perm\]"):
                validate_plan(bad)
        with pytest.raises(ValueError, match="halo_sorted_owner_ids missing"):
            validate_plan(
                dataclasses.replace(plan, halo_sorted_owner_ids=None))

    def test_ids_mismatch_rejected(self):
        import dataclasses

        plan = self._plan()
        sids = np.asarray(plan.halo_sorted_ids).copy()
        # keep monotone (and the padded tail at its sentinel) but break the
        # real edges' halo_index[perm] == sorted_ids tie
        sids[0, : int(plan.num_edges[0])] += 1
        bad = dataclasses.replace(plan, halo_sorted_ids=sids)
        with pytest.raises(
                ValueError, match=r"halo_index\[perm\].*this one is real"):
            validate_plan(bad)


def test_a_plan_cached_under_format_v10_rebuilds(tmp_path, monkeypatch):
    """Format v10 keyed a padded edge 0 on the halo-sorted route. Its cached
    plans no longer pass validate_plan, so the version in the cache key has
    to send the same call to a fresh build, not to the stale artifact."""
    from dgraph_tpu.train import checkpoint as ck

    rng = np.random.default_rng(3)
    edges = np.stack([rng.integers(0, 64, 400), rng.integers(0, 64, 400)])
    part = np.sort(rng.integers(0, 4, 64)).astype(np.int32)

    def cached():
        return ck.cached_edge_plan(
            str(tmp_path), edges, part, world_size=4, edge_owner="dst")[0]

    def v10_route(halo_idx, edge_mask, n_halo_rows, owner_idx):
        perm = np.argsort(halo_idx, axis=-1, kind="stable").astype(np.int32)
        return (perm, np.take_along_axis(halo_idx, perm, axis=-1),
                np.take_along_axis(owner_idx, perm, axis=-1))

    with monkeypatch.context() as m:
        m.setattr(ck, "PLAN_FORMAT_VERSION", 10)
        m.setattr(pl, "halo_sort_route", v10_route)
        stale = cached()
    with pytest.raises(ValueError, match="this one is masked"):
        validate_plan(stale)
    assert ck.PLAN_FORMAT_VERSION >= 11
    fresh = cached()
    validate_plan(fresh)
    assert len([d for d in tmp_path.iterdir() if d.name.startswith("plan_")]) == 2
    validate_plan(cached())  # and the warm hit is the fresh artifact


def test_a_plan_cached_under_format_v11_rebuilds(tmp_path, monkeypatch):
    """Format v11 had no ``halo_sorted_owner_ids`` (stood in for here by a
    row of zeros: a shard without the key does not even stack). Its cached
    plans no longer pass validate_plan, so the version in the cache key has
    to send the same call to a fresh build, not to the stale artifact."""
    from dgraph_tpu.train import checkpoint as ck

    rng = np.random.default_rng(3)
    edges = np.stack([rng.integers(0, 64, 400), rng.integers(0, 64, 400)])
    part = np.sort(rng.integers(0, 4, 64)).astype(np.int32)
    route = pl.halo_sort_route

    def cached():
        return ck.cached_edge_plan(
            str(tmp_path), edges, part, world_size=4, edge_owner="dst")[0]

    with monkeypatch.context() as m:
        m.setattr(ck, "PLAN_FORMAT_VERSION", 11)
        m.setattr(pl, "halo_sort_route", lambda *args: (
            *route(*args)[:2], np.zeros_like(route(*args)[2])))
        stale = cached()
    with pytest.raises(ValueError, match=r"owner_index\[halo_sort_perm\]"):
        validate_plan(stale)
    assert ck.PLAN_FORMAT_VERSION >= 12
    fresh = cached()
    validate_plan(fresh)
    assert len([d for d in tmp_path.iterdir() if d.name.startswith("plan_")]) == 2


@pytest.mark.parametrize("edge_owner", ["dst", "src"])
def test_every_build_gives_the_same_owner_ids_on_the_route(
        tmp_path, edge_owner):
    """Monolithic numpy, native and streamed-shard builds make the route in
    one place (``halo_sort_route``): the owner ids in the route's order are
    the same array, and are the owner index permuted."""
    from dgraph_tpu import native

    rng = np.random.default_rng(5)
    edges = np.stack([rng.integers(0, 200, 1500), rng.integers(0, 200, 1500)])
    part = np.sort(rng.integers(0, 4, 200)).astype(np.int32)
    kw = dict(world_size=4, edge_owner=edge_owner)
    plans = {"numpy": pl.build_edge_plan(edges, part, use_native=False, **kw)[0],
             "streamed": pl.build_edge_plan_sharded(
                 edges, part, out_dir=str(tmp_path / "shards"), **kw)[0]}
    if native.available():
        plans["native"] = pl.build_edge_plan(
            edges, part, use_native=True, **kw)[0]
    want = np.asarray(plans["numpy"].halo_sorted_owner_ids)
    owner = (plans["numpy"].dst_index if edge_owner == "dst"
             else plans["numpy"].src_index)
    np.testing.assert_array_equal(want, np.take_along_axis(
        np.asarray(owner), np.asarray(plans["numpy"].halo_sort_perm), axis=1))
    for name, plan in plans.items():
        validate_plan(plan)
        np.testing.assert_array_equal(
            np.asarray(plan.halo_sorted_owner_ids), want, err_msg=name)
