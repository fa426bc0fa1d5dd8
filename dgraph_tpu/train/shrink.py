"""Shrink-to-fit elastic world recovery: re-plan, reshard, adopt.

The recovery half of treating rank loss as a *planned redistribution to a
smaller world* ("Memory-efficient array redistribution through portable
collective communication", PAPERS.md) rather than a fatal crash.
Detection lives in :mod:`dgraph_tpu.comm.membership`; the restart policy
in :func:`dgraph_tpu.train.supervise.supervise_group`; this module owns
the world STATE and its recovery transitions:

- **One run directory, generational artifacts.** ``world.json`` is the
  single adoption pointer: ``{generation, world_size, resume_step, ...}``.
  Every generation ``g`` owns its own plan directory (``plan_g<g>``, a PR 8
  sharded v8 artifact), per-rank checkpoint directories
  (``ckpt_g<g>/rank_<r>``), membership directory (``membership_g<g>`` —
  fresh per generation so stale leases can never pollute the shrunk
  world), and graph snapshot (``graph_g<g>.npz``: renumbered edges,
  partition, counts, and ``orig_ids`` mapping generation-local vertex ids
  back to the original numbering, composed across shrinks).

- **Shrink = fold + rebuild + reshard + atomic adopt.**
  :func:`shrink_world` folds the lost ranks' vertices onto survivors
  (:func:`~dgraph_tpu.partition.fold_partition` — deterministic
  waterfill), renumbers, and rebuilds the plan for the surviving world
  size **in the background** through the streaming
  :func:`~dgraph_tpu.plan.build_plan_shards` (memory-budgeted, durable
  after every shard, RESUMABLE — a recovery killed mid-build picks up
  from its manifest) while the foreground gathers the newest checkpoint
  step durable on EVERY old rank (the last consistent cut — the dead
  rank's state only survives in its checkpoint) and reshards it with
  :func:`~dgraph_tpu.plan.reshard_vertex_data`.  Only after the new plan,
  checkpoints, and graph snapshot are all durable does ``world.json``
  flip — one atomic rename (:func:`~dgraph_tpu.plan_shards.
  atomic_write_json`), so a crash at ANY point leaves either the old
  world or the new world adopted, never a torn mix.

- **Bit-identical degraded resume.** Every step of the transition is a
  pure function of ``(old artifacts, lost_ranks)``: the fold is
  deterministic, the plan build is the same streaming core a fault-free
  W−1 build uses, and the reshard moves rows by vertex identity.  A
  resumed degraded run is therefore bit-identical to a fault-free run at
  the smaller world started from the same resharded checkpoint — the
  contract PR 5 pinned for restart/resume, extended to world shrinks
  (pinned end-to-end by ``tests/test_shrink.py``).
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

import numpy as np

import dgraph_tpu.obs.spans as spans

_logger = logging.getLogger("dgraph_tpu.shrink")

WORLD_POINTER = "world.json"


class ShrinkError(RuntimeError):
    """A world transition could not complete (no consistent checkpoint
    cut, missing generation artifacts, ...)."""

    def __init__(self, reason: str):
        super().__init__(f"shrink-to-fit recovery failed: {reason}")
        self.reason = reason

    def record(self) -> dict:
        return {"kind": "shrink_error", "reason": self.reason}


# ---------------------------------------------------------------------------
# generational layout helpers (ONE place derives every path)
# ---------------------------------------------------------------------------


def world_path(run_dir: str) -> str:
    return os.path.join(run_dir, WORLD_POINTER)


def plan_dir(run_dir: str, generation: int) -> str:
    return os.path.join(run_dir, f"plan_g{generation}")


def ckpt_dir(run_dir: str, generation: int) -> str:
    return os.path.join(run_dir, f"ckpt_g{generation}")


def rank_ckpt_dir(run_dir: str, generation: int, rank: int) -> str:
    return os.path.join(ckpt_dir(run_dir, generation), f"rank_{rank}")


def membership_dir(run_dir: str, generation: int, attempt: int = 0) -> str:
    """Membership directory for one (generation, supervisor-attempt)
    incarnation.  Fresh per ATTEMPT, not just per generation: a
    same-world collective restart (wedge) would otherwise relaunch into
    the killed attempt's stale leases — rendezvous would count them as
    present and the first poll would age them into a spurious RankLost
    against a peer that is merely slow to re-import."""
    return os.path.join(run_dir, f"membership_g{generation}_a{attempt}")


def graph_path(run_dir: str, generation: int) -> str:
    return os.path.join(run_dir, f"graph_g{generation}.npz")


def read_world(run_dir: str) -> dict:
    """The current adoption pointer; raises :class:`ShrinkError` when the
    run directory holds none (or a torn/invalid one — the atomic write
    makes that a real corruption, not a benign race)."""
    import json

    path = world_path(run_dir)
    try:
        with open(path) as fh:
            rec = json.load(fh)
    except OSError as e:
        raise ShrinkError(f"no world pointer at {path} ({e})")
    except ValueError as e:
        raise ShrinkError(f"world pointer {path} unreadable: {e}")
    if rec.get("kind") != "elastic_world":
        raise ShrinkError(f"{path} is not an elastic_world record")
    return rec


def write_world(run_dir: str, rec: dict) -> None:
    """ATOMIC adoption: the rename is the commit point of a world
    transition."""
    from dgraph_tpu.plan_shards import atomic_write_json

    atomic_write_json(world_path(run_dir), rec)


# ---------------------------------------------------------------------------
# world lifecycle
# ---------------------------------------------------------------------------


def init_world(
    run_dir: str,
    edge_index: np.ndarray,
    num_nodes: int,
    world_size: int,
    *,
    partition_method: str = "block",
    seed: int = 0,
    pad_multiple: int = 8,
    overlap: bool = False,
    lease_s: float = 5.0,
    heartbeat_interval_s: Optional[float] = None,
    memory_budget_bytes: Optional[int] = None,
) -> dict:
    """Create generation 0 of an elastic run: partition + renumber the
    graph, build the sharded plan artifact, snapshot the graph, and adopt
    ``world.json``.  Idempotent on rerun (the plan build resumes; the
    pointer write is last)."""
    from dgraph_tpu.partition import partition_graph
    from dgraph_tpu.plan import build_plan_shards
    from dgraph_tpu.plan_shards import atomic_savez

    os.makedirs(run_dir, exist_ok=True)
    new_edges, ren = partition_graph(
        edge_index, num_nodes, world_size, method=partition_method,
        seed=seed,
    )
    # fsync+rename, never a bare np.savez: a crash mid-write must not
    # leave a torn graph_g0.npz under the name every later generation
    # folds from (host-durable-write)
    atomic_savez(
        graph_path(run_dir, 0),
        edge_index=new_edges,
        partition=ren.partition,
        counts=ren.counts,
        orig_ids=ren.inv,  # generation-0 vertex id -> original id
    )
    build_plan_shards(
        new_edges, ren.partition, out_dir=plan_dir(run_dir, 0),
        world_size=world_size, pad_multiple=pad_multiple,
        overlap=overlap or None,
        write_layout=False, memory_budget_bytes=memory_budget_bytes,
    )
    rec = {
        "kind": "elastic_world",
        "generation": 0,
        "world_size": int(world_size),
        "resume_step": 0,
        "lease_s": float(lease_s),
        "heartbeat_interval_s": heartbeat_interval_s,
        "pad_multiple": int(pad_multiple),
        # plan-build knobs every later generation must REPLAY: a shrink
        # that rebuilt without the interior/boundary split would silently
        # outlaw the overlap lowering in the degraded world
        "plan_overlap": bool(overlap),
        "lost_history": [],
    }
    write_world(run_dir, rec)
    return rec


def build_generation_plan(
    run_dir: str,
    generation: int,
    edges: np.ndarray,
    partition: np.ndarray,
    world: dict,
    world_size: int,
) -> dict:
    """Rebuild the sharded plan artifact for one generation through the
    streaming per-rank builder (durable after every shard, RESUMABLE from
    its own manifest), replaying the world record's plan knobs — a
    transition that rebuilt without the interior/boundary split would
    silently outlaw the overlap lowering in the new world.
    Shared by the shrink AND grow transitions (:mod:`dgraph_tpu.train.
    grow` is lint-enforced jax-free, so the jax-pulling
    :mod:`dgraph_tpu.plan` import stays quarantined here)."""
    from dgraph_tpu.plan import build_plan_shards

    return build_plan_shards(
        edges, partition,
        out_dir=plan_dir(run_dir, generation),
        world_size=world_size,
        pad_multiple=int(world.get("pad_multiple", 8)),
        overlap=world.get("plan_overlap", False) or None,
        write_layout=False,
    )


def _walk_leaves(tree, path=()):
    """(path, leaf) pairs over dict/list/tuple trees — hand-rolled like
    chaos.poison_pytree; checkpointed host state is plain containers."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _walk_leaves(tree[k], path + (k,))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _walk_leaves(v, path + (i,))
    else:
        yield path, tree


def _map_tree(tree, fn, path=()):
    """Rebuild a dict/list/tuple tree with ``fn(path, leaf)`` at every
    leaf.  Functional on purpose: tuples (incl. optimizer-state
    NamedTuples) are immutable, so in-place leaf assignment cannot
    reshard them."""
    if isinstance(tree, dict):
        return {k: _map_tree(v, fn, path + (k,)) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        items = [_map_tree(v, fn, path + (i,)) for i, v in enumerate(tree)]
        if isinstance(tree, tuple):
            # NamedTuples (optax states) take positional fields; plain
            # tuples take an iterable
            return (
                type(tree)(*items) if hasattr(tree, "_fields")
                else tuple(items)
            )
        return items
    return fn(path, tree)


def _get_leaf(tree, path):
    node = tree
    for p in path:
        node = node[p]
    return node


def _reshard_states(
    states: list,
    old_counts: np.ndarray,
    n_pad_old: int,
    new_index: np.ndarray,
    new_counts: np.ndarray,
    n_pad_new: int,
    new_world: int,
) -> list:
    """Per-OLD-rank state trees -> per-NEW-rank state trees.  A leaf whose
    leading dim equals the old per-rank pad is vertex-sharded and moves
    through :func:`~dgraph_tpu.plan.reshard_vertex_data`; anything else is
    replicated (model params, scalars) and rank 0's copy is adopted."""
    from dgraph_tpu.plan import reshard_vertex_data

    resharded = {}
    for path, leaf in _walk_leaves(states[0]):
        arr = np.asarray(leaf)
        if arr.ndim >= 1 and arr.shape[0] == n_pad_old:
            stacked = np.stack([
                np.asarray(_get_leaf(states[r], path))
                for r in range(len(states))
            ])
            resharded[path] = reshard_vertex_data(
                stacked, old_counts, new_index, new_counts, n_pad_new
            )
    return [
        _map_tree(
            states[0],
            lambda path, leaf: (
                resharded[path][r] if path in resharded else leaf
            ),
        )
        for r in range(new_world)
    ]


def shrink_world(run_dir: str, lost_ranks) -> dict:
    """Transition the run to ``W - len(lost_ranks)`` ranks; returns the
    adopted world record (plus ``resume_step``).

    Crash-safe and rerunnable: artifacts are written under the NEW
    generation's names (the old world stays intact and adopted until the
    final pointer flip), the plan build resumes from its own manifest,
    and checkpoint/graph writes are atomic.  The plan rebuild runs in a
    background thread, overlapped with the checkpoint gather/reshard.
    """
    from dgraph_tpu import plan_shards as ps
    from dgraph_tpu.partition import fold_partition, renumber_contiguous
    from dgraph_tpu.train.checkpoint import (
        all_steps,
        restore_checkpoint,
        save_checkpoint,
    )

    world = read_world(run_dir)
    gen, W = int(world["generation"]), int(world["world_size"])
    lost = sorted(set(int(r) for r in lost_ranks))
    new_gen, new_world = gen + 1, W - len(lost)
    if new_world < 1:
        raise ShrinkError(
            f"cannot shrink world {W} by {len(lost)} lost rank(s)"
        )
    with spans.span(
        "shrink.recover", run_dir=run_dir, generation=new_gen,
        old_world=W, new_world=new_world, lost=lost,
    ) as rspan:
        graph = np.load(graph_path(run_dir, gen))
        part_fold, _survivor_map = fold_partition(
            graph["partition"], W, lost
        )
        ren = renumber_contiguous(part_fold, new_world)
        new_edges = ren.perm[np.asarray(graph["edge_index"])]
        orig_ids = np.asarray(graph["orig_ids"])[ren.inv]

        # background: rebuild the plan for the surviving world through the
        # streaming per-rank builder (durable + resumable, plan.* chaos
        # points live) while the foreground reshards the checkpoint
        build_out: dict = {}

        def _build():
            with spans.span("shrink.replan", parent=rspan,
                            world_size=new_world):
                try:
                    build_out["manifest"] = build_generation_plan(
                        run_dir, new_gen, new_edges, ren.partition,
                        world, new_world,
                    )
                except BaseException as e:  # re-raised on join
                    build_out["error"] = e

        builder = threading.Thread(target=_build, name="shrink-replan")
        builder.start()

        # foreground: the newest checkpoint step durable on EVERY old rank
        # — the dead ranks' state only survives in their checkpoints, and
        # a step some rank never finished saving is not a consistent cut
        step_sets = [
            set(all_steps(rank_ckpt_dir(run_dir, gen, r))) for r in range(W)
        ]
        common = set.intersection(*step_sets) if step_sets else set()
        if not common:
            builder.join()
            raise ShrinkError(
                f"no checkpoint step durable on all {W} rank(s) of "
                f"generation {gen} (per-rank steps: "
                f"{[sorted(s) for s in step_sets]})"
            )
        resume_step = max(common)
        with spans.span("shrink.gather", parent=rspan, step=resume_step):
            per_rank = [
                restore_checkpoint(
                    rank_ckpt_dir(run_dir, gen, r), step=resume_step
                )
                for r in range(W)
            ]
        builder.join()
        if "error" in build_out:
            raise build_out["error"]
        manifest = build_out["manifest"]
        statics = manifest["statics"]
        if not statics.get("homogeneous", True):
            raise NotImplementedError(
                "shrink_world currently reshards homogeneous vertex state"
            )
        n_pad_new = int(statics["n_dst_pad"])
        old_statics = ps.read_manifest(plan_dir(run_dir, gen))["statics"]
        n_pad_old = int(old_statics["n_dst_pad"])

        with spans.span("shrink.reshard", parent=rspan, step=resume_step):
            new_states = _reshard_states(
                [p["state"] for p in per_rank],
                np.asarray(graph["counts"]),
                n_pad_old,
                ren.inv,
                ren.counts,
                n_pad_new,
                new_world,
            )
            for r in range(new_world):
                save_checkpoint(
                    rank_ckpt_dir(run_dir, new_gen, r),
                    {"state": new_states[r], "step": resume_step},
                    resume_step,
                )
        # atomic like the checkpoints above it: the graph snapshot is a
        # payload the pointer flip below adopts, and a torn snapshot
        # under a valid name would poison every later fold
        ps.atomic_savez(
            graph_path(run_dir, new_gen),
            edge_index=new_edges,
            partition=ren.partition,
            counts=ren.counts,
            orig_ids=orig_ids,
        )
        rec = {
            **world,
            "generation": new_gen,
            "world_size": new_world,
            "resume_step": int(resume_step),
            "lost_history": list(world.get("lost_history", []))
            + [{"generation": gen, "lost": lost,
                "resume_step": int(resume_step)}],
        }
        # THE adoption: one atomic rename flips every reader (workers
        # derive plan/ckpt/membership paths from the generation) to the
        # degraded world
        write_world(run_dir, rec)
        rspan.annotate(resume_step=int(resume_step))
        _logger.info(
            "shrink-to-fit adopted: generation %d, world %d -> %d, lost "
            "%s, resume step %d", new_gen, W, new_world, lost, resume_step,
        )
    return rec
