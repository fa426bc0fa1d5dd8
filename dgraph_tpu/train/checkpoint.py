"""Checkpoint / resume + plan caching.

The reference checkpoints only model state_dicts with no optimizer/step state
and no resume path (``train_graphcast.py:150-151``, SURVEY §5); its important
persisted artifacts are preprocessing caches (partitioned graphs, per-rank
comm plans — ``distributed_graph_dataset.py:399-422``,
``ogbn_datasets.py:96-123``). This module provides both, better:

- full train-state checkpointing (params + opt_state + step) via orbax,
  with resume;
- a plan cache keyed by (graph content hash, world_size, edge_owner,
  pad_multiple) — the reference keys synthetic caches by config hash the
  same way (``synthetic_dataset.py:180-196``).
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
from typing import Any, Optional

import numpy as np

_logger = logging.getLogger("dgraph_tpu.checkpoint")


def atomic_pickle_dump(path: str, obj: Any) -> None:
    """Pickle to a temp file, flush + fsync, then os.replace into place:
    concurrent readers (multi-process launches polling a cache path) never
    see a truncated artifact, and a HOST crash cannot leave a
    durable-looking but empty/truncated file behind the rename — without
    the fsync, os.replace can commit the name before the kernel commits
    the data, and the post-crash filesystem shows a valid path holding
    zero bytes."""
    tmp = path + f".tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        pickle.dump(obj, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


# --- train state checkpointing (orbax) ---


def save_checkpoint(ckpt_dir: str, state: dict, step: int) -> None:
    """Save a pytree (e.g. {'params':…, 'opt_state':…, 'step':…}).

    Consults the ``ckpt.save`` chaos point (:mod:`dgraph_tpu.chaos`) at
    entry — a ``raise`` clause simulates the save-side IO fault whose
    recovery path is the restore-side fall-back-to-older-step."""
    from dgraph_tpu import chaos

    chaos.fire("ckpt.save")
    import orbax.checkpoint as ocp

    path = os.path.abspath(os.path.join(ckpt_dir, f"step_{step:08d}"))
    with ocp.PyTreeCheckpointer() as ckptr:
        ckptr.save(path, state, force=True)


def all_steps(ckpt_dir: str) -> list:
    """Ascending list of checkpoint step numbers present in ``ckpt_dir``.
    Quarantined entries (``step_XXXXXXXX.corrupt``, see
    :func:`restore_checkpoint`) are skipped — a step known bad is not a
    resume candidate."""
    if not os.path.isdir(ckpt_dir):
        return []
    return sorted(
        int(d.split("_")[1])
        for d in os.listdir(ckpt_dir)
        if d.startswith("step_") and d.split("_")[1].isdigit()
    )


def quarantined_steps(ckpt_dir: str) -> list:
    """Ascending step numbers of quarantined (``.corrupt``-renamed)
    checkpoint dirs — the operator's "what did the loader give up on"
    probe. Rename a dir back to ``step_XXXXXXXX`` to retry it."""
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for d in os.listdir(ckpt_dir):
        if d.startswith("step_") and d.endswith(".corrupt"):
            num = d[len("step_"):-len(".corrupt")]
            if num.isdigit():
                out.append(int(num))
    return sorted(out)


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = all_steps(ckpt_dir)
    return steps[-1] if steps else None


def restore_checkpoint(
    ckpt_dir: str, template: Optional[dict] = None, step: Optional[int] = None
) -> Optional[dict]:
    """Restore the given (or latest) step into template's structure; None if
    no checkpoint exists. ``template=None`` restores the raw saved tree.

    With ``step=None`` (the serving / resume path), a corrupt/truncated
    checkpoint (killed mid-save, torn copy) does not abort the restore:
    the loader logs it, falls back to the next-older step, and — once an
    older step restores successfully, proving the reader/template works —
    **quarantines** the failed dirs (renamed to ``step_XXXXXXXX.corrupt``,
    so the bad pickle is never silently re-read, and re-logged, on every
    subsequent load; ``all_steps`` skips quarantined entries and
    :func:`quarantined_steps` lists them). When every on-disk step fails
    the last error propagates (returning None there would silently
    restart from scratch) and NOTHING is quarantined — an all-steps
    failure is likely systematic (template mismatch, broken orbax env),
    and renaming every good checkpoint away would destroy the evidence.
    An explicitly requested ``step`` is strict: missing raises
    FileNotFoundError, unreadable raises the underlying error without
    quarantining — silently serving an older checkpoint than the one
    NAMED would mislabel every downstream metric.

    The ``ckpt.read`` chaos point fires at entry (a deterministic stand-in
    for the torn-copy/unreadable-volume faults the fallback loop exists
    for).
    """
    from dgraph_tpu import chaos

    chaos.fire("ckpt.read")
    import orbax.checkpoint as ocp

    steps = all_steps(ckpt_dir)
    if step is not None:
        if step not in steps:
            raise FileNotFoundError(
                f"checkpoint step {step} not found under {ckpt_dir!r} "
                f"(present: {steps})"
            )
        steps = [step]
    if not steps:
        return None
    last_err = None
    failed = []  # (step, path, error) pending quarantine
    for s in reversed(steps):
        path = os.path.abspath(os.path.join(ckpt_dir, f"step_{s:08d}"))
        try:
            with ocp.PyTreeCheckpointer() as ckptr:
                got = ckptr.restore(path, item=template)
        except Exception as e:  # noqa: BLE001 — any read/parse failure
            if step is not None:
                raise
            last_err = e
            failed.append((s, path, e))
            _logger.warning(
                "checkpoint step_%08d unreadable (%s: %s); falling back to "
                "next-older step", s, type(e).__name__, e,
            )
            continue
        # quarantine ONLY once an older step restored (that success proves
        # the reader works — an all-steps failure is systematic and would
        # otherwise rename every GOOD step away), and only when the
        # failed step is unreadable even RAW (template=None): a raw
        # restore that succeeds means the failure was a template/schema
        # mismatch — e.g. a code rollback across a state-schema change —
        # and the newest training progress must stay a resume candidate.
        # The rename is what makes "log once" true: the entry leaves
        # all_steps(), so no later load re-reads (or re-warns about) a
        # step already known bad. Reversible by renaming back;
        # best-effort (a read-only volume keeps fall-back-every-time).
        for fs, fpath, fe in failed:
            if template is not None:
                try:
                    with ocp.PyTreeCheckpointer() as ckptr:
                        ckptr.restore(fpath)
                    _logger.warning(
                        "checkpoint step_%08d restores raw but not into "
                        "the given template (%s: %s); NOT quarantining — "
                        "likely a state-schema mismatch, not corruption",
                        fs, type(fe).__name__, fe,
                    )
                    continue
                except Exception:  # noqa: BLE001 — genuinely unreadable
                    pass
            qpath = fpath + ".corrupt"
            try:
                os.replace(fpath, qpath)
                _logger.warning(
                    "checkpoint step_%08d quarantined to %s (%s: %s)",
                    fs, os.path.basename(qpath), type(fe).__name__, fe,
                )
            except OSError as qe:
                _logger.warning(
                    "checkpoint step_%08d quarantine failed: %s", fs, qe,
                )
        return got
    # every step failed: likely systematic (bad template, broken orbax
    # env) — quarantining here would destroy evidence wholesale
    raise last_err


def checkpoint_keys(ckpt_dir: str, step: Optional[int] = None):
    """Top-level pytree keys of the given (or latest) checkpoint, or None
    if no checkpoint exists. Lets callers pick a restore TEMPLATE from
    what the checkpoint actually contains (e.g. an 'ema' track) instead
    of try/except-ing template mismatches — which would also swallow
    genuine corruption/IO errors (ADVICE r3 #5)."""
    import orbax.checkpoint as ocp

    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        return None
    path = os.path.abspath(os.path.join(ckpt_dir, f"step_{step:08d}"))
    with ocp.PyTreeCheckpointer() as ckptr:
        md = ckptr.metadata(path)
    tree = getattr(getattr(md, "item_metadata", md), "tree", None)
    if isinstance(tree, dict):
        return set(tree.keys())
    return None


# --- plan cache ---


# Bump whenever EdgePlan's fields/defaults change shape or meaning: stale
# cache pickles must REBUILD, not silently inherit new class defaults for
# fields they were never built with (e.g. scatter_block_e).
PLAN_FORMAT_VERSION = 13  # v13: EdgePlan has one static fewer (plans
# and shard manifests cached with it rebuild, never half-read);
# v12: halo_sorted_owner_ids, the owner-side
# index in the halo-sorted order (the fused GCN layer's backward aggregates
# over it; validate_plan refuses a sorted route without it);
# v11: the halo-sorted route's padded edges
# sort last, at a sentinel id past the last vertex block
# (plan.halo_sort_route), not into block 0 at id 0 — validate_plan holds
# the new rule, so plans cached under the old one must rebuild;
# v10: wire_format static (dgraph_tpu.wire) —
# the adopted halo-payload codec rides EdgePlan statics + the sharded
# manifest, so cached plans predating the codec layer must rebuild and
# stamp their build-time resolution;
# v9: halo_pair_rows traffic matrix static — cached plans predating it
# must rebuild so the matrix lands in the manifest;
# v8: sharded plan artifacts — per-rank
# shard_XXXX.pkl files under plan_<key>/ with a checksummed manifest.json
# (dgraph_tpu.plan_shards), streamed by plan.build_edge_plan_sharded,
# loaded/repaired shard-by-shard here; the monolithic plan_<key>.pkl is
# gone (a ~40+ GB all-or-nothing artifact at papers100M scale);
# v7: overlap (interior/boundary OverlapSpec for
# the compute–communication-overlap halo lowering);
# v6: e_pad aligned to lcm(pad_multiple,
# SCATTER_BLOCK_E) so pallas operands need no per-call re-pad copy;
# v5: gather_mv (sorted-row-gather vblock hint);
# v4: halo-side sorted route (halo_sort_perm / halo_sorted_ids /
# halo_sort_mc); v3: scatter_block_e default 512 -> 1024


def _hash_array(h, arr: np.ndarray) -> None:
    # memoryview feeds hashlib without a copy; .tobytes() would materialize
    # the whole array again (26 GB for a papers100M edge list)
    arr = np.ascontiguousarray(arr)
    h.update(str(arr.dtype).encode())
    h.update(str(arr.shape).encode())
    h.update(memoryview(arr).cast("B"))


def _graph_fingerprint(edge_index: np.ndarray, partition: np.ndarray, **kw) -> str:
    h = hashlib.sha256()
    h.update(f"plan-format-v{PLAN_FORMAT_VERSION};".encode())
    _hash_array(h, edge_index)
    _hash_array(h, partition)
    h.update(repr(sorted(kw.items())).encode())
    return h.hexdigest()[:24]


def cached_edge_plan(
    cache_dir: str,
    edge_index: np.ndarray,
    src_partition: np.ndarray,
    dst_partition: Optional[np.ndarray] = None,
    *,
    ranks: Optional[list] = None,
    load_layout: Optional[bool] = None,
    memory_budget_bytes: Optional[int] = None,
    verify: bool = True,
    key_extra: Optional[dict] = None,
    **build_kwargs: Any,
):
    """build_edge_plan with an on-disk **sharded** cache (format v8).

    ``key_extra`` folds extra scalar knobs into the cache key WITHOUT
    forwarding them to the plan builder — upstream decisions (the
    partition method and its ``sample_frac``/``edge_balance`` blend)
    that shaped the inputs but are not build kwargs.  The partition
    content is hashed regardless; keying the knobs too keeps two blends
    that collide on content from sharing one artifact name and makes
    the cache directory self-describing.

    The cached artifact is a directory ``plan_<key>/`` of per-rank shard
    pickles plus a checksummed manifest (:mod:`dgraph_tpu.plan_shards`),
    streamed by :func:`~dgraph_tpu.plan.build_edge_plan_sharded`.  Loads
    verify every shard's checksum; a corrupt / truncated / missing shard
    (or a shard deleted out from under a valid manifest) rebuilds **just
    the bad shards** — logged with which shard triggered it, mirroring
    :func:`restore_checkpoint`'s fall-back-past-corrupt-steps contract —
    and only an unreadable manifest degrades to a full rebuild.  A
    build killed mid-stream resumes from the manifest on the next call.

    ``ranks`` loads only those shards (each-host-loads-its-shard; the
    returned plan's leading axis is ``len(ranks)``, statics still
    describe the full world) and defaults ``load_layout`` to False — the
    layout sidecar is O(E), and a host loading two shards must not read
    (or SHA-verify) an artifact as big as the edge list.
    ``memory_budget_bytes`` bounds the streaming build's per-shard RSS
    (:class:`~dgraph_tpu.plan_shards.PlanBuildMemoryExceeded`).

    ``verify=False`` skips SHA-256 verification on warm hits — at
    papers100M scale hashing the full artifact adds real wall time to
    every load.  Torn/truncated shards still surface as unpickle
    failures and take the same single-shard repair path; only silent
    bit-flips in an intact-length pickle go undetected.

    A falsy ``cache_dir`` ("" / None) builds without caching — the CLIs'
    ``--plan_cache ""`` convention resolves here, not at every call site.

    Parity: `_save_comm_plans`/`_load_comm_plans`
    (``distributed_graph_dataset.py:399-422``).
    """
    from dgraph_tpu.plan import build_edge_plan

    if not cache_dir:
        if ranks is not None:
            raise ValueError(
                "cached_edge_plan(ranks=...) needs a cache_dir: per-rank "
                "loading is a property of the sharded on-disk artifact"
            )
        # layout sidecar knobs describe the on-disk artifact; without a
        # cache there is none (build_edge_plan would reject the kwarg)
        build_kwargs.pop("write_layout", None)
        return build_edge_plan(
            edge_index, src_partition, dst_partition, **build_kwargs
        )
    os.makedirs(cache_dir, exist_ok=True)
    # The RESOLVED Pallas tile sizes must be part of the key: they're
    # baked into the built plan, and build_edge_plan defaults them from
    # the env-overridable module constants — a warm cache would otherwise
    # silently ignore DGRAPH_TPU_SCATTER_BLOCK_E/N (ADVICE r2 #2).
    # Likewise the RESOLVED overlap intent: overlap=None defaults from the
    # env pin / adopted tuning record (plan.resolve_overlap_intent — the
    # same rule the builder applies), and a warm spec-less artifact must
    # not satisfy a build that now wants the interior/boundary split.
    from dgraph_tpu import plan as _plan
    from dgraph_tpu import plan_shards as ps
    from dgraph_tpu.plan import build_edge_plan_sharded, load_sharded_plan

    # the v8 cache always streams through the numpy per-rank core: the
    # native core fills the whole [W, E_pad] stack at once — the
    # allocation the sharded artifact exists to avoid. The cores produce
    # identical plans, so an explicit use_native only changes the build's
    # time/RSS profile; honor old callers by ignoring it with a warning
    # rather than crashing deep inside build_plan_shards.
    if build_kwargs.pop("use_native", None):
        _logger.warning(
            "plan cache %s: use_native is ignored for sharded (v8) cache "
            "builds — the streaming numpy core bounds peak memory by one "
            "shard", cache_dir,
        )

    overlap_resolved = build_kwargs.get("overlap")
    if overlap_resolved is None:
        overlap_resolved = _plan.resolve_overlap_intent()
    key = _graph_fingerprint(
        edge_index,
        src_partition if dst_partition is None else np.concatenate([src_partition, dst_partition]),
        scatter_block_e=_plan.SCATTER_BLOCK_E,
        scatter_block_n=_plan.SCATTER_BLOCK_N,
        overlap=bool(overlap_resolved),
        **{
            f"x_{k}": v for k, v in sorted((key_extra or {}).items())
            if v is not None and (np.isscalar(v) or isinstance(v, str))
        },
        # write_layout is an artifact-shape knob, not a plan knob: the
        # shards are bit-identical either way, and the loader self-heals
        # a missing sidecar — keying on it would store a duplicate
        # multi-GB artifact per spelling
        **{k: v for k, v in build_kwargs.items()
           if k not in ("overlap", "write_layout")
           and (np.isscalar(v) or isinstance(v, str))},
    )
    plan_dir = os.path.join(cache_dir, f"plan_{key}")

    ll = (
        load_layout if load_layout is not None
        # no sidecar to load for a rank-subset (per-host) load, nor when
        # the caller opted out of writing it in the first place
        else ranks is None and build_kwargs.get("write_layout", True)
    )

    def _build(rebuild_ranks=()):
        return build_edge_plan_sharded(
            edge_index, src_partition, dst_partition,
            out_dir=plan_dir, fingerprint=key, ranks=ranks, load_layout=ll,
            memory_budget_bytes=memory_budget_bytes,
            rebuild_ranks=rebuild_ranks,
            **{**build_kwargs, "overlap": bool(overlap_resolved)},
        )

    try:
        return load_sharded_plan(
            plan_dir, ranks=ranks, load_layout=ll, verify=verify
        )
    except ps.PlanShardError as e:
        # one bad shard is a shard-level repair, never a full rebuild:
        # the builder resumes past every durable, checksum-intact shard
        # and reassembles only what's broken (plus the named shard, for
        # the unlikely checksum-intact-but-unpicklable case)
        _logger.warning(
            "plan cache %s: shard %s unreadable (%s); rebuilding that "
            "shard", plan_dir, e.rank, e.reason,
        )
        return _build(rebuild_ranks=(e.rank,) if e.rank >= 0 else ())
    except ps.PlanManifestError as e:
        if os.path.exists(ps.manifest_path(plan_dir)):
            # incomplete (killed mid-build -> resume) or corrupt (full
            # rebuild; the writer discards unverifiable progress itself)
            _logger.warning(
                "plan cache %s: %s; %s", plan_dir, e.reason,
                "resuming the interrupted build"
                if "incomplete" in e.reason else "rebuilding",
            )
        return _build()
