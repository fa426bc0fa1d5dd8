"""Online inference engine: checkpoint -> plan -> per-bucket jitted forward.

Serving a partitioned full-graph GNN differs from one more eval step in one
way that matters on TPU: requests arrive with arbitrary target-node counts,
and every novel shape reaching a jitted function is a multi-second XLA
compile in the middle of a millisecond latency budget. :class:`ServeEngine`
therefore holds ONE jitted, donated forward per :class:`~dgraph_tpu.serve.
bucketing.BucketLadder` size — each is the *same* shard_map forward the
train/eval steps run (``train.loop.model_apply``, so serve semantics cannot
drift from training) followed by a [bucket]-shaped gather of the requested
rows — and compiles all of them at startup (:meth:`warmup`). Steady state
replays cached executables only; :meth:`recompiles_since_warmup` is the
counter that proves it (pinned to 0 by ``--selftest`` and
``tests/test_serve.py``).

The request id space is the caller's ORIGINAL vertex numbering: the engine
carries the :class:`~dgraph_tpu.partition.Renumbering`-derived
``(rank, slot)`` map, so clients never see partition internals (the inverse
of what ``plan.unshard_vertex_data`` does for whole tensors, per-row).

The per-bucket forward is a registered audit program: the static-analysis
CLI traces it (:mod:`dgraph_tpu.analysis.trace`) AND lowers it
(:mod:`dgraph_tpu.analysis.hlo`, ISSUE 12) under every halo lowering —
collective schedule, operand bytes, and the donated ``(rank_idx,
slot_idx)`` scratch surviving lowering are all pinned against
``obs.footprint`` with zero compiles, so a serve-path schedule regression
is caught before any engine is ever warmed.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan
from dgraph_tpu.obs import spans
from dgraph_tpu.obs.metrics import Metrics, default_registry
from dgraph_tpu.serve.bucketing import BucketLadder, pad_ids
from dgraph_tpu.train.loop import model_apply


class ServeEngine:
    """Forward-only serving over one partitioned graph.

    Construction wires the static state (sharded params/features/plan and
    the original-id -> (rank, slot) map); :meth:`warmup` ahead-of-time
    compiles every bucket; :meth:`infer` is the hot path. Device arrays and
    jit caches live for the engine's lifetime — one engine per (graph,
    params) pair, shared by the micro-batcher's worker thread.
    """

    def __init__(
        self,
        model,
        mesh,
        plan,
        params,
        batch: dict,
        id_rank: np.ndarray,
        id_slot: np.ndarray,
        *,
        ladder: Optional[BucketLadder] = None,
        batch_args: Optional[Callable] = None,
        registry: Optional[Metrics] = None,
        tuning_record_id: Optional[str] = None,
        max_retries: int = 2,
        degrade_after: int = 3,
        retry_backoff_s: float = 0.05,
    ):
        self.model = model
        self.mesh = mesh
        self.ladder = ladder or BucketLadder.geometric()
        # self-healing knobs: a transient device error (lease blip, chaos
        # injection) is retried up to max_retries times per request; after
        # degrade_after CONSECUTIVE requests exhaust their retries the
        # engine degrades — sheds every request as QueueFull until
        # reset_degraded() — so a dead backend fails clients fast instead
        # of burning a retry storm per request
        self.max_retries = int(max_retries)
        self.degrade_after = int(degrade_after)
        self.retry_backoff_s = float(retry_backoff_s)
        self.degraded = False
        self._consecutive_failures = 0
        # the ENGINE lock: serializes the degraded-mode accounting (worker
        # thread) against reset_degraded / swap_params / append_vertices
        # (operator threads). The hot path never holds it across a device
        # dispatch — mutable state is flipped by single reference
        # assignments under the lock and read once per dispatch.
        self._lock = threading.RLock()
        # bumped by reset_degraded: a request DISPATCHED before a reset
        # must not count toward the fresh degrade window when it fails
        # after the reset (the resurrect-after-reset race this epoch
        # closes; pinned by tests/test_serve_control.py)
        self._failure_epoch = 0
        # provenance only (the ladder/plan themselves arrive already
        # built): stamped into serve_health so latency artifacts are
        # attributable to the tuning config that produced them
        self.tuning_record_id = tuning_record_id
        self.batch_args = batch_args
        self.registry = registry if registry is not None else default_registry
        self._plan = jax.tree.map(jnp.asarray, plan)
        self._batch = jax.tree.map(jnp.asarray, batch)
        # device-resident once: a checkpoint restore hands back numpy
        # leaves, and feeding those to jit re-transfers params every call
        self._params = jax.tree.map(jnp.asarray, params)
        self._id_rank = np.asarray(id_rank, np.int32)
        self._id_slot = np.asarray(id_slot, np.int32)
        if self._id_rank.shape != self._id_slot.shape:
            raise ValueError("id_rank / id_slot length mismatch")
        self.num_nodes = int(self._id_rank.shape[0])
        # host mirrors of the vertex-sharded batch leaves, for live delta
        # appends into reserved pad slots (append_vertices): mutate the
        # mirror, then flip self._batch to fresh device arrays in ONE
        # reference assignment
        self._host_x = np.asarray(batch["x"]) if "x" in batch else None
        self._host_vmask = (
            np.asarray(batch["vmask"]) if "vmask" in batch else None
        )
        # per-rank slot occupancy (real vertices per rank) — the free pad
        # slots above it are the append budget until the next re-plan
        world = next(iter(jax.tree.leaves(self._batch))).shape[0]
        self._slot_fill = np.bincount(
            self._id_rank, minlength=world
        ).astype(np.int64)
        # control-plane provenance: checkpoint lineage (swap_params
        # appends one record per rollover attempt) and the adopted graph
        # generation (dgraph_tpu.serve.deltas stamps it)
        self.ckpt_dir: Optional[str] = None
        self.lineage: list = []
        self.generation: Optional[int] = None
        self._batch_specs = jax.tree.map(lambda _: P(GRAPH_AXIS), batch)
        self._plan_specs = plan_in_specs(self._plan)
        # one independently-jitted forward per bucket: per-bucket executables
        # AND per-bucket compile accounting (each fn's jit cache should hold
        # its one entry after warmup and never grow)
        self._forwards = {b: self._build_forward() for b in self.ladder.sizes}
        self._full = jax.jit(self._make_forward_body())
        self._compiles_at_warmup: Optional[int] = None
        self.warmup_s: Optional[float] = None

    # --- construction helpers ---

    @classmethod
    def from_distributed_graph(
        cls, model, mesh, g, params, **kwargs
    ) -> "ServeEngine":
        """Wire an engine from a :class:`~dgraph_tpu.data.graph.
        DistributedGraph`: forward-only batch (features + optional edge
        weights / vertex mask) and the original-id -> (rank, slot) map from
        its renumbering."""
        ren = g.ren
        rank = np.asarray(ren.partition)[np.asarray(ren.perm)]
        slot = np.asarray(ren.perm) - np.asarray(ren.offsets)[rank]
        batch = {"x": g.features, "vmask": g.vertex_mask}
        if g.edge_weight is not None:
            batch["edge_weight"] = g.edge_weight
        kwargs.setdefault(
            "tuning_record_id", getattr(g, "tuning_record_id", None)
        )
        return cls(model, mesh, g.plan, params, batch, rank, slot, **kwargs)

    @classmethod
    def from_checkpoint(
        cls,
        model,
        mesh,
        g,
        ckpt_dir: str,
        *,
        step: Optional[int] = None,
        template: Optional[dict] = None,
        **kwargs,
    ) -> "ServeEngine":
        """Restore params via :func:`~dgraph_tpu.train.checkpoint.
        restore_checkpoint` (newest readable step; corrupt steps fall back
        older) and build the engine. The checkpoint may be a bare params
        tree or a train-state dict with a ``'params'`` entry."""
        from dgraph_tpu.train.checkpoint import restore_checkpoint

        state = restore_checkpoint(ckpt_dir, template, step=step)
        if state is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir!r}")
        params = state["params"] if isinstance(state, dict) and "params" in state else state
        eng = cls.from_distributed_graph(model, mesh, g, params, **kwargs)
        # remember the lineage root: swap_params(step=...) resolves bare
        # step numbers against this directory
        eng.ckpt_dir = ckpt_dir
        eng.lineage.append({
            "kind": "serve_rollover",
            "event": "restore",
            "ckpt_dir": ckpt_dir,
            "step": int(step) if step is not None else (
                int(state["step"])
                if isinstance(state, dict) and "step" in state else None
            ),
            "adopted": True,
        })
        return eng

    # --- forward construction ---

    def _make_forward_body(self):
        """Full-graph logits [W, n_pad, C] — the exact shard_map body
        ``make_eval_step`` runs up to (not including) its loss/metrics."""
        model, batch_args, mesh = self.model, self.batch_args, self.mesh
        batch_specs, plan_specs = self._batch_specs, self._plan_specs

        def shard_body(params, batch, plan):
            p = squeeze_plan(plan)
            b = jax.tree.map(lambda leaf: leaf[0], batch)
            return model_apply(model, params, b, p, batch_args)[None]

        def full(params, batch, plan):
            from dgraph_tpu.comm.collectives import shard_map_checks

            return jax.shard_map(
                shard_body,
                mesh=mesh,
                in_specs=(P(), batch_specs, plan_specs),
                out_specs=P(GRAPH_AXIS),
                **shard_map_checks(plan, GRAPH_AXIS),
            )(params, batch, plan)

        return full

    def _build_forward(self):
        full = self._make_forward_body()

        def fwd(params, batch, plan, rank_idx, slot_idx):
            # full forward + [bucket]-row gather in ONE program: the gather
            # shape is the only thing that varies across buckets, and the
            # index operands are per-request scratch — donated
            return full(params, batch, plan)[rank_idx, slot_idx]

        return jax.jit(fwd, donate_argnums=(3, 4))

    # --- hot path ---

    def infer(self, node_ids, _record: bool = True) -> np.ndarray:
        """Logits [n, num_classes] for ``node_ids`` (original numbering).

        Pads to the request's bucket, replays that bucket's executable, and
        slices the padding back off. Raises
        :class:`~dgraph_tpu.serve.errors.RequestTooLarge` past the ladder
        and ValueError on out-of-range ids.

        Self-healing: a transient device error is retried (same cached
        executable — a retry can never compile) up to ``max_retries``
        times with a short backoff; ``degrade_after`` consecutive
        retry-exhausted requests flip the engine into DEGRADED mode, where
        every request is shed fast with the structured
        :class:`~dgraph_tpu.serve.errors.QueueFull` until
        :meth:`reset_degraded`. The ``serve.infer`` chaos point
        (:mod:`dgraph_tpu.chaos`) fires inside the retried section, which
        is how both paths are tested deterministically.
        """
        from dgraph_tpu import chaos
        from dgraph_tpu.serve.errors import QueueFull, ServeError

        ids = np.asarray(node_ids)
        if ids.ndim != 1:
            raise ValueError(f"node_ids must be 1-D, got shape {ids.shape}")
        # ONE coherent control-plane snapshot under the engine lock: the
        # degraded flag, the failure epoch, the id maps, and the batch
        # reference all come from the same swap/append generation — and
        # the lock is never held across a device dispatch.  Piecemeal
        # unlocked reads here raced swap_params/append_vertices/
        # reset_degraded (host-lock-discipline; pinned in
        # tests/test_analysis_host.py).
        with self._lock:
            degraded = self.degraded
            consecutive = self._consecutive_failures
            # failure-epoch snapshot: if reset_degraded() lands while
            # this request is in flight, its eventual failure belongs to
            # the OLD epoch and must not count toward (or resurrect)
            # degraded mode
            epoch = self._failure_epoch
            id_rank, id_slot = self._id_rank, self._id_slot
            num_nodes = self.num_nodes
            params, batch, plan = self._params, self._batch, self._plan
        if ids.size and (ids.min() < 0 or ids.max() >= num_nodes):
            raise ValueError(
                f"node ids must be in [0, {num_nodes}), got "
                f"[{ids.min()}, {ids.max()}]"
            )
        # span parent = the batcher's ambient batch span when called from
        # the worker thread (contextvar), a root otherwise; one attr read
        # when tracing is off. The SAME span covers every retry, so the
        # trace id survives the retry/degraded paths.
        sp = spans.span("serve.infer", n=int(ids.shape[0]))
        if degraded:
            self.registry.counter("serve.shed_degraded")
            sp.end(error="backpressure: degraded shed")
            raise QueueFull(
                "engine degraded after repeated device failures; shedding "
                "load (reset_degraded() to re-admit)",
                degraded=True,
                consecutive_failures=consecutive,
            )
        t0 = time.perf_counter()
        try:
            bucket = self.ladder.bucket_for(ids.shape[0])
        except ServeError as e:  # RequestTooLarge: structured, never queued
            sp.end(error=e.code)
            raise
        padded, n = pad_ids(ids, bucket)
        # pad stage: bucket pick + id padding + the FIRST index-operand
        # build (rebuilds inside the retry loop are failure-path cost and
        # stay inside the infer stage)
        rank_idx = jnp.asarray(id_rank[padded])
        slot_idx = jnp.asarray(id_slot[padded])
        pad_ms = (time.perf_counter() - t0) * 1e3
        t_infer = time.perf_counter()
        last_err = None
        for attempt in range(self.max_retries + 1):
            if attempt:
                # index operands are rebuilt per retry: they are DONATED to
                # the executable, and a dispatch that failed midway may
                # already have invalidated them
                rank_idx = jnp.asarray(id_rank[padded])
                slot_idx = jnp.asarray(id_slot[padded])
            try:
                chaos.fire("serve.infer")
                with jax.set_mesh(self.mesh):
                    out = self._forwards[bucket](
                        params, batch, plan, rank_idx, slot_idx,
                    )
                out = np.asarray(jax.block_until_ready(out))[:n]
                break
            except ServeError:  # structured rejections are never transient
                sp.end(error="serve_error", attempts=attempt + 1)
                raise
            except Exception as e:  # noqa: BLE001 — transient device error
                last_err = e
                if attempt < self.max_retries:
                    self.registry.counter("serve.infer_retries")
                    time.sleep(self.retry_backoff_s)
        else:
            degraded_now = False
            with self._lock:
                if epoch == self._failure_epoch:
                    self._consecutive_failures += 1
                    consecutive = self._consecutive_failures
                    if (
                        self._consecutive_failures >= self.degrade_after
                        and not self.degraded
                    ):
                        self.degraded = True
                        degraded_now = True
            self.registry.counter("serve.infer_failures")
            if degraded_now:
                self.registry.gauge("serve.degraded", 1.0)
                print(
                    f"[serve] engine DEGRADED after "
                    f"{consecutive} consecutive infer "
                    f"failures (last: {type(last_err).__name__}: {last_err})",
                    flush=True,
                )
            sp.end(
                error=f"{type(last_err).__name__}: {last_err}",
                attempts=self.max_retries + 1,
            )
            raise last_err
        with self._lock:
            if epoch == self._failure_epoch:
                self._consecutive_failures = 0
        infer_ms = (time.perf_counter() - t_infer) * 1e3
        # per-stage timings for the batcher's request spans + health
        # quantiles (worker-thread single-writer; read right after infer)
        self.last_stage_ms = {"pad": pad_ms, "infer": infer_ms}
        sp.end(bucket=int(bucket), pad_ms=round(pad_ms, 3),
               infer_ms=round(infer_ms, 3))
        if _record:
            dt_ms = (time.perf_counter() - t0) * 1e3
            reg = self.registry
            reg.counter("serve.infer_calls")
            reg.histogram("serve.infer_ms", dt_ms)
            reg.histogram("serve.stage.pad_ms", pad_ms)
            reg.histogram("serve.stage.infer_ms", infer_ms)
            reg.histogram("serve.batch_occupancy", n / bucket)
            reg.gauge(
                "serve.recompiles_since_warmup",
                float(self.recompiles_since_warmup()),
            )
        return out

    def reset_degraded(self) -> None:
        """Re-admit traffic after a degraded period (the operator's — or a
        health-checker's — explicit decision: auto-undegrading would flap
        against a still-dead backend).

        Atomic against the batcher worker: state flips under the engine
        lock, and bumping the failure epoch makes any infer that was
        DISPATCHED before this reset report its failure into the old epoch
        — a concurrent failure can no longer resurrect degraded mode (or
        spend the fresh degrade window) the instant after an operator
        re-admitted traffic."""
        with self._lock:
            self._failure_epoch += 1
            self.degraded = False
            self._consecutive_failures = 0
        self.registry.gauge("serve.degraded", 0.0)

    # --- control plane: hot-swap rollover + live vertex appends ---

    def swap_params(self, source=None, *, step: Optional[int] = None,
                    params=None, parity_ids=None) -> dict:
        """Hot-swap to a newly restored checkpoint under the SAME warmed
        executables — zero recompiles, atomic per batch, automatic
        rollback on a bad checkpoint.

        ``source`` is a checkpoint directory (``step`` picks a step;
        default newest readable), defaulting to the engine's own
        :attr:`ckpt_dir`; or pass an explicit ``params`` tree. The staged
        params are validated BEFORE the live pointer moves — structure/
        shape/dtype against the warmed executables, host-side non-finite
        guard, and the served==eval parity oracle run *with the staged
        tree as an argument* through the already-compiled forwards — so a
        rejected swap (:class:`~dgraph_tpu.serve.errors.SwapRejected`)
        leaves the prior params serving without a single dropped request.
        See :func:`dgraph_tpu.serve.rollover.swap_params` for the full
        state machine; every attempt lands one record in :attr:`lineage`.
        """
        from dgraph_tpu.serve.rollover import swap_params as _swap

        return _swap(self, source, step=step, params=params,
                     parity_ids=parity_ids)

    def free_pad_slots(self) -> int:
        """Reserved pad capacity left for live vertex appends before the
        next re-plan must rebuild (``serve.deltas.replan``); 0 when the
        engine has no appendable batch."""
        # _host_x/_slot_fill are append_vertices' locked state; the lock
        # is reentrant, so the in-lock error-message call below still
        # works (host-lock-discipline)
        with self._lock:
            if self._host_x is None:
                return 0
            return int((self._host_x.shape[1] - self._slot_fill).sum())

    def append_vertices(self, features) -> np.ndarray:
        """Install new vertices into reserved pad slots, live — returns
        their (original-numbering) ids, ``num_nodes .. num_nodes+k``.

        The appended vertices are queryable immediately: their features
        enter the sharded batch, their vertex mask flips to 1.0, and the
        id map grows — all flipped in ONE reference assignment under the
        engine lock, so a concurrent batch sees entirely the old or
        entirely the new graph. Shapes never change (the rows were already
        padded), so the warmed executables replay untouched. Edges
        incident to appended vertices are NOT live until a background
        re-plan is adopted (:mod:`dgraph_tpu.serve.deltas`): until then an
        appended vertex aggregates nothing — exactly an isolated vertex.
        Raises ValueError when the pad budget is exhausted (the signal to
        re-plan)."""
        with self._lock:
            # validation INSIDE the lock too: the shape/dtype checks read
            # _host_x, which a concurrent append is allowed to replace
            # (host-lock-discipline); RLock keeps the nested
            # free_pad_slots() call below legal
            if self._host_x is None:
                raise ValueError(
                    "engine batch has no 'x' leaf to append into"
                )
            feats = np.asarray(features, self._host_x.dtype)
            if feats.ndim != 2 or feats.shape[1] != self._host_x.shape[2]:
                raise ValueError(
                    f"features must be [k, {self._host_x.shape[2]}], got "
                    f"{feats.shape}"
                )
            k = int(feats.shape[0])
            n_pad = self._host_x.shape[1]
            if k > int((n_pad - self._slot_fill).sum()):
                raise ValueError(
                    f"{k} new vertices exceed the {self.free_pad_slots()} "
                    "free pad slots; adopt a re-planned generation first "
                    "(serve.deltas.replan)"
                )
            from dgraph_tpu.serve.deltas import assign_new_vertices

            # deterministic waterfill SHARED with serve.deltas.replan:
            # the background rebuild replays the same placement, so
            # adoption never moves a vertex already served from a pad slot
            fill = self._slot_fill.copy()
            new_rank = assign_new_vertices(fill, k)
            new_slot = np.empty(k, np.int32)
            running = self._slot_fill.copy()
            for i, r in enumerate(new_rank):
                new_slot[i] = running[r]
                running[r] += 1
            # place_like: the SAME placement contract the rollover staging
            # uses (mirror multi-device shardings, keep single-device
            # leaves uncommitted) — shared so the two paths cannot drift
            from dgraph_tpu.serve.rollover import place_like

            x2 = self._host_x.copy()
            x2[new_rank, new_slot] = feats
            batch2 = dict(self._batch)
            batch2["x"] = place_like(x2, self._batch["x"])
            if self._host_vmask is not None:
                vm2 = self._host_vmask.copy()
                vm2[new_rank, new_slot] = 1.0
                batch2["vmask"] = place_like(vm2, self._batch["vmask"])
                self._host_vmask = vm2
            ids = np.arange(self.num_nodes, self.num_nodes + k, dtype=np.int64)
            # the flip: one reference assignment each — infer reads
            # self._batch / the id maps once per dispatch
            self._host_x = x2
            self._batch = batch2
            self._id_rank = np.concatenate([self._id_rank, new_rank])
            self._id_slot = np.concatenate([self._id_slot, new_slot])
            self._slot_fill = fill
            self.num_nodes += k
        self.registry.counter("serve.vertices_appended", float(k))
        return ids

    def rank_slot(self, node_ids) -> tuple:
        """(rank, slot) arrays for original vertex ids — the row addresses
        of those vertices in any ``[W, n_pad, ...]`` sharded tensor (e.g.
        :meth:`full_logits`)."""
        # one locked snapshot: append_vertices grows both maps together,
        # and an unlocked pair of reads could see one grown and one not
        # (host-lock-discipline)
        with self._lock:
            id_rank, id_slot = self._id_rank, self._id_slot
        ids = np.asarray(node_ids)
        return id_rank[ids], id_slot[ids]

    def full_logits(self) -> np.ndarray:
        """[W, n_pad, C] logits for the whole graph — the parity oracle the
        selftest checks the bucketed path against bit-for-bit, and the bulk
        (batch-scoring) escape hatch. Row (r, s) serves original vertex id
        with ``id_rank==r, id_slot==s``."""
        # same snapshot discipline as infer: one locked read of the
        # swap/append-mutable references, lock released before dispatch
        with self._lock:
            params, batch, plan = self._params, self._batch, self._plan
        with jax.set_mesh(self.mesh):
            out = self._full(params, batch, plan)
        return np.asarray(jax.block_until_ready(out))

    # --- warmup / recompile accounting ---

    def warmup(self) -> dict:
        """Ahead-of-time compile every bucket so the hot path never does.

        Each bucket runs twice: the first call's outputs carry mesh
        shardings its fresh host inputs did not, which legitimately earns
        any jitted step one extra compile (same effect pinned in
        tests/test_obs.py) — warming twice reaches the steady-state cache
        before the baseline is recorded. Returns a summary record.
        """
        t0 = time.perf_counter()
        for b in self.ladder.sizes:
            ids = np.zeros(b, np.int64)
            for _ in range(2):
                self.infer(ids, _record=False)
        # the full-logits oracle counts toward _total_compiles too — warm it
        # so a post-warmup parity check can't read as a hot-path recompile
        for _ in range(2):
            self.full_logits()
        self.warmup_s = round(time.perf_counter() - t0, 3)
        self._compiles_at_warmup = self._total_compiles()
        self.registry.gauge("serve.warmup_s", self.warmup_s)
        self.registry.gauge("serve.recompiles_since_warmup", 0.0)
        return {
            "kind": "serve_warmup",
            "buckets": [int(b) for b in self.ladder.sizes],
            "warmup_s": self.warmup_s,
            "compiles_at_warmup": self._compiles_at_warmup,
        }

    def _total_compiles(self) -> int:
        """Sum of jit-cache entries across the bucket forwards (plus the
        full-logits oracle). ``_cache_size`` is jax-private but present on
        0.4-0.6; if a future jax drops it the counter degrades to 0 rather
        than breaking serving."""
        total = 0
        for f in (*self._forwards.values(), self._full):
            cache_size = getattr(f, "_cache_size", None)
            if cache_size is not None:
                total += int(cache_size())
        return total

    def recompiles_since_warmup(self) -> int:
        """XLA compiles after :meth:`warmup` returned — the serving SLO
        invariant is that this stays 0 in steady state. Before warmup,
        every compile counts (a cold hot-path compile is exactly what the
        counter exists to expose)."""
        base = self._compiles_at_warmup or 0
        return max(0, self._total_compiles() - base)
