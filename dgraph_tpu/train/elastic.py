"""Preemption-aware training: failure detection + graceful save/resume.

Beyond-reference subsystem (SURVEY.md §5 lists failure detection/elastic as
ABSENT in the reference; checkpoint/resume was its whole recovery story).
TPU pods are preemptible — maintenance events and pool re-leases land as
SIGTERM with a grace window — so the trainer needs three things the
reference never had:

1. **Preemption detection**: a signal handler that flips a flag the train
   loop polls between steps (``PreemptionGuard``). Polling between steps
   (never inside jit) keeps the XLA program free of host callbacks.
2. **Graceful exit**: on the first poll after the signal, save a full
   train-state checkpoint (orbax, ``train/checkpoint.py``) and stop
   cleanly, so the next launch resumes from the exact step.
3. **Step watchdog**: a wedged device (a lost device hangs ANY dispatch
   indefinitely) never returns control to Python, so
   detection must be preemptive — a monitor thread that hard-exits the
   process with a distinct code if a step exceeds a deadline, letting the
   launcher restart and resume rather than hang forever.

Single-controller AND multi-controller safe: the handler runs per process;
checkpoint writes go through the lead process only (callers pass
``is_lead``), matching the lead-first convention in ``data/ogbn.py``.
"""

from __future__ import annotations

import contextlib
import os
import signal
import threading
import time
from typing import Callable, Optional

import dgraph_tpu.obs.spans as spans  # stdlib-only module (lint-enforced)

WEDGED_EXIT_CODE = 17  # distinct exit for "device wedged, restart+resume me"


class PreemptionGuard:
    """Flag-based preemption detection for the between-steps poll.

    Usage::

        guard = PreemptionGuard()              # installs SIGTERM/SIGINT
        for step in range(start, num_steps):
            state = train_step(state, batch)
            if guard.should_stop():            # poll AFTER each step
                save_checkpoint(ckpt_dir, state, step)
                break

    ``signals=()`` makes it inert (tests drive :meth:`request_stop`).
    """

    def __init__(self, signals=(signal.SIGTERM, signal.SIGINT)):
        self._stop = threading.Event()
        self._prev = {}
        for s in signals:
            self._prev[s] = signal.signal(s, self._handler)

    def _handler(self, signum, frame):
        self._stop.set()
        # chain to any previous CUSTOM handler so outer supervisors still
        # see it — but NOT Python's default SIGINT handler, which raises
        # KeyboardInterrupt mid-step and would bypass exactly the graceful
        # poll-and-checkpoint this class exists for
        prev = self._prev.get(signum)
        if (
            callable(prev)
            and prev not in (signal.SIG_IGN, signal.SIG_DFL)
            and prev is not signal.default_int_handler
        ):
            prev(signum, frame)

    def request_stop(self) -> None:
        """Programmatic preemption (tests; cooperative shutdown)."""
        self._stop.set()

    def should_stop(self) -> bool:
        return self._stop.is_set()

    def uninstall(self) -> None:
        for s, prev in self._prev.items():
            signal.signal(s, prev)


class StepWatchdog:
    """Hard deadline per training step for wedge detection.

    A wedged device hangs inside the dispatch, so no in-loop check can
    fire; this monitor thread exits the whole process (``os._exit``) with
    :data:`WEDGED_EXIT_CODE` if :meth:`beat` isn't called within
    ``deadline_s``. The launcher treats that exit as "restart and resume
    from the last checkpoint" — the elastic story for single-controller
    runs. Call :meth:`stop` before teardown.

    ``on_expire`` (tests / custom supervisors) replaces the hard exit.

    The FIRST step includes XLA trace+compile and can legitimately take many
    times the steady-state step time; until the first :meth:`beat`, the
    deadline is ``first_deadline_s`` (default 10x) so a slow compile does
    not trigger a spurious wedged-exit restart loop.
    """

    def __init__(self, deadline_s: float, on_expire: Optional[Callable] = None,
                 first_deadline_s: Optional[float] = None):
        self.deadline_s = deadline_s
        self.first_deadline_s = (
            first_deadline_s if first_deadline_s is not None else 10 * deadline_s
        )
        self._last = time.monotonic()
        self._beaten = False
        self._suspended = False
        self._on_expire = on_expire
        self._done = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def beat(self) -> None:
        """Mark the step boundary (call once per completed step)."""
        # _last FIRST: the monitor must never pair the steady deadline with
        # a first-step-age _last (race window if _beaten flipped first)
        self._last = time.monotonic()
        self._beaten = True

    @contextlib.contextmanager
    def suspended(self):
        """Context manager: pause expiry (e.g. around checkpoint saves —
        a long orbax write is not a wedged device) and restart the clock
        on exit."""
        self._suspended = True
        try:
            yield
        finally:
            self._last = time.monotonic()
            self._suspended = False

    def _run(self) -> None:
        while not self._done.wait(min(self.deadline_s / 4, 5.0)):
            if self._suspended:
                continue
            limit = self.deadline_s if self._beaten else self.first_deadline_s
            if time.monotonic() - self._last > limit:
                if self._on_expire is not None:
                    self._on_expire()
                    self._last = time.monotonic()  # custom handler: keep watching
                    continue
                print(
                    f"[elastic] step exceeded {self.deadline_s}s deadline — "
                    f"device wedged? exiting {WEDGED_EXIT_CODE} for restart+resume",
                    flush=True,
                )
                os._exit(WEDGED_EXIT_CODE)

    def stop(self) -> None:
        self._done.set()
        self._thread.join(timeout=5.0)


def run_elastic(
    train_step: Callable,  # state -> state (one step, device-synced inside)
    state,
    *,
    start_step: int,
    num_steps: int,
    ckpt_dir: Optional[str],
    checkpoint_every: int = 0,  # 0 = only on preemption/finish
    step_deadline_s: float = 0.0,  # 0 = no watchdog
    first_deadline_s: Optional[float] = None,  # None = watchdog default (10x)
    is_lead: bool = True,
    guard: Optional[PreemptionGuard] = None,
    rollback_on_abort: bool = True,
    membership=None,
):
    """Drive ``train_step`` with preemption polling, periodic checkpoints,
    and an optional per-step wedge watchdog. Returns (state, last_step,
    preempted: bool).

    The reference's trainers loop bare (``experiments/OGB/main.py:129-221``);
    this wrapper is what makes long runs restartable on preemptible TPU
    capacity. Resume by restoring the latest checkpoint and passing its
    step as ``start_step`` (see ``train/checkpoint.py::latest_step``) — or
    run the whole thing under ``python -m dgraph_tpu.train.supervise``,
    which restarts on :data:`WEDGED_EXIT_CODE` and crashes for you.

    ``first_deadline_s`` widens the FIRST step's watchdog allowance (trace +
    XLA compile legitimately dwarf the steady-state step time); None keeps
    :class:`StepWatchdog`'s 10x default. Callers whose first step compiles
    a large program should pass their compile budget here rather than
    inflating ``step_deadline_s`` for the whole run.

    Each step consults the ``step`` chaos point (:mod:`dgraph_tpu.chaos`)
    with the global step as the index, so injected wedges/preemptions/
    crashes land deterministically even across restart+resume.

    If ``train_step`` raises :class:`~dgraph_tpu.train.guard.
    NonFiniteAbort` (the non-finite step guard's consecutive-skip abort)
    and ``rollback_on_abort`` holds, the newest readable checkpoint is
    restored and ``(restored_state, its_step, True)`` returned — the
    caller decides whether to re-enter with a lower LR, different data
    order, or give up. With no checkpoint to roll back to the abort
    propagates.

    ``is_lead`` gates saves for SINGLE-controller runs (replicated or
    single-process state). In a multi-controller launch with state sharded
    across processes, pass ``is_lead=True`` on EVERY process: orbax must be
    entered by all hosts to serialize non-fully-addressable arrays (it
    coordinates lead-writes internally); gating to one process would
    deadlock or fail the save.

    ``membership`` (a :class:`~dgraph_tpu.comm.membership.Membership`)
    makes the loop a live member of an elastic world: background
    heartbeats are started (``start_heartbeats``, idempotent — the lease
    tracks the PROCESS, so a slow step or watchdog-suspended checkpoint
    write never reads as silence), loss polls run at step boundaries
    rate-limited to the heartbeat interval, and a detected peer loss
    saves a checkpoint
    (the survivor's contribution to the next consistent cut) and raises
    :class:`~dgraph_tpu.comm.membership.RankLostError` — the caller
    should exit :data:`~dgraph_tpu.comm.membership.RANK_LOST_EXIT_CODE`
    so ``supervise_group`` runs the shrink-to-fit recovery
    (:mod:`dgraph_tpu.train.shrink`).  Keep ``step_deadline_s`` below the
    membership ``lease_s``: a *wedged* rank must exit 17 (collective
    restart, same world) before its peers declare it lost.
    """
    from dgraph_tpu import chaos
    from dgraph_tpu.comm.membership import RankJoinError, RankLostError
    from dgraph_tpu.train.checkpoint import save_checkpoint
    from dgraph_tpu.train.guard import NonFiniteAbort

    if start_step >= num_steps:  # nothing to do (e.g. resuming a finished run)
        return state, start_step, False
    own_guard = guard is None
    guard = guard or PreemptionGuard()
    dog = (
        StepWatchdog(step_deadline_s, first_deadline_s=first_deadline_s)
        if step_deadline_s > 0 else None
    )
    preempted = False
    step = start_step
    last_saved = None
    # membership liveness is PROCESS-scoped, not step-scoped: the
    # background heartbeat thread (idempotent start) keeps the lease
    # alive through long steps and watchdog-suspended checkpoint writes —
    # a slow orbax save must never read as silence to peers. Loss POLLS
    # stay at step boundaries, rate-limited to the heartbeat interval
    # (a lease write + O(W) poll per step would hammer the shared
    # membership dir at short step times, and detection latency is
    # bounded by the lease anyway; 0.0 = check the first boundary).
    if membership is not None:
        membership.start_heartbeats()
    mem_next = 0.0

    def _save(st, n):
        # a long orbax write is not a wedged device — pause the watchdog
        nonlocal last_saved
        with (dog.suspended() if dog is not None else contextlib.nullcontext()):
            with spans.span("train.checkpoint", parent=run_span, step=n):
                save_checkpoint(ckpt_dir, {"state": st, "step": n}, n)
        last_saved = n

    # one span per attempt-run, one per step (both no-ops when tracing is
    # off — a single attribute read each). Under train.supervise the
    # inherited trace env roots these under the supervisor's attempt span,
    # which is what makes restart chains one joinable timeline.
    run_span = spans.span(
        "train.run", start_step=start_step, num_steps=num_steps,
        attempt=os.environ.get("DGRAPH_CHAOS_ATTEMPT"),
    )
    try:
        for step in range(start_step, num_steps):
            # fault injection lands HERE, at the host step boundary: a
            # 'wedge' holds the loop exactly like a hung dispatch (only the
            # watchdog can catch it), 'sigterm' exercises the preemption
            # poll below, 'raise' the supervisor's crash-restart path
            step_span = spans.span("train.step", parent=run_span, step=step)
            try:
                chaos.fire("step", index=step)
                state = train_step(state)
                step_span.end()
            except NonFiniteAbort as e:
                step_span.end(error="nonfinite_abort")
                restored = (
                    _rollback(ckpt_dir, state, dog)
                    if rollback_on_abort and ckpt_dir else None
                )
                if restored is None:
                    raise
                import json as _json

                print(
                    _json.dumps(
                        {**e.record(), "rolled_back_to": restored[1]}
                    ),
                    flush=True,
                )
                return restored[0], restored[1], True
            except BaseException as e:
                # a crashing step must still land its span record — this
                # is exactly the step the flight recorder needs to show
                # (the supervisor only sees "attempt crashed")
                step_span.end(error=f"{type(e).__name__}: {e}")
                raise
            if dog is not None:
                dog.beat()
            if membership is not None and time.monotonic() >= mem_next:
                mem_next = (
                    time.monotonic() + membership.heartbeat_interval_s
                )
                evs = membership.poll()
                lost_events = [e for e in evs if e.kind == "rank_lost"]
                join_events = [e for e in evs if e.kind == "join_request"]
                if lost_events:
                    # a survivor's job: land a durable checkpoint (its
                    # block of the next consistent cut) and exit for the
                    # group supervisor's shrink path
                    if ckpt_dir and is_lead:
                        _save(state, step + 1)
                    err = RankLostError(
                        tuple(e.rank for e in lost_events),
                        tuple(lost_events),
                    )
                    run_span.annotate(rank_lost=[e.rank for e in lost_events])
                    raise err
                if join_events:
                    # the arrival mirror: land a durable checkpoint (this
                    # rank's block of the cut the grow transition will
                    # reshard from) and exit for the group supervisor's
                    # grow path. Loss wins when both land in one poll —
                    # the world must shrink to a consistent cut before it
                    # can entertain newcomers.
                    if ckpt_dir and is_lead:
                        _save(state, step + 1)
                    err = RankJoinError(
                        tuple(e.token for e in join_events),
                        tuple(join_events),
                    )
                    run_span.annotate(
                        rank_join=[e.token for e in join_events]
                    )
                    raise err
            done_now = guard.should_stop()
            periodic = (
                checkpoint_every > 0 and (step + 1) % checkpoint_every == 0
            )
            if ckpt_dir and is_lead and (done_now or periodic):
                _save(state, step + 1)
            if done_now:
                preempted = True
                break
        else:
            if ckpt_dir and is_lead and last_saved != num_steps:
                _save(state, num_steps)
    finally:
        run_span.end(last_step=step, preempted=preempted)
        if dog is not None:
            dog.stop()
        if own_guard:
            guard.uninstall()
    return state, step + 1, preempted


def _rollback(ckpt_dir: str, state, dog: Optional[StepWatchdog]):
    """Restore the newest readable checkpoint for the non-finite abort
    path; None when the directory holds none. ``state`` is only the
    restore TEMPLATE (structure/shapes — its buffers may already be
    donated), never a value source."""
    from dgraph_tpu.train.checkpoint import latest_step, restore_checkpoint

    if latest_step(ckpt_dir) is None:
        return None
    with (dog.suspended() if dog is not None else contextlib.nullcontext()):
        got = restore_checkpoint(ckpt_dir, {"state": state, "step": 0})
    return got["state"], int(got["step"])
