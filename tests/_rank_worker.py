"""Multi-rank elastic worker for the rank-kill acceptance test
(test_shrink.py). Run under ``supervise_group`` as:

    DGRAPH_RANK=<r> python tests/_rank_worker.py <run_dir> <steps> <sleep_s>

One member of an elastic world (``train.shrink`` run-dir layout): reads
the ``world.json`` adoption pointer, loads ONLY its own plan shard (the
PR 8 rank-subset path), joins the membership directory via a retrying
rendezvous, and drives a deliberately tiny host-side numpy "training"
loop through ``run_elastic(membership=...)`` — heartbeating every step,
checkpointing every step, and exiting ``RANK_LOST_EXIT_CODE`` (19) after
a durable checkpoint when a peer's lease expires — or
``RANK_JOIN_EXIT_CODE`` (23) when a newcomer announces a join, so the
supervisor can grow the world (test_grow.py).  The per-vertex update
is keyed by ORIGINAL vertex id (``graph_g<g>.npz``'s ``orig_ids``), so a
wrong row anywhere in the shrink/reshard pipeline diverges from the
global oracle the test computes.

No jitted step on purpose: the recovery machinery under test is all host
code, and tier-1 cannot afford a fresh XLA compile per subprocess.  The
PreemptionGuard is INERT (``signals=()``) so the chaos ``sigterm``
rank-kill is an abrupt death — exactly the fault membership must detect
— not a graceful preemption.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def make_step_fn(orig_ids: np.ndarray, count: int, n_pad: int,
                 sleep_s: float):
    """One deterministic float64 momentum step per call.  ``g`` is keyed
    by original vertex id so state rows are distinguishable through any
    renumbering; pad rows stay exactly zero."""
    g = np.zeros(n_pad, np.float64)
    g[:count] = orig_ids.astype(np.float64) + 1.0

    def step_fn(state):
        if sleep_s:
            time.sleep(sleep_s)
        m = 0.5 * state["opt_state"]["m"] + g
        w = state["params"]["w"] + 0.25 * m
        return {"params": {"w": w}, "opt_state": {"m": m}}

    return step_fn


def main() -> None:
    run_dir, num_steps, sleep_s = (
        sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    )
    from dgraph_tpu.comm.membership import (
        RANK_JOIN_EXIT_CODE,
        RANK_LOST_EXIT_CODE,
        Membership,
        RankJoinError,
        RankLostError,
        rank_from_env,
    )

    rank = rank_from_env()
    from dgraph_tpu.plan import load_sharded_plan
    from dgraph_tpu.train import shrink
    from dgraph_tpu.train.checkpoint import latest_step, restore_checkpoint
    from dgraph_tpu.train.elastic import PreemptionGuard, run_elastic

    world = shrink.read_world(run_dir)
    gen, W = int(world["generation"]), int(world["world_size"])
    assert rank < W, f"rank {rank} outside adopted world {W}"

    # each-host-loads-its-shard: only THIS rank's plan shard is read
    plan, _ = load_sharded_plan(
        shrink.plan_dir(run_dir, gen), ranks=[rank], load_layout=False
    )
    n_pad = int(plan.n_dst_pad)
    count = int(plan.num_local_dst[0])
    graph = np.load(shrink.graph_path(run_dir, gen))
    offs = np.concatenate([[0], np.cumsum(graph["counts"])])
    orig_ids = np.asarray(graph["orig_ids"])[offs[rank]: offs[rank + 1]]
    assert orig_ids.shape[0] == count

    ckpt = shrink.rank_ckpt_dir(run_dir, gen, rank)
    state = {
        "params": {"w": np.zeros(n_pad, np.float64)},
        "opt_state": {"m": np.zeros(n_pad, np.float64)},
    }
    start = int(world.get("resume_step", 0))
    if latest_step(ckpt) is not None:
        got = restore_checkpoint(ckpt, {"state": state, "step": 0})
        state, start = got["state"], int(got["step"])
        print(f"WORKER_RESUME rank={rank} gen={gen} step={start}", flush=True)

    attempt = int(os.environ.get("DGRAPH_CHAOS_ATTEMPT", "0"))
    mem = Membership(
        shrink.membership_dir(run_dir, gen, attempt),
        rank=rank,
        world_size=W,
        lease_s=float(world["lease_s"]),
        generation=gen,
    )
    roster = mem.rendezvous(deadline_s=60.0)
    print(f"WORKER_JOINED rank={rank} roster={list(roster)}", flush=True)
    # lease maintenance must track the PROCESS, not the step cadence: a
    # loaded machine can stretch one step (orbax write) past the lease,
    # and a live-but-slow rank must never read as dead to its peers
    mem.start_heartbeats()

    try:
        state, last, preempted = run_elastic(
            make_step_fn(orig_ids, count, n_pad, sleep_s),
            state,
            start_step=start,
            num_steps=num_steps,
            ckpt_dir=ckpt,
            checkpoint_every=1,
            guard=PreemptionGuard(signals=()),  # abrupt SIGTERM death
            membership=mem,
        )
    except RankLostError as e:
        print(f"WORKER_RANK_LOST rank={rank} " + json.dumps(e.record()),
              flush=True)
        sys.exit(RANK_LOST_EXIT_CODE)
    except RankJoinError as e:
        # a joiner announced: checkpoint already durable (run_elastic
        # saved before raising) — exit 23 so the supervisor grows W+k
        print(f"WORKER_RANK_JOIN rank={rank} " + json.dumps(e.record()),
              flush=True)
        sys.exit(RANK_JOIN_EXIT_CODE)
    mem.stop_heartbeats()
    mem.leave()
    print(
        f"WORKER_DONE rank={rank} gen={gen} step={last} "
        f"preempted={preempted}",
        flush=True,
    )


if __name__ == "__main__":
    main()
