"""Supervised training worker for the chaos end-to-end recovery test
(test_chaos.py). Run as:  python tests/_chaos_worker.py <ckpt_dir> <steps>

A deliberately tiny elastic training run — host-side numpy state, one
orbax checkpoint per step, a tight wedge watchdog — whose ONLY job is to
prove the restart contract end to end: the test's supervisor arms
``DGRAPH_CHAOS="step=wedge@K:attempt=0"``, attempt 0 wedges at global step
K and is hard-exited by the watchdog with code 17, the supervisor
restarts, and this process resumes from ``latest_step()``.  The step
update is exact in float64 and checkpoints round-trip bit-exactly, so the
final state must be BIT-IDENTICAL to an uninterrupted run — the
acceptance pin for the whole recovery path.

No jitted step on purpose: the recovery machinery under test is all host
code, and tier-1 cannot afford a fresh XLA compile per subprocess.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def step_fn(state):
    # exact float64 arithmetic: sequential application is bit-deterministic
    # regardless of where a restart split the run
    return {"w": state["w"] * 1.5 + 1.0}


def main() -> None:
    ckpt_dir, num_steps = sys.argv[1], int(sys.argv[2])
    from dgraph_tpu.train.checkpoint import latest_step, restore_checkpoint
    from dgraph_tpu.train.elastic import PreemptionGuard, run_elastic

    state = {"w": np.zeros(4, np.float64)}
    start = 0
    if latest_step(ckpt_dir) is not None:
        got = restore_checkpoint(ckpt_dir, {"state": state, "step": 0})
        state, start = got["state"], int(got["step"])
        print(f"WORKER_RESUME step={start}", flush=True)

    state, last, preempted = run_elastic(
        step_fn,
        state,
        start_step=start,
        num_steps=num_steps,
        ckpt_dir=ckpt_dir,
        checkpoint_every=1,
        step_deadline_s=0.5,  # tight: the injected wedge must die fast
        first_deadline_s=30.0,  # subprocess cold start is not a wedge
        guard=PreemptionGuard(),
    )
    print(
        f"WORKER_DONE step={last} preempted={preempted} "
        f"w0={state['w'][0]!r}",
        flush=True,
    )


if __name__ == "__main__":
    main()
