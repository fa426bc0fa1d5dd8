"""Serve graph-delta worker for the chaos mid-replan atomicity test
(test_serve_control.py). Run as:

    python tests/_replan_worker.py <run_dir> init     # gen 0 + one delta
    python tests/_replan_worker.py <run_dir> replan   # fold deltas -> g+1

The test arms ``DGRAPH_CHAOS="serve.replan=sigterm@1"`` (kill at the
commit boundary: every generation-1 artifact durable, pointer not yet
flipped) or ``"plan.write=sigterm@2"`` (kill mid shard stream) around the
``replan`` phase and asserts the adoption contract: the pointer names the
OLD generation after the kill, and a chaos-free rerun resumes the build
and adopts generation 1 — old or new, never torn.

Host-side only (plan builds are numpy): no devices, no jitted step — the
adoption machinery under test is all host code, and tier-1 cannot afford
an XLA compile per subprocess.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def main() -> None:
    run_dir, phase = sys.argv[1], sys.argv[2]
    from dgraph_tpu.serve import deltas

    if phase == "init":
        rng = np.random.default_rng(7)
        num_nodes, feat = 48, 4
        edges = np.stack([
            np.arange(num_nodes), (np.arange(num_nodes) + 1) % num_nodes
        ])
        feats = rng.normal(size=(num_nodes, feat)).astype(np.float32)
        world = deltas.init_world(
            run_dir, edges, feats, world_size=4,
            partition_method="block", pad_multiple=4,
        )
        rec = deltas.append_delta(
            run_dir,
            rng.normal(size=(3, feat)).astype(np.float32),
            np.array([[0, 48], [48, 49]]),
        )
        print(json.dumps({"init": world, "delta": rec}), flush=True)
    elif phase == "replan":
        world = deltas.replan(run_dir)
        print(json.dumps({"replan": world}), flush=True)
    else:
        raise SystemExit(f"unknown phase {phase!r}")


if __name__ == "__main__":
    main()
