"""Worker program for the REAL multi-process launch test
(test_multiprocess_launch.py). Every process runs this same file — the
multi-controller contract (multihost.py's module docstring; the reference's
torchrun/mpirun launcher matrix, ``MPIBackendEngine.py:268-341``).

The graph axis spans ALL devices across BOTH processes, so every per-layer
halo all_to_all crosses the process boundary, and each process materializes
only its own shards host-side (``process_local_shards``) and feeds them via
``jax.make_array_from_process_local_data`` — the per-host data loading the
single-controller dryruns can never exercise.

Run by the test as:  python tests/_mp_worker.py <coord> <nprocs> <pid>
Prints one line ``MPOK <loss> <devices> <procs>`` on success.
"""

import os
import sys

# each process gets its share of virtual CPU devices BEFORE jax import
# (argv[4], default 4 — the oracle run uses 1 process x 8 devices)
_DPP = int(sys.argv[4]) if len(sys.argv) > 4 else 4
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={_DPP}"
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main(coord: str, nprocs: int, pid: int) -> None:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dgraph_tpu.comm import Communicator
    from dgraph_tpu.comm.mesh import (
        GRAPH_AXIS,
        plan_in_specs,
        squeeze_plan,
    )
    from dgraph_tpu.comm.multihost import (
        initialize_multihost,
        make_pod_mesh,
        process_local_shards,
    )
    from dgraph_tpu.data import DistributedGraph, synthetic
    from dgraph_tpu.models import GCN

    initialize_multihost(coord, nprocs, pid)
    assert jax.process_count() == nprocs, jax.process_count()
    W = jax.device_count()  # graph axis spans every device of every host
    assert W == _DPP * nprocs, W

    mesh = make_pod_mesh(ranks_per_graph=W, num_replicas=1)
    comm = Communicator.init_process_group("tpu", world_size=W)

    # identical partition on every process (same seed — the single-program
    # contract); each process MATERIALIZES only its own shards
    data = synthetic.sbm_classification_graph(
        num_nodes=128, num_classes=4, feat_dim=8, avg_degree=6.0, seed=0
    )
    g = DistributedGraph.from_global(
        data["edge_index"], data["features"], data["labels"], data["masks"],
        world_size=W, partition_method="random", add_symmetric_norm=True,
    )
    mine = process_local_shards(W)
    assert mine == list(range(pid * _DPP, (pid + 1) * _DPP)), (pid, mine)

    def gsh(spec):
        return NamedSharding(mesh, spec)

    def feed(arr, spec=P(GRAPH_AXIS)):
        """Global [W, ...] array from THIS process's rows only."""
        arr = np.asarray(arr)
        return jax.make_array_from_process_local_data(
            gsh(spec), np.ascontiguousarray(arr[mine]), arr.shape
        )

    plan = jax.tree.map(
        lambda leaf: feed(leaf) if getattr(leaf, "ndim", 0) > 0 else leaf,
        g.plan,
    )
    batch_x = feed(np.asarray(g.features, np.float32))
    batch_y = feed(np.asarray(g.labels))
    batch_m = feed(np.asarray(g.masks["train"]))
    batch_ew = feed(np.asarray(g.edge_weight, np.float32))

    model = GCN(hidden_features=16, out_features=4, comm=comm)

    def init_body(x_, plan_, ew_):
        return model.init(
            jax.random.key(0), x_[0], squeeze_plan(plan_), ew_[0]
        )

    with jax.set_mesh(mesh):
        params = jax.jit(
            jax.shard_map(
                init_body, mesh=mesh,
                in_specs=(P(GRAPH_AXIS), plan_in_specs(plan), P(GRAPH_AXIS)),
                out_specs=P(),
            )
        )(batch_x, plan, batch_ew)

        def body(p, x_, y_, m_, ew_, plan_):
            xx, yy, mm, ew = x_[0], y_[0], m_[0], ew_[0]
            pln = squeeze_plan(plan_)

            def lf(p):
                logits = model.apply(p, xx, pln, ew)
                logp = jax.nn.log_softmax(logits)
                ll = jnp.take_along_axis(logp, yy[:, None], axis=1)[:, 0]
                cnt = jax.lax.psum(mm.sum(), GRAPH_AXIS)
                return -(ll * mm).sum() / jnp.maximum(cnt, 1.0)

            loss, grads = jax.value_and_grad(lf)(p)
            grads = jax.tree.map(
                lambda t: jax.lax.psum(t, GRAPH_AXIS), grads
            )
            return jax.lax.psum(loss, GRAPH_AXIS), grads

        step = jax.jit(
            jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(GRAPH_AXIS), P(GRAPH_AXIS), P(GRAPH_AXIS),
                          P(GRAPH_AXIS), plan_in_specs(plan)),
                out_specs=(P(), P()),
                check_vma=False,
            )
        )
        loss, grads = step(params, batch_x, batch_y, batch_m, batch_ew, plan)
        loss = float(loss)  # replicated: every process fetches the same value
        gnorm = float(
            sum(float(jnp.abs(l).sum()) for l in jax.tree.leaves(grads))
        )
    assert np.isfinite(loss) and gnorm > 0
    print(f"MPOK {loss:.6f} {jax.device_count()} {jax.process_count()}",
          flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))
