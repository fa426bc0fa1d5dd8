"""Compile-only rehearsal of a multi-chip GCN cell: lower its train and eval
steps at the real size for a described ``v5e:2x2`` (no chip attached) and
print each chip's ``memory_analysis()``. This is how a four-chip
configuration is sized: ``hbm_peak_gb`` reads live buffers plus the loaded
programs' temporaries, which is arguments + temporaries of the larger step.

    JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_w4.py \\
        --workload gcn_papers100m.w4 [--scale 0.004,0.0045,0.005]

``--scale`` overrides the configuration's graph by
``experiments/papers100m_gcn.py``'s rule (V = floor(111 059 956 s), 14.5 V
directed edges). The graph and plan are built on the host as a run builds
them; the program is traced with ``jax.default_backend`` answering ``tpu``,
so that it takes the Pallas branches it takes on the chip (the process itself
sees the CPU; the steering is here, not an option of the program). Nothing
runs: a compile that passes is not a chip run, and no time comes from here.
"""

from __future__ import annotations

import argparse
import math
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
PAPERS100M_NODES = 111_059_956
PAPERS100M_DEGREE = 14.5


def rehearse(traffic: dict, sizes: dict, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import graphs
    from benchmark.builders.gcn import distributed_graph, gcn_model
    from dgraph_tpu.comm import Communicator, make_graph_mesh
    from dgraph_tpu.comm.mesh import GRAPH_AXIS
    from dgraph_tpu.train.loop import (
        init_params,
        make_eval_step,
        make_train_step,
    )

    W = traffic["world_size"]
    V, F, C = sizes["num_nodes"], sizes["feat"], sizes["classes"]
    t0 = time.perf_counter()
    edge_index = graphs.edges(traffic, V, sizes["num_edges"], seed)
    x, y, masks = graphs.node_data(
        V, F, C, seed, sizes["train_fraction"], sizes["val_fraction"])
    g = distributed_graph(edge_index, x, y, masks, W, sizes)
    print(f"V={V} directed_edges={g.num_edges} W={W} "
          f"n_pad={g.plan.n_src_pad} e_pad={g.plan.e_pad} "
          f"s_pad={g.plan.halo.s_pad} (host build {time.perf_counter() - t0:.1f} s)",
          flush=True)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_graph_mesh(ranks_per_graph=W, devices=list(topo.devices)[:W])
    on_axis, replicated = NamedSharding(mesh, P(GRAPH_AXIS)), NamedSharding(mesh, P())

    def described(tree, sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    plan = described(g.plan, on_axis)
    batch_tr = described(g.batch("train"), on_axis)
    batch_va = described(g.batch("val"), on_axis)
    comm = Communicator.init_process_group("tpu", world_size=W)
    model = gcn_model(sizes, comm)
    optimizer = optax.adam(sizes["learning_rate"])

    # the parameter tree depends on the widths alone: the program's own init,
    # run on CPU devices over a small graph of the same widths, gives it
    small = distributed_graph(
        graphs.edges(traffic, 2048, 8192, seed), x[:2048], y[:2048],
        {k: v[:2048] for k, v in masks.items()}, W, sizes)
    cpu_mesh = make_graph_mesh(ranks_per_graph=W, devices=jax.devices()[:W])
    params = described(init_params(
        model, cpu_mesh, jax.tree.map(jnp.asarray, small.plan),
        jax.tree.map(jnp.asarray, small.batch("train")), 0), replicated)

    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"  # the program's kernel dispatch asks
    try:
        with jax.set_mesh(mesh):
            opt_state = described(jax.eval_shape(optimizer.init, params),
                                  replicated)
            steps = {
                "train": (make_train_step(model, optimizer, mesh, g.plan),
                          (params, opt_state, batch_tr, plan)),
                "eval": (make_eval_step(model, mesh),
                         (params, batch_va, plan)),
            }
            for name, (step, args) in steps.items():
                t0 = time.perf_counter()
                compiled = step.lower(*args).compile()
                m = compiled.memory_analysis()
                text = compiled.as_text()
                total = (m.argument_size_in_bytes + m.temp_size_in_bytes
                         + m.output_size_in_bytes - m.alias_size_in_bytes)
                print(f"{name} step, per chip: arguments "
                      f"{m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
                      f"{m.temp_size_in_bytes / 1e9:.3f} GB, outputs "
                      f"{m.output_size_in_bytes / 1e9:.3f} GB, aliased "
                      f"{m.alias_size_in_bytes / 1e9:.3f} GB: "
                      f"{total / 1e9:.3f} GB of 16; arguments + temporaries "
                      f"{(m.argument_size_in_bytes + m.temp_size_in_bytes) / 1e9:.3f} GB; "
                      f"pallas calls {text.count('tpu_custom_call')}, "
                      f"all-to-all {text.count('all-to-all(')}; compiled in "
                      f"{time.perf_counter() - t0:.1f} s", flush=True)
    finally:
        jax.default_backend = real_backend


def main() -> int:
    from benchmark import run as harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--scale", default="",
                    help="comma-separated papers100M fractions to try instead "
                         "of the configuration's own graph")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    _, config, traffic = harness.find_cell(bench, args.workload)
    from dgraph_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if not args.scale:
        rehearse(traffic, config["sizes"], args.seed)
    for s in [float(v) for v in args.scale.split(",") if v]:
        nodes = math.floor(PAPERS100M_NODES * s)
        sizes = dict(config["sizes"], num_nodes=nodes,
                     num_edges=math.floor(PAPERS100M_DEGREE * nodes / 2))
        print(f"--- synthetic_scale {s}", flush=True)
        rehearse(traffic, sizes, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
