"""Builder for full-graph GCN training cells.

The construction is ``chip_smoke.py``'s, to the letter: one global graph ->
``DistributedGraph.from_global`` (plan cache and tuner off) ->
``make_graph_mesh`` -> ``Communicator`` -> ``GCN`` in the configuration's
compute dtype -> ``put_on_graph_axis`` for the plan and both batches ->
``init_opt_state`` / ``make_train_step`` / ``make_eval_step``. What a
configuration's sizes state beyond that is passed on as the trainers pass it:
``partition_method`` and ``pad_multiple`` (left out: the program's defaults),
``symmetric_norm`` (false: no edge weight, the unweighted kernels), ``remat``
(``nn.remat(GCN)``, as ``experiments/papers100m_gcn.py`` wraps it). The graph
depends on the seed and the traffic's law only, never on ``world_size``.

The timed steps are the loop ``train/loop.py::fit`` runs: inputs resident on
the device, one call of the jitted step, the host blocks on the loss.
"""

from __future__ import annotations

import time

from benchmark import graphs, weights
from benchmark.cells import Phase, annotate


def distributed_graph(edge_index, x, y, masks, world_size: int, size: dict):
    """Partition, plan and shard one global graph as the trainers do, with the
    plan cache and the tuner off."""
    from dgraph_tpu.data import DistributedGraph

    return DistributedGraph.from_global(
        edge_index, x, y, masks, world_size=world_size,
        add_symmetric_norm=size["symmetric_norm"],
        partition_method=size.get("partition_method"),
        pad_multiple=size.get("pad_multiple"),
        plan_cache_dir="", tune="off",
    )


def gcn_model(size: dict, comm):
    """The configuration's GCN, under ``nn.remat`` where it says so."""
    import jax.numpy as jnp

    from dgraph_tpu.models import GCN

    model_cls = GCN
    if size.get("remat"):
        import flax.linen as nn

        model_cls = nn.remat(GCN)
    return model_cls(size["hidden"], size["classes"], comm=comm,
                     num_layers=size["num_layers"],
                     dtype=jnp.dtype(size["compute_dtype"]))


class GCNCell:
    check_phase = "train"

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dgraph_tpu.comm import Communicator, make_graph_mesh
        from dgraph_tpu.comm.mesh import put_on_graph_axis
        from dgraph_tpu.train.loop import (
            init_opt_state,
            init_params,
            make_eval_step,
            make_train_step,
        )

        self.jax = jax
        self.ref = ctx.reference
        size = self.size = ctx.sizes
        W = ctx.traffic["world_size"]
        V, F, C = size["num_nodes"], size["feat"], size["classes"]

        # --- inputs, from the seed (host) ---
        t0 = time.perf_counter()
        self.edge_index = graphs.edges(ctx.traffic, V, size["num_edges"], ctx.seed)
        self.x, self.y, self.masks = graphs.node_data(
            V, F, C, ctx.seed, size["train_fraction"], size["val_fraction"])
        ctx.spans["input_synthesis_s"] = time.perf_counter() - t0

        # --- graph build (host): partition + plan ---
        t0 = time.perf_counter()
        g = distributed_graph(self.edge_index, self.x, self.y, self.masks, W, size)
        ctx.spans["plan_build_s"] = time.perf_counter() - t0
        ctx.say(f"graph: V={V} directed_edges={g.num_edges} W={W} "
                f"n_pad={g.plan.n_src_pad} e_pad={g.plan.e_pad} "
                f"s_pad={g.plan.halo.s_pad} halo_deltas={tuple(g.plan.halo_deltas)}")
        self.info = {
            "world_size": W, "n_pad": int(g.plan.n_src_pad),
            "e_pad": int(g.plan.e_pad), "s_pad": int(g.plan.halo.s_pad),
            "feat": F, "hidden": size["hidden"], "classes": C,
            "num_layers": size["num_layers"],
            "compute_bytes": jnp.dtype(size["compute_dtype"]).itemsize,
        }

        # --- mesh, model, placement ---
        self.mesh = make_graph_mesh(ranks_per_graph=W, devices=ctx.devices[:W])
        comm = Communicator.init_process_group("tpu", world_size=W)
        model = gcn_model(size, comm)
        t0 = time.perf_counter()
        self.plan = put_on_graph_axis(g.plan, self.mesh)
        self.batch_tr = put_on_graph_axis(g.batch("train"), self.mesh)
        self.batch_va = put_on_graph_axis(g.batch("val"), self.mesh)
        jax.block_until_ready((self.plan, self.batch_tr, self.batch_va))
        ctx.spans["placement_s"] = time.perf_counter() - t0
        if W > 1:
            for leaf in jax.tree.leaves((self.plan, self.batch_tr, self.batch_va)):
                shards = leaf.addressable_shards
                if len(shards) != W or any(
                        s.data.shape != (1,) + leaf.shape[1:] for s in shards):
                    raise AssertionError(
                        f"leaf {leaf.shape} is not sharded 1/W per device")
            ctx.say(f"placement: every plan/batch leaf holds 1/{W} per device")

        # --- weights from the seed, in the program's own tree ---
        t0 = time.perf_counter()
        # the program's own init runs, as in fit(): a user's launch pays
        # it, and it fixes the tree, the shapes and the placement; the values
        # are then the benchmark's, which the reference gets too
        inited = init_params(model, self.mesh, self.plan, self.batch_tr, 0)
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), inited)
        with jax.set_mesh(self.mesh):
            self.params = weights.seeded_params(
                shapes, ctx.seed, NamedSharding(self.mesh, P()),
                kernel_gain=size.get("kernel_gain", 1.0))
        del inited
        self.params0 = jax.tree.map(jnp.copy, self.params)  # the step donates
        optimizer = optax.adam(size["learning_rate"])
        self.opt_state = init_opt_state(optimizer, self.params, self.mesh)
        jax.block_until_ready((self.params0, self.opt_state))
        ctx.spans["weights_s"] = time.perf_counter() - t0

        self.train_step = make_train_step(model, optimizer, self.mesh, self.plan)
        self._undonated = lambda: make_train_step(
            model, optimizer, self.mesh, self.plan, donate=False)
        self.eval_step = make_eval_step(model, self.mesh)
        self.metrics = None
        self.eval_out = None
        self.phases = [
            Phase("train", "train_step_ms", 2 / 3, self.train_once),
            Phase("eval", "eval_step_ms", 1 / 3, self.eval_once),
        ]

    # --- the timed steps -------------------------------------------------
    def context(self):
        """fit() runs its whole loop under the mesh; so does the harness."""
        return self.jax.set_mesh(self.mesh)

    def train_once(self):
        with annotate("step_dispatch"):
            self.params, self.opt_state, self.metrics = self.train_step(
                self.params, self.opt_state, self.batch_tr, self.plan)
        with annotate("block"):
            self.jax.block_until_ready(self.metrics["loss"])

    def eval_once(self):
        with annotate("step_dispatch"):
            self.eval_out = self.eval_step(
                self.params, self.batch_va, self.plan)
        with annotate("block"):
            self.jax.block_until_ready(self.eval_out["loss"])

    # --- what the comparison reads from the program's state ---------------
    def loss(self) -> float:
        return float(self.metrics["loss"])

    def first_gradient(self):
        """The first gradient as the optimizer got it, worked out from its
        state after one step: mu = (1 - b1) g. A tree of new buffers."""
        return self.jax.jit(lambda mu: self.jax.tree.map(
            lambda m: m / 0.1, mu))(self.opt_state[0].mu)

    def delta_norms(self) -> dict:
        return weights.leaf_norms(self.params, self.params0)

    def eval_numbers(self) -> dict:
        return {"eval_loss": float(self.eval_out["loss"]),
                "eval_accuracy": float(self.eval_out["accuracy"])}

    def break_step(self, fault: str):
        """Tests only: put a fault under the timed path."""
        if fault != "frozen":
            raise ValueError(f"unknown fault {fault!r}")
        inner = self._undonated()

        def frozen(params, opt_state, batch, plan):
            _, _, metrics = inner(params, opt_state, batch, plan)
            return params, opt_state, metrics  # the state is not advanced

        self.train_step = frozen

    def release(self):
        self.host_params0 = self.jax.device_get(self.params0)
        for name in ("params", "params0", "opt_state", "plan", "batch_tr",
                     "batch_va", "metrics", "eval_out", "train_step",
                     "eval_step", "phases", "_undonated"):
            setattr(self, name, None)

    # --- the plain reference, on what the benchmark made ------------------
    def reference(self, steps: int, precision: str = "float32") -> dict:
        return self.ref.follow(
            self.host_params0, self.edge_index, self.x, self.y, self.masks,
            self.size, steps=steps, precision=precision)


def build(ctx):
    return GCNCell(ctx)
