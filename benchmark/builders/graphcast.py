"""Builder for GraphCast training cells: the step of
``experiments/graphcast_train.py``, call for call.

That script's loop lives inside its ``main`` and cannot be imported, so the
construction below is a copy of it (``build_graphcast_graphs`` -> statics,
plans and the grid mask as device arrays closed over by the jitted step ->
``model.init`` under ``shard_map`` -> ``optax.adamw`` over the three-phase
schedule -> the EMA track -> ``step(params, opt_state, ema, x, y)``, not
donated), and so is the timed step:

    jnp.asarray(x), jnp.asarray(y)  ->  step(...)  ->  block_until_ready(loss)

with one host batch transferred per step. The K batches are made from the
seed and laid out in the plan's order during set-up (the trainer's dataset
does the lay-out per step; that is synthesis, and not timed here).
"""

from __future__ import annotations

import time

from benchmark import weather, weights
from benchmark.cells import Phase, annotate

STATIC_KEYS = ("grid_node_static", "mesh_node_static", "mesh_edge_static",
               "g2m_edge_static", "m2g_edge_static")


class GraphCastCell:
    check_phase = "fed"

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import PartitionSpec as P

        from dgraph_tpu.comm import Communicator, make_graph_mesh
        from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan
        from dgraph_tpu.models.graphcast import (
            GraphCast, build_graphcast_graphs, rollout)
        from dgraph_tpu.obs.metrics import StepMetrics
        from dgraph_tpu.plan import shard_vertex_data
        from dgraph_tpu.train.ema import ema_init, ema_update
        from dgraph_tpu.train.schedules import graphcast_three_phase

        self.jax, self.jnp = jax, jnp
        self.ref = ctx.reference
        size = self.size = ctx.sizes
        world = ctx.traffic["world_size"]
        C = size["channels"]
        self.mesh = mesh = make_graph_mesh(
            ranks_per_graph=world, devices=ctx.devices[:world])
        comm = Communicator.init_process_group("tpu", world_size=world)

        # --- graph build (host) ---
        t0 = time.perf_counter()
        graphs = build_graphcast_graphs(
            size["mesh_level"], size["num_lat"], size["num_lon"], world)
        ctx.spans["plan_build_s"] = time.perf_counter() - t0
        ctx.say(f"graphs: grid={graphs.num_grid} (pad {graphs.n_grid_pad}) "
                f"mesh={graphs.num_mesh} (pad {graphs.n_mesh_pad}) edges "
                f"mesh={graphs.mesh_plan.e_pad} g2m={graphs.g2m_plan.e_pad} "
                f"m2g={graphs.m2g_plan.e_pad}")
        self.info = {
            "world_size": world, "latent": size["latent"],
            "processor_layers": size["processor_layers"], "channels": C,
            "n_grid": int(graphs.n_grid_pad), "n_mesh": int(graphs.n_mesh_pad),
            "e_mesh": int(graphs.mesh_plan.e_pad),
            "e_g2m": int(graphs.g2m_plan.e_pad),
            "e_m2g": int(graphs.m2g_plan.e_pad),
            "compute_bytes": jnp.dtype(size["compute_dtype"]).itemsize,
            "remat": bool(size["remat"]),
        }

        def lay_out(a):
            return shard_vertex_data(
                a[graphs.grid_ren.inv], graphs.grid_ren.counts,
                graphs.n_grid_pad)

        self._lay_out = lay_out
        self.make_batches(ctx.seed, ctx.traffic["batches"], ctx.spans)

        model = GraphCast(
            comm=comm, latent=size["latent"],
            processor_layers=size["processor_layers"], out_channels=C,
            dtype=jnp.dtype(size["compute_dtype"]), remat=size["remat"])

        # --- placement: statics, plans and the mask go to the device once ---
        t0 = time.perf_counter()
        statics = {k: jnp.asarray(getattr(graphs, k)) for k in STATIC_KEYS}
        plans = {
            "mesh": jax.tree.map(jnp.asarray, graphs.mesh_plan),
            "g2m": jax.tree.map(jnp.asarray, graphs.g2m_plan),
            "m2g": jax.tree.map(jnp.asarray, graphs.m2g_plan),
        }
        gmask = jnp.asarray(graphs.grid_mask)
        jax.block_until_ready((statics, plans, gmask))
        ctx.spans["placement_s"] = time.perf_counter() - t0
        st_specs = {k: P(GRAPH_AXIS) for k in statics}
        pl_specs = {k: plan_in_specs(p) for k, p in plans.items()}

        # --- weights from the seed, in the tree of the program's own init ---
        # (the trainer runs model.init at full size; only its shapes are
        # taken here, so that no init program is compiled in every run)
        t0 = time.perf_counter()

        def init_body(x, statics_, plans_):
            return model.init(
                jax.random.key(0), x[0],
                {k: v[0] for k, v in statics_.items()},
                {k: squeeze_plan(p) for k, p in plans_.items()})

        with jax.set_mesh(mesh):
            self._shapes = jax.eval_shape(jax.shard_map(
                init_body, mesh=mesh,
                in_specs=(P(GRAPH_AXIS), st_specs, pl_specs), out_specs=P(),
            ), jnp.asarray(self.batches[0][0]), statics, plans)
        schedule = graphcast_three_phase(
            size["peak_lr"], size["warmup_steps"], size["decay_steps"])
        opt = optax.adamw(schedule, weight_decay=size["weight_decay"])
        ema_decay = size["ema_decay"]
        self._opt, self._ema_init = opt, ema_init
        self.make_state(ctx.seed)
        ctx.spans["weights_s"] = time.perf_counter() - t0

        def train_body(params, x, y, mask, statics_, plans_):
            x_, y_, m_ = x[0], y[0], mask[0]
            st = {k: v[0] for k, v in statics_.items()}
            pln = {k: squeeze_plan(p) for k, p in plans_.items()}

            def lf(p):
                pred = model.apply(p, x_, st, pln)
                se = ((pred - y_) ** 2).sum(-1) * m_
                cnt = jax.lax.psum(m_.sum(), GRAPH_AXIS)
                return se.sum() / jnp.maximum(cnt, 1.0)

            loss, grads = jax.value_and_grad(lf)(params)
            return jax.lax.psum(loss, GRAPH_AXIS), grads

        body = jax.shard_map(
            train_body, mesh=mesh,
            in_specs=(P(), P(GRAPH_AXIS), P(GRAPH_AXIS), P(GRAPH_AXIS),
                      st_specs, pl_specs),
            out_specs=(P(), P()))

        @jax.jit
        def step(params, opt_state, ema, x, y):
            loss, grads = body(params, x, y, gmask, statics, plans)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            ema = ema_update(ema, params, ema_decay)
            return params, opt_state, ema, StepMetrics(loss=loss, grad_norm=None)

        self.step = step
        self.sm = None
        self.phases = [Phase("fed", "fed_step_ms", 1.0, self.fed_once)]

        if ctx.traced:
            # the forward alone, as the trainer's rollout evaluation runs it
            def eval_body(p, x0_, statics_, plans_):
                st = {k: v[0] for k, v in statics_.items()}
                pln = {k: squeeze_plan(pp) for k, pp in plans_.items()}
                return rollout(model, p, x0_[0], st, pln, 1)[:, None]

            self.forward = jax.jit(jax.shard_map(
                eval_body, mesh=mesh,
                in_specs=(P(), P(GRAPH_AXIS), st_specs, pl_specs),
                out_specs=P(None, GRAPH_AXIS)))
            self.fwd_args = (statics, plans)
            self.phases.append(Phase("fwd", None, 0.0, self.fwd_once))

    def context(self):
        return self.jax.set_mesh(self.mesh)

    # --- what depends on the seed -------------------------------------------
    def make_batches(self, seed, count, spans):
        """The K host batches, from the seed, in the plan's layout."""
        t0 = time.perf_counter()
        size = self.size
        self.fields = weather.batches(  # caller's order: the reference's input
            size["num_lat"], size["num_lon"], size["channels"], count, seed)
        self.batches = [(self._lay_out(x), self._lay_out(y))
                        for x, y in self.fields]
        self.info["h2d_bytes_per_step"] = {
            "fed": sum(a.nbytes for a in self.batches[0]),
            "fwd": self.batches[0][0].nbytes}
        self.cursor = 0
        spans["input_synthesis_s"] = time.perf_counter() - t0

    def make_state(self, seed):
        jax = self.jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        with jax.set_mesh(self.mesh):
            self.params = weights.seeded_params(
                self._shapes, seed, NamedSharding(self.mesh, P()))
            self.params0 = self.params  # not donated: the tree stays alive
            self.opt_state = self._opt.init(self.params)
            self.ema = self._ema_init(self.params)
        jax.block_until_ready((self.params, self.opt_state, self.ema))

    def reseed(self, seed):
        """tools/limits.py: another seed's inputs and weights under the same
        compiled step."""
        self.make_batches(seed, len(self.batches), {})
        self.make_state(seed)

    # --- the timed steps -------------------------------------------------
    def fed_once(self):
        jax, jnp = self.jax, self.jnp
        x, y = self.batches[self.cursor % len(self.batches)]
        self.cursor += 1
        with annotate("host_feed"):
            xd, yd = jnp.asarray(x), jnp.asarray(y)
        with annotate("step_dispatch"):
            self.params, self.opt_state, self.ema, self.sm = self.step(
                self.params, self.opt_state, self.ema, xd, yd)
        with annotate("block"):
            jax.block_until_ready(self.sm.loss)

    def fwd_once(self):
        jax, jnp = self.jax, self.jnp
        with annotate("host_feed"):
            xd = jnp.asarray(self.batches[0][0])
        with annotate("step_dispatch"):
            out = self.forward(self.ema, xd, *self.fwd_args)
        with annotate("block"):
            jax.block_until_ready(out)

    # --- what the comparison reads from the program's state ---------------
    def loss(self) -> float:
        return float(self.sm.loss)

    def first_gradient(self):
        """The first gradient as the optimizer got it, worked out from its
        state after one step: mu = (1 - b1) g. A tree of new buffers."""
        return self.jax.jit(lambda mu: self.jax.tree.map(
            lambda m: m / 0.1, mu))(self.opt_state[0].mu)

    def delta_norms(self) -> dict:
        return weights.leaf_norms(self.params, self.params0)

    def eval_numbers(self) -> dict:
        return {}

    def break_step(self, fault: str):
        """Tests only: put a fault under the timed path."""
        if fault != "frozen":
            raise ValueError(f"unknown fault {fault!r}")
        inner = self.step

        def frozen(params, opt_state, ema, x, y):
            sm = inner(params, opt_state, ema, x, y)[3]
            return params, opt_state, ema, sm  # the state is not advanced

        self.step = frozen

    def release(self):
        self.host_params0 = self.jax.device_get(self.params0)
        for name in ("params", "params0", "opt_state", "ema", "sm", "step",
                     "forward", "fwd_args", "phases"):
            setattr(self, name, None)
        self.jax.clear_caches()  # the step's constants (plans, statics) too

    def reference(self, steps: int, precision: str = "float32") -> dict:
        return self.ref.follow(self.host_params0, self.fields[:steps], self.size,
                          precision=precision)


def build(ctx):
    return GraphCastCell(ctx)
