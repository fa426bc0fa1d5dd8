"""GraphCast training (the reference's
``experiments/GraphCast/train_graphcast.py``): distributed mesh GNN on
synthetic ERA5-like weather, 3-phase LR schedule, checkpointing, and a
``--microbenchmark`` mode timing comm-vs-compute per block
(``microbenchmark_graphcast.py`` parity).
"""

from __future__ import annotations

import dataclasses
import time


@dataclasses.dataclass
class Config:
    """Distributed GraphCast training on synthetic weather."""

    mesh_level: int = 4
    num_lat: int = 181  # 1-degree grid default; 721 = ERA5 0.25-degree
    num_lon: int = 360
    channels: int = 73
    latent: int = 128
    processor_layers: int = 4
    peak_lr: float = 1e-3
    warmup_steps: int = 100
    decay_steps: int = 10_000
    steps: int = 200
    world_size: int = 0
    ckpt_dir: str = ""
    save_freq: int = 100
    microbenchmark: bool = False
    # GraphCast evaluates with Polyak-averaged weights (train/ema.py);
    # 0 disables the EMA track entirely
    ema_decay: float = 0.999
    # >0: after training, run an autoregressive rollout of this many steps
    # against the dataset's true trajectory (raw AND ema weights) and log
    # per-step RMSE — GraphCast's eval protocol (models.graphcast.rollout)
    eval_rollout: int = 0
    log_path: str = "logs/graphcast.jsonl"
    # elastic knobs (train/elastic.py): SIGTERM/SIGINT triggers a final
    # checkpoint + clean exit; a >0 deadline arms the per-step wedge
    # watchdog (exit 17 = restart+resume me)
    step_deadline_s: float = 0.0
    # thread grad-norm through the jitted step and emit obs step records;
    # build-time flag: False keeps the step byte-identical to before
    step_metrics: bool = False


def main(cfg: Config):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm import Communicator, make_graph_mesh
    from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan
    from dgraph_tpu.data.weather import SyntheticWeatherDataset
    from dgraph_tpu.obs import startup_record
    from dgraph_tpu.obs.metrics import StepMetrics
    from dgraph_tpu.models.graphcast import GraphCast, build_graphcast_graphs
    from dgraph_tpu.train.checkpoint import (
        checkpoint_keys, restore_checkpoint, save_checkpoint)
    from dgraph_tpu.train.ema import ema_init, ema_update
    from dgraph_tpu.train.schedules import graphcast_three_phase
    from dgraph_tpu.obs import spans
    from dgraph_tpu.utils.compile_cache import compile_totals
    from dgraph_tpu.utils import ExperimentLog

    world = cfg.world_size or len(jax.devices())
    mesh = make_graph_mesh(ranks_per_graph=world)
    comm = Communicator.init_process_group("tpu", world_size=world)
    log = ExperimentLog(cfg.log_path)
    log.write(startup_record("experiments.graphcast_train"))

    graphs = build_graphcast_graphs(cfg.mesh_level, cfg.num_lat, cfg.num_lon, world)
    ds = SyntheticWeatherDataset(graphs, cfg.num_lat, cfg.num_lon, cfg.channels)

    model = GraphCast(
        comm=comm,
        latent=cfg.latent,
        processor_layers=cfg.processor_layers,
        out_channels=cfg.channels,
    )

    statics = {
        "grid_node_static": jnp.asarray(graphs.grid_node_static),
        "mesh_node_static": jnp.asarray(graphs.mesh_node_static),
        "mesh_edge_static": jnp.asarray(graphs.mesh_edge_static),
        "g2m_edge_static": jnp.asarray(graphs.g2m_edge_static),
        "m2g_edge_static": jnp.asarray(graphs.m2g_edge_static),
    }
    plans = {
        "mesh": jax.tree.map(jnp.asarray, graphs.mesh_plan),
        "g2m": jax.tree.map(jnp.asarray, graphs.g2m_plan),
        "m2g": jax.tree.map(jnp.asarray, graphs.m2g_plan),
    }
    gmask = jnp.asarray(graphs.grid_mask)
    st_specs = {k: P(GRAPH_AXIS) for k in statics}
    pl_specs = {k: plan_in_specs(p) for k, p in plans.items()}

    def init_body(x, statics_, plans_):
        return model.init(
            jax.random.key(0),
            x[0],
            {k: v[0] for k, v in statics_.items()},
            {k: squeeze_plan(p) for k, p in plans_.items()},
        )

    x0, _ = ds.get_sharded(0)
    with jax.set_mesh(mesh):
        params = jax.jit(
            jax.shard_map(
                init_body,
                mesh=mesh,
                in_specs=(P(GRAPH_AXIS), st_specs, pl_specs),
                out_specs=P(),
            )
        )(jnp.asarray(x0), statics, plans)

    schedule = graphcast_three_phase(cfg.peak_lr, cfg.warmup_steps, cfg.decay_steps)
    opt = optax.adamw(schedule, weight_decay=0.1)
    opt_state = opt.init(params)
    ema = ema_init(params) if cfg.ema_decay > 0 else None
    step_idx = 0
    if cfg.ckpt_dir:
        base = {"params": params, "opt_state": opt_state, "step": 0}
        with_ema = dict(base, ema=ema if ema is not None else ema_init(params))
        # pick the template from what the checkpoint ACTUALLY contains
        # (ema track present or not) instead of try/except-ing a mismatch —
        # genuine corruption/IO errors now propagate with their original
        # traceback (ADVICE r3 #5). A pre-EMA checkpoint under an EMA run
        # restarts the track from the restored params; an EMA-bearing
        # checkpoint under ema_decay=0 drops the track.
        keys = checkpoint_keys(cfg.ckpt_dir)
        if keys is not None:
            ckpt_has_ema = "ema" in keys
            restored = restore_checkpoint(
                cfg.ckpt_dir, with_ema if ckpt_has_ema else base)
        else:
            # metadata unreadable (older orbax layout / partially synced
            # dir) but a checkpoint may still exist: fall back to the
            # two-template probe. A template mismatch is the ONLY error
            # retried; corruption/IO errors propagate from the retry.
            ckpt_has_ema = ema is not None
            try:
                restored = restore_checkpoint(
                    cfg.ckpt_dir, with_ema if ckpt_has_ema else base)
            except Exception:
                ckpt_has_ema = not ckpt_has_ema
                restored = restore_checkpoint(
                    cfg.ckpt_dir, with_ema if ckpt_has_ema else base)
        if restored:
            if ema is not None and not ckpt_has_ema:
                restored["ema"] = ema_init(restored["params"])
            elif ema is None:
                restored.pop("ema", None)
            params, opt_state, step_idx = (
                restored["params"],
                restored["opt_state"],
                int(restored["step"]),
            )
            ema = restored.get("ema", ema)
            log.write({"resumed_at_step": step_idx})

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
        train_body,
        mesh=mesh,
        in_specs=(P(), P(GRAPH_AXIS), P(GRAPH_AXIS), P(GRAPH_AXIS), st_specs, pl_specs),
        out_specs=(P(), P()),
    )

    @jax.jit
    def step(params, opt_state, ema, x, y):
        loss, grads = body(params, x, y, gmask, statics, plans)
        # build-time flag: the default (False) step is byte-identical to
        # the un-instrumented program — no overhead, no extra recompiles
        gn = optax.global_norm(grads) if cfg.step_metrics else None
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if ema is not None:  # trace-time constant (pytree vs None)
            ema = ema_update(ema, params, cfg.ema_decay)
        return params, opt_state, ema, StepMetrics(loss=loss, grad_norm=gn)

    if cfg.microbenchmark:
        _microbenchmark(model, params, statics, plans, mesh, comm, ds, log)
        return

    import contextlib

    from dgraph_tpu.train.elastic import PreemptionGuard, StepWatchdog

    # hand-rolled rather than run_elastic(): this loop owns per-step data
    # feeding (ds.get_sharded) and custom logging; the elastic pieces used
    # are the same objects, incl. watchdog suspension around saves
    guard = PreemptionGuard()
    dog = StepWatchdog(cfg.step_deadline_s) if cfg.step_deadline_s > 0 else None
    try:
        with jax.set_mesh(mesh):
            while step_idx < cfg.steps:
                x, y = ds.get_sharded(step_idx)
                t0 = time.perf_counter()
                params, opt_state, ema, sm = step(
                    params, opt_state, ema, jnp.asarray(x), jnp.asarray(y))
                jax.block_until_ready(sm.loss)
                if dog is not None:
                    dog.beat()
                dt = (time.perf_counter() - t0) * 1000
                step_idx += 1
                preempted = guard.should_stop()
                if step_idx % 10 == 0 or step_idx == cfg.steps or preempted:
                    log.write(sm.record(
                        step=step_idx,
                        step_ms=round(dt, 2),
                        lr=float(schedule(step_idx)),
                    ))
                if cfg.ckpt_dir and (step_idx % cfg.save_freq == 0 or preempted):
                    # a long orbax write is not a wedged device — suspend
                    # the watchdog for the duration (elastic.py:_save)
                    with (dog.suspended() if dog is not None
                          else contextlib.nullcontext()):
                        state = {"params": params, "opt_state": opt_state,
                                 "step": step_idx}
                        if ema is not None:
                            state["ema"] = ema
                        save_checkpoint(cfg.ckpt_dir, state, step_idx)
                if preempted:
                    log.write({"preempted_at_step": step_idx})
                    break
    finally:
        if dog is not None:
            dog.stop()
        guard.uninstall()

    # a preemption asked for a prompt exit — the final checkpoint is saved;
    # don't spend the grace period compiling a multi-minute rollout
    if cfg.eval_rollout > 0 and not guard.should_stop():
        from dgraph_tpu.models.graphcast import rollout as gc_rollout

        x0, truth = ds.trajectory_sharded(0, cfg.eval_rollout)

        def eval_body(p, x0_, statics_, plans_):
            st = {k: v[0] for k, v in statics_.items()}
            pln = {k: squeeze_plan(pp) for k, pp in plans_.items()}
            traj = gc_rollout(model, p, x0_[0], st, pln, cfg.eval_rollout)
            return traj[:, None]  # add the shard axis back: [T, 1, n, C]

        run_rollout = jax.jit(jax.shard_map(
            eval_body, mesh=mesh,
            in_specs=(P(), P(GRAPH_AXIS), st_specs, pl_specs),
            out_specs=P(None, GRAPH_AXIS),
        ))
        import numpy as np

        m_np = np.asarray(gmask)[None, :, :, None]  # [1, W, n, 1]
        denom = m_np.sum() * cfg.channels
        tracks = [("raw", params)] + ([("ema", ema)] if ema is not None else [])
        with jax.set_mesh(mesh):
            for label, p in tracks:
                traj = np.asarray(run_rollout(p, jnp.asarray(x0), statics, plans))
                rmse = np.sqrt(
                    ((traj - truth) ** 2 * m_np).sum(axis=(1, 2, 3)) / denom
                )
                log.write({
                    "rollout_eval": label, "steps": cfg.eval_rollout,
                    "rmse_per_step": [round(float(r), 5) for r in rmse],
                })
    log.write({"stages": spans.stage_totals(), "compiles": compile_totals()})


def _microbenchmark(model, params, statics, plans, mesh, comm, ds, log):
    """Comm-vs-compute split of one MeshEdgeBlock — parity with
    ``microbenchmark_graphcast.py:63-247``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan
    from dgraph_tpu.comm import collectives

    x0, _ = ds.get_sharded(0)
    x0 = jnp.asarray(x0)
    latent = model.latent
    mesh_plan = plans["mesh"]

    def gather_only(h, plan_):
        return collectives.gather(h, squeeze_plan(plan_), "src", GRAPH_AXIS)

    def local_only(h, plan_):
        p = squeeze_plan(plan_)
        return collectives.gather(h, p, "dst", GRAPH_AXIS)  # dst side = no comm

    h = jnp.zeros((mesh_plan.src_index.shape[0], mesh_plan.n_src_pad, latent))
    for name, fn in [("comm_gather", gather_only), ("local_gather", local_only)]:
        f = jax.jit(
            jax.shard_map(
                lambda h_, p_: fn(h_[0], p_)[None],
                mesh=mesh,
                in_specs=(P(GRAPH_AXIS), plan_in_specs(mesh_plan)),
                out_specs=P(GRAPH_AXIS),
            )
        )
        with jax.set_mesh(mesh):
            out = f(h, mesh_plan)
            jax.block_until_ready(out)
            import time as _t

            times = []
            for _ in range(20):
                t0 = _t.perf_counter()
                out = f(h, mesh_plan)
                jax.block_until_ready(out)
                times.append((_t.perf_counter() - t0) * 1000)
        import numpy as np

        log.write({f"{name}_ms_mean": float(np.mean(times)), f"{name}_ms_std": float(np.std(times))})


if __name__ == "__main__":
    import os as _os, sys as _sys

    # direct-invocation support (repo not pip-installed): put the repo
    # root on sys.path so `python experiments/<script>.py` works
    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
