"""CPU scan-delta step-time attribution: the bench tier that un-blinds
wedged rounds.

BENCH_r03–r05 each burned ~1200 s on wedged-lease probes and landed
``value: null`` — zero perf signal for three straight rounds.  PR 6's
``schedule_drift`` fallback made the *comm-schedule* dimension non-null;
this module closes ROADMAP item 5's remaining gap: a **timing** tier that
runs on the virtual-CPU backend (8 forced host devices, the same backend
tier-1 uses, so the persistent XLA cache is warm) and produces a per-phase
step-time breakdown per halo lowering — comparable across rounds even when
no chip ever comes up.

Protocol: bench.py's compile-inside-scan rules verbatim (n steps inside
one ``lax.scan`` under one jit, scalar-fetch completion barrier, report
the positive delta between two scan lengths so per-call overhead cancels
— :func:`dgraph_tpu.tune.measure._timed_scan_ms` is reused as-is).

Program variants, per halo lowering (the config pin drives resolution, the
same mechanism the trace auditor uses):

- ``full``           — 2-layer GCN train step: fwd + bwd + optimizer.
- ``no_optimizer``   — fwd + bwd only (optimizer = full − no_optimizer).
- ``exchange_only``  — the isolated exchange legs: one
  ``halo_exchange`` + ``halo_scatter_sum`` pair per layer, no compute to
  hide behind.
- ``interior_only``  — fwd + bwd with the exchange elided
  (``halo_deltas=()`` makes every collective statically vanish while all
  local gather/scatter/matmul work keeps identical shapes). Lowering-
  independent: measured once and shared.

Breakdown per lowering (``phases_ms``):

- ``interior``  = interior_only (local compute)
- ``exchange``  = exchange_only (isolated collective cost)
- ``optimizer`` = full − no_optimizer
- ``other``     = full − interior − exchange − optimizer (the residual;
  NEGATIVE values are signal, not error — they mean the lowering hid part
  of the isolated exchange cost behind compute, which is exactly what the
  overlap lowering exists to do).  ``exposed_exchange_ms``
  (no_optimizer − interior_only) is the directly-measured exposed cost.

The record also folds the newest MULTICHIP dryrun's per-family step times
(``MULTICHIP_r*.json`` — ``__graft_entry__`` stamps ``step_ms=`` per
family) so one artifact carries both the phase attribution and the
model-family table.  ``python -m dgraph_tpu.obs.attribution
--bench_fallback true`` is what bench.py's wedged path spawns.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Optional

from dgraph_tpu.plan import HALO_IMPLS as DEFAULT_IMPLS

SCHEMA_VERSION = 1


def _num(x) -> Optional[float]:
    """NaN-safe rounding: the JSON artifact must stay strictly valid (and
    schema-stable) even when a timing round never yields a positive
    delta."""
    if x is None or x != x:
        return None
    return round(float(x), 3)


def multichip_family_table(root: Optional[str] = None) -> Optional[dict]:
    """Per-family step times from the newest ``MULTICHIP_r*.json`` dryrun
    artifact (``__graft_entry__`` prints ``dryrun <family> OK: ...
    step_ms=<x>`` per family).  None when no artifact exists; families
    missing ``step_ms`` (pre-stamping rounds) simply don't appear."""
    root = root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    files = sorted(glob.glob(os.path.join(root, "MULTICHIP_r*.json")))
    if not files:
        return None
    try:
        with open(files[-1]) as fh:
            artifact = json.load(fh)
    except (OSError, ValueError):
        return None
    families = {
        m.group(1): float(m.group(2))
        for m in re.finditer(
            r"dryrun (\S+) OK:.*?step_ms=([0-9.]+)", artifact.get("tail", "")
        )
    }
    return {
        "source": os.path.basename(files[-1]),
        "ok": artifact.get("ok"),
        "n_devices": artifact.get("n_devices"),
        "step_ms_by_family": families,
    }


# ---------------------------------------------------------------------------
# workload + program variants
# ---------------------------------------------------------------------------


def _build_workload(world_size, num_nodes, num_edges, feat_dim, hidden,
                    num_classes, seed):
    """Real (device-array) 2-layer GCN workload over a ``world_size``-shard
    random graph with the interior/boundary split, so every lowering —
    including overlap — is legal. Mirrors the trace auditor's workload but
    with concrete buffers: this tier *executes*."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    from dgraph_tpu import plan as pl
    from dgraph_tpu.comm import Communicator
    from dgraph_tpu.comm.mesh import make_graph_mesh
    from dgraph_tpu.models import GCN
    from dgraph_tpu.train.loop import init_params

    devices = jax.devices()
    if len(devices) < world_size:
        raise RuntimeError(
            f"scan-delta attribution for world_size={world_size} needs that "
            f"many devices; have {len(devices)} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8)"
        )
    rng = np.random.default_rng(seed)
    part = np.sort(rng.integers(0, world_size, num_nodes)).astype(np.int32)
    edges = np.stack([
        rng.integers(0, num_nodes, num_edges),
        rng.integers(0, num_nodes, num_edges),
    ])
    plan, layout = pl.build_edge_plan(
        edges, part, world_size=world_size, overlap=True
    )
    mesh = make_graph_mesh(
        ranks_per_graph=world_size, devices=devices[:world_size]
    )
    comm = Communicator.init_process_group("tpu", world_size=world_size)
    model = GCN(
        hidden_features=hidden, out_features=num_classes, comm=comm,
        num_layers=2,
    )
    x = pl.shard_vertex_data(
        rng.normal(size=(num_nodes, feat_dim)).astype(np.float32),
        layout.src_counts, plan.n_src_pad,
    )
    batch = {
        "x": jnp.asarray(x),
        "y": jnp.asarray(
            rng.integers(0, num_classes, (world_size, plan.n_src_pad))
            .astype(np.int32)),
        "mask": jnp.ones((world_size, plan.n_src_pad), jnp.float32),
    }
    plan_dev = jax.tree.map(jnp.asarray, plan)
    params = init_params(model, mesh, plan_dev, batch, seed=seed)
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)
    return {
        "mesh": mesh, "model": model, "optimizer": optimizer,
        "plan": plan_dev, "batch": batch, "params": params,
        "opt_state": opt_state, "feat_dim": feat_dim, "hidden": hidden,
    }


def _train_scan(w, *, with_optimizer: bool, elide_exchange: bool = False):
    """(runner, initial state) for the scan-delta protocol over the train
    step. ``elide_exchange=True`` swaps in a ``halo_deltas=()`` plan: the
    collectives statically vanish (pinned by test_obs's impl-'none' spy)
    while every local op keeps its shape — the interior-only variant."""
    import functools

    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan

    model, optimizer, mesh = w["model"], w["optimizer"], w["mesh"]
    plan, batch = w["plan"], w["batch"]
    if elide_exchange:
        plan = dataclasses.replace(plan, halo_deltas=())
    batch_specs = jax.tree.map(lambda _: P(GRAPH_AXIS), batch)
    plan_specs = plan_in_specs(plan)

    def shard_body(params, batch_, plan_):
        p = squeeze_plan(plan_)
        b = jax.tree.map(lambda leaf: leaf[0], batch_)

        def lf(pp):
            logits = model.apply(pp, b["x"], p)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            ll = jnp.take_along_axis(logp, b["y"][:, None], axis=1)[:, 0]
            cnt = lax.psum(b["mask"].sum(), GRAPH_AXIS)
            return -(ll * b["mask"]).sum() / jnp.maximum(cnt, 1.0)

        loss, grads = jax.value_and_grad(lf)(params)
        return grads, lax.psum(loss, GRAPH_AXIS)

    from dgraph_tpu.comm.collectives import shard_map_checks
    from dgraph_tpu.comm.mesh import GRAPH_AXIS as _GA

    grad_fn = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), batch_specs, plan_specs), out_specs=(P(), P()),
        **shard_map_checks(plan, _GA),
    )

    @functools.partial(jax.jit, static_argnames="n", donate_argnums=(0, 1))
    def steps(params, opt_state, salt, n):
        def body(carry, _):
            p, o, s = carry
            grads, loss = grad_fn(p, batch, plan)
            if with_optimizer:
                updates, o = optimizer.update(grads, o, p)
                p = optax.apply_updates(p, updates)
            else:
                # keep a live dependence on the grads so backward work
                # cannot be dead-code-eliminated out of the timing loop
                loss = loss + optax.global_norm(grads) * 1e-20
            return (p, o, s + loss * 1e-20), None

        (p, o, s), _ = lax.scan(
            body, (params, opt_state, salt), None, length=n
        )
        return p, o, s

    def run(state, n):
        p, o, s = steps(*state, n)
        float(s)  # scalar fetch: the one trustworthy completion barrier
        return (p, o, s)

    # fresh copies per program: the scan DONATES (params, opt_state), and
    # the workload's originals must survive for the next variant
    state = (
        jax.tree.map(jnp.array, w["params"]),
        jax.tree.map(jnp.array, w["opt_state"]),
        jnp.float32(0.0),
    )

    def run_in_mesh(state, n):
        with jax.set_mesh(mesh):
            return run(state, n)

    return run_in_mesh, state


def _exchange_scan(w, impl: str, num_layers: int = 2):
    """(runner, initial state) for the exchange-only variant: per scan
    iteration, one ``halo_exchange`` + ``halo_scatter_sum`` pair per layer
    at the hidden width (the width the layers exchange at), chained
    through the carry so rounds serialize instead of hoisting."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm import collectives
    from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan

    mesh, plan, hidden = w["mesh"], w["plan"], w["hidden"]
    plan_specs = plan_in_specs(plan)

    def shard_body(x, plan_):
        p = squeeze_plan(plan_)
        h = x[0]
        for _ in range(num_layers):
            buf = collectives.halo_exchange(
                h, p.halo, GRAPH_AXIS, deltas=p.halo_deltas, impl=impl,
            )
            back = collectives.halo_scatter_sum(
                buf, p.halo, p.n_src_pad, GRAPH_AXIS,
                deltas=p.halo_deltas, impl=impl,
            )
            h = h + back * 1e-6
        return h[None]

    from dgraph_tpu.comm.collectives import shard_map_checks

    sm = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(GRAPH_AXIS), plan_specs), out_specs=P(GRAPH_AXIS),
        **shard_map_checks(plan, GRAPH_AXIS),
    )

    @functools.partial(jax.jit, static_argnames="n", donate_argnums=(0,))
    def steps(x, salt, n):
        def body(carry, _):
            xx, s = carry
            # fold the carry scalar in so iterations stay data-dependent
            out = sm(xx + (s * 1e-20).astype(xx.dtype), plan)
            return (out, s + out.sum() * 1e-20), None

        (x2, s), _ = lax.scan(body, (x, salt), None, length=n)
        return x2, s

    def run(state, n):
        with jax.set_mesh(mesh):
            x, s = steps(*state, n)
        float(s)
        return (x, s)

    world = plan.world_size
    n_pad = plan.n_src_pad
    x0 = jnp.ones((world, n_pad, hidden), jnp.float32)
    return run, (x0, jnp.float32(0.0))


# ---------------------------------------------------------------------------
# the attribution record
# ---------------------------------------------------------------------------


def scan_delta_attribution(
    world_size: int = 2,
    *,
    num_nodes: int = 96,
    num_edges: int = 400,
    feat_dim: int = 8,
    hidden: int = 16,
    num_classes: int = 4,
    impls=DEFAULT_IMPLS,
    n_long: int = 6,
    reps: int = 1,
    seed: int = 0,
    fold_multichip: bool = True,
) -> dict:
    """Per-phase ``{interior, exchange, optimizer, other}`` step-time
    breakdown per halo lowering, measured with the compile-inside-scan
    protocol on the current (virtual-CPU on a wedged round) backend.
    Returns the ``kind="cpu_scan_delta"`` record bench.py attaches."""
    import jax

    from dgraph_tpu import config as _cfg
    from dgraph_tpu.tune.measure import _timed_scan_ms

    w = _build_workload(
        world_size, num_nodes, num_edges, feat_dim, hidden, num_classes, seed
    )

    def time_one(run, state):
        # warm both scan lengths before timing, THREADING the state: the
        # scans donate their inputs, so the returned buffers are the only
        # live ones. A NaN round (host jitter swallowing a sub-ms delta —
        # seen under a loaded tier-1 run) retries with a doubled scan
        # length so the per-step signal amortizes above the noise; the
        # longer scans cost one extra compile each, only on retry.
        state = run(state, 1)
        for n in (n_long, 2 * n_long, 4 * n_long):
            state = run(state, n)
            ms, state = _timed_scan_ms(run, state, n, reps=reps)
            if ms == ms:
                return ms
        return float("nan")

    saved = (_cfg.halo_impl, _cfg.tuned_halo_impl)
    by_impl = {}
    try:
        # interior-only (exchange elided) is lowering-independent: one
        # measurement, shared by every impl's breakdown. Pin all_to_all so
        # overlap routing never engages on the delta-free plan.
        _cfg.set_flags(halo_impl="all_to_all", tuned_halo_impl=None)
        run, state = _train_scan(w, with_optimizer=False, elide_exchange=True)
        t_interior = time_one(run, state)

        for impl in impls:
            _cfg.set_flags(halo_impl=impl, tuned_halo_impl=None)
            run, state = _train_scan(w, with_optimizer=True)
            t_full = time_one(run, state)
            run, state = _train_scan(w, with_optimizer=False)
            t_no_opt = time_one(run, state)
            run, state = _exchange_scan(w, impl)
            t_exchange = time_one(run, state)

            t_opt = (
                max(t_full - t_no_opt, 0.0)
                if t_full == t_full and t_no_opt == t_no_opt else float("nan")
            )
            other = (
                t_full - t_interior - t_exchange - t_opt
                if all(v == v for v in (t_full, t_interior, t_exchange, t_opt))
                else float("nan")
            )
            exposed = (
                max(t_no_opt - t_interior, 0.0)
                if t_no_opt == t_no_opt and t_interior == t_interior
                else float("nan")
            )
            by_impl[impl] = {
                "full_ms": _num(t_full),
                "no_optimizer_ms": _num(t_no_opt),
                "exchange_only_ms": _num(t_exchange),
                "phases_ms": {
                    "interior": _num(t_interior),
                    "exchange": _num(t_exchange),
                    "optimizer": _num(t_opt),
                    "other": _num(other),
                },
                "exposed_exchange_ms": _num(exposed),
            }
    finally:
        _cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1])

    rec = {
        "kind": "cpu_scan_delta",
        "tier": "cpu_scan_delta",
        "schema": SCHEMA_VERSION,
        "backend": jax.default_backend(),
        "workload": {
            "world_size": world_size, "nodes": num_nodes, "edges": num_edges,
            "feat_dim": feat_dim, "hidden": hidden,
            "num_classes": num_classes, "n_long": n_long, "reps": reps,
            "seed": seed,
        },
        "interior_only_ms": _num(t_interior),
        "by_impl": by_impl,
        "multichip_dryrun": (
            multichip_family_table() if fold_multichip else None
        ),
    }
    return rec


# ---------------------------------------------------------------------------
# CLI — what bench.py's wedged-path fallback spawns
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Config:
    """CPU scan-delta step-time attribution (``--bench_fallback`` prints
    the record bench.py attaches on wedged rounds)."""

    bench_fallback: bool = False
    world: int = 2
    nodes: int = 96
    edges: int = 400
    feat_dim: int = 8
    hidden: int = 16
    num_classes: int = 4
    n_long: int = 6
    reps: int = 1
    impls: str = ",".join(DEFAULT_IMPLS)
    seed: int = 0
    log_path: str = "logs/attribution.jsonl"
    indent: int = 0


def main(cfg: Config) -> dict:
    from dgraph_tpu.obs.health import RunHealth
    from dgraph_tpu.utils import ExperimentLog

    health = RunHealth.begin("obs.attribution")
    log = ExperimentLog(cfg.log_path, echo=False)
    try:
        out = scan_delta_attribution(
            cfg.world, num_nodes=cfg.nodes, num_edges=cfg.edges,
            feat_dim=cfg.feat_dim, hidden=cfg.hidden,
            num_classes=cfg.num_classes,
            impls=tuple(s.strip() for s in cfg.impls.split(",") if s.strip()),
            n_long=cfg.n_long, reps=cfg.reps, seed=cfg.seed,
        )
        out["run_health"] = health.finish()
        log.write(out)
        print(json.dumps(out, indent=cfg.indent or None))
        return out
    except BaseException as e:  # every exit path carries a RunHealth record
        log.write({
            "kind": "run_health",
            **health.finish(
                f"attribution failed: {type(e).__name__}: {e}",
                wedge="interrupted"
                if isinstance(e, KeyboardInterrupt) else "stage_failure",
            ),
        })
        raise


if __name__ == "__main__":
    # host-side analysis pass: never claim an accelerator (the same
    # unconditional pin dgraph_tpu.analysis.__main__ uses — jax is already
    # imported, so the config update, not the env, redirects this process)
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
