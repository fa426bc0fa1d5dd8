"""The quickest proof that the system still starts on the chip.

One process. Trains the arxiv-shape GCN (V=169 343, ~2.33 M directed edges,
F=128, hidden 256, 40 classes, 2 conv layers, symmetric norm, bf16 compute,
random data and weights from ``--seed``) through the entry points a user
calls — ``DistributedGraph.from_global`` -> ``make_graph_mesh`` ->
``Communicator.init_process_group("tpu")`` -> ``init_params`` /
``make_train_step`` / ``make_eval_step`` — over ALL devices (W=1 on one
chip, W=4 on a four-chip host) with default kernel flags, plan cache and
tuner off, and checks what comes out:

- loss finite at every step and lower at the end than at step 0;
- the traced step holds Pallas kernels (on a TPU), none in interpret mode;
- logits of the default (Pallas) path agree with the jnp path on the same
  params: bf16 within 5e-2, one f32/"highest" forward within 1e-4
  (``bench.py``'s self-check tolerances);
- with more than one device: the halo lowering is not 'none', a collective
  is in the lowered module, every plan/batch leaf holds 1/W per device, and
  step-0 loss and logits agree with a W=1 run on ``jax.devices()[:1]``.

Any failed check raises; nothing is caught. Without a TPU the script exits
non-zero before doing any work. ``--tiny-cpu`` is the explicit tiny mode
tier-1 uses to exercise this file on the CPU backend; it says so in its
output. The times printed are this run's wall clock, labelled with the
device: a smoke, not a metric.

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

# bench.py's self-check tolerances (rtol = atol)
TOL_BF16 = 5e-2
TOL_F32 = 1e-4


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def assert_close(label: str, got, ref, tol: float) -> None:
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise AssertionError(f"{label}: non-finite values")
    delta = float(np.abs(got - ref).max())
    say(f"{label}: max |delta| = {delta:.3e} (tol {tol:g}, "
        f"max |ref| = {float(np.abs(ref).max()):.3e})")
    if not np.allclose(got, ref, rtol=tol, atol=tol):
        raise AssertionError(f"{label}: exceeds rtol=atol={tol:g}")


def build_graph(size: dict, seed: int, world_size: int):
    """The arxiv-shape workload (``bench.py``'s construction) as a
    DistributedGraph. Labels are a fixed random linear function of the
    features, so ten steps have something to learn."""
    from dgraph_tpu.data import DistributedGraph
    from dgraph_tpu.data.synthetic import random_edges

    V, F = size["nodes"], size["feat"]
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((V, F), dtype=np.float32)
    y = (x @ rng.standard_normal((F, size["classes"]), dtype=np.float32)
         ).argmax(-1)
    split = rng.random(V)
    t0 = time.perf_counter()
    g = DistributedGraph.from_global(
        random_edges(V, size["edges"], seed=seed), x, y,
        {"train": split < 0.6, "val": split >= 0.8},
        world_size=world_size, add_symmetric_norm=True,
        plan_cache_dir="", tune="off",
    )
    say(f"W={world_size} graph: {g.num_edges} directed edges, n_pad="
        f"{g.plan.n_src_pad} e_pad={g.plan.e_pad} s_pad={g.plan.halo.s_pad} "
        f"halo_deltas={tuple(g.plan.halo_deltas)}; partition+plan "
        f"{time.perf_counter() - t0:.1f} s (host)")
    return g


def make_logits_fn(model, mesh):
    """(params, batch, plan) -> [W, n_pad, C] logits: the per-shard forward
    (``train.loop.model_apply``) under shard_map, as the train, eval and
    serve steps run it."""
    import jax
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm.collectives import shard_map_checks
    from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan
    from dgraph_tpu.train.loop import model_apply

    def body(params, batch, plan):
        b = jax.tree.map(lambda leaf: leaf[0], batch)
        return model_apply(model, params, b, squeeze_plan(plan))[None]

    def fwd(params, batch, plan):
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), jax.tree.map(lambda _: P(GRAPH_AXIS), batch),
                      plan_in_specs(plan)),
            out_specs=P(GRAPH_AXIS),
            **shard_map_checks(plan, GRAPH_AXIS),
        )(params, batch, plan)

    return jax.jit(fwd)


def original_order(x_sharded, g) -> np.ndarray:
    """[W, n_pad, ...] -> [V, ...] in the caller's vertex numbering."""
    from dgraph_tpu.plan import unshard_vertex_data

    rows = unshard_vertex_data(np.asarray(x_sharded), g.ren.counts)
    out = np.empty_like(rows)
    out[g.ren.inv] = rows
    return out


def pallas_census(jaxpr) -> tuple:
    """(pallas_call count, how many of them run under an interpreter)."""
    from dgraph_tpu.analysis.trace import walk_eqns

    interpreted = []

    def visit(eqn):
        if eqn.primitive.name == "pallas_call":
            interpreted.append(bool(eqn.params.get("interpret")))

    walk_eqns(jaxpr, visit)
    return len(interpreted), sum(interpreted)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--tiny-cpu", action="store_true",
                    help="tiny shapes on a non-TPU backend (tier-1 only)")
    args = ap.parse_args()

    import jax

    platform = jax.default_backend()
    if platform != "tpu" and not args.tiny_cpu:
        print(f"chip_smoke: no TPU (jax.default_backend() is {platform!r}); "
              f"this script only passes on the chip", file=sys.stderr)
        return 2

    import jax.numpy as jnp
    import optax

    from dgraph_tpu import config, native
    from dgraph_tpu.comm import Communicator, make_graph_mesh
    from dgraph_tpu.comm.collectives import resolve_plan_impl
    from dgraph_tpu.comm.mesh import GRAPH_AXIS, put_on_graph_axis
    from dgraph_tpu.data.synthetic import ARXIV_EDGES, ARXIV_NODES
    from dgraph_tpu.models import GCN
    from dgraph_tpu.train.loop import (
        init_opt_state,
        init_params,
        make_eval_step,
        make_train_step,
        masked_cross_entropy,
    )
    from dgraph_tpu.utils.compile_cache import enable_compile_cache

    on_tpu = platform == "tpu"
    size = dict(zip(
        ("nodes", "edges", "feat", "hidden", "classes"),
        (ARXIV_NODES, ARXIV_EDGES, 128, 256, 40) if not args.tiny_cpu
        else (4_096, 16_384, 32, 64, 8),
    ))
    devices = jax.devices()
    W = len(devices)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": W}
    say(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"devices={W} jax={jax.__version__}")
    if args.tiny_cpu:
        say("TINY CPU MODE: tiny shapes on a non-TPU backend; checks the "
            "code path only and says nothing about the chip")
    say(f"compile cache: {enable_compile_cache()}")
    say(f"native host library: native.available()={native.available()}")
    dev_label = f"{device['kind']} x{W}"

    # --- the normal path: graph -> mesh -> comm -> params/steps ---
    g = build_graph(size, args.seed, W)
    mesh = make_graph_mesh(ranks_per_graph=W)
    comm = Communicator.init_process_group("tpu", world_size=W)
    model = GCN(size["hidden"], size["classes"], comm=comm, num_layers=2,
                dtype=jnp.bfloat16)
    plan = put_on_graph_axis(g.plan, mesh)
    batch_tr = put_on_graph_axis(g.batch("train"), mesh)
    batch_va = put_on_graph_axis(g.batch("val"), mesh)

    params = init_params(model, mesh, plan, batch_tr, args.seed)
    params0 = jax.device_get(params)  # the step donates its params
    optimizer = optax.adam(1e-2)
    opt_state = init_opt_state(optimizer, params, mesh)
    train_step = make_train_step(model, optimizer, mesh, plan)
    eval_step = make_eval_step(model, mesh)

    # --- what the step lowers to ---
    with jax.set_mesh(mesh):
        traced = train_step.trace(params, opt_state, batch_tr, plan)
        n_pallas, n_interp = pallas_census(traced.jaxpr)
        hlo = traced.lower().as_text()
    n_custom = hlo.count("tpu_custom_call")
    n_coll = sum(hlo.count(f"stablehlo.{op}") for op in
                 ("all_to_all", "collective_permute", "all_gather"))
    halo_impl = resolve_plan_impl(g.plan, GRAPH_AXIS)
    say(f"train step: {n_pallas} pallas_call eqns ({n_interp} interpreted), "
        f"{n_custom} tpu_custom_call in the lowered module, halo lowering "
        f"{halo_impl!r}, {n_coll} exchange collectives")
    if on_tpu:
        if not n_pallas or n_interp or not n_custom:
            raise AssertionError(
                "on a TPU the default step must hold compiled Pallas kernels")
    else:
        say("not a TPU: the dispatch picks the jnp path, no Pallas expected")
    if W > 1 and (halo_impl == "none" or not n_coll):
        raise AssertionError("W > 1 but the step exchanges nothing")

    if W > 1:
        for leaf in jax.tree.leaves((plan, batch_tr, batch_va)):
            shards = leaf.addressable_shards
            if len(shards) != W or any(
                    s.data.shape != (1,) + leaf.shape[1:] for s in shards):
                raise AssertionError(
                    f"leaf {leaf.shape} is not sharded 1/W per device: "
                    f"{[s.data.shape for s in shards]}")
        say(f"placement: every plan/batch leaf holds 1/{W} per device")

    # --- train + eval ---
    losses, walls = [], []
    with jax.set_mesh(mesh):
        for step in range(args.steps):
            t0 = time.perf_counter()
            params, opt_state, m = train_step(params, opt_state, batch_tr, plan)
            jax.block_until_ready((params, opt_state, m))
            walls.append(time.perf_counter() - t0)
            losses.append(float(m["loss"]))
        t0 = time.perf_counter()
        ev = jax.block_until_ready(eval_step(params, batch_va, plan))
        eval_first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        ev = jax.block_until_ready(eval_step(params, batch_va, plan))
        eval_s = time.perf_counter() - t0
    steady = float(np.median(walls[1:])) if len(walls) > 1 else float("nan")
    say(f"train step 0 (trace + compile + run) on {dev_label}: "
        f"{walls[0]:.2f} s; compile ~ {walls[0] - steady:.2f} s")
    say(f"train step wall ms on {dev_label}, steps 1..{args.steps - 1}: "
        + " ".join(f"{w * 1e3:.1f}" for w in walls[1:]))
    say(f"eval step on {dev_label}: first call {eval_first_s:.2f} s, then "
        f"{eval_s * 1e3:.1f} ms; val loss {float(ev['loss']):.4f} "
        f"acc {float(ev['accuracy']):.4f}")
    say("train loss: " + " ".join(f"{v:.4f}" for v in losses))
    if not np.isfinite(losses + [float(ev["loss"])]).all():
        raise AssertionError("non-finite loss")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")

    # --- Pallas path vs jnp path, same params ---
    model_f32 = GCN(size["hidden"], size["classes"], comm=comm, num_layers=2,
                    dtype=None)

    def logits_pair(bf16_fn):
        """(bf16 logits, f32/'highest' logits) of the trained params. The
        kernel flags are read at trace time, so each path needs jit
        objects that have not traced under the other's flags."""
        with jax.set_mesh(mesh):
            lo = bf16_fn(params, batch_va, plan)
            with jax.default_matmul_precision("highest"):
                hi = make_logits_fn(model_f32, mesh)(params, batch_va, plan)
        return np.asarray(lo), np.asarray(hi)

    logits_fn = make_logits_fn(model, mesh)
    with jax.set_mesh(mesh):
        logits0 = np.asarray(logits_fn(params0, batch_va, plan))
    default_bf16, default_f32 = logits_pair(logits_fn)
    config.set_flags(use_pallas_scatter=False, use_pallas_fused=False)
    jnp_bf16, jnp_f32 = logits_pair(make_logits_fn(model, mesh))
    config.set_flags(use_pallas_scatter=None, use_pallas_fused=None)
    assert_close("default vs jnp path, bf16 logits", default_bf16, jnp_bf16,
                 TOL_BF16)
    assert_close("default vs jnp path, f32/highest logits", default_f32,
                 jnp_f32, TOL_F32)

    # --- W devices vs one device, step 0 ---
    if W > 1:
        g1 = build_graph(size, args.seed, 1)
        mesh1 = make_graph_mesh(ranks_per_graph=1, devices=devices[:1])
        model1 = GCN(size["hidden"], size["classes"], num_layers=2,
                     dtype=jnp.bfloat16,
                     comm=Communicator.init_process_group("tpu", world_size=1))
        with jax.set_mesh(mesh1):
            logits1 = np.asarray(make_logits_fn(model1, mesh1)(
                params0,
                put_on_graph_axis(g1.batch("val"), mesh1),
                put_on_graph_axis(g1.plan, mesh1),
            ))
        got, ref = original_order(logits0, g), original_order(logits1, g1)
        assert_close(f"W={W} vs W=1, step-0 bf16 logits", got, ref, TOL_BF16)
        loss1 = float(masked_cross_entropy(
            ref, original_order(g1.labels, g1),
            original_order(g1.masks["train"], g1), None))
        say(f"step-0 train loss: W={W} step {losses[0]:.5f}, W=1 forward "
            f"{loss1:.5f}")
        if abs(losses[0] - loss1) > 1e-2 * abs(loss1):
            raise AssertionError("W>1 and W=1 step-0 losses differ by > 1%")

    for d in devices:
        stats = d.memory_stats()
        say(f"{d}: peak_bytes_in_use="
            + (str(stats["peak_bytes_in_use"]) if stats
               and "peak_bytes_in_use" in stats else "not reported"))

    result = {"ok": True, "device": device}
    if args.tiny_cpu:
        result["tiny_cpu"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
