"""Measured phase: time one candidate plan with bench.py's protocol.

n training steps run INSIDE one jit (``lax.scan``), completion is forced
with a scalar fetch, and the reported number is the delta between two
scan lengths — per-call dispatch latency cancels out. A round that never
yields a positive delta returns NaN, which the search's NaN guard drops
(never crowned winner).

Scope: single-shard plans (``world_size == 1`` — the bench workload).
Multi-chip candidates return NaN with a warning; their ranking stays
analytic. This is deliberate: a rank-0-only proxy measurement would time
the compute and skip the exchange — exactly the term multi-chip tuning
exists to rank.
"""

from __future__ import annotations

import logging
import time

import numpy as np

_logger = logging.getLogger("dgraph_tpu.tune")


def _timed_scan_ms(run, state, n_long: int, reps: int = 2, max_rounds: int = 4):
    """Median positive (long-short)/(n_long-1) delta in ms (bench.py's
    protocol, compacted); NaN when no round yields one."""
    deltas = []
    rounds = 0
    while len(deltas) < reps and rounds < max_rounds:
        rounds += 1
        t0 = time.perf_counter()
        state = run(state, 1)
        t_short = time.perf_counter() - t0
        t0 = time.perf_counter()
        state = run(state, n_long)
        t_long = time.perf_counter() - t0
        d = (t_long - t_short) / (n_long - 1) * 1000.0
        if d > 0:
            deltas.append(d)
    if not deltas:
        return float("nan"), state
    ds = sorted(deltas)
    mid = len(ds) // 2
    return (ds[mid] if len(ds) % 2 else (ds[mid - 1] + ds[mid]) / 2), state


def measure_plan_ms(
    plan,
    *,
    feat_dim: int,
    dtype="bfloat16",
    seed: int = 0,
    hidden: int = 64,
    num_classes: int = 32,
    n_long: int = 4,
) -> float:
    """Steps/ms of a 2-layer GCN train step over ``plan`` on one device.

    Returns NaN for multi-shard plans (see module docstring) and on
    timing-protocol failure — callers must apply the NaN guard.
    """
    if plan.world_size != 1:
        _logger.warning(
            "measured phase supports world_size == 1 only (got %d); "
            "candidate keeps its analytic rank", plan.world_size,
        )
        return float("nan")

    import functools

    import jax
    import jax.numpy as jnp
    import optax

    from dgraph_tpu.comm import Communicator
    from dgraph_tpu.models import GCN

    dname = getattr(dtype, "__name__", None) or str(dtype)
    jdtype = jnp.bfloat16 if dname in ("bfloat16", "bf16") else jnp.float32
    sq_plan = jax.tree.map(lambda leaf: jnp.asarray(np.asarray(leaf)[0]), plan)
    comm = Communicator.init_process_group("single")
    model = GCN(
        hidden_features=hidden, out_features=num_classes, comm=comm,
        num_layers=2, dtype=jdtype,
    )

    n_pad = plan.n_src_pad
    x = jax.random.normal(jax.random.key(seed), (n_pad, feat_dim), jnp.float32)
    y = jax.random.randint(jax.random.key(seed + 1), (n_pad,), 0, num_classes)
    mask = jnp.ones((n_pad,), jnp.float32)
    params = model.init(jax.random.key(seed + 2), x, sq_plan)
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)

    @functools.partial(jax.jit, static_argnames="n", donate_argnums=(0, 1))
    def steps(params, opt_state, salt, n):
        def lf(p):
            logits = model.apply(p, x, sq_plan)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            ll = jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
            return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)

        def body(carry, _):
            p, o, s = carry
            loss, grads = jax.value_and_grad(lf)(p)
            updates, o = optimizer.update(grads, o, p)
            p = optax.apply_updates(p, updates)
            return (p, o, s + loss * 1e-20), None

        (p, o, s), _ = jax.lax.scan(
            body, (params, opt_state, salt), None, length=n
        )
        return p, o, s

    def run(state, n):
        p, o, s = steps(*state, n)
        float(s)  # completion barrier: the scalar fetch waits for the scan
        return (p, o, s)

    state = (params, opt_state, jnp.float32(0.0))
    state = run(state, 1)
    state = run(state, n_long)  # both lengths compiled before timing
    ms, _ = _timed_scan_ms(run, state, n_long)
    return ms
