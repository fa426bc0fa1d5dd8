"""Benchmark harness. One process, one chip. Prints ONE JSON line to
stdout with the primary metric (arxiv-scale GCN epoch time) plus roofline
context and a GraphCast reference-scale step time:

  {"metric": "arxiv_gcn_epoch_time", "value": N, "unit": "ms",
   "device": {"platform": "tpu", "kind": "...", "count": 1},
   "mfu_pct": ..., "hbm_pct": ..., "model_tflops_s": ...,
   "graphcast_step_ms": ..., "config": {...}}

Stage progress goes to stderr. Without a TPU the run fails: a number from
another backend is not this metric. ``DGRAPH_BENCH_SMOKE=1`` is the
explicit tiny mode that validates the code path on whatever backend is
there (the CPU, in tier-1); its output names that platform, says
``"smoke": true`` and carries no roofline share.

Measured quantities mirror the reference's harnesses:
- per-epoch full-graph GCN training time, avg excluding compile
  (``experiments/OGB/main.py:129-221``) on an arxiv-shaped synthetic graph
  (169 343 vertices / 2.33M directed edges / 128 features / 40 classes);
- GraphCast training step time (``microbenchmark_graphcast.py:63-247``) at
  the paper's level-6 mesh / 721x1440 ERA5 grid scale. Level 6 or the
  stage fails: the GCN number is still printed and the exit code is
  non-zero.

Roofline context (VERDICT r1 #1): model_tflops_s counts the DENSE matmul
FLOPs only (gather/scatter one-hot work is overhead, not model math);
mfu_pct is vs the device's bf16 peak and hbm_pct is the achieved fraction
of its HBM peak for the analytic minimum edge/vertex stream traffic — both
from ``DEVICE_PEAKS``, keyed by ``device_kind``; a device that is not in
the table is an error.

Timing protocol: run n epochs INSIDE one jit (lax.scan), force completion
with a scalar fetch, and report the delta between two scan lengths — per-
call dispatch latency cancels out. If a rep round yields no positive
delta, the round is retried; persistent failure reports NaN and exits
nonzero rather than a nonsense number (ADVICE r1 #3).

Before measuring, each Pallas kernel the flags select is checked on the
chip against numpy at the plans' tile sizes; a kernel that fails to
compile or to agree ends the run.

Env knobs: DGRAPH_BENCH_DTYPE (bfloat16|float32, default bfloat16),
DGRAPH_BENCH_GRAPHCAST=0 to skip stage 2, DGRAPH_BENCH_GC_LATENT /
_GC_LAYERS / _GC_LEVEL to resize it, and the kernel flags of
``dgraph_tpu/config.py``.
"""

from __future__ import annotations

import json
import os
import sys
import time

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
}


def device_peaks(device_kind: str) -> dict:
    """The peaks of the device the run is on; unknown devices raise."""
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add it to "
            f"bench.DEVICE_PEAKS with its source (known: "
            f"{sorted(DEVICE_PEAKS)})"
        )
    return DEVICE_PEAKS[device_kind]


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def _git_rev() -> str:
    """The commit every round JSON is stamped with."""
    from dgraph_tpu.obs.health import git_rev

    return git_rev()


def _ledger_ingest(out: dict) -> None:
    """Append a record to the internal ledger. Bench is the one emitter
    where the DGRAPH_LEDGER_DIR knob defaults ON; maybe_ingest swallows
    every failure — the ledger must never cost the round's JSON line."""
    from dgraph_tpu.obs import ledger

    ledger.maybe_ingest(out, source="bench", default_on=True)

def _make_runner(scan_fn):
    """(params, opt_state, salt), n -> new state; the trailing float(s)
    scalar fetch is the completion barrier."""

    def run(state, n):
        p, o, s = scan_fn(*state, n)
        float(s)
        return (p, o, s)

    return run


def _timed_scan_ms(epochs_fn, state, n_long, reps=3, max_rounds=6):
    """Median positive (long-short)/(n_long-1) delta in ms; retries noisy
    rounds, returns (ms, state) or (nan, state) if no round yields a
    positive delta."""
    deltas = []
    rounds = 0
    while len(deltas) < reps and rounds < max_rounds:
        rounds += 1
        t0 = time.perf_counter()
        state = epochs_fn(state, 1)
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        state = epochs_fn(state, n_long)
        t_long = time.perf_counter() - t0
        d = (t_long - t1) / (n_long - 1) * 1000.0
        log(f"  round {rounds}: 1-iter {t1*1000:.1f} ms, {n_long}-iter "
            f"{t_long*1000:.1f} ms -> {d:.2f} ms/iter")
        if d > 0:
            deltas.append(d)
    if not deltas:
        return float("nan"), state
    ds = sorted(deltas)
    mid = len(ds) // 2
    median = ds[mid] if len(ds) % 2 else (ds[mid - 1] + ds[mid]) / 2
    return median, state


# One dtype/precision/tolerance table for EVERY chip self-check: f32/highest
# (atomicAdd-parity path) AND bf16/default (the dtype+precision the bf16
# training VJPs actually emit — a Mosaic acc-dtype bug is invisible to the
# f32 check alone, seen r2). Resolved lazily (jnp import).
def _selfcheck_cases():
    import jax.numpy as jnp

    return [(jnp.float32, "highest", 1e-4), (jnp.bfloat16, "default", 5e-2)]


def _check_one(label: str, run, ref, tol) -> None:
    """One chip self-check case: a kernel that fails to compile raises
    from ``run()``, one that disagrees with the reference raises here."""
    import numpy as np

    got = np.asarray(run())
    if not np.allclose(got, ref, rtol=tol, atol=tol):
        raise AssertionError(
            f"self-check on chip {label}: max |got - ref| = "
            f"{float(np.abs(got - ref).max()):.3e} exceeds tol {tol:g}"
        )
    log(f"self-check on chip {label}: OK")


def pallas_selfcheck() -> None:
    """Pallas correctness check on the chip (VERDICT r1 weak #3): the Mosaic
    lowering class of bug is invisible to the interpret-mode CI tests, so
    verify the real kernel against numpy right before using it. Raises on
    a compile failure or a mismatch."""
    import numpy as np
    import jax.numpy as jnp

    from dgraph_tpu.ops.pallas_segment import max_chunks_hint, sorted_segment_sum

    rng = np.random.default_rng(7)
    E, N, F = 8192, 2048, 128
    ids = np.sort(rng.integers(0, N, E)).astype(np.int32)
    data = rng.standard_normal((E, F)).astype(np.float32)
    want = np.zeros((N, F), np.float32)
    np.add.at(want, ids, data)
    # check the exact tile configs the plans emit (a Mosaic bug can be
    # tile-size-dependent), plus the library default
    from dgraph_tpu.plan import SCATTER_BLOCK_E, SCATTER_BLOCK_N

    configs = {(512, 256), (SCATTER_BLOCK_E, SCATTER_BLOCK_N)}
    for be, bn in sorted(configs):
        for dt, prec, tol in _selfcheck_cases():
            _check_one(
                f"scatter(be={be},bn={bn},{dt.__name__})",
                lambda dt=dt, prec=prec, be=be, bn=bn: sorted_segment_sum(
                    jnp.asarray(data, dt), jnp.asarray(ids), N,
                    max_chunks_per_block=max_chunks_hint(
                        ids, N, block_e=be, block_n=bn
                    ),
                    block_e=be, block_n=bn, precision=prec,
                ).astype(jnp.float32),
                want, tol,
            )


def pallas_fused_selfcheck(check_bwd_pair: bool = True) -> None:
    """Chip check for the FUSED bias+relu scatter kernel forward and (when
    ``check_bwd_pair``) its backward KERNEL PAIR. Raises on a compile
    failure or a mismatch."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from dgraph_tpu.ops.pallas_segment import (
        max_chunks_hint,
        sorted_segment_sum_bias_relu,
    )
    from dgraph_tpu.plan import SCATTER_BLOCK_E, SCATTER_BLOCK_N

    rng = np.random.default_rng(11)
    E, N, F = 8192, 2048, 128
    ids = np.sort(rng.integers(0, N, E)).astype(np.int32)
    ids[-64:] = N + 1  # padded-edge tail
    data = rng.standard_normal((E, F)).astype(np.float32)
    bias = rng.standard_normal((N, F)).astype(np.float32)
    w = rng.uniform(0.5, 2.0, E).astype(np.float32)
    want = np.zeros((N, F), np.float32)
    wantw = np.zeros((N, F), np.float32)
    for e in range(E):
        if ids[e] >= N:
            continue
        m = np.maximum(data[e] + bias[ids[e]], 0)
        want[ids[e]] += m
        wantw[ids[e]] += w[e] * m
    be, bn = SCATTER_BLOCK_E, SCATTER_BLOCK_N
    mc = max_chunks_hint(ids, N, block_e=be, block_n=bn)
    for dt, prec, tol in _selfcheck_cases():
        for use_w, ref in [(False, want), (True, wantw)]:
            _check_one(
                f"fused-bias-relu({dt.__name__},w={use_w})",
                lambda dt=dt, prec=prec, use_w=use_w: sorted_segment_sum_bias_relu(
                    jnp.asarray(data, dt), jnp.asarray(ids),
                    jnp.asarray(bias, dt), N,
                    edge_weight=jnp.asarray(w, dt) if use_w else None,
                    max_chunks_per_block=mc, block_e=be, block_n=bn,
                    precision=prec,
                ).astype(jnp.float32),
                ref, tol,
            )
    if not check_bwd_pair:
        return
    # gradient check: the unweighted VJP runs the fused-bwd KERNEL PAIR
    # (chunk-major gd kernel + epilogue="act" d_bias reduction) when
    # gather_mv > 0 — a Mosaic miscompile there would silently corrupt
    # training, so the chip gate must cover it too. Reference grads by
    # numpy: d_data[e] = act_e * g[ids[e]]; d_bias[v] = g[v] * count_v.
    from dgraph_tpu.ops.pallas_segment import max_vblocks_hint

    mv = max_vblocks_hint(ids, N, block_e=be, block_n=bn)
    tgt = rng.standard_normal((N, F)).astype(np.float32)
    gd_want = np.zeros((E, F), np.float32)
    db_want = np.zeros((N, F), np.float32)
    for e in range(E):
        if ids[e] >= N:
            continue
        act_e = (data[e] + bias[ids[e]] > 0).astype(np.float32)
        gd_want[e] = act_e * tgt[ids[e]]
        db_want[ids[e]] += act_e
    db_want *= tgt

    def loss(d, b):
        out = sorted_segment_sum_bias_relu(
            d, jnp.asarray(ids), b, N, max_chunks_per_block=mc,
            block_e=be, block_n=bn, gather_mv=mv, precision="highest",
        )
        return (out.astype(jnp.float32) * jnp.asarray(tgt)).sum()

    def grads():
        gd, db = jax.grad(loss, argnums=(0, 1))(
            jnp.asarray(data), jnp.asarray(bias)
        )
        # one array so _check_one's single compare covers both
        return jnp.concatenate(
            [gd.astype(jnp.float32).ravel(), db.astype(jnp.float32).ravel()]
        )

    _check_one(
        "fused-bwd-kernel-pair(grads,f32)", grads,
        np.concatenate([gd_want.ravel(), db_want.ravel()]), 2e-4,
    )

    # bf16/default kernel-pair grads vs the COMPOSED backward (gather_mv=0
    # disables the pair) at the SAME bf16 rounding — an f32 reference
    # would differ by whole elements at ReLU-boundary mask flips. The
    # bf16 variant is the one bf16 training actually runs; "a Mosaic
    # acc-dtype bug is invisible to the f32 check alone" (r2).
    def grads_bf16(gmv):
        def lo(d, b):
            out = sorted_segment_sum_bias_relu(
                d, jnp.asarray(ids), b, N, max_chunks_per_block=mc,
                block_e=be, block_n=bn, gather_mv=gmv, precision="default",
            )
            return (out.astype(jnp.float32) * jnp.asarray(tgt)).sum()

        gd, db = jax.grad(lo, argnums=(0, 1))(
            jnp.asarray(data, jnp.bfloat16), jnp.asarray(bias, jnp.bfloat16)
        )
        return jnp.concatenate(
            [gd.astype(jnp.float32).ravel(), db.astype(jnp.float32).ravel()]
        )

    _check_one(
        "fused-bwd-kernel-pair(grads,bf16)", lambda: grads_bf16(mv),
        np.asarray(grads_bf16(0)), 5e-2,
    )


def pallas_gather_selfcheck() -> None:
    """Chip check for the sorted ROW-GATHER kernel. Only run when the env
    pins DGRAPH_TPU_PALLAS_GATHER=1 (the kernel is explicit-opt-in until
    on-chip A/B data exists). Raises on a compile failure or a mismatch."""
    import numpy as np
    import jax.numpy as jnp

    from dgraph_tpu.ops.pallas_segment import (
        max_chunks_hint,
        max_vblocks_hint,
        sorted_row_gather,
    )

    from dgraph_tpu.plan import SCATTER_BLOCK_E, SCATTER_BLOCK_N

    rng = np.random.default_rng(13)
    E, N, F = 8192, 2048, 128
    ids = np.sort(rng.integers(0, N, E)).astype(np.int32)
    ids[-64:] = N + 1
    x = rng.standard_normal((N, F)).astype(np.float32)
    want = np.where((ids < N)[:, None], x[np.clip(ids, 0, N - 1)], 0.0)
    # the exact tile configs the plans emit, plus the library default
    # (Mosaic bugs can be tile-size-dependent — same invariant as
    # pallas_selfcheck)
    for be, bn in sorted({(512, 256), (SCATTER_BLOCK_E, SCATTER_BLOCK_N)}):
        mv = max_vblocks_hint(ids, N, block_e=be, block_n=bn)
        mc = max_chunks_hint(ids, N, block_e=be, block_n=bn)
        for dt, prec, tol in _selfcheck_cases():
            _check_one(
                f"sorted-gather(be={be},bn={bn},{dt.__name__})",
                lambda dt=dt, prec=prec, be=be, bn=bn, mv=mv, mc=mc:
                sorted_row_gather(
                    jnp.asarray(x, dt), jnp.asarray(ids), max_vblocks=mv,
                    block_e=be, block_n=bn, scatter_mc=mc, precision=prec,
                ).astype(jnp.float32),
                want, tol,
            )


def bench_gcn(dtype_name: str, peaks: "dict | None"):
    """Arxiv-shape GCN epoch time (ms) + roofline context. ``peaks`` is the
    device's ``DEVICE_PEAKS`` row, or None in smoke mode — then no rate and
    no share of a peak is reported."""
    import functools

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    from dgraph_tpu.comm import Communicator
    from dgraph_tpu.data.synthetic import ARXIV_EDGES, ARXIV_NODES, random_edges
    from dgraph_tpu.models import GCN
    from dgraph_tpu.plan import build_edge_plan

    # ogbn-arxiv shape (V=169343, E~1.17M directed, symmetrized ~2.33M) —
    # the same construction (data.synthetic.random_edges) the tune CLI
    # signs, so `python -m dgraph_tpu.tune` records adopt here
    V, E_half, F, C, H = ARXIV_NODES, ARXIV_EDGES, 128, 40, 256
    if os.environ.get("DGRAPH_BENCH_SMOKE") == "1":  # tiny path validation
        V, E_half, F, C, H = 4_096, 16_384, 32, 8, 64
    edge_index = random_edges(V, E_half, seed=0)

    # tuning-record adoption (dgraph_tpu.tune): a persisted winner for this
    # exact workload signature overrides the hard-coded pad_multiple and
    # halo lowering; the record id rides the output JSON either way so the
    # number is attributable to its config (null = defaults)
    from dgraph_tpu.tune.record import (
        adopt_record,
        clear_adoption,
        lookup_record,
    )
    from dgraph_tpu.tune.signature import graph_signature

    pad_multiple, record_id, tuned_halo_impl = 128, None, None
    sig = graph_signature(edge_index, V, 1, dtype=dtype_name, feat_dim=F)
    rec = lookup_record(sig)
    if rec is not None:
        tuned = adopt_record(rec)
        pad_multiple = tuned.get("pad_multiple", pad_multiple)
        record_id = rec.record_id
        tuned_halo_impl = rec.config.get("halo_impl")
        log(f"tuning record {record_id} adopted "
            f"(pad_multiple={pad_multiple}, halo_impl={tuned_halo_impl})")
    else:
        clear_adoption()

    log("building plan (host)...")
    part = np.zeros(V, np.int32)  # single-chip: world size 1
    plan_np, _ = build_edge_plan(
        edge_index, part, world_size=1, edge_owner="dst",
        pad_multiple=pad_multiple,
        overlap=True if tuned_halo_impl == "overlap" else None,
    )
    # interior/boundary split of the workload (plan.py): the boundary
    # fraction bounds the halo payload, the interior fraction bounds what
    # the overlap lowering can hide it behind — reported next to the
    # adopted record so the lowering choice is auditable from the JSON
    from dgraph_tpu.plan import interior_boundary_edge_counts

    edge_split = interior_boundary_edge_counts(plan_np)
    log(f"edge split: interior {edge_split['interior_frac']:.3f} / "
        f"boundary {edge_split['boundary_frac']:.3f}")
    log("moving plan to device...")
    plan = jax.tree.map(lambda leaf: jnp.asarray(np.asarray(leaf)[0]), plan_np)
    jax.block_until_ready(jax.tree.leaves(plan))

    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
    comm = Communicator.init_process_group("single")
    model = GCN(hidden_features=H, out_features=C, comm=comm, num_layers=2, dtype=dtype)

    log("generating data on device...")
    x = jax.random.normal(jax.random.key(0), (plan_np.n_src_pad, F), jnp.float32)
    y = jax.random.randint(jax.random.key(1), (plan_np.n_src_pad,), 0, C)
    mask = (jnp.arange(plan_np.n_src_pad) < V).astype(jnp.float32)
    jax.block_until_ready(x)

    log("initializing model...")
    params = model.init(jax.random.key(2), x, plan)
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)

    @functools.partial(jax.jit, static_argnames="n", donate_argnums=(0, 1))
    def epochs(params, opt_state, salt, n):
        def lf(p):
            logits = model.apply(p, x, plan)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            ll = jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
            return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)

        def body(carry, _):
            p, o, s = carry
            loss, grads = jax.value_and_grad(lf)(p)
            updates, o = optimizer.update(grads, o, p)
            p = optax.apply_updates(p, updates)
            return (p, o, s + loss * 1e-20), None

        (p, o, s), _ = jax.lax.scan(body, (params, opt_state, salt), None, length=n)
        return p, o, s

    N_LONG = 6
    log(f"compiling (n=1 and n={N_LONG})...")
    state = (params, opt_state, jnp.float32(0.0))
    run = _make_runner(epochs)
    state = run(state, 1)
    state = run(state, N_LONG)
    log("warmup done; timing...")
    dt_ms, state = _timed_scan_ms(run, state, N_LONG)

    # --- roofline context ---
    Vp, Ep = plan_np.n_src_pad, plan_np.e_pad
    b = 2 if dtype_name == "bfloat16" else 4
    # dense model FLOPs: fwd projections (2 per conv layer) + head; x3 for
    # fwd+bwd (dgrad+wgrad)
    dense_fwd = 2 * Vp * F * H * 2 + 2 * Vp * H * H * 2 + 2 * Vp * H * C
    model_flops = 3 * dense_fwd
    # analytic minimum HBM stream traffic per epoch (each E-row tensor
    # counted once per producing/consuming op):
    #   fwd/layer: 2 gathers (write E.H + read V.H each) + 1 scatter
    #     (read E.H, write V.H)
    #   bwd/layer: 1 take (write E.H, read V.H) + 2 segment sums
    #     (read E.H, write V.H each)
    per_layer = 6 * (Ep * H + Vp * H) * b
    hbm_bytes = 2 * per_layer + 3 * (Vp * (F + H) * b)  # + input/proj streams
    # the RESOLVED lowering + deciding source (env pin > record >
    # heuristic > plan), not just the record's wish: an env-pinned or
    # heuristic-chosen lowering was previously invisible in BENCH_r*.json.
    # On this single-chip plan the truthful resolution is usually
    # ('none', 'plan'); halo_impl_env_pin records the operator's raw
    # request alongside, so a pinned-but-degraded state is still visible.
    from dgraph_tpu import config as _dcfg
    from dgraph_tpu.plan import resolve_halo_impl

    halo_impl, halo_impl_source = resolve_halo_impl(
        plan_np.world_size, plan_np.halo_deltas,
        overlap_available=plan_np.overlap is not None,
        pair_rows=getattr(plan_np, "halo_pair_rows", ()),
    )
    # the RESOLVED wire format rides the JSON the same way: which codec
    # this run's halo payloads would ship with, who decided (env > record
    # > plan > fp32 default), and the operator's raw env pin
    from dgraph_tpu.wire.spec import resolve_wire_format

    wire_format, wire_format_source = resolve_wire_format(
        plan_np.world_size, tuple(plan_np.halo_deltas),
        plan_format=getattr(plan_np, "wire_format", "fp32"),
    )
    split_info = {
        "interior_edge_frac": round(edge_split["interior_frac"], 4),
        "boundary_edge_frac": round(edge_split["boundary_frac"], 4),
        "tuned_halo_impl": tuned_halo_impl,
        "halo_impl": halo_impl,
        "halo_impl_source": halo_impl_source,
        "halo_impl_env_pin": _dcfg.halo_impl,
        "wire_format": wire_format,
        "wire_format_source": wire_format_source,
        "wire_format_env_pin": _dcfg.wire_format,
    }
    # the resolved wire format joins the ledger: operand_bytes rides
    # regress's byte-exact class, so a codec or pricing change that
    # alters what this workload ships on the wire goes RED across
    # commits (footprint prices the exchange at the resolved format)
    from dgraph_tpu.obs.footprint import plan_footprint

    _fp_ex = plan_footprint(
        plan_np, dtype_name, H
    )["collectives"]["halo_exchange"]
    _ledger_ingest({
        "kind": "wire_compile",
        "workload": {"world_size": plan_np.world_size,
                     "nodes": Vp, "hidden": H},
        "wire_format": wire_format,
        "wire_format_source": wire_format_source,
        "halo_impl": halo_impl,
        "operand_bytes": _fp_ex["operand_bytes_per_shard"],
        "compression_ratio": _fp_ex["compression_ratio"],
        "git_rev": _git_rev(),
    })
    if dt_ms != dt_ms or peaks is None:  # NaN timing or smoke mode: no
        # roofline numbers (keep JSON valid; the record id still rides
        # along — a null metric must stay attributable to the config that
        # failed to produce it)
        return dt_ms, {"tuning_record": record_id, **split_info}
    secs = dt_ms / 1e3
    tflops_s = model_flops / secs / 1e12
    gbps = hbm_bytes / secs / 1e9
    return dt_ms, {
        "model_tflops_s": round(tflops_s, 2),
        "mfu_pct": round(100 * tflops_s / peaks["bf16_tflops"], 2),
        "hbm_gbps_min": round(gbps, 1),
        "hbm_pct": round(100 * gbps / peaks["hbm_gbps"], 1),
        "tuning_record": record_id,
        **split_info,
    }


def bench_graphcast(dtype_name: str):
    """GraphCast train-step time at reference scale (level-6 mesh,
    721x1440 grid) on one chip. Plans come from the host; all feature data
    is generated on device."""
    import functools

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    from dgraph_tpu.comm import Communicator
    from dgraph_tpu.models.graphcast import GraphCast, build_graphcast_graphs

    level = int(os.environ.get("DGRAPH_BENCH_GC_LEVEL", "6"))
    latent = int(os.environ.get("DGRAPH_BENCH_GC_LATENT", "256"))
    layers = int(os.environ.get("DGRAPH_BENCH_GC_LAYERS", "16"))
    nlat, nlon, ch = 721, 1440, 73
    if os.environ.get("DGRAPH_BENCH_SMOKE") == "1":  # tiny path validation
        level, latent, layers, nlat, nlon, ch = 1, 16, 2, 19, 36, 8
    log(f"graphcast: building level-{level} graphs on host...")
    t0 = time.time()
    graphs = build_graphcast_graphs(level, nlat, nlon, 1)
    log(f"graphcast: graphs built in {time.time()-t0:.1f}s "
        f"(g2m={graphs.g2m_plan.e_pad} m2g={graphs.m2g_plan.e_pad} "
        f"mesh={graphs.mesh_plan.e_pad})")

    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
    comm = Communicator.init_process_group("single")
    model = GraphCast(
        comm=comm, latent=latent, processor_layers=layers, out_channels=ch,
        dtype=dtype,
    )

    def dev(a):
        return jnp.asarray(np.asarray(a)[0])

    statics = {
        "grid_node_static": dev(graphs.grid_node_static),
        "mesh_node_static": dev(graphs.mesh_node_static),
        "mesh_edge_static": dev(graphs.mesh_edge_static),
        "g2m_edge_static": dev(graphs.g2m_edge_static),
        "m2g_edge_static": dev(graphs.m2g_edge_static),
    }
    plans = {
        "mesh": jax.tree.map(dev, graphs.mesh_plan),
        "g2m": jax.tree.map(dev, graphs.g2m_plan),
        "m2g": jax.tree.map(dev, graphs.m2g_plan),
    }
    jax.block_until_ready(jax.tree.leaves((statics, plans)))
    log("graphcast: statics+plans on device")

    n_grid = plans["g2m"].n_src_pad
    x = jax.random.normal(jax.random.key(3), (n_grid, ch), jnp.float32)
    y = jax.random.normal(jax.random.key(4), (n_grid, ch), jnp.float32)
    gmask = dev(graphs.grid_mask)

    # init on a TINY level-1 graph: params depend only on feature dims
    # (statics are 4-wide at every level), and an eager full-scale init
    # materializes the level-6 forward's intermediates op-by-op — the OOM
    # seen in the first r2 capture happened here, not in the step itself.
    tiny = build_graphcast_graphs(1, 10, 18, 1)
    t_statics = {
        "grid_node_static": dev(tiny.grid_node_static),
        "mesh_node_static": dev(tiny.mesh_node_static),
        "mesh_edge_static": dev(tiny.mesh_edge_static),
        "g2m_edge_static": dev(tiny.g2m_edge_static),
        "m2g_edge_static": dev(tiny.m2g_edge_static),
    }
    t_plans = {
        "mesh": jax.tree.map(dev, tiny.mesh_plan),
        "g2m": jax.tree.map(dev, tiny.g2m_plan),
        "m2g": jax.tree.map(dev, tiny.m2g_plan),
    }
    x_tiny = jnp.zeros((t_plans["g2m"].n_src_pad, ch), jnp.float32)
    params = model.init(jax.random.key(5), x_tiny, t_statics, t_plans)
    opt = optax.adamw(1e-4, weight_decay=0.1)
    opt_state = opt.init(params)
    log("graphcast: params initialized; compiling step scan...")

    @functools.partial(jax.jit, static_argnames="n", donate_argnums=(0, 1))
    def steps(params, opt_state, salt, n):
        def lf(p):
            pred = model.apply(p, x, statics, plans)
            se = ((pred - y) ** 2).sum(-1) * gmask
            return se.sum() / jnp.maximum(gmask.sum(), 1.0)

        def body(carry, _):
            p, o, s = carry
            loss, grads = jax.value_and_grad(lf)(p)
            updates, o = opt.update(grads, o, p)
            p = optax.apply_updates(p, updates)
            return (p, o, s + loss * 1e-20), None

        (p, o, s), _ = jax.lax.scan(body, (params, opt_state, salt), None, length=n)
        return p, o, s

    run = _make_runner(steps)
    state = (params, opt_state, jnp.float32(0.0))
    state = run(state, 1)
    state = run(state, 4)
    log("graphcast: warmup done; timing...")
    ms, _ = _timed_scan_ms(run, state, 4)
    return ms, {"level": level, "latent": latent, "layers": layers}


def _hbm_peak_gb():
    """Cumulative peak HBM (GB) so OOM regressions show as numbers, not
    crashes (VERDICT r2 next #7). PJRT exposes no reset, so per-stage
    attribution is by ordering: read after each stage; a later stage's
    value is that stage's peak iff it exceeds the earlier ones. None
    where the backend reports no memory stats (the CPU)."""
    import jax

    stats = jax.local_devices()[0].memory_stats()
    if stats and "peak_bytes_in_use" in stats:
        return round(stats["peak_bytes_in_use"] / 1e9, 3)
    return None


def main() -> int:
    """Exit code 0 = every stage measured; 1 = no TPU (and not smoke mode),
    a NaN timing, or the GraphCast stage failed (the JSON line, with the
    GCN number, is still printed in the last two cases). A failed kernel
    self-check or GCN stage raises."""
    import traceback

    t_start = time.time()
    smoke = os.environ.get("DGRAPH_BENCH_SMOKE") == "1"
    log("importing jax...")
    import jax

    from dgraph_tpu import config as cfg
    from dgraph_tpu.obs import spans
    from dgraph_tpu.obs.health import RunHealth
    from dgraph_tpu.utils.compile_cache import enable_compile_cache

    platform = jax.default_backend()
    if platform != "tpu" and not smoke:
        log(f"no TPU: jax.default_backend() is {platform!r}. bench.py "
            f"measures the chip and does not fall back; DGRAPH_BENCH_SMOKE=1 "
            f"is the explicit tiny mode for other backends.")
        return 1
    log(f"compile cache: {enable_compile_cache()}")
    health = RunHealth.begin("bench")
    health.snapshot_backend()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device}" + (" (SMOKE: tiny shapes, not a measurement)"
                               if smoke else ""))
    peaks = None if smoke else device_peaks(dev.device_kind)

    dtype_name = os.environ.get("DGRAPH_BENCH_DTYPE", "bfloat16")
    if platform == "tpu":
        # every Pallas kernel the flags select, on the chip, before it is
        # measured; a failure raises — no kernel is switched off here
        if cfg.pallas_scatter_enabled():
            pallas_selfcheck()
        if cfg.pallas_fused_enabled():
            pallas_fused_selfcheck(
                check_bwd_pair=cfg.pallas_fused_bwd_enabled())
        if cfg.pallas_gather_enabled():
            pallas_gather_selfcheck()

    with spans.span("bench.gcn", dtype=dtype_name):
        dt_ms, roof = bench_gcn(dtype_name, peaks)
    hbm_gcn = _hbm_peak_gb()
    log(f"gcn epoch time {dt_ms:.2f} ms {roof} hbm_peak={hbm_gcn} GB")

    gc_ms, gc_info, hbm_gc, gc_error = float("nan"), {}, None, None
    gc_enabled = os.environ.get("DGRAPH_BENCH_GRAPHCAST", "1") != "0"
    if gc_enabled:
        try:
            with spans.span("bench.graphcast"):
                gc_ms, gc_info = bench_graphcast(dtype_name)
            hbm_gc = _hbm_peak_gb()
            log(f"graphcast step time {gc_ms:.2f} ms {gc_info} "
                f"hbm_peak={hbm_gc} GB")
        except Exception as e:  # the GCN number must still be printed
            gc_error = f"{type(e).__name__}: {e}"
            log("graphcast stage FAILED:\n" + traceback.format_exc())

    failed = dt_ms != dt_ms or (gc_enabled and gc_ms != gc_ms)
    out = {
        # a smoke run's tiny-shape time never carries the metric's name
        "metric": "smoke_gcn_epoch_time" if smoke else "arxiv_gcn_epoch_time",
        "value": round(dt_ms, 3) if dt_ms == dt_ms else None,
        "unit": "ms",
        "device": device,
        "smoke": smoke,  # True = tiny shapes on whatever backend is there,
        # NOT a measurement of the chip
        "git_rev": _git_rev(),
        **roof,
        "hbm_peak_gb_gcn": hbm_gcn,
        "graphcast_step_ms": round(gc_ms, 2) if gc_ms == gc_ms else None,
        "graphcast_config": gc_info,
        "graphcast_error": gc_error,
        "hbm_peak_gb_graphcast": hbm_gc,
        "config": {
            "dtype": dtype_name,
            "pallas_scatter": cfg.pallas_scatter_enabled(),
            "pallas_fused": cfg.pallas_fused_enabled(),
            "pallas_fused_bwd": cfg.pallas_fused_bwd_enabled(),
            "pallas_gather": cfg.pallas_gather_enabled(),
        },
        "wall_s": round(time.time() - t_start, 1),
        "run_health": {"bench": health.finish(
            gc_error or ("NaN timing" if failed else None),
            "stage_failure" if failed else None)},
    }
    _ledger_ingest(out)
    print(json.dumps(out))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
