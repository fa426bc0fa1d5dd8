"""Hot-op microbenchmarks on the local accelerator: row gather, segment
sum (XLA vs Pallas), one-hot scatter variants.

The kernel-level companion of ``comm_benchmarks.py`` (together they mirror
the reference's ``experiments/Benchmarks`` suite, ``TestNCCL.py:23-111``),
pointed at the per-chip primitives instead of the wire.

Timing protocol (see ``bench.py``): every op is timed as an in-jit
``lax.scan`` of n iterations with a scalar fetch, reporting the delta
between two scan lengths (per-call dispatch latency cancels).

Usage:
    python experiments/kernel_benchmarks.py --num_nodes 169343 \
        --num_edges 2332486 --feat_dims 128,256 --out logs/kernels.jsonl
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from functools import partial
from typing import Optional


@dataclasses.dataclass
class Config:
    """Per-chip hot-op microbenchmarks."""

    num_nodes: int = 169_343  # ogbn-arxiv scale
    num_edges: int = 2_332_486
    feat_dims: str = "128,256"
    reps: int = 3
    n_long: int = 11
    out: Optional[str] = "logs/kernel_benchmarks.jsonl"
    pallas: bool = True  # include the Pallas sorted-segment-sum variants
    dtypes: str = "float32"  # comma list: float32,bfloat16
    # tile sweep for the Pallas kernel (grid-step overhead dominates at
    # small block_e: fewer/bigger DMAs win until VMEM pressure pushes back)
    sweep: bool = False
    sweep_block_e: str = "512,1024,2048,4096"
    sweep_block_n: str = "256,512"
    # comma list of op names to skip (resume after a device hang without
    # re-dispatching the op that hung; r4: gather_sorted_xla)
    skip_ops: str = ""


def _bench(op, arg, *, reps: int, n_long: int, label: str = "?"):
    """One op's in-jit scan timing — delegates to the shared protocol
    (``dgraph_tpu.utils.timing.timed_scan_ms``; ``salt_input`` keeps bf16
    inputs bf16). A per-op failure (e.g. a Mosaic compile crash at an
    untried width) records NaN instead of killing the remaining ops —
    during a scarce lease window every surviving row counts
    (adopt_sweep filters non-finite ms, so NaN rows cannot win a tile)."""
    import sys
    import traceback

    from dgraph_tpu.utils.timing import salt_input, timed_scan_ms

    try:
        t = timed_scan_ms(
            lambda s: op(salt_input(arg, s)), reps=reps, n_long=n_long
        )
    except Exception as e:  # noqa: BLE001
        print(f"bench op {label} raised {type(e).__name__}: "
              f"{str(e).splitlines()[0] if str(e) else ''}",
              file=sys.stderr)
        # negative limit = innermost frames (the Mosaic/pallas one that
        # names the failed lowering); a positive limit shows only _bench
        traceback.print_exc(limit=-5, file=sys.stderr)
        return float("nan")
    return t if t is not None else float("nan")  # NaN survives round()


def main(cfg: Config):
    import numpy as np
    import jax
    import jax.numpy as jnp

    from dgraph_tpu.ops import local as local_ops
    from dgraph_tpu.ops.pallas_segment import max_chunks_hint, sorted_segment_sum

    if cfg.out:
        os.makedirs(os.path.dirname(cfg.out) or ".", exist_ok=True)

    def record(**kw):
        # non-finite ms/gbps (per-op failure) become null: json.dumps
        # would emit a bare NaN token, which Python's json re-reads but
        # strict parsers (jq) reject on the streamed jsonl (ADVICE r4).
        # adopt_sweep already drops None rows.
        for k in ("ms", "gbps"):
            v = kw.get(k)
            if isinstance(v, float) and not np.isfinite(v):
                kw[k] = None
        kw["ts"] = time.time()
        line = json.dumps(kw)
        print(line)
        # stream to disk immediately: a device hang mid-sweep killed the
        # process in r4 and the buffered write-at-end lost every completed
        # measurement (only the stdout tail survived)
        if cfg.out:
            with open(cfg.out, "a") as f:
                f.write(line + "\n")

    skipped = {s.strip() for s in cfg.skip_ops.split(",") if s.strip()}
    rng = np.random.default_rng(0)
    V, E = cfg.num_nodes, cfg.num_edges
    N = ((V + 127) // 128) * 128
    E_pad = ((E + 127) // 128) * 128
    idx = jnp.asarray(rng.integers(0, V, E_pad).astype(np.int32))
    sids_np = np.sort(rng.integers(0, V, E_pad)).astype(np.int32)
    sids = jnp.asarray(sids_np)
    on_tpu = jax.default_backend() == "tpu"

    dtype_list = [
        jnp.bfloat16 if d.strip() in ("bfloat16", "bf16") else jnp.float32
        for d in cfg.dtypes.split(",")
    ]
    for F in [int(f) for f in cfg.feat_dims.split(",")]:
      for dt in dtype_list:
        b = 2 if dt == jnp.bfloat16 else 4
        dname = "bf16" if dt == jnp.bfloat16 else "f32"
        x = jnp.asarray(rng.standard_normal((N, F)), dt)
        ed = jnp.asarray(rng.standard_normal((E_pad, F)), dt)
        bench = partial(_bench, reps=cfg.reps, n_long=cfg.n_long)

        if "gather_plain" not in skipped:
            t = bench(lambda a: a[idx], x, label=f"gather_plain/{dname}/F{F}")
            record(op="gather_plain", F=F, dtype=dname, ms=round(t, 3),
                   gbps=round(E_pad * F * b / t / 1e6, 1))
        if "gather_col_split" not in skipped:
            t = bench(lambda a: local_ops.row_take(a, idx, col_block=128), x,
                      label=f"gather_col_split/{dname}/F{F}")
            record(op="gather_col_split", F=F, dtype=dname, ms=round(t, 3),
                   gbps=round(E_pad * F * b / t / 1e6, 1))
        # sorted-id gathers: the owner-side case (XLA vs the Pallas
        # transpose kernel — the A/B that decides use_pallas_gather)
        if "gather_sorted_xla" not in skipped:
            t = bench(lambda a: local_ops.row_take(a, sids, col_block=128), x,
                      label=f"gather_sorted_xla/{dname}/F{F}")
            record(op="gather_sorted_xla", F=F, dtype=dname, ms=round(t, 3),
                   gbps=round(E_pad * F * b / t / 1e6, 1))
        if cfg.pallas and on_tpu:
            from dgraph_tpu.ops.pallas_segment import (
                max_vblocks_hint,
                sorted_row_gather,
            )

            mv = max_vblocks_hint(sids_np, N)
            mc0 = max_chunks_hint(sids_np, N)
            prec0 = "default" if dt == jnp.bfloat16 else "highest"
            if "gather_sorted_pallas" not in skipped:
                t = bench(
                    lambda a: sorted_row_gather(
                        a, sids, max_vblocks=mv, scatter_mc=mc0,
                        precision=prec0,
                    ),
                    x,
                    label=f"gather_sorted_pallas/{dname}/F{F}",
                )
                record(op="gather_sorted_pallas", F=F, dtype=dname, mv=mv,
                       ms=round(t, 3),
                       gbps=round(E_pad * F * b / t / 1e6, 1))
        if "segment_sum_xla" not in skipped:
            t = bench(
                lambda a: local_ops.segment_sum(
                    a, sids, N, indices_are_sorted=True), ed,
                label=f"segment_sum_xla/{dname}/F{F}",
            )
            record(op="segment_sum_xla", F=F, dtype=dname, ms=round(t, 3),
                   gbps=round(E_pad * F * b / t / 1e6, 1))
        if cfg.pallas and on_tpu:
            if cfg.sweep:
                tiles = [
                    (int(be), int(bn))
                    for be in cfg.sweep_block_e.split(",")
                    for bn in cfg.sweep_block_n.split(",")
                ]
            else:
                tiles = [(1024, 256)]
            for be, bn in tiles:
                mc = max_chunks_hint(sids_np, N, block_e=be, block_n=bn)
                precs = ("default",) if dt == jnp.bfloat16 else ("highest", "default")
                for prec in precs:
                    # match the family name OR the full recorded op name
                    # (a user copies the latter from the jsonl/stdout)
                    if {"segment_sum_pallas",
                            f"segment_sum_pallas_{prec}"} & skipped:
                        continue
                    t = bench(
                        lambda a, prec=prec, be=be, bn=bn, mc=mc: sorted_segment_sum(
                            a, sids, N, max_chunks_per_block=mc,
                            block_e=be, block_n=bn, precision=prec,
                        ),
                        ed,
                        label=(f"segment_sum_pallas_{prec}/{dname}"
                               f"/F{F}/be{be}bn{bn}"),
                    )
                    record(op=f"segment_sum_pallas_{prec}", F=F, dtype=dname,
                           block_e=be, block_n=bn, mc=mc, ms=round(t, 3),
                           gbps=round(E_pad * F * b / t / 1e6, 1))
                # the gather kernel shares the plan's (block_e, block_n)
                # fields, so tile winners must be picked for BOTH kernels
                if cfg.sweep and "gather_sorted_pallas_sweep" not in skipped:
                    # max_vblocks_hint / sorted_row_gather / prec0 are in
                    # scope from the non-sweep gather block above (same
                    # cfg.pallas-and-on_tpu guard)
                    mv = max_vblocks_hint(sids_np, N, block_e=be, block_n=bn)
                    t = bench(
                        lambda a, be=be, bn=bn, mv=mv, mc=mc, prec0=prec0:
                        sorted_row_gather(
                            a, sids, max_vblocks=mv, block_e=be, block_n=bn,
                            scatter_mc=mc, precision=prec0),
                        x,
                        label=(f"gather_sorted_pallas_sweep/{dname}"
                               f"/F{F}/be{be}bn{bn}"),
                    )
                    record(op="gather_sorted_pallas_sweep", F=F, dtype=dname,
                           block_e=be, block_n=bn, mv=mv, ms=round(t, 3),
                           gbps=round(E_pad * F * b / t / 1e6, 1))

    # records were streamed to cfg.out by record() as they completed


if __name__ == "__main__":
    import os as _os, sys as _sys

    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
