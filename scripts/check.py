#!/usr/bin/env python
"""One-shot static-analysis gate: lint + trace audit + selftest.

Runs the analysis CLI in subprocesses (each pinned to the virtual-CPU
backend — this script never dials an accelerator and works on a machine
with no chip at all) and exits nonzero if ANY pass fails:

    python scripts/check.py            # lint + audit + analysis selftest
    python scripts/check.py --all      # also the chaos/tune/serve selftests
    python scripts/check.py --jobs 4   # fan the independent selftest
                                       # subprocesses out 4 wide (default
                                       # stays serial)

Intended as the pre-merge gate and as the cheap first half of a bench
round: everything here is compile-free (abstract tracing only), so a full
run is ~30 s on a laptop CPU.  This file stays jax-free on purpose — it
must be able to report a broken environment rather than hang in it.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PASSES = [
    # default analysis = lint + ALL audit tiers (jaxpr trace, lowered
    # StableHLO, cross-rank SPMD, host) on the canonical workload
    ("analysis", [sys.executable, "-m", "dgraph_tpu.analysis"]),
    ("analysis-selftest",
     [sys.executable, "-m", "dgraph_tpu.analysis", "--selftest", "true"]),
    # host-side concurrency & durability auditor: guarded-field/lock
    # discipline, lock-order cycles, atomic durable writes,
    # pointer-flip-last commits, chaos-registry coverage — stdlib ast,
    # zero compiles by construction (the vacuity mutants must go RED)
    ("host-auditor-selftest",
     [sys.executable, "-m", "dgraph_tpu.analysis.host",
      "--selftest", "true"]),
    ("spans-selftest",
     [sys.executable, "-m", "dgraph_tpu.obs.spans", "--selftest", "true"]),
    # sharded plan artifacts (cache format v8): manifest/shard integrity,
    # writer resume, memory budget, chaos points — pure numpy+stdlib IO
    ("plan-shards-selftest",
     [sys.executable, "-m", "dgraph_tpu.plan_shards", "--selftest", "true"]),
    # elastic world membership: heartbeat/lease liveness, barriers,
    # rendezvous, straggler/loss events — pure stdlib, fake-clock driven
    ("membership-selftest",
     [sys.executable, "-m", "dgraph_tpu.comm.membership",
      "--selftest", "true"]),
    # cross-rank SPMD divergence auditor standalone: per-rank lowered-
    # module identity + collective issue order on 2/4-shard worlds and a
    # real shrink transition, plus the seeded-divergence vacuity mutants
    # — lower-only, zero XLA compiles
    ("spmd-selftest",
     [sys.executable, "-m", "dgraph_tpu.analysis.spmd",
      "--selftest", "true"]),
    # perf-trajectory drift sentinel: the six seeded-drift vacuity
    # mutants (inflated wire bytes, slowed scan-delta, fattened p99,
    # dropped fallback tier, drifted wire-format bytes, drifted grown
    # world) must each go RED and the clean fixture ledger must gate
    # GREEN — pure stdlib, zero compiles
    ("regress-selftest",
     [sys.executable, "-m", "dgraph_tpu.obs.regress",
      "--selftest", "true"]),
    # grow-to-fit transition smoke: join rendezvous -> background W+k
    # re-plan -> reshard -> atomic adoption on a tiny fixture run, plus
    # the two subprocess sigterm pins (commit boundary AND mid-shard
    # stream must both leave world.json on a complete generation) —
    # compile-free, fake-clock driven
    ("grow-selftest",
     [sys.executable, "-m", "dgraph_tpu.train.grow",
      "--selftest", "true"]),
    # wire codec layer: registry byte pins, numpy round-trip bounds per
    # format, the wrong-scale/dropped-row vacuity mutants, the resolver
    # ladder, the delta-skip accounting, and the jax-free guard —
    # pure stdlib + numpy, zero compiles
    ("wire-selftest",
     [sys.executable, "-m", "dgraph_tpu.wire", "--selftest", "true"]),
]

EXTRA_SELFTESTS = [
    ("chaos-selftest",
     [sys.executable, "-m", "dgraph_tpu.chaos", "--selftest", "true"]),
    ("tune-selftest",
     [sys.executable, "-m", "dgraph_tpu.tune", "--selftest", "true"]),
    ("serve-selftest",
     [sys.executable, "-m", "dgraph_tpu.serve", "--selftest", "true"]),
]


def run_pass(name: str, argv: list, timeout: float) -> dict:
    env = dict(os.environ)
    # hard assignment, not setdefault: every pass is a host-side static
    # check, and an ambient JAX_PLATFORMS=tpu (a TPU VM's default) would
    # have each of them claim the chip
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    try:
        proc = subprocess.run(
            argv, cwd=REPO, env=env, capture_output=True, text=True,
            timeout=timeout,
        )
        rc = proc.returncode
        lines = (proc.stdout or "").strip().splitlines()
        last = lines[-1] if lines else ""
        try:
            parsed = json.loads(last)
        except ValueError:
            parsed = None
        detail = (
            (parsed or {}).get("failures")
            or (proc.stderr or "").strip().splitlines()[-1:]
            if rc else None
        )
    except subprocess.TimeoutExpired:
        rc, detail = 124, [f"timed out after {timeout}s"]
    return {"pass": name, "rc": rc, "ok": rc == 0, "detail": detail}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--all", action="store_true",
                    help="also run the chaos/tune/serve CLI selftests")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per-pass timeout in seconds")
    ap.add_argument("--jobs", type=int, default=1,
                    help="run up to N selftest subprocesses concurrently "
                         "(every pass is an independent subprocess; the "
                         "serial default keeps tier-1 timing unchanged)")
    args = ap.parse_args()

    passes = PASSES + (EXTRA_SELFTESTS if args.all else [])
    results = []
    # the passes are independent subprocesses by construction — fan them
    # out --jobs wide (max_workers=1 reproduces the serial gate exactly),
    # PRINTING in submission order so logs stay stable either way
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=max(1, args.jobs)) as pool:
        futures = [
            (name, argv, pool.submit(run_pass, name, argv, args.timeout))
            for name, argv in passes
        ]
        for name, argv, fut in futures:
            print(f"[check] {name}: {' '.join(argv[1:])}", flush=True)
            res = fut.result()
            print(f"[check] {name}: {'OK' if res['ok'] else 'FAILED'}"
                  + (f" — {res['detail']}" if not res["ok"] else ""),
                  flush=True)
            results.append(res)
    ok = all(r["ok"] for r in results)
    print(json.dumps({"kind": "check_report", "ok": ok, "passes": results}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
