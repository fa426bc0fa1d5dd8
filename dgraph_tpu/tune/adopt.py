"""Kernel-sweep winner picking (the ``scripts/adopt_sweep.py`` logic,
promoted into the tuner so the search can consume measured tile data).

Reads ``logs/kernel_benchmarks.jsonl`` (the ``kernel_benchmarks.py
--sweep true`` output) and derives: the fastest (block_e, block_n) per
(kernel, dtype, F), the XLA-vs-Pallas verdicts the config defaults hang
on, and the consensus tile pair a plan should carry. The NaN-row guard
lives here: NaN ``ms`` rows mark per-op failures (a crashed compile, a
noisy timing), and ``min()`` over a dict containing NaN can crown the
crashed tile as winner (every ``x < nan`` is False), so non-finite rows
are dropped before any ranking. :func:`dgraph_tpu.tune.search.search`
applies the same guard to its measured phase.

Pure stdlib by design: ``scripts/adopt_sweep.py`` stays a thin wrapper
that loads this file directly (no package import, hence no jax import),
so the script keeps working with the TPU in any state.
"""

from __future__ import annotations

import json
from collections import defaultdict
from typing import Optional


def load_rows(path: str) -> list:
    """JSONL rows from an append-only sweep log (non-JSON lines skipped)."""
    rows = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line.startswith("{"):
                rows.append(json.loads(line))
    return rows


def deployed_scatter_op(dtype: str) -> str:
    """The scatter variant the framework actually DEPLOYS per dtype
    (ops/local.py: prec='highest' whenever dtype != bfloat16 — comparing
    the bf16-MXU 'default' variant for f32 would judge a kernel that
    never runs in f32 training)."""
    is_bf16 = dtype in ("bf16", "bfloat16")
    return (
        "segment_sum_pallas_default" if is_bf16 else "segment_sum_pallas_highest"
    )


def pick_winners(rows: list) -> dict:
    """Structured winner report from sweep rows.

    Returns ``{"winners": {(op, dtype, F): (block_e, block_n)}, "tiles":
    {key: {(be, bn): ms}}, "verdicts": [{flag, dtype, F, xla_ms,
    pallas_ms, verdict, speedup}], "consensus": (be, bn) | None,
    "consensus_votes": (n, total)}``. Latest record wins for identical
    keys (the log is append-only); non-finite ``ms`` rows are dropped
    (the NaN guard).
    """

    def key(r, *names):
        return tuple(r.get(n) for n in names)

    sweep = defaultdict(dict)  # (op, dtype, F) -> {(be, bn): ms}
    flat = {}  # (op, dtype, F) -> ms (non-sweep rows)
    for r in rows:
        ms = r.get("ms")
        if ms is None or ms != ms:  # NaN guard
            continue
        k = key(r, "op", "dtype", "F")
        if "block_e" in r:
            sweep[k][(r["block_e"], r["block_n"])] = r["ms"]
        else:
            flat[k] = r["ms"]

    winners = {k: min(tiles, key=tiles.get) for k, tiles in sweep.items()}

    verdicts = []
    for k, ms_x in sorted(flat.items()):
        op, dtype, F = k
        if op == "segment_sum_xla":
            pl_ops, flag = [deployed_scatter_op(dtype)], "use_pallas_scatter"
        elif op == "gather_sorted_xla":
            pl_ops = ["gather_sorted_pallas", "gather_sorted_pallas_sweep"]
            flag = "use_pallas_gather"
        else:
            continue
        best_p = None
        for pl_op in pl_ops:
            k_pl = (pl_op, dtype, F)
            cands = [flat[k_pl]] if k_pl in flat else []
            if k_pl in sweep:
                cands.append(min(sweep[k_pl].values()))
            for ms in cands:
                best_p = ms if best_p is None else min(best_p, ms)
        if best_p is None:
            continue
        verdicts.append(
            {
                "flag": flag,
                "dtype": dtype,
                "F": F,
                "xla_ms": ms_x,
                "pallas_ms": best_p,
                "verdict": "PALLAS" if best_p < ms_x else "XLA",
                "speedup": ms_x / best_p,
            }
        )

    # consensus tile across kernels/dtypes: the plan carries ONE
    # (scatter_block_e, scatter_block_n) pair serving BOTH kernels, so
    # each (kernel FAMILY, dtype, F) gets exactly one vote — counting
    # both precision variants of the scatter would double-weight it
    # against the gather
    def family(op, dtype):
        if op.startswith("segment_sum_pallas"):
            return ("scatter", dtype) if op == deployed_scatter_op(dtype) else None
        if op.startswith("gather_sorted_pallas"):
            return ("gather", dtype)
        return None

    votes = defaultdict(int)
    for (op, dtype, F), best in winners.items():
        if family(op, dtype) is None:
            continue
        votes[best] += 1
    consensus, n_votes = None, (0, 0)
    if votes:
        consensus, n = max(votes.items(), key=lambda kv: kv[1])
        n_votes = (n, sum(votes.values()))

    return {
        "winners": winners,
        "tiles": dict(sweep),
        "verdicts": verdicts,
        "consensus": consensus,
        "consensus_votes": n_votes,
    }


def sweep_report(path: str = "logs/kernel_benchmarks.jsonl") -> Optional[dict]:
    """pick_winners over a log file; None when the log is missing or empty
    (the search treats that as 'no measured kernel data')."""
    try:
        rows = load_rows(path)
    except OSError:
        return None
    if not rows:
        return None
    return pick_winners(rows)


def main(path: str = "logs/kernel_benchmarks.jsonl") -> None:
    """Print the human report (byte-compatible with the historical
    ``scripts/adopt_sweep.py`` workflow)."""
    rows = load_rows(path)
    if not rows:
        raise SystemExit(f"no records in {path}")
    report = pick_winners(rows)

    print("== tile winners (lowest ms) ==")
    for k in sorted(report["winners"]):
        best = report["winners"][k]
        tiles = report["tiles"][k]
        ranked = sorted(tiles.items(), key=lambda kv: kv[1])
        line = ", ".join(f"{be}x{bn}={ms:.3f}" for (be, bn), ms in ranked[:4])
        print(
            f"{k[0]} [{k[1]} F={k[2]}]: WINNER block_e={best[0]} "
            f"block_n={best[1]}  ({line})"
        )

    print("\n== XLA vs Pallas verdicts (deployed precision per dtype) ==")
    for v in report["verdicts"]:
        print(
            f"{v['flag']} [{v['dtype']} F={v['F']}]: xla={v['xla_ms']:.3f} "
            f"pallas={v['pallas_ms']:.3f} -> {v['verdict']} "
            f"({v['speedup']:.2f}x)"
        )

    if report["consensus"] is not None:
        be, bn = report["consensus"]
        n, total = report["consensus_votes"]
        print(
            f"\n== consensus: block_e={be} block_n={bn} "
            f"({n}/{total} family votes) =="
        )
        print(
            "adopt in: dgraph_tpu/plan.py (scatter_block_e/_n defaults) + "
            "PLAN_FORMAT_VERSION bump if changed"
        )
