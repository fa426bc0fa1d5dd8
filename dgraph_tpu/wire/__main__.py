"""Wire-codec selftest CLI (compile-free, jax-free).

``python -m dgraph_tpu.wire --selftest true`` proves on fixed fixtures,
with zero XLA compiles and without importing jax:

- registry integrity: WireFormat to_dict -> JSON -> from_dict is
  identity, ``format_id`` stable across the trip, and the priced
  ``wire_row_bytes`` pins hold (fp32 F*4, bf16 F*2, fp8 F+4 — the exact
  numbers obs.footprint charges and the trace/HLO tiers pin);
- numpy reference codecs: every format round-trips within its pinned
  :func:`~dgraph_tpu.wire.spec.np_roundtrip_bound`, fp32 is the
  identity, and an all-zero fp8 wire row decodes to exactly 0.0 (the
  value ppermute hands non-receivers);
- error compensation: the residual-carry telescopes, so T steps of
  compensated encode drift by at most ONE step's quantization error
  (T-independent) where the uncompensated stream drifts linearly in T;
- the resolution ladder: env pin > tuned record > plan-attached >
  fp32 default, with precondition failures (fp8 without e4m3, unknown
  names) degrading to the next tier;
- the vacuity mutants — wrong fp8 scale, dropped compensation residual —
  must each go RED. A check that cannot fail proves nothing.

Wired as the ``wire-selftest`` pass in ``scripts/check.py``.
"""

from __future__ import annotations

import dataclasses
import json
import sys

import numpy as np

from dgraph_tpu.wire.spec import (
    WIRE_FORMATS,
    WireFormat,
    delta_skip_rows,
    np_decode,
    np_encode,
    np_encode_compensated,
    np_roundtrip_bound,
    resolve_wire_format,
)


def _selftest() -> dict:
    failures = []

    def check(cond, msg):
        if not cond:
            failures.append(msg)

    jax_preloaded = "jax" in sys.modules
    rng = np.random.default_rng(0)

    # --- registry + pricing pins ------------------------------------
    F, f32_size = 128, 4
    pins = {"fp32": F * 4, "bf16": F * 2, "fp8": F + 4}
    for name, fmt in WIRE_FORMATS.items():
        wire = json.loads(json.dumps(fmt.to_dict()))
        back = WireFormat.from_dict(wire)
        check(back == fmt, f"{name}: JSON round-trip lost structure")
        check(back.format_id == fmt.format_id,
              f"{name}: format_id unstable across round-trip")
        check(fmt.wire_row_bytes(F, f32_size) == pins[name],
              f"{name}: wire_row_bytes {fmt.wire_row_bytes(F, f32_size)} "
              f"!= pinned {pins[name]}")
    check(WIRE_FORMATS["bf16"].compression_ratio(F, f32_size) == 2.0,
          "bf16 must halve f32 wire rows (the >=45% acceptance cut)")
    # the identity format prices at the ACTIVATION itemsize: a bf16
    # program's fp32-format wire is already 2-byte rows
    check(WIRE_FORMATS["fp32"].wire_row_bytes(F, 2) == F * 2,
          "fp32 identity must price at the activation itemsize")

    # --- numpy codec round-trips ------------------------------------
    x = rng.standard_normal((6, 16)).astype(np.float32)
    x[2] *= 1e3  # large-magnitude row exercises the per-row scale
    x[4] = 0.0   # all-zero (masked) row must survive exactly
    for name in WIRE_FORMATS:
        y = np_encode(x, name)
        z = np_decode(y, name)
        bound = np_roundtrip_bound(name)
        rowmax = np.max(np.abs(x), axis=-1, keepdims=True)
        err = np.max(np.abs(z - x), axis=-1, keepdims=True)
        check(bool(np.all(err <= bound * rowmax + 1e-12)),
              f"{name}: round-trip error exceeds pinned bound {bound}")
        if name == "fp32":
            check(z is x or bool(np.array_equal(z, x)),
                  "fp32 must be the bit-identity")
        if name == "fp8":
            check(y.dtype == np.uint8 and y.shape == (6, 20),
                  "fp8 wire operand must be one [.., F+4] uint8 array")
            check(bool(np.all(np_decode(np.zeros_like(y), name) == 0.0)),
                  "all-zero fp8 wire row must decode to exactly 0.0")

    # vacuity: a codec whose decode disagrees with its encode scale must
    # blow the bound — otherwise the bound proves nothing
    y_bad = np_encode(x, "fp8", _scale_gain=2.0)
    err_bad = np.max(np.abs(np_decode(y_bad, "fp8") - x))
    check(err_bad > np_roundtrip_bound("fp8") * float(np.max(np.abs(x))),
          "vacuity: wrong-scale fp8 mutant stayed inside the bound")

    # --- compensated mode: drift is T-independent --------------------
    T = 64
    v = rng.standard_normal((3, 16)).astype(np.float32)
    for name in ("fp8", "bf16"):
        bound = np_roundtrip_bound(name)
        rowmax = float(np.max(np.abs(v)))
        acc, acc_drop = np.zeros_like(v), np.zeros_like(v)
        resid = None
        for _ in range(T):
            y, resid = np_encode_compensated(v, resid, name)
            acc += np_decode(y, name)
            y_drop, _ = np_encode_compensated(v, None, name,
                                              _drop_residual=True)
            acc_drop += np_decode(y_drop, name)
        drift = float(np.max(np.abs(acc - T * v)))
        drift_drop = float(np.max(np.abs(acc_drop - T * v)))
        check(drift <= 2.0 * bound * rowmax,
              f"{name}: compensated drift {drift:.4g} exceeds the "
              f"one-step pin {2.0 * bound * rowmax:.4g} after {T} steps")
        check(drift_drop > 4.0 * bound * rowmax,
              f"vacuity: {name} dropped-residual mutant did not drift "
              f"(compensation test proves nothing)")
        check(drift < drift_drop,
              f"{name}: compensation did not beat the uncompensated "
              f"stream")

    # --- resolution ladder ------------------------------------------
    from dgraph_tpu import config as _cfg

    saved = (_cfg.wire_format, _cfg.tuned_wire_format)
    deltas = (1, 3)
    try:
        for env, tuned, plan, fp8_ok, want in (
            ("bf16", None, "fp32", True, ("bf16", "env")),
            ("auto", "fp8", "fp32", True, ("fp8", "record")),
            ("auto", None, "bf16", True, ("bf16", "plan")),
            ("auto", None, "fp32", True, ("fp32", "default")),
            # precondition failure degrades to the next tier
            ("fp8", "bf16", "fp32", False, ("bf16", "record")),
            ("not-a-format", None, "bf16", True, ("bf16", "plan")),
        ):
            _cfg.set_flags(wire_format=env, tuned_wire_format=tuned)
            got = resolve_wire_format(4, deltas, plan_format=plan,
                                      fp8_ok=fp8_ok)
            check(got == want,
                  f"ladder(env={env}, tuned={tuned}, plan={plan}, "
                  f"fp8_ok={fp8_ok}) -> {got}, want {want}")
        # no cross-rank traffic: nothing rides a wire, format is moot
        _cfg.set_flags(wire_format="fp8", tuned_wire_format=None)
        check(resolve_wire_format(1, ()) == ("fp32", "plan"),
              "empty-deltas plan must resolve ('fp32', 'plan')")
    finally:
        _cfg.set_flags(wire_format=saved[0], tuned_wire_format=saved[1])

    # --- delta-skip accounting ---------------------------------------
    rows = ((0, 64, 1, 2), (1, 0, 1, 0), (2, 1, 0, 1), (0, 2, 1, 0))
    ds = delta_skip_rows(rows, world_size=4, s_pad=64)
    check(ds["live_rows_total"] == sum(v for r in rows for v in r),
          f"delta-skip live-row accounting wrong: {ds}")
    check(ds["a2a_rows_per_shard"] == 3 * 64
          and ds["live_rows_total"] < 4 * ds["a2a_rows_per_shard"],
          f"delta-skip must price the dense a2a baseline: {ds}")

    if not jax_preloaded:
        check("jax" not in sys.modules,
              "selftest imported jax — wire spec is not jax-free")

    return {"kind": "wire_selftest", "formats": sorted(WIRE_FORMATS),
            "failures": failures, "ok": not failures}


@dataclasses.dataclass
class Config:
    """Wire-codec CLI: ``--selftest true`` runs the compile-free codec
    + resolver invariant and vacuity-mutant suite; exit 1 on any
    failure."""

    selftest: bool = False
    indent: int = 0


def main(cfg: Config) -> None:
    if not cfg.selftest:
        print(__doc__)
        return
    out = _selftest()
    print(json.dumps(out, indent=cfg.indent or None))
    if out["failures"]:
        raise SystemExit(1)


if __name__ == "__main__":
    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
