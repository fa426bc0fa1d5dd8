"""Runtime metrics: host-side registry + the per-step aux pytree.

Two pieces with one rule — observability must cost nothing when off:

- :class:`Metrics`: a plain host-side registry of counters, gauges, and
  histograms with quantile snapshots (plan-build walltimes, cache hits,
  serve latency percentiles…).  Never traced; safe to call anywhere,
  including from the serve batcher's threads.
- :class:`StepMetrics`: the aux pytree a jitted train step returns when
  built with ``step_metrics=True`` (``train.loop.make_train_step``).  The
  flag is a Python build-time constant, so the disabled step traces to the
  byte-identical program it always had — zero device overhead and zero
  extra recompiles (pinned by tests/test_obs.py's cache-hit assertion).

One step -> one JSONL record: ``StepMetrics.record()`` coerces device
scalars to floats and stamps the schema, ``ExperimentLog.write`` appends
it.  ``StepMetrics.from_record`` round-trips the schema for readers.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

from dgraph_tpu.plan import pytree_dataclass

STEP_SCHEMA_VERSION = 1

# fields serialized into / parsed out of a step record, in schema order.
# nonfinite_skipped (0.0/1.0) is set only by guard-enabled steps
# (train.loop.make_train_step(nonfinite_guard=True)) — additive, so
# schema 1 readers are unaffected (unset fields never serialize).
_STEP_FIELDS = ("loss", "accuracy", "grad_norm", "mask_count",
                "nonfinite_skipped")


@pytree_dataclass
class StepMetrics:
    """Aux pytree threaded out of the jitted train step.

    Leaves are device scalars inside jit; ``record()`` is the host-side
    exit point. Unset fields (None) vanish from the pytree and the record
    — a model without a mask simply never reports ``mask_count``.
    """

    loss: Any = None
    accuracy: Any = None
    grad_norm: Any = None
    mask_count: Any = None
    nonfinite_skipped: Any = None  # 0.0/1.0 from the non-finite step guard
    # int32 [4], a sparse-expert LM's counts of the step
    # (parallel.expert.HELD_STATS); not a field of the step record
    moe_rows: Any = None

    # dict-style access so call sites written against the legacy metrics
    # dict (``m["loss"]``) take a StepMetrics unchanged
    def __getitem__(self, key: str):
        if key not in _STEP_FIELDS:
            raise KeyError(key)
        return getattr(self, key)

    def record(self, **extra) -> dict:
        """One JSONL-ready dict: floats only, schema-stamped. ``extra``
        carries host-side context (step index, wall_ms, lr...)."""
        out = {"kind": "step", "schema": STEP_SCHEMA_VERSION}
        for name in _STEP_FIELDS:
            v = getattr(self, name)
            if v is not None:
                out[name] = float(v)
        out.update(extra)
        return out

    @classmethod
    def from_record(cls, rec: dict) -> "StepMetrics":
        """Inverse of :meth:`record` (reader side; extras are dropped)."""
        if rec.get("kind") != "step":
            raise ValueError(f"not a step record: kind={rec.get('kind')!r}")
        return cls(**{k: rec[k] for k in _STEP_FIELDS if k in rec})


# quantiles every histogram snapshot reports: the serving SLO trio
DEFAULT_QUANTILES = (0.5, 0.95, 0.99)


def _q_label(q: float) -> str:
    """0.5 -> 'p50', 0.95 -> 'p95', 0.999 -> 'p99.9'."""
    return "p" + format(q * 100, "g")


class _Histogram:
    """Bounded-memory histogram: count/mean/min/max are exact running
    aggregates; quantiles come from a fixed-size uniform reservoir
    (Vitter's algorithm R, deterministic seed), so a serving process
    observing millions of latencies holds at most ``MAX_SAMPLES`` floats
    per histogram and a snapshot sort is O(MAX_SAMPLES log MAX_SAMPLES)
    under the registry lock. Quantiles are exact until ``MAX_SAMPLES``
    observations, then unbiased estimates."""

    MAX_SAMPLES = 4096

    __slots__ = ("count", "total", "vmin", "vmax", "values", "_rng")

    def __init__(self):
        import random

        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")
        self.values: list = []  # uniform sample of the observations
        self._rng = random.Random(0x5EED)

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        self.vmin = v if v < self.vmin else self.vmin
        self.vmax = v if v > self.vmax else self.vmax
        if len(self.values) < self.MAX_SAMPLES:
            self.values.append(v)
        else:
            j = self._rng.randrange(self.count)
            if j < self.MAX_SAMPLES:
                self.values[j] = v

    def quantile(self, q: float) -> float:
        """Empirical quantile with linear interpolation between order
        statistics (numpy's default 'linear' method, so snapshots agree
        with offline np.percentile analysis of the same JSONL). Raises
        ValueError on an empty histogram or q outside [0, 1]."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile must be in [0, 1], got {q}")
        if not self.values:
            raise ValueError("quantile of an empty histogram")
        s = sorted(self.values)
        pos = q * (len(s) - 1)
        lo = int(pos)
        frac = pos - lo
        if frac == 0.0:
            return s[lo]
        return s[lo] + (s[lo + 1] - s[lo]) * frac

    def snapshot(self, quantiles: tuple = DEFAULT_QUANTILES) -> dict:
        if not self.count:
            return {"count": 0}
        out = {
            "count": self.count,
            "mean": self.total / self.count,
            "min": self.vmin,
            "max": self.vmax,
        }
        for q in quantiles:
            out[_q_label(q)] = self.quantile(q)
        return out


class Metrics:
    """Host-side metrics registry; snapshot() is JSON-ready. Guarded by one
    lock so concurrent producers (the serve micro-batcher's worker thread +
    client submit threads) can share a registry; the per-call cost is one
    uncontended mutex, nothing on the device path."""

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        self._counters: dict = {}
        self._gauges: dict = {}
        self._histograms: dict = {}

    def counter(self, name: str, inc: float = 1.0) -> float:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + float(inc)
            return self._counters[name]

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = float(value)

    def histogram(self, name: str, value: float) -> None:
        with self._lock:
            self._histograms.setdefault(name, _Histogram()).observe(value)

    def quantile(self, name: str, q: float) -> float:
        """Quantile of a recorded histogram (KeyError if it was never
        observed) — the accessor serve latency percentiles read."""
        with self._lock:
            return self._histograms[name].quantile(q)

    def snapshot(self, quantiles: tuple = DEFAULT_QUANTILES) -> dict:
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {
                    k: h.snapshot(quantiles) for k, h in self._histograms.items()
                },
            }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()


default_registry = Metrics()


def step_record(
    metrics,
    *,
    step: int,
    wall_ms: Optional[float] = None,
    **extra,
) -> dict:
    """Record-builder that takes either a :class:`StepMetrics` or the
    legacy metrics dict, so experiments can log one schema regardless of
    which form their step returns."""
    if not isinstance(metrics, StepMetrics):
        metrics = StepMetrics(
            **{k: metrics[k] for k in _STEP_FIELDS if k in metrics}
        )
    if wall_ms is not None:
        extra["wall_ms"] = round(float(wall_ms), 3)
    return metrics.record(step=int(step), **extra)
