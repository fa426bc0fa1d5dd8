"""Versioned, persisted tuning records and their adoption rules.

A :class:`TuningRecord` is the durable output of one autotuner run: the
winning configuration for one workload signature, plus enough cost context
to audit *why* it won. Records are single JSON files named
``tune_<signature_key>.json`` inside the plan-cache directory (the same
directory :func:`~dgraph_tpu.train.checkpoint.cached_edge_plan` uses), so
the artifacts that must travel together — the cached plan and the config
that built it — live together.

Adoption rules (implemented by :func:`lookup_record`):

- env ``DGRAPH_TUNE_RECORD=<path>`` pins one record file unconditionally
  (a signature mismatch is warned about, not rejected — pinning exists for
  exactly the "I know better" case);
- env ``DGRAPH_TUNE_RECORD=off`` (or ``0`` / ``none``) disables adoption;
- otherwise the caller's plan-cache dir, then :func:`default_record_dir`
  (env ``DGRAPH_TUNE_DIR``, default ``cache/plans``), are probed for a
  record whose stored signature matches field-by-field. No match -> the
  hard-coded defaults, exactly as before the tuner existed.

:func:`adopt_record` applies the runtime-scoped knobs (the tuned halo
lowering, via :mod:`dgraph_tpu.config` so ``comm.collectives`` and
``obs.footprint`` both see it) and returns the build-scoped kwargs
(partition method, pad_multiple) for the caller to pass explicitly —
adoption never mutates plan-builder module state behind the caller's back.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Optional

from dgraph_tpu.tune.signature import signature_key, signatures_match

_logger = logging.getLogger("dgraph_tpu.tune")

RECORD_SCHEMA_VERSION = 1

# config keys a record may carry; "serve" is a nested dict (ladder geometry)
_BUILD_KEYS = ("partition_method", "pad_multiple")
_KNOWN_CONFIG_KEYS = _BUILD_KEYS + (
    "edge_owner",
    "halo_impl",
    "wire_format",
    "use_pallas_scatter",
    "scatter_block_e",
    "scatter_block_n",
    "serve",
)

ENV_RECORD = "DGRAPH_TUNE_RECORD"
ENV_DIR = "DGRAPH_TUNE_DIR"
_DISABLE_VALUES = ("", "0", "off", "none", "disabled", "false")


def default_record_dir() -> str:
    """Where records land when no plan-cache dir is in play: env
    ``DGRAPH_TUNE_DIR``, else the repo-conventional ``cache/plans``."""
    return os.environ.get(ENV_DIR) or os.path.join("cache", "plans")


def record_path(directory: str, sig: dict) -> str:
    return os.path.join(directory, f"tune_{signature_key(sig)}.json")


@dataclasses.dataclass
class TuningRecord:
    """One workload's winning configuration, JSON round-trippable."""

    record_id: str
    signature: dict
    config: dict
    cost: dict
    phase: str  # 'analytic' | 'measured'
    created_at: str = ""
    schema: int = RECORD_SCHEMA_VERSION

    @classmethod
    def create(
        cls, signature: dict, config: dict, cost: dict, phase: str
    ) -> "TuningRecord":
        rid = f"tune-{signature_key(signature)}-v{RECORD_SCHEMA_VERSION}"
        rec = cls(
            record_id=rid,
            signature=dict(signature),
            config=dict(config),
            cost=dict(cost),
            phase=phase,
            created_at=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        )
        rec.validate()
        return rec

    def validate(self) -> None:
        """Structural validation; raises ValueError naming the defect (a
        hand-edited or truncated record must fail adoption loudly, not
        half-apply)."""
        errors = []
        if self.schema != RECORD_SCHEMA_VERSION:
            errors.append(
                f"schema {self.schema} != supported {RECORD_SCHEMA_VERSION}"
            )
        if not isinstance(self.signature, dict) or "degree_digest" not in self.signature:
            errors.append("signature missing or lacks degree_digest")
        if self.phase not in ("analytic", "measured"):
            errors.append(f"phase {self.phase!r} not analytic|measured")
        if not isinstance(self.config, dict) or not self.config:
            errors.append("config empty")
        else:
            unknown = set(self.config) - set(_KNOWN_CONFIG_KEYS)
            if unknown:
                errors.append(f"unknown config keys {sorted(unknown)}")
            pm = self.config.get("pad_multiple")
            if pm is not None and (not isinstance(pm, int) or pm < 1):
                errors.append(f"pad_multiple {pm!r} not a positive int")
            impl = self.config.get("halo_impl")
            if impl is not None:
                from dgraph_tpu.plan import HALO_IMPLS

                known = ("none",) + HALO_IMPLS
                if impl not in known:
                    errors.append(
                        f"halo_impl {impl!r} unknown (known: {known})"
                    )
            wf = self.config.get("wire_format")
            if wf is not None:
                from dgraph_tpu.wire.spec import WIRE_FORMAT_NAMES

                if wf not in WIRE_FORMAT_NAMES:
                    errors.append(
                        f"wire_format {wf!r} unknown "
                        f"(known: {WIRE_FORMAT_NAMES})"
                    )
            serve = self.config.get("serve")
            if serve is not None:
                # the serve CLI indexes these directly; a partial dict must
                # fail HERE (load/validate time), not as a KeyError deep in
                # serving startup
                if not isinstance(serve, dict) or not (
                    all(
                        isinstance(serve.get(k), int)
                        and not isinstance(serve.get(k), bool)
                        for k in ("min_bucket", "max_bucket")
                    )
                    and isinstance(serve.get("growth"), (int, float))
                ):
                    errors.append(
                        "serve config must carry int min_bucket/max_bucket "
                        f"and numeric growth, got {serve!r}"
                    )
        if not isinstance(self.cost, dict) or "winner_us" not in self.cost:
            errors.append("cost missing winner_us")
        if errors:
            raise ValueError("invalid TuningRecord: " + "; ".join(errors))

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TuningRecord":
        known = {f.name for f in dataclasses.fields(cls)}
        rec = cls(**{k: v for k, v in d.items() if k in known})
        rec.validate()
        return rec

    def save(self, directory: str) -> str:
        """Atomic durable write to ``directory``; returns the path.

        Routed through :func:`~dgraph_tpu.plan_shards.atomic_write_json`
        (fsync before the rename): a tuning record silently truncated by
        a host crash would otherwise be *adopted* as a corrupt-but-named
        config on the next run (``analysis.host``'s
        ``host-durable-write`` rule pins the routing)."""
        from dgraph_tpu.plan_shards import atomic_write_json

        os.makedirs(directory, exist_ok=True)
        path = record_path(directory, self.signature)
        atomic_write_json(path, self.to_dict())
        return path

    @classmethod
    def load(cls, path: str) -> "TuningRecord":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def lookup_record(sig: dict, cache_dir: str = "") -> Optional[TuningRecord]:
    """Resolve the record to adopt for ``sig`` under the adoption rules
    above. Returns None when adoption is disabled, nothing matches, or a
    candidate file is unreadable/mismatched (logged, never raised — a
    corrupt record degrades to the defaults, not a crash)."""
    pin = os.environ.get(ENV_RECORD)
    if pin is not None:
        if pin.strip().lower() in _DISABLE_VALUES:
            return None
        try:
            rec = TuningRecord.load(pin)
        except (OSError, ValueError, KeyError, TypeError) as e:
            _logger.warning(
                "%s=%s unreadable (%s: %s); tuning disabled for this run",
                ENV_RECORD, pin, type(e).__name__, e,
            )
            return None
        if not signatures_match(rec.signature, sig):
            _logger.warning(
                "pinned tuning record %s was tuned for a different workload "
                "(signature mismatch); adopting anyway because %s pins it",
                rec.record_id, ENV_RECORD,
            )
        return rec
    for d in dict.fromkeys((cache_dir or "", default_record_dir())):
        if not d:
            continue
        path = record_path(d, sig)
        if not os.path.exists(path):
            continue
        try:
            rec = TuningRecord.load(path)
        except (OSError, ValueError, KeyError, TypeError) as e:
            _logger.warning(
                "tuning record %s unreadable (%s: %s); ignoring",
                path, type(e).__name__, e,
            )
            continue
        if not signatures_match(rec.signature, sig):
            # filename collision or a hand-edit: the stored signature is
            # authoritative, and it says this record is for another graph
            _logger.warning(
                "tuning record %s signature does not match this workload; "
                "ignoring", path,
            )
            continue
        return rec
    return None


def clear_adoption() -> None:
    """Reset the process-global tuned flags to the no-record state.

    Adoption state is process-global (``config.tuned_halo_impl`` /
    ``config.tuned_wire_format`` / ``config.tuning_record_id``); a
    consumer that looked up a record and found NONE must call this so a
    previously adopted graph's halo lowering (or wire codec) cannot
    silently leak onto an untuned one built later in the same
    process."""
    from dgraph_tpu import config as _cfg

    _cfg.set_flags(
        tuned_halo_impl=None, tuned_wire_format=None, tuning_record_id=None
    )


def adopt_record(rec: TuningRecord) -> dict:
    """Apply runtime-scoped knobs and return build-scoped kwargs.

    Sets ``dgraph_tpu.config.tuned_halo_impl`` (consulted by the halo
    lowering resolver between the env pin and the heuristic) and
    ``config.tuning_record_id`` (process-level attribution for consumers
    without a graph handle), then returns ``{partition_method,
    pad_multiple}`` (the keys present in the record) for the caller to
    feed into the plan build. The flags describe the MOST RECENT adoption
    decision; lookup misses must go through :func:`clear_adoption`.
    """
    from dgraph_tpu import config as _cfg

    from dgraph_tpu.plan import HALO_IMPLS

    impl = rec.config.get("halo_impl")
    _cfg.set_flags(tuned_halo_impl=impl if impl in HALO_IMPLS else None)
    # the tuned wire format rides the 'record' tier of wire.spec.
    # resolve_wire_format; an fp32 winner clears the flag (identity is
    # the default, not an adoption)
    wf = rec.config.get("wire_format")
    _cfg.set_flags(
        tuned_wire_format=wf if wf not in (None, "fp32") else None
    )
    _cfg.set_flags(tuning_record_id=rec.record_id)
    _logger.info(
        "adopted tuning record %s (phase=%s): %s",
        rec.record_id, rec.phase,
        {k: v for k, v in rec.config.items() if k != "serve"},
    )
    # longitudinal trajectory: each adoption joins the perf ledger when
    # DGRAPH_LEDGER_DIR is set (off by default; maybe_ingest swallows
    # every failure — adoption must never break on observability)
    from dgraph_tpu.obs.ledger import maybe_ingest

    maybe_ingest(
        {"kind": "tune_record", **rec.to_dict()},
        source="tune.adopt", default_on=False,
    )
    return {k: rec.config[k] for k in _BUILD_KEYS if k in rec.config}
