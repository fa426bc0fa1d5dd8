"""``python -m dgraph_tpu.tune`` — the plan autotuner CLI.

Default mode searches the config space for the arxiv-shaped synthetic
workload (the bench graph: same construction, same signature), persists
the winning :class:`~dgraph_tpu.tune.record.TuningRecord` into the record
directory, and prints it as one JSON line. ``--budget 0`` (the default) is
analytic-only — pure host numpy, no device ever dialed; ``--budget N``
spends up to N seconds timing the top-K survivors on the local backend.

``--selftest`` is the compile-free tier-1 smoke: a tiny two-shard graph
goes through the full pipeline — search, record save, signature lookup,
mismatch fallback, adoption — with hard assertions, exit 0 only if all
hold.

Every exit path (success, selftest failure, crash) writes a RunHealth
record to the JSONL log, and the search trace streams there too
(``kind="tune_trace"``, one row per candidate).
"""

from __future__ import annotations

import dataclasses
import json
import tempfile


@dataclasses.dataclass
class Config:
    """Plan autotuner (``--budget 0`` = analytic-only; ``--selftest`` for
    the compile-free tier-1 smoke)."""

    selftest: bool = False
    # workload: the bench's arxiv-shaped synthetic graph unless overridden
    arxiv: bool = True
    nodes: int = 4096
    edges: int = 16384  # directed edges before symmetrization
    symmetrize: bool = True
    world: int = 1  # the bench protocol's world size
    feat_dim: int = 128
    dtype: str = "bfloat16"  # bench's default activation dtype
    # search
    budget: float = 0.0  # measured-phase seconds; 0 = analytic only
    top_k: int = 3
    methods: str = ""  # comma list; "" = full space for this world size
    pads: str = ""  # comma list; "" = default pad_multiple ladder
    max_request: int = 1024  # serve-ladder request ceiling
    seed: int = 0
    sweep_log: str = "logs/kernel_benchmarks.jsonl"
    # outputs
    out_dir: str = ""  # "" = tune.record.default_record_dir()
    log_path: str = "logs/tune.jsonl"
    indent: int = 0  # >0 pretty-prints the record


def _build_workload(cfg: Config):
    from dgraph_tpu.data.synthetic import arxiv_shaped_edges, random_edges

    if cfg.arxiv:
        return arxiv_shaped_edges(cfg.seed)
    return (
        random_edges(cfg.nodes, cfg.edges, cfg.seed, cfg.symmetrize),
        cfg.nodes,
    )


def _run_search(cfg: Config, log):
    from dgraph_tpu.tune.record import default_record_dir
    from dgraph_tpu.tune.search import search

    edge_index, num_nodes = _build_workload(cfg)
    methods = [m for m in cfg.methods.split(",") if m] or None
    pads = [int(p) for p in cfg.pads.split(",") if p] or None
    result = search(
        edge_index,
        num_nodes,
        cfg.world,
        feat_dim=cfg.feat_dim,
        dtype=cfg.dtype,
        budget_s=cfg.budget,
        top_k=cfg.top_k,
        methods=methods,
        pad_multiples=pads,
        max_request=cfg.max_request,
        seed=cfg.seed,
        sweep_log=cfg.sweep_log,
        log=log,
    )
    out_dir = cfg.out_dir or default_record_dir()
    path = result.record.save(out_dir)
    return result, path


def _selftest(cfg: Config, log) -> dict:
    """Compile-free end-to-end check of the whole subsystem."""
    from dgraph_tpu import config as _dcfg
    from dgraph_tpu.tune.record import TuningRecord, adopt_record, lookup_record
    from dgraph_tpu.tune.signature import graph_signature

    failures = []
    with tempfile.TemporaryDirectory(prefix="dgraph_tune_selftest_") as tmp:
        cfg = dataclasses.replace(
            cfg, arxiv=False, nodes=400, edges=1600, world=2, feat_dim=16,
            budget=0.0, max_request=64, out_dir=tmp, sweep_log="",
        )
        result, path = _run_search(cfg, log)
        rec = result.record

        if rec.cost["winner_us"] > rec.cost["default_us"]:
            failures.append(
                f"winner cost {rec.cost['winner_us']} exceeds default "
                f"{rec.cost['default_us']} (the default is in the space; "
                f"the minimum cannot be above it)"
            )
        if not any(t.get("phase") == "analytic" for t in result.trace):
            failures.append("no analytic trace rows emitted")

        # overlap knob coverage (all analytic — no XLA compile): every
        # priced candidate must carry the overlap-vs-serial numbers, and
        # on a 2-shard graph with interior edges the exposed overlap cost
        # strictly beats serial rounds, so the winner adopts it
        priced = [
            t for t in result.trace
            if t.get("phase") == "analytic" and "overlap_exposed_us" in t
        ]
        if not priced:
            failures.append("analytic trace rows carry no overlap pricing")
        elif not all(
            t["overlap_exposed_us"] <= t["exchange_us"] or
            t["halo_impl"] != "overlap" for t in priced
        ):
            failures.append("an overlap winner priced above its exchange")
        if rec.config.get("halo_impl") != "overlap":
            failures.append(
                f"2-shard workload with interior edges should adopt the "
                f"overlap lowering, got {rec.config.get('halo_impl')!r}"
            )

        # the adopted record must round-trip tuned_halo_impl='overlap'
        # through save -> load -> adopt (the knob is useless if the
        # persisted winner cannot re-apply it next process)
        reloaded_ov = TuningRecord.load(path)
        saved_impl = _dcfg.tuned_halo_impl
        try:
            adopt_record(reloaded_ov)
            if _dcfg.tuned_halo_impl != "overlap":
                failures.append(
                    f"adopt_record set tuned_halo_impl="
                    f"{_dcfg.tuned_halo_impl!r}, expected 'overlap'"
                )
            from dgraph_tpu.plan import resolve_halo_impl

            impl, source = resolve_halo_impl(2, (1,), overlap_available=True)
            if (impl, source) != ("overlap", "record"):
                failures.append(
                    f"resolve_halo_impl under the adopted record returned "
                    f"({impl!r}, {source!r}), expected ('overlap', 'record')"
                )
            # a plan WITHOUT the split must degrade, never half-lower
            impl_no_spec, _ = resolve_halo_impl(2, (1,), overlap_available=False)
            if impl_no_spec == "overlap":
                failures.append(
                    "resolve_halo_impl lowered 'overlap' on a plan without "
                    "the interior/boundary split"
                )
        finally:
            _dcfg.set_flags(tuned_halo_impl=saved_impl)

        # round trip: the persisted JSON reloads, validates, and is found
        # by a signature lookup
        reloaded = TuningRecord.load(path)
        if reloaded.record_id != rec.record_id:
            failures.append("record round-trip changed record_id")
        edge_index, num_nodes = _build_workload(cfg)
        sig = graph_signature(
            edge_index, num_nodes, cfg.world, dtype=cfg.dtype,
            feat_dim=cfg.feat_dim,
        )
        found = lookup_record(sig, cache_dir=tmp)
        if found is None or found.record_id != rec.record_id:
            failures.append("signature lookup missed the saved record")

        # a different workload must fall back to None, not half-adopt
        other = graph_signature(
            edge_index, num_nodes, cfg.world + 1, dtype=cfg.dtype,
            feat_dim=cfg.feat_dim,
        )
        if lookup_record(other, cache_dir=tmp) is not None:
            failures.append("mismatched signature adopted a record")

        kw = adopt_record(rec)
        if "partition_method" not in kw or "pad_multiple" not in kw:
            failures.append(f"adopt_record returned {kw}, expected build kwargs")

    return {
        "kind": "tune_selftest",
        "failures": failures,
        "record_id": rec.record_id,
        "phase": rec.phase,
        "cost": rec.cost,
    }


def main(cfg: Config) -> dict:
    from dgraph_tpu.obs.health import RunHealth
    from dgraph_tpu.utils import ExperimentLog

    health = RunHealth.begin("tune.cli")
    log = ExperimentLog(cfg.log_path, echo=False)
    try:
        if cfg.selftest:
            out = _selftest(cfg, log)
            failures = out["failures"]
            out["run_health"] = health.finish(
                "; ".join(failures) if failures else None,
                wedge="stage_failure" if failures else None,
            )
            log.write(out)
            print(json.dumps(out, indent=cfg.indent or None))
            if failures:
                raise SystemExit("tune selftest FAILED: " + "; ".join(failures))
            return out
        if cfg.budget > 0:
            # the measured phase is about to touch the backend; record the
            # topology the numbers will come from
            health.snapshot_backend()
        result, path = _run_search(cfg, log)
        out = {
            "kind": "tuning_record",
            **result.record.to_dict(),
            "path": path,
            "ranked": result.ranked,
            "measured": result.measured,
            "run_health": health.finish(),
        }
        log.write(out)
        print(json.dumps(out, indent=cfg.indent or None))
        return out
    except SystemExit:
        raise
    except BaseException as e:  # every exit path carries a RunHealth record
        log.write(
            {
                "kind": "run_health",
                **health.finish(
                    f"tune failed: {type(e).__name__}: {e}",
                    wedge="interrupted"
                    if isinstance(e, KeyboardInterrupt)
                    else "stage_failure",
                ),
            }
        )
        raise


if __name__ == "__main__":
    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
