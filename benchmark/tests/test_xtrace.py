"""The reduction from a trace to numbers: interval arithmetic on hand-made
intervals, and the reducers on two small traces recorded on the chip (PR 24):
``data/gcn_w1_cut.trace.json.gz``, two train and two eval steps of
``gcn_arxiv.w1`` cut to the operations of 0.2 ms and more, and
``data/gcn_w4_cut.trace.json.gz``, two train steps of the same graph on four
chips cut to 0.3 ms and more plus everything under the halo scopes."""

import gzip
import json
import os
import re

import pytest

from benchmark import xtrace
from benchmark.reducers import exposed, host_span, idle, scope_rest, scope_time

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_gaps_and_uncovered_by_hand():
    busy = xtrace.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert busy == [(0, 3), (5, 6)]
    assert xtrace.total(busy) == 4
    assert xtrace.gaps(busy, -1, 8) == [(-1, 0), (3, 5), (6, 8)]
    assert xtrace.clip(busy, 2, 5.5) == [(2, 3), (5, 5.5)]
    # a collective over [2, 6]; compute covers [0, 3] and [5, 6]: 2 s exposed
    assert xtrace.uncovered([(2, 6)], busy) == 2


def test_gap_goes_to_the_host_span_that_covers_most_of_it():
    spans = [xtrace.Span("step_dispatch", 0.0, 1.0), xtrace.Span("block", 1.0, 3.0)]
    assert xtrace.attribute((0.5, 1.2), spans) == "step_dispatch"
    assert xtrace.attribute((0.9, 2.0), spans) == "block"
    assert xtrace.attribute((5.0, 6.0), spans) == "host_other"


def synthetic(n_dev=2):
    """Two devices, one phase of two 10 s steps. Per step and device: a 4 s
    gather, then a 3 s all-to-all of which the last second runs beside a 2 s
    matmul, then 2 s idle."""
    devices, host = {}, [xtrace.Span("bench_phase.train", 0.0, 20.0)]
    for k in range(2):
        t = 10.0 * k
        host += [xtrace.Span("bench_step.train", t, 10.0),
                 xtrace.Span("step_dispatch", t, 0.5),
                 xtrace.Span("block", t + 0.5, 9.5)]
        for d in range(n_dev):
            devices.setdefault(f"/device:TPU:{d}", []).extend([
                xtrace.Op("fusion.1", "jit(step)/dgraph.local_take/gather:",
                          "custom fusion", t, 4.0),
                xtrace.Op("all-to-all.1", "jit(step)/dgraph.halo_exchange/all_to_all:",
                          "all-to-all", t + 4.0, 3.0),
                xtrace.Op("fusion.2", "jit(step)/Dense_0/dot_general:",
                          "convolution fusion", t + 6.0, 2.0)])
    return xtrace.RunRecord(
        trace=xtrace.assemble(devices, host), spans={"plan_build_s": 1.5},
        info={}, counts={"train": 2}, step_times={}, device_kind="TPU v5 lite",
        say=lambda m: None)


def test_reducers_on_a_synthetic_two_device_trace():
    run = synthetic()
    halo = {"phase": "train", "match": ["dgraph\\.halo_", "all-to-all"]}
    gather = {"phase": "train", "match": ["dgraph\\.local_take"],
              "unless": ["dgraph\\.halo_"]}
    assert scope_time.reduce(run, halo) == pytest.approx(3000.0)
    assert scope_time.reduce(run, gather) == pytest.approx(4000.0)
    assert exposed.reduce(run, halo) == pytest.approx(2000.0)
    assert idle.reduce(run, {"phase": "train"}) == pytest.approx(20.0)
    assert host_span.reduce(run, {"phase": "train", "span": "step_dispatch"}) \
        == pytest.approx(500.0)
    acc = xtrace.account(run.trace, "train")
    assert acc["step_ms"] == pytest.approx(10000.0)
    assert acc["ops_ms"] == pytest.approx(9000.0)
    assert acc["idle_ms"] == pytest.approx(2000.0)
    assert acc["remainder_ms"] == pytest.approx(-1000.0)  # the overlap
    assert run.trace.busy_s == pytest.approx(16.0)
    assert run.trace.window_s == pytest.approx(20.0)
    gaps = xtrace.breakdown(run.trace)["idle_gaps"]
    assert gaps[0][0] == "block" and gaps[0][1] == pytest.approx(2.0)
    assert scope_time.reduce(run, {"phase": "eval", "match": ["x"]}) is None


def recorded():
    tr = xtrace.load_file(os.path.join(DATA, "gcn_w1_cut.trace.json.gz"))
    return xtrace.RunRecord(
        trace=tr, spans={}, info={}, counts={}, step_times={},
        device_kind="TPU v5 lite", say=lambda m: None)


def test_recorded_trace_per_scope_sums_match_a_plain_count():
    run = recorded()
    with gzip.open(os.path.join(DATA, "gcn_w1_cut.trace.json.gz"), "rt") as f:
        events = [e for e in json.load(f)["traceEvents"]
                  if e.get("ph") == "X" and e["pid"] == 3]
    steps = run.trace.phases["train"]["steps"]
    assert len(steps) == 2 and len(run.trace.phases["eval"]["steps"]) == 2
    boundary = run.trace.phases["eval"]["steps"][0].start

    def plain(pattern, train=True):
        # eval's operations start after train's last host span ends
        return sum(e["dur"] for e in events
                   if re.search(pattern, e["args"].get("tf_op", ""))
                   and (e["ts"] * 1e-6 < boundary - 0.002) == train) / 2 / 1e3

    seg = {"phase": "train", "match": ["dgraph\\.scatter_"]}
    gat = {"phase": "train", "match": ["dgraph\\.local_take"],
           "unless": ["dgraph\\.scatter_"]}
    assert scope_time.reduce(run, seg) == pytest.approx(
        plain(r"dgraph\.scatter_"), rel=1e-9)
    assert scope_time.reduce(run, gat) == pytest.approx(
        plain(r"dgraph\.local_take"), rel=1e-9)
    # the cut keeps the operations of 0.2 ms and more: nearly all of the step
    acc = xtrace.account(run.trace, "train")
    assert 425 < acc["ops_ms"] < acc["step_ms"] < 436
    assert acc["remainder_ms"] == pytest.approx(0.0, abs=1e-6)
    assert 0 < idle.reduce(run, {"phase": "train"}) < 2.0
    top = xtrace.breakdown(run.trace)["device_ops"][0]
    assert "gather" in top[0] and 0.010 < top[1] < 0.030


def test_recorded_four_chip_trace_halo_time_and_its_exposed_part():
    tr = xtrace.load_file(os.path.join(DATA, "gcn_w4_cut.trace.json.gz"))
    run = xtrace.RunRecord(trace=tr, spans={}, info={}, counts={},
                           step_times={}, device_kind="TPU v5 lite",
                           say=lambda m: None)
    assert len(tr.devices) == 4 and len(tr.phases["train"]["steps"]) == 2
    with open(os.path.join(os.path.dirname(DATA), os.pardir, "layer_metrics",
                           "halo_ms.train.json")) as f:
        params = json.load(f)["params"]
    with gzip.open(os.path.join(DATA, "gcn_w4_cut.trace.json.gz"), "rt") as f:
        events = json.load(f)["traceEvents"]
    plain = sum(e["dur"] for e in events if e.get("ph") == "X" and (
        "dgraph.halo_" in e.get("args", {}).get("tf_op", "")
        or "all-to-all" in e["name"])) / 4 / 2 / 1e3
    halo = scope_time.reduce(run, params)
    assert halo == pytest.approx(plain, rel=1e-9)
    assert 13.0 < halo < 16.0  # 14.45 ms in the full trace (PERF.md)
    # operations on one TPU core run one after another: all of it is exposed
    assert exposed.reduce(run, params) == pytest.approx(halo, rel=1e-6)
    # every device is idle for the same short while each step
    assert 0 < idle.reduce(run, {"phase": "train"}) < 8.0


def test_the_rest_is_what_no_sibling_metric_matches():
    run = synthetic()
    run.trace.devices["/device:TPU:0"].append(
        xtrace.Op("fusion.9", "jit(step)/adam/mul:", "loop fusion", 8.5, 0.5))
    rest = scope_rest.reduce(run, {"phase": "train", "others": [
        "gather_ms.train", "halo_ms.train", "segsum_ms.train", "dense_ms.train"]})
    assert rest == pytest.approx(1e3 * 0.5 / 2 / 2)


def test_a_roofline_share_over_100_fails_the_run():
    from benchmark.reducers import roofline

    run = synthetic()
    run.info.update(hidden=256, e_pad=2 ** 40, n_pad=8, compute_bytes=2)
    with pytest.raises(AssertionError):
        roofline.reduce(run, {
            "phase": "train", "match": ["dgraph\\.local_take"],
            "work": "segsum_call_bytes", "peak": "hbm_gbps", "peak_unit": 1e9})
