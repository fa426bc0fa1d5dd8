"""The per-layer metrics that read what the program itself records (its
always-on set-up stages and its plan counters): a traced CPU rehearsal of
each cell prints every one of them that lists the cell, and on a program
that records nothing (the parent of the PR that added them) the reducers
find nothing to read and do not raise."""

import json
import os
import types

import pytest

from benchmark.reducers import program_counter_ratio, program_stage
from benchmark.tests.test_rehearsal import BENCH, CELLS, ROOT, run_cell

GCN = ("gcn_arxiv.w1", "gcn_papers100m.w4")
# name -> (unit, better, source, moves, workloads), as ISSUE 26 declared them
SEVEN = {
    "partition_s": ("s", "lower", "program_span", "setup_s", GCN),
    "plan_s": ("s", "lower", "program_span", "setup_s",
               ("gcn_arxiv.w1", "graphcast_small.w1", "gcn_papers100m.w4")),
    "shard_s": ("s", "lower", "program_span", "setup_s", GCN),
    "init_s": ("s", "lower", "program_span", "setup_s", GCN),
    "segsum_grid_fill_pct.train": (
        "%", "higher", "program_counter", "train_step_ms", GCN),
    "segsum_grid_fill_pct.fed": (
        "%", "higher", "program_counter", "fed_step_ms",
        ("graphcast_small.w1",)),
    "halo_wire_fill_pct.train": (
        "%", "higher", "program_counter", "train_step_ms",
        ("gcn_papers100m.w4",)),
}
HAD_BEFORE = 21  # per-layer entries of the benchmark these were appended to


def test_the_seven_metrics_are_declared_with_their_cells():
    got = {m["name"]: (m["unit"], m["better"], m["source"], m["moves"],
                       tuple(m["workloads"]))
           for m in BENCH["per_layer"] if m["name"] in SEVEN}
    assert got == SEVEN
    # appended: they come after every entry the benchmark had (a later PR
    # may append more, after or between them)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert min(names.index(n) for n in SEVEN) >= HAD_BEFORE
    for name in SEVEN:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            assert json.load(f)["reducer"] in (
                "program_stage", "program_counter_ratio")


@pytest.mark.parametrize("name", CELLS)
def test_traced_rehearsal_prints_the_cells_program_metrics(name):
    out, result = run_cell(name, trace=1, seed=2**31 + 26)
    assert out.returncode == 0, out.stderr[-2000:]
    want = {n for n, row in SEVEN.items() if name in row[4]}
    assert want  # every cell has some
    got = result["metrics"]
    for metric in want:
        value = got["cpu_rehearsal." + metric]["value"]
        if metric.endswith("_s"):
            assert value > 0, metric
        else:
            assert 0 < value <= 100, metric
    stages = {"partition_s", "plan_s", "shard_s"}
    if stages <= want:  # the stages lie inside the call the builder times
        assert sum(got["cpu_rehearsal." + m]["value"] for m in stages) \
            <= got["cpu_rehearsal.plan_build_s"]["value"]
    # and none of another cell's
    lists = {m["name"]: m["workloads"] for m in BENCH["per_layer"]}
    assert all(name in lists[n.split(".", 1)[1]] for n in got
               if n.split(".", 1)[1] in lists)


def test_untraced_line_is_unchanged_in_shape():
    out, result = run_cell(CELLS[0], trace=0)
    assert out.returncode == 0, out.stderr[-2000:]
    want = {m["name"] for m in BENCH["end_to_end"]
            if CELLS[0] in m.get("workloads", [CELLS[0]])}
    assert {n.split(".", 1)[1] for n in result["metrics"]} == want
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "rehearsal"}


def test_a_program_that_records_nothing_gives_nothing(monkeypatch):
    """The parent's side of a traced run: no stage table, no counters."""
    from dgraph_tpu.obs import metrics, spans

    run = types.SimpleNamespace(say=lambda msg: None)
    monkeypatch.delattr(spans, "stage_totals")
    assert program_stage.reduce(run, {"stages": ["setup.plan"]}) is None
    monkeypatch.setattr(metrics, "default_registry", metrics.Metrics())
    assert program_counter_ratio.reduce(run, {
        "numerator": "plan.segsum_used_chunks",
        "denominator": "plan.segsum_grid_steps"}) is None


def test_the_readers_sum_stages_and_divide_counters(monkeypatch):
    from dgraph_tpu.obs import metrics, spans

    said = []
    run = types.SimpleNamespace(say=said.append)
    row = {"count": 1, "max_s": 1.0, "last_s": 1.0}
    monkeypatch.setattr(spans, "stage_totals", lambda: {
        "setup.init_params": dict(row, total_s=1.5),
        "setup.init_opt_state": dict(row, total_s=0.25),
        "setup.plan": dict(row, total_s=9.0)})
    assert program_stage.reduce(run, {"stages": [
        "setup.init_params", "setup.init_opt_state"]}) == 1.75
    assert program_stage.reduce(run, {"stages": ["setup.partition"]}) is None
    reg = metrics.Metrics()
    monkeypatch.setattr(metrics, "default_registry", reg)
    reg.counter("plan.segsum_grid_steps", 40)
    reg.counter("plan.segsum_grid_steps.halo_sort", 30)
    reg.counter("plan.segsum_used_chunks", 10)
    reg.counter("plan.segsum_used_chunks.halo_sort", 3)
    assert program_counter_ratio.reduce(run, {
        "numerator": "plan.segsum_used_chunks",
        "denominator": "plan.segsum_grid_steps"}) == 25.0
    assert "halo_sort=3/30" in said[-1]
