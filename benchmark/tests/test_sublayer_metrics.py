"""The per-layer metrics that read the program's child scopes, its buffer
gauge, its route counters and its self-check stage (ISSUE 36): declared with
their cells; their patterns run over ``data/gcn_w4_children_cut.trace.json.gz``,
recorded on the chip from that PR's tree (two train steps of
``gcn_papers100m.w4`` on four chips, seed 3600000402, cut as its siblings
were: the operations of 0.3 ms and more plus everything under the
``dgraph.local_take`` and ``dgraph.halo_*`` scopes, ``tf_op`` and
``hlo_category`` alone of each event's arguments); over hand-made operations
for the expert layer's; and over the two traces recorded before the children
existed, where every one of them finds nothing and none raises."""

import json
import os

import pytest

from benchmark import xtrace
from benchmark.reducers import (program_counter_ratio, program_gauge_ratio,
                                program_stage, scope_time)
from benchmark.tests.test_rehearsal import BENCH, ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
GCN = ("gcn_arxiv.w1", "gcn_papers100m.w4")
W4 = ("gcn_papers100m.w4",)
GC = ("graphcast_small.w1",)
MOE = ("sdar_30b_a3b.bd8k", "lfm2_8b_a1b.seq16k")
SEQ = ("ouro_2p6b.seq8k",) + MOE
# name -> (unit, better, source, layer, moves, workloads), as ISSUE 36 has them
THIRTEEN = {
    "gather_rows_ms.train": ("ms", "lower", "device_trace", "local gather", "train_step_ms", GCN),
    "gather_mask_ms.train": ("ms", "lower", "device_trace", "local gather", "train_step_ms", GCN),
    "gather_rows_ms.fed": ("ms", "lower", "device_trace", "local gather", "fed_step_ms", GC),
    "gather_mask_ms.fed": ("ms", "lower", "device_trace", "local gather", "fed_step_ms", GC),
    "halo_wire_ms.train": ("ms", "lower", "device_trace", "halo exchange", "train_step_ms", W4),
    "halo_send_gather_ms.train": ("ms", "lower", "device_trace", "halo exchange", "train_step_ms", W4),
    "halo_scatter_add_ms.train": ("ms", "lower", "device_trace", "halo exchange", "train_step_ms", W4),
    "moe_dispatch_ms.fed": ("ms", "lower", "device_trace", "sparse experts", "fed_step_ms", MOE),
    "moe_combine_ms.fed": ("ms", "lower", "device_trace", "sparse experts", "fed_step_ms", MOE),
    "moe_router_ms.fed": ("ms", "lower", "device_trace", "sparse experts", "fed_step_ms", MOE),
    "moe_buffer_fill_pct.fed": ("%", "lower", "program_counter", "sparse experts", "fed_step_ms", MOE),
    "gather_bwd_transposed_pct.train": ("%", "higher", "program_counter", "local gather", "train_step_ms", GCN),
    "selfcheck_s": ("s", "lower", "program_span", "sequence attention", "setup_s", SEQ),
}
HAD_BEFORE = 50  # per-layer entries of the benchmark these were appended to
DEVICE = [n for n, row in THIRTEEN.items() if row[2] == "device_trace"]
# the metric that holds each child's layer whole
PARENT = {"gather": "gather_ms", "halo": "halo_ms", "moe": "moe_ms"}


def spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_the_thirteen_metrics_are_declared_with_their_cells():
    got = {m["name"]: (m["unit"], m["better"], m["source"], m["layer"],
                       m["moves"], tuple(m["workloads"]))
           for m in BENCH["per_layer"] if m["name"] in THIRTEEN}
    assert got == THIRTEEN
    names = [m["name"] for m in BENCH["per_layer"]]
    assert min(names.index(n) for n in THIRTEEN) >= HAD_BEFORE
    layers = {m["layer"] for m in BENCH["per_layer"][:HAD_BEFORE]}
    for name, row in THIRTEEN.items():
        assert row[3] in layers  # a layer the benchmark already names
        assert spec(name)["reducer"] == {
            "device_trace": "scope_time", "program_span": "program_stage",
            "program_counter": ("program_gauge_ratio" if "fill" in name
                                else "program_counter_ratio")}[row[2]]
    # a child is read with its parent's phase and its parent's exclusions
    for name in DEVICE:
        parent = spec(f"{PARENT[name.split('_')[0]]}.{name.rsplit('.', 1)[1]}")
        mine = spec(name)["params"]
        assert mine["phase"] == parent["params"]["phase"]
        if name.startswith("gather_"):
            assert mine["unless"] == parent["params"]["unless"]


def record(file=None, trace=None):
    tr = trace or xtrace.load_file(os.path.join(DATA, file))
    said = []
    return xtrace.RunRecord(
        trace=tr, spans={}, info={}, counts={}, step_times={},
        device_kind="TPU v5 lite", say=said.append), said


def matched(run, name):
    found = scope_time.matched_ops(run, spec(name)["params"])
    return [] if found is None else [
        id(o) for ops, _ in found.values() for o in ops]


@pytest.fixture(scope="module")
def children_run():
    return record("gcn_w4_children_cut.trace.json.gz")[0]


@pytest.mark.parametrize("parent, children", [
    ("gather_ms.train", ("gather_rows_ms.train", "gather_mask_ms.train")),
    ("halo_ms.train", ("halo_wire_ms.train", "halo_send_gather_ms.train",
                       "halo_scatter_add_ms.train")),
])
def test_children_split_their_parent_on_the_recorded_trace(
        children_run, parent, children):
    """Every operation a child matches is its parent's, no operation is
    under two children, each child finds something, and the children sum to
    no more than the parent."""
    whole = set(matched(children_run, parent))
    seen = set()
    for name in children:
        mine = matched(children_run, name)
        assert mine and set(mine) <= whole, name
        assert not seen & set(mine), name
        seen |= set(mine)
    values = {n: scope_time.reduce(children_run, spec(n)["params"])
              for n in (parent,) + children}
    assert all(v > 0 for v in values.values())
    assert sum(values[n] for n in children) <= values[parent]


# what the builder's traced run the cut was made from read over its six steps
# (seed 3600000402; PERF.md section 5); the cut keeps two of them
RECORDED = {
    "gather_ms.train": 209.898, "gather_rows_ms.train": 170.242,
    "gather_mask_ms.train": 35.998, "halo_ms.train": 118.645,
    "halo_wire_ms.train": 37.596, "halo_send_gather_ms.train": 32.348,
    "halo_scatter_add_ms.train": 31.334,
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_trace_reads_what_the_chip_run_read(children_run, name):
    assert scope_time.reduce(children_run, spec(name)["params"]) \
        == pytest.approx(RECORDED[name], rel=1e-3)


def test_a_transposed_send_gather_is_the_scatter_add(children_run):
    """Under plain AD the backward of ``x[send_idx]`` keeps the child's
    name: ``.../send_gather/scatter-add``. The scatter-add metric holds it,
    the send-gather metric does not; ``remat``'s forward, which runs under
    the backward's prefix, is a send gather."""
    ops = {id(o): o for d in children_run.trace.devices.values() for o in d}
    adds = [ops[i] for i in matched(children_run, "halo_scatter_add_ms.train")]
    sends = [ops[i] for i in matched(children_run, "halo_send_gather_ms.train")]
    assert all("/send_gather/" in o.scope and "scatter-add" in o.scope
               and "transpose(" in o.scope for o in adds)
    assert not any("scatter-add" in o.scope for o in sends)
    assert any("rematted_computation" in o.scope for o in sends)
    assert any("transpose(" not in o.scope for o in sends)


@pytest.mark.parametrize("file", ["gcn_w1_cut.trace.json.gz",
                                  "gcn_w4_cut.trace.json.gz"])
@pytest.mark.parametrize("name", DEVICE)
def test_old_traces_give_nothing_and_do_not_raise(file, name):
    """Recorded before the children existed: a parent's program. The
    metrics that hold the layers whole still read them."""
    run, _ = record(file)
    assert scope_time.reduce(run, spec(name)["params"]) is None
    assert scope_time.reduce(run, spec("gather_ms.train")["params"]) > 0


def synthetic_fed():
    """One device, one ``fed`` phase of two 1 s steps whose operations carry
    the paths an expert layer's and an edge block's do (forward, recomputed
    forward and backward)."""
    moe = "jit(step)/jvp(LoopLM)/while/body/dgraph.lm.loop_pass/dgraph.lm.moe"
    back = ("jit(step)/transpose(jvp(LoopLM))/while/body/checkpoint/"
            "rematted_computation/dgraph.lm.loop_pass/dgraph.lm.moe")
    take = "jit(step)/jvp(GraphCast)/enc_edge/dgraph.local_take"
    scopes = [  # (path, seconds)
        (moe + "/router/router/dot_general:", 0.010),
        (moe + "/routes/jit(argsort)/sort:", 0.004),
        (moe + "/dispatch/gather:", 0.020),
        (moe + "/experts/pallas_call:", 0.100),
        (moe + "/experts/jit(silu)/logistic:", 0.030),
        (moe + "/combine/gather:", 0.050),
        (back + "/dispatch/gather:", 0.020),
        (back + "/combine/mul:", 0.006),
        (take + "/rows/jit(_take)/gather:", 0.040),
        (take + "/mask/mul:", 0.008),
        (take + "/pallas_call:", 0.015),  # the backward's segment-sum
        ("jit(step)/jvp(GraphCast)/dgraph.scatter_sum/dgraph.local_take/"
         "rows/gather:", 0.5),  # a scatter's own take: not the gather's
    ]
    devices, host = {"/device:TPU:0": []}, [
        xtrace.Span("bench_phase.fed", 0.0, 2.0)]
    for k in range(2):
        host.append(xtrace.Span("bench_step.fed", float(k), 1.0))
        t = float(k)
        for path, dur in scopes:
            devices["/device:TPU:0"].append(
                xtrace.Op("fusion.1", path, "loop fusion", t, dur))
            t += dur
    return record(trace=xtrace.assemble(devices, host))[0]


def test_the_expert_and_fed_children_on_hand_made_operations():
    run = synthetic_fed()
    read = lambda n: scope_time.reduce(run, spec(n)["params"])  # noqa: E731
    assert read("moe_router_ms.fed") == pytest.approx(10.0)
    assert read("moe_dispatch_ms.fed") == pytest.approx(44.0)  # with routes
    assert read("moe_combine_ms.fed") == pytest.approx(56.0)
    assert read("moe_ms.fed") == pytest.approx(240.0)
    assert read("gather_rows_ms.fed") == pytest.approx(40.0)
    assert read("gather_mask_ms.fed") == pytest.approx(8.0)
    assert read("gather_ms.fed") == pytest.approx(63.0)
    assert read("gather_rows_ms.train") is None  # another phase


def test_the_gauge_ratio_reads_the_registry_or_nothing():
    from dgraph_tpu.obs.metrics import default_registry

    params = spec("moe_buffer_fill_pct.fed")["params"]
    run, said = record("gcn_w1_cut.trace.json.gz")
    default_registry.reset()
    assert program_gauge_ratio.reduce(run, params) is None  # a parent's
    default_registry.counter("moe.buffer_rows", 65536)
    assert program_gauge_ratio.reduce(run, params) is None  # no step yet
    default_registry.gauge("moe.rows_max_layer", 44030)
    assert program_gauge_ratio.reduce(run, params) == pytest.approx(
        100 * 44030 / 65536)
    assert "moe.rows_max_layer=44030" in said[-1]
    default_registry.reset()
    # the other two read nothing from a program that records nothing
    assert program_counter_ratio.reduce(
        run, spec("gather_bwd_transposed_pct.train")["params"]) is None
    default_registry.counter("gather.bwd_chunks", 4)
    default_registry.counter("gather.bwd_transposed", 4)
    assert program_counter_ratio.reduce(
        run, spec("gather_bwd_transposed_pct.train")["params"]) == 100.0
    default_registry.reset()
    assert spec("selfcheck_s")["params"]["stages"] == [
        "setup.attention_selfcheck"]
    from dgraph_tpu.obs import spans
    if "setup.attention_selfcheck" not in spans.stage_totals():
        assert program_stage.reduce(run, spec("selfcheck_s")["params"]) is None
