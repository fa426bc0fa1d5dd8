"""The looped-LM cell's own per-layer metrics: the traced CPU rehearsal prints
those a CPU run can read (the program's stages, spans and counters; the
device-trace ones need a chip), the device classes part the operations of a
step without counting a ``while`` container beside its body, and on a program
that keeps no ``lm.*`` counters (the parent of the PR that added them) the
reader finds nothing and does not raise."""

import json
import os
import types

from benchmark import xtrace
from benchmark.reducers import program_counter_ratio, scope_rest, scope_time
from benchmark.tests.test_rehearsal import BENCH, ROOT, run_cell

CELL = "ouro_2p6b.seq8k"
NEW = ("attn_ms.fed", "attn_roofline.fed", "lm_dense_ms.fed",
       "lm_dense_roofline.fed", "exit_loss_ms.fed", "lm_other_ms.fed",
       "loop_held_pct.fed")


def spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_the_new_metrics_are_declared_for_the_cell_alone():
    rows = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        assert rows[name]["workloads"] == [CELL]
        assert rows[name]["moves"] == "fed_step_ms"
        assert spec(name)["name"] == name
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW):] == list(NEW)  # appended, in the issue's order
    assert BENCH["workloads"][-1] == {
        "name": CELL, "config": "ouro_2p6b", "traffic": "seq8k", "chips": 1,
        "why": BENCH["workloads"][-1]["why"]}


def test_traced_rehearsal_reads_the_programs_own_spans_and_counters():
    out, result = run_cell(CELL, trace=1, seed=2**31 + 28)
    assert out.returncode == 0, out.stderr[-2000:]
    assert result["correct"] is True
    got = {n.split(".", 1)[1]: v["value"] for n, v in result["metrics"].items()}
    assert got["loop_held_pct.fed"] == 25.0
    for name in ("init_s", "compile_s", "placement_s", "dispatch_ms.fed",
                 "host_feed_ms.fed", "eval_ms.fed"):
        assert got[name] > 0, name  # the trainer's spans are on the host line
    assert "lm start-up: attention=dense" in out.stdout


def op(scope, name, category, dur):
    return xtrace.Op(name, scope, category, 0.0, dur)


def test_the_device_classes_part_a_step():
    lp = "jit(lm_train_step)/jvp(LoopLM.hidden)/while/body/stack/dgraph.lm.loop_pass/while/body/layers"
    ops = [
        op("", "while.249", "while", 700.0),  # spans everything below it
        op("", "while", "while", 30.0),
        op(lp + "/dgraph.comm.seq_attention/jit(flash_attention)/pallas_call",
           "flash_attention.3", "custom-call", 50.0),
        op(lp + "/q_proj/dot_general", "fusion.7", "convolution fusion", 100.0),
        op("transpose(jvp(" + lp + "))/down_proj/dot_general", "fusion.9",
           "convolution fusion", 200.0),
        op("jit(lm_train_step)/jvp(dgraph.lm.exit_loss)/while/body/dgraph.lm.head/head/dot_general",
           "fusion.11", "convolution fusion", 40.0),
        op(lp + "/dgraph.lm.rotary/mul", "fusion.12", "loop fusion", 7.0),
        op("jit(lm_train_step)/dgraph.lm.optimizer/add", "fusion.13", "loop fusion", 3.0),
    ]
    step = xtrace.Span("bench_step.fed", -1.0, 2000.0)
    trace = xtrace.Trace({"/device:TPU:0": ops}, [], {
        "fed": {"span": step, "steps": [step]}})
    run = types.SimpleNamespace(trace=trace, say=lambda m: None)
    read = lambda name: scope_time.reduce(run, spec(name)["params"])
    assert read("attn_ms.fed") == 50e3
    assert read("lm_dense_ms.fed") == 300e3
    assert read("exit_loss_ms.fed") == 40e3
    other = scope_rest.reduce(run, spec("lm_other_ms.fed")["params"])
    assert other == 10e3  # rotary + optimizer; neither container
    leaves = sum(o.dur for o in ops if o.category != "while")
    assert read("attn_ms.fed") + read("lm_dense_ms.fed") \
        + read("exit_loss_ms.fed") + other == leaves * 1e3


def test_a_program_without_lm_counters_gives_nothing(monkeypatch):
    from dgraph_tpu.obs import metrics

    monkeypatch.setattr(metrics, "default_registry", metrics.Metrics())
    run = types.SimpleNamespace(say=lambda m: None)
    assert program_counter_ratio.reduce(
        run, spec("loop_held_pct.fed")["params"]) is None
