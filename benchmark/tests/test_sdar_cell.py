"""The block-diffusion sparse-expert cell's own per-layer metrics: the traced
CPU rehearsal prints those a CPU run can read (the program's stages, spans and
counters; the device-trace ones need a chip), the device classes part the
operations of a step without counting a ``while`` container beside its body
or anything twice, and on a program that keeps no ``moe.*`` / ``attn.*``
counters (the parent of the PR that added them) the readers find nothing and
do not raise. (What ``test_looplm_cell.py`` holds for the looped LM's cell.)"""

import json
import os
import types

from benchmark import xtrace
from benchmark.reducers import program_counter_ratio, scope_rest, scope_time
from benchmark.tests.test_rehearsal import BENCH, ROOT, run_cell

CELL = "sdar_30b_a3b.bd8k"
NEW = ("moe_ms.fed", "moe_gmm_roofline.fed", "moe_rows_here_pct.fed",
       "moe_tile_fill_pct.fed", "bd_attn_roofline.fed",
       "bd_attn_tile_fill_pct.fed", "sdar_dense_ms.fed", "sdar_other_ms.fed")
SHARED = ("fed_step_ms", "placement_s", "compile_s", "init_s",
          "dispatch_ms.fed", "host_feed_ms.fed", "eval_ms.fed",
          "device_idle_pct.fed", "attn_ms.fed", "exit_loss_ms.fed")


def spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_the_new_metrics_are_declared_for_the_cell_alone():
    rows = {m["name"]: m for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in NEW:
        assert rows[name]["workloads"] == [CELL]
        assert rows[name]["moves"] == "fed_step_ms"
        assert spec(name)["name"] == name
    for name in SHARED:  # appended to the lists that were there
        assert rows[name]["workloads"][-1] == CELL, name
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    assert BENCH["workloads"][-1] == {
        "name": CELL, "config": "sdar_30b_a3b", "traffic": "bd8k", "chips": 1,
        "why": BENCH["workloads"][-1]["why"]}
    assert len(BENCH["workloads"][-1]["why"]) <= 200
    assert BENCH["configs"][-1]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]


def test_traced_rehearsal_reads_the_programs_own_spans_and_counters():
    out, result = run_cell(CELL, trace=1, seed=2**31 + 32)
    assert out.returncode == 0, out.stderr[-2000:]
    assert result["correct"] is True
    got = {n.split(".", 1)[1]: v["value"] for n, v in result["metrics"].items()}
    for name in ("init_s", "compile_s", "placement_s", "dispatch_ms.fed",
                 "host_feed_ms.fed", "eval_ms.fed"):
        assert got[name] > 0, name
    assert 0 < got["moe_rows_here_pct.fed"] < 100  # 4 of 16 held: about 25
    assert 0 < got["moe_tile_fill_pct.fed"] <= 100
    # the oracle's one tile is the whole square: L (L + 4) of 4 L^2 at L = 128
    assert abs(got["bd_attn_tile_fill_pct.fed"] - 100 * 132 / 512) < 1e-9
    assert "attention=dense" in out.stdout
    # read after the window, so that every timed step is in the count
    assert out.stdout.index("[bench] phase fed:") \
        < out.stdout.index("rows_dropped=0")
    assert "% of (row, choice) pairs" in out.stdout


def test_a_frozen_step_and_the_float8_control_are_not_correct():
    for extra in (("--break-step", "frozen"), ("--control", "1")):
        out, result = run_cell(CELL, *extra, seed=2**31 + 33)
        assert out.returncode == 0, out.stderr[-2000:]
        assert result["correct"] is False, extra


def test_rows_dropped_by_the_timed_step_are_not_correct():
    """A row buffer too small for the rows routed here, under the timed path
    only: the three steps the comparison reads are sound and every gap passes,
    and the count read after the window fails the limited ``loss_gap``."""
    out, result = run_cell(CELL, "--break-step", "dropped", seed=2**31 + 34)
    assert out.returncode == 0, out.stderr[-2000:]
    assert result["correct"] is False
    assert "were dropped: not correct" in out.stdout
    failed = [l for l in out.stderr.splitlines() if "FAILED check" in l]
    assert len(failed) == 1 and "loss_gap: value=nan" in failed[0]


def op(scope, name, category, dur):
    return xtrace.Op(name, scope, category, 0.0, dur)


def test_the_device_classes_part_a_step():
    lp = "jit(lm_train_step)/jvp(LoopLM.hidden)/while/body/stack/dgraph.lm.loop_pass/while/body/layers"
    moe = lp + "/experts/dgraph.lm.moe"
    ops = [
        op("", "while.249", "while", 700.0),  # spans everything below it
        op(lp + "/dgraph.comm.seq_attention/vmap(jit(splash))/pallas_call",
           "splash_mqa_fwd.3", "custom-call", 50.0),
        op(lp + "/q_proj/dot_general", "fusion.7", "convolution fusion", 100.0),
        op("transpose(jvp(" + lp + "))/o_proj/dot_general", "fusion.9",
           "convolution fusion", 200.0),
        op(moe + "/router/router/dot_general", "fusion.21", "convolution fusion", 5.0),
        op(moe + "/experts/pallas_call", "gmm.4", "custom-call", 30.0),
        op("transpose(jvp(" + moe + "))/experts/pallas_call", "tgmm.5",
           "custom-call", 20.0),
        op(moe + "/experts/mul", "fusion.22", "loop fusion", 4.0),
        op(moe + "/dispatch/gather", "fusion.23", "gather", 9.0),
        op(moe + "/combine/gather", "fusion.24", "gather", 11.0),
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
    assert read("sdar_dense_ms.fed") == 300e3  # the router's product is the experts'
    assert read("moe_ms.fed") == 79e3
    assert read("exit_loss_ms.fed") == 40e3
    gmm = scope_time.matcher(spec("moe_gmm_roofline.fed")["params"])
    assert [o.name for o in ops if gmm(o)] == ["gmm.4", "tgmm.5"]
    other = scope_rest.reduce(run, spec("sdar_other_ms.fed")["params"])
    assert other == 10e3  # rotary + optimizer; not the container
    leaves = sum(o.dur for o in ops if o.category != "while")
    assert read("attn_ms.fed") + read("sdar_dense_ms.fed") + read("moe_ms.fed") \
        + read("exit_loss_ms.fed") + other == leaves * 1e3


def test_a_program_without_the_counters_gives_nothing(monkeypatch):
    from dgraph_tpu.obs import metrics

    monkeypatch.setattr(metrics, "default_registry", metrics.Metrics())
    run = types.SimpleNamespace(say=lambda m: None)
    for name in ("moe_rows_here_pct.fed", "moe_tile_fill_pct.fed",
                 "bd_attn_tile_fill_pct.fed"):
        assert program_counter_ratio.reduce(run, spec(name)["params"]) is None
