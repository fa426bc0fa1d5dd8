"""The cell of the decoder with several layer kinds: its entries in
``BENCHMARK.json`` are additions, the traced CPU rehearsal prints the metrics
a CPU run can read (the program's stages, spans and counters; the device-trace
ones need a chip), the device classes part the operations of a step without
counting anything twice, and on a program that keeps no ``moe.*`` counters
(the parent of the PR that added them) the readers find nothing and do not
raise. (What ``test_sdar_cell.py`` holds for the block-diffusion cell; its and
``test_looplm_cell.py``'s cases that pin the LAST entry of a list to their own
cell read red since this cell was appended: this file holds what they
would.)"""

import json
import os
import types

from benchmark import opsbytes, xtrace
from benchmark.reducers import program_counter_ratio, scope_rest, scope_time
from benchmark.tests.test_rehearsal import BENCH, ROOT, run_cell

CELL = "lfm2_8b_a1b.seq16k"
NEW = ("conv_ms.fed", "conv_proj_roofline.fed", "conv_gate_roofline.fed",
       "lfm2_attn_roofline.fed", "lfm2_moe_gmm_roofline.fed",
       "lfm2_dense_ms.fed", "lfm2_other_ms.fed")
SHARED = ("fed_step_ms", "placement_s", "compile_s", "init_s",
          "dispatch_ms.fed", "host_feed_ms.fed", "eval_ms.fed",
          "device_idle_pct.fed", "attn_ms.fed", "exit_loss_ms.fed",
          "moe_ms.fed", "moe_rows_here_pct.fed", "moe_tile_fill_pct.fed")


def spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_the_new_metrics_are_declared_for_the_cell_alone():
    rows = {m["name"]: m for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in NEW:
        assert rows[name]["workloads"] == [CELL]
        assert rows[name]["moves"] == "fed_step_ms"
        assert spec(name)["name"] == name
        if name.endswith("_roofline.fed"):
            assert rows[name]["unit"] == "%" and rows[name]["better"] == "higher"
    for name in SHARED:  # appended to the lists that were there
        assert rows[name]["workloads"][-1] == CELL, name
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    assert BENCH["workloads"][-1] == {
        "name": CELL, "config": "lfm2_8b_a1b", "traffic": "seq16k", "chips": 1,
        "why": BENCH["workloads"][-1]["why"]}
    assert len(BENCH["workloads"][-1]["why"]) <= 200
    assert BENCH["configs"][-1]["name"] == "lfm2_8b_a1b"
    assert BENCH["configs"][-1]["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    with open(os.path.join(ROOT, "benchmark", "traffic", "seq16k.json")) as f:
        assert json.load(f) == {"world_size": 1, "seq_len": 16384, "batches": 8,
                                "zipf_exponent": 1.0}
    # the cells that were there, in their order, ahead of it
    assert [w["name"] for w in BENCH["workloads"][:-1]] == [
        "gcn_arxiv.w1", "graphcast_small.w1", "gcn_papers100m.w4",
        "ouro_2p6b.seq8k", "sdar_30b_a3b.bd8k"]


def test_traced_rehearsal_reads_the_programs_own_spans_and_counters():
    out, result = run_cell(CELL, trace=1, seed=2**31 + 34)
    assert out.returncode == 0, out.stderr[-2000:]
    assert result["correct"] is True
    got = {n.split(".", 1)[1]: v["value"] for n, v in result["metrics"].items()}
    for name in ("init_s", "compile_s", "placement_s", "dispatch_ms.fed",
                 "host_feed_ms.fed", "eval_ms.fed"):
        assert got[name] > 0, name
    assert 0 < got["moe_rows_here_pct.fed"] < 100  # 4 of 16 held: about 25
    assert 0 < got["moe_tile_fill_pct.fed"] <= 100
    assert "attention=dense" in out.stdout
    assert "layers_by_kind={'conv': 4, 'attention': 1, 'dense_ffn': 1, " \
        "'expert_ffn': 4}" in out.stdout
    # read after the window, so that every timed step is in the count
    assert out.stdout.index("[bench] phase fed:") \
        < out.stdout.index("rows_dropped=0")
    assert "% of (row, choice) pairs" in out.stdout


def test_a_frozen_step_and_the_float8_control_are_not_correct():
    for extra in (("--break-step", "frozen"), ("--control", "1")):
        out, result = run_cell(CELL, *extra, seed=2**31 + 35)
        assert out.returncode == 0, out.stderr[-2000:]
        assert result["correct"] is False, extra


def op(scope, name, category, dur):
    return xtrace.Op(name, scope, category, 0.0, dur)


def test_the_device_classes_part_a_step():
    lp = "jit(lm_train_step)/jvp(LoopLM.hidden)/while/body/stack/dgraph.lm.loop_pass/while/body/layers_2"
    moe = lp + "/experts/dgraph.lm.moe"
    conv = lp + "/conv/dgraph.lm.conv"
    ops = [
        op("", "while.249", "while", 700.0),  # spans everything below it
        op(lp.replace("layers_2", "layers_1")
           + "/dgraph.comm.seq_attention/vmap(jit(splash))/pallas_call",
           "splash_mqa_fwd.3", "custom-call", 50.0),
        op(lp.replace("layers_2", "layers_1") + "/q_proj/dot_general",
           "fusion.7", "convolution fusion", 100.0),
        op("transpose(jvp(" + lp.replace("layers_2", "layers_0")
           + "))/down_proj/dot_general", "fusion.9", "convolution fusion", 200.0),
        op(conv + "/in_proj/in_proj/dot_general", "fusion.30",
           "convolution fusion", 60.0),
        op("transpose(jvp(" + conv + "))/out_proj/out_proj/dot_general",
           "fusion.31", "convolution fusion", 25.0),
        op(conv + "/gate_conv/mul", "fusion.32", "loop fusion", 8.0),
        op("transpose(jvp(" + conv + "))/gate_conv/mul", "fusion.33",
           "loop fusion", 13.0),
        op(moe + "/router/router/dot_general", "fusion.21", "convolution fusion", 5.0),
        op(moe + "/experts/pallas_call", "gmm.4", "custom-call", 30.0),
        op("transpose(jvp(" + moe + "))/experts/pallas_call", "tgmm.5",
           "custom-call", 20.0),
        op(moe + "/dispatch/gather", "fusion.23", "gather", 9.0),
        op("jit(lm_train_step)/jvp(dgraph.lm.exit_loss)/while/body/dgraph.lm.head/dot_general",
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
    assert read("conv_ms.fed") == 106e3
    assert read("lfm2_dense_ms.fed") == 300e3  # not the conv's, not the router's
    assert read("moe_ms.fed") == 64e3
    assert read("exit_loss_ms.fed") == 40e3
    hit = lambda name: [o.name for o in ops if scope_time.matcher(
        spec(name)["params"])(o)]
    assert hit("conv_proj_roofline.fed") == ["fusion.30", "fusion.31"]
    assert hit("conv_gate_roofline.fed") == ["fusion.32", "fusion.33"]
    assert hit("lfm2_moe_gmm_roofline.fed") == ["gmm.4", "tgmm.5"]
    assert hit("lfm2_attn_roofline.fed") == ["splash_mqa_fwd.3"]
    other = scope_rest.reduce(run, spec("lfm2_other_ms.fed")["params"])
    assert other == 10e3  # rotary + optimizer; not the container
    leaves = sum(o.dur for o in ops if o.category != "while")
    assert read("attn_ms.fed") + read("conv_ms.fed") + read("lfm2_dense_ms.fed") \
        + read("moe_ms.fed") + read("exit_loss_ms.fed") + other == leaves * 1e3


def test_the_rooflines_name_work_files_that_read_the_cells_info():
    info = {"seq_len": 16384, "heads": 32, "head_dim": 64, "hidden": 2048,
            "expert_width": 1792, "experts_per_token": 4, "layers_conv": 4,
            "layers_attention": 1, "layers_expert_ffn": 4, "loop_steps": 1,
            "compute_bytes": 2}
    for name in NEW:
        params = spec(name)["params"]
        if "work" in params:
            assert opsbytes.work(params["work"], info, 0) >= 0
            assert params["peak"] in opsbytes.device_peaks("TPU v5 lite")
    # the gate pass is bounded by bytes, the others by operations
    assert spec("conv_gate_roofline.fed")["params"]["peak"] == "hbm_gbps"


def test_a_program_without_the_counters_gives_nothing(monkeypatch):
    from dgraph_tpu.obs import metrics

    monkeypatch.setattr(metrics, "default_registry", metrics.Metrics())
    run = types.SimpleNamespace(say=lambda m: None)
    for name in ("moe_rows_here_pct.fed", "moe_tile_fill_pct.fed"):
        assert program_counter_ratio.reduce(run, spec(name)["params"]) is None
    assert opsbytes.work("lfm2_moe_flops", {}, 0) == 0.0
