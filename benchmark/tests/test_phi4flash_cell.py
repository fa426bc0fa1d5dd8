"""The cell of the decoder-hybrid-decoder: its entries in ``BENCHMARK.json``
are additions, the traced CPU rehearsal prints the metrics a CPU run can read
(the program's stages, spans and counters; the device-trace ones need a chip),
the device classes part the operations of a step without counting anything
twice (the scan's ``while`` containers among them), the work functions by
hand, and a frozen step and the float8 control are not correct. Nothing here
is pinned to "the last workload": a later cell may follow this one."""

import json
import os
import types

from benchmark import opsbytes, xtrace
from benchmark.reducers import program_counter_ratio, scope_rest, scope_time
from benchmark.tests.test_rehearsal import BENCH, ROOT, run_cell

CELL = "phi4_mini_flash.seq8k"
NEW = ("ssm_ms.fed", "ssm_scan_ms.fed", "ssm_scan_roofline.fed",
       "ssm_proj_roofline.fed", "gmu_ms.fed", "phi4_attn_roofline.fed",
       "win_attn_tile_fill_pct.fed", "phi4_dense_ms.fed", "phi4_other_ms.fed")
SHARED = ("fed_step_ms", "placement_s", "compile_s", "init_s", "selfcheck_s",
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
        if name.endswith("_roofline.fed"):
            assert rows[name]["unit"] == "%" and rows[name]["better"] == "higher"
    for name in SHARED:  # appended to the lists that were there
        assert CELL in rows[name]["workloads"], name
        assert rows[name]["workloads"].index(CELL) \
            > rows[name]["workloads"].index("lfm2_8b_a1b.seq16k"), name
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "phi4_mini_flash",
                    "traffic": "seq8k", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    cfg = next(c for c in BENCH["configs"] if c["name"] == "phi4_mini_flash")
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cfg["file"] == "benchmark/configs/phi4_mini_flash.json"
    # the cells that were there, in their order, ahead of it
    names = [w["name"] for w in BENCH["workloads"]]
    assert names[:names.index(CELL)] == [
        "gcn_arxiv.w1", "graphcast_small.w1", "gcn_papers100m.w4",
        "ouro_2p6b.seq8k", "sdar_30b_a3b.bd8k", "lfm2_8b_a1b.seq16k"]
    # the traffic mix is the one the benchmark had
    with open(os.path.join(ROOT, "benchmark", "traffic", "seq8k.json")) as f:
        assert json.load(f) == {"world_size": 1, "seq_len": 8192, "batches": 8,
                                "zipf_exponent": 1.0}
    # the three numbers the cell limits; a frozen step reads 1 on the second
    with open(os.path.join(ROOT, "benchmark", "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == set(limits["tiny_limits"]) == {
        "loss_gap", "delta_norm_gap", "grad_diff_gap"}
    assert limits["limits"]["delta_norm_gap"]["limit"] < 1


def test_traced_rehearsal_reads_the_programs_own_spans_and_counters():
    out, result = run_cell(CELL, trace=1, seed=2**31 + 39)
    assert out.returncode == 0, out.stderr[-2000:]
    assert result["correct"] is True
    got = {n.split(".", 1)[1]: v["value"] for n, v in result["metrics"].items()}
    for name in ("init_s", "compile_s", "placement_s", "dispatch_ms.fed",
                 "host_feed_ms.fed", "eval_ms.fed"):
        assert got[name] > 0, name
    # 128 tokens, window 8: (36 + 120 * 8 + 2 * 8256) of 3 * 128^2 pairs
    assert abs(got["win_attn_tile_fill_pct.fed"] - 100 * 17508 / 49152) < 1e-6
    assert "attention=dense" in out.stdout
    assert "layers_by_kind={'conv': 0, 'attention': 3, 'dense_ffn': 6, " \
        "'expert_ffn': 0, 'ssm': 2, 'gmu': 1, 'window': 1, 'cross': 1}" \
        in out.stdout
    assert "attention_mask=window+causal" in out.stdout
    assert "program counters: attn.mask_pairs=17508 attn.tile_pairs=49152" \
        in out.stdout


def test_a_frozen_step_and_the_float8_control_are_not_correct():
    for extra in (("--break-step", "frozen"), ("--control", "1")):
        out, result = run_cell(CELL, *extra, seed=2**31 + 40)
        assert out.returncode == 0, out.stderr[-2000:]
        assert result["correct"] is False, extra


def op(scope, name, category, dur):
    return xtrace.Op(name, scope, category, 0.0, dur)


def test_the_device_classes_part_a_step():
    lp = "jit(lm_train_step)/jvp(LoopLM.hidden)/while/body/closed_call/stack/" \
        "dgraph.lm.loop_pass/while/body/closed_call/layers_0"
    back = lambda s: s.replace("jvp(LoopLM.hidden)",
                               "transpose(jvp(LoopLM.hidden))")
    ssm = lp + "/ssm/dgraph.lm.ssm"
    gmu = lp.replace("layers_0", "layers_4") + "/gmu/dgraph.lm.gmu"
    att = lp.replace("layers_0", "layers_1")
    ops = [
        op("", "while.249", "while", 900.0),  # spans everything below it
        op(ssm + "/scan/chunks/while", "while.31", "while", 55.0),  # a container
        op(ssm + "/in_proj/in_proj/dot_general", "fusion.1",
           "convolution fusion", 60.0),
        op(ssm + "/conv/mul", "fusion.2", "loop fusion", 6.0),
        op(ssm + "/dt_bc/x_proj/dot_general", "fusion.3", "convolution fusion", 4.0),
        op(ssm + "/scan/chunks/while/body/closed_call/mul", "fusion.4",
           "loop fusion", 50.0),
        op(ssm + "/scan/carry/exp", "fusion.5", "loop fusion", 5.0),
        op(back(ssm + "/scan/while/body/while/body/mul"), "fusion.6",
           "loop fusion", 120.0),
        op(back(ssm + "/scan/while"), "while.77", "while", 121.0),  # a container
        op(back(ssm + "/out_proj/out_proj/dot_general"), "fusion.7",
           "convolution fusion", 30.0),
        op(ssm + "/gate/mul", "fusion.8", "loop fusion", 3.0),
        op(gmu + "/in_proj/dot_general", "fusion.9", "convolution fusion", 20.0),
        op(gmu + "/mul", "fusion.10", "loop fusion", 2.0),
        op(att + "/dgraph.comm.seq_attention/vmap(jit(splash))/pallas_call",
           "splash_mqa_fwd.3", "custom-call", 40.0),
        op(att + "/dgraph.lm.diff/sub", "fusion.11", "loop fusion", 4.0),
        op(att + "/qkv_proj/dot_general", "fusion.12", "convolution fusion", 25.0),
        op(back(lp + "/layers_0.ffn/gate_up_proj/dot_general"), "fusion.13",
           "convolution fusion", 200.0),
        op("jit(lm_train_step)/jvp(dgraph.lm.exit_loss)/while/body/dgraph.lm.head/dot_general",
           "fusion.14", "convolution fusion", 35.0),
        op("jit(lm_train_step)/dgraph.lm.optimizer/add", "fusion.15",
           "loop fusion", 9.0),
    ]
    step = xtrace.Span("bench_step.fed", -1.0, 3000.0)
    trace = xtrace.Trace({"/device:TPU:0": ops}, [], {
        "fed": {"span": step, "steps": [step]}})
    run = types.SimpleNamespace(trace=trace, say=lambda m: None)
    read = lambda name: scope_time.reduce(run, spec(name)["params"])
    assert read("ssm_ms.fed") == (60 + 6 + 4 + 50 + 5 + 120 + 30 + 3) * 1e3
    assert read("ssm_scan_ms.fed") == (50 + 5 + 120) * 1e3  # no container twice
    assert read("gmu_ms.fed") == 22e3
    assert read("attn_ms.fed") == 40e3
    assert read("phi4_dense_ms.fed") == 225e3  # not the mixers', not the head's
    assert read("exit_loss_ms.fed") == 35e3
    hit = lambda name: [o.name for o in ops if scope_time.matcher(
        spec(name)["params"])(o)]
    assert hit("ssm_scan_roofline.fed") == ["fusion.4", "fusion.5", "fusion.6"]
    assert hit("ssm_proj_roofline.fed") == ["fusion.1", "fusion.3", "fusion.7",
                                            "fusion.9"]
    assert hit("phi4_attn_roofline.fed") == ["splash_mqa_fwd.3"]
    other = scope_rest.reduce(run, spec("phi4_other_ms.fed")["params"])
    assert other == 13e3  # the difference's own work + the optimizer
    leaves = sum(o.dur for o in ops if o.category != "while")
    assert read("ssm_ms.fed") + read("gmu_ms.fed") + read("attn_ms.fed") \
        + read("phi4_dense_ms.fed") + read("exit_loss_ms.fed") + other \
        == leaves * 1e3


def test_the_rooflines_name_work_files_that_read_the_cells_info():
    info = {"seq_len": 8192, "heads": 40, "head_dim": 64, "hidden": 2560,
            "window": 512, "ssm_inner": 5120, "ssm_state": 16,
            "ssm_dt_rank": 160, "layers_ssm": 2, "layers_gmu": 1,
            "layers_window": 1, "layers_full": 2, "compute_bytes": 2}
    for name in NEW:
        params = spec(name)["params"]
        if "work" in params:
            assert opsbytes.work(params["work"], info, 0) > 0
            assert params["peak"] in opsbytes.device_peaks("TPU v5 lite")
    # the scan is bounded by bytes, the others by operations
    assert spec("ssm_scan_roofline.fed")["params"]["peak"] == "hbm_gbps"
    # by hand: 8 streams a layer (3 in the compute dtype, 5 float32), 2 layers
    assert opsbytes.work("phi4_ssm_scan_bytes", info, 0) \
        == 2 * 8192 * 5120 * (3 * 2 + 5 * 4)
    assert opsbytes.work("phi4_ssm_scan_bytes", info, 0) / 819e9 < 0.003
    # the windowed layer's pairs are a sixteenth of a full layer's, about
    w = opsbytes.work("phi4_attn_flops", dict(info, layers_full=0), 0)
    f = opsbytes.work("phi4_attn_flops", dict(info, layers_window=0), 0)
    assert 0.11 < w / (f / 2) < 0.13
    assert f == 3 * 2 * (8192 * 8193 // 2) * 40 * 384


def test_a_program_without_the_counters_gives_nothing(monkeypatch):
    from dgraph_tpu.obs import metrics

    monkeypatch.setattr(metrics, "default_registry", metrics.Metrics())
    run = types.SimpleNamespace(say=lambda m: None)
    assert program_counter_ratio.reduce(
        run, spec("win_attn_tile_fill_pct.fed")["params"]) is None
    # and a trace without the scopes (the parent's) gives no time to read
    ops = [op("jit(lm_train_step)/dgraph.lm.optimizer/add", "fusion.1",
              "loop fusion", 1.0)]
    step = xtrace.Span("bench_step.fed", -1.0, 10.0)
    trace = xtrace.Trace({"/device:TPU:0": ops}, [], {
        "fed": {"span": step, "steps": [step]}})
    run = types.SimpleNamespace(trace=trace, say=lambda m: None, info={},
                                device_kind="TPU v5 lite")
    from benchmark.reducers import roofline

    for name in NEW:
        s = spec(name)
        if s["reducer"] == "scope_time":
            assert scope_time.reduce(run, s["params"]) is None, name
        if s["reducer"] == "roofline":
            assert roofline.reduce(run, s["params"]) is None, name
