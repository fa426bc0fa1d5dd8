"""The cell of the hybrid Mamba-2 / expert decoder: its entries in
``BENCHMARK.json`` are additions, the cell is found by new files alone, the
traced CPU rehearsal prints the metrics a CPU run can read (the program's
stages, spans and counters; the device-trace ones need a chip), the device
classes part the operations of a step without counting anything twice, the
work functions by hand at the published sizes, and a frozen step, a dropped
row and the float8 control are not correct. Nothing here is pinned to "the
last workload": a later cell may follow this one."""

import json
import os
import types

from benchmark import opsbytes, xtrace
from benchmark.reducers import (program_counter_ratio, roofline, scope_rest,
                                scope_time)
from benchmark.tests.test_rehearsal import BENCH, ROOT, run_cell

CELL = "nemotron3_nano_30b_a3b.seq8k"
CONFIG = "nemotron3_nano_30b_a3b"
NEW = ("ssd_ms.fed", "ssd_core_ms.fed", "ssd_conv_ms.fed", "moe_shared_ms.fed",
       "nemotron_dense_ms.fed", "nemotron_other_ms.fed",
       "ssd_proj_roofline.fed", "ssd_core_roofline.fed",
       "nemotron_moe_gmm_roofline.fed", "nemotron_attn_roofline.fed")
SHARED = ("fed_step_ms", "placement_s", "compile_s", "init_s", "selfcheck_s",
          "dispatch_ms.fed", "host_feed_ms.fed", "eval_ms.fed",
          "device_idle_pct.fed", "attn_ms.fed", "exit_loss_ms.fed",
          "moe_ms.fed", "moe_rows_here_pct.fed", "moe_tile_fill_pct.fed",
          "moe_dispatch_ms.fed", "moe_combine_ms.fed", "moe_router_ms.fed",
          "moe_buffer_fill_pct.fed")


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
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "seq8k",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    cfg = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    # the cells that were there, in their order, ahead of it
    names = [w["name"] for w in BENCH["workloads"]]
    assert names[:names.index(CELL)] == [
        "gcn_arxiv.w1", "graphcast_small.w1", "gcn_papers100m.w4",
        "ouro_2p6b.seq8k", "sdar_30b_a3b.bd8k", "lfm2_8b_a1b.seq16k",
        "phi4_mini_flash.seq8k"]
    # one four-chip cell of eight: 8 x 25 % = 2 places, one taken
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
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
    # builder, reference and work functions are found by name
    for path in (f"builders/nemotron_h.py", "reference/nemotron_h.py",
                 "work/nemotron_ssd_proj_flops.py",
                 "work/nemotron_ssd_core_flops.py",
                 "work/nemotron_moe_flops.py", "work/nemotron_attn_flops.py"):
        assert os.path.exists(os.path.join(ROOT, "benchmark", path)), path


def test_traced_rehearsal_reads_the_programs_own_spans_and_counters():
    out, result = run_cell(CELL, trace=1, seed=2**31 + 42)
    assert out.returncode == 0, out.stderr[-2000:]
    assert result["correct"] is True
    got = {n.split(".", 1)[1]: v["value"] for n, v in result["metrics"].items()}
    for name in ("init_s", "compile_s", "placement_s", "dispatch_ms.fed",
                 "host_feed_ms.fed", "eval_ms.fed"):
        assert got[name] > 0, name
    # 4 of 16 experts held, 2 a token: about a quarter of the routes, in a
    # 256-row buffer (the worst case 128 x 2) that nothing can overflow
    assert 5 < got["moe_rows_here_pct.fed"] < 60
    assert 0 < got["moe_buffer_fill_pct.fed"] <= 100
    assert 0 < got["moe_tile_fill_pct.fed"] <= 100
    assert "attention=dense" in out.stdout
    assert "layers_by_kind={'conv': 0, 'attention': 1, 'dense_ffn': 0, " \
        "'expert_ffn': 4, 'ssd': 4, 'mixer_only': 5, 'experts_only': 4}" \
        in out.stdout
    assert "moe_shared_width=64" in out.stdout
    assert "rows_dropped=0" in out.stdout
    # every metric the cell declares that a CPU run can read is on the line
    declared = {m["name"] for m in BENCH["per_layer"]
                if CELL in m.get("workloads", [])}
    cpu_readable = {m["name"] for m in BENCH["per_layer"]
                    if CELL in m.get("workloads", [])
                    and m["source"] != "device_trace"} - {"selfcheck_s"}
    assert cpu_readable <= set(got) <= declared | {"fed_step_ms"}


def test_a_frozen_step_a_dropped_row_and_the_float8_control_are_not_correct():
    for extra in (("--break-step", "frozen"), ("--break-step", "dropped"),
                  ("--control", "1")):
        out, result = run_cell(CELL, *extra, seed=2**31 + 43)
        assert out.returncode == 0, out.stderr[-2000:]
        assert result["correct"] is False, extra


def op(scope, name, category, dur):
    return xtrace.Op(name, scope, category, 0.0, dur)


def test_the_device_classes_part_a_step():
    lp = "jit(lm_train_step)/jvp(LoopLM.hidden)/while/body/closed_call/stack/" \
        "dgraph.lm.loop_pass/while/body/closed_call/layers_0"
    back = lambda s: s.replace("jvp(LoopLM.hidden)",
                               "transpose(jvp(LoopLM.hidden))")
    ssd = lp + "/ssd/dgraph.lm.ssd"
    moe = lp.replace("layers_0", "layers_1") + "/experts/dgraph.lm.moe"
    att = lp.replace("layers_0", "layers_5")
    ops = [
        op("", "while.249", "while", 900.0),  # spans everything below it
        op(ssd + "/state/while", "while.31", "while", 9.0),  # a container
        op(ssd + "/in_proj/in_proj/dot_general", "fusion.1",
           "convolution fusion", 60.0),
        op(ssd + "/conv/mul", "fusion.2", "loop fusion", 6.0),
        op(ssd + "/softplus", "fusion.3", "loop fusion", 1.0),
        op(ssd + "/chunk/exp", "fusion.4", "loop fusion", 7.0),
        op(ssd + "/chunk/dot_general", "fusion.5", "convolution fusion", 5.0),
        op(ssd + "/state/while/body/closed_call/mul", "fusion.6",
           "loop fusion", 8.0),
        op(back(ssd + "/state/dot_general"), "fusion.7", "convolution fusion",
           4.0),
        op(ssd + "/norm/mul", "fusion.8", "loop fusion", 3.0),
        op(back(ssd + "/out_proj/out_proj/dot_general"), "fusion.9",
           "convolution fusion", 30.0),
        op(moe + "/router/router/dot_general", "fusion.10",
           "convolution fusion", 2.0),
        op(moe + "/experts/pallas_call", "gmm.3", "custom-call", 11.0),
        op(moe + "/experts/select_n", "fusion.11", "loop fusion", 4.0),
        op(moe + "/combine/gather", "fusion.12", "loop fusion", 6.0),
        op(moe + "/shared/shared_up_proj/dot_general", "fusion.13",
           "convolution fusion", 20.0),
        op(back(moe + "/shared/integer_pow"), "fusion.14", "loop fusion", 1.5),
        op(att + "/dgraph.comm.seq_attention/pallas_call",
           "flash_attention_fwd.3", "custom-call", 40.0),
        op(att + "/q_proj/dot_general", "fusion.15", "convolution fusion", 25.0),
        op("jit(lm_train_step)/jvp(dgraph.lm.exit_loss)/while/body/dgraph.lm.head/dot_general",
           "fusion.16", "convolution fusion", 35.0),
        op("jit(lm_train_step)/dgraph.lm.optimizer/add", "fusion.17",
           "loop fusion", 9.0),
    ]
    step = xtrace.Span("bench_step.fed", -1.0, 3000.0)
    trace = xtrace.Trace({"/device:TPU:0": ops}, [], {
        "fed": {"span": step, "steps": [step]}})
    run = types.SimpleNamespace(trace=trace, say=lambda m: None)
    read = lambda name: scope_time.reduce(run, spec(name)["params"])
    assert read("ssd_ms.fed") == (60 + 6 + 1 + 7 + 5 + 8 + 4 + 3 + 30) * 1e3
    assert read("ssd_core_ms.fed") == (7 + 5 + 8 + 4) * 1e3  # no container
    assert read("ssd_conv_ms.fed") == 6e3
    assert read("moe_ms.fed") == (2 + 11 + 4 + 6 + 20 + 1.5) * 1e3
    assert read("moe_shared_ms.fed") == 21.5e3
    assert read("moe_router_ms.fed") == 2e3
    assert read("attn_ms.fed") == 40e3
    assert read("nemotron_dense_ms.fed") == 25e3  # q, k, v, o alone
    assert read("exit_loss_ms.fed") == 35e3
    hit = lambda name: [o.name for o in ops if scope_time.matcher(
        spec(name)["params"])(o)]
    assert hit("ssd_proj_roofline.fed") == ["fusion.1", "fusion.9"]
    assert hit("ssd_core_roofline.fed") == ["fusion.4", "fusion.5", "fusion.6",
                                            "fusion.7"]
    assert hit("nemotron_moe_gmm_roofline.fed") == ["gmm.3"]  # not the shared
    assert hit("nemotron_attn_roofline.fed") == ["flash_attention_fwd.3"]
    other = scope_rest.reduce(run, spec("nemotron_other_ms.fed")["params"])
    assert other == 9e3  # the optimizer
    leaves = sum(o.dur for o in ops if o.category != "while")
    assert read("ssd_ms.fed") + read("moe_ms.fed") + read("attn_ms.fed") \
        + read("nemotron_dense_ms.fed") + read("exit_loss_ms.fed") + other \
        == leaves * 1e3


def test_the_work_functions_at_the_published_sizes_by_hand(monkeypatch):
    """The issue's arithmetic: 154.8 M Mamba-2 projection weights, 9.98 M a
    routed expert, 19.96 M the shared one, 23.4 M attention."""
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        s = json.load(f)["sizes"]
    info = {"seq_len": 8192, "hidden": s["hidden_size"],
            "heads": s["num_attention_heads"], "head_dim": s["head_dim"],
            "ssd_heads": s["mamba_num_heads"],
            "ssd_head_dim": s["mamba_head_dim"], "ssd_groups": s["n_groups"],
            "ssd_state": s["ssm_state_size"], "ssd_chunk": s["chunk_size"],
            "expert_width": s["moe_intermediate_size"],
            "shared_width": s["moe_shared_expert_intermediate_size"],
            "experts_per_token": s["num_experts_per_tok"], "layers_ssd": 4,
            "layers_attention": 1, "layers_expert_ffn": 4, "loop_steps": 1}
    from benchmark.work import nemotron_moe_flops, nemotron_ssd_proj_flops

    d = info["hidden"]
    assert round(4 * nemotron_ssd_proj_flops.weights(info) / 1e6, 1) == 154.8
    assert round(nemotron_moe_flops.weights(info) / 1e6, 2) == 9.98
    assert round(2 * d * info["shared_width"] / 1e6, 2) == 19.96
    attn = d * (info["heads"] + 2 * s["num_key_value_heads"]) \
        * info["head_dim"] + info["heads"] * info["head_dim"] * d
    assert round(attn / 1e6, 1) == 23.4
    for name in NEW:
        params = spec(name)["params"]
        if "work" in params:
            assert params["peak"] in opsbytes.device_peaks("TPU v5 lite")
    assert opsbytes.work("nemotron_ssd_proj_flops", info, 0) \
        == 3 * 2 * 8192 * 4 * (2688 * 10304 + 4096 * 2688)
    # the chunked products: 0.27 TFLOP a step, 1.4 ms at the bf16 peak
    core = opsbytes.work("nemotron_ssd_core_flops", info, 0)
    assert core == 3 * 4 * 8192 * (64.5 * 2 * 5120 + 4 * 64 * 64 * 128)
    assert 0.001 < core / 197e12 < 0.002
    assert opsbytes.work("nemotron_attn_flops", info, 0) \
        == 3 * 2 * 8192 * 8192 * 32 * 128
    # the routed experts' work follows the rows the program counted
    from dgraph_tpu.obs import metrics

    reg = metrics.Metrics()
    monkeypatch.setattr(metrics, "default_registry", reg)
    assert opsbytes.work("nemotron_moe_flops", info, 0) == 0.0
    reg.counter("moe.rows_routed", 10 * 8192 * 6 * 4)
    reg.counter("moe.rows_here", 10 * 3072 * 4)  # a sixteenth
    assert opsbytes.work("nemotron_moe_flops", info, 0) \
        == 3 * 2 * (4 * 3072) * 2 * 2688 * 1856


def test_a_program_without_the_scopes_gives_nothing(monkeypatch):
    from dgraph_tpu.obs import metrics

    monkeypatch.setattr(metrics, "default_registry", metrics.Metrics())
    run = types.SimpleNamespace(say=lambda m: None)
    assert program_counter_ratio.reduce(
        run, spec("moe_rows_here_pct.fed")["params"]) is None
    # a trace without the scopes (the parent's) gives no time to read
    ops = [op("jit(lm_train_step)/dgraph.lm.optimizer/add", "fusion.1",
              "loop fusion", 1.0)]
    step = xtrace.Span("bench_step.fed", -1.0, 10.0)
    trace = xtrace.Trace({"/device:TPU:0": ops}, [], {
        "fed": {"span": step, "steps": [step]}})
    run = types.SimpleNamespace(trace=trace, say=lambda m: None, info={},
                                device_kind="TPU v5 lite")
    for name in NEW:
        s = spec(name)
        if s["reducer"] == "scope_time":
            assert scope_time.reduce(run, s["params"]) is None, name
        if s["reducer"] == "roofline":
            assert roofline.reduce(run, s["params"]) is None, name
