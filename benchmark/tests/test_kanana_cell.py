"""The cell of the decoder that attends through a latent with a decoupled
rotary key (one leading dense layer, then sigmoid-routed SwiGLU experts beside
a shared expert): its entries in ``BENCHMARK.json`` are additions, the cell is
found by new files alone, the traced CPU rehearsal prints the metrics a CPU
run can read (the program's stages, spans and counters; the device-trace ones
need a chip), the device classes part the operations of a step and tell the
latent's own work from the plain projections', the work functions by hand at
the published sizes (the case ISSUE 49 asked of ``test_opsbytes.py``, here
because no file the benchmark had is edited), and a frozen step, a dropped
row and the float8 control are not correct. Nothing here is pinned to "the
last workload": a later cell may follow this one."""

import json
import os
import types

from benchmark import opsbytes, xtrace
from benchmark.reducers import roofline, scope_rest, scope_time
from benchmark.tests.test_rehearsal import BENCH, ROOT, run_cell

CELL = "kanana2_30b_a3b.seq16k"
CONFIG = "kanana2_30b_a3b"
NEW = ("mla_proj_ms.fed", "mla_attn_roofline.fed", "kanana_shared_ms.fed",
       "kanana_other_ms.fed")
SHARED = ("fed_step_ms", "placement_s", "compile_s", "init_s", "selfcheck_s",
          "dispatch_ms.fed", "host_feed_ms.fed", "eval_ms.fed",
          "device_idle_pct.fed", "attn_ms.fed", "exit_loss_ms.fed",
          "moe_ms.fed", "moe_rows_here_pct.fed", "moe_tile_fill_pct.fed",
          "moe_dispatch_ms.fed", "moe_combine_ms.fed", "moe_router_ms.fed",
          "moe_buffer_fill_pct.fed", "moe_buffer_used_pct.fed",
          # other cells', under the names they have: the same scopes, the
          # same work (SDAR's projections outside attention and the experts;
          # LFM2's grouped products, counted over the EXPERT layers only: the
          # leading dense layer routes nothing)
          "sdar_dense_ms.fed", "lfm2_moe_gmm_roofline.fed")


def spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_the_new_metrics_are_declared_for_the_cell_alone():
    rows = {m["name"]: m for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in NEW:
        assert rows[name]["workloads"] == [CELL]
        assert rows[name]["moves"] == "fed_step_ms"
        assert rows[name]["source"] == "device_trace"
        assert spec(name)["name"] == name
        if name.endswith("_roofline.fed"):
            assert rows[name]["unit"] == "%" and rows[name]["better"] == "higher"
    # layers under the names the benchmark already had
    for name in NEW[:2]:
        assert rows[name]["layer"] == rows["attn_ms.fed"]["layer"]
    # the shared expert is reported where it runs, by the scope Nemotron's
    # moe_shared_ms.fed reads, under a name of this cell's own: that
    # metric's list is pinned to its cell by benchmark/tests/
    # test_nemotron_h_cell.py, a file this PR may not edit
    assert rows["kanana_shared_ms.fed"]["layer"] \
        == rows["moe_shared_ms.fed"]["layer"]
    assert spec("kanana_shared_ms.fed")["params"] \
        == spec("moe_shared_ms.fed")["params"]
    assert rows["kanana_other_ms.fed"]["layer"] \
        == rows["smallthinker_other_ms.fed"]["layer"]
    names = [w["name"] for w in BENCH["workloads"]]
    earlier = names[:names.index(CELL)]
    for name in SHARED:  # appended to the lists that were there
        cells = rows[name]["workloads"]
        assert CELL in cells, name
        assert all(cells.index(c) < cells.index(CELL)
                   for c in cells if c in earlier), name
    # SDAR's roofline of the grouped products counts a routed row in EVERY
    # layer of info["layers"]: a fifth too many here, so the cell is not on it
    assert CELL not in rows["moe_gmm_roofline.fed"]["workloads"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "seq16k",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "16x" in cell["why"]
    cfg = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(cfg["why"]) <= 200
    # the cells that were there, in their order, ahead of it
    assert earlier[:9] == [
        "gcn_arxiv.w1", "graphcast_small.w1", "gcn_papers100m.w4",
        "ouro_2p6b.seq8k", "sdar_30b_a3b.bd8k", "lfm2_8b_a1b.seq16k",
        "phi4_mini_flash.seq8k", "nemotron3_nano_30b_a3b.seq8k",
        "smallthinker_21b_a3b.seq16k"]
    # still one four-chip cell
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    # the traffic mix is the one the benchmark had (lfm2_8b_a1b's)
    with open(os.path.join(ROOT, "benchmark", "traffic", "seq16k.json")) as f:
        assert json.load(f) == {"world_size": 1, "seq_len": 16384,
                                "batches": 8, "zipf_exponent": 1.0}
    # the three numbers the cell limits; a frozen step reads 1 on the second
    with open(os.path.join(ROOT, "benchmark", "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == set(limits["tiny_limits"]) == {
        "loss_gap", "delta_norm_gap", "grad_diff_gap"}
    assert limits["limits"]["delta_norm_gap"]["limit"] < 1
    # builder, reference and work functions are found by name
    for path in ("builders/kanana.py", "reference/kanana.py",
                 "work/kanana_attn_flops.py"):
        assert os.path.exists(os.path.join(ROOT, "benchmark", path)), path


def test_traced_rehearsal_reads_the_programs_own_spans_and_counters():
    out, result = run_cell(CELL, trace=1, seed=2**31 + 49)
    assert out.returncode == 0, out.stderr[-2000:]
    assert result["correct"] is True
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    got = {n.split(".", 1)[1]: v["value"] for n, v in result["metrics"].items()}
    # what the parametrised cases of test_rehearsal.py and
    # test_program_metrics.py ask of a cell, less the graph cells' own
    # (plan_build_s; ISSUE 26's seven, of which a sequence cell has init_s)
    for name in ("init_s", "compile_s", "placement_s", "dispatch_ms.fed",
                 "host_feed_ms.fed", "eval_ms.fed"):
        assert got[name] > 0, name
    # 4 of 16 experts held, 2 a token: about a quarter of the routes, in a
    # buffer whose last rung (the worst case 128 x 2) nothing can overflow
    assert 5 < got["moe_rows_here_pct.fed"] < 60
    assert 0 < got["moe_buffer_fill_pct.fed"] <= 100
    assert 0 < got["moe_tile_fill_pct.fed"] <= 100
    assert 0 < got["moe_buffer_used_pct.fed"] <= 100
    assert "attention=dense" in out.stdout
    assert "layers_by_kind={'conv': 0, 'attention': 3, 'dense_ffn': 1, " \
        "'expert_ffn': 2, 'attn_mla': 3}" in out.stdout
    assert "latent_attention={'qk_head_dim': 24, 'kv_rank': 32, " \
        "'rope_dim': 8}" in out.stdout
    assert "moe_shared_width=64" in out.stdout
    assert "rows_dropped=0" in out.stdout
    # the router's choices over the two expert layers, beside the reference's
    assert "by layer: " in out.stdout
    # every metric the cell declares that a CPU run can read is on the line,
    # and none of another cell's
    declared = {m["name"] for m in BENCH["per_layer"]
                if CELL in m.get("workloads", [])}
    cpu_readable = {m["name"] for m in BENCH["per_layer"]
                    if CELL in m.get("workloads", [])
                    and m["source"] != "device_trace"} - {"selfcheck_s"}
    assert cpu_readable <= set(got) <= declared | {"fed_step_ms"}


def test_a_frozen_step_a_dropped_row_and_the_float8_control_are_not_correct():
    for extra in (("--break-step", "frozen"), ("--break-step", "dropped"),
                  ("--control", "1")):
        out, result = run_cell(CELL, *extra, seed=2**31 + 50)
        assert out.returncode == 0, out.stderr[-2000:]
        assert result["correct"] is False, extra


def op(scope, name, category, dur):
    return xtrace.Op(name, scope, category, 0.0, dur)


def test_the_device_classes_part_a_step():
    lp = "jit(lm_train_step)/jvp(LoopLM.hidden)/while/body/closed_call/stack/" \
        "dgraph.lm.loop_pass/while/body/closed_call/layers_1"
    back = lambda s: s.replace("jvp(LoopLM.hidden)",
                               "transpose(jvp(LoopLM.hidden))")
    att = lp + "/layers_1.attend_latent"
    moe = lp + "/experts/dgraph.lm.moe"
    dense0 = lp.replace("layers_1", "layers_0")
    ops = [
        op("", "while.249", "while", 900.0),  # spans everything below it
        op(att + "/q_proj/dot_general", "fusion.1", "convolution fusion", 25.0),
        op(att + "/dgraph.lm.rotary/select_n", "fusion.2", "loop fusion", 3.0),
        # the latent's own work: down, the norm, up, the one rotary key, the
        # broadcast and the concatenation
        op(att + "/dgraph.lm.mla_down/kv_a_proj/dot_general", "fusion.3",
           "convolution fusion", 4.0),
        op(att + "/dgraph.lm.mla_down/kv_a_norm/mul", "fusion.4",
           "loop fusion", 0.5),
        op(att + "/dgraph.lm.mla_up/kv_b_proj/dot_general", "fusion.5",
           "convolution fusion", 9.0),
        op(att + "/dgraph.lm.mla_up/dgraph.lm.rotary/select_n", "fusion.6",
           "loop fusion", 0.25),
        op(att + "/dgraph.lm.mla_up/concatenate", "fusion.7", "loop fusion",
           2.0),
        op(att + "/dgraph.comm.seq_attention/transpose", "fusion.8",
           "loop fusion", 1.0),
        op(att + "/dgraph.comm.seq_attention/pallas_call", "splash_mha_fwd.3",
           "custom-call", 26.0),
        op(back(att + "/dgraph.comm.seq_attention/pallas_call"),
           "splash_mha_dkv.3", "custom-call", 40.0),
        op(back(att + "/dgraph.comm.seq_attention/pallas_call"),
           "splash_mha_dq.3", "custom-call", 36.0),
        op(att + "/o_proj/dot_general", "fusion.9", "convolution fusion", 17.0),
        # the leading dense layer's MLP
        op(dense0 + "/gate_proj/dot_general", "fusion.10",
           "convolution fusion", 30.0),
        # an expert layer's half: router, the rung taken, the shared expert
        op(moe + "/router/router/dot_general", "fusion.11",
           "convolution fusion", 2.0),
        op(moe + "/routes/sort", "sort.1", "sort", 3.0),
        op(moe + "/cond/branch_0_fun/jit(_rung)/dispatch/gather", "fusion.12",
           "loop fusion", 4.0),
        op(moe + "/cond/branch_0_fun/jit(_rung)/experts/jit(gmm)/pallas_call",
           "gmm.3", "custom-call", 11.0),
        op(moe + "/cond/branch_0_fun/jit(_rung)/combine/gather", "fusion.13",
           "loop fusion", 6.0),
        op(moe + "/shared/shared_up_proj/dot_general", "fusion.14",
           "convolution fusion", 8.0),
        op("jit(lm_train_step)/jvp(dgraph.lm.exit_loss)/while/body/dgraph.lm.head/dot_general",
           "fusion.15", "convolution fusion", 35.0),
        op("jit(lm_train_step)/dgraph.lm.optimizer/add", "fusion.16",
           "loop fusion", 9.0),
    ]
    step = xtrace.Span("bench_step.fed", -1.0, 3000.0)
    trace = xtrace.Trace({"/device:TPU:0": ops}, [], {
        "fed": {"span": step, "steps": [step]}})
    run = types.SimpleNamespace(trace=trace, say=lambda m: None)
    read = lambda name: scope_time.reduce(run, spec(name)["params"])
    assert read("attn_ms.fed") == (1 + 26 + 40 + 36) * 1e3
    assert read("mla_proj_ms.fed") == (4 + 0.5 + 9 + 0.25 + 2) * 1e3
    assert read("moe_ms.fed") == (2 + 3 + 4 + 11 + 6 + 8) * 1e3
    assert read("kanana_shared_ms.fed") == 8e3
    assert read("moe_router_ms.fed") == 2e3
    # the plain projections AND the latent's two products: q, kv_a, kv_b, o
    # and the dense MLP (the shared expert's are the expert layer's)
    assert read("sdar_dense_ms.fed") == (25 + 4 + 9 + 17 + 30) * 1e3
    assert read("exit_loss_ms.fed") == 35e3
    hit = lambda name: [o.name for o in ops if scope_time.matcher(
        spec(name)["params"])(o)]
    assert hit("lfm2_moe_gmm_roofline.fed") == ["gmm.3"]
    assert hit("mla_attn_roofline.fed") == [
        "fusion.8", "splash_mha_fwd.3", "splash_mha_dkv.3", "splash_mha_dq.3"]
    other = scope_rest.reduce(run, spec("kanana_other_ms.fed")["params"])
    assert other == (3 + 9) * 1e3  # q's rotary embedding and the optimizer
    # the classes part the step: the latent's scopes less the two products
    # that are dense projections too
    leaves = sum(o.dur for o in ops if o.category != "while")
    assert read("attn_ms.fed") + read("moe_ms.fed") \
        + read("sdar_dense_ms.fed") + (read("mla_proj_ms.fed") - 13e3) \
        + read("exit_loss_ms.fed") + other == leaves * 1e3


def test_the_work_functions_at_the_published_sizes_by_hand(monkeypatch):
    """The issue's arithmetic: 134 225 920 causal pairs at 16 384 tokens; a
    forward ``2 P H (192 + 128)`` = 2.75 TFLOP a layer, the backward ``2 P H
    (3 x 192 + 2 x 128)`` = 7.15, the forward twice under remat: 12.65 a
    layer, 63.2 a step; and the padded form does not change it."""
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        s = json.load(f)["sizes"]
    info = {"seq_len": 16384, "rows": 16384, "hidden": s["hidden_size"],
            "heads": s["num_attention_heads"],
            "qk_head_dim": s["qk_head_dim"], "v_head_dim": s["v_head_dim"],
            "expert_width": s["moe_intermediate_size"],
            "experts_per_token": s["num_experts_per_tok"],
            "layers": 5, "layers_attention": 5, "layers_expert_ffn": 4,
            "loop_steps": 1, "remat": True}
    from benchmark.work import kanana_attn_flops

    P, H = 134225920, 32
    assert kanana_attn_flops.pairs(info) == P
    fwd, bwd = 2 * P * H * (192 + 128), 2 * P * H * (3 * 192 + 2 * 128)
    assert (round(fwd / 1e12, 2), round(bwd / 1e12, 2)) == (2.75, 7.15)
    attn = opsbytes.work("kanana_attn_flops", info, 0)
    assert attn == 5 * (2 * fwd + bwd)
    assert opsbytes.work("kanana_attn_flops", dict(info, remat=False), 0) \
        == 5 * (fwd + bwd)
    # at a small T by hand, and with the kernels' q and k zero-padded: the
    # model's 192 is counted, so padding lowers the share and cannot raise it
    small = dict(info, seq_len=8, heads=3, layers_attention=2)
    by_hand = 2 * 36 * 3 * 2 * ((192 + 128) * 2 + 3 * 192 + 2 * 128)
    assert opsbytes.work("kanana_attn_flops", small, 0) == by_hand
    assert opsbytes.work("kanana_attn_flops",
                         dict(small, qk_padded_to=256), 9) == by_hand
    assert 0.32 < attn / 197e12 < 0.325  # 321 ms at the bf16 peak
    for name in NEW + ("lfm2_moe_gmm_roofline.fed",):
        params = spec(name)["params"]
        if "work" in params:
            assert params["peak"] in opsbytes.device_peaks("TPU v5 lite")
    # the routed experts' work follows the rows the program counted, over
    # the four EXPERT layers (the leading dense layer routes nothing)
    from dgraph_tpu.obs import metrics

    reg = metrics.Metrics()
    monkeypatch.setattr(metrics, "default_registry", reg)
    assert opsbytes.work("lfm2_moe_flops", info, 0) == 0.0
    reg.counter("moe.rows_routed", 10 * 16384 * 6 * 4)
    reg.counter("moe.rows_here", 10 * 6144 * 4)  # a sixteenth
    assert opsbytes.work("lfm2_moe_flops", info, 0) \
        == 3 * 2 * (4 * 6144) * 3 * 2048 * 768


def test_a_program_without_the_scopes_gives_nothing():
    """The parent's side of a traced run of any cell: a trace without the
    latent's scopes gives the new metrics no time to read, and they do not
    raise."""
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
