"""The cell of the decoder whose router reads its layer's input (ReGLU experts,
no shared expert, a full layer without positions among windowed ones with
rotary positions): its entries in ``BENCHMARK.json`` are additions, the cell
is found by new files alone, the traced CPU rehearsal prints the metrics a CPU
run can read (the program's stages, spans and counters; the device-trace ones
need a chip), the device classes part the operations of a step without
counting anything twice and tell the windowed layers' attention from the full
layer's, the work functions by hand at the published sizes, and a frozen step,
a dropped row and the float8 control are not correct. Nothing here is pinned
to "the last workload": a later cell may follow this one."""

import json
import os
import re
import types

from benchmark import opsbytes, xtrace
from benchmark.reducers import (program_counter_ratio, roofline, scope_rest,
                                scope_time)
from benchmark.tests.test_rehearsal import BENCH, ROOT, run_cell

CELL = "smallthinker_21b_a3b.seq16k"
CONFIG = "smallthinker_21b_a3b"
NEW = ("attn_win_ms.fed", "smallthinker_other_ms.fed",
       "smallthinker_attn_roofline.fed")
SHARED = ("fed_step_ms", "placement_s", "compile_s", "init_s", "selfcheck_s",
          "dispatch_ms.fed", "host_feed_ms.fed", "eval_ms.fed",
          "device_idle_pct.fed", "attn_ms.fed", "exit_loss_ms.fed",
          "moe_ms.fed", "moe_rows_here_pct.fed", "moe_tile_fill_pct.fed",
          "moe_dispatch_ms.fed", "moe_combine_ms.fed", "moe_router_ms.fed",
          "moe_buffer_fill_pct.fed", "moe_buffer_used_pct.fed",
          "win_attn_tile_fill_pct.fed",
          # SDAR's, under the names they have: the same scopes, the same work
          "sdar_dense_ms.fed", "moe_gmm_roofline.fed")


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
    assert rows["attn_win_ms.fed"]["layer"] == rows["attn_ms.fed"]["layer"]
    assert rows["smallthinker_attn_roofline.fed"]["layer"] \
        == rows["attn_ms.fed"]["layer"]
    names = [w["name"] for w in BENCH["workloads"]]
    earlier = names[:names.index(CELL)]
    for name in SHARED:  # appended to the lists that were there
        cells = rows[name]["workloads"]
        assert CELL in cells, name
        assert all(cells.index(c) < cells.index(CELL)
                   for c in cells if c in earlier), name
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "seq16k",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    cfg = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                              "vocab_size"]
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(cfg["why"]) <= 200
    # the cells that were there, in their order, ahead of it
    assert earlier == [
        "gcn_arxiv.w1", "graphcast_small.w1", "gcn_papers100m.w4",
        "ouro_2p6b.seq8k", "sdar_30b_a3b.bd8k", "lfm2_8b_a1b.seq16k",
        "phi4_mini_flash.seq8k", "nemotron3_nano_30b_a3b.seq8k"]
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
    for path in ("builders/smallthinker.py", "reference/smallthinker.py",
                 "work/smallthinker_attn_flops.py"):
        assert os.path.exists(os.path.join(ROOT, "benchmark", path)), path


def test_traced_rehearsal_reads_the_programs_own_spans_and_counters():
    out, result = run_cell(CELL, trace=1, seed=2**31 + 46)
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
    assert got["moe_buffer_used_pct.fed"] == 100  # one rung at this size
    # the dense oracle visits every pair of the four 128 x 128 squares
    assert abs(got["win_attn_tile_fill_pct.fed"]
               - 100 * 19056 / (4 * 128 * 128)) < 1e-6
    assert "attention=dense" in out.stdout
    assert "layers_by_kind={'conv': 0, 'attention': 4, 'dense_ffn': 0, " \
        "'expert_ffn': 4, 'window': 3, 'attn_win': 3}" in out.stdout
    assert "nope_layers=1" in out.stdout
    assert "moe_route_ahead_layers=4" in out.stdout
    assert "attention_mask=causal+window" in out.stdout
    assert "rows_dropped=0" in out.stdout
    # the seeded embedding lies on bfloat16's grid, so the first layer's
    # router, which reads its rows themselves, is handed the same numbers in
    # the program's bfloat16 stream and in the float32 reference and chooses
    # alike (a heavy token id's tie would move all its rows at once)
    by_layer = re.search(r"by layer: ([0-9. ]+)\)", out.stdout).group(1)
    assert len(by_layer.split()) == 4 and float(by_layer.split()[0]) == 0
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
        out, result = run_cell(CELL, *extra, seed=2**31 + 47)
        assert out.returncode == 0, out.stderr[-2000:]
        assert result["correct"] is False, extra


def op(scope, name, category, dur):
    return xtrace.Op(name, scope, category, 0.0, dur)


def test_the_device_classes_part_a_step():
    lp = "jit(lm_train_step)/jvp(LoopLM.hidden)/while/body/closed_call/stack/" \
        "dgraph.lm.loop_pass/while/body/closed_call/layers_0"
    back = lambda s: s.replace("jvp(LoopLM.hidden)",
                               "transpose(jvp(LoopLM.hidden))")
    full = lp + "/layers_0.attend"
    win = full.replace("layers_0", "layers_1")
    moe = lp.replace("layers_0", "layers_1") + "/experts/dgraph.lm.moe"
    ops = [
        op("", "while.249", "while", 900.0),  # spans everything below it
        # the router at the layer's top, before attention
        op(moe + "/router/router/dot_general", "fusion.1",
           "convolution fusion", 2.0),
        op(full + "/q_proj/dot_general", "fusion.2", "convolution fusion", 25.0),
        op(full + "/dgraph.comm.seq_attention/pallas_call",
           "flash_attention_fwd.3", "custom-call", 40.0),
        op(back(full + "/dgraph.comm.seq_attention/pallas_call"),
           "flash_mha_bwd_dkv.3", "custom-call", 50.0),
        op(win + "/dgraph.lm.attn_win/dgraph.comm.seq_attention/pallas_call",
           "splash_mha_fwd.3", "custom-call", 30.0),
        op(back(win + "/dgraph.lm.attn_win/dgraph.comm.seq_attention/"
                "pallas_call"), "splash_mha_dkv.3", "custom-call", 35.0),
        op(win + "/o_proj/dot_general", "fusion.3", "convolution fusion", 20.0),
        op(win + "/dgraph.lm.rotary/mul", "fusion.4", "loop fusion", 1.5),
        # the experts' half, after it: the one sort, then the rung taken
        op(moe + "/routes/sort", "sort.1", "sort", 3.0),
        op(moe + "/cond/branch_0_fun/jit(_rung)/dispatch/gather", "fusion.5",
           "loop fusion", 4.0),
        op(moe + "/cond/branch_0_fun/jit(_rung)/experts/jit(gmm)/pallas_call",
           "gmm.3", "custom-call", 11.0),
        op(moe + "/cond/branch_0_fun/jit(_rung)/experts/mul", "fusion.6",
           "loop fusion", 2.5),
        op(moe + "/cond/branch_0_fun/jit(_rung)/combine/gather", "fusion.7",
           "loop fusion", 6.0),
        op("jit(lm_train_step)/jvp(dgraph.lm.exit_loss)/while/body/dgraph.lm.head/dot_general",
           "fusion.8", "convolution fusion", 35.0),
        op("jit(lm_train_step)/dgraph.lm.optimizer/add", "fusion.9",
           "loop fusion", 9.0),
    ]
    step = xtrace.Span("bench_step.fed", -1.0, 3000.0)
    trace = xtrace.Trace({"/device:TPU:0": ops}, [], {
        "fed": {"span": step, "steps": [step]}})
    run = types.SimpleNamespace(trace=trace, say=lambda m: None)
    read = lambda name: scope_time.reduce(run, spec(name)["params"])
    assert read("attn_ms.fed") == (40 + 50 + 30 + 35) * 1e3
    assert read("attn_win_ms.fed") == (30 + 35) * 1e3  # the rest: the full layer
    assert read("moe_ms.fed") == (2 + 3 + 4 + 11 + 2.5 + 6) * 1e3
    assert read("moe_router_ms.fed") == 2e3  # where it now runs
    assert read("moe_dispatch_ms.fed") == (3 + 4) * 1e3  # the sorts and the gather
    assert read("moe_combine_ms.fed") == 6e3
    assert read("sdar_dense_ms.fed") == (25 + 20) * 1e3  # q, k, v, o
    assert read("exit_loss_ms.fed") == 35e3
    hit = lambda name: [o.name for o in ops if scope_time.matcher(
        spec(name)["params"])(o)]
    assert hit("moe_gmm_roofline.fed") == ["gmm.3"]
    assert hit("smallthinker_attn_roofline.fed") == [
        "flash_attention_fwd.3", "flash_mha_bwd_dkv.3", "splash_mha_fwd.3",
        "splash_mha_dkv.3"]
    other = scope_rest.reduce(run, spec("smallthinker_other_ms.fed")["params"])
    assert other == (1.5 + 9) * 1e3  # the rotary embedding and the optimizer
    leaves = sum(o.dur for o in ops if o.category != "while")
    assert read("attn_ms.fed") + read("moe_ms.fed") \
        + read("sdar_dense_ms.fed") + read("exit_loss_ms.fed") + other \
        == leaves * 1e3


def test_the_work_functions_at_the_published_sizes_by_hand(monkeypatch):
    """The issue's arithmetic: 20.97 M attention, 5.898 M an expert,
    58 722 304 allowed pairs under the window and 134 225 920 under the full
    mask at 16 384."""
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        s = json.load(f)["sizes"]
    info = {"seq_len": 16384, "hidden": s["hidden_size"],
            "heads": s["num_attention_heads"],
            "kv_heads": s["num_key_value_heads"], "head_dim": s["head_dim"],
            "window": s["sliding_window_size"],
            "expert_width": s["moe_ffn_hidden_size"],
            "experts_per_token": s["moe_num_active_primary_experts"],
            "layers_full": 1, "layers_window": 3, "rows": 16384, "layers": 4}
    from benchmark.work import smallthinker_attn_flops

    assert round(smallthinker_attn_flops.weights(info) / 1e6, 2) == 20.97
    # a routed expert: W_gate and W_up d -> F, W_down F -> d
    assert round(3 * info["hidden"] * info["expert_width"] / 1e6, 3) == 5.898
    assert smallthinker_attn_flops.pairs(info) == (134225920, 58722304)
    for name in NEW + ("moe_gmm_roofline.fed",):
        params = spec(name)["params"]
        if "work" in params:
            assert params["peak"] in opsbytes.device_peaks("TPU v5 lite")
    # 4 x 3584 operations an allowed pair, three forwards a step: 13.35 TFLOP
    attn = opsbytes.work("smallthinker_attn_flops", info, 0)
    assert attn == 3 * (134225920 + 3 * 58722304) * 4 * 28 * 128
    assert 0.067 < attn / 197e12 < 0.069  # 67.8 ms at the bf16 peak
    # a sequence the window covers: the windowed layers count as full ones
    short = dict(info, seq_len=2048)
    assert smallthinker_attn_flops.pairs(short)[0] \
        == smallthinker_attn_flops.pairs(short)[1] == 2048 * 2049 // 2
    # the routed experts' work follows the rows the program counted, so it is
    # right whatever share of the experts the configuration holds
    from dgraph_tpu.obs import metrics

    reg = metrics.Metrics()
    monkeypatch.setattr(metrics, "default_registry", reg)
    assert opsbytes.work("sdar_moe_flops", info, 0) == 0.0
    reg.counter("moe.rows_routed", 10 * 16384 * 6 * 4)
    reg.counter("moe.rows_here", 10 * 12288 * 4)  # an eighth
    assert opsbytes.work("sdar_moe_flops", info, 0) \
        == 3 * 2 * (4 * 12288) * 3 * 2560 * 768


def test_a_program_without_the_scopes_gives_nothing(monkeypatch):
    from dgraph_tpu.obs import metrics

    monkeypatch.setattr(metrics, "default_registry", metrics.Metrics())
    run = types.SimpleNamespace(say=lambda m: None)
    assert program_counter_ratio.reduce(
        run, spec("moe_buffer_used_pct.fed")["params"]) is None
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


def test_the_seeded_embedding_lies_on_bfloat16s_grid():
    """``SmallThinkerCell._seeded``: the plain seeded weights but for the
    embedding, which is rounded to bfloat16's grid (by ``reduce_precision``:
    a cast there and back may be kept in excess precision, and on the chip it
    was), so that the first layer's router is handed the same numbers in the
    program's bfloat16 stream and in the float32 reference."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import weights
    from benchmark.builders import smallthinker
    from benchmark.builders.looplm import seeded_lm_params
    from dgraph_tpu.train import lm

    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        tiny = json.load(f)["tiny"]
    mesh = lm.lm_mesh(1, jax.devices()[:1])
    model = smallthinker.model_of(tiny, lm.lm_comm(1))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros(128, jnp.int32), jnp.arange(128)))
    cell = types.SimpleNamespace(jax=jax, mesh=mesh, _shapes=shapes,
                                 _replicated=NamedSharding(mesh, P()))
    got = smallthinker.SmallThinkerCell._seeded(cell, 2**31 + 46)
    with jax.set_mesh(mesh):
        plain = seeded_lm_params(shapes, 2**31 + 46, cell._replicated)
    flat = dict(jax.tree_util.tree_flatten_with_path(plain)[0])
    seen = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(got)[0]:
        a, b = np.asarray(leaf), np.asarray(flat[path])
        if weights.leaf_name(path) == "params/embed/embedding":
            seen += 1
            assert a.dtype == np.float32 and (a != b).any()
            np.testing.assert_array_equal(
                a, b.astype(ml_dtypes.bfloat16).astype(np.float32))
        else:
            np.testing.assert_array_equal(a, b)
    assert seen == 1
