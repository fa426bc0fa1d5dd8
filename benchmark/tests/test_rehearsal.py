"""The command itself, for every cell of BENCHMARK.json, rehearsed on CPU
devices at the configurations' tiny sizes (the four-chip cell on four virtual
devices); the same command with the timed path broken underneath, and with
the control precision in the program's place: each has to come out as not
correct and say on stderr's last lines which number failed; and a cell added
by new files alone."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def run_cell(name, *extra, seed=7, trace=0, root=ROOT, bench=BENCH):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cmd = bench["command"] + [
        "--workload", name, "--seed", str(seed), "--seconds", "1",
        "--trace", str(trace), "--rehearse-cpu", "1", *extra]
    cmd[0] = sys.executable
    out = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                         text=True, timeout=600)
    return out, (json.loads(out.stdout.strip().splitlines()[-1])
                 if out.returncode == 0 else None)


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearses_and_is_correct(name):
    out, result = run_cell(name, seed=2**31 + 11)
    assert out.returncode == 0, out.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0
    assert result["rehearsal"] == "cpu"
    names = set(result["metrics"])
    assert all(n.startswith("cpu_rehearsal.") for n in names)
    want = {m["name"] for m in BENCH["end_to_end"]
            if name in m.get("workloads", [name])}
    assert {n.split(".", 1)[1] for n in names} == want
    for line in out.stdout.splitlines():
        if line.startswith("[bench] check "):
            assert "limit=" in line  # each number is printed beside its limit


@pytest.mark.parametrize("name", CELLS)
def test_traced_rehearsal_reports_span_metrics(name):
    out, result = run_cell(name, trace=1)
    assert out.returncode == 0, out.stderr[-2000:]
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    got = {n.split(".", 1)[1] for n in result["metrics"]}
    assert {"plan_build_s", "placement_s", "compile_s"} <= got


def failure_lines(out, name, seed):
    """What a failing run has to leave as its last lines on stderr."""
    tail = out.stderr.strip().splitlines()
    start = max(i for i, l in enumerate(tail)
                if l.startswith("benchmark: correct=false"))
    block = tail[start:]
    assert len(tail) - start == len(block)  # nothing after it but its own lines
    assert all(l.startswith("benchmark: ") for l in block)
    assert f"cell={name} seed={seed} traced=0" in block[0]
    failed = [l for l in block if l.startswith("benchmark: FAILED check ")]
    for l in failed:
        assert re.search(r"check (\S+): value=\S+ limit=\S+ value/limit=\S+ "
                         rf"cell={re.escape(name)} seed={seed} traced=0$", l), l
    others = [l for l in block if l.startswith("benchmark: other ")]
    return {l.split()[3].rstrip(":") for l in failed}, others


@pytest.mark.parametrize("name", CELLS)
def test_a_step_that_does_not_advance_its_state_is_not_correct(name):
    out, result = run_cell(name, "--break-step", "frozen", seed=2**31 + 5)
    assert out.returncode == 0, out.stderr[-2000:]
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] > 0
    failed, others = failure_lines(out, name, 2**31 + 5)
    assert "delta_norm_gap" in failed  # the number that fault is there for
    assert others  # every other number compared follows


@pytest.mark.parametrize("name", CELLS)
def test_the_control_precision_is_not_correct(name):
    out, result = run_cell(name, "--control", "1", seed=23)
    assert out.returncode == 0, out.stderr[-2000:]
    assert result["correct"] is False
    failed, _ = failure_lines(out, name, 23)
    assert "grad_diff_gap" in failed  # the number a lower precision fails


def test_a_run_that_raises_says_so_on_stderr():
    out, _ = run_cell(CELLS[0], "--break-step", "no_such_fault")
    assert out.returncode != 0
    assert not out.stdout.strip().splitlines()[-1].startswith("{")
    last = out.stderr.strip().splitlines()[-1]
    assert last.startswith("benchmark: raised ValueError") \
        and f"cell={CELLS[0]} seed=7" in last


def test_a_cell_is_added_by_new_files_alone(tmp_path):
    """A configuration, a traffic mix, a cell's limits and a per-layer metric
    (with a reducer of its own), each a new file, and new entries of
    BENCHMARK.json: no file that is there is edited, and the cell runs."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "dgraph_tpu"), root / "dgraph_tpu")
    os.symlink(os.path.join(ROOT, "csrc"), root / "csrc")
    before = {os.path.relpath(os.path.join(d, f), root): os.path.getmtime(
        os.path.join(d, f)) for d, _, fs in os.walk(root / "benchmark") for f in fs}
    b = root / "benchmark"
    config = json.loads((b / "configs" / "gcn_arxiv.json").read_text())
    config["name"] = "gcn_dummy"
    config["tiny"].update(num_nodes=2048, num_edges=6144, hidden=32)
    (b / "configs" / "gcn_dummy.json").write_text(json.dumps(config))
    (b / "traffic" / "w1_mild.json").write_text(json.dumps(
        {"world_size": 1, "graph_law": "power_law",
         "law_params": {"exponent": 0.3}}))
    limits = json.loads((b / "limits" / "gcn_arxiv.w1.json").read_text())
    limits["cell"] = "gcn_dummy.w1_mild"
    (b / "limits" / "gcn_dummy.w1_mild.json").write_text(json.dumps(limits))
    (b / "reducers" / "span_ms.py").write_text(
        "def reduce(run, params):\n"
        "    v = run.spans.get(params['span'])\n"
        "    return None if v is None else 1e3 * v\n")
    (b / "layer_metrics" / "weights_ms.json").write_text(json.dumps(
        {"name": "weights_ms", "reducer": "span_ms",
         "params": {"span": "weights_s"}}))
    bench = json.loads(json.dumps(BENCH))
    cell = "gcn_dummy.w1_mild"
    bench["configs"].append({
        "name": "gcn_dummy", "source": "test",
        "file": "benchmark/configs/gcn_dummy.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "gcn_dummy",
                               "traffic": "w1_mild", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gcn_arxiv.w1" in m.get("workloads", []):
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "weights_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "placement", "moves": "setup_s",
        "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    out, result = run_cell(cell, root=str(root), bench=bench)
    assert out.returncode == 0, out.stderr[-2000:]
    assert result["correct"] is True
    out, result = run_cell(cell, trace=1, root=str(root), bench=bench)
    assert out.returncode == 0, out.stderr[-2000:]
    got = {n.split(".", 1)[1] for n in result["metrics"]}
    assert {"weights_ms", "plan_build_s", "dispatch_ms.train"} <= got
    assert all(os.path.getmtime(root / rel) == t for rel, t in before.items())


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"]
    out = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert not out.stdout.strip().startswith("{")
