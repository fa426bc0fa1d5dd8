"""``tools/replay.py``, and ``tools/limits.py``, which reads the numbers the limits of ``correct`` are
set from, at tiny sizes on the CPU: per seed in a new cell (GCN) and, where
the cell can take another seed under its compiled step, in one (GraphCast)."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.mark.parametrize("cell", ["gcn_arxiv.w1", "graphcast_small.w1",
                                  "gcn_papers100m.w4"])
def test_limits_tool_reads_sound_runs_and_control(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools", "limits.py"),
         "--workload", cell, "--seeds", "3,4", "--control-seeds", "4",
         "--rehearse-cpu", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.splitlines()
    assert sum(l.startswith("seed ") and " sound " in l for l in lines) == 2
    assert sum(l.startswith("seed 4 control(") for l in lines) == 1
    summary = [l for l in lines if l.startswith("grad_diff_gap:")]
    assert len(summary) == 1 and "over 2 seeds" in summary[0]
    ratio = float(summary[0].rsplit("ratio ", 1)[1].split(";")[0])
    assert ratio > 1.0  # the control lies above the sound runs


def test_replay_tool_runs_sets_and_controls(tmp_path):
    """``tools/replay.py``: two sets over the same seeds, a traced run and a
    control, each a new process of the command; it reports the spreads and
    ends ALL OK only if the sound runs were correct and the control was not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools", "replay.py"),
         "--workload", "gcn_arxiv.w1", "--seeds", "1,2", "--seconds", "1",
         "--traced-seed", "3", "--control-seeds", "4", "--short-seconds", "1",
         "--rehearse-cpu", "1", "--out", os.path.relpath(tmp_path, ROOT)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    lines = out.stdout.splitlines()
    assert lines[-1] == "[replay] gcn_arxiv.w1: ALL OK"
    assert sum("correct=True" in l for l in lines) == 5
    assert sum("correct=False" in l for l in lines) == 1
    assert any("train_step_ms: set1 median=" in l and "set2/set1-1=" in l
               for l in lines)
