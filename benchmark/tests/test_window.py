"""The window's step metric is the phase's elapsed time over all its steps:
a stall in one step moves it, as it moves what a user waits for."""

import time

import numpy as np

from benchmark import run as harness


def test_step_time_is_elapsed_time_over_steps():
    calls = []

    def step():
        calls.append(1)
        time.sleep(0.06 if len(calls) == 10 else 0.002)  # one stalled step

    buf = np.empty(4096)
    t0 = time.perf_counter()
    n = harness.run_phase(step, 3, 0.3, buf)
    elapsed = time.perf_counter() - t0
    assert n * 3 == len(calls)
    per_step = buf[:n].mean()  # what main() reports, in seconds
    assert abs(per_step * len(calls) - elapsed) < 0.01 * elapsed
    assert per_step > 1.15 * np.median(buf[:n])  # the stall is in it
    line = harness.phase_line("fed", buf[:n] * 1e3, 3)
    assert f"steps={len(calls)} " in line and "slow(>1.2x median" in line
    assert " 3:" in line.split("slow")[1].replace("[", " ")  # the tenth step is in sample 3
