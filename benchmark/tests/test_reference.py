"""The plain references against ``dgraph_tpu`` at tiny sizes on the CPU, for
every cell of BENCHMARK.json (the four-chip cell on four virtual devices),
and the control: the reference in the configuration's control precision has
to fail at least one of the cell's numbers, where the program passes all."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def readings(cell_name, seed):
    import jax

    from benchmark import run as harness

    entry, config, traffic = harness.find_cell(BENCH, cell_name)
    cell = harness.build_cell(config, traffic, seed,
                              jax.devices()[:entry["chips"]], True)
    with cell.context():
        got, _, _ = harness.first_steps(cell, harness.CompileWatch())
    cell.release()
    ref = cell.reference(harness.CHECK_STEPS, "float32")
    low = cell.reference(harness.CHECK_STEPS,
                         config["correct"]["control_precision"])
    limits = harness.cell_limits(cell_name, True)
    return harness.compare(got, ref, limits), harness.compare(low, ref, limits)


@pytest.mark.parametrize("cell_name", [w["name"] for w in BENCH["workloads"]])
def test_program_passes_and_control_fails(cell_name):
    sound, control = readings(cell_name, seed=13)
    assert all(ok for *_, ok in sound), sound
    assert not all(ok for *_, ok in control), control
    failed = {name for name, *_, ok in control if not ok}
    assert "grad_diff_gap" in failed  # the number a lower precision fails
