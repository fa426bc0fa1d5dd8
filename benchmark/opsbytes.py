"""Peaks of the device and the work a kernel has to do, from shapes alone.

The yardstick for the roofline shares: the table of published peaks
(``peaks.json``, keyed by ``device_kind``; an unknown device is an error, not
a default) and the functions that give the operations or bytes the algorithm
needs for one step, from the padded shapes the builder reports in
``cell.info``. Each is a module ``benchmark/work/<name>.py`` with one
function ``work(info, calls)``, named by a roofline metric's ``work``
parameter; a later roofline brings its own file. Time comes from the trace,
never from here.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def device_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add it to "
            f"benchmark/peaks.json with its source (known: {sorted(table)})")
    return table[device_kind]


def work(name: str, info: dict, calls: float) -> float:
    return importlib.import_module(f"benchmark.work.{name}").work(info, calls)
