"""What a builder hands the harness.

A builder module (``benchmark/builders/<builder>.py``) has one function,
``build(ctx) -> cell``. The cell has:

- ``phases``: the timed steps, in window order. ``Phase.step()`` is one step
  through the program's own call, ended by ``block_until_ready``; its host
  parts sit inside ``annotate(...)`` spans (``host_feed``, ``step_dispatch``,
  ``block``), which land on the profiler's clock in a traced run and cost
  some tens of nanoseconds otherwise.
- ``check_phase``: the phase whose first steps the comparison follows.
- ``loss()``, ``first_gradient()``, ``delta_norms()``, ``eval_numbers()``: read
  from the state the window's own steps left (no model is run for them).
- ``release()``: fetch the seeded weights to the host and drop every device
  buffer of the program, so that the reference has the chip to itself.
- ``reference(steps, precision)``: the numbers of the configuration's plain
  reference (``ctx.reference``, the module the configuration names), in the
  same keys.
- ``info``: padded shapes for the ops/bytes functions.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Phase:
    name: str  # 'train', 'eval', 'fed', 'fwd': the suffix of its metrics
    metric: Optional[str]  # the end-to-end metric its time per step reports
    share: float  # of the window; 0 = traced runs only
    step: Callable[[], None]


@dataclasses.dataclass
class Context:
    """What the harness hands a builder."""

    traffic: dict
    sizes: dict  # config['sizes'], or config['tiny'] in a CPU rehearsal
    reference: object  # the module benchmark/reference/<config['reference']>.py
    seed: int
    devices: list
    traced: bool
    spans: dict  # set-up spans in seconds, by name (read by span reducers)
    say: Callable[[str], None]
