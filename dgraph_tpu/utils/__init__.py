"""Shared utilities.

Module-level imports here are LAZY (PEP 562 ``__getattr__``) on purpose:
``dgraph_tpu.utils.env`` is the jax-free home of the cross-boundary
env-var constants, imported by modules under the ``jax-free-module``
contract (``chaos``, ``train/supervise.py``, ``comm/membership.py``) —
an eager ``from dgraph_tpu.utils.logging import ExperimentLog`` here would
drag jax into this package's import and break that contract for every
submodule.  ``from dgraph_tpu.utils import ExperimentLog`` call sites
keep working unchanged through the lazy hook.
"""

from __future__ import annotations

from dgraph_tpu.utils.env import RANK_ENV_VAR

__all__ = [
    "ExperimentLog", "largest_split", "split_per_rank",
    "RANK_ENV_VAR",
]

_LAZY = {
    "ExperimentLog": ("dgraph_tpu.utils.logging", "ExperimentLog"),
    "largest_split": ("dgraph_tpu.utils.data_splitting", "largest_split"),
    "split_per_rank": ("dgraph_tpu.utils.data_splitting", "split_per_rank"),
}


def __getattr__(name: str):
    if name in _LAZY:
        import importlib

        module, attr = _LAZY[name]
        value = getattr(importlib.import_module(module), attr)
        globals()[name] = value  # cache: pay the import once
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
