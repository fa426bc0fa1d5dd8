"""Traffic generation for graph cells: one generator, driven by a traffic
file's ``graph_law``, which names a module ``benchmark/laws/<law>.py`` with
one function ``edges(num_nodes, num_edges, seed, **law_params)``. A later
law is a new file there. Everything is drawn from ``--seed``; the program
only ever receives the arrays made here.

Every law ends in the same symmetrisation, so each gives 2 x ``num_edges``
directed edges. The node data (features, labels that are a fixed random
linear function of the features, a random split) is
``chip_smoke.py::build_graph``'s, with the split's shares the configuration's.
"""

from __future__ import annotations

import importlib

import numpy as np


def symmetrise(src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    return np.stack(
        [np.concatenate([src, dst]), np.concatenate([dst, src])]
    ).astype(np.int64)


def edges(traffic: dict, num_nodes: int, num_edges: int, seed: int) -> np.ndarray:
    """[2, 2 * num_edges] directed edges under the traffic file's law."""
    law = importlib.import_module(f"benchmark.laws.{traffic['graph_law']}")
    return law.edges(num_nodes, num_edges, seed, **traffic.get("law_params", {}))


def node_data(num_nodes: int, feat: int, classes: int, seed: int,
              train_fraction: float, val_fraction: float):
    """(x [V, F] f32, y [V] int, {'train','val'} boolean masks)."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((num_nodes, feat), dtype=np.float32)
    y = (x @ rng.standard_normal((feat, classes), dtype=np.float32)).argmax(-1)
    split = rng.random(num_nodes)
    return x, y, {"train": split < train_fraction,
                  "val": split >= 1.0 - val_fraction}
