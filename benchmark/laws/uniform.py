"""Uniform random edges: a copy of
``dgraph_tpu/data/synthetic.py::random_edges``."""

import numpy as np

from benchmark.graphs import symmetrise


def edges(num_nodes: int, num_edges: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    src = rng.integers(0, num_nodes, num_edges)
    dst = rng.integers(0, num_nodes, num_edges)
    return symmetrise(src, dst)
