"""Hub-skewed edges: sources by Zipf weights, as
``dgraph_tpu/data/synthetic.py::power_law_graph`` draws them, destinations
uniform, then symmetrised as ``uniform`` is."""

import numpy as np

from benchmark.graphs import symmetrise


def edges(num_nodes: int, num_edges: int, seed: int,
          exponent: float = 0.75) -> np.ndarray:
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, num_nodes + 1) ** exponent
    w /= w.sum()
    src = rng.choice(num_nodes, num_edges, p=w)
    dst = rng.integers(0, num_nodes, num_edges)
    return symmetrise(src, dst)
