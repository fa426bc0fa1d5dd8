"""Synthetic weather batches for GraphCast cells, drawn from ``--seed``.

The law is ``dgraph_tpu/data/weather.py``'s (each channel a sum of three
random low-frequency spherical harmonics; the target is the input rolled
three columns east, damped, plus a tenth of the channel mean), computed for
all channels at once. Rows are grid points in lat-major order and all
differ.
"""

from __future__ import annotations

import numpy as np


def batches(num_lat: int, num_lon: int, channels: int, count: int, seed: int):
    """[(x, y)] * count, each [num_lat * num_lon, channels] float32."""
    rng = np.random.default_rng(seed)
    lat = np.linspace(0, np.pi, num_lat)[:, None]
    lon = np.linspace(0, 2 * np.pi, num_lon, endpoint=False)[:, None]
    out = []
    for _ in range(count):
        fields = np.zeros((num_lat, num_lon, channels), np.float32)
        for _ in range(3):
            kl = rng.integers(1, 4, channels)
            kk = rng.integers(1, 5, channels)
            ph = rng.uniform(0, 2 * np.pi, channels)
            amp = rng.normal(0, 1.0, channels)
            fields += np.einsum(
                "ac,bc->abc", amp * np.sin(kl * lat + ph), np.cos(kk * lon)
            ).astype(np.float32)
        x = fields.reshape(num_lat * num_lon, channels)
        rolled = np.roll(fields, shift=3, axis=1).reshape(x.shape)
        y = (0.9 * rolled + 0.1 * x.mean(axis=1, keepdims=True)).astype(np.float32)
        out.append((x, y))
    return out
