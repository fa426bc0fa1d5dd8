"""The ops/bytes functions against hand counts at a tiny shape, and the
table of peaks."""

import pytest

from benchmark import opsbytes


def test_segsum_bytes_by_hand():
    info = {"hidden": 256, "e_pad": 1000, "n_pad": 100, "compute_bytes": 2}
    # one call: 1000 x 128 bf16 messages in, 1000 int32 ids in, 100 x 128 out
    by_hand = 1000 * 128 * 2 + 1000 * 4 + 100 * 128 * 2
    assert opsbytes.work("segsum_call_bytes", info, 1) == by_hand
    assert opsbytes.work("segsum_call_bytes", info, 12) == 12 * by_hand
    narrow = dict(info, hidden=64)  # narrower than a column block
    assert opsbytes.work("segsum_call_bytes", narrow, 1) == 1100 * 64 * 2 + 4000


def test_graphcast_dense_flops_by_hand():
    info = {"latent": 2, "channels": 3, "processor_layers": 1, "n_grid": 5,
            "n_mesh": 4, "e_mesh": 7, "e_g2m": 6, "e_m2g": 15}
    L, C = 2, 3
    embed = 5 * ((C + 4) * L + L * L) + 4 * (4 * L + L * L) \
        + (7 + 6 + 15) * (4 * L + L * L)
    enc = (5 + 4 + 2 * 6) * L * L + 4 * 3 * L * L + 5 * 2 * L * L
    proc = (4 + 4 + 2 * 7) * L * L + 4 * 3 * L * L
    dec = (4 + 5 + 2 * 15) * L * L + 5 * 3 * L * L
    head = 5 * (L * L + L * C)
    assert opsbytes.work("graphcast_dense_flops", info, 0) == 6 * (
        embed + enc + proc + dec + head)


def test_unknown_device_is_an_error():
    assert opsbytes.device_peaks("TPU v5 lite")["bf16_tflops"] == 197.0
    with pytest.raises(KeyError):
        opsbytes.device_peaks("cpu")
