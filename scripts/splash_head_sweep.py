"""The splash kernels at ``kanana2_30b_a3b.seq16k``'s attention shape on the
chip, stand-alone: 16 384 causal rows, 32 query heads on 32 key heads of 192
(128 without positions + 64 rotated) and 32 value heads of 128, bfloat16:

    chiprun --chips 1 -- python scripts/splash_head_sweep.py

prints one line a variant: q and k as they are (192) and zero-padded to 256
here (exact: a zero column adds nothing to a score; the softmax scale stays
192's), each at ``block_kv_compute`` 128, 256 and 512, the forward and the
forward + backward in ms, each form after the kernels' self-check at its
head sizes (192 | 128, 256 | 128). It showed that ``parallel/sequence.py::
SPLASH_KV_COMPUTE`` serves such a head too and that it runs unpadded (PERF.md
section 6, PR 49). A CPU run is refused: times come
from the chip only."""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

T, H, DQK, DV = 16384, 32, 192, 128
REPEATS = 5


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dgraph_tpu.parallel import sequence as seq

    if jax.default_backend() != "tpu":
        print("splash_head_sweep: needs a TPU", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.standard_normal((T, H, DQK)), jnp.bfloat16)
            for _ in range(2))
    v, w = (jnp.asarray(rng.standard_normal((T, H, DV)), jnp.bfloat16)
            for _ in range(2))
    mask = seq.CausalMask(T)

    def timed(fn):
        jax.block_until_ready(fn(q, k, v))  # compiles
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = fn(q, k, v)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / REPEATS * 1e3

    widen = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, -DQK % 128)))
    for pad in (False, True):
        ok = seq._splash_selfcheck(
            seq.CausalMask(0), 1, head_dim=DQK + (-DQK % 128 if pad else 0),
            v_head_dim=DV)
        print(f"pad_to_256={int(pad)} selfcheck={'passed' if ok else 'FAILED'}",
              flush=True)
        for kvc in (128, 256, 512):
            seq.SPLASH_KV_COMPUTE = kvc
            attend = lambda q, k, v: seq._splash_dense(
                widen(q) if pad else q, widen(k) if pad else k, v, mask=mask,
                scale=DQK ** -0.5)
            fwd = jax.jit(attend)
            both = jax.jit(jax.grad(
                lambda q, k, v: (attend(q, k, v).astype(jnp.float32)
                                 * w.astype(jnp.float32)).sum(),
                argnums=(0, 1, 2)))
            print(f"pad_to_256={int(pad)} block_kv_compute={kvc} "
                  f"forward_ms={timed(fwd):.2f} "
                  f"forward_backward_ms={timed(both):.2f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
