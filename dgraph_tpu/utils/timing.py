"""Device-timing helpers for single-op measurements.

The reference's static phase-timer registry (``DGraph/utils``) has no twin
here: once-per-launch phases are :func:`dgraph_tpu.obs.spans.
stage` (always-on totals, ``spans.stage_totals()``), per-step spans are
:func:`dgraph_tpu.obs.spans.span`, and both land in a ``jax.profiler.trace``
on the profiler's clock. ``jax.named_scope`` replaces nvtx.annotate
(``microbenchmark_graphcast.py:126``); what is left here is the scan-delta
protocol for timing one op on the device.
"""

from __future__ import annotations

import jax


named_scope = jax.named_scope


def salt_input(a, salt):
    """Fold a scan-carry scalar into an op input with no meaningful value
    change: ``a + cast(salt * 1e-20)`` keeps a LIVE data dependence on the
    loop carry so scan iterations serialize and XLA cannot hoist the op
    out of the timing loop. The scale makes the perturbation ~1e-18 on
    O(1) inputs — numerically invisible — and the cast avoids promoting
    bf16 inputs to the f32 carry dtype (which would silently benchmark
    f32 kernels).

    Previously ``cast(salt) * 0``: XLA's simplifier folded that to a
    constant despite float NaN/Inf semantics, severed the chain, and
    loop-invariant code motion hoisted the op — producing impossible
    ~0 ms "measurements" (caught in r3 via a 0.011 ms 240k-row gather).

    FLOAT inputs only: for integer dtypes the 1e-20 scale would cast to
    exactly 0 and silently reopen the hole, so that's a hard error.
    """
    import jax.numpy as jnp

    if not jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating):
        raise TypeError(
            f"salt_input needs a float array (got {jnp.asarray(a).dtype}): "
            f"an integer cast of salt*1e-20 is exactly 0, which severs the "
            f"loop-carried dependence the hoist-proofing relies on")
    return a + (salt * 1e-20).astype(a.dtype)


def timed_scan_ms(fn, *, reps: int = 3, n_long: int = 8):
    """Best positive (long - short) / (n_long - 1) delta in ms for one op.

    The single-chip timing protocol (bench.py's): run the op n times
    INSIDE one jit via ``lax.scan`` with a scalar carry fetched to host,
    and subtract a 1-iteration run so per-call dispatch latency cancels.

    ``fn(salt)`` must return an array and fold ``salt`` (f32 scalar) into
    its inputs via :func:`salt_input`. Returns None if no rep produced a
    positive delta.
    """
    import functools
    import time as _time

    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames="n")
    def loop(s, n):
        def body(acc, _):
            out = fn(acc)
            # consume the WHOLE output: a single-element fetch
            # (out.ravel()[0]) lets XLA slice through sliceable ops —
            # a row gather collapses to gathering ONE row and the
            # "measurement" is ~0 (caught in r3: a 9 TB/s CPU gather).
            # The sum can still fuse into the producer (output writes may
            # be elided), but every input byte is genuinely read.
            return acc + out.astype(jnp.float32).sum() * 1e-20, None

        acc, _ = jax.lax.scan(body, s, None, length=n)
        return acc

    float(loop(jnp.float32(0), 1))
    float(loop(jnp.float32(0), n_long))
    best = None
    for r in range(reps):
        # DISTINCT carry per dispatch: value-identical dispatches are the
        # memoization case this whole protocol exists to avoid
        t0 = _time.perf_counter(); float(loop(jnp.float32(r + 1), 1))
        t1 = _time.perf_counter() - t0
        t0 = _time.perf_counter(); float(loop(jnp.float32(r + 101), n_long))
        tl = _time.perf_counter() - t0
        d = (tl - t1) / (n_long - 1) * 1000.0
        if d > 0 and (best is None or d < best):
            best = d
    return best
