"""Pallas TPU kernel: device-initiated one-sided halo transport.

The TPU analogue of DGraph's NVSHMEM backend — its fastest, precisely
because the halo exchange is GPU-initiated one-sided puts instead of
host-scheduled collectives (PAPER.md L1/L2; "Demystifying NVSHMEM",
PAPERS.md). Here the boundary tiles move as ``pltpu.make_async_remote_copy``
puts issued from INSIDE one Pallas kernel:

- One put per live ``halo_delta``: tile ``k`` (the ``[S, F]`` block headed
  to peer ``(me + sign*deltas[k]) % W``) DMAs straight into the
  destination shard's ``[W*S, F]`` halo buffer at rows
  ``[me*S, (me+1)*S)`` — the plan's halo-slot numbering, so no receive
  placement pass and no separate exchange buffer staged through HBM
  (``ppermute`` rounds stage one send block + one recv block per round;
  ``all_to_all`` stages the full padded ``[W, S, F]`` operand).
- DMA semaphores live in kernel scratch (one send/recv pair per delta);
  every put is started before any is waited on, so all tiles are on the
  wire concurrently — "The Big Send-off" (PAPERS.md) motivates exactly
  this per-tile DMA shape for sparse neighbor traffic.
- The fused-mask variant stages tile ``k`` in a two-slot VMEM buffer,
  applies the plan's ``send_mask`` there (an exact elementwise multiply —
  bit-parity with the jnp path is free), and puts from VMEM: tile
  ``k+1``'s stage+mask overlaps tile ``k``'s in-flight put (double
  buffering; slot reuse waits the put two tiles back). The masked send
  block never exists in HBM at all.
- A barrier semaphore (``pltpu.get_barrier_semaphore``) makes every
  sender wait until each shard it writes to has entered the kernel — a
  put must never land in a buffer the receiver has not allocated+zeroed
  yet. (Pallas interpret mode executes shards lock-step and does not
  model the race; the barrier is compiled only for real Mosaic
  lowerings.)

Off-TPU the kernels run in Pallas ``interpret=True`` mode — that is how
the tier-1 parity pins (bit-identical fwd+bwd vs the ``all_to_all``
lowering, ``tests/test_pallas_p2p.py``) run on the CPU backend without a
chip. The transport itself is a pure data movement: every arithmetic op
that decides a bit (gather, mask multiply, segment-sum) is either the
exact same jnp op the ``all_to_all`` path runs or an exact elementwise
multiply inside the kernel.

``python -m dgraph_tpu.ops.pallas_p2p --selftest true`` is the
interpret-mode smoke ``scripts/check.py`` runs (tiny CPU compiles only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Largest [n_deltas, S, F] send stack (bytes) the fused-mask variant will
# hold in VMEM (two staging slots ride alongside it). Bigger stacks fall
# back to pre-masked HBM-direct puts — same values, no VMEM staging.
FUSED_MASK_VMEM_BUDGET = 4 * 1024 * 1024

# collective_id for the kernel's barrier semaphore (one id is enough: the
# barrier self-resets — each wait decrements what the signals added — and
# XLA orders the kernels within a program by their data dependencies)
P2P_COLLECTIVE_ID = 7


def p2p_interpret_mode():
    """Falsy on a TPU (compile with Mosaic); elsewhere — the tier-1/CPU
    path — the Pallas TPU interpreter's params. (``interpret=True``, the
    generic interpreter, can discharge neither a remote put on a
    multi-axis mesh nor its own index arithmetic under the shard_map vma
    checker.)"""
    if jax.default_backend() == "tpu":
        return False
    return pltpu.InterpretParams()


def _logical_device_ids(axis_name, graph_ids):
    """Raveled LOGICAL device ids over the FULL axis env (row-major in
    env order) with the ``axis_name`` component replaced by ``graph_ids``
    — a ``('replica', 'graph')`` mesh must target
    ``replica_idx * W + graph_rank``, not the bare graph rank (the way
    Mosaic numbers LOGICAL device ids)."""
    try:
        from jax._src import core as jax_core

        sizes = jax_core.get_axis_env().axis_sizes
        axes = [(a, s) for a, s in sizes.items() if a is not None]
    except Exception as e:  # axis env introspection is jax-internal —
        # fail LOUDLY: silently falling back to bare graph ranks would
        # address replica 0's devices from every replica on a
        # ('replica', 'graph') mesh (corrupted halos, no error raised)
        raise RuntimeError(
            "pallas_p2p cannot introspect the mesh axis env to compute "
            "logical device ids (jax-internal API changed?); update "
            "dgraph_tpu.ops.pallas_p2p._logical_device_ids for this jax "
            f"version ({jax.__version__})"
        ) from e
    ids = jnp.zeros((), jnp.int32)
    for a, s in axes:
        comp = graph_ids if a == axis_name else lax.axis_index(a)
        ids = ids * s + comp
    return jnp.atleast_1d(ids)


def _transport_kernel(
    meta_ref,  # SMEM i32[3n+1]: target logical ids[n] | source logical
    # ids[n] | source graph ranks[n] | dst_row
    mask_ref,  # [n, S] f32 send mask (VMEM; only read when fused_mask)
    blocks_ref,  # [n, S, F] send tiles (VMEM when fused_mask else ANY/HBM)
    zeros_ref,  # [W*S, F] zeroed landing buffer (aliased to the output)
    out_ref,  # [W*S, F] halo buffer (this shard's; peers put into it)
    staging,  # VMEM (2, S, F) double buffer (fused_mask)
    send_sems,  # DMA sem per outbound put
    recv_sems,  # DMA sem per inbound put
    *,
    n: int,
    S: int,
    fused_mask: bool,
    interpret: bool,
):
    del zeros_ref
    if not interpret:
        # ready barrier: signal every shard that will put into MY buffer,
        # then wait for one signal from each shard I put into (senders and
        # receivers are the same delta set, mirrored)
        barrier = pltpu.get_barrier_semaphore()
        for k in range(n):
            pltpu.semaphore_signal(
                barrier, inc=1, device_id=meta_ref[n + k],
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
        pltpu.semaphore_wait(barrier, n)
    dst_row = meta_ref[3 * n]
    copies = []
    for k in range(n):
        if fused_mask:
            slot = k % 2
            if k >= 2:
                # slot reuse: the put issued two tiles back read this slot
                # — wait its send semaphore before overwriting (classic
                # double buffering; tile k's stage+mask runs while tile
                # k-1's put is still on the wire)
                copies[k - 2].wait_send()
            staging[slot] = blocks_ref[k] * mask_ref[k][:, None].astype(
                blocks_ref.dtype
            )
            src = staging.at[slot]
        else:
            src = blocks_ref.at[k]
        c = pltpu.make_async_remote_copy(
            src_ref=src,
            dst_ref=out_ref.at[pl.ds(dst_row, S)],
            send_sem=send_sems.at[k],
            recv_sem=recv_sems.at[k],
            device_id=meta_ref[k],
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        c.start()
        copies.append(c)
    # drain outbound sends; under fused_mask the slot-reuse waits above
    # already consumed every send semaphore but the last two slots'
    for c in (copies[-2:] if fused_mask else copies):
        c.wait_send()
    for k in range(n):
        # wait each inbound tile: same-size descriptor on the recv
        # semaphore over the rows peer sources[k] lands in
        src_row = meta_ref[2 * n + k] * S
        landing = out_ref.at[pl.ds(src_row, S)]
        pltpu.make_async_copy(landing, landing, recv_sems.at[k]).wait()


@functools.lru_cache(maxsize=None)
def _make_transport(n, W, S, F, dtype_name, fused_mask, interpret, vma):
    dtype = jnp.dtype(dtype_name)
    kern = functools.partial(
        _transport_kernel, n=n, S=S, fused_mask=fused_mask,
        interpret=interpret,
    )
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((W * S, F), dtype, vma=vma),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM if fused_mask else pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            # the two-slot staging buffer exists only on the fused-mask
            # path; the non-fused path (reverse legs, over-budget stacks)
            # must not carry 2*S*F of dead VMEM — that is exactly the
            # large-tile case it falls back for
            pltpu.VMEM((2, S, F) if fused_mask else (1, 1), dtype),
            pltpu.SemaphoreType.DMA((n,)),
            pltpu.SemaphoreType.DMA((n,)),
        ],
        # the zeroed landing buffer IS the output: it must be materialized
        # before the kernel (and so before any peer's put) — rows no put
        # covers stay exactly 0, matching the round lowerings
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            collective_id=P2P_COLLECTIVE_ID
        ),
        interpret=interpret,
        name=f"dgraph_p2p_transport_n{n}",
    )


def transport_fused_mask(blocks, S: int, F: int, dtype) -> bool:
    """Whether the fused in-kernel masking variant engages for this tile
    stack (it must fit the VMEM staging budget)."""
    n = blocks.shape[0]
    return n * S * F * jnp.dtype(dtype).itemsize <= FUSED_MASK_VMEM_BUDGET


def p2p_transport(
    blocks: jax.Array,  # [n_deltas, S, F] send tiles, one per live delta
    axis_name: str,
    deltas: tuple,  # static live rank offsets (EdgePlan.halo_deltas)
    W: int,
    S: int,
    *,
    sign: int = 1,  # +1: tile k -> (me + deltas[k]) % W (the exchange);
    # -1: tile k -> (me - deltas[k]) % W (its transpose / reverse leg)
    mask=None,  # [n_deltas, S] send mask; None = tiles are pre-masked
) -> jax.Array:
    """One-sided delivery of per-delta halo tiles; returns the ``[W*S, F]``
    halo buffer (rows ``[p*S, (p+1)*S)`` hold the tile peer ``p`` put,
    zeros where no put landed — the exact layout/values of the
    ``all_to_all`` and ``ppermute`` lowerings).

    Pure data movement: when ``mask`` is given (and the stack fits VMEM)
    the masking multiply runs in-kernel, overlapped with the previous
    tile's put; otherwise the caller pre-masks and the kernel only moves
    bytes. Not differentiable by itself — ``comm.collectives`` wraps the
    two directions into an explicit custom-VJP pair.
    """
    n = len(deltas)
    F = blocks.shape[-1]
    interpret = p2p_interpret_mode()
    fused = mask is not None and transport_fused_mask(blocks, S, F, blocks.dtype)
    if mask is not None and not fused:
        blocks = blocks * mask[..., None].astype(blocks.dtype)
    if mask is None or not fused:
        # never read on the non-fused path — keep the VMEM operand tiny
        mask = jnp.ones((1, 1), jnp.float32)
    me = lax.axis_index(axis_name)
    d = jnp.asarray(deltas, jnp.int32)
    targets = (me + sign * d) % W
    sources = (me - sign * d) % W
    meta = jnp.concatenate([
        _logical_device_ids(axis_name, targets),
        _logical_device_ids(axis_name, sources),
        sources,
        (me * S)[None],
    ]).astype(jnp.int32)
    # the output varies over the manual axes its DATA operands vary over
    # (``meta`` is addressing: it names a different device per replica,
    # but every replica group moves the same values); the shard_map vma
    # checker wants that declared on out_shape AND on the landing buffer
    # the output aliases (an aliased output takes its input's type)
    vma = jax.typeof(mask).vma | jax.typeof(blocks).vma
    zeros = jnp.zeros((W * S, F), blocks.dtype)
    if vma:
        zeros = lax.pcast(zeros, tuple(vma), to="varying")
    fn = _make_transport(
        n, W, S, F, jnp.dtype(blocks.dtype).name, fused, interpret, vma
    )
    return fn(meta, mask, blocks, zeros)


# ---------------------------------------------------------------------------
# selftest CLI (scripts/check.py's interpret-mode smoke)
# ---------------------------------------------------------------------------


def _selftest_failures(seed: int = 0) -> list:
    """Interpret-mode transport parity on 2- and 4-shard rings: the kernel
    must deliver exactly what one masked ``all_to_all`` delivers, both
    put directions, fused and pre-masked. Tiny CPU compiles only."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    failures = []
    if jax.default_backend() == "tpu":
        # the smoke validates the INTERPRET path; on a real chip the
        # parity pins in tests/test_pallas_p2p.py are the authority
        return failures
    for W, deltas in ((2, (1,)), (4, (1, 3))):
        if len(jax.devices()) < W:
            failures.append(
                f"need {W} devices for the {W}-shard smoke; have "
                f"{len(jax.devices())} (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=8)"
            )
            continue
        S, F = 8, 32
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(W, W, S, F)).astype(np.float32)
        m = (rng.random((W, W, S)) > 0.3).astype(np.float32)
        for r in range(W):
            for p in range(W):
                if (p - r) % W not in deltas:
                    m[r, p] = 0.0
        xj = jnp.asarray(x.reshape(W, W * S, F))
        mj = jnp.asarray(m.reshape(W, W * S))
        mesh = jax.make_mesh((W,), ("x",))

        def run(body):
            from dgraph_tpu.comm.collectives import shard_map_checks

            f = jax.shard_map(
                body, mesh=mesh, in_specs=(P("x"), P("x")),
                out_specs=P("x"),
                **shard_map_checks(impl="pallas_p2p"),
            )
            return np.asarray(jax.jit(f)(xj, mj))

        def ref_body(xb, mb):
            xb, mb = xb.reshape(W, S, F), mb.reshape(W, S)
            send = xb * mb[..., None]
            recv = lax.all_to_all(send, "x", split_axis=0, concat_axis=0)
            return recv.reshape(W * S, F)

        want = run(ref_body)
        for premask in (False, True):
            def p2p_body(xb, mb, premask=premask):
                xb, mb = xb.reshape(W, S, F), mb.reshape(W, S)
                me = lax.axis_index("x")
                rows = (me + jnp.asarray(deltas, jnp.int32)) % W
                blocks, msk = xb[rows], mb[rows]
                if premask:
                    blocks = blocks * msk[..., None]
                    return p2p_transport(blocks, "x", deltas, W, S)
                return p2p_transport(blocks, "x", deltas, W, S, mask=msk)

            got = run(p2p_body)
            if not (got == want).all():
                failures.append(
                    f"W={W} premask={premask}: transport != all_to_all "
                    f"({int((got != want).sum())} differing elements)"
                )
    return failures


def main(cfg) -> dict:
    import json

    from dgraph_tpu.obs.health import RunHealth

    health = RunHealth.begin("ops.pallas_p2p")
    try:
        failures = _selftest_failures(cfg.seed) if cfg.selftest else []
        out = {
            "kind": "pallas_p2p_selftest",
            "backend": jax.default_backend(),
            "failures": failures,
            "run_health": health.finish(
                "; ".join(failures) if failures else None,
                wedge="stage_failure" if failures else None,
            ),
        }
        print(json.dumps(out, indent=cfg.indent or None))
        if failures:
            raise SystemExit(
                "pallas_p2p selftest FAILED: " + "; ".join(failures)
            )
        return out
    except SystemExit:
        raise
    except BaseException as e:
        print(json.dumps({
            "kind": "pallas_p2p_selftest",
            "failures": [f"{type(e).__name__}: {e}"],
            "run_health": health.finish(
                f"pallas_p2p selftest crashed: {type(e).__name__}: {e}",
                wedge="stage_failure",
            ),
        }))
        raise


if __name__ == "__main__":
    import dataclasses

    from dgraph_tpu.utils.cli import parse_config

    @dataclasses.dataclass
    class Config:
        """Device-initiated one-sided halo transport (``--selftest`` runs
        the interpret-mode parity smoke)."""

        selftest: bool = False
        seed: int = 0
        indent: int = 0

    main(parse_config(Config))
