"""Pallas DMA-discipline verifier: static checks over the ``pallas_p2p``
transport kernel's jaxpr.

"Demystifying NVSHMEM" (PAPERS.md) makes the point this module encodes:
device-initiated one-sided communication is only correct under an exact
semaphore/ordering discipline, and that discipline is *invisible* to
every numeric test — Pallas interpret mode executes shards lock-step, so
a dropped wait or a premature staging-slot overwrite produces bit-perfect
CPU parity and corrupts halos only on real hardware under real timing.
The discipline is, however, fully *static*: the transport kernel is a
straight-line jaxpr whose DMA starts, waits, semaphore indices and
staging-slot indices are all literal, so every rule below is checkable
with zero chips and zero XLA compiles (``jax.make_jaxpr`` only).

Per transport ``pallas_call`` the verifier proves:

- **paired waits** — every ``dma_start``'s send semaphore AND recv
  semaphore is waited by a later ``dma_wait`` on the same
  (semaphore, index);
- **nothing outstanding at exit** — per (semaphore, index), waits cover
  starts by the last eqn (an un-drained DMA at kernel exit is a race
  against the next kernel's buffer reuse);
- **wait-before-reuse** — a write to a staging slot that an earlier put
  read must be preceded by that put's send-semaphore wait (the classic
  double-buffer hazard: overwriting bytes still on the wire);
- **VMEM discipline** — the fused-mask variant stages through exactly two
  tile-sized VMEM slots and only engages when the send stack fits
  ``ops.pallas_p2p.FUSED_MASK_VMEM_BUDGET``; the pre-masked variant
  carries no dead staging;
- **destination rows provably local** — every remote put lands in
  ``out_ref[ds(start, S)]`` where ``start`` is loaded from the meta
  scalar the host computes as ``axis_index * S`` (checked by producer
  chase in the ENCLOSING jaxpr), so the landing rows are exactly
  ``[me*S, (me+1)*S)`` — the plan's halo-slot numbering, never another
  shard's rows.

``python -m dgraph_tpu.analysis.kernel --selftest true`` runs the
vacuity guards: deliberately broken kernel variants (dropped send wait,
dropped recv wait, slot reuse without wait, wrong dst-row slot, oversized
staging) must each go RED while the real transport stays GREEN.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from dgraph_tpu.analysis.trace import walk_eqns  # noqa: F401  (re-export)

__all__ = [
    "collect_transports",
    "verify_transport",
    "audit_workload_kernels",
    "kernel_selftest_failures",
]


def _aval_space(aval) -> str:
    """Best-effort memory-space tag of a pallas MemRef aval ('vmem',
    'smem', 'semaphore', 'any', or '?' for plain arrays)."""
    s = str(aval)
    for tag in ("semaphore", "vmem", "smem", "any"):
        if f"<{tag}" in s or f"{tag}_mem" in s:
            return "semaphore" if tag == "semaphore" else tag
    return "?"


def _walk_with_parent(jaxpr, visit) -> None:
    """Like :func:`~dgraph_tpu.analysis.trace.walk_eqns` but hands the
    ENCLOSING jaxpr to ``visit(eqn, parent)`` — the kernel verifier needs
    it to chase a pallas_call operand back to its producer."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        visit(eqn, jaxpr)
        for p in eqn.params.values():
            for item in p if isinstance(p, (list, tuple)) else [p]:
                inner = getattr(item, "jaxpr", None)
                if inner is not None:
                    _walk_with_parent(getattr(inner, "jaxpr", inner), visit)
                elif hasattr(item, "eqns"):
                    _walk_with_parent(item, visit)


def collect_transports(closed_jaxpr) -> list:
    """Every ``pallas_call`` eqn carrying at least one remote DMA, paired
    with its enclosing jaxpr: ``[(eqn, parent_jaxpr), ...]``."""
    from dgraph_tpu.analysis.trace import _remote_put_count

    out = []

    def visit(eqn, parent):
        if eqn.primitive.name != "pallas_call":
            return
        inner = eqn.params.get("jaxpr")
        if inner is None:
            return
        if _remote_put_count(getattr(inner, "jaxpr", inner)):
            out.append((eqn, parent))

    _walk_with_parent(closed_jaxpr, visit)
    return out


# ---------------------------------------------------------------------------
# jaxpr decoding helpers
# ---------------------------------------------------------------------------


def _literal_val(x) -> Optional[int]:
    try:
        from jax._src.core import Literal
    except ImportError:  # pragma: no cover - jax layout drift
        from jax.core import Literal

    if isinstance(x, Literal):
        try:
            return int(x.val)
        except (TypeError, ValueError):
            return None
    if isinstance(x, int):
        return int(x)
    return None


def _indexer_key(transforms) -> tuple:
    """Hashable identity of a ref's indexing transforms: literal index
    values and slice (start, size) pairs, with dynamic starts reduced to
    the producing var's id (so the same loaded scalar matches)."""
    from jax.extend.core import Literal

    out = []
    for idxr in transforms or ():
        for idx in getattr(idxr, "indices", ()) or ():
            if isinstance(idx, Literal):
                out.append(("lit", _literal_val(idx)))
            elif hasattr(idx, "start"):  # Slice
                start = idx.start
                lit = _literal_val(start)
                out.append((
                    "slice",
                    lit if lit is not None else f"var{id(start)}",
                    getattr(idx, "size", None),
                ))
            elif isinstance(idx, int):
                out.append(("lit", idx))
            else:
                out.append(("var", id(idx)))
    return tuple(out)


def _first_slice(transforms):
    """The leading (start, size) of a ref's first indexer — the landing
    row window of a DMA destination."""
    for idxr in transforms or ():
        for idx in getattr(idxr, "indices", ()) or ():
            if hasattr(idx, "start") and hasattr(idx, "size"):
                return idx.start, int(idx.size)
            lit = _literal_val(idx)
            if lit is not None:
                return lit, 1
    return None, None


@dataclasses.dataclass
class _Dma:
    pos: int
    src: object
    src_t: object
    dst: object
    dst_t: object
    send_key: tuple  # (id(sem var), indexer key)
    recv_key: tuple
    remote: bool
    dst_start: object
    dst_size: Optional[int]


def _decode_dma(eqn, pos: int) -> _Dma:
    from jax import tree_util as jtu

    (src, src_t, dst, dst_t, dst_sem, dst_sem_t, src_sem, src_sem_t,
     device_id) = jtu.tree_unflatten(eqn.params["tree"], eqn.invars)
    start, size = _first_slice(dst_t)
    return _Dma(
        pos=pos, src=src, src_t=src_t, dst=dst, dst_t=dst_t,
        send_key=(id(src_sem), _indexer_key(src_sem_t))
        if src_sem is not None else None,
        recv_key=(id(dst_sem), _indexer_key(dst_sem_t))
        if dst_sem is not None else None,
        remote=device_id is not None,
        dst_start=start, dst_size=size,
    )


def _chase(producers: dict, var, through=("convert_element_type", "reshape",
                                          "broadcast_in_dim", "squeeze",
                                          "expand_dims")):
    """Follow single-operand pass-through eqns back to the interesting
    producer of ``var`` (or None for a jaxpr invar/constvar)."""
    seen = 0
    while var in producers and seen < 32:
        eqn = producers[var]
        if eqn.primitive.name not in through:
            return eqn
        var = eqn.invars[0]
        seen += 1
    return None


def _producer_map(jaxpr) -> dict:
    out = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out[v] = eqn
    return out


# ---------------------------------------------------------------------------
# the verifier
# ---------------------------------------------------------------------------


def verify_transport(call_eqn, parent_jaxpr, label: str, failures: list,
                     budget: Optional[int] = None) -> dict:
    """Statically verify ONE transport pallas_call's DMA discipline;
    returns the per-kernel record and appends human-readable failures."""
    import numpy as np

    from dgraph_tpu.ops.pallas_p2p import FUSED_MASK_VMEM_BUDGET

    budget = FUSED_MASK_VMEM_BUDGET if budget is None else budget
    kj = call_eqn.params["jaxpr"]
    kj = getattr(kj, "jaxpr", kj)

    def fail(msg):
        failures.append(f"[kernel:{label}] {msg}")

    # --- kernel operand layout (meta | mask | blocks | zeros | out |
    # staging | send_sems | recv_sems) -------------------------------------
    invars = list(kj.invars)
    if len(invars) != 8:
        fail(
            f"unrecognized transport kernel layout: {len(invars)} operands "
            f"(expected meta/mask/blocks/zeros/out + staging/send/recv "
            f"sems) — update analysis.kernel alongside ops.pallas_p2p"
        )
        return {"label": label, "ok": False}
    meta, mask, blocks, zeros, out_ref, staging, send_sems, recv_sems = invars
    meta_len = int(meta.aval.shape[0])
    n = (meta_len - 1) // 3
    if 3 * n + 1 != meta_len or n < 1:
        fail(f"meta operand length {meta_len} is not 3n+1")
        return {"label": label, "ok": False}
    blocks_shape = tuple(int(s) for s in blocks.aval.shape)
    S, F = blocks_shape[1], blocks_shape[2]
    itemsize = np.dtype(blocks.aval.dtype).itemsize
    fused = tuple(int(s) for s in mask.aval.shape) != (1, 1)
    out_rows = int(out_ref.aval.shape[0])

    # --- VMEM discipline ---------------------------------------------------
    staging_shape = tuple(int(s) for s in staging.aval.shape)
    tile_bytes = S * F * itemsize
    stack_bytes = n * tile_bytes
    if fused:
        if _aval_space(blocks.aval) != "vmem":
            fail("fused-mask kernel does not stage its send stack in VMEM")
        if stack_bytes > budget:
            fail(
                f"fused-mask send stack is {stack_bytes} B in VMEM; the "
                f"budget is {budget} B — this stack must fall back to "
                f"pre-masked HBM-direct puts"
            )
        if staging_shape != (2, S, F):
            import math

            fail(
                f"staging buffer is {staging_shape}; the double-buffer "
                f"contract is exactly two [S={S}, F={F}] slots "
                f"({2 * tile_bytes} B), not "
                f"{math.prod(staging_shape) * itemsize} B"
            )
    else:
        if staging_shape not in ((1, 1),):
            fail(
                f"pre-masked kernel carries a {staging_shape} staging "
                f"buffer — dead VMEM on the path that exists to avoid it"
            )

    # --- classify eqns in order --------------------------------------------
    starts: list = []
    waits: list = []  # (pos, waited key)
    slot_writes: list = []  # (pos, slot literal)
    meta_loads: dict = {}  # outvar -> literal index into meta
    for pos, eqn in enumerate(kj.eqns):
        name = eqn.primitive.name
        if name == "dma_start":
            starts.append(_decode_dma(eqn, pos))
        elif name == "dma_wait":
            d = _decode_dma(eqn, pos)
            # dma_wait waits the semaphore in its dst slot (wait_send
            # swaps src/dst so the send semaphore lands there)
            waits.append((pos, d.recv_key))
        elif name in ("swap", "addupdate") and eqn.invars and eqn.invars[0] is staging:
            # swap binds (ref, val, *transform_leaves); the staging write's
            # only dynamic-or-literal transform leaf is the slot index
            slot = None
            for v in eqn.invars[2:]:
                slot = _literal_val(v)
                if slot is not None:
                    break
            slot_writes.append((pos, slot))
        elif name == "get" and eqn.invars and eqn.invars[0] is meta:
            idx = None
            for v in eqn.invars[1:]:
                idx = _literal_val(v)
                if idx is not None:
                    break
            for ov in eqn.outvars:
                meta_loads[ov] = idx

    remote = [d for d in starts if d.remote]
    if not remote:
        fail("transport kernel issues no remote dma_start at all")

    # --- paired waits + nothing outstanding --------------------------------
    for d in starts:
        for key, which in ((d.send_key, "send"), (d.recv_key, "recv")):
            if key is None:
                if which == "send" and d.remote:
                    fail(f"remote dma_start at eqn {d.pos} has no send "
                         f"semaphore")
                continue
            if not any(w_pos > d.pos and w_key == key
                       for w_pos, w_key in waits):
                fail(
                    f"dma_start at eqn {d.pos} has no later dma_wait on its "
                    f"{which} semaphore — the transfer is unsynchronized"
                )
    per_key_starts: dict = {}
    for d in starts:
        for key in (d.send_key, d.recv_key):
            if key is not None:
                per_key_starts[key] = per_key_starts.get(key, 0) + 1
    per_key_waits: dict = {}
    for _pos, key in waits:
        per_key_waits[key] = per_key_waits.get(key, 0) + 1
    for key, n_started in per_key_starts.items():
        if per_key_waits.get(key, 0) < n_started:
            fail(
                f"semaphore {key[1]} outstanding at kernel exit: "
                f"{n_started} start(s), {per_key_waits.get(key, 0)} wait(s)"
            )

    # --- wait-before-reuse (double-buffer slot discipline) ------------------
    for w_pos, slot in slot_writes:
        for d in starts:
            if d.pos >= w_pos or d.src is not staging:
                continue
            d_slot = None
            for entry in _indexer_key(d.src_t):
                if entry[0] == "lit":
                    d_slot = entry[1]
                    break
            if d_slot != slot:
                continue
            waited = any(
                d.pos < p < w_pos and key == d.send_key
                for p, key in waits
            )
            if not waited:
                fail(
                    f"staging slot {slot} rewritten at eqn {w_pos} while "
                    f"the put started at eqn {d.pos} may still be reading "
                    f"it — wait the send semaphore before slot reuse"
                )

    # --- destination rows provably [me*S, (me+1)*S) -------------------------
    dst_slot_idx = 3 * n  # meta layout: targets[n] | sources[n] | ranks[n] | me*S
    for d in remote:
        if d.dst is not out_ref:
            fail(f"remote put at eqn {d.pos} does not target the halo "
                 f"output buffer")
            continue
        if d.dst_size != S:
            fail(
                f"remote put at eqn {d.pos} lands {d.dst_size} rows; the "
                f"halo slot is exactly S={S} rows"
            )
        start = d.dst_start
        lit = _literal_val(start)
        if lit is not None:
            fail(
                f"remote put at eqn {d.pos} lands at constant row {lit}, "
                f"not this shard's me*S halo slot"
            )
            continue
        if meta_loads.get(start, -1) != dst_slot_idx:
            fail(
                f"remote put at eqn {d.pos}: destination row is not loaded "
                f"from meta[{dst_slot_idx}] (the me*S slot) — landing rows "
                f"are not provably inside [me*S, (me+1)*S)"
            )
    if out_rows % S != 0:
        fail(f"halo buffer rows {out_rows} not a multiple of S={S}")

    # --- enclosing-jaxpr provenance: meta[3n] == axis_index * S -------------
    producers = _producer_map(parent_jaxpr)
    meta_src = _chase(producers, call_eqn.invars[0])
    ok_meta = False
    if meta_src is not None and meta_src.primitive.name == "concatenate":
        tail = meta_src.invars[-1]
        mul = _chase(producers, tail)
        if mul is not None and mul.primitive.name == "mul":
            lit = [_literal_val(v) for v in mul.invars]
            axis_ops = [
                _chase(producers, v) for v in mul.invars
                if _literal_val(v) is None
            ]
            ok_meta = (
                S in lit
                and any(
                    e is not None and e.primitive.name == "axis_index"
                    for e in axis_ops
                )
            )
    if remote and not ok_meta:
        fail(
            f"meta[{dst_slot_idx}] is not computed as axis_index * S in the "
            f"enclosing program — cannot prove the puts land in this "
            f"shard's own halo rows"
        )

    return {
        "label": label,
        "n_deltas": n,
        "s_pad": S,
        "feat_dim": F,
        "fused_mask": fused,
        "num_dma_starts": len(starts),
        "num_remote_puts": len(remote),
        "num_dma_waits": len(waits),
        "num_slot_writes": len(slot_writes),
        "stack_bytes": stack_bytes,
        "ok": True,
    }


# ---------------------------------------------------------------------------
# workload-level audit (the real transports, as the models trace them)
# ---------------------------------------------------------------------------


def audit_workload_kernels(w, programs=None) -> dict:
    """Pin ``pallas_p2p``, trace every registered program abstractly, and
    verify each transport kernel's DMA discipline. Returns a
    ``kind="kernel_audit"`` report (``ok``/``failures`` caller contract
    like the other audit tiers)."""
    import jax

    from dgraph_tpu import config as _cfg
    from dgraph_tpu.analysis.trace import PROGRAMS

    failures: list = []
    kernels = []
    saved = (_cfg.halo_impl, _cfg.tuned_halo_impl, _cfg.use_pallas_p2p)
    try:
        _cfg.set_flags(
            halo_impl="pallas_p2p", tuned_halo_impl=None, use_pallas_p2p=True
        )
        for label, build in (programs or PROGRAMS).items():
            fn, args = build(w)
            jaxpr = jax.make_jaxpr(fn)(*args)
            transports = collect_transports(jaxpr)
            if not transports:
                failures.append(
                    f"[kernel:{label}] pallas_p2p pinned but the program "
                    f"traced no transport kernels"
                )
            for i, (eqn, parent) in enumerate(transports):
                kernels.append(
                    verify_transport(eqn, parent, f"{label}#{i}", failures)
                )
    finally:
        _cfg.set_flags(
            halo_impl=saved[0], tuned_halo_impl=saved[1],
            use_pallas_p2p=saved[2],
        )
    return {
        "kind": "kernel_audit",
        "world_size": w.world_size,
        "num_halo_deltas": len(w.plan_np.halo_deltas),
        "kernels": kernels,
        "failures": failures,
        "ok": not failures,
    }


# ---------------------------------------------------------------------------
# vacuity guards: broken kernels the verifier MUST flag
# ---------------------------------------------------------------------------


def _mutant_jaxpr(W: int, S: int, F: int, deltas: tuple, mutation: Optional[str]):
    """Trace a transport-shaped kernel with one seeded discipline bug
    (``mutation`` in {None, 'drop_send_wait', 'drop_recv_wait',
    'no_slot_wait', 'bad_dst_row', 'oversize_staging'}) under shard_map —
    ``jax.make_jaxpr`` only, zero compiles."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm.collectives import shard_map_checks
    from dgraph_tpu.ops.pallas_p2p import _logical_device_ids

    n = len(deltas)
    slots = 4 if mutation == "oversize_staging" else 2

    def kern(meta_ref, mask_ref, blocks_ref, zeros_ref, out_ref, staging,
             send_sems, recv_sems):
        del zeros_ref
        dst_idx = 2 * n if mutation == "bad_dst_row" else 3 * n
        dst_row = meta_ref[dst_idx]
        copies = []
        for k in range(n):
            slot = k % slots
            if k >= slots and mutation != "no_slot_wait":
                copies[k - slots].wait_send()
            staging[slot] = blocks_ref[k] * mask_ref[k][:, None].astype(
                blocks_ref.dtype
            )
            c = pltpu.make_async_remote_copy(
                src_ref=staging.at[slot],
                dst_ref=out_ref.at[pl.ds(dst_row, S)],
                send_sem=send_sems.at[k],
                recv_sem=recv_sems.at[k],
                device_id=meta_ref[k],
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            c.start()
            copies.append(c)
        if mutation == "no_slot_wait":
            # drain EVERY send here so only the reuse ORDERING is wrong
            # (the paired-wait rule stays satisfied; rule C alone fires)
            drain = copies
        else:
            # the slot-reuse waits above consumed all but the last
            # ``slots`` sends — drain those, minus the seeded drop
            drain = copies[-slots:]
            if mutation == "drop_send_wait":
                drain = drain[:-1]
        for c in drain:
            c.wait_send()
        for k in range(n):
            if mutation == "drop_recv_wait" and k == n - 1:
                continue
            src_row = meta_ref[2 * n + k] * S
            landing = out_ref.at[pl.ds(src_row, S)]
            pltpu.make_async_copy(landing, landing, recv_sems.at[k]).wait()

    call = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((W * S, F), jnp.float32,
                                       vma=frozenset({"x"})),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((slots, S, F), jnp.float32),
            pltpu.SemaphoreType.DMA((n,)),
            pltpu.SemaphoreType.DMA((n,)),
        ],
        input_output_aliases={3: 0},
        interpret=True,
        name=f"dgraph_p2p_mutant_{mutation or 'clean'}",
    )

    def body(blocks, mask):
        me = lax.axis_index("x")
        d = jnp.asarray(deltas, jnp.int32)
        targets = (me + d) % W
        sources = (me - d) % W
        meta = jnp.concatenate([
            _logical_device_ids("x", targets),
            _logical_device_ids("x", sources),
            sources,
            (me * S)[None],
        ]).astype(jnp.int32)
        zeros = lax.pcast(jnp.zeros((W * S, F), jnp.float32), "x",
                          to="varying")
        return call(meta, mask, blocks, zeros)

    mesh = jax.make_mesh((W,), ("x",))
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("x"), P("x")),
        out_specs=P("x"),
        **shard_map_checks(impl="pallas_p2p"),
    )
    blocks = jax.ShapeDtypeStruct((W * n, S, F), np.float32)
    mask = jax.ShapeDtypeStruct((W * n, S), np.float32)
    return jax.make_jaxpr(fn)(blocks, mask)


def kernel_selftest_failures(W: int = 4, S: int = 8, F: int = 16) -> list:
    """Vacuity guards for the DMA verifier: the clean kernel must verify
    GREEN and every seeded discipline mutation must go RED. Needs W >= 4
    so three live deltas exercise the slot-reuse path."""
    deltas = tuple(range(1, min(W, 4)))
    failures: list = []

    def run(mutation):
        jaxpr = _mutant_jaxpr(W, S, F, deltas, mutation)
        transports = collect_transports(jaxpr)
        if len(transports) != 1:
            return [f"expected 1 transport, traced {len(transports)}"]
        mism: list = []
        verify_transport(*transports[0], f"mutant:{mutation}", mism)
        return mism

    clean = run(None)
    if clean:
        failures.append(
            f"verifier flagged the CLEAN transport kernel: {clean[:3]}"
        )
    for mutation, hint in (
        ("drop_send_wait", "send semaphore"),
        ("drop_recv_wait", "recv semaphore"),
        ("no_slot_wait", "slot"),
        ("bad_dst_row", "meta["),
        ("oversize_staging", "staging"),
    ):
        mism = run(mutation)
        if not mism:
            failures.append(
                f"verifier accepted the {mutation!r} mutant — the "
                f"{hint} rule is vacuous"
            )
    return failures


def main(cfg) -> dict:
    import json

    from dgraph_tpu.obs.health import RunHealth

    health = RunHealth.begin("analysis.kernel")
    try:
        failures: list = []
        report = None
        if cfg.selftest:
            failures.extend(kernel_selftest_failures())
        if cfg.audit:
            from dgraph_tpu.analysis.trace import build_audit_workload

            w = build_audit_workload(cfg.world, seed=cfg.seed)
            report = audit_workload_kernels(w)
            failures.extend(report["failures"])
        out = {
            "kind": "kernel_verifier",
            "failures": failures,
            "audit": {
                "kernels": len(report["kernels"]),
                "ok": report["ok"],
            } if report else None,
            "run_health": health.finish(
                "; ".join(failures) if failures else None,
                wedge="stage_failure" if failures else None,
            ),
        }
        print(json.dumps(out, indent=cfg.indent or None))
        if failures:
            raise SystemExit(
                "kernel verifier FAILED: " + "; ".join(failures[:8])
            )
        return out
    except SystemExit:
        raise
    except BaseException as e:
        print(json.dumps({
            "kind": "kernel_verifier",
            "failures": [f"{type(e).__name__}: {e}"],
            "run_health": health.finish(
                f"kernel verifier crashed: {type(e).__name__}: {e}",
                wedge="stage_failure",
            ),
        }))
        raise


if __name__ == "__main__":
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    from dgraph_tpu.utils.cli import parse_config

    @dataclasses.dataclass
    class Config:
        """Pallas DMA-discipline verifier (``--selftest`` runs the broken-
        kernel vacuity guards; ``--audit`` verifies the real transports)."""

        selftest: bool = False
        audit: bool = True
        world: int = 2
        seed: int = 0
        indent: int = 0

    main(parse_config(Config))
