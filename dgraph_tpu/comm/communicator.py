"""Communicator facade — the user-facing API, parity with
``DGraph/Communicator.py`` (SURVEY.md §1 L4).

The reference validates a backend name in {nccl, mpi, nvshmem} and forwards
every call to a backend engine (``Communicator.py:24-141``). On TPU there is
one runtime (XLA), so the "backends" collapse to two *modes*:

- ``"tpu"`` (:class:`TpuComm`): SPMD over a mesh axis; methods must be
  called inside ``shard_map`` (or a jitted function with the mesh bound).
  Collectives lower to XLA ``all_to_all``/``psum`` over ICI/DCN — the
  NCCL/NVSHMEM/MPI wire mechanics (SURVEY.md §2.4) are all subsumed.
- ``"single"`` (:class:`SingleComm`): world size 1, no collectives — the
  reference's ``SingleProcessDummyCommunicator`` pattern
  (``GraphCast/dist_utils.py:8-39``), used so model code is testable
  without a mesh. Model code is byte-identical under either comm — the
  reference's key "fake backend" design point, kept on purpose.

Unlike the reference there is no process-group initialization to perform
(no ``init_process_group`` collective; ``jax.distributed.initialize`` is
only needed for true multi-host runs and is orthogonal to this object), so
``Communicator.init_process_group`` simply constructs the right comm object.
Methods that exist purely for API parity (``barrier``, ``destroy``,
``alloc_buffer``) are cheap no-ops or jnp allocations, documented as such.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from dgraph_tpu.comm import collectives
from dgraph_tpu.comm.mesh import GRAPH_AXIS, REPLICA_AXIS
from dgraph_tpu.plan import EdgePlan, HaloSpec

# Every collective issued through the facade carries a named region so
# Perfetto traces (jax.profiler.trace) attribute wire time to the API
# call that caused it (collectives.py annotates the primitive layer the
# same way).
from dgraph_tpu.utils.timing import named_scope as _scoped


@dataclasses.dataclass(frozen=True)
class _BaseComm:
    """Static (hashable, non-pytree) comm descriptor; safe as a flax module
    attribute or jit static arg."""

    graph_axis: Optional[str]
    replica_axis: Optional[str]

    # -- world/rank introspection (inside shard_map for tpu mode) --
    def get_rank(self):
        if self.graph_axis is None:
            return 0
        return lax.axis_index(self.graph_axis)

    def get_world_size(self) -> int:
        raise NotImplementedError

    # -- the differentiable primitives (L5) --
    def halo_exchange(self, x, halo: HaloSpec, deltas=None, impl=None,
                      wire_format=None):
        """Exchange boundary features. ``deltas``/``impl``/``wire_format``
        (from the plan / :func:`collectives.resolve_plan_impl` /
        :func:`collectives.resolve_plan_wire_format`) select the lowering
        and payload codec — resolve once per call site and thread them, so
        one jitted step can never mix lowerings (plan-less callers default
        to the padded all_to_all with the fp32 identity wire)."""
        return collectives.halo_exchange(
            x, halo, self.graph_axis, deltas=deltas, impl=impl,
            wire_format=wire_format,
        )

    def halo_exchange_overlap(self, x, plan: EdgePlan):
        """The overlap lowering's exchange: double-buffered ppermute rounds
        whose [W*S, F] result the boundary takes index directly."""
        return collectives.halo_exchange_overlap(
            x, plan.halo, self.graph_axis, tuple(plan.halo_deltas),
            collectives.resolve_plan_wire_format(plan, self.graph_axis),
        )

    def overlap_active(self, plan: EdgePlan) -> bool:
        """True when this plan lowers its halo exchange as the
        interior/boundary overlap schedule (models' routing predicate)."""
        return collectives.overlap_active(plan, self.graph_axis)

    def interior_take(self, x, plan: EdgePlan, side: str = "src"):
        """Interior-subset per-edge rows from the local table (no
        dependence on the in-flight exchange)."""
        return collectives.interior_take(x, plan, side)

    def boundary_take(self, x_or_halo, plan: EdgePlan, side: str = "src"):
        """Boundary-subset per-edge rows (halo side reads the exchange
        output buffer; owner side reads the local table)."""
        return collectives.boundary_take(x_or_halo, plan, side)

    def interior_scatter_sum(self, edata_int, plan: EdgePlan, side: str = "dst"):
        return collectives.interior_scatter_sum(edata_int, plan, side)

    def boundary_scatter_sum(self, edata_bnd, plan: EdgePlan, side: str = "dst"):
        return collectives.boundary_scatter_sum(edata_bnd, plan, side)

    def gather_scatter_overlap(self, x_local, halo_buf, plan: EdgePlan,
                               edge_weight=None):
        """Overlap-scheduled neighbor sum into the owner side (interior
        from the local table while the boundary rounds fly, then merge)."""
        return collectives.gather_scatter_overlap(
            x_local, halo_buf, plan, edge_weight
        )

    def scatter_bias_relu_overlap(self, stream_local, halo_buf, bias,
                                  plan: EdgePlan, side: str = "dst",
                                  edge_weight=None):
        """Overlap-scheduled fused Σ w·relu(stream + bias) aggregation."""
        return collectives.scatter_bias_relu_overlap(
            stream_local, halo_buf, bias, plan, side, self.graph_axis,
            edge_weight,
        )

    def gather(self, x, plan: EdgePlan, side: str = "src"):
        return collectives.gather(x, plan, side, self.graph_axis)

    def halo_extend(self, x, plan: EdgePlan, side: str = "src"):
        """gather's communication half: ONE full-width halo exchange ->
        the extended vertex table. Pair with local_take to feature-chunk
        the local work without re-issuing the collective per chunk."""
        return collectives.halo_extend(x, plan, side, self.graph_axis)

    def local_take(self, x_full, plan: EdgePlan, side: str = "src"):
        """gather's local half (no collectives): per-edge rows from the
        halo-extended table."""
        return collectives.local_take(x_full, plan, side)

    def gather_concat(self, x_src, x_dst, plan: EdgePlan):
        return collectives.gather_concat(x_src, x_dst, plan, self.graph_axis)

    def scatter(self, edata, plan: EdgePlan, side: str = "dst"):
        """Scatter-add per-edge values to vertices (``op=sum`` only, like the
        reference's maintained path, ``NCCLBackendEngine.py:183-215``)."""
        return collectives.scatter_sum(edata, plan, side, self.graph_axis)

    scatter_sum = scatter

    def scatter_bias_relu(self, edata, bias, plan: EdgePlan, side: str = "dst",
                          edge_weight=None):
        """Fused Σ w·relu(edata + bias[owner]) aggregation (the reference's
        fused scatter kernel family; Pallas on TPU, composed ops elsewhere)."""
        return collectives.scatter_bias_relu(
            edata, bias, plan, side, self.graph_axis, edge_weight
        )

    def take_scatter_bias_relu(self, table, bias, plan: EdgePlan,
                               stream_side: str = "src",
                               owner_side: str = "dst", edge_weight=None):
        """``scatter_bias_relu(local_take(table), bias)`` as one op whose
        gradient to ``table`` is the transposed aggregation from
        owner-side vertex tables where that can pay
        (:func:`collectives.take_scatter_bias_relu`)."""
        return collectives.take_scatter_bias_relu(
            table, bias, plan, stream_side, owner_side, self.graph_axis,
            edge_weight,
        )

    @_scoped("dgraph.comm.put")
    def put(self, send: jax.Array) -> jax.Array:
        """Deliver per-peer blocks by offsets — the ``BackendEngine.put``
        contract (``Engine.py:67-86``): two-sided backends alltoallv the
        blocks; one-sided backends write them at precomputed remote
        offsets. On TPU both collapse to ONE ``lax.all_to_all`` whose
        received blocks land in sender-rank order — exactly the
        ``CommPattern.put_forward_remote_offset`` positions (the plan's
        halo-slot numbering), so no receive-placement pass exists.

        Args:
          send: [W, S, F] — block ``send[p]`` goes to peer p (pad to the
            common S; mask padding upstream).
        Returns: [W*S, F]; rows [p*S, (p+1)*S) hold peer p's block.
        """
        W, S, F = send.shape
        if self.graph_axis is None:
            if W != 1:
                raise ValueError("put with world_size 1 expects send.shape[0] == 1")
            return send.reshape(S, F)
        recv = lax.all_to_all(send, self.graph_axis, split_axis=0, concat_axis=0)
        return recv.reshape(W * S, F)

    @_scoped("dgraph.comm.seq_attention")
    def seq_attention(self, q, k, v, *, causal: bool = False, kv_mask=None,
                      impl: str = "ring", mask=None):
        """Exact attention over the axis-sharded token/vertex dimension.

        ``tpu`` mode runs ring attention (K/V blocks stream around the
        graph axis via ppermute — :mod:`dgraph_tpu.parallel.sequence`) or,
        with ``impl='ulysses'``, the all-to-all head-sharded variant;
        ``single`` mode is the dense oracle. All three are exact, so model
        code is byte-identical under any choice. Wherever a device ends up
        holding a full-sequence view (single mode, or the Ulysses dense
        stage), the Mosaic kernels take over when enabled + self-checked +
        the shapes qualify (``config.use_flash_attention``): the splash
        kernels for a plain causal call, the library's flash kernels for one
        with a ``kv_mask`` or not causal (``sequence._flash_dense``).

        Args:
          q/k/v: [T_loc, H, D] per-shard (full [T, H, D] in single mode);
            k and v may have fewer heads (grouped-query: KV head
            ``j // (H / Hkv)`` serves query head j), and v a head size of
            its own (the result's; one device only).
          kv_mask: [T_loc] 1.0 = real position (padding excluded from keys).
          impl: 'ring' (default; O(T/W) memory, ICI neighbor hops) or
            'ulysses' (2 all_to_alls, needs heads % axis == 0).
          mask: a structured mask object beside ``causal``
            (``parallel.sequence.BlockDiffusionMask``, ``WindowMask``),
            over the full sequence. ``single`` mode only: the dense oracle honours it
            exactly; on a TPU the tile-skipping splash kernels do, with
            native grouped-query heads, once their self-check has passed
            for this kind of mask and head grouping. Ring and Ulysses
            refuse it by name.
        """
        from dgraph_tpu.parallel.sequence import (
            _flash_applicable,
            _flash_dense,
            _splash_dense,
            dense_attention,
            repeat_kv,
            ring_attention,
            ulysses_attention,
        )

        if impl not in ("ring", "ulysses"):
            raise ValueError(f"unknown seq_attention impl: {impl!r}")
        if mask is not None:
            if causal or kv_mask is not None:
                raise ValueError(
                    f"the {mask.name} mask stands in causal's place and "
                    f"takes no kv_mask (causal={causal})")
            if self.graph_axis is not None:
                raise NotImplementedError(
                    f"{impl} attention has no {mask.name} mask: a structured "
                    f"mask runs where one device holds the whole sequence "
                    f"(ROADMAP R11)")
            if _flash_applicable(q, require_pinned=True, mask=mask,
                                 group=q.shape[1] // k.shape[1],
                                 v_head_dim=v.shape[-1]):
                return _splash_dense(q, k, v, mask=mask, scale=None)
            return dense_attention(q, k, v, mask=mask)
        if self.graph_axis is None:
            # flash here ONLY on an explicit pinned True (post-self-check):
            # single mode is the dense ORACLE parity harnesses compare
            # against — an unverified kernel must not replace it on auto
            if _flash_applicable(q, require_pinned=True,
                                 group=q.shape[1] // k.shape[1],
                                 v_head_dim=v.shape[-1], causal=causal,
                                 kv_mask=kv_mask):
                return _flash_dense(q, k, v, causal=causal, scale=None,
                                    kv_mask=kv_mask)
            return dense_attention(q, k, v, causal=causal, kv_mask=kv_mask)
        if v.shape[-1] != q.shape[-1]:
            raise NotImplementedError(
                f"{impl} attention carries one head size; values of "
                f"{v.shape[-1]} beside a q.k head of {q.shape[-1]} run where "
                f"one device holds the whole sequence (ROADMAP R10)")
        k, v = repeat_kv(q, k, v)  # the ring and Ulysses know one head count
        if impl == "ulysses":
            return ulysses_attention(
                q, k, v, self.graph_axis, causal=causal, kv_mask=kv_mask
            )
        return ring_attention(
            q, k, v, self.graph_axis, causal=causal, kv_mask=kv_mask
        )

    # -- reductions over mesh axes --
    @_scoped("dgraph.comm.all_reduce_sum")
    def all_reduce_sum(self, x):
        if self.graph_axis is None:
            return x
        return lax.psum(x, self.graph_axis)

    @_scoped("dgraph.comm.all_reduce_mean")
    def all_reduce_mean(self, x):
        if self.graph_axis is None:
            return x
        return lax.pmean(x, self.graph_axis)

    @_scoped("dgraph.comm.replica_mean")
    def replica_mean(self, x):
        if self.replica_axis is None:
            return x
        return lax.pmean(x, self.replica_axis)

    @_scoped("dgraph.comm.grad_sync")
    def grad_sync(self, grads):
        """Gradient synchronization — the DDP all-reduce equivalent
        (``experiments/OGB/main.py:111-112``): SUM over the graph axis (each
        shard holds a different slice of the one sample, so shard grads are
        partial sums of the same global loss) and MEAN over the replica axis
        (each replica holds a different sample). Matches the reference's
        loss scaling ``* ranks_per_sample / world_size``
        (``train_graphcast.py:29-34``)."""
        if self.graph_axis is not None:
            grads = jax.tree.map(lambda g: lax.psum(g, self.graph_axis), grads)
        if self.replica_axis is not None:
            grads = jax.tree.map(lambda g: lax.pmean(g, self.replica_axis), grads)
        return grads

    # -- parity no-ops --
    def barrier(self):
        """No-op: XLA's dataflow scheduling orders collectives; the
        reference's liberal ``dist.barrier()`` has no TPU analogue."""

    def destroy(self):
        """No-op (reference parity; and note ``Communicator.destroy`` in the
        reference never called the engine's destroy either — SURVEY §2.6)."""

    def alloc_buffer(self, shape, dtype=jnp.float32):
        """Parity with ``Communicator.alloc_buffer`` (``Communicator.py:99``):
        on TPU buffers are values, not symmetric-heap allocations."""
        return jnp.zeros(shape, dtype)


@dataclasses.dataclass(frozen=True)
class TpuComm(_BaseComm):
    """SPMD communicator bound to mesh axis names. Use inside shard_map."""

    world_size: int = 1

    def get_world_size(self) -> int:
        return self.world_size


@dataclasses.dataclass(frozen=True)
class SingleComm(_BaseComm):
    """World-size-1 communicator (no mesh, no collectives)."""

    def get_world_size(self) -> int:
        return 1


class Communicator:
    """Constructor facade, parity with ``DGraph/Communicator.py:24-66``."""

    SUPPORTED_BACKENDS = ("tpu", "single")

    @staticmethod
    def init_process_group(
        backend: str = "tpu",
        *,
        world_size: Optional[int] = None,
        graph_axis: str = GRAPH_AXIS,
        replica_axis: Optional[str] = None,
    ) -> _BaseComm:
        if backend == "tpu":
            if world_size is None:
                raise ValueError("backend='tpu' requires world_size (graph-axis size)")
            return TpuComm(
                graph_axis=graph_axis, replica_axis=replica_axis, world_size=world_size
            )
        if backend == "single":
            return SingleComm(graph_axis=None, replica_axis=replica_axis)
        raise ValueError(
            f"Backend {backend!r} not supported; expected one of "
            f"{Communicator.SUPPORTED_BACKENDS} (the reference's nccl/mpi/nvshmem "
            "backends are all subsumed by 'tpu' — SURVEY.md §2.4)"
        )
