"""dgraph_tpu — a TPU-native framework for distributed full-graph GNN training.

A ground-up JAX/XLA/Pallas re-design of the capabilities of LBANN/DGraph
(reference: /root/reference, surveyed in SURVEY.md): vertex-partitioned graphs
sharded over a TPU mesh, halo exchange and plan-based distributed
gather/scatter-sum lowered to XLA collectives (`all_to_all` / `ppermute` over
ICI/DCN) under `jax.shard_map`, and local CSR aggregation as (Pallas-backed)
segment reductions.

Architecture (vs. the reference's layer map, SURVEY.md §1):

- The reference's three backend engines (NCCL / MPI / NVSHMEM,
  ``DGraph/distributed/{nccl,mpi,nvshmem}``) collapse into ONE programming
  model on TPU: SPMD via ``jax.shard_map`` over a ``jax.sharding.Mesh`` with
  XLA collectives. There is no process-group plumbing; ``jax.distributed``
  and the XLA runtime own the wire.
- The reference's comm-plan builders (``DGraph/distributed/commInfo.py``,
  ``nccl/_NCCLCommPlan.py``) become pure host-side numpy plan builders
  (:mod:`dgraph_tpu.plan`) that emit **static-shape, padded** plans — exactly
  what XLA's compile-once model wants.
- The reference's CUDA local kernels (``DGraph/distributed/csrc``) become
  jnp gather / segment-sum with optional Pallas TPU kernels
  (:mod:`dgraph_tpu.ops`). TPU has no atomics, so scatter-add is a
  (sorted-)segment reduction, which the plan builder's dedup/sort already
  sets up.
- The user-facing :class:`~dgraph_tpu.comm.Communicator` facade keeps the
  reference's API shape (``DGraph/Communicator.py``) with backends
  ``"tpu"`` (mesh-sharded SPMD) and ``"single"`` (the reference's
  SingleProcessDummyCommunicator pattern, for tests and 1-device runs).
"""

from dgraph_tpu.version import __version__
from dgraph_tpu import partition
from dgraph_tpu.plan import (
    CommPattern,
    EdgePlan,
    HaloSpec,
    build_comm_pattern,
    build_edge_plan,
)
from dgraph_tpu.comm import Communicator, TpuComm, SingleComm

__all__ = [
    "__version__",
    "partition",
    "CommPattern",
    "EdgePlan",
    "HaloSpec",
    "build_comm_pattern",
    "build_edge_plan",
    "Communicator",
    "TpuComm",
    "SingleComm",
]
