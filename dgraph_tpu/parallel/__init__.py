"""Parallelism strategies — one namespace over the mesh/collective layer.

Maps the reference's parallelism inventory (SURVEY.md §2.3) onto mesh axes:

- Graph/spatial partition parallelism (the reference's core; activations
  sharded by vertex, halo exchange per layer — the graph analogue of
  context/sequence parallelism): the ``graph`` mesh axis +
  :mod:`dgraph_tpu.comm.collectives`.
- Data parallelism (DDP gradient all-reduce): the ``replica`` mesh axis +
  :meth:`~dgraph_tpu.comm.communicator._BaseComm.grad_sync`.
- Hybrid partition-groups x replicas (``ranks_per_graph``,
  ``NCCLBackendEngine.py:56-64``): the 2-D ``('replica','graph')`` mesh from
  :func:`~dgraph_tpu.comm.mesh.make_graph_mesh`.
- Activation-stat parallelism (distributed BatchNorm,
  ``distributed_layers.py:22-207``):
  :class:`~dgraph_tpu.models.norm.DistributedBatchNorm`.

- Sequence/context parallelism (absent in the reference; first-class here):
  ring attention (K/V blocks streaming over ``lax.ppermute``) and the
  Ulysses all-to-all layout swap — :mod:`dgraph_tpu.parallel.sequence`.
- Pipeline parallelism: GPipe microbatch streaming over a ``pipe`` axis —
  :mod:`dgraph_tpu.parallel.pipeline`.
- Tensor parallelism: Megatron column/row-parallel linear pairs —
  :mod:`dgraph_tpu.parallel.tensor`.
- Expert parallelism: a dropless top-k sparse-expert layer that holds a
  share of the experts, alone or over an ``expert`` axis —
  :mod:`dgraph_tpu.parallel.expert`.

Every strategy in SURVEY §2.3 (plus four the reference lacks) is therefore
implemented and tested on the virtual 8-device mesh.
"""

from dgraph_tpu.parallel.expert import (
    held_experts_ffn,
    load_balance_loss,
    moe_apply,
    route_topk,
)
from dgraph_tpu.parallel.pipeline import pipeline_apply, stack_stage_params
from dgraph_tpu.parallel.tensor import (
    column_parallel_dense,
    row_parallel_dense,
    shard_columns,
    shard_rows,
    tensor_parallel_mlp,
)
from dgraph_tpu.parallel.sequence import (
    dense_attention,
    ring_attention,
    ring_attention_sharded,
    ulysses_attention,
)
from dgraph_tpu.comm import collectives
from dgraph_tpu.comm.collectives import (
    gather,
    gather_concat,
    halo_exchange,
    halo_scatter_sum,
    psum_mean,
    scatter_sum,
)
from dgraph_tpu.comm.mesh import (
    GRAPH_AXIS,
    REPLICA_AXIS,
    make_graph_mesh,
    plan_in_specs,
    replicated_specs,
    squeeze_plan,
)

__all__ = [
    "column_parallel_dense",
    "row_parallel_dense",
    "tensor_parallel_mlp",
    "shard_columns",
    "shard_rows",
    "moe_apply",
    "held_experts_ffn",
    "route_topk",
    "load_balance_loss",
    "pipeline_apply",
    "stack_stage_params",
    "dense_attention",
    "ring_attention",
    "ring_attention_sharded",
    "ulysses_attention",
    "collectives",
    "gather",
    "gather_concat",
    "halo_exchange",
    "halo_scatter_sum",
    "psum_mean",
    "scatter_sum",
    "GRAPH_AXIS",
    "REPLICA_AXIS",
    "make_graph_mesh",
    "plan_in_specs",
    "replicated_specs",
    "squeeze_plan",
]
