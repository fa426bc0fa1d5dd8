"""Mesh construction and sharding helpers.

The reference composes partition groups × replicas by integer arithmetic on
ranks (``ranks_per_graph``; ``NCCLBackendEngine.py:56-64``,
``GraphCast/dist_utils.py:50-113``). On TPU this is a 2-D
``jax.sharding.Mesh`` with axes ``('replica', 'graph')``: graph-partition
collectives ride the inner (ICI-contiguous) ``graph`` axis; data-parallel
gradient sync rides ``replica`` (ICI or DCN for multi-slice — XLA routes
hybrid meshes automatically).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


GRAPH_AXIS = "graph"
REPLICA_AXIS = "replica"


def make_graph_mesh(
    ranks_per_graph: Optional[int] = None,
    num_replicas: int = 1,
    devices=None,
) -> Mesh:
    """Build a ``('replica', 'graph')`` mesh over the first
    ``num_replicas * ranks_per_graph`` of ``devices`` (a sub-mesh of the
    host when that is fewer than all of them).

    ``ranks_per_graph`` defaults to (num_devices / num_replicas) — the
    reference's ``ranks_per_graph`` knob (``NCCLBackendEngine.py:56-64``).
    Both axes are ``Auto``: every program here places its data with
    explicit ``shard_map`` specs and indexes global arrays freely outside
    it, which ``jax.make_mesh``'s default ``Explicit`` axes reject.
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if ranks_per_graph is None:
        ranks_per_graph = n // num_replicas
    need = ranks_per_graph * num_replicas
    if not 0 < need <= n:
        raise ValueError(
            f"ranks_per_graph ({ranks_per_graph}) x num_replicas ({num_replicas})"
            f" needs {need} devices; have {n}"
        )
    return jax.make_mesh(
        (num_replicas, ranks_per_graph), (REPLICA_AXIS, GRAPH_AXIS),
        (AxisType.Auto, AxisType.Auto), devices=devices[:need],
    )


def put_on_graph_axis(tree, mesh: Mesh):
    """Place a pytree of ``[W, ...]`` leaves (a stacked plan or batch) on
    the mesh with ``NamedSharding(mesh, P('graph'))``: device ``r`` of the
    graph axis holds row ``r`` and nothing else. (``jnp.asarray`` would
    put the whole stack on the default device and leave every step to
    re-shard from it.) Streamed feature tables too large to stack on the
    host use :func:`dgraph_tpu.data.memmap.shard_rows_to_device`.

    The always-on stage ``setup.place`` (:func:`dgraph_tpu.obs.spans.stage`)
    times the host's part only: ``device_put`` returns once the copies are
    issued, and this function does not wait for them."""
    from dgraph_tpu.obs import spans

    with spans.stage("setup.place", **tree_size(tree)):
        return jax.device_put(tree, NamedSharding(mesh, P(GRAPH_AXIS)))


def tree_size(tree) -> dict:
    """``{"leaves", "bytes"}`` of a pytree of arrays: the attributes that
    size a set-up stage (:func:`dgraph_tpu.obs.spans.stage`) over it."""
    leaves = jax.tree.leaves(tree)
    return {"leaves": len(leaves),
            "bytes": sum(int(getattr(leaf, "nbytes", 0)) for leaf in leaves)}


def plan_in_specs(plan) -> object:
    """A pytree of ``P('graph')`` matching ``plan``'s structure, for shard_map
    in_specs: every plan leaf has a leading [world_size] axis."""
    return jax.tree.map(lambda _: P(GRAPH_AXIS), plan)


def squeeze_plan(plan):
    """Drop the leading per-shard axis of size 1 that shard_map leaves on
    every plan leaf (use inside the shard_map body)."""
    return jax.tree.map(lambda leaf: leaf[0], plan)


def replicated_specs(tree) -> object:
    """P() (fully replicated) specs for a pytree (e.g. model params)."""
    return jax.tree.map(lambda _: P(), tree)
