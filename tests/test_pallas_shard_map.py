"""Every kernel of ``ops/pallas_segment.py`` INSIDE ``jax.shard_map`` with
the vma checker on (interpret mode, 8-device mesh), forward and ``jax.grad``,
against the jnp oracle run through the same shard_map.

On a TPU the default flags put these kernels inside the train step's
shard_map; a ``pallas_call`` whose ``out_shape`` carries no ``vma`` fails to
trace there. The kernel tests in ``test_pallas_segment.py`` call the kernels
outside shard_map, and on CPU the dispatch never picks Pallas, so only this
file sees that failure."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from dgraph_tpu.comm.collectives import shard_map_checks
from dgraph_tpu.comm.mesh import GRAPH_AXIS
from dgraph_tpu.ops import local as local_ops
from dgraph_tpu.ops.pallas_segment import (
    max_chunks_hint,
    max_vblocks_hint,
    sorted_row_gather,
    sorted_segment_sum,
    sorted_segment_sum_bias_relu,
)

# The TPU interpreter, not ``interpret=True``: the generic interpreter
# evaluates the scalar-prefetch index maps with unvarying grid indices
# against the (varying) prefetched operands and trips the checker itself.
INTERP = pltpu.InterpretParams()
W, E, N, F = 8, 256, 64, 8
BE, BN = 128, 32


def _shards(rng):
    ids = np.sort(rng.integers(0, N, (W, E)), axis=1).astype(np.int32)
    ids[:, -16:] = N + 1  # padded-edge tail
    data = rng.normal(size=(W, E, F)).astype(np.float32)
    bias = rng.normal(size=(W, N, F)).astype(np.float32)
    wgt = rng.uniform(0.5, 2.0, (W, E)).astype(np.float32)
    mc = max(max_chunks_hint(i, N, BE, BN) for i in ids)
    mv = max(max_vblocks_hint(i, N, BE, BN) for i in ids)
    return ids, data, bias, wgt, mc, mv


def _run(mesh, body, *args):
    """value and grads (wrt the float operands) of sum(body(...)**2) with
    ``body`` applied per shard under shard_map, checker on."""
    n_float = sum(np.issubdtype(a.dtype, np.floating) for a in args)

    def loss(*a):
        out = jax.shard_map(
            lambda *s: body(*(x[0] for x in s))[None],
            mesh=mesh,
            in_specs=tuple(P(GRAPH_AXIS) for _ in a),
            out_specs=P(GRAPH_AXIS),
            **shard_map_checks(),
        )(*a)
        return (out.astype(jnp.float32) ** 2).sum(), out

    (_, out), grads = jax.jit(
        jax.value_and_grad(loss, argnums=tuple(range(n_float)), has_aux=True)
    )(*map(jnp.asarray, args))
    return [np.asarray(out)] + [np.asarray(g) for g in grads]


def _assert_close(got, want):
    for g, w in zip(got, want, strict=True):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def test_checker_is_on():
    assert shard_map_checks() == {}


def test_segment_sum_in_shard_map(mesh8, rng):
    ids, data, _, _, mc, _ = _shards(rng)
    got = _run(mesh8, lambda d, i: sorted_segment_sum(
        d, i, N, max_chunks_per_block=mc, block_e=BE, block_n=BN,
        interpret=INTERP), data, ids)
    want = _run(mesh8, lambda d, i: local_ops.segment_sum(
        d, i, N, indices_are_sorted=True), data, ids)
    _assert_close(got, want)


def test_row_gather_in_shard_map(mesh8, rng):
    ids, _, bias, _, mc, mv = _shards(rng)
    got = _run(mesh8, lambda x, i: sorted_row_gather(
        x, i, max_vblocks=mv, block_e=BE, block_n=BN, scatter_mc=mc,
        interpret=INTERP), bias, ids)
    want = _run(mesh8, lambda x, i: local_ops.row_take(x, i, oob="fill"),
                bias, ids)
    _assert_close(got, want)


def _composed_bias_relu(d, b, w, i):
    m = jax.nn.relu(d + local_ops.row_take(b, i, oob="fill"))
    if w is not None:
        m = m * w[:, None]
    return local_ops.segment_sum(m, i, N, indices_are_sorted=True)


def test_fused_bias_relu_weighted_in_shard_map(mesh8, rng):
    ids, data, bias, wgt, mc, _ = _shards(rng)
    got = _run(mesh8, lambda d, b, w, i: sorted_segment_sum_bias_relu(
        d, i, b, N, edge_weight=w, max_chunks_per_block=mc, block_e=BE,
        block_n=BN, interpret=INTERP, precision="highest"),
        data, bias, wgt, ids)
    want = _run(mesh8, _composed_bias_relu, data, bias, wgt, ids)
    _assert_close(got, want)


def _bwd_fused_count():
    from dgraph_tpu.obs.metrics import default_registry

    return default_registry.snapshot()["counters"].get("segsum.bwd_fused", 0)


@pytest.mark.parametrize("use_w", [False, True])
def test_fused_bias_relu_and_bwd_pair_in_shard_map(mesh8, rng, use_w):
    """``gather_mv`` > 0, with and without an edge weight: the VJP runs the
    fused-backward kernel pair (gd kernel, which also gives d_w, + the
    ``epilogue='act'`` reduction)."""
    ids, data, bias, wgt, mc, mv = _shards(rng)
    wgt[:, ::7] = 0.0  # some edges weigh nothing
    floats = (data, bias, wgt) if use_w else (data, bias)

    def fused(d, b, *rest):
        *w, i = rest
        return sorted_segment_sum_bias_relu(
            d, i, b, N, edge_weight=w[0] if w else None,
            max_chunks_per_block=mc, block_e=BE, block_n=BN,
            interpret=INTERP, gather_mv=mv, precision="highest")

    def composed(d, b, *rest):
        *w, i = rest
        return _composed_bias_relu(d, b, w[0] if w else None, i)

    fused_before = _bwd_fused_count()
    got = _run(mesh8, fused, *floats, ids)
    assert _bwd_fused_count() == fused_before + 1
    want = _run(mesh8, composed, *floats, ids)
    assert len(got) == len(floats) + 1
    _assert_close(got, want)

