"""The fused GCN layer's aggregation as one op with one VJP (ISSUE 33):
``collectives.take_scatter_bias_relu``. Its forward is the two calls it
replaced; its gradient to the streamed table runs as the transposed
aggregation from owner-side vertex tables (``ops.pallas_segment``'s
``epilogue="grad"``) where the kernels run and a table slice fits on-chip
memory, and through the ops' own VJPs elsewhere.

On the CPU the dispatch never takes the Pallas branches, so the tests steer
``jax.default_backend`` and run the kernels in interpret mode (in the test,
not through an option of the program). The interpreters cannot run beside
collectives in one multi-device CPU program, and the op has none: a rank's
shard of a 2- or 4-rank plan is tested on one device, its halo rows filled
with what an exchange could have brought.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dgraph_tpu import config as cfg
from dgraph_tpu import plan as pl
from dgraph_tpu.comm import Communicator, collectives
from dgraph_tpu.obs.metrics import default_registry
from dgraph_tpu.ops import local as local_ops

V, E_HALF, F = 600, 2500, 256  # two 128-column chunks a layer


@pytest.fixture
def tpu_interpret(monkeypatch):
    """The program's TPU branches, their kernels interpreted."""
    from jax.experimental import pallas

    real_call = pallas.pallas_call

    def interpreted(*args, **kwargs):
        kwargs["interpret"] = True
        return real_call(*args, **kwargs)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas, "pallas_call", interpreted)


def _plan(world_size, seed=0, mask_some=True, **build):
    """A symmetrised random graph's plan, stacked over ranks, with padded
    edges (every rank's row ends in them) and, with ``mask_some``, a few
    REAL edges masked out: their route entries move to the sentinel."""
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, V, E_HALF), rng.integers(0, V, E_HALF)
    edges = np.stack([np.concatenate([src, dst]), np.concatenate([dst, src])])
    part = np.sort(rng.integers(0, world_size, V)).astype(np.int32)
    plan, _ = pl.build_edge_plan(
        edges, part, world_size=world_size, edge_owner="dst",
        pad_multiple=128, **build)
    assert (np.asarray(plan.num_edges) < plan.e_pad).all()
    if mask_some and plan.halo_sort_perm is not None:
        mask = np.asarray(plan.edge_mask).copy()
        for r in range(world_size):
            real = np.flatnonzero(mask[r] > 0)
            mask[r, rng.choice(real, size=len(real) // 10, replace=False)] = 0
        n_halo_rows = plan.n_src_pad + world_size * plan.halo.s_pad
        perm, sids, oids = pl.halo_sort_route(
            np.asarray(plan.src_index), mask, n_halo_rows,
            np.asarray(plan.dst_index))
        plan = dataclasses.replace(
            plan, edge_mask=mask, halo_sort_perm=perm, halo_sorted_ids=sids,
            halo_sorted_owner_ids=oids)
        pl.validate_plan(plan)
    return plan


def _shard(plan, r):
    return jax.tree.map(lambda leaf: jnp.asarray(np.asarray(leaf)[r]), plan)


def _oracle(table, bias, w, plan, tgt):
    """Composed jnp ops in float32: Σ_out tgt · Σ_e w·relu(table[src]·mask
    + bias[dst]) (a padded edge's dst is out of range and dropped)."""
    t32, b32 = table.astype(jnp.float32), bias.astype(jnp.float32)
    rows = jnp.take(t32, plan.src_index, axis=0) * plan.edge_mask[:, None]
    m = jax.nn.relu(rows + jnp.take(b32, plan.dst_index, axis=0, mode="fill",
                                    fill_value=0))
    if w is not None:
        m = m * w[:, None]
    out = jax.ops.segment_sum(m, plan.dst_index, num_segments=bias.shape[0])
    return (out * tgt).sum()


def _counts():
    c = default_registry.snapshot()["counters"]
    return np.array([c.get("gather.bwd_transposed", 0),
                     c.get("gather.bwd_permuted", 0)])


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("world_size", [1, 2, 4])
def test_gradients_match_the_composed_ops_and_the_oracle(
        tpu_interpret, world_size, dtype, weighted):
    """Gradients to table, bias and (where it is differentiated) the edge
    weight: bit-equal to the two ops' own VJPs, which the op replaced, and
    close to plain float32 autodiff; padded and masked edges, and on 2 and
    4 ranks a table with halo rows."""
    plan_np = _plan(world_size, seed=world_size)
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(7)
    n_rows = plan_np.n_src_pad + world_size * plan_np.halo.s_pad
    argnums = (0, 1, 2) if weighted else (0, 1)
    for r in range(world_size):
        plan = _shard(plan_np, r)
        table = jnp.asarray(rng.standard_normal((n_rows, F)), dt)
        bias = jnp.asarray(rng.standard_normal((plan_np.n_dst_pad, F)), dt)
        tgt = jnp.asarray(
            rng.standard_normal((plan_np.n_dst_pad, F)), jnp.float32)
        w = jnp.asarray(
            rng.uniform(0.5, 2.0, plan_np.e_pad), jnp.float32
        ) if weighted else None

        def loss(fn):
            return lambda t, b, w_: (fn(t, b, w_).astype(jnp.float32)
                                     * tgt).sum()

        one_op = loss(lambda t, b, w_: collectives.take_scatter_bias_relu(
            t, b, plan, "src", "dst", None, w_))
        two_ops = loss(lambda t, b, w_: collectives._take_then_scatter(
            t, b, w_, plan, "src", "dst", None))
        before = _counts()
        got = jax.jit(jax.grad(one_op, argnums))(table, bias, w)
        assert (_counts() - before).tolist() == [2, 0]  # two chunks
        want = jax.jit(jax.grad(two_ops, argnums))(table, bias, w)
        ref = jax.grad(_oracle, argnums)(table, bias, w, plan, tgt)
        tol = 1e-4 if dtype == "float32" else 0.05
        for name, a, b, c in zip(("d_table", "d_bias", "d_w"), got, want, ref):
            a, b, c = (np.asarray(x, np.float32) for x in (a, b, c))
            np.testing.assert_array_equal(a, b, err_msg=f"{name} rank {r}")
            assert np.abs(c).sum() > 0, name
            err = np.linalg.norm(a - c) / np.linalg.norm(c)
            assert err < tol, (name, r, err)
        if world_size > 1:  # the halo rows of the table got a gradient
            assert np.abs(np.asarray(
                got[0], np.float32)[plan_np.n_src_pad:]).sum() > 0


@pytest.mark.parametrize("k", [2, 3])
@pytest.mark.parametrize("world_size", [1, 2, 4])
def test_a_streamed_table_taken_in_row_parts_gives_the_same_bits(
        tpu_interpret, monkeypatch, world_size, k):
    """ISSUE 35: a streamed table slice too large for on-chip memory is
    gathered in k row parts (``ops.local.row_take``; gcn_papers100m.w4's
    halo-extended tables). The layer's value and its three gradients are
    what they were to the bit, on every rank's shard: each gathered row is
    one table row, chosen, and the VJP never sees the parts."""
    plan_np = _plan(world_size, seed=world_size)
    rng = np.random.default_rng(11)
    n_rows = plan_np.n_src_pad + world_size * plan_np.halo.s_pad
    whole_bytes = local_ops.GATHER_TABLE_BYTES

    def parts_counted():
        return default_registry.snapshot()["counters"].get(
            "gather.row_parts", 0)

    for r in range(world_size):
        plan = _shard(plan_np, r)
        table = jnp.asarray(rng.standard_normal((n_rows, F)), jnp.bfloat16)
        bias = jnp.asarray(
            rng.standard_normal((plan_np.n_dst_pad, F)), jnp.bfloat16)
        tgt = jnp.asarray(
            rng.standard_normal((plan_np.n_dst_pad, F)), jnp.float32)
        w = jnp.asarray(rng.uniform(0.5, 2.0, plan_np.e_pad), jnp.float32)

        def run():
            # a fresh function each time: jit caches a trace by identity
            def loss(t, b, w_):
                out = collectives.take_scatter_bias_relu(
                    t, b, plan, "src", "dst", None, w_)
                return (out.astype(jnp.float32) * tgt).sum()

            before = parts_counted()
            got = jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(table, bias, w)
            return parts_counted() - before, got

        monkeypatch.setattr(local_ops, "GATHER_TABLE_BYTES", whole_bytes)
        counted, whole = run()
        assert counted == 0
        monkeypatch.setattr(
            local_ops, "GATHER_TABLE_BYTES", -(-n_rows // k) * 128 * 2)
        counted, parted = run()
        assert counted == 2 * k  # two column chunks, traced once
        for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(parted)):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                err_msg=f"rank {r}")
        assert np.abs(np.asarray(parted[1][0], np.float32)).sum() > 0


def _engaged_args(plan_np, dtype=jnp.bfloat16):
    plan = _shard(plan_np, 0)
    n_rows = plan_np.n_src_pad + plan_np.world_size * plan_np.halo.s_pad
    table = jnp.ones((n_rows, F), dtype)
    bias = jnp.ones((plan_np.n_dst_pad, F), dtype)
    w = jnp.ones((plan_np.e_pad,), jnp.float32)
    return plan, table, bias, w


def test_the_forward_is_the_two_calls_it_replaced(tpu_interpret):
    """Undifferentiated (the eval step, the serve engine) the op is
    ``scatter_bias_relu(local_take(...))`` a chunk: the custom VJP's inner
    jaxpr is the composed function's, equation for equation."""
    plan, table, bias, w = _engaged_args(_plan(1))

    def one_op(t, b, w_, p):
        return collectives.take_scatter_bias_relu(
            t, b, p, "src", "dst", None, w_)

    def two_ops(t, b, w_, p):
        return collectives.map_vertex_chunks(
            lambda tc, bc: collectives.scatter_bias_relu(
                collectives.local_take(tc, p, "src"), bc, p, "dst", None,
                edge_weight=w_),
            (t, b))

    outer = jax.make_jaxpr(one_op)(table, bias, w, plan)
    (call,) = [e for e in outer.jaxpr.eqns
               if e.primitive.name.startswith("custom_vjp_call")]
    assert len(outer.jaxpr.eqns) == 1
    inner = call.params["call_jaxpr"]
    want = jax.make_jaxpr(two_ops)(table, bias, w, plan)
    assert str(inner.jaxpr) == str(want.jaxpr)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(one_op)(table, bias, w, plan), np.float32),
        np.asarray(jax.jit(two_ops)(table, bias, w, plan), np.float32))


def _gcn_backward_counts(plan_np):
    """(transposed, permuted) counted while a two-layer GCN's gradient is
    traced (hidden 256: two chunks a layer; nothing runs)."""
    from dgraph_tpu.models import GCN

    plan = _shard(plan_np, 0)
    comm = Communicator.init_process_group("single")
    model = GCN(hidden_features=F, out_features=8, comm=comm, num_layers=2,
                dtype=jnp.bfloat16)
    x = jnp.zeros((plan_np.n_src_pad, 32), jnp.float32)
    w = jnp.ones((plan_np.e_pad,), jnp.float32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), x, plan, w))

    def loss(p):
        return (model.apply(p, x, plan, w) ** 2).sum()

    before = _counts()
    jax.make_jaxpr(jax.grad(loss))(params)
    return (_counts() - before).tolist()


def test_counters_read_four_transposed_on_the_tiny_gcn(tpu_interpret):
    assert _gcn_backward_counts(_plan(1, mask_some=False)) == [4, 0]


def test_a_table_slice_over_on_chip_memory_keeps_the_permutation(
        tpu_interpret, monkeypatch):
    """Two gathers from HBM cost more than the permutation they replace:
    the size rule of ``map_vertex_chunks``, on the owner-side slice."""
    plan_np = _plan(1, mask_some=False)
    slice_bytes = plan_np.n_dst_pad * 128 * 2  # [n_owner_pad, 128] bf16
    monkeypatch.setattr(local_ops, "GATHER_TABLE_BYTES", slice_bytes)
    assert _gcn_backward_counts(plan_np) == [4, 0]
    # in two row parts it could be gathered on chip, but four gathers and
    # two selects do not beat the permutation: the route asks for ONE part
    monkeypatch.setattr(local_ops, "GATHER_TABLE_BYTES", slice_bytes - 1)
    assert local_ops.on_chip_row_parts(plan_np.n_dst_pad, 128 * 2) == 2
    assert _gcn_backward_counts(plan_np) == [0, 4]


def test_a_plan_without_the_sorted_route_keeps_the_ops_own_vjps(
        tpu_interpret):
    assert _gcn_backward_counts(
        _plan(1, mask_some=False, sort_route=False)) == [0, 4]


def test_off_the_tpu_the_backward_is_the_ops_own():
    """No steering: the CPU dispatch takes the composed ops, and the op's
    gradients are theirs."""
    plan_np = _plan(1)
    plan, table, bias, w = _engaged_args(plan_np, jnp.float32)
    table = table * jnp.linspace(-1, 1, F)[None, :]

    def loss(fn):
        return lambda t, b, w_: (fn(t, b, w_) ** 2).sum()

    before = _counts()
    got = jax.grad(loss(lambda t, b, w_: collectives.take_scatter_bias_relu(
        t, b, plan, "src", "dst", None, w_)), (0, 1, 2))(table, bias, w)
    assert (_counts() - before).tolist() == [0, 2]
    want = jax.grad(loss(lambda t, b, w_: collectives._take_then_scatter(
        t, b, w_, plan, "src", "dst", None)), (0, 1, 2))(table, bias, w)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_fused_backward_switch_covers_the_route(
        tpu_interpret, monkeypatch):
    """``config.pallas_fused_bwd_enabled()`` off: no kernel pair, so no
    transposed route either (no flag of its own)."""
    monkeypatch.setattr(cfg, "use_pallas_fused_bwd", False)
    assert _gcn_backward_counts(_plan(1, mask_some=False)) == [0, 4]


@pytest.mark.parametrize("n", [1024, 1000])
def test_take_values_is_the_element_gather(n):
    """The edge weights in the halo-sorted order: a row gather of 128-wide
    rows and a lane select where the vector divides into them, the plain
    element gather elsewhere; the same values to the bit."""
    from dgraph_tpu.ops.local import take_values

    rng = np.random.default_rng(n)
    w = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    perm = jnp.asarray(rng.permutation(n).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(take_values)(w, perm)),
        np.asarray(w)[np.asarray(perm)])
    jaxpr = str(jax.make_jaxpr(take_values)(w, perm))
    # eight row gathers, one after the other, or one element gather
    assert jaxpr.count("optimization_barrier") == (7 if n % 128 == 0 else 0)
