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


def _graph(world_size, seed=0):
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, V, E_HALF), rng.integers(0, V, E_HALF)
    edges = np.stack([np.concatenate([src, dst]), np.concatenate([dst, src])])
    return edges, np.sort(rng.integers(0, world_size, V)).astype(np.int32)


def _long_e_pad(plan):
    """Two more whole edge chunks of padding than the plan was built with."""
    be = plan.scatter_block_e
    return (-(-plan.e_pad // be) + 2) * be


def _plan(world_size, seed=0, long_tail=True, **build):
    """A symmetrised random graph's plan, stacked over ranks. Every rank's
    row ends in padded edges, and with ``long_tail`` in more of them than a
    kernel's edge chunk holds, so whole chunks are padding. ``edge_mask``
    is the padding mask and nothing else (``validate_plan``)."""
    edges, part = _graph(world_size, seed)
    build = dict(world_size=world_size, edge_owner="dst",
                 pad_multiple=128) | build
    plan, _ = pl.build_edge_plan(edges, part, **build)
    if long_tail:
        plan, _ = pl.build_edge_plan(
            edges, part, e_pad=_long_e_pad(plan), **build)
    assert (np.asarray(plan.num_edges) < plan.e_pad).all()
    pl.validate_plan(plan)
    return plan


@pytest.mark.parametrize("world_size", [1, 2, 4])
def test_a_plan_that_masks_real_edges_is_refused(world_size):
    """Until PR 37 this file's plans masked a tenth of their REAL edges
    (ids left in place, the route moved to the sentinel) and the fused op
    was held to ``scatter_bias_relu(local_take())`` on them: both added
    ``w·relu(bias[v])`` for such an edge, where ``scatter_sum`` and so
    every unfused model dropped it. The op no longer reads ``edge_mask``
    at all, so the program states the contract it always needed:
    ``edge_mask`` is the padding mask, and a plan that masks a real edge is
    refused, by ``validate_plan`` and where plans enter the program
    (tests/test_plan_validation.py)."""
    plan = _plan(world_size, seed=world_size)
    rng = np.random.default_rng(world_size)
    mask = np.asarray(plan.edge_mask).copy()
    for r in range(world_size):
        real = np.flatnonzero(mask[r] > 0)
        mask[r, rng.choice(real, size=len(real) // 10, replace=False)] = 0
    perm, sids, oids = pl.halo_sort_route(
        np.asarray(plan.src_index), mask,
        plan.n_src_pad + world_size * plan.halo.s_pad,
        np.asarray(plan.dst_index))
    masked = dataclasses.replace(
        plan, edge_mask=mask, halo_sort_perm=perm, halo_sorted_ids=sids,
        halo_sorted_owner_ids=oids)
    with pytest.raises(ValueError, match="edge_mask must be the padding"):
        pl.validate_plan(masked)
    with pytest.raises(ValueError, match="edge_mask must be the padding"):
        pl._require_padding_mask(
            masked.src_index, masked.dst_index, mask, masked.halo_side,
            masked.n_src_pad, masked.n_dst_pad)


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


def _counted(*names):
    c = default_registry.snapshot()["counters"]
    return np.array([c.get(n, 0) for n in names])


def _counts():
    return _counted("gather.bwd_transposed", "gather.bwd_permuted")


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("world_size", [1, 2, 4])
def test_gradients_match_the_composed_ops_and_the_oracle(
        tpu_interpret, world_size, dtype, weighted):
    """Gradients to table, bias and (where it is differentiated) the edge
    weight: bit-equal to the two ops' own VJPs, which the op replaced, and
    close to plain float32 autodiff; padded edges (whole chunks of them),
    and on 2 and 4 ranks a table with halo rows."""
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
        return _counted("gather.row_parts")[0]

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


def _built(kind, tmp_path):
    """The plan of a case of the unmasked forward: one through each builder
    that fills ``edge_mask`` (numpy, the native core, the streamed shards),
    the owner-side padded id ``n_owner_pad`` inside the kernels' last
    vertex block (640 % 256, as gcn_arxiv's 169 344) or past it (768); a
    padded tail longer than two edge chunks in all of them."""
    if kind == "shards":  # the streamed build has no native core
        plan, _ = pl.build_edge_plan_sharded(
            *_graph(1, seed=5), out_dir=str(tmp_path / "plan"), world_size=1,
            edge_owner="dst", pad_multiple=128,
            e_pad=_long_e_pad(_plan(1, seed=5, long_tail=False)))
        pl.validate_plan(plan)
    elif kind == "no_sort_route":  # the take's VJP: a segment-sum by idx
        plan = _plan(1, seed=5, sort_route=False)
    elif kind == "unsorted_edges":  # the fallback that ends in scatter_sum
        plan = _plan(1, seed=5, sort_edges=False)
    else:
        plan = _plan(
            1, seed=5, use_native=kind == "native",
            pad_multiple=256 if kind == "past_last_block" else 128)
    in_last_block = plan.n_dst_pad % plan.scatter_block_n != 0
    assert in_last_block == (kind != "past_last_block")
    assert plan.e_pad - int(plan.num_edges[0]) > 2 * plan.scatter_block_e
    return plan


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("kind,route", [
    (kind, route)
    for kind in ("in_last_block", "past_last_block", "native", "shards",
                 "row_parts")
    for route in ("transposed", "permuted", "off_tpu")
] + [
    # no transposed route without the sorted route or the sorted owner ids:
    # with the kernels ("permuted") and without
    (kind, route)
    for kind in ("no_sort_route", "unsorted_edges")
    for route in ("permuted", "off_tpu")
])
def test_the_unmasked_forward_is_the_masked_composition_to_the_bit(
        request, monkeypatch, tmp_path, kind, route, weighted):
    """ISSUE 37: the fused layer takes its per-edge rows WITHOUT
    ``local_take``'s edge-mask multiply, because its aggregation drops a
    padded edge by its id. Held to the masked composition
    ``scatter_bias_relu(local_take(table), bias)`` bit for bit: the value
    and the gradients to table, bias and the edge weight's live slots, on
    the transposed route, on the permuted one with the kernels, and off the
    TPU. Row 0 of the table, which every padded slot gathers, is large and
    positive (it would pass the ReLU) and the padded tail is longer than
    two edge chunks: a leak would be seen."""
    if route != "off_tpu":
        request.getfixturevalue("tpu_interpret")
    if route == "permuted":
        monkeypatch.setattr(
            collectives, "_transposed_bwd_applies", lambda *a: False)
    plan_np = _built(kind, tmp_path)
    plan = _shard(plan_np, 0)
    n_rows = plan_np.n_src_pad + plan_np.halo.s_pad
    if kind == "row_parts":
        # an owner-side slice fits whole (the transposed route's rule), the
        # stream's, which has the halo rows too, in two parts
        assert plan_np.n_dst_pad < n_rows <= 2 * plan_np.n_dst_pad
        monkeypatch.setattr(
            local_ops, "GATHER_TABLE_BYTES", plan_np.n_dst_pad * 128 * 2)
    rng = np.random.default_rng(13)
    table = rng.standard_normal((n_rows, F))
    table[0] = 100.0
    table = jnp.asarray(table, jnp.bfloat16)
    bias = jnp.asarray(
        rng.standard_normal((plan_np.n_dst_pad, F)), jnp.bfloat16)
    tgt = jnp.asarray(
        rng.standard_normal((plan_np.n_dst_pad, F)), jnp.float32)
    w = jnp.asarray(
        rng.uniform(0.5, 2.0, plan_np.e_pad), jnp.float32
    ) if weighted else None
    argnums = (0, 1, 2) if weighted else (0, 1)

    def unmasked(t, b, w_):
        return collectives.take_scatter_bias_relu(
            t, b, plan, "src", "dst", None, w_)

    def masked(t, b, w_):
        return collectives.map_vertex_chunks(
            lambda tc, bc: collectives.scatter_bias_relu(
                collectives.local_take(tc, plan, "src"), bc, plan, "dst",
                None, edge_weight=w_),
            (t, b))

    def value_and_grads(fn):
        def loss(t, b, w_):
            out = fn(t, b, w_)
            return (out.astype(jnp.float32) * tgt).sum(), out

        return jax.jit(jax.value_and_grad(loss, argnums, has_aux=True))(
            table, bias, w)

    before = _counts()
    parts = _counted("gather.row_parts")
    (_, got_out), got = value_and_grads(unmasked)
    assert (_counts() - before).tolist() == (
        [2, 0] if route == "transposed" else [0, 2])
    assert _counted("gather.row_parts") - parts == (
        4 if kind == "row_parts" else 0)
    (_, want_out), want = value_and_grads(masked)
    np.testing.assert_array_equal(
        np.asarray(got_out, np.float32), np.asarray(want_out, np.float32))
    assert np.abs(np.asarray(got_out, np.float32)).sum() > 0
    live = np.asarray(plan.edge_mask) > 0
    # what the kernels were handed in the padded slots: row 0, not zeros
    rows = collectives._local_take_unmasked(table[:, :128], plan, "src")
    assert (np.asarray(rows, np.float32)[~live] == 100.0).all()
    for name, a, b in zip(("d_table", "d_bias", "d_w"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if name == "d_w":
            assert not a[~live].any()  # a padded slot's weight: no gradient
            a, b = a[live], b[live]
        assert np.abs(a).sum() > 0, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    # row 0 is the row every padded slot gathered: where the take's VJP is
    # a plain segment-sum by the halo-side id (no sorted route, whose
    # sentinel drops a padded slot), only gd == 0 on those slots keeps
    # their cotangent out of it
    np.testing.assert_array_equal(
        np.asarray(got[0][0], np.float32), np.asarray(want[0][0], np.float32))


def _engaged_args(plan_np, dtype=jnp.bfloat16):
    plan = _shard(plan_np, 0)
    n_rows = plan_np.n_src_pad + plan_np.world_size * plan_np.halo.s_pad
    table = jnp.ones((n_rows, F), dtype)
    bias = jnp.ones((plan_np.n_dst_pad, F), dtype)
    w = jnp.ones((plan_np.e_pad,), jnp.float32)
    return plan, table, bias, w


def test_the_forward_is_the_two_calls_it_replaced(tpu_interpret):
    """Undifferentiated (the eval step, the serve engine) the op is
    ``scatter_bias_relu`` of the chunk's rows, taken as ``local_take``
    takes them but for its edge-mask multiply (ISSUE 37): the custom VJP's
    inner jaxpr is that composition's, equation for equation, and its
    value the masked composition's to the bit."""
    plan, table, bias, w = _engaged_args(_plan(1))

    def one_op(t, b, w_, p):
        return collectives.take_scatter_bias_relu(
            t, b, p, "src", "dst", None, w_)

    def composed(take):
        return lambda t, b, w_, p: collectives.map_vertex_chunks(
            lambda tc, bc: collectives.scatter_bias_relu(
                take(tc, p, "src"), bc, p, "dst", None, edge_weight=w_),
            (t, b))

    two_ops = composed(collectives._local_take_unmasked)
    masked = jax.make_jaxpr(composed(collectives.local_take))(
        table, bias, w, plan)

    outer = jax.make_jaxpr(one_op)(table, bias, w, plan)
    (call,) = [e for e in outer.jaxpr.eqns
               if e.primitive.name.startswith("custom_vjp_call")]
    assert len(outer.jaxpr.eqns) == 1
    inner = call.params["call_jaxpr"]
    want = jax.make_jaxpr(two_ops)(table, bias, w, plan)
    assert str(inner.jaxpr) == str(want.jaxpr)
    # one multiply a chunk fewer than the masked composition, nothing else
    assert str(masked.jaxpr).count(" mul ") - str(want.jaxpr).count(
        " mul ") == 2
    np.testing.assert_array_equal(
        np.asarray(jax.jit(one_op)(table, bias, w, plan), np.float32),
        np.asarray(jax.jit(composed(collectives.local_take))(
            table, bias, w, plan), np.float32))


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
    assert _gcn_backward_counts(_plan(1, long_tail=False)) == [4, 0]


def test_a_table_slice_over_on_chip_memory_keeps_the_permutation(
        tpu_interpret, monkeypatch):
    """Two gathers from HBM cost more than the permutation they replace:
    the size rule of ``map_vertex_chunks``, on the owner-side slice."""
    plan_np = _plan(1, long_tail=False)
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
        _plan(1, long_tail=False, sort_route=False)) == [0, 4]


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
    assert _gcn_backward_counts(_plan(1, long_tail=False)) == [0, 4]


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
