"""Pallas sorted-segment-sum vs the jnp oracle (interpret mode on CPU —
the reference's CUDA-kernel-vs-dense-loop test pattern,
``tests/test_local_kernels.py:26-154``)."""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dgraph_tpu.ops.pallas_segment import max_chunks_hint, sorted_segment_sum


@pytest.mark.parametrize("E,N,F", [(1000, 300, 16), (4096, 512, 128), (37, 8, 4)])
def test_matches_oracle(rng, E, N, F):
    ids = np.sort(rng.integers(0, N, E)).astype(np.int32)
    data = rng.normal(size=(E, F)).astype(np.float32)
    mc = max_chunks_hint(ids, N, block_e=256, block_n=256)
    got = sorted_segment_sum(
        jnp.asarray(data),
        jnp.asarray(ids),
        N,
        max_chunks_per_block=mc,
        interpret=True,
    )
    expected = np.zeros((N, F), np.float32)
    np.add.at(expected, ids, data)
    np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-5, atol=1e-5)


def test_masked_rows_dropped(rng):
    """Out-of-range ids (the plan's padded-edge convention) contribute 0."""
    E, N, F = 512, 100, 8
    ids = np.sort(rng.integers(0, N, E)).astype(np.int32)
    ids[-50:] = N + 1  # padded edges
    data = rng.normal(size=(E, F)).astype(np.float32)
    got = sorted_segment_sum(
        jnp.asarray(data), jnp.asarray(ids), N,
        max_chunks_per_block=max_chunks_hint(ids, N), interpret=True,
    )
    expected = np.zeros((N, F), np.float32)
    np.add.at(expected, ids[:-50], data[:-50])
    np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-5, atol=1e-5)


def test_skewed_segments(rng):
    """Hub vertex with most of the edges (power-law worst case)."""
    E, N, F = 2000, 64, 8
    ids = np.concatenate([np.zeros(1500, np.int32), np.sort(rng.integers(1, N, 500))])
    ids = np.sort(ids).astype(np.int32)
    data = rng.normal(size=(E, F)).astype(np.float32)
    got = sorted_segment_sum(
        jnp.asarray(data), jnp.asarray(ids), N,
        max_chunks_per_block=max_chunks_hint(ids, N), interpret=True,
    )
    expected = np.zeros((N, F), np.float32)
    np.add.at(expected, ids, data)
    np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-4, atol=1e-4)


def test_grad_is_gather_transpose(rng):
    """VJP == g[ids] with OOB ids dropped (gather-bwd = scatter-sum duality,
    the reference pins the same pair in ``tests/test_NCCLCommPlan.py``)."""
    import jax

    E, N, F = 600, 100, 8
    ids = np.sort(rng.integers(0, N, E)).astype(np.int32)
    ids[-40:] = N + 1  # padded edges
    data = rng.normal(size=(E, F)).astype(np.float32)
    g_out = rng.normal(size=(N, F)).astype(np.float32)
    mc = max_chunks_hint(ids, N)

    def loss(d):
        out = sorted_segment_sum(
            d, jnp.asarray(ids), N, max_chunks_per_block=mc, interpret=True
        )
        return (out * g_out).sum()

    got = jax.grad(loss)(jnp.asarray(data))
    expected = np.zeros_like(data)
    expected[:-40] = g_out[ids[:-40]]
    np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-5, atol=1e-5)


def test_grad_relu_input_op(rng):
    import jax

    E, N, F = 300, 50, 4
    ids = np.sort(rng.integers(0, N, E)).astype(np.int32)
    data = rng.normal(size=(E, F)).astype(np.float32)
    g_out = rng.normal(size=(N, F)).astype(np.float32)
    mc = max_chunks_hint(ids, N)

    def loss_pallas(d):
        return (
            sorted_segment_sum(
                d, jnp.asarray(ids), N, max_chunks_per_block=mc,
                interpret=True, input_op="relu",
            ) * g_out
        ).sum()

    def loss_ref(d):
        import jax.nn

        out = jax.ops.segment_sum(jax.nn.relu(d), jnp.asarray(ids), num_segments=N)
        return (out * g_out).sum()

    import jax

    got = jax.grad(loss_pallas)(jnp.asarray(data))
    want = jax.grad(loss_ref)(jnp.asarray(data))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_fused_relu_input_op(rng):
    """input_op='relu' == relu-then-sum (Fused_ReLU_Scatter_Kernel parity)."""
    E, N, F = 777, 128, 16
    ids = np.sort(rng.integers(0, N, E)).astype(np.int32)
    data = rng.normal(size=(E, F)).astype(np.float32)
    got = sorted_segment_sum(
        jnp.asarray(data), jnp.asarray(ids), N,
        max_chunks_per_block=max_chunks_hint(ids, N), interpret=True,
        input_op="relu",
    )
    expected = np.zeros((N, F), np.float32)
    np.add.at(expected, ids, np.maximum(data, 0.0))
    np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-5, atol=1e-5)


@contextlib.contextmanager
def _bwd_branch_counts():
    """Yields a function giving how many traced backwards of the fused op
    took (the kernel pair, the composed ops) since entry: the program's
    ``segsum.bwd_fused`` / ``segsum.bwd_composed`` counters."""
    from dgraph_tpu.obs.metrics import default_registry

    def read():
        c = default_registry.snapshot()["counters"]
        return (c.get("segsum.bwd_fused", 0), c.get("segsum.bwd_composed", 0))

    before = read()
    yield lambda: tuple(int(a - b) for a, b in zip(read(), before))


class TestFusedBiasRelu:
    """sorted_segment_sum_bias_relu (the reference's fused scatter family,
    local_data_kernels.cuh:34-116): interpret-mode kernel vs numpy oracle,
    and the collectives.scatter_bias_relu fallback vs composed ops."""

    def _case(self, seed=0, E=2048, N=512, F=32):
        rng = np.random.default_rng(seed)
        ids = np.sort(rng.integers(0, N, E)).astype(np.int32)
        ids[-32:] = N + 1  # padded-edge tail (OOB ids must drop)
        data = rng.standard_normal((E, F)).astype(np.float32)
        bias = rng.standard_normal((N, F)).astype(np.float32)
        w = rng.uniform(0.5, 2.0, E).astype(np.float32)
        return ids, data, bias, w

    def _oracle(self, ids, data, bias, w, N):
        out = np.zeros((N, bias.shape[1]), np.float32)
        for e in range(len(ids)):
            if ids[e] >= N:
                continue
            m = np.maximum(data[e] + bias[ids[e]], 0)
            out[ids[e]] += w[e] * m if w is not None else m
        return out

    @pytest.mark.parametrize("use_w", [False, True])
    def test_kernel_interpret_matches_oracle(self, use_w):
        from dgraph_tpu.ops.pallas_segment import (
            max_chunks_hint,
            sorted_segment_sum_bias_relu,
        )

        ids, data, bias, w = self._case()
        N = bias.shape[0]
        got = np.asarray(
            sorted_segment_sum_bias_relu(
                jnp.asarray(data), jnp.asarray(ids), jnp.asarray(bias), N,
                edge_weight=jnp.asarray(w) if use_w else None,
                max_chunks_per_block=max_chunks_hint(ids, N),
                interpret=True,
            )
        )
        want = self._oracle(ids, data, bias, w if use_w else None, N)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_kernel_gradients_match_composite(self):
        from dgraph_tpu.ops.pallas_segment import (
            max_chunks_hint,
            sorted_segment_sum_bias_relu,
        )

        ids, data, bias, w = self._case(1, E=1024, N=256, F=16)
        N = bias.shape[0]
        tgt = jnp.asarray(
            np.random.default_rng(2).standard_normal((N, 16)).astype(np.float32)
        )
        mc = max_chunks_hint(ids, N)
        safe = np.clip(ids, 0, N - 1).astype(np.int32)
        valid = (ids < N).astype(np.float32)[:, None]

        def fused(d, b, wgt):
            out = sorted_segment_sum_bias_relu(
                d, jnp.asarray(ids), b, N, edge_weight=wgt,
                max_chunks_per_block=mc, interpret=True,
            )
            return (out * tgt).sum()

        def composed(d, b, wgt):
            rows = jnp.take(b, jnp.asarray(safe), axis=0)
            m = jnp.maximum(d + rows, 0) * wgt[:, None] * jnp.asarray(valid)
            out = jax.ops.segment_sum(m, jnp.asarray(safe), num_segments=N)
            return (out * tgt).sum()

        args = (jnp.asarray(data), jnp.asarray(bias), jnp.asarray(w))
        ga = jax.grad(fused, argnums=(0, 1, 2))(*args)
        gb = jax.grad(composed, argnums=(0, 1, 2))(*args)
        for a, b, name in zip(ga, gb, ["d_data", "d_bias", "d_w"]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
                err_msg=name,
            )

    def _zero_some_weights(self, w, seed):
        """A weight of 0 on a tenth of the edges: gd must read 0 there
        while d_w does not."""
        w = w.copy()
        w[np.random.default_rng(seed).random(w.shape[0]) < 0.1] = 0.0
        return w

    @pytest.mark.parametrize("use_w", [False, True])
    @pytest.mark.parametrize("be,bn", [(128, 128), (256, 64)])
    def test_kernel_bwd_pair_matches_composite(self, be, bn, use_w):
        """The KERNEL backward (chunk-major gd [+ d_w] kernel + the
        epilogue='act' d_bias reduction — engaged when gather_mv > 0,
        with or without an edge weight) must produce the same gradients
        as plain autodiff through the composed ops, masked tail edges
        (ids >= N) and zero weights included. This is the path the bf16
        GCN epoch runs on TPU."""
        from dgraph_tpu.ops.pallas_segment import (
            max_chunks_hint,
            max_vblocks_hint,
            sorted_segment_sum_bias_relu,
        )

        ids, data, bias, w = self._case(4, E=1024, N=256, F=16)
        w = self._zero_some_weights(w, 12)
        N = bias.shape[0]
        tgt = jnp.asarray(
            np.random.default_rng(5).standard_normal((N, 16)).astype(np.float32)
        )
        mc = max_chunks_hint(ids, N, block_e=be, block_n=bn)
        mv = max_vblocks_hint(ids, N, block_e=be, block_n=bn)
        assert mv > 0
        safe = np.clip(ids, 0, N - 1).astype(np.int32)
        valid = (ids < N).astype(np.float32)[:, None]

        def fused(d, b, wgt):
            out = sorted_segment_sum_bias_relu(
                d, jnp.asarray(ids), b, N,
                edge_weight=wgt if use_w else None,
                max_chunks_per_block=mc, block_e=be, block_n=bn,
                gather_mv=mv, interpret=True,
            )
            return (out * tgt).sum()

        def composed(d, b, wgt):
            rows = jnp.take(b, jnp.asarray(safe), axis=0)
            m = jnp.maximum(d + rows, 0) * jnp.asarray(valid)
            if use_w:
                m = m * wgt[:, None]
            out = jax.ops.segment_sum(m, jnp.asarray(safe), num_segments=N)
            return (out * tgt).sum()

        args = (jnp.asarray(data), jnp.asarray(bias), jnp.asarray(w))
        with _bwd_branch_counts() as took:
            ga = jax.grad(fused, argnums=(0, 1, 2))(*args)
        assert took() == (1, 0), "the kernel pair did not engage"
        gb = jax.grad(composed, argnums=(0, 1, 2))(*args)
        for a, b, name in zip(ga, gb, ["d_data", "d_bias", "d_w"]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
                err_msg=name,
            )
        if use_w:
            assert np.abs(np.asarray(ga[2])[:-32]).max() > 0
            np.testing.assert_array_equal(np.asarray(ga[0])[w == 0], 0.0)
            np.testing.assert_array_equal(np.asarray(ga[2])[-32:], 0.0)

    @pytest.mark.parametrize("use_w", [False, True])
    def test_kernel_bwd_pair_bf16_matches_composed_bwd(self, use_w):
        """bf16 KERNEL backward vs the bf16 COMPOSED backward (gather_mv=0
        disables the kernel pair): both decide the ReLU mask from the same
        bf16-rounded operands in f32, so they must agree to accumulation
        rounding — an f32 reference would differ by whole elements at
        ReLU-boundary flips, which is inherent to bf16, not a kernel bug.
        d_w (f32, a sum over F of bf16-rounded products on the composed
        side) is held to the same rounding."""
        from dgraph_tpu.ops.pallas_segment import (
            max_chunks_hint,
            max_vblocks_hint,
            sorted_segment_sum_bias_relu,
        )

        ids, data, bias, w = self._case(6, E=1024, N=256, F=16)
        w = self._zero_some_weights(w, 13)
        N = bias.shape[0]
        mc = max_chunks_hint(ids, N)
        mv = max_vblocks_hint(ids, N)
        tgt = jnp.asarray(
            np.random.default_rng(7).standard_normal((N, 16)).astype(np.float32)
        )

        def loss(d, b, wgt, gmv):
            out = sorted_segment_sum_bias_relu(
                jnp.asarray(d, jnp.bfloat16), jnp.asarray(ids),
                jnp.asarray(b, jnp.bfloat16), N,
                edge_weight=wgt if use_w else None,
                max_chunks_per_block=mc, gather_mv=gmv, interpret=True,
            )
            return (out.astype(jnp.float32) * tgt).sum()

        args = (jnp.asarray(data), jnp.asarray(bias), jnp.asarray(w))
        gk = jax.grad(lambda d, b, x: loss(d, b, x, mv),
                      argnums=(0, 1, 2))(*args)
        gc = jax.grad(lambda d, b, x: loss(d, b, x, 0),
                      argnums=(0, 1, 2))(*args)
        for a, b, name in zip(gk, gc, ["d_data", "d_bias", "d_w"]):
            # d_w sums F products: rounding scales with the row's norm
            tol = 0.02 * (4 if name == "d_w" else 1)
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=tol, atol=tol, err_msg=name,
            )

    @pytest.mark.parametrize("use_w", [False, True])
    def test_fused_bwd_kill_switch_routes_to_composed(self, use_w):
        """With use_pallas_fused_bwd=False the VJP must bypass the kernel
        pair even when gather_mv>0 (ADVICE r4: the pair needs its own
        disable for Mosaic-regression debugging), weighted or not, and
        grads must match the enabled path. The flag is read at trace time,
        so flipping it here exercises the branch without env vars."""
        from dgraph_tpu import config
        from dgraph_tpu.ops.pallas_segment import (
            max_chunks_hint,
            max_vblocks_hint,
            sorted_segment_sum_bias_relu,
        )

        ids, data, bias, w = self._case(9, E=512, N=128, F=8)
        N = bias.shape[0]
        mc = max_chunks_hint(ids, N)
        mv = max_vblocks_hint(ids, N)
        tgt = jnp.asarray(
            np.random.default_rng(11).standard_normal((N, 8)).astype(np.float32)
        )

        def loss(d, b, wgt):
            out = sorted_segment_sum_bias_relu(
                d, jnp.asarray(ids), b, N,
                edge_weight=wgt if use_w else None,
                max_chunks_per_block=mc, gather_mv=mv, interpret=True,
            )
            return (out.astype(jnp.float32) * tgt).sum()

        # the pair and the composed bwd agree numerically by design, so a
        # silently-ignored flag would still pass an allclose — count the
        # kernel-pair factory's invocations to prove the ROUTING flips,
        # and read the program's own count of the branch taken beside it
        from dgraph_tpu.ops import pallas_segment as ps

        real_make = ps._make_fused_bwd
        calls = []

        def counting_make(*a, **kw):
            calls.append(1)
            return real_make(*a, **kw)

        args = (jnp.asarray(data), jnp.asarray(bias), jnp.asarray(w))
        old_flag = config.use_pallas_fused_bwd
        ps._make_fused_bwd = counting_make
        try:
            with _bwd_branch_counts() as took:
                g_on = jax.grad(loss, argnums=(0, 1, 2))(*args)
            assert calls, "kernel pair did not engage with the flag on"
            assert took() == (1, 0)
            calls.clear()
            config.set_flags(use_pallas_fused_bwd=False)
            with _bwd_branch_counts() as took:
                g_off = jax.grad(loss, argnums=(0, 1, 2))(*args)
            assert not calls, "kill switch ignored: kernel pair still ran"
            assert took() == (0, 1)
        finally:
            ps._make_fused_bwd = real_make
            config.set_flags(use_pallas_fused_bwd=old_flag)
        for a, b, name in zip(g_on, g_off, ["d_data", "d_bias", "d_w"]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
                err_msg=name,
            )

    def test_collectives_fallback_equals_composed(self):
        """Off-TPU, scatter_bias_relu must equal gather+relu+scatter_sum."""
        from dgraph_tpu.comm import collectives as coll
        from dgraph_tpu.plan import build_edge_plan

        rng = np.random.default_rng(3)
        V, E, W = 64, 300, 1
        edges = np.stack([rng.integers(0, V, E), rng.integers(0, V, E)])
        plan, _ = build_edge_plan(
            edges, np.zeros(V, np.int32), world_size=1, edge_owner="dst"
        )
        p0 = jax.tree.map(lambda l: jnp.asarray(np.asarray(l)[0]), plan)
        ed = jnp.asarray(rng.standard_normal((plan.e_pad, 8)), jnp.float32)
        bias = jnp.asarray(rng.standard_normal((plan.n_dst_pad, 8)), jnp.float32)
        w = jnp.asarray(rng.uniform(0.5, 2, plan.e_pad), jnp.float32)

        got = coll.scatter_bias_relu(ed, bias, p0, "dst", None, w)
        m = jax.nn.relu(ed + coll.gather(bias, p0, "dst", None)) * w[:, None]
        m = m * p0.edge_mask[:, None]
        want = coll.scatter_sum(m, p0, "dst", None)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )


class TestSortedRowGather:
    """The transpose kernel: x[ids] for sorted ids as blocked one-hot MXU
    matmuls (interpret mode on CPU; the chip self-check gates real Mosaic)."""

    def _case(self, seed=0, N=2000, E=8192, F=128, masked_tail=100):
        rng = np.random.default_rng(seed)
        ids = np.sort(rng.integers(0, N, E)).astype(np.int32)
        if masked_tail:
            ids[-masked_tail:] = N + 1
        x = rng.standard_normal((N, F)).astype(np.float32)
        want = np.where((ids < N)[:, None], x[np.clip(ids, 0, N - 1)], 0.0)
        return x, ids, want

    def test_matches_numpy_with_masked_tail(self):
        from dgraph_tpu.ops.pallas_segment import (
            max_chunks_hint,
            max_vblocks_hint,
            sorted_row_gather,
        )

        x, ids, want = self._case()
        mv = max_vblocks_hint(ids, x.shape[0])
        mc = max_chunks_hint(ids, x.shape[0])
        got = np.asarray(sorted_row_gather(
            jnp.asarray(x), jnp.asarray(ids), max_vblocks=mv, scatter_mc=mc,
            interpret=True, precision="highest",
        ))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def test_odd_sizes_and_tiles(self):
        from dgraph_tpu.ops.pallas_segment import (
            max_chunks_hint,
            max_vblocks_hint,
            sorted_row_gather,
        )

        # non-multiple N and E force the padding paths in the schedule
        x, ids, want = self._case(seed=3, N=777, E=3001, F=64, masked_tail=7)
        for be, bn in [(256, 128), (1024, 512)]:
            mv = max_vblocks_hint(ids, x.shape[0], block_e=be, block_n=bn)
            mc = max_chunks_hint(ids, x.shape[0], block_e=be, block_n=bn)
            got = np.asarray(sorted_row_gather(
                jnp.asarray(x), jnp.asarray(ids), max_vblocks=mv,
                block_e=be, block_n=bn, scatter_mc=mc, interpret=True,
                precision="highest",
            ))
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6,
                                       err_msg=f"tiles ({be},{bn})")

    def test_vjp_is_sorted_segment_sum(self):
        from dgraph_tpu.ops.pallas_segment import (
            max_chunks_hint,
            max_vblocks_hint,
            sorted_row_gather,
        )

        x, ids, _ = self._case(seed=5)
        N = x.shape[0]
        mv = max_vblocks_hint(ids, N)
        mc = max_chunks_hint(ids, N)
        rng = np.random.default_rng(6)
        g = rng.standard_normal((ids.shape[0], x.shape[1])).astype(np.float32)

        def loss(xx):
            out = sorted_row_gather(
                xx, jnp.asarray(ids), max_vblocks=mv, scatter_mc=mc,
                interpret=True, precision="highest",
            )
            return (out * jnp.asarray(g)).sum()

        dx = np.asarray(jax.grad(loss)(jnp.asarray(x)))
        want = np.zeros_like(x)
        np.add.at(want, ids[ids < N], g[ids < N])
        np.testing.assert_allclose(dx, want, rtol=1e-5, atol=1e-5)

    def test_take_rows_routes_to_kernel_when_pinned(self):
        """config.use_pallas_gather=True + sorted hints must swap the
        forward to the kernel (structural: pallas_call in the jaxpr);
        auto must NOT (explicit-opt-in contract)."""
        from dgraph_tpu import config as cfg
        from dgraph_tpu.ops import local as L

        x = jnp.zeros((512, 32), jnp.float32)
        ids = jnp.asarray(np.sort(np.random.default_rng(0).integers(
            0, 512, 1024)).astype(np.int32))

        def has_pallas(flag):
            old = cfg.use_pallas_gather
            try:
                cfg.set_flags(use_pallas_gather=flag)
                jx = jax.make_jaxpr(lambda a: L.take_rows(
                    a, ids, indices_are_sorted=True,
                    pallas_hints=(512, 256, 2), gather_mv=2,
                ))(x)
                return "pallas_call" in str(jx)
            finally:
                cfg.set_flags(use_pallas_gather=old)

        # off-TPU take_rows also gates on backend; emulate the TPU branch
        # by checking _make_take_rows directly
        from dgraph_tpu.ops.local import _make_take_rows

        fn = _make_take_rows(512, True, 128, True, 512, 256, 2, 2)
        jx = jax.make_jaxpr(lambda a: fn(a, ids))(x)
        assert "pallas_call" in str(jx), "mv>0 must route to the kernel"
        assert has_pallas(None) is False  # auto = OFF on CPU regardless
