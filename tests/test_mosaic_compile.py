"""Compile-only: kernels of the main path at a benchmark cell's real shape,
lowered by Mosaic for a DESCRIBED ``v5e`` (no chip attached), so a change
that breaks a kernel's TPU lowering fails here and not on the chip.
Interpret mode checks none of it (tiling, minor-dim inserts, VMEM).

Nothing runs: a compile that passes is not a chip run and gives no time.
The topology is described inside a fixture, never at import (one process at
a time may load libtpu; the worker that is given this file is the one that
does), and every such compile lives in this one file."""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# gcn_arxiv.w1 (benchmark/configs/gcn_arxiv.json): e_pad, n_owner_pad, one
# 128-column feature chunk in bf16, the plan's blocks (plan.SCATTER_BLOCK_*)
# and a gather_mv of 2 (1024 sorted edges span at most two 256-row blocks)
E, N, F, BE, BN, MV = 2_332_672, 169_344, 128, 1024, 256, 2


@pytest.mark.parametrize("has_weight", [False, True])
def test_fused_bwd_gd_kernel_compiles_at_arxiv_shape(one_chip, has_weight):
    """The fused scatter's gd kernel (with d_w where weighted: the lane
    reduction and the 32-bit minor-dim insert of the weight are what
    Mosaic could refuse)."""
    from dgraph_tpu.ops.pallas_segment import _make_fused_bwd

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    fn = _make_fused_bwd(N, MV, BE, BN, False, "default", has_weight)
    args = [shape((E, F), jnp.bfloat16), shape((N, F), jnp.bfloat16),
            shape((N, F), jnp.bfloat16), shape((E,), jnp.int32)]
    if has_weight:
        args.append(shape((E,), jnp.float32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    gd, d_w = jax.eval_shape(fn, *args)
    assert (gd.shape, gd.dtype) == ((E, F), jnp.bfloat16)
    if has_weight:
        assert (d_w.shape, d_w.dtype) == ((E,), jnp.float32)
    else:
        assert d_w is None


@pytest.mark.parametrize("has_weight", [False, True])
def test_transposed_grad_kernel_compiles_at_arxiv_shape(one_chip, has_weight):
    """The fused layer's gradient to its streamed table (ISSUE 33): the
    forward kernel under ``epilogue="grad"``, two streamed ``[E, 128]``
    operands and the table's block resident, at the halo-sorted route's
    hint (``halo_sort_mc`` reads 3 in ``gcn_arxiv.w1``)."""
    from dgraph_tpu.ops.pallas_segment import sorted_segment_grad_bias_relu

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    def fn(rows, g_rows, ids, table, *w):
        return sorted_segment_grad_bias_relu(
            rows, g_rows, ids, table, N, edge_weight=w[0] if w else None,
            max_chunks_per_block=3, block_e=BE, block_n=BN)

    args = [shape((E, F), jnp.bfloat16), shape((E, F), jnp.bfloat16),
            shape((E,), jnp.int32), shape((N, F), jnp.bfloat16)]
    if has_weight:
        args.append(shape((E,), jnp.float32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = jax.eval_shape(fn, *args)
    assert (out.shape, out.dtype) == ((N, F), jnp.bfloat16)


# phi4_mini_flash.seq8k (benchmark/configs/phi4_mini_flash.json): seq_len,
# d_inner, d_state; u in bf16, the rest float32. Its scan_chunk is 128 (a
# channel block of 1024 lanes); 512 is the longest power of two that takes
# the kernels there (256 lanes), 1024 keeps the lax form.
SCAN_T, SCAN_C, SCAN_N = 8192, 5120, 16


@pytest.mark.parametrize("bt,bc", [(128, 1024), (512, 256)])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_selective_scan_kernels_compile_at_phi4_shape(one_chip, direction, bt,
                                                      bc):
    """The selective scan's kernels (ISSUE 41) at the cell's shape and the
    channel block derived there: the state scratch over the time blocks, the
    columns spread over the lanes, the backward's states and decays (16.8 MB
    at 128 steps) in VMEM under the limit derived from them."""
    from dgraph_tpu.ops import pallas_scan as ps

    def shape(*s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    T, C, N = SCAN_T, SCAN_C, SCAN_N
    u = shape(T, C, dtype=jnp.bfloat16)
    assert ps.applies(u, shape(C, N), bt) and not ps.applies(
        u, shape(C, N), 1024)
    assert ps.channel_block(C, N, bt, 2) == bc
    args = [u, shape(T, C), shape(N, C), shape(T, N), shape(T, N), shape(C)]
    if direction == "forward":
        fn = lambda *a: ps.fused_forward(*a, bt)
        args.append(shape(N, C))
        want = [((T, C), jnp.float32), ((T // bt, N, C), jnp.float32),
                ((N, C), jnp.float32)]
    else:
        fn = lambda *a: ps.fused_backward(*a, bt)
        args += [shape(T // bt, N, C), shape(T, C), shape(N, C)]
        want = [((T, C), jnp.bfloat16), ((T, C), jnp.float32),
                ((N, C), jnp.float32), ((T, N), jnp.float32),
                ((T, N), jnp.float32), ((C,), jnp.float32),
                ((N, C), jnp.float32)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert [(o.shape, o.dtype) for o in jax.eval_shape(fn, *args)] == want


# nemotron3_nano_30b_a3b.seq8k (benchmark/configs/nemotron3_nano_30b_a3b.json):
# T 8192, 64 heads of 64 in 8 groups of 128 states; the published chunk of
# 128 steps, and 512, the longest power of two whose blocks fit the budget.
SSD_T, SSD_H, SSD_P, SSD_G, SSD_N = 8192, 64, 64, 8, 128


@pytest.mark.parametrize("L", [128, 512])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_chunked_recurrence_kernels_compile_at_nemotron_shape(one_chip,
                                                              direction, L):
    """The Mamba-2 recurrence's kernels (ISSUE 44) at the cell's shape: the
    padded transpose that turns a head's row into a column, the products
    with a transposed left operand, a 64-lane head inside a 128-lane tile,
    the state scratch over the chunks, under the limit derived from
    ``vmem_bytes`` (which Mosaic's own count must stay within)."""
    from dgraph_tpu.ops import pallas_ssd as ps

    def shape(*s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    T, H, P, G, N = SSD_T, SSD_H, SSD_P, SSD_G, SSD_N
    bf16 = jnp.bfloat16
    x3, B3 = shape(T, H, P, dtype=bf16), shape(T, G, N, dtype=bf16)
    assert ps.applies(x3, B3, L) and not ps.applies(x3, B3, 1024)
    rows = shape(G, H // G, T)
    args = [shape(T, H * P, dtype=bf16), rows, rows,
            shape(T, G * N, dtype=bf16), shape(T, G * N, dtype=bf16),
            shape(1, H * P)]
    if direction == "forward":
        fn = lambda *a: ps.fused_forward(*a, L, P)
        args.append(shape(N, H * P))
        want = [((T, H * P), jnp.float32), ((T // L, N, H * P), jnp.float32),
                ((N, H * P), jnp.float32)]
    else:
        fn = lambda *a: ps.fused_backward(*a, L, P)
        args += [shape(T // L, N, H * P), shape(T, H * P), shape(N, H * P)]
        want = [((T, H * P), bf16), ((G, H // G, T), jnp.float32),
                ((G, H // G, T), jnp.float32), ((T, G * N), bf16),
                ((T, G * N), bf16), ((8, H * P), jnp.float32),
                ((N, H * P), jnp.float32)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert [(o.shape, o.dtype) for o in jax.eval_shape(fn, *args)] == want


# kanana2_30b_a3b.seq16k (benchmark/configs/kanana2_30b_a3b.json): 16 384
# causal rows, 32 query heads on 32 key heads of 192 and value heads of 128.
MLA_T, MLA_H, MLA_QK, MLA_V = 16384, 32, 192, 128


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_splash_kernels_compile_at_a_qk_head_of_192_on_values_of_128(
        one_chip, direction):
    """The splash kernels (ISSUE 49) at the latent layers' shape: a q.k head
    of a lane tile and a half, AS IT IS (no zero columns), on a value head of
    another size, at ``SPLASH_KV_COMPUTE`` columns a softmax step."""
    from dgraph_tpu.parallel import sequence as seq

    def shape(d):
        return jax.ShapeDtypeStruct((MLA_T, MLA_H, d), jnp.bfloat16,
                                    sharding=one_chip)

    attend = lambda q, k, v: seq._splash_dense(
        q, k, v, mask=seq.CausalMask(MLA_T), scale=None)
    fn = attend if direction == "forward" else jax.grad(
        lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    args = [shape(MLA_QK), shape(MLA_QK), shape(MLA_V)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "192" in text and "bf16[16384,32,256]" not in text  # not padded
    out = jax.eval_shape(fn, *args)
    want = [MLA_V] if direction == "forward" else [MLA_QK, MLA_QK, MLA_V]
    assert [o.shape for o in jax.tree.leaves(out)] == [
        (MLA_T, MLA_H, d) for d in want]


# (rows, query heads, KV heads, q.k head, value head, mask, vmem_bytes in
# MiB) of a cell's attention; a plain causal call (ISSUE 52: no mask object,
# the route is ``_flash_dense``'s) has None for its mask
ONE_KERNEL_CELLS = {
    "kanana2_30b_a3b.seq16k": (MLA_T, MLA_H, MLA_H, MLA_QK, MLA_V,
                               "causal mask", 83.75),
    "sdar_30b_a3b.bd8k": (16384, 32, 4, 128, 128, "block diffusion", 58.25),
    "ouro_2p6b.seq8k": (8192, 16, 16, 128, 128, None, 34.125),
    "smallthinker_21b_a3b.seq16k/full": (16384, 28, 4, 128, 128, None, 58.25),
    "nemotron3_nano_30b_a3b.seq8k": (8192, 32, 2, 128, 128, None, 34.125),
}


@pytest.mark.parametrize("cell", list(ONE_KERNEL_CELLS))
def test_the_one_kernel_splash_backward_compiles_at_a_cells_shape(
        one_chip, monkeypatch, cell):
    """ISSUE 50: the backward of a splash call as one kernel
    (``ops/pallas_attention.py``) at the two claimed cells' attention shapes:
    16 384 causal rows at 32 heads on 32 of 192 | 128 (a KV head's K, V, dk,
    dv blocks and float32 accumulators resident: 83.6 MiB by ``vmem_bytes``,
    the limit Mosaic is handed), and 16 384 block-diffusion rows at 32 heads
    on 4 of 128 (a noised query tile's visits are two runs of key tiles).
    On a TPU the route is taken by shape; the library's forward keeps its
    log-sum-exp for it, and neither of the library's backward kernels is in
    the program. ISSUE 52: and at the three plain causal shapes at a head of
    128 that ``_flash_dense`` sends the same way once ``("causal", group,
    128)`` is latched (16 heads on 16 and 32 on 2 at 8 192 rows, 28 on 4 at
    16 384): the splash forward with K and V of ``Hkv`` heads as they are, the
    one backward kernel, and none of the library's flash kernels."""
    from dgraph_tpu.obs.metrics import default_registry
    from dgraph_tpu.ops import pallas_attention
    from dgraph_tpu.parallel import sequence as seq

    T, H, Hkv, D, Dv, mask, mib = ONE_KERNEL_CELLS[cell]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_attention.vmem_bytes(T, D, Dv, 2, 1024) == mib * 2 ** 20 \
        <= pallas_attention.VMEM_BUDGET
    if mask is None:
        monkeypatch.setattr(seq, "_splash_verified", {("causal", H // Hkv, D)})
        attend = lambda q, k, v: seq._flash_dense(
            q, k, v, causal=True, scale=None, kv_mask=None)
    else:
        mask = seq.CausalMask(T) if mask == "causal mask" \
            else seq.BlockDiffusionMask(T // 2, 4)
        attend = lambda q, k, v: seq._splash_dense(
            q, k, v, mask=mask, scale=None)

    def shape(h, d):
        return jax.ShapeDtypeStruct((T, h, d), jnp.bfloat16,
                                    sharding=one_chip)

    fn = jax.grad(
        lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    args = [shape(H, D), shape(Hkv, D), shape(Hkv, Dv)]
    counters = lambda: default_registry.snapshot()["counters"]
    before = counters().get("attn.bwd_one_kernel", 0)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert counters()["attn.bwd_one_kernel"] == before + 1
    assert "splash_bwd_one_kernel" in text and "splash_mqa_fwd" in text
    assert "splash_mqa_dkv" not in text and "splash_mqa_dq" not in text
    assert "flash_mha" not in text and "flash_attention" not in text
    assert "bf16[16384,32,256]" not in text  # no head is padded in HBM
    assert [o.shape for o in jax.eval_shape(fn, *args)] == [
        (T, H, D), (T, Hkv, D), (T, Hkv, Dv)]


@pytest.mark.parametrize("latched", ["splash", "flash"])
def test_ulysses_attention_compiles_across_four_chips_on_either_route(
        topo, monkeypatch, latched):
    """No cell runs the Ulysses stage (ROADMAP R10), so its kernels are seen
    here alone: ``ulysses_attention`` under ``shard_map`` over the four chips
    of a described host, causal, 16 heads of 128 at 8 192 rows, forward and
    backward. With ``("causal", 1, 128)`` latched its per-head stage is the
    splash forward and the one backward kernel (ISSUE 52: K and V come
    repeated, so one key head a query head); with the flash kernels' latch
    alone it keeps the library's flash kernels."""
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dgraph_tpu import config as cfg
    from dgraph_tpu.parallel import sequence as seq

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(cfg, "use_flash_attention", None)  # auto
    monkeypatch.setattr(seq, "_flash_verified", latched == "flash")
    monkeypatch.setattr(seq, "_splash_verified",
                        {("causal", 1, 128)} if latched == "splash" else set())
    mesh = Mesh(np.array(topo.devices), ("seq",))
    rows = jax.ShapeDtypeStruct((8192, 16, 128), jnp.bfloat16,
                                sharding=NamedSharding(mesh, P("seq")))
    attend = shard_map(
        lambda q, k, v: seq.ulysses_attention(q, k, v, "seq", causal=True),
        mesh=mesh, in_specs=(P("seq"),) * 3, out_specs=P("seq"),
        # the library's kernels (flash and splash forward alike) declare no
        # vma on their out_shape, so under the checker, which every call
        # site of the program keeps on, NEITHER route traces (PERF.md
        # section 7, #7b); what is compiled here is the kernels' lowering
        check_vma=False)
    fn = jax.grad(lambda *a: attend(*a).astype(jnp.float32).sum(),
                  argnums=(0, 1, 2))
    with jax.set_mesh(mesh):
        text = jax.jit(fn).lower(rows, rows, rows).compile().as_text()
    assert "all-to-all" in text
    assert ("splash_bwd_one_kernel" in text, "splash_mqa_fwd" in text,
            "flash_mha" in text) == (
        (True, True, False) if latched == "splash" else (False, False, True))
