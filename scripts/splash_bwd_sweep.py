"""The splash attention's backward on the chip, stand-alone, at the seven
sequence cells' attention shapes and masks: the library's two kernels (dkv,
then dq: still the route of a shape whose blocks do not fit) against the one
kernel of ``dgraph_tpu/ops/pallas_attention.py``:

    chiprun --chips 1 -- python scripts/splash_bwd_sweep.py

prints one line a shape: the forward, and forward + backward under each
backward, in ms (bfloat16, the mean of ``REPEATS`` calls after one that
compiles), the backward alone as their difference, and the largest
difference between the two backwards' gradients (both round once from
float32 sums, so they differ by roundings of single products). The two-kernel
side is reached the way a shape past the budget reaches it: with
``pallas_attention.VMEM_BUDGET`` at 0. A plain causal shape at a head of whole
lanes (Ouro's, SmallThinker's full layer's, Nemotron's: the route
``_flash_dense`` gave the splash kernels at ISSUE 52) has two columns more:
the forward and forward + backward of the library's FLASH kernels with K and
V repeated to the query heads, which is what ran there before and what a
``kv_mask`` or a shape past the budget still runs. A CPU run is refused: times
come from the chip only.

    JAX_PLATFORMS=cpu python scripts/splash_bwd_sweep.py --memory

needs no chip: it compiles the whole train step of ``kanana2_30b_a3b.seq16k``
and of ``sdar_30b_a3b.bd8k`` for a DESCRIBED v5e, under each backward, and
prints ``memory_analysis()`` (arguments + temporaries, GB) and the kernels'
names in the program. Nothing runs, and a compile gives no time."""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

REPEATS = 5
# cell: (mask kind, rows, window or block, query heads, kv heads, q.k head,
# value head), as benchmark/configs/<configuration>.json has them
SHAPES = {
    "kanana2_30b_a3b.seq16k": ("causal", 16384, None, 32, 32, 192, 128),
    "sdar_30b_a3b.bd8k": ("block_diffusion", 16384, 4, 32, 4, 128, 128),
    "smallthinker_21b_a3b.seq16k": ("window", 16384, 4096, 28, 4, 128, 128),
    "lfm2_8b_a1b.seq16k": ("causal", 16384, None, 32, 8, 64, 64),
    "phi4_mini_flash.seq8k/window": ("window", 8192, 512, 40, 20, 64, 128),
    "phi4_mini_flash.seq8k/full": ("causal", 8192, None, 40, 20, 64, 128),
    "ouro_2p6b.seq8k": ("causal", 8192, None, 16, 16, 128, 128),
    "smallthinker_21b_a3b.seq16k/full": ("causal", 16384, None, 28, 4, 128, 128),
    "nemotron3_nano_30b_a3b.seq8k": ("causal", 8192, None, 32, 2, 128, 128),
}


def mask_of(seq, kind, rows, arg):
    if kind == "block_diffusion":
        return seq.BlockDiffusionMask(rows // 2, arg)
    return seq.CausalMask(rows) if kind == "causal" \
        else seq.WindowMask(rows, arg)


def timings() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dgraph_tpu.ops import pallas_attention as pa
    from dgraph_tpu.parallel import sequence as seq

    if jax.default_backend() != "tpu":
        print("splash_bwd_sweep: needs a TPU", file=sys.stderr)
        return 2
    budget = pa.VMEM_BUDGET
    for cell, (kind, T, arg, H, Hkv, D, Dv) in SHAPES.items():
        rng = np.random.default_rng(0)
        q, k, v, w = (jnp.asarray(rng.standard_normal((T, h, d)),
                                  jnp.bfloat16)
                      for h, d in ((H, D), (Hkv, D), (Hkv, Dv), (H, Dv)))
        mask = mask_of(seq, kind, T, arg)

        def timed(fn):
            jax.block_until_ready(fn(q, k, v))  # compiles
            t0 = time.perf_counter()
            for _ in range(REPEATS):
                out = fn(q, k, v)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / REPEATS * 1e3, out

        attend = lambda q, k, v: seq._splash_dense(
            q, k, v, mask=mask, scale=None)
        both = lambda attend: jax.jit(jax.grad(
            lambda q, k, v: (attend(q, k, v).astype(jnp.float32)
                             * w.astype(jnp.float32)).sum(),
            argnums=(0, 1, 2)))
        gap = lambda g, h: max(
            float(jnp.abs(a.astype(jnp.float32)
                          - b.astype(jnp.float32)).max())
            for a, b in zip(g, h))
        fwd, _ = timed(jax.jit(attend))
        pa.VMEM_BUDGET = 0
        two, g2 = timed(both(attend))
        pa.VMEM_BUDGET = budget
        assert seq._one_kernel_backward(T, D, Dv, q.dtype), cell
        one, g1 = timed(both(attend))
        line = (f"{cell} {kind} T={T} heads={H}on{Hkv} head={D}|{Dv} "
                f"forward_ms={fwd:.2f} two_kernels_fb_ms={two:.2f} "
                f"one_kernel_fb_ms={one:.2f} backward_ms={two - fwd:.2f}->"
                f"{one - fwd:.2f} ratio={(one - fwd) / (two - fwd):.3f} "
                f"max_grad_gap={gap(g1, g2):.4f}")
        if kind == "causal" and D % 128 == 0 and Dv == D:
            flash = lambda q, k, v: seq._flash_kernels(
                q, k, v, causal=True, scale=None, kv_mask=None)
            f_fwd, _ = timed(jax.jit(flash))
            f_both, gf = timed(both(flash))
            line += (f" flash_forward_ms={f_fwd:.2f} flash_fb_ms={f_both:.2f}"
                     f" flash_backward_ms={f_both - f_fwd:.2f}"
                     f" max_grad_gap_to_flash={gap(g1, gf):.4f}")
        print(line, flush=True)
    return 0


def models(comm):
    """``(configuration, model, batch shapes, the splash latch)`` of the two
    claimed cells, as their builders make them (Kanana's has ``model_of``;
    SDAR's builder spells its model out, and so does this)."""
    import jax
    import jax.numpy as jnp

    from benchmark.builders import kanana
    from dgraph_tpu.models.looplm import HeldExperts, LoopLM

    def sizes(name):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               f"{name}.json")) as f:
            return json.load(f)["sizes"]

    tokens = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32)
    size = sizes("kanana2_30b_a3b")
    yield ("kanana2_30b_a3b", size, kanana.model_of(size, comm),
           tokens(16384), ("causal", 1, (192, 128)))
    size = sizes("sdar_30b_a3b")
    model = LoopLM(
        vocab=size["vocab_size"], hidden_size=size["hidden_size"],
        num_layers=size["num_hidden_layers"],
        num_heads=size["num_attention_heads"],
        num_kv_heads=size["num_key_value_heads"], head_dim=size["head_dim"],
        intermediate=0, comm=comm, loop_steps=1, exit_gate=False,
        rms_eps=size["rms_norm_eps"], rope_theta=float(size["rope_theta"]),
        dtype=jnp.dtype(size["compute_dtype"]), remat=size["remat"],
        sandwich_norm=False, qk_norm=True,
        experts=HeldExperts(
            n_total=size["num_experts_total"], n_held=size["num_experts"],
            k=size["num_experts_per_tok"],
            width=size["moe_intermediate_size"],
            first_held=size["first_expert"], rows=size["moe_buffer_rows"]),
        block_length=size["block_length"], mask_token=size["mask_token_id"])
    batch = (tokens(8192), jax.ShapeDtypeStruct((8192,), jnp.bool_),
             jax.ShapeDtypeStruct((8192,), jnp.float32))
    yield "sdar_30b_a3b", size, model, batch, ("block_diffusion", 8, 128)


def memory() -> int:
    """Whole train steps compiled for a described v5e, under each backward."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies

    from dgraph_tpu import config as cfg
    from dgraph_tpu.ops import pallas_attention as pa
    from dgraph_tpu.parallel import sequence as seq
    from dgraph_tpu.train import lm

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"  # the program's TPU branches
    cfg.use_flash_attention = True
    mesh, comm = lm.lm_mesh(1, topo.devices[:1]), lm.lm_comm(1)
    budget = pa.VMEM_BUDGET
    for name, size, model, batch, latch in models(comm):
        seq._splash_verified.add(latch)
        optimizer = optax.adamw(size["learning_rate"], b1=size["beta1"],
                                b2=size["beta2"],
                                weight_decay=size["weight_decay"])
        params = jax.eval_shape(lambda t: model.init(
            jax.random.key(0), *lm._probe_rows(model, t)),
            jnp.zeros((lm.INIT_PROBE_TOKENS,), jnp.int32))
        opt_state = jax.eval_shape(optimizer.init, params)
        T = jax.tree.leaves(batch)[0].shape[0]
        for label, b in (("two kernels", 0), ("one kernel", budget)):
            pa.VMEM_BUDGET = b
            step = lm.make_lm_train_step(model, optimizer, mesh, comm,
                                         seq_len=T)
            t0 = time.perf_counter()
            with jax.set_mesh(mesh):
                compiled = step.lower(params, opt_state, batch).compile()
            m, text = compiled.memory_analysis(), compiled.as_text()
            print(f"{name} {label}: arguments "
                  f"{m.argument_size_in_bytes / 1e9:.3f} GB + temporaries "
                  f"{m.temp_size_in_bytes / 1e9:.3f} GB; custom calls named "
                  f"splash_bwd_one_kernel x"
                  f"{text.count('/splash_bwd_one_kernel')}, splash_mqa_dkv x"
                  f"{text.count('/splash_mqa_dkv')}, splash_mqa_dq x"
                  f"{text.count('/splash_mqa_dq')} "
                  f"({time.perf_counter() - t0:.0f} s of compile)", flush=True)
        pa.VMEM_BUDGET = budget
    return 0


if __name__ == "__main__":
    sys.exit(memory() if "--memory" in sys.argv[1:] else timings())
