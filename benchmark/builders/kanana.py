"""Builder for the cell of a sparse-expert decoder that attends through a
latent with a decoupled rotary key (multi-head latent attention; one leading
dense layer, then sigmoid-routed SwiGLU experts beside one shared expert;
kanana-2-30b-a3b, ``model_type`` deepseek_v3): the trainer of
``dgraph_tpu/train/lm.py``, called, not copied, as ``builders/looplm.py`` does
for the looped LM (whose Zipf ids, seeded weights and cell methods this one
shares), ``builders/sdar.py`` for the expert layers' counts,
``builders/lfm2.py`` for the router's choices over runs of layers and
``builders/nemotron_h.py`` for the comparison's gradient and the faults of
the tests (the class below is that cell's, with this model, this ``info`` and
the plain seeded weights).

Set-up is ``lm_setup`` (attention chosen after the chip's self-check, which
here covers the splash kernels under the causal mask at q.k heads of 192 on
value heads of 128, one key head a query head; ``model.init``;
``optimizer.init``), then the benchmark's weights in the same tree. The timed
step is ``LMTrainer.step`` on one packed sequence of token ids; the
traced-only ``fwd`` phase is ``LMTrainer.evaluate``. After the window the
registry's ``moe.rows_dropped`` over every step run is read: a dropped row
makes the run not correct (none can be while the buffer is the worst case).

Traffic: as ``builders/looplm.py``'s, ``batches`` packed sequences of
``seq_len`` ids, Zipf over this chip's slice of the vocabulary, cycled one a
step.
"""

from __future__ import annotations

import time

from benchmark.builders.looplm import LoopLMCell
from benchmark.builders.nemotron_h import NemotronHCell
from benchmark.cells import Phase


def layer_kinds(size: dict) -> list:
    """The program's kind of each layer: ``first_k_dense_replace`` dense
    layers, then expert layers (``moe_layer_freq`` 1), all attending through
    the latent."""
    dense = size["first_k_dense_replace"]
    return ["attn_mla+dense"] * dense \
        + ["attn_mla+experts"] * (size["num_hidden_layers"] - dense)


def model_of(size: dict, comm):
    """The program's model at a configuration's sizes (``sizes`` or ``tiny``)."""
    import jax.numpy as jnp

    from dgraph_tpu.models.looplm import HeldExperts, LatentAttention, LoopLM

    if size["tie_word_embeddings"] or size["attention_bias"] \
            or not size["norm_topk_prob"] or not size["rope_interleave"] \
            or size["scoring_func"] != "sigmoid" \
            or size["topk_method"] != "noaux_tc" \
            or size["hidden_act"] != "silu" \
            or size["n_group"] != 1 or size["topk_group"] != 1 \
            or size["num_key_value_heads"] != size["num_attention_heads"] \
            or size["q_lora_rank"] is not None \
            or size["qk_head_dim"] != size["qk_nope_head_dim"] \
            + size["qk_rope_head_dim"]:
        raise ValueError("the cell is built for an untied head, no bias, a "
                         "normalised sigmoid router with a selection bias "
                         "and no group limit, SwiGLU experts, interleaved "
                         "rotary pairs, one key head a query head and no "
                         "query latent; the configuration says otherwise")
    pattern = tuple(layer_kinds(size))
    return LoopLM(
        vocab=size["vocab_size"], hidden_size=size["hidden_size"],
        num_layers=len(pattern), pattern=pattern,
        first_depth=size["first_layer"], tie_head=False,
        num_heads=size["num_attention_heads"],
        num_kv_heads=size["num_key_value_heads"],
        head_dim=size["qk_head_dim"], intermediate=size["intermediate_size"],
        comm=comm, loop_steps=1, exit_gate=False,
        rms_eps=size["rms_norm_eps"], rope_theta=float(size["rope_theta"]),
        dtype=jnp.dtype(size["compute_dtype"]), remat=size["remat"],
        sandwich_norm=False,
        mla=LatentAttention(
            kv_rank=size["kv_lora_rank"], nope_dim=size["qk_nope_head_dim"],
            rope_dim=size["qk_rope_head_dim"], v_head_dim=size["v_head_dim"]),
        experts=HeldExperts(
            n_total=size["n_routed_experts_total"],
            n_held=size["n_routed_experts"], k=size["num_experts_per_tok"],
            width=size["moe_intermediate_size"],
            first_held=size["first_expert"], rows=size["moe_buffer_rows"],
            score="sigmoid", select_bias=True, gate_eps=1e-20,
            gate_scale=float(size["routed_scaling_factor"]),
            form="gated_silu",
            # the published shared experts are one MLP of their summed width
            shared_width=size["n_shared_experts"]
            * size["moe_intermediate_size"]))


class KananaCell(NemotronHCell):
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dgraph_tpu.obs import spans
        from dgraph_tpu.train import lm

        self.jax, self.lm = jax, lm
        self.ref = ctx.reference
        size = self.size = ctx.sizes
        self.traffic = ctx.traffic
        W = ctx.traffic["world_size"]
        T = self.seq_len = size.get("seq_len", ctx.traffic["seq_len"])
        self.make_batches(ctx.seed, ctx.spans)

        self.mesh = lm.lm_mesh(W, ctx.devices[:W])
        comm = lm.lm_comm(W)
        model = model_of(size, comm)
        peak, warm = size["learning_rate"], size["warmup_steps"]
        self._opt = optax.adamw(
            lambda count: peak * jnp.minimum(1.0, (count + 1) / warm),
            b1=size["beta1"], b2=size["beta2"],
            weight_decay=size["weight_decay"])
        self._step_kw = dict(seq_len=T)
        if ctx.traced:
            spans.enable(sink=lambda rec: None)

        t0 = time.perf_counter()
        self.trainer = lm.lm_setup(
            model, self._opt, self.mesh, comm, seed=0, **self._step_kw)
        ctx.say("lm start-up: " + " ".join(
            f"{k}={v}" for k, v in self.trainer.startup.items()))
        self._shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            self.trainer.params)
        self._replicated = NamedSharding(self.mesh, P())
        self.trainer.params = self.trainer.opt_state = None
        self.make_state(ctx.seed)
        ctx.spans["weights_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        jax.block_until_ready(self.trainer.feed(self.batches[0]))
        ctx.spans["placement_s"] = time.perf_counter() - t0

        kinds = self.trainer.startup["layers_by_kind"]
        fed = self.batches[0].nbytes
        self.info = {
            "world_size": W, "seq_len": T, "rows": T,
            "vocab": size["vocab_size"], "hidden": size["hidden_size"],
            "heads": size["num_attention_heads"],
            "kv_heads": size["num_key_value_heads"],
            "qk_head_dim": size["qk_head_dim"],
            "v_head_dim": size["v_head_dim"],
            "kv_rank": size["kv_lora_rank"],
            "rope_dim": size["qk_rope_head_dim"],
            "dense_width": size["intermediate_size"],
            "expert_width": size["moe_intermediate_size"],
            "shared_width": size["n_shared_experts"]
            * size["moe_intermediate_size"],
            "experts_held": size["n_routed_experts"],
            "experts_total": size["n_routed_experts_total"],
            "experts_per_token": size["num_experts_per_tok"],
            "layers": size["num_hidden_layers"],
            "layers_attention": kinds["attention"],
            "layers_dense_ffn": kinds["dense_ffn"],
            "layers_expert_ffn": kinds["expert_ffn"], "loop_steps": 1,
            "compute_bytes": jnp.dtype(size["compute_dtype"]).itemsize,
            "remat": bool(size["remat"]),
            "h2d_bytes_per_step": {"fed": fed, "fwd": fed},
        }
        self.say = ctx.say
        self.sm = None
        self.cursor = 0
        self.phases = [Phase("fed", "fed_step_ms", 1.0, self.fed_once)]
        if ctx.traced:
            self.phases.append(Phase("fwd", None, 0.0, self.fwd_once))

    # the plain seeded weights: no leaf of this model needs values of its own
    _seeded = LoopLMCell._seeded


def build(ctx):
    return KananaCell(ctx)
