"""Builder for the cell of a decoder with layers of several kinds (gated short
convolutions among attention layers, a leading dense FFN and then held,
sigmoid-routed experts): the trainer of ``dgraph_tpu/train/lm.py``, called,
not copied, as ``builders/looplm.py`` does for the looped LM (whose Zipf ids,
seeded weights and cell methods this one shares) and ``builders/sdar.py`` for
the expert layers' counts (whose reading of dropped rows and of the router's
choices this one shares).

Set-up is ``lm_setup`` (attention chosen after the chip's self-check, which
here covers the kernels at the model's head size and grouping;
``model.init``; ``optimizer.init``), then the benchmark's weights in the same
tree. The timed step is ``LMTrainer.step`` on one packed sequence of token
ids; the traced-only ``fwd`` phase is ``LMTrainer.evaluate``. After the window
the registry's ``moe.rows_dropped`` over every step run is read: a dropped row
makes the run not correct (none can be while the buffer is the worst case).

Traffic: as ``builders/looplm.py``'s, ``batches`` packed sequences of
``seq_len`` ids, Zipf over this chip's slice of the vocabulary, cycled one a
step.
"""

from __future__ import annotations

import time

from benchmark.builders.looplm import LoopLMCell, seeded_lm_params
from benchmark.builders.sdar import SdarCell
from benchmark.cells import Phase


class Lfm2Cell(SdarCell):
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dgraph_tpu.models.looplm import HeldExperts, LoopLM
        from dgraph_tpu.obs import spans
        from dgraph_tpu.train import lm

        self.jax, self.lm = jax, lm
        self.ref = ctx.reference
        size = self.size = ctx.sizes
        self.traffic = ctx.traffic
        W = ctx.traffic["world_size"]
        T = self.seq_len = size.get("seq_len", ctx.traffic["seq_len"])
        if not (size["norm_topk_prob"] and size["use_expert_bias"]
                and size["tie_embedding"]):
            raise ValueError("the cell is built for a normalised router with "
                             "a selection bias and a tied head; the "
                             "configuration says otherwise")
        pattern = tuple(size["layer_pattern"])
        if len(pattern) != size["num_hidden_layers"]:
            raise ValueError("layer_pattern and num_hidden_layers disagree")
        self.make_batches(ctx.seed, ctx.spans)

        self.mesh = lm.lm_mesh(W, ctx.devices[:W])
        comm = lm.lm_comm(W)
        model = LoopLM(
            vocab=size["vocab_size"], hidden_size=size["hidden_size"],
            num_layers=len(pattern), pattern=pattern,
            conv_kernel=size["conv_L_cache"], tie_head=True,
            num_heads=size["num_attention_heads"],
            num_kv_heads=size["num_key_value_heads"],
            head_dim=size["head_dim"], intermediate=size["intermediate_size"],
            comm=comm, loop_steps=1, exit_gate=False,
            rms_eps=size["norm_eps"], rope_theta=float(size["rope_theta"]),
            dtype=jnp.dtype(size["compute_dtype"]), remat=size["remat"],
            sandwich_norm=False, qk_norm=True,
            experts=HeldExperts(
                n_total=size["num_experts_total"], n_held=size["num_experts"],
                k=size["num_experts_per_tok"],
                width=size["moe_intermediate_size"],
                first_held=size["first_expert"],
                rows=size["moe_buffer_rows"], score="sigmoid",
                select_bias=True, gate_eps=1e-6,
                gate_scale=float(size["routed_scaling_factor"])))
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
            "head_dim": size["head_dim"],
            "dense_width": size["intermediate_size"],
            "expert_width": size["moe_intermediate_size"],
            "experts_held": size["num_experts"],
            "experts_total": size["num_experts_total"],
            "experts_per_token": size["num_experts_per_tok"],
            "conv_kernel": size["conv_L_cache"],
            "layers": len(pattern), "layers_conv": kinds["conv"],
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

    # plain token ids, as the looped LM's cell draws them
    make_batches = LoopLMCell.make_batches

    def _seeded(self, seed):
        """``seeded_lm_params``, and the tied embedding at N(0, 1 / hidden):
        the head reads the same matrix (the configuration's ``assumed``)."""
        jax = self.jax
        with jax.set_mesh(self.mesh):
            params = seeded_lm_params(self._shapes, seed, self._replicated)
            scale = self.size["hidden_size"] ** -0.5
            emb = jax.jit(lambda e: e * scale, donate_argnums=0)(
                params["params"]["embed"]["embedding"])
        params["params"]["embed"]["embedding"] = emb
        return params

    def program_choices(self):
        """The experts every token chose in the program's forward pass over
        the first batch at the seeded weights, [expert layers, T, k] on the
        host (the runs of expert layers in stack order)."""
        import jax.numpy as jnp
        import numpy as np

        tr, T = self.trainer, self.seq_len

        def chosen(params, tokens, positions):
            _, got = tr.model.apply(params, tokens, positions, method="hidden",
                                    mutable=["intermediates"])
            runs = got["intermediates"]["stack"]
            return jnp.concatenate([  # leaf: [the one pass, layers, T, k]
                self.jax.tree.leaves(runs[name])[0][0]
                for name in sorted(runs, key=lambda n: int(n.rsplit("_", 1)[1]))])

        return np.asarray(self.jax.jit(chosen)(
            self.params0, jnp.asarray(self.batches[0], jnp.int32),
            jnp.arange(T, dtype=jnp.int32)))


def build(ctx):
    return Lfm2Cell(ctx)
