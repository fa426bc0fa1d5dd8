"""Builder for the cell of a hybrid Mamba-2 / expert decoder whose every layer
is one residual half (Mamba-2 mixers, sigmoid-routed squared-ReLU experts
beside one shared expert, grouped-query attention): the trainer of
``dgraph_tpu/train/lm.py``, called, not copied, as ``builders/looplm.py`` does
for the looped LM (whose Zipf ids, seeded weights and cell methods this one
shares), ``builders/sdar.py`` for the expert layers' counts and
``builders/lfm2.py`` for the router's choices over runs of layers.

Set-up is ``lm_setup`` (attention chosen after the chip's self-check;
``model.init``; ``optimizer.init``), then the benchmark's weights in the same
tree. The timed step is ``LMTrainer.step`` on one packed sequence of token
ids; the traced-only ``fwd`` phase is ``LMTrainer.evaluate``. After the window
the registry's ``moe.rows_dropped`` over every step run is read: a dropped row
makes the run not correct (none can be while the buffer is the worst case).

The comparison's copy of the first gradient is fetched leaf by leaf and
divided on the host, as ``builders/phi4flash.py``'s: the state is 16 B x 667 M,
and a second tree of device buffers the size of the gradient does not fit
beside it and a step's reserved temporaries.

Traffic: as ``builders/looplm.py``'s, ``batches`` packed sequences of
``seq_len`` ids, Zipf over this chip's slice of the vocabulary, cycled one a
step.
"""

from __future__ import annotations

import math
import time

from benchmark import weights
from benchmark.builders.lfm2 import Lfm2Cell
from benchmark.builders.looplm import seeded_lm_params
from benchmark.builders.phi4flash import Phi4FlashCell
from benchmark.builders.sdar import SdarCell
from benchmark.cells import Phase

A_RANGE = (1.0, 16.0)  # -A a head, uniform (Mamba-2's A_init_range)


def model_of(size: dict, comm):
    """The program's model at a configuration's sizes (``sizes`` or ``tiny``)."""
    import jax.numpy as jnp

    from dgraph_tpu.models.looplm import HeldExperts, LoopLM, Mamba2Mixer

    pattern = tuple(layer_kinds(size))
    if size["tie_word_embeddings"] or size["use_bias"] or size["mlp_bias"] \
            or size["attention_bias"] or size["mamba_proj_bias"] \
            or not size["use_conv_bias"] or not size["norm_topk_prob"] \
            or size["mlp_hidden_act"] != "relu2" \
            or size["n_shared_experts"] != 1 \
            or size["n_group"] != 1 or size["topk_group"] != 1:
        raise ValueError("the cell is built for an untied head, no bias but "
                         "the convolution's, a normalised router without "
                         "group limits, squared-ReLU experts and one shared "
                         "expert; the configuration says otherwise")
    return LoopLM(
        vocab=size["vocab_size"], hidden_size=size["hidden_size"],
        num_layers=len(pattern), pattern=pattern,
        first_depth=size["first_layer"], tie_head=False,
        num_heads=size["num_attention_heads"],
        num_kv_heads=size["num_key_value_heads"], head_dim=size["head_dim"],
        intermediate=0, comm=comm, loop_steps=1, exit_gate=False,
        rms_eps=size["layer_norm_epsilon"], rope_theta=None,
        dtype=jnp.dtype(size["compute_dtype"]), remat=size["remat"],
        sandwich_norm=False,
        ssd=Mamba2Mixer(
            heads=size["mamba_num_heads"], head_dim=size["mamba_head_dim"],
            groups=size["n_groups"], state=size["ssm_state_size"],
            conv=size["conv_kernel"], chunk=size["chunk_size"]),
        experts=HeldExperts(
            n_total=size["n_routed_experts_total"],
            n_held=size["n_routed_experts"], k=size["num_experts_per_tok"],
            width=size["moe_intermediate_size"],
            first_held=size["first_expert"], rows=size["moe_buffer_rows"],
            score="sigmoid", select_bias=True, gate_eps=1e-20,
            gate_scale=float(size["routed_scaling_factor"]), form="relu2",
            shared_width=size["moe_shared_expert_intermediate_size"]))


def layer_kinds(size: dict) -> list:
    """The program's kind of each letter of the configuration's pattern."""
    kinds = {"M": "ssd+none", "E": "none+experts", "*": "attn+none"}
    pattern = [kinds[c] for c in size["hybrid_override_pattern"]]
    if len(pattern) != size["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    return pattern


def seeded_params(shapes, seed: int, sharding, size: dict):
    """``seeded_lm_params``, then the leaves whose values decide whether the
    recurrence computes anything (the configuration's ``assumed``): ``A_log =
    log U(1, 16)`` a head, ``D = 1``, ``dt_bias`` the inverse softplus of a
    log-uniform step size in ``[time_step_min, time_step_max]`` floored at
    ``time_step_floor``."""
    import jax
    import jax.numpy as jnp

    lo, hi = math.log(size["time_step_min"]), math.log(size["time_step_max"])
    floor = size["time_step_floor"]

    def special(params):
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        out = []
        for i, (path, a) in enumerate(flat):
            kind = weights.leaf_name(path).rsplit("/", 1)[-1]
            key = jax.random.fold_in(jax.random.key(seed), i)
            if kind == "A_log":
                a = jnp.log(jax.random.uniform(key, a.shape, a.dtype,
                                               *A_RANGE))
            elif kind == "D":
                a = jnp.ones_like(a)
            elif kind == "dt_bias":
                dt = jnp.maximum(jnp.exp(jax.random.uniform(
                    key, a.shape, a.dtype) * (hi - lo) + lo), floor)
                a = dt + jnp.log(-jnp.expm1(-dt))
            out.append(a)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(special, donate_argnums=0, out_shardings=sharding)(
        seeded_lm_params(shapes, seed, sharding))


class NemotronHCell(Lfm2Cell):
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
            "head_dim": size["head_dim"],
            "ssd_heads": size["mamba_num_heads"],
            "ssd_head_dim": size["mamba_head_dim"],
            "ssd_groups": size["n_groups"],
            "ssd_state": size["ssm_state_size"],
            "ssd_chunk": size["chunk_size"],
            "conv_kernel": size["conv_kernel"],
            "expert_width": size["moe_intermediate_size"],
            "shared_width": size["moe_shared_expert_intermediate_size"],
            "experts_held": size["n_routed_experts"],
            "experts_total": size["n_routed_experts_total"],
            "experts_per_token": size["num_experts_per_tok"],
            "layers": size["num_hidden_layers"], "layers_ssd": kinds["ssd"],
            "layers_attention": kinds["attention"],
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

    def _seeded(self, seed):
        with self.jax.set_mesh(self.mesh):
            return seeded_params(self._shapes, seed, self._replicated,
                                 self.size)

    # host arrays, leaf by leaf: no second tree of device buffers
    first_gradient = Phi4FlashCell.first_gradient

    def break_step(self, fault: str):
        """Tests only: ``frozen`` as ``builders/phi4flash.py``'s (the
        trainer's own forward pass in the step's place: a second, undonating
        train step would be a second copy of 8 GB of state), ``dropped`` as
        ``builders/sdar.py``'s."""
        if fault == "frozen":
            return Phi4FlashCell.break_step(self, fault)
        return SdarCell.break_step(self, fault)


def build(ctx):
    return NemotronHCell(ctx)
