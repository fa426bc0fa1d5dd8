"""Builder for the cell of a sparse-expert decoder whose router reads the
layer's input before attention, with ReGLU experts and no shared expert, one
full attention layer without positions among windowed layers with rotary
positions (SmallThinker): the trainer of ``dgraph_tpu/train/lm.py``, called,
not copied, as ``builders/looplm.py`` does for the looped LM (whose Zipf ids,
seeded weights and cell methods this one shares), ``builders/sdar.py`` for
the expert layers' counts, ``builders/lfm2.py`` for the router's choices
over runs of layers and ``builders/nemotron_h.py`` for the comparison's
gradient and the faults of the tests (the class below is that cell's, with
this model, this ``info`` and the plain seeded weights).

Set-up is ``lm_setup`` (attention chosen after the chip's self-check, which
here covers the flash kernels for the full layer and the splash kernels under
the window at the model's head grouping; ``model.init``; ``optimizer.init``),
then the benchmark's weights in the same tree. The timed step is
``LMTrainer.step`` on one packed sequence of token ids; the traced-only
``fwd`` phase is ``LMTrainer.evaluate``. After the window the registry's
``moe.rows_dropped`` over every step run is read: a dropped row makes the run
not correct (none can be while the buffer is the worst case).

The comparison's copy of the first gradient is fetched leaf by leaf and
divided on the host (``NemotronHCell.first_gradient``): a second tree of
device buffers the size of the gradient does not fit beside the state and a
step's reserved temporaries.

Traffic: as ``builders/looplm.py``'s, ``batches`` packed sequences of
``seq_len`` ids, Zipf over this chip's slice of the vocabulary, cycled one a
step.
"""

from __future__ import annotations

import time

from benchmark import weights
from benchmark.builders.looplm import seeded_lm_params
from benchmark.builders.nemotron_h import NemotronHCell
from benchmark.cells import Phase

KINDS = {0: "attn+experts", 1: "attn_win+experts"}  # by the layouts' value


def layer_kinds(size: dict) -> list:
    """The program's kind of each layer of the configuration's ``layout``
    (the published ``sliding_window_layout`` and ``rope_layout``, which are
    one list: 1 a windowed layer with rotary positions, 0 a full layer
    without)."""
    if len(size["layout"]) != size["num_hidden_layers"]:
        raise ValueError("layout and num_hidden_layers disagree")
    return [KINDS[flag] for flag in size["layout"]]


def model_of(size: dict, comm):
    """The program's model at a configuration's sizes (``sizes`` or ``tiny``)."""
    import jax.numpy as jnp

    from dgraph_tpu.models.looplm import HeldExperts, LoopLM

    if size["tie_word_embeddings"] or not size["norm_topk_prob"] \
            or not size["moe_primary_router_apply_softmax"]:
        raise ValueError("the cell is built for an untied head and a softmax "
                         "router renormalised over the chosen; the "
                         "configuration says otherwise")
    pattern = tuple(layer_kinds(size))
    return LoopLM(
        vocab=size["vocab_size"], hidden_size=size["hidden_size"],
        num_layers=len(pattern), pattern=pattern, tie_head=False,
        num_heads=size["num_attention_heads"],
        num_kv_heads=size["num_key_value_heads"], head_dim=size["head_dim"],
        intermediate=0, comm=comm, loop_steps=1, exit_gate=False,
        rms_eps=size["rms_norm_eps"], rope_theta=float(size["rope_theta"]),
        full_attn_rope=False, window=size["sliding_window_size"],
        dtype=jnp.dtype(size["compute_dtype"]), remat=size["remat"],
        sandwich_norm=False,
        experts=HeldExperts(
            n_total=size["moe_num_primary_experts_total"],
            n_held=size["moe_num_primary_experts"],
            k=size["moe_num_active_primary_experts"],
            width=size["moe_ffn_hidden_size"],
            first_held=size["first_expert"], rows=size["moe_buffer_rows"],
            ladder=size["moe_buffer_ladder"],
            form="gated_relu", router_reads="layer_input"))


class SmallThinkerCell(NemotronHCell):
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
            "window": size["sliding_window_size"],
            "expert_width": size["moe_ffn_hidden_size"],
            "experts_held": size["moe_num_primary_experts"],
            "experts_total": size["moe_num_primary_experts_total"],
            "experts_per_token": size["moe_num_active_primary_experts"],
            "layers": size["num_hidden_layers"],
            "layers_window": kinds.get("attn_win", 0),
            "layers_full": kinds["attention"] - kinds.get("attn_win", 0),
            "loop_steps": 1,
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

    def expert_rows(self) -> float:
        """``SdarCell``'s line and count, and beside it the fullest layer of
        any step and the buffer's rows the layers took (the configuration
        takes the one buffer: a window's steps route 1-5 x the even router's
        rows here, by seed and by step)."""
        from dgraph_tpu.obs.metrics import default_registry

        dropped = super().expert_rows()
        snap = default_registry.snapshot()
        self.say("expert buffer: " + " ".join(
            f"{k}={snap['counters'].get('moe.' + k, 0):.0f}"
            for k in ("rows_buffered", "buffer_rows_offered"))
            + f" rows_max_layer={snap['gauges'].get('moe.rows_max_layer', 0):.0f}")
        return dropped

    def _seeded(self, seed):
        """The plain seeded weights with ONE leaf given values of its own: the
        embedding on bfloat16's grid (stored float32, as every leaf). The
        first layer's router reads the embedding's rows themselves, so every
        occurrence of a token id routes alike; the program's stream is
        bfloat16 and the reference's float32, and where a heavy id's sixth
        and seventh logits lie within that rounding ALL its rows (Zipf: up to
        a tenth of the batch) go to another expert at once in one of the two:
        a tie of the seed's, not a fault of either side (seed 4600000607 read
        ``grad_diff_gap`` 0.0165 so, 36 others at most 0.0058). On the grid
        both routers are handed the same numbers in the first step, the one
        whose gradient is compared; deeper layers read a stream attention has
        added to, row by row."""
        jax = self.jax
        # (not a cast there and back: the compiler may keep the excess
        # precision of such a pair, and on the chip it does)
        on_grid = jax.jit(lambda a: jax.lax.reduce_precision(
            a, exponent_bits=8, mantissa_bits=7), donate_argnums=0)
        with jax.set_mesh(self.mesh):
            params = seeded_lm_params(self._shapes, seed, self._replicated)
            return jax.tree_util.tree_map_with_path(
                lambda path, a: on_grid(a) if weights.leaf_name(path).endswith(
                    "embed/embedding") else a, params)


def build(ctx):
    return SmallThinkerCell(ctx)
