"""Builder for the cell of a decoder-hybrid-decoder (selective state-space
layers, differential attention under a window and full, gated memory units
and cross-attention that read what one earlier layer kept): the trainer of
``dgraph_tpu/train/lm.py``, called, not copied, as ``builders/looplm.py`` does
for the looped LM, whose Zipf ids, seeded weights and cell methods this one
shares.

Set-up is ``lm_setup`` (attention chosen after the chip's self-checks, which
here cover the splash kernels under the window mask and the causal one at a
q.k head of 64 beside a v head of 128; ``model.init``; ``optimizer.init``),
then the benchmark's weights in the same tree. The timed step is
``LMTrainer.step`` on one packed sequence of token ids; the traced-only ``fwd``
phase is ``LMTrainer.evaluate``.

The comparison's copy of the first gradient is fetched leaf by leaf and
divided on the host: the cell's state is the largest of any (16 B x 697 M),
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
from benchmark.builders.looplm import LoopLMCell, seeded_lm_params
from benchmark.cells import Phase

DT_RANGE = (1e-3, 0.1)  # the step sizes b_dt is seeded for, log-uniform
LAMBDA_STD = 0.1


def model_of(size: dict, comm):
    """The program's model at a configuration's sizes (``sizes`` or ``tiny``)."""
    import jax.numpy as jnp

    from dgraph_tpu.models.looplm import LoopLM, StateSpace

    pattern = tuple(size["layer_pattern"])
    if len(pattern) != size["num_hidden_layers"]:
        raise ValueError("layer_pattern and num_hidden_layers disagree")
    if size["d_inner"] != size["expand"] * size["hidden_size"] \
            or size["head_dim"] * size["num_attention_heads"] \
            != size["hidden_size"]:
        raise ValueError("d_inner is expand x hidden, the heads make up hidden")
    if not size["tie_word_embeddings"] or size["mlp_bias"] \
            or size["lm_head_bias"] or not size["attention_bias"]:
        raise ValueError("the cell is built for a tied head, biased attention "
                         "projections and no other bias; the configuration "
                         "says otherwise")
    return LoopLM(
        vocab=size["vocab_size"], hidden_size=size["hidden_size"],
        num_layers=len(pattern), pattern=pattern,
        first_depth=size["first_layer"], tie_head=True,
        num_heads=size["num_attention_heads"],
        num_kv_heads=size["num_key_value_heads"], head_dim=size["head_dim"],
        intermediate=size["intermediate_size"], comm=comm, loop_steps=1,
        exit_gate=False, rms_eps=size["layer_norm_eps"], rope_theta=None,
        dtype=jnp.dtype(size["compute_dtype"]), remat=size["remat"],
        sandwich_norm=False, norm="layer", attn_bias=True, fused_mlp=True,
        window=size["sliding_window"],
        ssm=StateSpace(inner=size["d_inner"], state=size["d_state"],
                       conv=size["d_conv"], dt_rank=size["dt_rank"],
                       chunk=size["scan_chunk"]))


def seeded_params(shapes, seed: int, sharding, hidden: int):
    """``seeded_lm_params``, then the leaves whose values decide whether the
    model computes anything (the configuration's ``assumed``): ``A_log =
    log(1 .. N)`` a channel, ``D = 1``, ``dt_bias`` the inverse softplus of a
    log-uniform step size, the lambda vectors ``N(0, 0.1^2)``, the tied
    embedding ``N(0, 1 / hidden)``."""
    import jax
    import jax.numpy as jnp

    lo, hi = (math.log(v) for v in DT_RANGE)

    def special(params):
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        out = []
        for i, (path, a) in enumerate(flat):
            kind = weights.leaf_name(path).rsplit("/", 1)[-1]
            key = jax.random.fold_in(jax.random.key(seed), i)
            if kind == "A_log":
                a = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, a.shape[-1] + 1, dtype=a.dtype)), a.shape)
            elif kind == "D":
                a = jnp.ones_like(a)
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(key, a.shape, a.dtype)
                             * (hi - lo) + lo)
                a = dt + jnp.log(-jnp.expm1(-dt))
            elif kind.startswith("lambda_"):
                a = LAMBDA_STD * jax.random.normal(key, a.shape, a.dtype)
            elif kind == "embedding":
                a = a * hidden ** -0.5
            out.append(a)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(special, donate_argnums=0, out_shardings=sharding)(
        seeded_lm_params(shapes, seed, sharding))


class Phi4FlashCell(LoopLMCell):
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
            "world_size": W, "seq_len": T, "vocab": size["vocab_size"],
            "hidden": size["hidden_size"],
            "heads": size["num_attention_heads"],
            "kv_heads": size["num_key_value_heads"],
            "head_dim": size["head_dim"], "window": size["sliding_window"],
            "intermediate": size["intermediate_size"],
            "ssm_inner": size["d_inner"], "ssm_state": size["d_state"],
            "ssm_dt_rank": size["dt_rank"],
            "layers": len(size["layer_pattern"]), "layers_ssm": kinds["ssm"],
            "layers_gmu": kinds["gmu"], "layers_window": kinds["window"],
            "layers_full": kinds["attention"] - kinds["window"],
            "loop_steps": 1,
            "compute_bytes": jnp.dtype(size["compute_dtype"]).itemsize,
            "remat": bool(size["remat"]),
            "h2d_bytes_per_step": {"fed": fed, "fwd": fed},
        }
        self.sm = None
        self.cursor = 0
        self.phases = [Phase("fed", "fed_step_ms", 1.0, self.fed_once)]
        if ctx.traced:
            self.phases.append(Phase("fwd", None, 0.0, self.fwd_once))

    def _seeded(self, seed):
        with self.jax.set_mesh(self.mesh):
            return seeded_params(self._shapes, seed, self._replicated,
                                 self.size["hidden_size"])

    def first_gradient(self):
        """The first gradient as the optimizer got it, mu = (1 - b1) g,
        fetched leaf by leaf and divided on the host: a tree of host arrays,
        no new device buffer."""
        import numpy as np

        keep = np.float32(1.0 - self.size["beta1"])
        return self.jax.tree.map(lambda m: np.asarray(m) / keep,
                                 self.trainer.opt_state[0].mu)

    def break_step(self, fault: str):
        """Tests only: put a fault under the timed path. The inherited frozen
        step runs a second, undonating train step, whose outputs would be a
        second copy of this cell's 8.4 GB of state (the chip refused to load
        it); here the step that leaves the state where it was is the trainer's
        own forward pass, which reports the loss and nothing else."""
        if fault != "frozen":
            raise ValueError(f"unknown fault {fault!r}")
        from dgraph_tpu.obs.metrics import StepMetrics

        tr = self.trainer
        forward = tr.eval_step

        def frozen(params, opt_state, tokens):
            return params, opt_state, StepMetrics(loss=forward(params, tokens))

        tr.train_step = frozen


def build(ctx):
    return Phi4FlashCell(ctx)
