"""Builder for looped-LM training cells: the trainer of
``dgraph_tpu/train/lm.py``, called, not copied.

Set-up is ``lm_setup`` (the program's own: attention chosen after the chip's
self-check, ``model.init``, ``optimizer.init``), after which the parameters'
VALUES are replaced by the benchmark's, made from ``--seed`` in the same tree
(the plain reference gets the same). The timed step is ``LMTrainer.step``: one
host batch of token ids to the device, the jitted train step, the host blocks
on the loss; its ``host_feed`` / ``step_dispatch`` / ``block`` spans are the
program's own (``obs.spans``), switched on in a traced run so that they lie on
the profiler's clock. The traced-only ``fwd`` phase is ``LMTrainer.evaluate``.

Traffic: ``batches`` packed sequences of ``seq_len`` token ids, drawn from the
seed with ``P(id = k) ~ 1 / (k + 1)^zipf_exponent`` over the whole vocabulary,
cycled one a step.
"""

from __future__ import annotations

import time

from benchmark import weights
from benchmark.cells import Phase


def zipf_tokens(rng, seq_len: int, vocab: int, exponent: float):
    """``seq_len`` ids with ``P(k) ~ 1 / (k + 1)^exponent`` over all ``vocab``
    ids: the rank-frequency law of text, rank order = id order."""
    import numpy as np

    w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(w / w.sum())
    ids = np.searchsorted(cdf, rng.random(seq_len), side="right")
    return np.minimum(ids, vocab - 1).astype(np.int32)


def seeded_lm_params(shapes, seed: int, sharding):
    """The benchmark's weights in the program's tree, made on the device in
    one jitted call. By the leaf's name: ``kernel`` ~ N(0, 1 / fan_in) with
    fan_in the last axis but one (the layers' kernels carry a leading layer
    axis, which ``weights.seeded_params`` would take for the fan-in);
    ``scale`` = 1 + 0.02 N(0, 1); ``embedding`` ~ N(0, 1), the size of what
    each sandwich-normed sublayer adds to the stream; ``bias`` ~ 0.02 N(0, 1).
    No leaf is inert in the first gradients."""
    import jax
    import jax.numpy as jnp

    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for k, (path, s) in zip(jax.random.split(key, len(paths)), paths):
            kind = weights.leaf_name(path).rsplit("/", 1)[-1]
            n = jax.random.normal(k, s.shape, jnp.float32)
            if kind == "kernel":
                n = n * (1.0 / s.shape[-2]) ** 0.5
            elif kind == "scale":
                n = 1.0 + 0.02 * n
            elif kind != "embedding":
                n = 0.02 * n
            out.append(n.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make, out_shardings=sharding)(jax.random.key(seed))


class LoopLMCell:
    check_phase = "fed"

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dgraph_tpu.models.looplm import LoopLM
        from dgraph_tpu.obs import spans
        from dgraph_tpu.train import lm

        self.jax, self.lm = jax, lm
        self.ref = ctx.reference
        size = self.size = ctx.sizes
        self.traffic = ctx.traffic
        W = ctx.traffic["world_size"]
        # a tiny preset (CPU tests) states a shorter sequence of its own
        T = self.seq_len = size.get("seq_len", ctx.traffic["seq_len"])
        self.make_batches(ctx.seed, ctx.spans)

        self.mesh = lm.lm_mesh(W, ctx.devices[:W])
        comm = lm.lm_comm(W)
        model = LoopLM(
            vocab=size["vocab_size"], hidden_size=size["hidden_size"],
            num_layers=size["num_hidden_layers"],
            num_heads=size["num_attention_heads"],
            num_kv_heads=size["num_key_value_heads"],
            head_dim=size["head_dim"], intermediate=size["intermediate_size"],
            comm=comm, loop_steps=size["total_ut_steps"],
            exit_gate=size["exit_gate"], rms_eps=size["rms_norm_eps"],
            rope_theta=float(size["rope_theta"]),
            dtype=jnp.dtype(size["compute_dtype"]), remat=size["remat"])
        peak, warm = size["learning_rate"], size["warmup_steps"]
        self._opt = optax.adamw(  # linear warm-up; the first step is 1/warm
            lambda count: peak * jnp.minimum(1.0, (count + 1) / warm),
            b1=size["beta1"], b2=size["beta2"],
            weight_decay=size["weight_decay"])
        self._step_kw = dict(seq_len=T, beta=size["exit_beta"])
        if ctx.traced:  # the trainer's spans onto the profiler's clock
            spans.enable(sink=lambda rec: None)

        # --- the program's own set-up; then the benchmark's weights -------
        t0 = time.perf_counter()
        self.trainer = lm.lm_setup(
            model, self._opt, self.mesh, comm, seed=0, **self._step_kw)
        ctx.say("lm start-up: " + " ".join(
            f"{k}={v}" for k, v in self.trainer.startup.items()))
        self._shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            self.trainer.params)
        self._replicated = NamedSharding(self.mesh, P())
        # the init's values go (and the moments with them, or three copies
        # of the optimizer state would be live at once); the tree stays
        self.trainer.params = self.trainer.opt_state = None
        self.make_state(ctx.seed)
        ctx.spans["weights_s"] = time.perf_counter() - t0

        # --- placement: the first batch onto the mesh, waited for ---------
        t0 = time.perf_counter()
        jax.block_until_ready(self.trainer.feed(self.batches[0]))
        ctx.spans["placement_s"] = time.perf_counter() - t0

        self.info = {
            "world_size": W, "seq_len": T, "vocab": size["vocab_size"],
            "hidden": size["hidden_size"], "heads": size["num_attention_heads"],
            "kv_heads": size["num_key_value_heads"],
            "head_dim": size["head_dim"],
            "intermediate": size["intermediate_size"],
            "layers": size["num_hidden_layers"],
            "loop_steps": size["total_ut_steps"],
            "remat": bool(size["remat"]),
            "h2d_bytes_per_step": {"fed": self.batches[0].nbytes,
                                   "fwd": self.batches[0].nbytes},
        }
        self.sm = None
        self.cursor = 0
        self.phases = [Phase("fed", "fed_step_ms", 1.0, self.fed_once)]
        if ctx.traced:
            self.phases.append(Phase("fwd", None, 0.0, self.fwd_once))

    def context(self):
        return self.jax.set_mesh(self.mesh)

    # --- what depends on the seed -------------------------------------------
    def make_batches(self, seed, spans):
        import numpy as np

        t0 = time.perf_counter()
        rng = np.random.default_rng(seed)
        tr = self.traffic
        self.batches = [
            zipf_tokens(rng, self.seq_len, self.size["vocab_size"],
                        tr["zipf_exponent"]) for _ in range(tr["batches"])]
        self.cursor = 0
        spans["input_synthesis_s"] = time.perf_counter() - t0

    def _seeded(self, seed):
        with self.jax.set_mesh(self.mesh):
            return seeded_lm_params(self._shapes, seed, self._replicated)

    def make_state(self, seed):
        # (no reseed() for tools/limits.py: it keeps a reseeded cell's state
        # on the device while the reference runs, and the two do not fit)
        from dgraph_tpu.train.loop import init_opt_state

        self.seed = seed
        self.trainer.params = self._seeded(seed)
        self.trainer.opt_state = init_opt_state(
            self._opt, self.trainer.params, self.mesh)
        self.jax.block_until_ready(
            (self.trainer.params, self.trainer.opt_state))

    @property
    def params0(self):
        """The seeded weights, made again (the step donates its state, and a
        second copy held through the window would be 4 B a weight)."""
        return self._seeded(self.seed)

    # --- the timed steps ----------------------------------------------------
    def fed_once(self):
        tokens = self.batches[self.cursor % len(self.batches)]
        self.cursor += 1
        self.sm = self.trainer.step(tokens)

    def fwd_once(self):
        self.trainer.evaluate(self.batches[0])

    # --- what the comparison reads from the program's state -------------------
    def loss(self) -> float:
        return float(self.sm.loss)

    def first_gradient(self):
        """The first gradient as the optimizer got it, worked out from its
        state after one step: mu = (1 - b1) g. A tree of new buffers."""
        keep = 1.0 - self.size["beta1"]
        return self.jax.jit(lambda mu: self.jax.tree.map(
            lambda m: m / keep, mu))(self.trainer.opt_state[0].mu)

    def delta_norms(self) -> dict:
        return weights.leaf_norms(self.trainer.params, self.params0)

    def eval_numbers(self) -> dict:
        return {}

    def break_step(self, fault: str):
        """Tests only: put a fault under the timed path."""
        if fault != "frozen":
            raise ValueError(f"unknown fault {fault!r}")
        tr = self.trainer
        inner = self.lm.make_lm_train_step(
            tr.model, self._opt, self.mesh, tr.comm, donate=False,
            **self._step_kw)

        def frozen(params, opt_state, tokens):
            return params, opt_state, inner(params, opt_state, tokens)[2]

        tr.train_step = frozen

    def release(self):
        self.host_params0 = self.jax.device_get(self.params0)
        for name in ("trainer", "sm", "phases"):
            setattr(self, name, None)
        self.jax.clear_caches()

    def reference(self, steps: int, precision: str = "float32") -> dict:
        return self.ref.follow(self.host_params0, self.batches[:steps],
                               self.size, precision=precision)


def build(ctx):
    return LoopLMCell(ctx)
