"""Builder for the block-diffusion sparse-expert LM cell: the trainer of
``dgraph_tpu/train/lm.py``, called, not copied, as ``builders/looplm.py``
does for the looped LM (whose seeded weights, Zipf ids and cell methods this
one shares).

Set-up is ``lm_setup`` (attention chosen after the chip's self-check, which
here also covers the splash kernels under the block-diffusion mask and the
model's head grouping; ``model.init``; ``optimizer.init``), then the
benchmark's weights in the same tree. The timed step is ``LMTrainer.step`` on
a batch ``(tokens, masked, weight)``; the traced-only ``fwd`` phase is
``LMTrainer.evaluate``. After the window the registry's ``moe.rows_dropped``
over every step run is read: a dropped row makes the run not correct.

Traffic: ``batches`` packed sequences of ``seq_len`` token ids, Zipf over the
ids below the mask token's, each noised on the host with the batch, from the
seed, as a collator would: per block b of ``block_length`` tokens a rate
``t_b = eps + (1 - eps) u_b``, each token of b masked with probability
``t_b``, ``weight = 1 / t_b``. Cycled one a step.
"""

from __future__ import annotations

import dataclasses
import itertools
import time

from benchmark.builders.looplm import LoopLMCell, seeded_lm_params, zipf_tokens
from benchmark.cells import Phase


def noised_batch(rng, seq_len, data_ids, exponent, block, eps):
    """(tokens [L] int32, masked [L] bool, weight [L] float32)."""
    import numpy as np

    tokens = zipf_tokens(rng, seq_len, data_ids, exponent)
    t = np.repeat(eps + (1.0 - eps) * rng.random(seq_len // block), block)
    masked = rng.random(seq_len) < t
    return tokens, masked, (1.0 / t).astype(np.float32)


class SdarCell(LoopLMCell):
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
        if size["block_length"] != ctx.traffic["block_length"]:
            raise ValueError("the traffic mix noises blocks of another length "
                             "than the configuration attends over")
        if not size["norm_topk_prob"]:
            raise ValueError("the program's router renormalises the chosen "
                             "gates; the configuration says it does not")
        self.make_batches(ctx.seed, ctx.spans)

        self.mesh = lm.lm_mesh(W, ctx.devices[:W])
        comm = lm.lm_comm(W)
        model = LoopLM(
            vocab=size["vocab_size"], hidden_size=size["hidden_size"],
            num_layers=size["num_hidden_layers"],
            num_heads=size["num_attention_heads"],
            num_kv_heads=size["num_key_value_heads"],
            head_dim=size["head_dim"], intermediate=0, comm=comm,
            loop_steps=1, exit_gate=False, rms_eps=size["rms_norm_eps"],
            rope_theta=float(size["rope_theta"]),
            dtype=jnp.dtype(size["compute_dtype"]), remat=size["remat"],
            sandwich_norm=False, qk_norm=True,
            experts=HeldExperts(
                n_total=size["num_experts_total"], n_held=size["num_experts"],
                k=size["num_experts_per_tok"],
                width=size["moe_intermediate_size"],
                first_held=size["first_expert"],
                rows=size["moe_buffer_rows"]),
            block_length=size["block_length"],
            mask_token=size["mask_token_id"])
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

        fed = sum(a.nbytes for a in self.batches[0])
        self.info = {
            "world_size": W, "seq_len": T, "rows": 2 * T,
            "block_length": size["block_length"], "vocab": size["vocab_size"],
            "hidden": size["hidden_size"], "heads": size["num_attention_heads"],
            "kv_heads": size["num_key_value_heads"],
            "head_dim": size["head_dim"],
            "expert_width": size["moe_intermediate_size"],
            "experts_held": size["num_experts"],
            "experts_total": size["num_experts_total"],
            "experts_per_token": size["num_experts_per_tok"],
            "layers": size["num_hidden_layers"], "loop_steps": 1,
            "remat": bool(size["remat"]),
            "h2d_bytes_per_step": {"fed": fed, "fwd": fed},
        }
        self.say = ctx.say
        self.sm = None
        self.cursor = 0
        self.phases = [Phase("fed", "fed_step_ms", 1.0, self.fed_once)]
        if ctx.traced:
            self.phases.append(Phase("fwd", None, 0.0, self.fwd_once))

    def make_batches(self, seed, spans):
        import numpy as np

        t0 = time.perf_counter()
        rng = np.random.default_rng(seed)
        tr, size = self.traffic, self.size
        self.batches = [
            noised_batch(rng, self.seq_len, size["mask_token_id"],
                         tr["zipf_exponent"], tr["block_length"],
                         tr["mask_rate_eps"]) for _ in range(tr["batches"])]
        self.cursor = 0
        spans["input_synthesis_s"] = time.perf_counter() - t0

    # --- the comparison -----------------------------------------------------
    def break_step(self, fault: str):
        """Tests only. ``dropped``: from the fourth call on (after the steps
        the comparison reads) the timed step has a row buffer too small for
        the rows routed here, so only the count read after the window can
        tell."""
        if fault != "dropped":
            return super().break_step(fault)
        tr = self.trainer
        small = self.lm.make_lm_train_step(
            tr.model.clone(experts=dataclasses.replace(
                tr.model.experts, rows=self.seq_len // 4)),
            self._opt, self.mesh, tr.comm, **self._step_kw)
        sound, calls = tr.train_step, itertools.count()
        tr.train_step = lambda *args: (
            sound if next(calls) < 3 else small)(*args)

    def expert_rows(self) -> float:
        """Say what the registry holds of the expert layers' rows over every
        step the trainer has run; return the rows dropped."""
        from dgraph_tpu.obs.metrics import default_registry

        snap = default_registry.snapshot()
        c = snap["counters"]
        self.say("expert rows: " + " ".join(
            f"{k}={c.get('moe.' + k, 0):.0f}" for k in (
                "rows_routed", "rows_here", "rows_tiled", "rows_dropped"))
            + f" rows_max_expert={snap['gauges'].get('moe.rows_max_expert', 0):.0f}")
        return c.get("moe.rows_dropped", 0)

    def program_choices(self):
        """The experts every row chose in the program's forward pass over the
        first batch at the seeded weights, [layers, 2L, k] on the host."""
        import jax.numpy as jnp
        import numpy as np

        tr, L = self.trainer, self.seq_len
        tokens, masked, _ = self.batches[0]
        rows = np.concatenate(
            [np.where(masked, self.size["mask_token_id"], tokens), tokens])
        positions = np.tile(np.arange(L, dtype=np.int32), 2)

        def chosen(params, rows, positions):
            _, got = tr.model.apply(params, rows, positions, method="hidden",
                                    mutable=["intermediates"])
            leaf, = self.jax.tree.leaves(got)
            return leaf[0]  # the one pass

        return np.asarray(self.jax.jit(chosen)(
            self.params0, jnp.asarray(rows, jnp.int32), jnp.asarray(positions)))

    def release(self):
        # after the window: the check steps, the warm-up and every timed step
        self.rows_dropped = self.expert_rows()
        with self.context():
            self.trainer.params = self.trainer.opt_state = None
            self.chosen = self.program_choices()
        super().release()

    def reference(self, steps: int, precision: str = "float32") -> dict:
        import numpy as np

        out = self.ref.follow(self.host_params0, self.batches[:steps],
                              self.size, precision=precision)
        want = out.pop("chosen")
        same = (self.chosen[..., :, None] == want[..., None, :]).any(-1)
        self.say(f"router: {100.0 * (1.0 - same.mean()):.4f} % of (row, choice) "
                 f"pairs of the program's first forward pass are not among the "
                 f"{precision} reference's choices for the row (by layer: "
                 + " ".join(f"{100.0 * (1.0 - s.mean()):.3f}" for s in same)
                 + "); printed, not limited")
        if self.rows_dropped:
            # a dropped row is a wrong result whatever the gaps read, and the
            # harness takes `correct` from the gaps alone: no loss compares
            # with this one, so the limited loss_gap fails
            self.say(f"{self.rows_dropped:.0f} rows routed to this chip's "
                     f"experts did not fit moe_buffer_rows and were dropped: "
                     f"not correct")
            out["loss"] = [float("nan")] * steps
        return out


def build(ctx):
    return SdarCell(ctx)
