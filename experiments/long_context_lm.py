"""Long-context LM training over a sequence-sharded mesh (ring attention).

A thin CLI over :mod:`dgraph_tpu.train.lm` (set-up, the jitted step and the
host-fed loop live there). The sequence-parallel counterpart of the graph
experiment CLIs: trains
:class:`~dgraph_tpu.models.transformer.SeqTransformerLM` on a synthetic
induction corpus (second half repeats the first half, so exact causal
attention over the FULL sequence is required to get below the unigram
floor — a model whose attention were truncated to its local shard cannot
copy across the T/2 boundary once T/2 > T/W).

Every attention layer is exact ring attention over the mesh
(:mod:`dgraph_tpu.parallel.sequence`); per-device memory is O(T/W), so
sequence length scales with the mesh.

Usage:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python experiments/long_context_lm.py --seq_len 2048 --steps 200
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class Config:
    """Sequence-parallel causal LM on synthetic induction data."""

    seq_len: int = 2048
    vocab: int = 64
    latent: int = 128
    num_layers: int = 2
    num_heads: int = 4
    steps: int = 200
    lr: float = 3e-3
    world_size: Optional[int] = None  # None = all devices
    # 'ring' (O(T/W) memory) or 'ulysses' (all-to-all head sharding; its
    # full-sequence dense stage uses the Mosaic kernels on TPU, splash for a
    # plain causal call and the library's flash otherwise, when
    # config.use_flash_attention allows AND their chip self-check passed)
    attn_impl: str = "ring"
    # >0: expert-parallel MoE FFN over the same axis (one expert per rank,
    # DeepSpeed-MoE axis fusion); k = experts per token
    moe_k: int = 0
    moe_aux_weight: float = 0.01
    # >0: the looped LM (models/looplm.py) in place of SeqTransformerLM: its
    # num_layers layers are passed over loop_steps times with the same
    # parameters, and with more than one pass the exit gate and the
    # exit-distribution loss (train/lm.py) are on
    loop_steps: int = 0
    exit_beta: float = 0.1
    seed: int = 0
    log_path: str = "logs/long_context_lm.jsonl"
    log_every: int = 20
    # thread grad-norm through the jitted step + emit obs step records
    # (build-time flag; False = byte-identical un-instrumented step)
    step_metrics: bool = False


def main(cfg: Config):
    import numpy as np
    import jax
    import optax

    from dgraph_tpu.models.transformer import SeqTransformerLM, moe_param_specs
    from dgraph_tpu.obs import startup_record
    from dgraph_tpu.train.lm import fit_lm, lm_comm
    from dgraph_tpu.utils import ExperimentLog

    W = cfg.world_size or len(jax.devices())
    T = cfg.seq_len
    if T % W or T % 2:
        raise SystemExit(
            f"seq_len {T} must be even (induction corpus halves) and divide "
            f"by world_size {W}"
        )
    if cfg.moe_k > 0 and W == 1:
        raise SystemExit("moe_k > 0 needs world_size > 1 (one expert a rank)")
    comm = lm_comm(W)
    if cfg.loop_steps > 0:
        from dgraph_tpu.models.looplm import LoopLM

        model = LoopLM(
            vocab=cfg.vocab, hidden_size=cfg.latent,
            num_layers=cfg.num_layers, num_heads=cfg.num_heads,
            head_dim=cfg.latent // cfg.num_heads, intermediate=4 * cfg.latent,
            comm=comm, loop_steps=cfg.loop_steps,
            exit_gate=cfg.loop_steps > 1, attn_impl=cfg.attn_impl)
    else:
        model = SeqTransformerLM(
            vocab=cfg.vocab, latent=cfg.latent, num_layers=cfg.num_layers,
            num_heads=cfg.num_heads, max_len=T, comm=comm,
            attn_impl=cfg.attn_impl, moe_k=cfg.moe_k,
        )
    rng = np.random.default_rng(cfg.seed)

    def batches():
        while True:
            half = rng.integers(1, cfg.vocab, T // 2)
            yield np.concatenate([half, half]).astype(np.int32)

    log = ExperimentLog(cfg.log_path)
    log.write(startup_record("experiments.long_context_lm"))
    uniform = float(np.log(cfg.vocab))
    fit_lm(
        model, optax.adam(cfg.lr), batches(), seq_len=T, world_size=W,
        steps=cfg.steps, seed=cfg.seed, beta=cfg.exit_beta,
        aux_weight=cfg.moe_aux_weight / max(cfg.num_layers, 1),
        param_specs_fn=moe_param_specs if cfg.moe_k > 0 else None,
        step_metrics=cfg.step_metrics, log_every=cfg.log_every,
        log=lambda rec: log.write(dict(rec, uniform_nats=uniform)),
    )


if __name__ == "__main__":
    import os as _os, sys as _sys

    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
