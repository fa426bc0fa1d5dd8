"""Sequence transformer LM over a sequence-sharded mesh axis — the
long-context training demonstrator for :mod:`dgraph_tpu.parallel.sequence`.

Beyond-reference (the reference has no sequence models, SURVEY.md §2.5):
this is the framework's long-context story made end-to-end trainable. The
sequence dimension is sharded over a mesh axis exactly like graph vertices
are; every attention layer runs EXACT causal attention over the full
sequence via ring attention (K/V blocks streaming over ppermute, O(T/W)
memory per device; the comm facade's ``seq_attention``, which is the dense
oracle under a single-device comm). All other ops (LN, FFN, embedding,
head) are token-local, so the ONLY communication per layer is the
attention collective itself. The Ulysses all-to-all lowering
(:func:`dgraph_tpu.parallel.sequence.ulysses_attention`) is available for
hand-rolled blocks; this model uses the ring.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


class TransformerBlock(nn.Module):
    latent: int
    num_heads: int
    comm: Any  # _BaseComm: seq_attention routes ring/ulysses/dense by mode
    dtype: Any = None
    causal: bool = True
    attn_impl: str = "ring"  # or 'ulysses' (heads % axis == 0)
    # MoE FFN: one expert per rank of the SEQUENCE axis (the classic
    # DeepSpeed-MoE axis fusion — tokens are already sharded over it).
    # 0 = dense FFN.
    moe_k: int = 0  # top-k routing (1 = switch, 2 = GShard/Mixtral)

    @nn.compact
    def __call__(self, x):  # [T_loc, L]
        from dgraph_tpu import config as _cfg

        dt = _cfg.resolve_compute_dtype(self.dtype)
        L, Hh = self.latent, self.num_heads
        if L % Hh:
            raise ValueError(f"latent {L} not divisible by heads {Hh}")
        dh = L // Hh
        y = nn.LayerNorm(dtype=dt, name="ln_attn")(x)
        qkv = nn.Dense(3 * L, dtype=dt, name="qkv")(y)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        n = x.shape[0]
        attn = self.comm.seq_attention(
            q.reshape(n, Hh, dh), k.reshape(n, Hh, dh), v.reshape(n, Hh, dh),
            causal=self.causal, impl=self.attn_impl,
        )
        x = x + nn.Dense(L, dtype=dt, name="attn_out")(attn.reshape(n, L))
        y = nn.LayerNorm(dtype=dt, name="ln_ffn")(x)
        if self.moe_k > 0:
            if self.comm.graph_axis is None:
                # a silent dense fallback would be a DIFFERENT architecture
                # (no router/expert params) masquerading as the same config
                # (ADVICE r3 #3) — fail loudly instead
                raise ValueError(
                    "moe_k > 0 needs a sharded communicator (graph_axis); "
                    "SingleComm has no expert axis. Run with world_size > 1 "
                    "or set moe_k=0."
                )
            return x + self._moe_ffn(y, dt)
        h = nn.silu(nn.Dense(4 * L, dtype=dt, name="ffn_up")(y))
        return x + nn.Dense(L, dtype=dt, name="ffn_down")(h)

    def _moe_ffn(self, y, dt):
        """Expert-parallel FFN over the sequence axis. Expert weights carry
        a leading [1] axis per shard (global [E, ...], sharded over the
        axis — :func:`moe_param_specs` derives the per-leaf partition
        specs); all experts share the
        same init and diverge through routing. The router's load-balance
        loss is stashed in a mutable 'losses' collection."""
        from dgraph_tpu.parallel.expert import load_balance_loss, moe_apply

        L = self.latent
        E = self.comm.get_world_size()
        logits = nn.Dense(E, dtype=dt, name="router")(y)
        w1 = self.param(
            "moe_w1", nn.initializers.lecun_normal(), (1, L, 4 * L))
        w2 = self.param(
            "moe_w2", nn.initializers.lecun_normal(), (1, 4 * L, L))

        def expert_fn(p, z):
            h = nn.silu(z @ p["w1"].astype(z.dtype))
            return h @ p["w2"].astype(z.dtype)

        out = moe_apply(
            y, logits, expert_fn, {"w1": w1[0], "w2": w2[0]},
            self.comm.graph_axis, k=self.moe_k,
        )
        if self.is_mutable_collection("losses"):
            self.sow(
                "losses", "moe_aux",
                load_balance_loss(logits, self.comm.graph_axis),
            )
        return out


class SeqTransformerLM(nn.Module):
    """Token-in, next-token-logits-out causal LM. Per-shard inputs: this
    shard's [T_loc] token ids plus its global position offset (rank *
    T_loc) baked into the learned positional embedding lookup."""

    vocab: int
    latent: int
    num_layers: int = 2
    num_heads: int = 4
    max_len: int = 4096
    comm: Any = None
    dtype: Any = None
    attn_impl: str = "ring"
    moe_k: int = 0  # >0: expert-parallel FFN over the sequence axis

    @nn.compact
    def __call__(self, tokens, positions):  # [T_loc] int32, [T_loc] int32
        h = nn.Embed(self.vocab, self.latent, name="tok_embed")(tokens)
        h = h + nn.Embed(self.max_len, self.latent, name="pos_embed")(positions)
        for i in range(self.num_layers):
            h = TransformerBlock(
                self.latent, self.num_heads, comm=self.comm,
                dtype=self.dtype, attn_impl=self.attn_impl,
                moe_k=self.moe_k,
                name=f"block_{i}",
            )(h)
        from dgraph_tpu import config as _cfg

        dt = _cfg.resolve_compute_dtype(self.dtype)
        h = nn.LayerNorm(dtype=dt, name="ln_out")(h)
        return nn.Dense(self.vocab, dtype=dt, name="head")(h).astype(jnp.float32)


def moe_param_specs(params_or_shapes, axis_name: str = "graph"):
    """Per-leaf PartitionSpecs for an LM param tree: MoE expert weights
    (``moe_w*`` leaves, global [E, ...]) shard over ``axis_name``;
    everything else replicates. The ONE place the leading-[1]-per-shard
    convention and the ``moe_w`` naming are interpreted — derive specs
    here, never by hand (a silently replicated expert leaf trains one
    shared expert while reporting E of them)."""
    from jax.sharding import PartitionSpec as P
    from jax.tree_util import tree_map_with_path

    def spec(path, _leaf):
        # match the FINAL path component exactly: a future 'moe_weight_norm'
        # or a parent module named 'moe_w*' must not silently shard
        # (ADVICE r3 #4)
        leaf_name = str(getattr(path[-1], "key", path[-1])) if path else ""
        return P(axis_name) if leaf_name in ("moe_w1", "moe_w2") else P()

    return tree_map_with_path(spec, params_or_shapes)
