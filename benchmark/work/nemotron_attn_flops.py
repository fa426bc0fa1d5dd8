"""Useful attention FLOPs of one train step of a stack in which only some
layers attend, 16 query heads a KV head: ``lfm2_attn_flops``'s count (causal,
``2 T^2 H D`` forward a layer, three forwards a step, over the attention
layers only; grouped KV heads change bytes, not FLOPs)."""

from benchmark.work.lfm2_attn_flops import work  # noqa: F401
