"""Distributed graph convolution (GCN).

Reference parity: ``experiments/OGB/GCN.py`` —
``GraphConvLayer`` (``GCN.py:28-67``): per-edge concat of src/dst features →
Linear → ReLU → scatter_add aggregation; ``CommAwareGCN`` (``GCN.py:70-118``):
two conv layers with halo exchanges + final fc.

TPU-first: the layer is written per-shard against the
:class:`~dgraph_tpu.comm.communicator._BaseComm` API, so the same module runs
single-device (SingleComm) or mesh-sharded inside shard_map (TpuComm) — the
reference's dummy-communicator pattern (``GraphCast/dist_utils.py:8-39``).
Aggregation defaults to the edge-owner side ('dst'), where the segment-sum is
rank-local; an optional symmetric-normalization edge weight reproduces
standard GCN (Kipf-Welling) semantics.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dgraph_tpu.plan import EdgePlan


class GraphConvLayer(nn.Module):
    """concat(src, dst) -> Dense -> activation -> scatter-sum to `aggregate_to`.

    Parity: ``experiments/OGB/GCN.py:28-67``, which fuses the ReLU into the
    CUDA scatter kernel (``local_data_kernels.cuh:34-72``). Here that fusion
    lives inside the Pallas kernel too (``pallas_call`` is an XLA fusion
    barrier, so without it the [E, F] message tensor round-trips HBM):
    relu default + owner-side aggregation takes the fused
    ``scatter_bias_relu`` path below.
    """

    out_features: int
    comm: Any  # _BaseComm (static dataclass)
    aggregate_to: str = "dst"
    activation: Any = nn.relu
    dtype: Any = None  # compute dtype (e.g. jnp.bfloat16); params stay f32

    @nn.compact
    def __call__(
        self,
        x: jax.Array,  # [n_pad, F] per-shard vertex features
        plan: EdgePlan,  # per-shard plan
        edge_weight: Optional[jax.Array] = None,  # [e_pad]
    ) -> jax.Array:
        # TPU-first algebra: Dense(concat(h_src, h_dst)) == Dense_s(h_src) +
        # Dense_d(h_dst), so project at the VERTEX level ([N,F]@[F,D], N << E)
        # and gather the projected D-dim rows — instead of materializing the
        # [E, 2F] concat the reference builds per edge (GCN.py:34-67). Saves
        # ~(E/N)x matmul FLOPs and the [E,2F] HBM round trip; exact same math.
        from dgraph_tpu import config as _cfg

        dt = _cfg.resolve_compute_dtype(self.dtype)
        h_s = nn.Dense(self.out_features, name="src_proj", dtype=dt)(x)
        h_d = nn.Dense(self.out_features, use_bias=False, name="dst_proj", dtype=dt)(x)
        # fused path (relu + owner-side aggregation, homogeneous plans):
        # the owner-side projection rides into the scatter kernel as a
        # per-vertex-block bias, so the [E, F] message tensor never exists
        # (collectives.scatter_bias_relu; falls back to composed ops
        # off-TPU — same math, pinned by the equivalence tests)
        # Feature-chunked edge pipeline: every per-edge intermediate is at
        # most gather_col_block (128) wide. The r3 jaxpr audit showed the
        # epoch's HBM traffic dominated by [E, D]-sized tensors that exist
        # only as glue — the col-split gather's concat, the activation
        # round trip — and none of them fuse past a gather/pallas_call
        # boundary. Chunking the LOCAL work (take -> activation -> scatter
        # per 128-wide slice) removes every edge-level concat: the only
        # concat left is [N, D] at the vertex level (~E/N smaller). The
        # halo exchange is hoisted to ONE full-width collective per side
        # (comm.halo_extend) so chunking never multiplies all_to_alls.
        # Gated on: feature-separable activation (relu — softmax-style
        # activations normalize ACROSS features and must see full width)
        # and a collective-free aggregation side. Every chunk ends in a
        # vertex-level [N, chunk] sum, so the chunks run one after the
        # other (map_vertex_chunks: each gather table is live through its
        # own gather only, which is what lets the compiler place it on chip).
        from dgraph_tpu.comm.collectives import map_vertex_chunks

        # Overlap routing (plans carrying an interior/boundary split whose
        # resolved halo lowering is 'overlap'): issue the boundary exchange
        # FIRST as double-buffered ppermute rounds,
        # aggregate interior edges from the local tables while it flies,
        # merge the landed boundary contributions last. Same math — relu
        # is per-edge and the aggregation sums over a partitioned edge
        # set — with the collective hidden behind the interior work.
        use_overlap = self.comm.overlap_active(plan)

        if (
            self.activation is nn.relu
            and plan.homogeneous
            and self.aggregate_to != plan.halo_side
        ):
            owner, stream = self.aggregate_to, (
                "src" if self.aggregate_to == "dst" else "dst"
            )
            h_bias = h_d if owner == "dst" else h_s
            h_stream = h_s if owner == "dst" else h_d
            if use_overlap:
                halo_buf = self.comm.halo_exchange_overlap(h_stream, plan)
                return map_vertex_chunks(
                    lambda hs, hb, b: self.comm.scatter_bias_relu_overlap(
                        hs, hb, b, plan, side=owner, edge_weight=edge_weight,
                    ),
                    (h_stream, halo_buf, h_bias),
                )
            h_ext = self.comm.halo_extend(h_stream, plan, side=stream)
            return self.comm.take_scatter_bias_relu(
                h_ext, h_bias, plan, stream_side=stream, owner_side=owner,
                edge_weight=edge_weight,
            )

        separable = self.activation in (nn.relu, jax.nn.relu)
        if separable and self.aggregate_to != plan.halo_side:
            if use_overlap:
                owner = self.aggregate_to
                h_halo = h_s if plan.halo_side == "src" else h_d
                h_own = h_d if plan.halo_side == "src" else h_s
                halo_buf = self.comm.halo_exchange_overlap(h_halo, plan)
                from dgraph_tpu.comm.collectives import overlap_edge_weight

                w_int, w_bnd = overlap_edge_weight(edge_weight, plan)

                def chunked_ov(halo_c, own_c, buf_c):
                    m_i = self.comm.interior_take(
                        halo_c, plan, side=plan.halo_side
                    ) + self.comm.interior_take(own_c, plan, side=owner)
                    m_i = self.activation(m_i)
                    if w_int is not None:
                        m_i = m_i * w_int[:, None]
                    agg = self.comm.interior_scatter_sum(m_i, plan, side=owner)
                    m_b = self.comm.boundary_take(
                        buf_c, plan, side=plan.halo_side
                    ) + self.comm.boundary_take(own_c, plan, side=owner)
                    m_b = self.activation(m_b)
                    if w_bnd is not None:
                        m_b = m_b * w_bnd[:, None]
                    return agg + self.comm.boundary_scatter_sum(
                        m_b, plan, side=owner
                    )

                return map_vertex_chunks(chunked_ov, (h_halo, h_own, halo_buf))
            hs_ext = self.comm.halo_extend(h_s, plan, side="src")
            hd_ext = self.comm.halo_extend(h_d, plan, side="dst")

            def chunked(hs_c, hd_c):
                m = self.comm.local_take(
                    hs_c, plan, side="src"
                ) + self.comm.local_take(hd_c, plan, side="dst")
                m = self.activation(m)
                if edge_weight is not None:
                    m = m * edge_weight[:, None]
                return self.comm.scatter_sum(m, plan, side=self.aggregate_to)

            return map_vertex_chunks(chunked, (hs_ext, hd_ext))

        # full-width fallback: non-separable activation or halo-side
        # aggregation (chunking would repeat the reverse exchange)
        m = self.comm.gather(h_s, plan, side="src") + self.comm.gather(
            h_d, plan, side="dst"
        )
        m = self.activation(m)
        if edge_weight is not None:
            m = m * edge_weight[:, None]
        return self.comm.scatter_sum(m, plan, side=self.aggregate_to)


class GCN(nn.Module):
    """Two GraphConv layers + linear head (``CommAwareGCN``, GCN.py:70-118)."""

    hidden_features: int
    out_features: int
    comm: Any
    num_layers: int = 2
    aggregate_to: str = "dst"
    dropout_rate: float = 0.0
    dtype: Any = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        plan: EdgePlan,
        edge_weight: Optional[jax.Array] = None,
        deterministic: bool = True,
    ) -> jax.Array:
        from dgraph_tpu import config as _cfg

        for _ in range(self.num_layers):
            x = GraphConvLayer(
                self.hidden_features,
                comm=self.comm,
                aggregate_to=self.aggregate_to,
                dtype=self.dtype,
            )(x, plan, edge_weight)
            if self.dropout_rate > 0:
                x = nn.Dropout(self.dropout_rate, deterministic=deterministic)(x)
        head_dt = _cfg.resolve_compute_dtype(self.dtype)
        return nn.Dense(self.out_features, dtype=head_dt)(x).astype(jnp.float32)
