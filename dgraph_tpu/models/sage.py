"""GraphSAGE (mean aggregator) — one of the reference's tracked configs
(BASELINE.md: "ogbn-arxiv GraphSAGE (4-way)").

SAGEConv: h_v = act(W_self x_v + W_nbr mean_{u->v} x_u). The neighbor mean is
a distributed gather (src side, halo exchange) + local segment mean on the
dst-owner side.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from dgraph_tpu.plan import EdgePlan


class SAGEConv(nn.Module):
    out_features: int
    comm: Any
    activation: Any = nn.relu
    dtype: Any = None  # None -> config.default_compute_dtype

    @nn.compact
    def __call__(self, x: jax.Array, plan: EdgePlan) -> jax.Array:
        from dgraph_tpu import config as _cfg

        from dgraph_tpu.comm.collectives import map_vertex_chunks

        dt = _cfg.resolve_compute_dtype(self.dtype)
        # cast BEFORE the edge pipeline: aggregating the raw f32 input
        # would run every [e_pad, F] take/scatter at double width (the
        # dtype-discipline rule — see tests/test_dtype_discipline.py)
        xa = x.astype(dt) if dt is not None else x
        if plan.halo_side != "dst" and self.comm.overlap_active(plan):
            # overlap route: the boundary rounds go out first; the interior
            # neighbor sum (reading only the local table) runs while they
            # fly; boundary contributions merge once landed. One exchange
            # per layer, chunk-local work exactly as below.
            halo_buf = self.comm.halo_exchange_overlap(xa, plan)
            agg = map_vertex_chunks(
                lambda xc, hb: self.comm.gather_scatter_overlap(xc, hb, plan),
                (xa, halo_buf),
            )
        elif plan.halo_side != "dst":
            # feature-chunked neighbor sum (models/gcn.py rationale): the
            # per-edge op here is IDENTITY, so chunking is exact for any
            # activation; one full-width halo exchange, local work in
            # <=col_block-wide slices run one after the other, concat only
            # at the vertex level
            x_ext = self.comm.halo_extend(xa, plan, side="src")
            agg = map_vertex_chunks(
                lambda xc: self.comm.scatter_sum(
                    self.comm.local_take(xc, plan, side="src"),
                    plan, side="dst",
                ),
                (x_ext,),
            )
        else:
            h_src = self.comm.gather(xa, plan, side="src")  # [e_pad, F]
            agg = self.comm.scatter_sum(h_src, plan, side="dst")  # [n_pad, F]
        ones = plan.edge_mask[:, None]
        deg = self.comm.scatter_sum(ones, plan, side="dst")  # [n_pad, 1]
        # divide in agg's dtype: a f32 degree would promote mean_nbr to a
        # full-width f32 vertex tensor
        mean_nbr = agg / jnp.maximum(deg, 1.0).astype(agg.dtype)
        out = nn.Dense(self.out_features, dtype=dt)(x) + nn.Dense(
            self.out_features, use_bias=False, dtype=dt
        )(mean_nbr)
        return self.activation(out)


class GraphSAGE(nn.Module):
    hidden_features: int
    out_features: int
    comm: Any
    num_layers: int = 2
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, plan: EdgePlan) -> jax.Array:
        from dgraph_tpu import config as _cfg

        for _ in range(self.num_layers):
            x = SAGEConv(self.hidden_features, comm=self.comm, dtype=self.dtype)(x, plan)
        head_dt = _cfg.resolve_compute_dtype(self.dtype)
        return nn.Dense(self.out_features, dtype=head_dt)(x).astype(jnp.float32)
