"""Composability pin: sequence parallelism (ring attention over 'seq') and
expert parallelism (MoE over 'expert') in ONE shard_map body on a 2-D
(4 seq x 2 expert) mesh, vs the dense single-device oracle.

The parallel/ modules claim their helpers 'compose freely with the other
axes of a mesh'; this test is that claim, executed."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dgraph_tpu.parallel.expert import moe_apply
from dgraph_tpu.parallel.sequence import dense_attention, ring_attention

SEQ, EXP = 4, 2
T, H, D = 32, 2, 8  # T_loc = 8 per seq shard
F = H * D


def _mesh():
    devs = jax.devices()
    if len(devs) < SEQ * EXP:
        pytest.skip(f"need {SEQ * EXP} devices")
    return Mesh(np.array(devs[: SEQ * EXP]).reshape(SEQ, EXP), ("seq", "expert"))


def _expert_fn(p, x):
    return jnp.tanh(x @ p["w"])


def test_ring_attention_then_moe_on_2d_mesh():
    mesh = _mesh()
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((T, H, D)), jnp.float32)
        for _ in range(3)
    )
    wr = jnp.asarray(rng.standard_normal((F, EXP)).astype(np.float32))
    experts = [
        {"w": rng.standard_normal((F, F)).astype(np.float32) * 0.5}
        for _ in range(EXP)
    ]
    stacked = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *experts)

    def body(q, k, v, wr, ep):
        # sequence-parallel exact attention over 'seq' (each expert-column
        # of the mesh holds a replica of the sequence shards)
        a = ring_attention(q, k, v, "seq", causal=True)
        toks = a.reshape(-1, F)  # [T_loc, F]
        # expert-parallel MoE over 'expert' on the attention output
        out = moe_apply(
            toks, toks @ wr, _expert_fn, jax.tree.map(lambda l: l[0], ep),
            "expert",
        )
        return out.reshape(a.shape)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("seq"), P("seq"), P("seq"), P(), P("expert")),
        out_specs=P("seq"),
        check_vma=False,
    )
    got = fn(q, k, v, wr, stacked)

    # dense oracle
    a = dense_attention(q, k, v, causal=True)
    toks = np.asarray(a.reshape(-1, F))
    probs = np.asarray(jax.nn.softmax(jnp.asarray(toks) @ wr, axis=-1))
    eid = probs.argmax(-1)
    gate = np.take_along_axis(probs, eid[:, None], 1)[:, 0]
    want = np.stack([
        gate[t] * np.tanh(toks[t] @ experts[eid[t]]["w"]) for t in range(T)
    ]).reshape(np.asarray(a).shape)
    np.testing.assert_allclose(np.asarray(got), want, rtol=3e-5, atol=3e-5)
