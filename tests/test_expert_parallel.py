"""One expert a rank of an 8-expert axis (``moe_apply``: top-1 switch routing
and top-2 mixtures, dropless) vs a dense single-device oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dgraph_tpu.parallel.expert import load_balance_loss, moe_apply

E = 8  # experts = devices
T, F = 64, 16  # tokens per shard, features


def _mesh():
    devs = jax.devices()
    if len(devs) < E:
        pytest.skip(f"need {E} devices")
    return Mesh(np.array(devs[:E]), ("expert",))


def _expert_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _params(rng):
    return [
        {
            "w": rng.standard_normal((F, F)).astype(np.float32) * 0.5,
            "b": rng.standard_normal(F).astype(np.float32) * 0.1,
        }
        for _ in range(E)
    ]


def _dense_oracle(x, logits, params_list):
    """Top-1, raw softmax gate: every token through its expert."""
    probs = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    expert = np.argmax(np.asarray(probs), axis=-1)
    gate = np.take_along_axis(np.asarray(probs), expert[:, None], 1)[:, 0]
    out = np.zeros_like(np.asarray(x))
    for t in range(len(x)):
        p = params_list[int(expert[t])]
        out[t] = gate[t] * np.tanh(np.asarray(x)[t] @ p["w"] + p["b"])
    return out


@pytest.mark.parametrize("load", ["spread", "one_expert"])
def test_moe_equals_dense_oracle(load):
    """``one_expert``: every token of every shard chooses expert 3, the load
    a capacity factor would drop most of; no token is dropped."""
    mesh = _mesh()
    rng = np.random.default_rng(0)
    params_list = _params(rng)
    stacked = jax.tree.map(
        lambda *xs: jnp.asarray(np.stack(xs)), *params_list
    )
    # identical tokens/logits on every shard (P() = replicated): each shard
    # routes the same T tokens, so the oracle is per-shard identical too
    x = jnp.asarray(rng.standard_normal((T, F)), jnp.float32)
    logits = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    if load == "one_expert":
        logits = logits.at[:, 3].add(10.0)

    fn = jax.shard_map(
        lambda p, x_, lg: moe_apply(
            x_, lg, _expert_fn, jax.tree.map(lambda l: l[0], p), "expert",
        ),
        mesh=mesh,
        in_specs=(P("expert"), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    got = fn(stacked, x, logits)
    want = _dense_oracle(x, logits, params_list)
    assert np.abs(want).min(axis=1).max() > 0  # no token left out
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_moe_gradients_flow_to_router_and_experts():
    mesh = _mesh()
    rng = np.random.default_rng(1)
    params_list = _params(rng)
    stacked = jax.tree.map(
        lambda *xs: jnp.asarray(np.stack(xs)), *params_list
    )
    x = jnp.asarray(rng.standard_normal((T, F)), jnp.float32)
    wr = jnp.asarray(rng.standard_normal((F, E)).astype(np.float32) * 0.3)

    def loss(stacked, wr, x):
        fn = jax.shard_map(
            lambda p, x_, wr_: moe_apply(
                x_, x_ @ wr_, _expert_fn, jax.tree.map(lambda l: l[0], p),
                "expert",
            ),
            mesh=mesh,
            in_specs=(P("expert"), P(), P()),
            out_specs=P(),
            check_vma=False,
        )
        return (fn(stacked, x, wr) ** 2).sum()

    gs, gr = jax.grad(loss, argnums=(0, 1))(stacked, wr, x)
    assert any(float(jnp.abs(l).sum()) > 0 for l in jax.tree.leaves(gs)), (
        "no gradient reached the experts"
    )
    assert float(jnp.abs(gr).sum()) > 0, "no gradient reached the router"


def test_load_balance_loss_range():
    mesh = _mesh()
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    fn = jax.shard_map(
        lambda lg: load_balance_loss(lg, "expert"),
        mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
    )
    val = float(fn(logits))
    # perfectly balanced -> 1.0; collapsed -> E. Random logits near 1.
    assert 0.9 < val < E


def _dense_topk_oracle(x, logits, params_list, k):
    """Dense oracle for top-k: per token, the gate-weighted
    sum of its top-k experts' outputs with gates renormalized over k."""
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    order = np.argsort(-probs, axis=-1)[:, :k]  # [T, k]
    out = np.zeros_like(np.asarray(x))
    for t in range(x.shape[0]):
        sel = order[t]
        g = probs[t, sel]
        g = g / g.sum()
        for c, e in enumerate(sel):
            p = params_list[e]
            out[t] += g[c] * np.asarray(
                _expert_fn({k2: jnp.asarray(v) for k2, v in p.items()},
                           jnp.asarray(x[t][None]))
            )[0]
    return out


def test_top2_matches_dense_oracle():
    mesh = _mesh()
    rng = np.random.default_rng(3)
    x = rng.standard_normal((E, T, F)).astype(np.float32)
    logits = rng.standard_normal((E, T, E)).astype(np.float32)
    params_list = _params(rng)
    stacked = jax.tree.map(
        lambda *xs: jnp.asarray(np.stack(xs)), *params_list
    )

    def body(x_, lg, ep):
        return moe_apply(
            x_, lg, _expert_fn, jax.tree.map(lambda l: l[0], ep),
            "expert", k=2,
        )

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("expert"), P("expert"), P("expert")),
        out_specs=P("expert"),
        check_vma=False,
    )
    with jax.set_mesh(mesh):
        got = np.asarray(fn(
            jnp.asarray(x.reshape(E * T, F)),
            jnp.asarray(logits.reshape(E * T, E)), stacked,
        ))
    for s in range(E):  # every shard against the dense oracle
        want = _dense_topk_oracle(x[s], logits[s], params_list, k=2)
        np.testing.assert_allclose(
            got[s * T:(s + 1) * T], want, rtol=2e-5, atol=2e-5,
            err_msg=f"shard {s}",
        )


def test_top2_keeps_every_route_under_pressure():
    """Two of the eight experts get every route of every shard (odd tokens
    choose expert 0 then 1, even tokens 1 then 0): 8 x the even load, which a
    capacity factor would cut to first choices or less. Every token still
    gets both of its experts, gates renormalised over the two."""
    mesh = _mesh()
    rng = np.random.default_rng(4)
    x = rng.standard_normal((E, T, F)).astype(np.float32)
    logits = np.zeros((E, T, E), np.float32)
    odd = (np.arange(T) % 2).astype(bool)
    logits[:, odd, 0], logits[:, odd, 1] = 4.0, 2.0   # odd: 1st->e0, 2nd->e1
    logits[:, ~odd, 1], logits[:, ~odd, 0] = 4.0, 2.0  # even: 1st->e1, 2nd->e0

    params_list = _params(rng)
    stacked = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *params_list)

    def body(x_, lg, ep):
        return moe_apply(
            x_, lg, _expert_fn, jax.tree.map(lambda l: l[0], ep),
            "expert", k=2,
        )

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("expert"), P("expert"), P("expert")),
        out_specs=P("expert"),
        check_vma=False,
    )
    with jax.set_mesh(mesh):
        got = np.asarray(fn(jnp.asarray(x.reshape(E * T, F)),
                            jnp.asarray(logits.reshape(E * T, E)), stacked))
    for s in range(E):
        want = _dense_topk_oracle(x[s], logits[s], params_list, k=2)
        np.testing.assert_allclose(
            got[s * T:(s + 1) * T], want, rtol=2e-5, atol=2e-5,
            err_msg=f"shard {s}")


def test_top2_router_gradients_flow():
    mesh = _mesh()
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((E * T, F)), jnp.float32)
    logits = jnp.asarray(rng.standard_normal((E * T, E)), jnp.float32)
    params_list = _params(rng)
    stacked = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *params_list)

    def loss(lg):
        def body(x_, lg_, ep):
            out = moe_apply(
                x_, lg_, _expert_fn, jax.tree.map(lambda l: l[0], ep),
                "expert", k=2,
            )
            return out

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("expert"), P("expert"), P("expert")),
            out_specs=P("expert"),
            check_vma=False,
        )
        return (fn(x, lg, stacked) ** 2).sum()

    with jax.set_mesh(mesh):
        g = jax.grad(loss)(logits)
    assert float(jnp.abs(g).sum()) > 0  # the router learns through gates
