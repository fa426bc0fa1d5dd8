"""Expert parallelism: a sparse-expert layer that is TOLD which experts it
holds (``n_held`` of ``n_total``, ids ``first_held ...``), routes over all of
them, drops no token, and computes its own experts' part of the result over
the rows routed to them (ROADMAP R9).

Beyond-reference (SURVEY.md §2.3 lists expert parallelism as absent in the
reference). The routes (``T * k`` of them, :func:`route_topk`) are ordered by
expert with two sorts (named scope ``routes``), the rows routed to held
experts are gathered into one buffer in expert order (``dispatch``), the experts run over the buffer, and each token sums
its gate-weighted rows back: row gathers in both directions of
differentiation, no scatter and no one-hot matrix. The router learns through
the gate product.

The router has two forms (:func:`route_topk`): ``p = softmax(W_r x)``, the k
largest, ``g_e = p_e / sum of the chosen`` (SDAR's); and ``s = sigmoid(W_r
x)``, the k largest of ``s + b`` with ``b`` a selection bias that enters the
choice and not the gate, ``g_e = scale * s_e / (sum of the chosen s + eps)``
(LFM2's). Dispatch, grouped products and combine do not know which.

Two layers stand on that (:func:`held_experts_apply`):

- :func:`held_experts_ffn`, the FFN of a sparse-expert model at published
  sizes in either of two forms (gated SiLU, three products an expert; ungated
  squared ReLU, two), the held experts' rows multiplied as groups
  (:func:`grouped_matmul`). With no mesh axis its partial sum is the chip's
  share of an expert-parallel deployment (what the absent experts would add
  is left out); with an axis the shares of the ranks are summed.
- :func:`moe_apply`, one expert of any form a rank of an axis (k = 1 switch
  routing, k >= 2 GShard/Mixtral mixtures): the ``n_held = 1`` case.

Over an axis every rank gathers all tokens and their routes, computes its
experts' part for all of them, and the parts are summed and scattered back
(``all_gather`` / ``psum_scatter``): exact whatever the imbalance, at the
price of every rank seeing every token. (The capacity-dropping
``all_to_all`` exchange this module began with is gone: a production
exchange is what is left of R9.)
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax


def load_balance_loss(router_logits: jax.Array, axis_name: str) -> jax.Array:
    """Switch-transformer auxiliary loss: E * Σ_e (frac_tokens_e ·
    mean_prob_e), psum-averaged over the axis. Add to the task loss to keep
    routing spread across experts."""
    E = lax.psum(1, axis_name)
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    frac = jnp.mean(
        jax.nn.one_hot(jnp.argmax(probs, -1), E, dtype=jnp.float32), axis=0
    )
    mean_p = probs.mean(axis=0)
    frac = lax.pmean(frac, axis_name)
    mean_p = lax.pmean(mean_p, axis_name)
    return E * jnp.sum(frac * mean_p)


def route_topk(router_logits: jax.Array, k: int, *, normalize: bool = True,
               score: str = "softmax", select_bias: Optional[jax.Array] = None,
               eps: Optional[float] = None, scale: float = 1.0):
    """(gates, experts), each ``[T, k]``, over ALL experts, in float32. Two
    forms of router:

    - ``score="softmax"`` (the default; GShard/Mixtral/Qwen3-MoE): the scores
      are ``softmax(router_logits)``, the ``k`` largest are chosen, and with
      ``normalize`` the chosen gates are divided by their sum over all ``k``
      chosen, wherever those experts live (``norm_topk_prob``); without, they
      stay the raw probabilities (the switch estimator).
    - ``score="sigmoid"`` (DeepSeek-V3's and LFM2's): the scores are
      ``s = sigmoid(router_logits)``, each expert's own. ``select_bias``
      ``[n_total]`` steers the CHOICE only: the ``k`` largest of ``s + b``
      are chosen and their gates are ``s`` (not ``s + b``), so the bias takes
      no gradient (it is a buffer that a balancing rule outside the loss
      would move). With ``normalize`` the gates are divided by their sum
      ``+ eps``; then times ``scale`` (``routed_scaling_factor``).

    ``eps=None`` guards the softmax form's division as before (a sum under
    1e-20 is held there); a given ``eps`` is ADDED to the sum, as the
    sigmoid form's published code does."""
    x = router_logits.astype(jnp.float32)
    if score == "softmax":
        scores = jax.nn.softmax(x, axis=-1)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(x)
    else:
        raise ValueError(f"unknown router score {score!r}")
    if select_bias is None:
        gates, experts = lax.top_k(scores, k)
    else:
        _, experts = lax.top_k(
            scores + lax.stop_gradient(select_bias.astype(jnp.float32)), k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    if normalize:
        total = gates.sum(-1, keepdims=True)
        gates = gates / (jnp.maximum(total, 1e-20) if eps is None
                         else total + eps)
    if scale != 1.0:
        gates = gates * scale
    return gates, experts.astype(jnp.int32)


class HeldRoutes(NamedTuple):
    """Where the routes to the held experts lie in the row buffer (rows
    ordered by expert, ``rows`` of them at most)."""

    token: jax.Array  # [rows] the token of each buffer row
    route: jax.Array  # [rows] its flat route index t * k + c
    pos: jax.Array  # [T, k] the buffer row of each route (clamped)
    valid: jax.Array  # [T, k] routed to a held expert, and inside the buffer
    live: jax.Array  # [rows] this buffer row holds such a route
    group_sizes: jax.Array  # [n_held] rows of each held expert, in order
    stats: jax.Array  # [5] int32: rows here, most of one expert, dropped,
    # rows the grouped products' tiles cover, rows in the buffer (HELD_STATS)


# What one layer counts. Over a step's layers the trainer sums them, except
# the two ``rows_max_*``, which are maxima: the most rows one expert got, and
# the most rows ONE layer put into its buffer (how near it came to ``rows``).
HELD_STATS = ("rows_here", "rows_max_expert", "rows_dropped", "rows_tiled",
              "rows_max_layer")
HELD_STATS_MAX = tuple(i for i, name in enumerate(HELD_STATS)
                       if name.startswith("rows_max_"))


def held_routes(experts: jax.Array, *, first_held: int, n_held: int,
                rows: int, tile_rows: int) -> HeldRoutes:
    """Order the ``T * k`` routes by expert and keep those to experts
    ``first_held .. first_held + n_held - 1``: two sorts of ``T * k`` keys
    (the order and its inverse), no scatter. ``rows`` bounds the buffer: a
    route past it is DROPPED and counted (``stats[2]``); ``rows = T * k`` can
    drop nothing."""
    T, k = experts.shape
    local = experts.reshape(T * k) - first_held
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    pos = jnp.argsort(order).astype(jnp.int32).reshape(T, k)
    sizes = (key[:, None] == jnp.arange(n_held, dtype=jnp.int32)).sum(
        0, dtype=jnp.int32)
    ends = jnp.minimum(jnp.cumsum(sizes), rows)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]])
    kept = ends - starts
    here = sizes.sum()
    tiles = jnp.where(kept > 0, (ends + tile_rows - 1) // tile_rows
                      - starts // tile_rows, 0)
    valid = (key.reshape(T, k) < n_held) & (pos < rows)
    return HeldRoutes(
        token=order[:rows] // k, route=order[:rows],
        pos=jnp.minimum(pos, rows - 1), valid=valid,
        live=jnp.arange(rows, dtype=jnp.int32) < ends[-1],
        group_sizes=kept,
        stats=jnp.stack([here, sizes.max(), here - ends[-1],
                         tiles.sum() * tile_rows, ends[-1]]).astype(jnp.int32))


def _sum_slots(rows: jax.Array, pos: jax.Array, weight: jax.Array,
               valid: jax.Array) -> jax.Array:
    """``out[t] = sum_c weight[t, c] * rows[pos[t, c]]`` over the valid
    slots, float32: ``k`` row gathers, no scatter."""
    out = 0.0
    for c in range(pos.shape[1]):
        got = rows[pos[:, c]].astype(jnp.float32) * weight[:, c, None]
        out = out + jnp.where(valid[:, c, None], got, 0.0)
    return out


@jax.custom_vjp
def _to_buffer(x, routes: HeldRoutes):
    """``x[token]``: each buffer row's token. Its transpose is
    :func:`_sum_slots` (a token's cotangent is the sum over its routes), so
    neither direction scatters."""
    return x[routes.token]


def _to_buffer_fwd(x, routes):
    return x[routes.token], routes


def _to_buffer_bwd(routes, g):  # g has x's dtype
    ones = jnp.ones(routes.pos.shape, jnp.float32)
    return _sum_slots(g, routes.pos, ones, routes.valid).astype(g.dtype), None


_to_buffer.defvjp(_to_buffer_fwd, _to_buffer_bwd)


@jax.custom_vjp
def _from_buffer(y, gates, routes: HeldRoutes):
    """``out[t] = sum over t's valid routes of gate * y[row of the route]``,
    float32. The gates' gradient is worked out in the buffer's order from
    the one gather the rows' gradient needs anyway."""
    return _sum_slots(y, routes.pos, gates, routes.valid)


def _from_buffer_fwd(y, gates, routes):
    return _from_buffer(y, gates, routes), (y, gates, routes)


def _from_buffer_bwd(res, g):
    y, gates, routes = res
    T, k = gates.shape
    # the cotangent in the rows' dtype before it is gathered: it is the
    # residual stream's, which has that dtype already, and a float32 buffer
    # of rows is twice the bytes
    g_rows = g.astype(y.dtype)[routes.token]  # [rows, d]
    gate_rows = gates.reshape(T * k)[routes.route]
    d_y = jnp.where(routes.live[:, None],
                    g_rows * gate_rows[:, None].astype(y.dtype), 0)
    d_gate_rows = jnp.where(
        routes.live, jnp.einsum("rd,rd->r", g_rows, y,
                                preferred_element_type=jnp.float32), 0.0)
    d_gates = jnp.where(routes.valid, d_gate_rows[routes.pos], 0.0)
    return d_y.astype(y.dtype), d_gates.astype(gates.dtype), None


_from_buffer.defvjp(_from_buffer_fwd, _from_buffer_bwd)

# Row tile of the grouped products: an expert's rows start where the last
# one's end, so a tile on a boundary is computed for both (PERF.md section 6,
# PR 32: 512 rows and columns of up to 1024 read fastest on the chip).
GROUPED_TILE_ROWS = 512


def grouped_tile_rows(m: int) -> int:
    """The row tile of a buffer of ``m`` rows: GROUPED_TILE_ROWS, a smaller
    buffer whole, and where that does not divide ``m`` (6 routes a token over
    a 128-token probe: 768 rows) their greatest common divisor: the kernel
    pads no tile."""
    tile = min(GROUPED_TILE_ROWS, m)
    return tile if m % tile == 0 else math.gcd(m, GROUPED_TILE_ROWS)


def _grouped_tiling(m: int, k: int, n: int):
    """Tiles of one grouped product: :func:`grouped_tile_rows` rows, and along
    each of the other two dimensions the largest listed tile that divides it
    (2048 -> 1024, 768 -> 768: no tile is padded), else the whole of it."""
    def fit(x):
        return next((t for t in (1024, 768, 512, 256, 128) if x % t == 0), x)

    return grouped_tile_rows(m), fit(k), fit(n)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, interpret: bool = False) -> jax.Array:
    """``lhs[rows of group e] @ rhs[e]`` for every group, rows of a group
    contiguous and in group order: ``[m, k] x [g, k, n] -> [m, n]`` in
    ``lhs``'s dtype with float32 accumulation. Rows past the groups' end are
    NOT written (callers mask them). On a TPU the Mosaic grouped matmul that
    ships with JAX (``megablox.gmm``: only the row tiles that hold a group's
    rows are computed, its backward is a grouped product and a transposed
    one); elsewhere ``lax.ragged_dot``, the only one of the two that runs
    off a TPU beside collectives (on the chip, at the SDAR cell's shape, it
    takes 10.27 ms forward + backward a layer against the kernel's 7.33:
    PERF.md section 6, PR 32). ``interpret`` is for the test that sets the
    kernel beside it on the CPU."""
    if jax.default_backend() == "tpu" or interpret:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype,
                            _grouped_tiling, None, None, False, interpret)
    return lax.ragged_dot(lhs, rhs, group_sizes,
                          preferred_element_type=lhs.dtype)


def buffer_rows(tokens: int, k: int, n_held: int,
                rows: Optional[int] = None) -> int:
    """Rows of one layer's buffer: ``rows``, and never more than the worst
    case ``tokens * min(k, n_held)`` (a token's ``k`` experts are distinct),
    which is also what None asks for."""
    most = tokens * min(k, n_held)
    return most if rows is None else min(rows, most)


def held_experts_apply(
    x: jax.Array,  # [T, d] this shard's tokens
    gates: jax.Array,  # [T, k] float32, of route_topk
    experts: jax.Array,  # [T, k] int32 expert ids in 0 .. n_total - 1
    rows_fn: Callable,  # (buffer [rows, d], HeldRoutes) -> [rows, d']
    *,
    n_held: int,
    first_held: int = 0,
    rows: Optional[int] = None,  # row buffer; None = the worst case
    axis_name: Optional[str] = None,
):
    """``out[t] = sum over t's chosen experts e that are held here of
    gate[t, e] * (expert e's row for x_t)``, float32 ``[T, d']``, and
    ``stats`` (``HELD_STATS``, int32). ``rows_fn`` computes the held experts'
    rows from the buffer of tokens routed to them (expert order,
    ``routes.group_sizes`` rows each; what it returns past ``routes.live`` is
    never read). The worst case is ``T * min(k, n_held)`` rows (a token's
    ``k`` experts are distinct); no token is dropped while the rows routed
    here fit ``rows``, and ``stats[2]`` counts those that did not.

    With ``axis_name`` (inside ``shard_map``, tokens sharded over the axis,
    rank r holding experts ``first_held + r * n_held ...``): every rank
    gathers all tokens and their routes, computes its experts' part for all
    of them, and the parts are summed and scattered back (``psum_scatter``):
    the whole layer, still dropless.
    """
    if axis_name is not None:
        first_held = first_held + lax.axis_index(axis_name) * n_held
        x, gates, experts = (lax.all_gather(a, axis_name, tiled=True)
                             for a in (x, gates, experts))
    T, k = experts.shape
    rows = buffer_rows(T, k, n_held, rows)
    with jax.named_scope("routes"):
        routes = held_routes(experts, first_held=first_held, n_held=n_held,
                             rows=rows, tile_rows=grouped_tile_rows(rows))
    with jax.named_scope("dispatch"):
        xs = _to_buffer(x, routes)
    with jax.named_scope("experts"):
        y = rows_fn(xs, routes)
    with jax.named_scope("combine"):
        out = _from_buffer(y, gates, routes)
        if axis_name is not None:
            out = lax.psum_scatter(out, axis_name, tiled=True)
    return out, routes.stats


# The forms of an expert (and of the shared expert beside them):
# W_down (silu(W_gate x) * W_up x), and W_down relu(W_up x)^2 with no gate.
EXPERT_FORMS = ("gated_silu", "relu2")


def held_experts_ffn(
    x: jax.Array,  # [T, d] this shard's tokens, compute dtype
    gates: jax.Array,
    experts: jax.Array,
    w_gate: Optional[jax.Array],  # [n_held, d, f]; None for form "relu2"
    w_up: jax.Array,  # [n_held, d, f] the held experts' weights
    w_down: jax.Array,  # [n_held, f, d]
    *,
    form: str = "gated_silu",
    first_held: int = 0,
    rows: Optional[int] = None,
    axis_name: Optional[str] = None,
):
    """The held experts' part of a sparse FFN over the buffer
    (:func:`held_experts_apply`, :func:`grouped_matmul`), expert e's row for
    a token being, by ``form``: ``"gated_silu"``, ``W_down,e (silu(W_gate,e
    x_t) * W_up,e x_t)``, three grouped products; ``"relu2"``, ``W_down,e
    relu(W_up,e x_t)^2``, two, and no ``w_gate``. The gradient reaches the
    router through ``gates``."""
    if form not in EXPERT_FORMS or (w_gate is None) != (form == "relu2"):
        raise ValueError(f"expert form {form!r} (of {EXPERT_FORMS}) with"
                         f"{'out' if w_gate is None else ''} a gate kernel")
    dt = x.dtype

    def ffn(xs, routes):
        # rows past the routed ones are not written by the products: zeroed
        # BEFORE the nonlinearity, so that nothing downstream or in the
        # backward pass ever multiplies what happens to lie there
        live = routes.live[:, None]

        def product(a, w):
            return jnp.where(live, grouped_matmul(
                a, w.astype(dt), routes.group_sizes), 0)

        if form == "relu2":
            return product(jnp.square(jax.nn.relu(product(xs, w_up))), w_down)
        g = product(xs, w_gate)
        u = product(xs, w_up)
        return product(jax.nn.silu(g) * u, w_down)

    return held_experts_apply(
        x, gates, experts, ffn, n_held=w_up.shape[0], first_held=first_held,
        rows=rows, axis_name=axis_name)


def moe_apply(
    x: jax.Array,  # [T, F] this shard's tokens
    router_logits: jax.Array,  # [T, E], E = the axis's size
    expert_fn: Callable,  # (params, [N, F]) -> [N, F'] THIS rank's expert
    expert_params,
    axis_name: str,
    *,
    k: int = 1,
    normalize_gates: Optional[bool] = None,
) -> jax.Array:
    """A mixture of one expert a rank of ``axis_name``, each of any form:
    every token's gate-weighted sum over its ``k`` chosen experts' outputs,
    in ``x``'s dtype, dropless (:func:`held_experts_apply` with
    ``n_held = 1``; the rank's expert runs densely over its buffer). k = 1
    keeps the raw-probability switch estimator; k > 1 renormalises the chosen
    gates (GShard/Mixtral) unless overridden."""
    E = lax.psum(1, axis_name)
    if router_logits.shape[-1] != E:  # both static under shard_map
        raise ValueError(
            f"router width {router_logits.shape[-1]} != expert-axis size "
            f"{E}: an expert id past the axis would be held by no rank")
    if not 1 <= k <= E:
        raise ValueError(f"top-k k={k} must be in [1, {E}]")
    gates, experts = route_topk(
        router_logits, k, normalize=k > 1 if normalize_gates is None
        else normalize_gates)
    out, _ = held_experts_apply(
        x, gates, experts, lambda xs, routes: expert_fn(expert_params, xs),
        n_held=1, axis_name=axis_name)
    return out.astype(x.dtype)
