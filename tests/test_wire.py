"""Wire codec layer (dgraph_tpu.wire): format registry + byte-pricing
pins, numpy/jax codec parity, the resolution ladder, and end-to-end
parity of compressed halo payloads across the
halo lowerings — fp32 identity bit-identical (forward AND backward),
bf16/fp8 within the pinned round-trip bounds on 2- and 4-shard graphs.

Compile budget (tests/README.md): the analysis-tier tests here are
compile-FREE (make_jaxpr / lower only); the execution tests reuse one
small graph per world size and pin several formats against the SAME
all_to_all baseline, so the whole file adds only tiny-shape compiles.
"""

import logging
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dgraph_tpu import config as cfg
from dgraph_tpu import plan as pl
from dgraph_tpu.comm import collectives
from dgraph_tpu.comm.mesh import make_graph_mesh
from dgraph_tpu.plan import shard_edge_data, shard_vertex_data, unshard_vertex_data
from dgraph_tpu.testing import (
    dense_gather,
    dense_scatter_sum,
    spmd_apply,
    unshard_edge_data,
)
from dgraph_tpu.wire import spec as WS
from dgraph_tpu.wire.spec import (
    FP8_SCALE_BYTES,
    WIRE_FORMAT_NAMES,
    WIRE_FORMATS,
    WireFormat,
    delta_skip_rows,
    fp8_available,
    get_format,
    np_decode,
    np_encode,
    np_encode_compensated,
    np_roundtrip_bound,
    resolve_wire_format,
)

requires_fp8 = pytest.mark.skipif(
    not fp8_available(), reason="float8_e4m3fn dtype unavailable"
)

# Global relative-error pins for one wire trip through a REAL lowering
# (metric: max |got - want| / max |want|). Looser than the per-row
# np_roundtrip_bound because the dense oracle compares across rows with
# different maxima; a broken codec (wrong scale, dropped lanes) misses
# these by orders of magnitude.
FWD_BOUND = {"bf16": 8e-3, "fp8": 9e-2}
GRAD_BOUND = {"bf16": 5e-2, "fp8": 3.5e-1}


@pytest.fixture
def wire_flags():
    """Save/restore every flag the wire + halo ladders read."""
    saved = (cfg.wire_format, cfg.tuned_wire_format, cfg.halo_impl,
             cfg.tuned_halo_impl)
    yield
    cfg.set_flags(wire_format=saved[0], tuned_wire_format=saved[1],
                  halo_impl=saved[2], tuned_halo_impl=saved[3])


def _graph(rng, W, V=96, E=600):
    edges = rng.integers(0, V, size=(2, E))
    part = np.sort(rng.integers(0, W, V)).astype(np.int32)
    return edges, part


def _rel_err(got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    denom = max(float(np.max(np.abs(want))), 1e-12)
    return float(np.max(np.abs(got - want))) / denom


# ---------------------------------------------------------------------------
# registry + pricing pins (pure — what footprint/tuner/trace/HLO all price)
# ---------------------------------------------------------------------------


def test_registry_byte_pins():
    F, b = 128, 4  # f32 activations
    assert WIRE_FORMAT_NAMES == ("fp32", "bf16", "fp8"), (
        "registry order is the lossless-first tuner tie-break — "
        "reordering silently changes what ties adopt"
    )
    rows = {n: get_format(n).wire_row_bytes(F, b) for n in WIRE_FORMAT_NAMES}
    assert rows == {"fp32": 512, "bf16": 256, "fp8": 132}
    assert get_format("fp8").wire_feat_dim(F) == F + FP8_SCALE_BYTES
    assert get_format("fp32").compression_ratio(F, b) == 1.0
    assert get_format("bf16").compression_ratio(F, b) == 2.0
    assert get_format("fp8").compression_ratio(F, b) == 512 / 132


def test_format_serialization_roundtrip():
    for name in WIRE_FORMAT_NAMES:
        fmt = get_format(name)
        back = WireFormat.from_dict(fmt.to_dict())
        assert back == fmt
        assert back.format_id == fmt.format_id
    with pytest.raises(ValueError, match="unknown wire format"):
        get_format("int4")


# ---------------------------------------------------------------------------
# numpy reference codecs: round-trip bounds + error compensation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", WIRE_FORMAT_NAMES)
def test_np_roundtrip_within_pinned_bound(rng, name):
    if name == "fp8" and not fp8_available():
        pytest.skip("float8_e4m3fn dtype unavailable")
    x = rng.normal(size=(16, 8)).astype(np.float32)
    x[3] *= 1e-4   # tiny rows exercise the per-row fp8 scale
    x[5] *= 1e4
    x[7] = 0.0     # all-zero rows must decode to exactly 0.0
    y = np_encode(x, name)
    back = np_decode(y, name, np.float32)
    bound = np_roundtrip_bound(name)
    if name == "fp32":
        assert (back == x).all()
        return
    row_max = np.max(np.abs(x), axis=-1, keepdims=True)
    err = np.abs(back - x) / np.maximum(row_max, 1e-30)
    assert float(err.max()) <= bound, (name, float(err.max()))
    assert (back[7] == 0.0).all(), "all-zero row must decode to exact zeros"


@requires_fp8
def test_np_wrong_scale_blows_the_bound(rng):
    """Vacuity: a decode that disagrees with its encode scale must be
    caught by the same bound the parity tests pin."""
    x = rng.normal(size=(8, 8)).astype(np.float32)
    y = np_encode(x, "fp8", _scale_gain=2.0)
    back = np_decode(y, "fp8", np.float32)
    row_max = np.max(np.abs(x), axis=-1, keepdims=True)
    err = np.abs(back - x) / np.maximum(row_max, 1e-30)
    assert float(err.max()) > np_roundtrip_bound("fp8")


@requires_fp8
def test_compensated_accumulation_drift_bound(rng):
    """Error feedback: the receiver's T-step accumulation of decoded
    payloads telescopes to (fp32 sum - final residual), so its relative
    drift stays within ONE round-trip bound instead of growing with T."""
    T, F = 64, 8
    steps = rng.uniform(0.5, 1.5, size=(T, 4, F)).astype(np.float32)
    acc_fp32 = steps.sum(axis=0)
    resid = None
    acc_comp = np.zeros((4, F), np.float32)
    acc_plain = np.zeros((4, F), np.float32)
    for x in steps:
        y, resid = np_encode_compensated(x, resid, "fp8")
        acc_comp += np_decode(y, "fp8", np.float32)
        acc_plain += np_decode(np_encode(x, "fp8"), "fp8", np.float32)
    err_comp = _rel_err(acc_comp, acc_fp32)
    err_plain = _rel_err(acc_plain, acc_fp32)
    assert err_comp <= np_roundtrip_bound("fp8"), err_comp
    # uncompensated rounding of all-positive steps drifts with T
    assert err_comp < err_plain, (err_comp, err_plain)


# ---------------------------------------------------------------------------
# jax codecs vs the numpy ground truth (eager, tiny ops)
# ---------------------------------------------------------------------------


def test_jax_transform_identity_cases():
    from dgraph_tpu.wire.codec import make_wire_transform

    assert make_wire_transform("fp32", "float32") == (None, None)
    # activations already riding the wire dtype: casts would be noise
    assert make_wire_transform("bf16", "bfloat16") == (None, None)


def test_jax_bf16_matches_numpy(rng):
    from dgraph_tpu.wire.codec import make_wire_transform

    enc, dec = make_wire_transform("bf16", "float32")
    x = rng.normal(size=(6, 8)).astype(np.float32)
    y_j = np.asarray(enc(jnp.asarray(x)))
    y_np = np_encode(x, "bf16")
    assert y_j.dtype == y_np.dtype and (
        y_j.view(np.uint8) == y_np.view(np.uint8)
    ).all()
    back = np.asarray(dec(jnp.asarray(y_np)))
    assert (back == np_decode(y_np, "bf16", np.float32)).all()


@requires_fp8
def test_jax_fp8_matches_numpy(rng):
    from dgraph_tpu.wire.codec import make_wire_transform

    enc, dec = make_wire_transform("fp8", "float32")
    x = rng.normal(size=(6, 8)).astype(np.float32)
    x[2] = 0.0
    y_j = np.asarray(enc(jnp.asarray(x)))
    y_np = np_encode(x, "fp8")
    assert y_j.shape == y_np.shape == (6, 8 + FP8_SCALE_BYTES)
    assert (y_j == y_np).all(), "fp8 packing must match the reference bit for bit"
    back = np.asarray(dec(jnp.asarray(y_np)))
    assert (back == np_decode(y_np, "fp8", np.float32)).all()


def test_bf16_codec_cotangent_rides_the_wire_encoded(rng):
    """The custom-VJP pair: encode's bwd DECODES the cotangent (and
    vice versa) — AD never differentiates through the cast, and the
    cotangent crosses the wire in the same format as the forward."""
    from dgraph_tpu.wire.codec import make_wire_codec

    encode, decode = make_wire_codec("bf16", "float32")
    x = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    g_wire = jnp.asarray(rng.normal(size=(4, 8))).astype(jnp.bfloat16)
    _, vjp = jax.vjp(encode, x)
    (ct,) = vjp(g_wire)
    want = np_decode(np.asarray(g_wire), "bf16", np.float32)
    assert (np.asarray(ct) == want).all()
    g_act = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    _, vjp = jax.vjp(decode, encode(x))
    (ct,) = vjp(g_act)
    assert (np.asarray(ct).view(np.uint8)
            == np_encode(np.asarray(g_act), "bf16").view(np.uint8)).all()


# ---------------------------------------------------------------------------
# resolution ladder (pure)
# ---------------------------------------------------------------------------


DELTAS = (1, 2)  # any non-empty cross-rank traffic


def test_resolver_env_beats_record_beats_plan(wire_flags):
    cfg.set_flags(wire_format="bf16", tuned_wire_format="fp32")
    assert resolve_wire_format(4, DELTAS, plan_format="fp32") == ("bf16", "env")
    cfg.set_flags(wire_format="auto", tuned_wire_format="bf16")
    assert resolve_wire_format(4, DELTAS, plan_format="fp32") == (
        "bf16", "record"
    )
    cfg.set_flags(wire_format="auto", tuned_wire_format=None)
    assert resolve_wire_format(4, DELTAS, plan_format="bf16") == (
        "bf16", "plan"
    )


def test_resolver_default_rows(wire_flags):
    cfg.set_flags(wire_format="auto", tuned_wire_format=None)
    # the attached fp32 default is not an adoption: source says 'default'
    assert resolve_wire_format(4, DELTAS, plan_format="fp32") == (
        "fp32", "default"
    )
    # no cross-rank traffic: there is no wire to encode
    assert resolve_wire_format(1, ()) == ("fp32", "plan")


def test_resolver_degrades_with_one_warning(wire_flags, caplog):
    cfg.set_flags(wire_format="int4", tuned_wire_format=None)
    WS._degrade_warned.clear()
    with caplog.at_level(logging.WARNING, logger="dgraph_tpu.wire"):
        assert resolve_wire_format(4, DELTAS, plan_format="bf16") == (
            "bf16", "plan"
        )
        n_first = len(caplog.records)
        assert n_first == 1, "unknown env pin must warn exactly once"
        assert resolve_wire_format(4, DELTAS, plan_format="bf16") == (
            "bf16", "plan"
        )
        assert len(caplog.records) == n_first, "repeat resolution re-warned"
    # fp8 without the e4m3 dtype degrades the same way
    cfg.set_flags(wire_format="fp8")
    WS._degrade_warned.clear()
    assert resolve_wire_format(4, DELTAS, plan_format="fp32", fp8_ok=False) == (
        "fp32", "default"
    )


def test_plan_attaches_buildtime_resolution(rng, wire_flags):
    edges, part = _graph(rng, 4)
    cfg.set_flags(wire_format="auto", tuned_wire_format=None)
    plan, _ = pl.build_edge_plan(edges, part, world_size=4)
    assert plan.wire_format == "fp32"
    cfg.set_flags(wire_format="bf16")
    plan_b, _ = pl.build_edge_plan(edges, part, world_size=4)
    assert plan_b.wire_format == "bf16"
    # a cache round-trip keeps the adopted codec even when the loading
    # process has no env pin / record (rank-identical statics)
    cfg.set_flags(wire_format="auto")
    assert resolve_wire_format(
        4, tuple(plan_b.halo_deltas), plan_format=plan_b.wire_format
    ) == ("bf16", "plan")


def test_sharded_plan_roundtrip_keeps_wire_format(rng, tmp_path, wire_flags):
    from dgraph_tpu.plan import build_plan_shards, load_sharded_plan

    edges, part = _graph(rng, 4)
    cfg.set_flags(wire_format="bf16", tuned_wire_format=None)
    build_plan_shards(
        edges, part, out_dir=str(tmp_path), world_size=4, write_layout=False
    )
    cfg.set_flags(wire_format="auto")
    sub, _ = load_sharded_plan(str(tmp_path), ranks=[0], load_layout=False)
    assert sub.wire_format == "bf16"


def test_serve_health_wire_provenance(rng, wire_flags):
    from dgraph_tpu.serve.health import _wire_provenance

    assert _wire_provenance(None) is None
    edges, part = _graph(rng, 4)
    cfg.set_flags(wire_format="auto", tuned_wire_format=None)
    plan, _ = pl.build_edge_plan(edges, part, world_size=4)
    assert _wire_provenance(plan) == {"format": "fp32", "source": "default"}
    cfg.set_flags(tuned_wire_format="bf16")
    assert _wire_provenance(plan) == {"format": "bf16", "source": "record"}


# ---------------------------------------------------------------------------
# footprint pricing: the acceptance cut (pure)
# ---------------------------------------------------------------------------


def test_footprint_bf16_cuts_wire_bytes_at_least_45pct(rng, wire_flags):
    """The ISSUE's acceptance pin on an arxiv-shaped workload (sparse
    power-law-ish graph, F=128 f32 activations): pricing the halo
    exchange at bf16 must cut wire bytes >= 45% vs fp32 — and the priced
    rows must be exactly the registry's wire_row_bytes."""
    from dgraph_tpu.obs.footprint import plan_footprint

    W, F = 4, 128
    edges, part = _graph(rng, W, V=400, E=2800)
    cfg.set_flags(wire_format="auto", tuned_wire_format=None)
    plan, _ = pl.build_edge_plan(edges, part, world_size=W)

    def exchange_at(fmt):
        cfg.set_flags(wire_format=fmt)
        return plan_footprint(plan, "float32", feat_dim=F)[
            "collectives"]["halo_exchange"]

    ex = {f: exchange_at(f) for f in ("fp32", "bf16", "fp8")
          if f != "fp8" or fp8_available()}
    for name, rep in ex.items():
        assert rep["wire_format"] == name
        assert rep["wire_row_bytes"] == get_format(name).wire_row_bytes(F, 4)
        assert rep["compression_ratio"] == round(
            get_format(name).compression_ratio(F, 4), 4
        )
    base = ex["fp32"]["ici_bytes_total"]
    assert base > 0
    for name, rep in ex.items():
        if name == "fp32":
            continue
        cut = 1.0 - rep["ici_bytes_total"] / base
        assert cut >= 0.45, (name, cut)
        # byte-EXACT scaling: same rows, re-priced per row
        rows = base // ex["fp32"]["wire_row_bytes"]
        assert rep["ici_bytes_total"] == rows * rep["wire_row_bytes"]


def test_delta_skip_accounting_matches_plan(rng):
    edges, part = _graph(rng, 4)
    plan, _ = pl.build_edge_plan(edges, part, world_size=4)
    acc = delta_skip_rows(
        plan.halo_pair_rows, plan.world_size, plan.halo.s_pad
    )
    assert acc["num_halo_deltas"] == len(plan.halo_deltas)
    assert acc["live_rows_max_shard"] <= acc["a2a_rows_per_shard"]
    assert acc["ppermute_rows_per_shard"] == (
        len(plan.halo_deltas) * plan.halo.s_pad
    )


# ---------------------------------------------------------------------------
# analysis tiers under pinned formats (compile-free: trace + lower only)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def audit_workload_f32():
    # f32 compute so the bf16/fp8 codecs actually engage (the audit
    # workload's default bf16 compute makes bf16 the identity format)
    from dgraph_tpu.analysis.trace import build_audit_workload

    return build_audit_workload(2, compute_dtype="float32")


@pytest.mark.parametrize("fmt", ["bf16", "fp8"])
def test_trace_audit_green_under_pinned_format(
    audit_workload_f32, wire_flags, fmt
):
    """Every (program, lowering) pair still passes the byte-exact trace
    audit with a compressed wire: traced operand bytes == footprint-
    priced bytes at the ENCODED width (fp8's F+4 scale lanes included)."""
    if fmt == "fp8" and not fp8_available():
        pytest.skip("float8_e4m3fn dtype unavailable")
    from dgraph_tpu.analysis.trace import audit_workload

    cfg.set_flags(wire_format=fmt, tuned_wire_format=None)
    rep = audit_workload(audit_workload_f32)
    assert rep["ok"], rep["failures"]
    ops = [op for p in rep["programs"] for op in p["collective_operands"]]
    assert ops
    for op in ops:
        assert op["traced_bytes"] == op["footprint_bytes"]


def test_hlo_audit_green_under_bf16(audit_workload_f32, wire_flags):
    """The LOWERED modules agree too: StableHLO collective operands are
    byte-exact against the bf16-priced footprint (the wire cast must
    survive XLA lowering, not just tracing)."""
    from dgraph_tpu.analysis import hlo as H

    cfg.set_flags(wire_format="bf16", tuned_wire_format=None)
    rep = H.audit_workload_hlo(audit_workload_f32)
    assert rep["ok"], rep["failures"]
    rows = 0
    for p in rep["programs"]:
        for op in p["collective_operands"]:
            assert op["bytes"] == op["footprint_bytes"] > 0, (p["impl"], op)
            rows += 1
    assert rows > 0


def test_fp32_identity_jaxpr_is_unchanged(rng, wire_flags):
    """The structural identity guarantee: pinning wire_format='fp32'
    traces the EXACT jaxpr the default path traces, forward and grad —
    the codec layer adds nothing (so bit-identity is by construction,
    not by luck)."""
    W, F = 4, 6
    edges, part = _graph(rng, W)
    cfg.set_flags(wire_format="auto", tuned_wire_format=None,
                  halo_impl="all_to_all")
    plan, _ = pl.build_edge_plan(edges, part, world_size=W)
    mesh = make_graph_mesh(ranks_per_graph=W, num_replicas=8 // W)
    xs = jnp.zeros((W, plan.n_src_pad, F), jnp.float32)
    ct = jnp.zeros((W, plan.e_pad, F), jnp.float32)

    def fwd(p, x):
        return spmd_apply(mesh, collectives.gather, p, x,
                          static_args=("src", "graph"))

    def loss(p, x):
        return jnp.sum(fwd(p, x) * ct)

    def jaxprs():
        # custom-vjp params print their bwd closures' memory addresses;
        # strip them so the comparison is structural
        return tuple(
            re.sub(r" at 0x[0-9a-f]+", "", s) for s in (
                str(jax.make_jaxpr(fwd)(plan, xs)),
                str(jax.make_jaxpr(jax.grad(loss, argnums=1))(plan, xs)),
            )
        )

    auto = jaxprs()
    cfg.set_flags(wire_format="fp32")
    assert jaxprs() == auto
    # and the lossy format is NOT a no-op on the same program
    cfg.set_flags(wire_format="bf16")
    assert jaxprs() != auto


# ---------------------------------------------------------------------------
# execution parity across lowerings (the file's only compiles)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=[2, 4])
def wire_case(request):
    rng = np.random.default_rng(7)
    W = request.param
    V, E = (48, 300) if W == 2 else (96, 600)
    edges, part = _graph(rng, W, V, E)
    plan, layout = pl.build_edge_plan(
        edges, part, world_size=W, overlap=True
    )
    mesh = make_graph_mesh(ranks_per_graph=W, num_replicas=8 // W)
    return W, edges, part, plan, layout, mesh


def _gather_once(mesh, plan, xs, *, fmt, impl):
    cfg.set_flags(wire_format=fmt, tuned_wire_format=None, halo_impl=impl)
    return np.asarray(spmd_apply(
        mesh, collectives.gather, plan, xs, static_args=("src", "graph")
    ))


def test_fp32_identity_execution_bitwise(wire_case, wire_flags):
    W, edges, part, plan, layout, mesh = wire_case
    if W != 4:
        pytest.skip("one world size is enough for the executed identity pin")
    rng = np.random.default_rng(11)
    x = rng.normal(size=(len(part), 6)).astype(np.float32)
    xs = jnp.asarray(shard_vertex_data(x, layout.src_counts, plan.n_src_pad))
    base = _gather_once(mesh, plan, xs, fmt="auto", impl="all_to_all")
    got = _gather_once(mesh, plan, xs, fmt="fp32", impl="all_to_all")
    assert (got == base).all(), "fp32 identity drifted from the default path"
    np.testing.assert_allclose(
        unshard_edge_data(got, layout), dense_gather(x, edges, "src"),
        rtol=1e-6,
    )


@pytest.mark.parametrize("fmt", ["bf16", "fp8"])
def test_lossy_gather_forward_within_bound(wire_case, wire_flags, fmt):
    if fmt == "fp8" and not fp8_available():
        pytest.skip("float8_e4m3fn dtype unavailable")
    W, edges, part, plan, layout, mesh = wire_case
    rng = np.random.default_rng(13)
    x = rng.normal(size=(len(part), 6)).astype(np.float32)
    xs = jnp.asarray(shard_vertex_data(x, layout.src_counts, plan.n_src_pad))
    got = _gather_once(mesh, plan, xs, fmt=fmt, impl="all_to_all")
    err = _rel_err(unshard_edge_data(got, layout),
                   dense_gather(x, edges, "src"))
    assert err <= FWD_BOUND[fmt], (W, fmt, err)


def test_bf16_forward_parity_across_lowerings(wire_case, wire_flags):
    """Every lowering quantizes the SAME per-row payloads: transports
    may differ in routing, never in codec arithmetic."""
    W, edges, part, plan, layout, mesh = wire_case
    if W != 4:
        pytest.skip("cross-lowering sweep runs once, on the 4-shard ring")
    rng = np.random.default_rng(17)
    x = rng.normal(size=(len(part), 6)).astype(np.float32)
    xs = jnp.asarray(shard_vertex_data(x, layout.src_counts, plan.n_src_pad))
    out = {impl: _gather_once(mesh, plan, xs, fmt="bf16", impl=impl)
           for impl in pl.HALO_IMPLS}
    base = out["all_to_all"]
    assert (out["overlap"] == base).all(), "overlap differs from all_to_all"
    np.testing.assert_allclose(out["ppermute"], base, rtol=1e-6, atol=1e-6)
    err = _rel_err(unshard_edge_data(base, layout),
                   dense_gather(x, edges, "src"))
    assert err <= FWD_BOUND["bf16"], err


@requires_fp8
def test_fp8_forward_parity_overlap_vs_a2a(wire_case, wire_flags):
    W, edges, part, plan, layout, mesh = wire_case
    if W != 2:
        pytest.skip("the fp8 cross-lowering pin runs once, on 2 shards")
    rng = np.random.default_rng(19)
    x = rng.normal(size=(len(part), 6)).astype(np.float32)
    xs = jnp.asarray(shard_vertex_data(x, layout.src_counts, plan.n_src_pad))
    a2a = _gather_once(mesh, plan, xs, fmt="fp8", impl="all_to_all")
    got = _gather_once(mesh, plan, xs, fmt="fp8", impl="overlap")
    assert (got == a2a).all(), "overlap fp8 payload differs from all_to_all"


def _gather_grad_once(mesh, plan, xs, ct_sh, *, fmt, impl):
    cfg.set_flags(wire_format=fmt, tuned_wire_format=None, halo_impl=impl)

    def loss_fn(xs_):
        out = spmd_apply(mesh, collectives.gather, plan, xs_,
                         static_args=("src", "graph"))
        return jnp.sum(out * ct_sh)

    with jax.set_mesh(mesh):
        return np.asarray(jax.jit(jax.grad(loss_fn))(xs))


@pytest.mark.parametrize("fmt", ["bf16", "fp8"])
def test_lossy_gather_grad_within_bound(wire_case, wire_flags, fmt):
    """Backward: the cotangent rides the reverse wire ENCODED (the
    custom-VJP trips / hand-built reverse legs), so the sharded gradient
    tracks the dense transpose within the format's bound."""
    if fmt == "fp8" and not fp8_available():
        pytest.skip("float8_e4m3fn dtype unavailable")
    W, edges, part, plan, layout, mesh = wire_case
    if W != 2:
        pytest.skip("grad parity runs once, on 2 shards")
    rng = np.random.default_rng(23)
    V, F = len(part), 3
    x = rng.normal(size=(V, F)).astype(np.float32)
    xs = jnp.asarray(shard_vertex_data(x, layout.src_counts, plan.n_src_pad))
    ct = rng.normal(size=(edges.shape[1], F)).astype(np.float32)
    ct_sh = jnp.asarray(shard_edge_data(ct, layout, plan.e_pad))
    grad = _gather_grad_once(mesh, plan, xs, ct_sh, fmt=fmt, impl="all_to_all")
    err = _rel_err(unshard_vertex_data(grad, layout.src_counts),
                   dense_scatter_sum(ct, edges, "src", V))
    assert err <= GRAD_BOUND[fmt], (fmt, err)


def test_bf16_grad_parity_across_lowerings(wire_case, wire_flags):
    W, edges, part, plan, layout, mesh = wire_case
    if W != 2:
        pytest.skip("grad cross-lowering pin runs once, on 2 shards")
    rng = np.random.default_rng(29)
    V, F = len(part), 3
    x = rng.normal(size=(V, F)).astype(np.float32)
    xs = jnp.asarray(shard_vertex_data(x, layout.src_counts, plan.n_src_pad))
    ct = rng.normal(size=(edges.shape[1], F)).astype(np.float32)
    ct_sh = jnp.asarray(shard_edge_data(ct, layout, plan.e_pad))
    grads = {impl: _gather_grad_once(mesh, plan, xs, ct_sh,
                                     fmt="bf16", impl=impl)
             for impl in ("all_to_all", "overlap")}
    assert (grads["overlap"] == grads["all_to_all"]).all(), (
        "overlap bf16 backward differs from all_to_all"
    )


def test_config_flip_cannot_recompile_a_served_program(
    wire_case, wire_flags
):
    """The serve discipline: the format is resolved ONCE at trace time
    and baked into the executable as a static — flipping the env pin
    under a live jitted program changes NOTHING (no retrace, no
    recompile, bit-identical outputs). Re-resolution (a new engine /
    bench round) is the only way to change wire."""
    W, edges, part, plan, layout, mesh = wire_case
    if W != 2:
        pytest.skip("zero-recompile pin runs once, on 2 shards")
    rng = np.random.default_rng(31)
    x = rng.normal(size=(len(part), 4)).astype(np.float32)
    xs = jnp.asarray(shard_vertex_data(x, layout.src_counts, plan.n_src_pad))
    cfg.set_flags(wire_format="bf16", tuned_wire_format=None,
                  halo_impl="all_to_all")
    f = jax.jit(lambda p, x_: spmd_apply(
        mesh, collectives.gather, p, x_, static_args=("src", "graph")
    ))
    with jax.set_mesh(mesh):
        first = np.asarray(f(plan, xs))
        cfg.set_flags(wire_format="fp32")
        second = np.asarray(f(plan, xs))
    assert (first == second).all(), (
        "a config flip leaked into a compiled executable"
    )
    cache_size = getattr(f, "_cache_size", None)
    if cache_size is not None:
        assert cache_size() == 1, "config flip forced a retrace"
