"""``python -m dgraph_tpu.analysis`` — static-analysis CLI: contract
linter + trace auditor + lowered-artifact (StableHLO) auditor +
cross-rank SPMD divergence auditor + host-side concurrency & durability
auditor.

The host tier (``analysis.host``, ISSUE 15) audits the *other* program —
the jax-free concurrent control plane: per-class guarded-field/lock
discipline (races), the inter-class lock-acquisition-order graph
(deadlocks), atomic-writer routing for durable artifacts and the
pointer-flip-last commit contract (torn writes), and chaos-registry
coverage drift.  Its per-file rules run inside the lint pass (one
registry, one pragma); the repo-level graphs land in the report's
``host_audit`` section.

Default mode lints the whole ``dgraph_tpu`` tree and audits the canonical
2-shard workload under every halo lowering at ALL verification tiers —
the jaxpr-level trace audit, the post-lowering HLO audit, and the
cross-rank SPMD audit (every
rank's program lowered from its own plan-shard-subset view and proven
identical, in identical collective order) — printing one JSON line and
exiting nonzero on any finding or drift; the pre-merge gate
``scripts/check.py`` wraps it.

``--selftest`` is the compile-free tier-1 registration: lint-rule fixture
checks (every rule must fire on a violating snippet and stay quiet on a
clean one), a clean-tree lint, the 2- AND 4-shard trace AND HLO audits
across all four halo lowerings (op counts + operand bytes pinned against
``obs.footprint`` at both tiers), the cross-rank SPMD
audits (2- and 4-shard worlds plus both generations of a real
``train/shrink.py`` W -> W-1 transition), and vacuity guards proving each
tier still FAILS on seeded drift: a wrong lowering, wrong bytes, a mixed
program, a seeded extra all-gather, a dropped donation (declare- and
shape-level), a raw ``shard_map`` check kwarg, and the seeded SPMD
divergences (a rank-dependent branch dropping one ppermute round on rank
1, a swapped two-collective order, a rank-divergent tuned record).  Zero
XLA compiles: the jaxpr tier traces abstractly and the HLO/SPMD tiers
are lower-only (``jit(...).lower()``; jit-cache counters asserted — the
rule ``tests/README.md`` documents).

``--bench_fallback`` prints the compact ``schedule_drift`` record bench.py
attaches to its JSON when no healthy chip ever comes up (ROADMAP item 5's
non-null fallback tier); ``--fallback_kind hlo_drift`` /
``--fallback_kind spmd_drift`` select the lowered-artifact and cross-rank
drift records instead (bench attaches all of them).

``--list_rules`` prints the lint-rule registry (name, scope, description)
— the machine-readable source the rule-catalog table in
``docs/static-analysis.md`` is pinned against.

Every exit path carries a RunHealth record; reports stream to the JSONL
log (``--log_path``) via ExperimentLog.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import tempfile

# The audit traces multi-shard shard_map programs, which needs a multi-
# device (virtual CPU) backend.  jax is already IMPORTED here (the
# package __init__ pulls it in) and reads JAX_PLATFORMS at import time,
# so the env pin below only reaches subprocesses — the jax.config.update
# is what redirects THIS process.  Analysis is a host-side static pass:
# it must never claim an accelerator, so the pin is unconditional.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


@dataclasses.dataclass
class Config:
    """Static analysis (``--selftest`` for the compile-free tier-1 smoke;
    ``--bench_fallback`` for the bench's fallback records —
    ``--fallback_kind hlo_drift`` selects the lowered-artifact tier)."""

    selftest: bool = False
    bench_fallback: bool = False
    fallback_kind: str = "schedule_drift"  # or "hlo_drift" / "spmd_drift"
    list_rules: bool = False  # print the lint-rule registry and exit
    lint: bool = True
    audit: bool = True
    hlo: bool = True     # lowered-artifact (StableHLO) tier
    spmd: bool = True    # cross-rank SPMD divergence tier
    host: bool = True    # host-side concurrency & durability tier
    root: str = ""  # lint root; "" = the repo containing this package
    world: int = 2  # audit world size (default mode)
    # bench-fallback workload shape (a reduced arxiv-like graph: the
    # drift signal is structural — op counts and byte ratios — so it does
    # not need the full 169k-node build on a wedged round's clock)
    nodes: int = 4096
    edges: int = 16384
    feat_dim: int = 32
    seed: int = 0
    log_path: str = "logs/analysis.jsonl"
    indent: int = 0


# ---------------------------------------------------------------------------
# lint-rule fixtures: every rule must fire on `bad` and not on `good`
# ---------------------------------------------------------------------------

_FIXTURES = {
    "jax-free-module": {
        "path": "dgraph_tpu/chaos/__init__.py",
        "bad": "def poison(tree):\n    import jax\n    return jax.tree.map(id, tree)\n",
        "good": "import os\n\ndef poison(tree):\n    return tree\n",
    },
    "no-config-read-in-trace": {
        "path": "dgraph_tpu/comm/collectives.py",
        "bad": (
            "from dgraph_tpu import config as _cfg\n"
            "import jax\n"
            "def step(x):\n"
            "    def body(y):\n"
            "        return y if _cfg.halo_impl == 'auto' else -y\n"
            "    return jax.jit(body)(x)\n"
        ),
        "good": (
            "from dgraph_tpu import config as _cfg\n"
            "import jax\n"
            "def step(x):\n"
            "    impl = _cfg.halo_impl\n"
            "    def body(y):\n"
            "        return y if impl == 'auto' else -y\n"
            "    return jax.jit(body)(x)\n"
        ),
    },
    "no-span-in-trace": {
        "path": "dgraph_tpu/train/loop.py",
        "bad": (
            "import jax\n"
            "from dgraph_tpu.obs import spans\n"
            "def step(x):\n"
            "    def body(y):\n"
            "        with spans.span('inner', stage='agg'):\n"
            "            return y * 2\n"
            "    return jax.jit(body)(x)\n"
        ),
        "good": (
            "import jax\n"
            "from dgraph_tpu.obs import spans\n"
            "def step(x):\n"
            "    with spans.span('outer', stage='step'):\n"
            "        return jax.jit(lambda y: y * 2)(x)\n"
        ),
    },
    "custom-vjp-paired": {
        "path": "dgraph_tpu/ops/local.py",
        "bad": (
            "import jax\n"
            "@jax.custom_vjp\n"
            "def f(x):\n"
            "    return x\n"
        ),
        "good": (
            "import jax\n"
            "@jax.custom_vjp\n"
            "def f(x):\n"
            "    return x\n"
            "f.defvjp(lambda x: (x, None), lambda r, g: (g,))\n"
        ),
    },
    "named-scope-on-collectives": {
        "path": "dgraph_tpu/comm/collectives.py",
        "bad": (
            "from jax import lax\n"
            "def exchange(x, axis):\n"
            "    return lax.all_to_all(x, axis, 0, 0)\n"
        ),
        "good": (
            "from jax import lax\n"
            "@_scoped('dgraph.exchange')\n"
            "def exchange(x, axis):\n"
            "    return lax.all_to_all(x, axis, 0, 0)\n"
        ),
    },
    "no-monolithic-plan-pickle": {
        "path": "dgraph_tpu/train/checkpoint.py",
        "bad": (
            "from dgraph_tpu.train.checkpoint import atomic_pickle_dump\n"
            "def cache(path, edge_index, part):\n"
            "    from dgraph_tpu.plan import build_edge_plan\n"
            "    plan = build_edge_plan(edge_index, part)\n"
            "    atomic_pickle_dump(path, plan)\n"
        ),
        "good": (
            "from dgraph_tpu.train.checkpoint import atomic_pickle_dump\n"
            "def save(path, step, params):\n"
            "    atomic_pickle_dump(path, {'step': step, 'params': params})\n"
        ),
    },
    "no-nondeterminism-in-plan": {
        "path": "dgraph_tpu/plan.py",
        "bad": (
            "import numpy as np\n"
            "def build(edges):\n"
            "    perm = np.random.permutation(len(edges))\n"
            "    return edges[perm]\n"
        ),
        "good": (
            "import numpy as np\n"
            "def build(edges, seed):\n"
            "    rng = np.random.default_rng(seed)\n"
            "    return edges[rng.permutation(len(edges))]\n"
        ),
    },
    # trace-time SPMD divergence at its source: a rank read steering
    # PYTHON control flow in a traced body hands every rank a different
    # program (the deadlock class analysis.spmd audits at the artifact
    # level). Host-side rank reads OUTSIDE the traced boundary are the
    # sanctioned pattern (checkpoint dirs, leader logging).
    "no-rank-branch-in-trace": {
        "path": "dgraph_tpu/train/loop.py",
        "bad": (
            "import jax\n"
            "def step(x):\n"
            "    def body(y):\n"
            "        if jax.process_index() == 0:\n"
            "            return y * 2\n"
            "        return y\n"
            "    return jax.jit(body)(x)\n"
        ),
        "good": (
            "import jax\n"
            "def launch(x):\n"
            "    if jax.process_index() == 0:\n"
            "        print('leader owns the checkpoint dir')\n"
            "    return jax.jit(lambda y: y * 2)(x)\n"
        ),
    },
    # an ad-hoc narrowing cast next to a collective ships bytes the
    # footprint/trace/tuner pipeline never prices; the sanctioned shape
    # is the wire codec pair (encode before the exchange, decode after,
    # both priced). Casting to x.dtype (no literal) stays green.
    "no-unpriced-wire-cast": {
        "path": "dgraph_tpu/comm/collectives.py",
        "bad": (
            "from jax import lax\n"
            "def exchange(x, axis):\n"
            "    send = x.astype('bfloat16')\n"
            "    return lax.all_to_all(send, axis, 0, 0)\n"
        ),
        "good": (
            "from jax import lax\n"
            "from dgraph_tpu.wire.codec import make_wire_transform\n"
            "def exchange(x, axis, enc, dec):\n"
            "    recv = lax.all_to_all(enc(x), axis, 0, 0)\n"
            "    return dec(recv).astype(x.dtype)\n"
        ),
    },
}

# the rank-env spelling of the same divergence (os.environ[RANK_ENV_VAR]
# slicing a traced operand) must fire too — and the pragma must suppress
# it like any other rule
_RANK_ENV_BRANCH_BAD = (
    "import os\n"
    "import jax\n"
    "from dgraph_tpu.utils.env import RANK_ENV_VAR\n"
    "def step(x):\n"
    "    def body(y):\n"
    "        r = int(os.environ[RANK_ENV_VAR])\n"
    "        return y[r:]\n"
    "    return jax.jit(body)(x)\n"
)


# pallas_call kernel bodies are traced code too — until ISSUE 12 they
# were the trace-discipline rules' blind spot (kernels reach pallas_call
# through a functools.partial alias, which the descent now sees through)
_KERNEL_FIXTURES = {
    "no-config-read-in-trace": {
        "path": "dgraph_tpu/ops/pallas_segment.py",
        "bad": (
            "import functools\n"
            "from jax.experimental import pallas as pl\n"
            "from dgraph_tpu import config as _cfg\n"
            "def _kernel(x_ref, o_ref):\n"
            "    o_ref[...] = x_ref[...] * (2 if _cfg.use_pallas_scatter else 1)\n"
            "def transport(x, shape):\n"
            "    kern = functools.partial(_kernel)\n"
            "    return pl.pallas_call(kern, out_shape=shape)(x)\n"
        ),
        "good": (
            "import functools\n"
            "from jax.experimental import pallas as pl\n"
            "from dgraph_tpu import config as _cfg\n"
            "def _kernel(x_ref, o_ref, *, scale):\n"
            "    o_ref[...] = x_ref[...] * scale\n"
            "def transport(x, shape):\n"
            "    scale = 2 if _cfg.use_pallas_scatter else 1\n"
            "    kern = functools.partial(_kernel, scale=scale)\n"
            "    return pl.pallas_call(kern, out_shape=shape)(x)\n"
        ),
    },
    "no-span-in-trace": {
        "path": "dgraph_tpu/ops/pallas_segment.py",
        "bad": (
            "import functools\n"
            "from jax.experimental import pallas as pl\n"
            "from dgraph_tpu.obs import spans\n"
            "def _kernel(x_ref, o_ref):\n"
            "    with spans.span('segsum.tile', stage='scatter'):\n"
            "        o_ref[...] = x_ref[...]\n"
            "def transport(x, shape):\n"
            "    kern = functools.partial(_kernel)\n"
            "    return pl.pallas_call(kern, out_shape=shape)(x)\n"
        ),
        "good": (
            "import functools\n"
            "from jax.experimental import pallas as pl\n"
            "from dgraph_tpu.obs import spans\n"
            "def _kernel(x_ref, o_ref):\n"
            "    o_ref[...] = x_ref[...]\n"
            "def transport(x, shape):\n"
            "    with spans.span('segsum.call', stage='scatter'):\n"
            "        kern = functools.partial(_kernel)\n"
            "        return pl.pallas_call(kern, out_shape=shape)(x)\n"
        ),
    },
}


_SHARD_MAP_FIXTURES = {
    "no-unchecked-shard-map": {
        "path": "dgraph_tpu/train/loop.py",
        "bad": (
            "import jax\n"
            "def build(body, mesh, specs):\n"
            "    return jax.shard_map(body, mesh=mesh, in_specs=specs,\n"
            "                         out_specs=specs, check_vma=False)\n"
        ),
        "good": (
            "import jax\n"
            "from dgraph_tpu.comm.collectives import shard_map_checks\n"
            "def build(body, mesh, specs, plan):\n"
            "    return jax.shard_map(body, mesh=mesh, in_specs=specs,\n"
            "                         out_specs=specs,\n"
            "                         **shard_map_checks(plan, 'graph'))\n"
        ),
    },
}

# the RELAXED_CHECKS splat spelling must fire too (the blanket escape
# parallel/sequence.py carried before its ISSUE 12 audit)
_SHARD_MAP_SPLAT_BAD = (
    "import jax\n"
    "RELAXED_CHECKS = {'check_vma': False}\n"
    "def build(body, mesh, specs):\n"
    "    return jax.shard_map(body, mesh=mesh, in_specs=specs,\n"
    "                         out_specs=specs, **RELAXED_CHECKS)\n"
)


def _check(failures, cond, msg):
    if not cond:
        failures.append(msg)


def _lint_fixture_checks(failures: list) -> None:
    from dgraph_tpu.analysis import lint as L

    fixture_sets = (
        list(_FIXTURES.items())
        + list(_KERNEL_FIXTURES.items())
        + list(_SHARD_MAP_FIXTURES.items())
    )
    for name, fx in fixture_sets:
        rule = L.RULES[name]
        for kind, src in (("bad", fx["bad"]), ("good", fx["good"])):
            tree = ast.parse(src)
            lines = src.splitlines()
            if name == "jax-free-module":
                got = rule.check(fx["path"], tree, lines, root="")
            else:
                got = rule.check(fx["path"], tree, lines)
            if kind == "bad":
                _check(
                    failures, got,
                    f"rule {name!r} missed its fixture ({fx['path']})",
                )
            else:
                _check(
                    failures, not got,
                    f"rule {name!r} false-positived on clean code "
                    f"({fx['path']}): {got}",
                )
    # the **RELAXED_CHECKS splat spelling of an unchecked shard_map must
    # fire too (keyword fixture above covers check_vma=)
    got = L.RULES["no-unchecked-shard-map"].check(
        "dgraph_tpu/parallel/sequence.py",
        ast.parse(_SHARD_MAP_SPLAT_BAD),
        _SHARD_MAP_SPLAT_BAD.splitlines(),
    )
    _check(
        failures, got,
        "no-unchecked-shard-map missed a **RELAXED_CHECKS splat",
    )
    # the rank-env slicing spelling of trace-time SPMD divergence
    got = L.RULES["no-rank-branch-in-trace"].check(
        "dgraph_tpu/train/loop.py",
        ast.parse(_RANK_ENV_BRANCH_BAD),
        _RANK_ENV_BRANCH_BAD.splitlines(),
    )
    _check(
        failures, got,
        "no-rank-branch-in-trace missed an os.environ[RANK_ENV_VAR] "
        "slice in a traced body",
    )
    # pragma suppression: the bad jax-free fixture goes quiet when allowed
    src = "def poison(tree):\n    import jax  # lint: allow(jax-free-module)\n"
    got = L.RULES["jax-free-module"].check(
        "dgraph_tpu/chaos/__init__.py", ast.parse(src), src.splitlines(),
        root="",
    )
    got = [
        f for f in got
        if not L._suppressed(src.splitlines(), f.line, f.rule)
    ]
    _check(failures, not got, "pragma did not suppress a finding")
    # ...and the wire-cast rule honors the same pragma (an allowed cast
    # is a documented, greppable decision, e.g. a diagnostic-only path)
    src = (
        "from jax import lax\n"
        "def exchange(x, axis):\n"
        "    send = x.astype('bfloat16')  # lint: allow(no-unpriced-wire-cast)\n"
        "    return lax.all_to_all(send, axis, 0, 0)\n"
    )
    got = L.RULES["no-unpriced-wire-cast"].check(
        "dgraph_tpu/comm/collectives.py", ast.parse(src), src.splitlines(),
    )
    got = [
        f for f in got
        if not L._suppressed(src.splitlines(), f.line, f.rule)
    ]
    _check(
        failures, not got,
        "pragma did not suppress a no-unpriced-wire-cast finding",
    )
    # transitive module-level check: importing a dgraph_tpu module that
    # itself imports jax at module level must fire
    with tempfile.TemporaryDirectory(prefix="dgraph_lint_selftest_") as tmp:
        os.makedirs(os.path.join(tmp, "dgraph_tpu", "chaos"))
        with open(os.path.join(tmp, "dgraph_tpu", "helper.py"), "w") as fh:
            fh.write("import jax\n")
        target = os.path.join(tmp, "dgraph_tpu", "chaos", "__init__.py")
        with open(target, "w") as fh:
            fh.write("from dgraph_tpu.helper import thing\n")
        got = L.lint_file(target, tmp)
        _check(
            failures,
            any(f.rule == "jax-free-module" for f in got),
            "transitive jax-free-module check missed a jax-using import",
        )


def _audit_vacuity_checks(failures: list, w2, w4) -> None:
    """The auditor must still FAIL on real drift — a green audit is only
    evidence if these reds stay red."""
    from dgraph_tpu import config as _cfg
    from dgraph_tpu.analysis import trace as T

    # wrong lowering family: a ppermute-pinned program audited as
    # all_to_all must fail
    saved = (_cfg.halo_impl, _cfg.tuned_halo_impl)
    try:
        _cfg.set_flags(halo_impl="ppermute", tuned_halo_impl=None)
        fn, args = T._train_program(w2)
        mism: list = []
        T._audit_one_program("vacuity", "all_to_all", fn, args, w2.plan_np, mism)
        _check(failures, mism, "auditor accepted a mismatched lowering family")

        # wrong bytes: auditing the 2-shard trace against the 4-shard
        # plan's footprint must fail on operand bytes
        fn, args = T._train_program(w2)
        mism = []
        T._audit_one_program("vacuity", "ppermute", fn, args, w4.plan_np, mism)
        _check(
            failures, mism,
            "auditor accepted operand bytes from the wrong plan",
        )
    finally:
        _cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1])

    # mixed all_to_all + ppermute legs in ONE program must stay RED in
    # the one-family audit: the exchange lowered one way and its reverse
    # leg another is exactly the PR 4 hazard
    import jax
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu.comm import collectives
    from dgraph_tpu.comm.mesh import GRAPH_AXIS, plan_in_specs, squeeze_plan

    def mixed(xs, plan):
        def body(plan_, x):
            p = squeeze_plan(plan_)
            buf = collectives.halo_exchange(
                x[0], p.halo, GRAPH_AXIS, deltas=p.halo_deltas,
                impl="all_to_all",
            )
            back = collectives.halo_scatter_sum(
                buf, p.halo, p.n_src_pad, GRAPH_AXIS,
                deltas=p.halo_deltas, impl="ppermute",
            )
            return back[None]

        return jax.shard_map(
            body, mesh=w2.mesh,
            in_specs=(plan_in_specs(w2.plan), P(GRAPH_AXIS)),
            out_specs=P(GRAPH_AXIS),
            **collectives.shard_map_checks(impl="all_to_all"),
        )(plan, xs)

    mism = []
    T._audit_one_program(
        "vacuity-mixed", "all_to_all", mixed,
        (w2.batch["x"], w2.plan), w2.plan_np, mism,
    )
    _check(
        failures,
        any("mixed halo lowerings" in m for m in mism),
        "auditor accepted a program mixing an all_to_all leg with a "
        "ppermute leg",
    )

    # dropped donation: a step that returns only metrics must report the
    # params/opt_state donations unmatched
    fn, args = T._train_program(w2)
    dropped = lambda p, o, b, pl: fn(p, o, b, pl)[2]  # noqa: E731
    unmatched = T.donation_unmatched(dropped, args, (w2.params, w2.opt_state))
    _check(failures, unmatched, "donation check missed dropped buffers")


def _hlo_vacuity_checks(failures: list, w2) -> None:
    """The lowered-artifact auditor must still FAIL on seeded drift: an
    extra XLA-level all-gather, a dropped donation (both the declare-level
    drop and the shape-uncovered drop), and a wrong lowering family —
    the reds that make the HLO tier's green mean something."""
    import warnings

    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from dgraph_tpu import config as _cfg
    from dgraph_tpu.analysis import hlo as H
    from dgraph_tpu.analysis.trace import _train_program
    from dgraph_tpu.comm.collectives import shard_map_checks
    from dgraph_tpu.comm.mesh import GRAPH_AXIS
    from dgraph_tpu.train.loop import make_train_step

    saved = (_cfg.halo_impl, _cfg.tuned_halo_impl)
    try:
        _cfg.set_flags(halo_impl="all_to_all", tuned_halo_impl=None)
        fn, args = _train_program(w2)

        # seeded extra all-gather: the accidental-collective class must
        # go RED at the artifact level
        def seeded(params, opt_state, batch, plan):
            out = fn(params, opt_state, batch, plan)
            extra = jax.shard_map(
                # each rank keeps its own copy of the gathered rows: the vma
                # checker is on, and all_gather's output counts as varying
                lambda x: lax.all_gather(x[0], GRAPH_AXIS)[None],
                mesh=w2.mesh, in_specs=(P(GRAPH_AXIS),),
                out_specs=P(GRAPH_AXIS),
                **shard_map_checks(relax="seeded vacuity mutant"),
            )(batch["x"])
            return out, extra

        mism: list = []
        H._audit_one_lowering(
            "vacuity-extra-ag", "all_to_all",
            H.lower_program(jax.jit(seeded, donate_argnums=(0, 1)), args),
            w2.plan_np, w2.mesh, mism,
        )
        _check(
            failures,
            any("unscheduled all_gather" in m for m in mism),
            "HLO auditor accepted an XLA-materialized all_gather the plan "
            "never scheduled",
        )

        # dropped donation (declare level): donate=False must leave zero
        # donor entries in the lowered module
        donated = len(jax.tree.leaves((w2.params, w2.opt_state)))
        nd = make_train_step(
            w2.model, w2.optimizer, w2.mesh, w2.plan, donate=False
        )
        mism = []
        H._donation_failures(
            H.donation_entries(H.lower_program(nd, args)), donated,
            "vacuity-no-donate", mism,
        )
        _check(failures, mism, "HLO auditor missed a dropped donation")

        # dropped donation (shape level): a metrics-only step donates
        # buffers no output can cover — XLA would silently drop the alias
        mo = jax.jit(
            lambda p, o, b, pl: fn(p, o, b, pl)[2], donate_argnums=(0, 1)
        )
        mism = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # jax warns on unusable donations
            H._donation_failures(
                H.donation_entries(H.lower_program(mo, args)), donated,
                "vacuity-uncovered", mism,
            )
        _check(
            failures, mism,
            "HLO auditor missed a donation no output type covers",
        )

        # wrong lowering family at the artifact level
        _cfg.set_flags(halo_impl="ppermute", tuned_halo_impl=None)
        fn2, args2 = _train_program(w2)
        mism = []
        H._audit_one_lowering(
            "vacuity-family", "all_to_all", H.lower_program(fn2, args2),
            w2.plan_np, w2.mesh, mism,
        )
        _check(
            failures, mism,
            "HLO auditor accepted a mismatched lowering family",
        )
    finally:
        _cfg.set_flags(halo_impl=saved[0], tuned_halo_impl=saved[1])


def _selftest(cfg: Config, log) -> dict:
    from dgraph_tpu.analysis.hlo import audit_workload_hlo
    from dgraph_tpu.analysis.lint import run_lint
    from dgraph_tpu.analysis.trace import audit_workload, build_audit_workload

    failures: list = []
    _lint_fixture_checks(failures)

    tree_report = run_lint(cfg.root or None)
    _check(
        failures, tree_report["ok"],
        f"tree lint found violations: {tree_report['findings']}",
    )

    audits = {}
    hlo_audits = {}
    workloads = {}
    for world in (2, 4):
        w = build_audit_workload(world, seed=cfg.seed)
        workloads[world] = w
        rep = audit_workload(w)
        audits[world] = rep
        log.write(rep)
        _check(
            failures, rep["ok"],
            f"{world}-shard trace audit drifted: {rep['failures']}",
        )
        _check(
            failures, rep["num_halo_deltas"] >= 1,
            f"{world}-shard audit graph has no cross-rank traffic "
            f"(the byte pins would be vacuous)",
        )
        # the lowered-artifact tier: same workloads, one level down —
        # lower-only (jit(...).lower(); still zero XLA compiles)
        hrep = audit_workload_hlo(w)
        hlo_audits[world] = hrep
        log.write(hrep)
        _check(
            failures, hrep["ok"],
            f"{world}-shard HLO audit drifted: {hrep['failures']}",
        )

    _audit_vacuity_checks(failures, workloads[2], workloads[4])
    _hlo_vacuity_checks(failures, workloads[2])

    # the cross-rank SPMD tier: 2- and 4-shard worlds, both generations
    # of a real W -> W-1 shrink, and the seeded-divergence mutants —
    # lower-only, jit-cache counters ride the spmd summary
    from dgraph_tpu.analysis.spmd import spmd_selftest

    spmd_summary = spmd_selftest(log, seed=cfg.seed)
    failures.extend(spmd_summary.pop("failures"))

    # the host-side concurrency & durability tier: per-rule fixture
    # pairs + the vacuity mutants (unlocked guarded-field write, seeded
    # lock-order cycle, bare-open manifest write, pointer-flip-before-
    # payload, unregistered chaos fire site — each must go RED) + the
    # clean-tree audit — pure stdlib ast, zero compiles by construction
    from dgraph_tpu.analysis.host import (
        host_selftest_failures, run_host_audit,
    )

    failures.extend(host_selftest_failures(cfg.root or None))
    host_audit = run_host_audit(cfg.root or None)
    log.write(host_audit)

    return {
        "kind": "analysis_selftest",
        "failures": failures,
        "lint_files_checked": tree_report["files_checked"],
        "audit": {
            str(wld): {
                "ok": rep["ok"],
                "exchange_legs": rep["exchange_legs"],
                "num_halo_deltas": rep["num_halo_deltas"],
            }
            for wld, rep in audits.items()
        },
        "hlo_audit": {
            str(wld): {
                "ok": rep["ok"],
                "exchange_legs": rep["exchange_legs"],
                "donation": rep["donation"],
            }
            for wld, rep in hlo_audits.items()
        },
        "host_audit": {
            "ok": host_audit["ok"],
            "files_checked": host_audit["files_checked"],
            "lock_edges": host_audit["lock_edges"],
            "chaos_points": host_audit["chaos_points"],
        },
        "spmd_audit": spmd_summary,
    }


def main(cfg: Config) -> dict:
    from dgraph_tpu.obs.health import RunHealth
    from dgraph_tpu.utils import ExperimentLog

    health = RunHealth.begin("analysis.cli")
    log = ExperimentLog(cfg.log_path, echo=False)
    try:
        if cfg.list_rules:
            from dgraph_tpu.analysis.lint import RULES

            out = {
                "kind": "rule_catalog",
                "rules": [
                    {"name": r.name, "scope": r.scope,
                     "description": r.description}
                    for r in sorted(RULES.values(), key=lambda r: r.name)
                ],
            }
            print(json.dumps(out, indent=cfg.indent or None))
            return out
        if cfg.bench_fallback:
            if cfg.fallback_kind == "hlo_drift":
                from dgraph_tpu.analysis.hlo import hlo_drift_record

                out = hlo_drift_record(
                    8, num_nodes=cfg.nodes, num_edges=cfg.edges,
                    feat_dim=cfg.feat_dim, seed=cfg.seed,
                )
            elif cfg.fallback_kind == "spmd_drift":
                from dgraph_tpu.analysis.spmd import spmd_drift_record

                # cross-rank identity is per-rank-lowering-heavy; a
                # reduced shape keeps the wedged round's budget (the
                # signal — do the ranks agree at all — is structural)
                out = spmd_drift_record(
                    4, num_nodes=min(cfg.nodes, 1024),
                    num_edges=min(cfg.edges, 4096),
                    feat_dim=cfg.feat_dim, seed=cfg.seed,
                )
            else:
                from dgraph_tpu.analysis.trace import schedule_drift_record

                out = schedule_drift_record(
                    8, num_nodes=cfg.nodes, num_edges=cfg.edges,
                    feat_dim=cfg.feat_dim, seed=cfg.seed,
                )
            out["run_health"] = health.finish(
                "; ".join(out["failures"]) if out["drift"] else None,
                wedge="stage_failure" if out["drift"] else None,
            )
            log.write(out)
            print(json.dumps(out, indent=cfg.indent or None))
            return out
        if cfg.selftest:
            out = _selftest(cfg, log)
            failures = out["failures"]
            out["run_health"] = health.finish(
                "; ".join(failures) if failures else None,
                wedge="stage_failure" if failures else None,
            )
            log.write(out)
            print(json.dumps(out, indent=cfg.indent or None))
            if failures:
                raise SystemExit(
                    "analysis selftest FAILED: " + "; ".join(failures)
                )
            return out

        problems: list = []
        out = {"kind": "analysis_report"}
        if cfg.lint:
            from dgraph_tpu.analysis.lint import run_lint

            lint_report = run_lint(cfg.root or None)
            out["lint"] = lint_report
            if not lint_report["ok"]:
                problems.extend(
                    f"{f['rule']} {f['path']}:{f['line']}"
                    for f in lint_report["findings"]
                )
        if cfg.audit or cfg.hlo:
            from dgraph_tpu.analysis.trace import build_audit_workload

            w = build_audit_workload(cfg.world, seed=cfg.seed)
        if cfg.audit:
            from dgraph_tpu.analysis.trace import audit_workload

            audit_report = audit_workload(w)
            out["audit"] = audit_report
            problems.extend(audit_report["failures"])
        if cfg.hlo:
            from dgraph_tpu.analysis.hlo import audit_workload_hlo

            hlo_report = audit_workload_hlo(w)
            out["hlo_audit"] = hlo_report
            problems.extend(hlo_report["failures"])
        if cfg.host:
            # host-side concurrency & durability tier: the per-FILE host
            # rules (lock discipline, durable writes, pointer-flip-last)
            # already ran in the lint pass above — this section adds the
            # repo-level graphs (lock-acquisition order, chaos-registry
            # coverage) plus the structural summary
            from dgraph_tpu.analysis.host import run_host_audit

            host_report = run_host_audit(
                cfg.root or None, file_rules=not cfg.lint
            )
            out["host_audit"] = host_report
            problems.extend(host_report["failures"])
        if cfg.spmd:
            from dgraph_tpu.analysis.spmd import (
                audit_plan_dir_spmd, build_spmd_fixture,
            )

            with tempfile.TemporaryDirectory(
                prefix="dgraph_spmd_cli_"
            ) as tmp:
                build_spmd_fixture(cfg.world, tmp, seed=cfg.seed)
                spmd_report = audit_plan_dir_spmd(tmp)
            out["spmd_audit"] = spmd_report
            problems.extend(spmd_report["failures"])
        out["ok"] = not problems
        out["run_health"] = health.finish(
            "; ".join(problems) if problems else None,
            wedge="stage_failure" if problems else None,
        )
        log.write(out)
        print(json.dumps(out, indent=cfg.indent or None))
        if problems:
            raise SystemExit("analysis FAILED: " + "; ".join(problems[:10]))
        return out
    except SystemExit:
        raise
    except BaseException as e:  # every exit path carries a RunHealth record
        log.write({
            "kind": "run_health",
            **health.finish(
                f"analysis failed: {type(e).__name__}: {e}",
                wedge="interrupted"
                if isinstance(e, KeyboardInterrupt) else "stage_failure",
            ),
        })
        raise


if __name__ == "__main__":
    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
