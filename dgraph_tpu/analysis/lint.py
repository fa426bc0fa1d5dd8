"""Contract linter: stdlib-``ast`` rules for the repo's cross-layer contracts.

Each rule encodes one invariant that used to be enforced only by review
discipline (and in two cases was already silently broken when this linter
first ran — see the pinned regressions in ``tests/test_analysis.py``):

- ``jax-free-module`` — ``dgraph_tpu.chaos``, ``train/supervise.py`` and
  ``obs/health.py`` must never *use* jax: a wedged lease can hang any jax
  API call, and these are exactly the modules that must outlive a wedged
  child (the supervisor) or be loadable standalone without triggering a
  backend (bench's health loader).  The rule flags any ``import jax`` in
  those files (any scope) and any import of a ``dgraph_tpu`` module whose
  own module level imports jax.  The package ``__init__`` is exempt by
  design: normal package imports pay it, but the standalone loaders load
  these files by path precisely to skip it, so the contract is about the
  modules' OWN code.
- ``no-config-read-in-trace`` — no ``dgraph_tpu.config`` attribute read or
  ``os.environ`` access lexically inside a function that is passed to (or
  decorated with) ``jit`` / ``shard_map`` / ``custom_vjp`` / ``grad`` /
  ``scan`` and friends.  This is the PR 4 mixed-lowering hazard, machine
  checked: a config read at trace time can hand two legs of one op
  different lowerings, and a cached executable silently ignores later
  flag flips.  Resolve once OUTSIDE the traced function and thread the
  decision through as a static argument (``comm.collectives.
  resolve_plan_impl`` is the pattern).
- ``custom-vjp-paired`` — every ``jax.custom_vjp`` function must call
  ``defvjp`` in the same file: an unpaired declaration traces fine and
  fails only when somebody differentiates through it.
- ``named-scope-on-collectives`` — every public function in
  ``comm/collectives.py`` that issues a ``lax`` collective must be wrapped
  in a named scope: un-scoped collectives are invisible in Perfetto
  traces, and perf attribution of the halo exchange is the whole point of
  the obs layer.
- ``no-nondeterminism-in-plan`` — plan/partition builds must be
  deterministic functions of (graph, seed): no unseeded RNG, no
  wall-clock reads.  Plans are content-addressed into an on-disk cache
  and signed by the tuner; a nondeterministic build breaks both.

Suppression: append ``# lint: allow(<rule-name>)`` on the offending line
(or the line above) — every suppression is a documented, greppable
decision, e.g. ``obs/health.py``'s opt-in backend snapshot.

Adding a rule: write ``check(path, tree, lines) -> list[Finding]``,
decorate with :func:`rule`, and add a fixture pair to the selftest in
``__main__.py`` (a snippet that must fire + one that must not).  Rules are
pure stdlib (``ast`` only) so the linter runs without jax anywhere.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Callable, Optional

# jax-free and stdlib-free by contract — the linter stays importable
# without jax anywhere (the one env-var name no-rank-branch-in-trace
# greps for lives in the same shared home its runtime readers use)
from dgraph_tpu.utils.env import RANK_ENV_VAR

# functions whose function-valued arguments are traced by jax: a config
# read inside one is a trace-time read (the PR 4 hazard class).
# pallas_call is one of them — the kernel body is traced like any jit
# body, so a config read or span inside a kernel is a trace-time read too.
TRACING_ENTRY_POINTS = frozenset({
    "jit", "shard_map", "custom_vjp", "custom_jvp", "grad", "value_and_grad",
    "vjp", "jvp", "linearize", "scan", "while_loop", "fori_loop", "cond",
    "checkpoint", "remat", "pmap", "vmap", "make_jaxpr", "eval_shape",
    "pallas_call",
})

# lax collectives that must appear only inside named scopes in the
# collectives facade (named-scope-on-collectives)
COLLECTIVE_CALLS = frozenset({
    "all_to_all", "ppermute", "psum", "pmean", "pmax", "pmin", "all_gather",
    "psum_scatter", "pshuffle",
})

_PRAGMA = re.compile(r"#\s*lint:\s*allow\(([a-z0-9_,\- ]+)\)")


@dataclasses.dataclass
class Finding:
    """One rule violation at one source location."""

    rule: str
    path: str
    line: int
    message: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Rule:
    name: str
    description: str
    applies: Callable[[str], bool]  # repo-relative posix path -> bool
    check: Callable[[str, ast.AST, list], list]  # (relpath, tree, lines)
    # human-readable applies-to (what the `applies` predicate encodes) —
    # printed by ``--list-rules`` and machine-checked against the rule
    # catalog table in docs/static-analysis.md
    scope: str = ""


RULES: dict = {}


def rule(name: str, description: str, applies, scope: str = ""):
    """Register a rule. ``applies`` is a predicate over the repo-relative
    posix path (use :func:`path_matcher` for prefix/suffix sets);
    ``scope`` is its human-readable rendering for ``--list-rules`` and
    the docs table."""

    def deco(fn):
        RULES[name] = Rule(name, description, applies, fn, scope)
        return fn

    return deco


def path_matcher(*prefixes: str):
    def match(relpath: str) -> bool:
        return any(relpath.startswith(p) for p in prefixes)

    return match


def _suppressed(lines: list, lineno: int, rule_name: str) -> bool:
    """True when the finding's line (or the one above) carries
    ``# lint: allow(<rule>)`` for this rule."""
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(lines):
            m = _PRAGMA.search(lines[ln - 1])
            if m and rule_name in [s.strip() for s in m.group(1).split(",")]:
                return True
    return False


def _dotted(node) -> str:
    """Best-effort dotted name of an expression (``a.b.c`` -> "a.b.c")."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _last_segment(node) -> str:
    d = _dotted(node)
    return d.rsplit(".", 1)[-1] if d else ""


# ---------------------------------------------------------------------------
# jax-free-module
# ---------------------------------------------------------------------------

JAX_FREE_TARGETS = (
    "dgraph_tpu/chaos/",
    "dgraph_tpu/train/supervise.py",
    "dgraph_tpu/obs/health.py",
    # the span tracer is imported by the supervisor and loaded standalone
    # by bench's wedge-surviving loader — same contract as health.py
    "dgraph_tpu/obs/spans.py",
    # shard/manifest integrity IO must run without a backend: the v8 plan
    # artifact is repaired/inspected on hosts where jax may be wedged
    "dgraph_tpu/plan_shards.py",
    # liveness is the thing that must keep working while jax is wedged:
    # heartbeats/polls/barriers/rendezvous never touch an accelerator API
    "dgraph_tpu/comm/membership.py",
    # the shared home of cross-boundary env-var names (RANK_ENV_VAR):
    # imported by every module above, so it must never pull jax in
    "dgraph_tpu/utils/env.py",
    # the package __init__ the env import pays on the way in: its heavy
    # exports (ExperimentLog, the split helpers) are PEP 562-lazy precisely so
    # this file stays jax-free at module level — enforcing it here means
    # a restored eager import turns every target above RED instead of
    # silently re-poisoning them
    "dgraph_tpu/utils/__init__.py",
    # serving control-plane bookkeeping: the model registry, tenant
    # quota table, and structured serve errors are inspected by the
    # supervisor and health tooling in processes that never dial a
    # backend — and the serve package __init__ is PEP 562-lazy for the
    # same reason utils' is (an eager engine import here would poison
    # all three)
    "dgraph_tpu/serve/__init__.py",
    "dgraph_tpu/serve/errors.py",
    "dgraph_tpu/serve/registry.py",
    "dgraph_tpu/serve/tenancy.py",
    # the host-side concurrency/durability auditor is stdlib-ast by
    # contract: it audits exactly the modules that must outlive a wedge,
    # so it must never need a backend to run
    "dgraph_tpu/analysis/host/",
    # the perf-trajectory ledger + drift sentinel + report: the
    # longitudinal store is read/written by bench's supervisor and by
    # operators on machines where jax is wedged or absent, so the whole
    # pipeline (normalize, gate, render) is stdlib-only by contract
    "dgraph_tpu/obs/ledger.py",
    "dgraph_tpu/obs/regress.py",
    "dgraph_tpu/obs/report.py",
    # the grow-to-fit transition: the world-growth decision path (join
    # discovery, unfold, gather, adopt) must keep working while jax is
    # wedged — everything that pulls jax (plan builder, reshard kernel)
    # is reached through train/shrink.py's function-scope imports, and
    # the join announcement path rides membership.py (already a target)
    "dgraph_tpu/train/grow.py",
    # the wire-format registry and its selftest: wire formats are DATA
    # (resolved, priced, serialized into plans and tuning records) on
    # hosts with no backend — wire/codec.py holds the jax encode/decode
    # pairs and is deliberately outside this list (wire/__init__
    # lazy-exports it)
    "dgraph_tpu/wire/spec.py",
    "dgraph_tpu/wire/__main__.py",
)


def _module_level_imports(tree: ast.AST):
    """(node, module) pairs for imports executed at module import time —
    top-level statements, descending into top-level ``if``/``try`` blocks
    (guarded imports still run at import time)."""
    out = []
    stack = list(getattr(tree, "body", []))
    while stack:
        node = stack.pop()
        if isinstance(node, ast.Import):
            out.extend((node, a.name) for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            if node.level == 0 and node.module:
                out.append((node, node.module))
        elif isinstance(node, (ast.If, ast.Try, ast.With)):
            for field in ("body", "orelse", "finalbody"):
                stack.extend(getattr(node, field, []))
            for handler in getattr(node, "handlers", []):
                stack.extend(handler.body)
    return out


def _all_imports(tree: ast.AST):
    """(node, module, names) for every import anywhere in the file."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out.append((node, a.name, ()))
        elif isinstance(node, ast.ImportFrom) and node.level == 0 and node.module:
            out.append((node, node.module, tuple(a.name for a in node.names)))
    return out


def _module_file(root: str, dotted: str) -> Optional[str]:
    """Resolve a dotted module path to a file under ``root`` (or None for
    third-party / stdlib modules)."""
    base = os.path.join(root, *dotted.split("."))
    for cand in (base + ".py", os.path.join(base, "__init__.py")):
        if os.path.isfile(cand):
            return cand
    return None


def _file_uses_jax_at_module_level(root: str, path: str, _seen=None) -> bool:
    """True when importing ``path`` as a module pulls jax in, following
    package-internal module-level imports transitively. The top-level
    package ``__init__`` files are skipped (see module docstring)."""
    _seen = _seen if _seen is not None else set()
    if path in _seen:
        return False
    _seen.add(path)
    try:
        tree = ast.parse(open(path).read())
    except (OSError, SyntaxError):
        return False
    for _node, mod in _module_level_imports(tree):
        if mod == "jax" or mod.startswith("jax."):
            return True
        if mod.startswith("dgraph_tpu"):
            dep = _module_file(root, mod)
            if dep and not dep.endswith(os.path.join("dgraph_tpu", "__init__.py")):
                if _file_uses_jax_at_module_level(root, dep, _seen):
                    return True
    return False


@rule(
    "jax-free-module",
    "chaos/, train/supervise.py and obs/health.py must not use jax in any "
    "scope, nor import dgraph_tpu modules that use jax at module level",
    path_matcher(*JAX_FREE_TARGETS),
    scope=", ".join(t.replace("dgraph_tpu/", "") for t in JAX_FREE_TARGETS),
)
def check_jax_free(relpath: str, tree: ast.AST, lines: list, root: str = ""):
    findings = []
    for node, mod, names in _all_imports(tree):
        if mod == "jax" or mod.startswith("jax."):
            findings.append(Finding(
                "jax-free-module", relpath, node.lineno,
                f"import of {mod!r} in a jax-free module (a wedged lease can "
                f"hang any jax call; this module must outlive one)",
            ))
            continue
        targets = []
        if mod.startswith("dgraph_tpu"):
            targets.append(mod)
            # `from dgraph_tpu.x import y` may name a submodule y
            targets.extend(f"{mod}.{n}" for n in names)
        for t in targets:
            dep = _module_file(root, t) if root else None
            if (
                dep
                and not dep.endswith(os.path.join("dgraph_tpu", "__init__.py"))
                and _file_uses_jax_at_module_level(root, dep)
            ):
                findings.append(Finding(
                    "jax-free-module", relpath, node.lineno,
                    f"import of {t!r}, whose module level pulls in jax",
                ))
                break
    return findings


# ---------------------------------------------------------------------------
# no-config-read-in-trace
# ---------------------------------------------------------------------------


def _config_aliases(tree: ast.AST) -> set:
    """Names bound to the ``dgraph_tpu.config`` module anywhere in the
    file (``from dgraph_tpu import config as _cfg``, ``import
    dgraph_tpu.config as cfg``, ...)."""
    aliases = set()
    for node, mod, _names in _all_imports(tree):
        if isinstance(node, ast.ImportFrom):
            if mod == "dgraph_tpu":
                for a in node.names:
                    if a.name == "config":
                        aliases.add(a.asname or a.name)
        else:
            for a in node.names:
                if a.name == "dgraph_tpu.config" and a.asname:
                    aliases.add(a.asname)
    return aliases


def _partial_target(call: ast.Call):
    """The function NAME a ``functools.partial(fn, ...)`` call binds, or
    None — pallas kernels reach ``pallas_call`` through exactly this
    wrapper (static kwargs baked in), so the descent must see through
    it."""
    if _last_segment(call.func) != "partial" or not call.args:
        return None
    first = call.args[0]
    return first.id if isinstance(first, ast.Name) else None


def _traced_functions(tree: ast.AST) -> list:
    """Function nodes handed to jax tracing machinery: decorated with a
    tracing entry point, or passed (by name, inline lambda, inline
    ``functools.partial``, or a name bound to a partial) as an argument
    to one. ``pallas_call`` kernels count — directly or through a
    ``kern = functools.partial(kernel_fn, ...)`` alias."""
    traced, by_name, partial_alias = [], {}, {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, []).append(node)
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                if _last_segment(target) in TRACING_ENTRY_POINTS:
                    traced.append(node)
        elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            # kern = functools.partial(kernel_fn, ...) -> kern aliases it
            fn_name = _partial_target(node.value)
            if fn_name:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        partial_alias[t.id] = fn_name
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _last_segment(node.func) not in TRACING_ENTRY_POINTS:
            continue
        for arg in list(node.args) + [k.value for k in node.keywords]:
            if isinstance(arg, ast.Lambda):
                traced.append(arg)
            elif isinstance(arg, ast.Name):
                traced.extend(by_name.get(arg.id, []))
                traced.extend(by_name.get(partial_alias.get(arg.id, ""), []))
            elif isinstance(arg, ast.Call):
                fn_name = _partial_target(arg)
                if fn_name:
                    traced.extend(by_name.get(fn_name, []))
    return traced


@rule(
    "no-config-read-in-trace",
    "no dgraph_tpu.config / os.environ read lexically inside a function "
    "passed to jit/shard_map/custom_vjp/... (the PR 4 mixed-lowering "
    "hazard: resolve before the trace, thread the decision through)",
    path_matcher("dgraph_tpu/"),
    scope="dgraph_tpu/",
)
def check_config_read_in_trace(relpath: str, tree: ast.AST, lines: list):
    aliases = _config_aliases(tree)
    findings = []
    for fn in _traced_functions(tree):
        for node in ast.walk(fn):
            bad = None
            if isinstance(node, ast.Attribute):
                base = _dotted(node.value)
                if base in aliases:
                    bad = f"config read '{base}.{node.attr}'"
                elif base == "os" and node.attr in ("environ", "getenv"):
                    bad = f"environment read 'os.{node.attr}'"
            elif isinstance(node, ast.ImportFrom) and (
                node.module == "dgraph_tpu"
                and any(a.name == "config" for a in node.names)
                or node.module == "dgraph_tpu.config"
            ):
                bad = "dgraph_tpu.config imported"
            elif isinstance(node, ast.Import) and any(
                a.name == "dgraph_tpu.config" for a in node.names
            ):
                bad = "dgraph_tpu.config imported"
            if bad:
                findings.append(Finding(
                    "no-config-read-in-trace", relpath, node.lineno,
                    f"{bad} inside traced function "
                    f"{getattr(fn, 'name', '<lambda>')!r} (line {fn.lineno}): "
                    f"a trace-time read freezes into the executable and can "
                    f"desynchronize legs of one op",
                ))
    return findings


# ---------------------------------------------------------------------------
# no-span-in-trace
# ---------------------------------------------------------------------------

# host-side span entry points (obs.spans: span, its always-on twin stage)
# that must never execute inside a traced body: a host clock read there
# measures TRACING (once), not execution (every step), and a span id would
# freeze into the cached executable — both silently wrong, never crashing
SPAN_CALLS = frozenset({"span", "start_span", "stage", "record_span"})


@rule(
    "no-span-in-trace",
    "no obs.spans span / stage call lexically inside a function passed to "
    "jit/shard_map/scan/... (host timing in a traced body measures "
    "tracing, not execution; spans stay at host boundaries)",
    path_matcher("dgraph_tpu/"),
    scope="dgraph_tpu/",
)
def check_span_in_trace(relpath: str, tree: ast.AST, lines: list):
    findings = []
    for fn in _traced_functions(tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            last = _last_segment(node.func)
            bad = None
            if last in SPAN_CALLS:
                # only span-shaped calls: a string name argument or
                # keyword attrs (filters regex Match.span(int) lookalikes)
                named = any(
                    isinstance(a, ast.Constant) and isinstance(a.value, str)
                    for a in node.args
                ) or bool(node.keywords)
                if named:
                    bad = f"span call '{dotted or last}'"
            if bad:
                findings.append(Finding(
                    "no-span-in-trace", relpath, node.lineno,
                    f"{bad} inside traced function "
                    f"{getattr(fn, 'name', '<lambda>')!r} (line {fn.lineno}):"
                    f" host-side timing inside a jit/shard_map/scan body "
                    f"runs at trace time, not per step — move it outside "
                    f"the traced boundary",
                ))
    return findings


# ---------------------------------------------------------------------------
# no-rank-branch-in-trace
# ---------------------------------------------------------------------------

# call names that return this process's rank identity
RANK_IDENTITY_CALLS = frozenset({"process_index", "rank_from_env"})


def _rank_env_aliases(tree: ast.AST) -> set:
    """Names bound to RANK_ENV_VAR in this file (``from dgraph_tpu.utils.
    env import RANK_ENV_VAR [as ...]`` — chaos re-exports it too)."""
    aliases = set()
    for node, mod, _names in _all_imports(tree):
        if not isinstance(node, ast.ImportFrom):
            continue
        if mod in ("dgraph_tpu.utils.env", "dgraph_tpu.chaos",
                   "dgraph_tpu.utils"):
            for a in node.names:
                if a.name == "RANK_ENV_VAR":
                    aliases.add(a.asname or a.name)
    return aliases


def _rank_read(expr: ast.AST, env_aliases: set, cfg_aliases: set):
    """The rank-identity read inside ``expr``, or None: a
    ``jax.process_index()``-family call, a ``$DGRAPH_RANK`` env read (by
    literal or by RANK_ENV_VAR alias), or a rank field on the config
    module."""
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Call) and (
            _last_segment(sub.func) in RANK_IDENTITY_CALLS
        ):
            return f"'{_dotted(sub.func) or _last_segment(sub.func)}()'", sub
        if isinstance(sub, ast.Constant) and sub.value == RANK_ENV_VAR:
            return f"'{RANK_ENV_VAR}' environment read", sub
        if isinstance(sub, ast.Name) and sub.id in env_aliases:
            return f"'{sub.id}' (RANK_ENV_VAR) environment read", sub
        if isinstance(sub, ast.Attribute) and sub.attr == "RANK_ENV_VAR":
            return "'RANK_ENV_VAR' environment read", sub
        if (
            isinstance(sub, ast.Attribute)
            and _dotted(sub.value) in cfg_aliases
            and "rank" in sub.attr.lower()
        ):
            return f"config rank field '{_dotted(sub.value)}.{sub.attr}'", sub
    return None


def _control_flow_exprs(fn: ast.AST):
    """Expressions that steer PYTHON control flow (or indexing) inside a
    function body: a per-rank value here changes what gets TRACED, not
    what gets computed — every rank builds a different program."""
    for node in ast.walk(fn):
        if isinstance(node, (ast.If, ast.While, ast.IfExp, ast.Assert)):
            yield node.test
        elif isinstance(node, ast.For):
            yield node.iter
        elif isinstance(node, ast.Subscript):
            yield node.slice
        elif isinstance(node, ast.comprehension):
            yield node.iter
            yield from node.ifs


@rule(
    "no-rank-branch-in-trace",
    "no DGRAPH_RANK / jax.process_index() / config rank-field read inside "
    "Python control flow of a function passed to jit/shard_map/... — every "
    "rank would trace a DIFFERENT program, and mismatched collective "
    "schedules deadlock (not error) on real transports; resolve rank-"
    "dependent decisions on the host, outside the traced boundary",
    path_matcher("dgraph_tpu/"),
    scope="dgraph_tpu/",
)
def check_rank_branch_in_trace(relpath: str, tree: ast.AST, lines: list):
    env_aliases = _rank_env_aliases(tree)
    cfg_aliases = _config_aliases(tree)
    findings = []
    seen = set()
    for fn in _traced_functions(tree):
        for expr in _control_flow_exprs(fn):
            hit = _rank_read(expr, env_aliases, cfg_aliases)
            if hit is None:
                continue
            why, node = hit
            key = (node.lineno, node.col_offset)
            if key in seen:
                continue
            seen.add(key)
            findings.append(Finding(
                "no-rank-branch-in-trace", relpath, node.lineno,
                f"{why} steering Python control flow inside traced "
                f"function {getattr(fn, 'name', '<lambda>')!r} (line "
                f"{fn.lineno}): each rank traces a different program — "
                f"trace-time SPMD divergence, the collective-schedule "
                f"deadlock analysis.spmd exists to catch, here at its "
                f"source",
            ))
    return findings


# ---------------------------------------------------------------------------
# custom-vjp-paired
# ---------------------------------------------------------------------------


@rule(
    "custom-vjp-paired",
    "every jax.custom_vjp declaration must have a defvjp call in the same "
    "file (an unpaired one only fails under differentiation)",
    path_matcher("dgraph_tpu/"),
    scope="dgraph_tpu/",
)
def check_custom_vjp_paired(relpath: str, tree: ast.AST, lines: list):
    declared = {}  # name -> lineno
    paired = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                if _last_segment(target) == "custom_vjp":
                    declared[node.name] = node.lineno
        elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            if _last_segment(node.value.func) == "custom_vjp":
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        declared[t.id] = node.lineno
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            if node.func.attr == "defvjp":
                paired.add(_dotted(node.func.value))
    return [
        Finding(
            "custom-vjp-paired", relpath, line,
            f"custom_vjp function {name!r} has no defvjp call in this file",
        )
        for name, line in sorted(declared.items(), key=lambda kv: kv[1])
        if name not in paired
    ]


# ---------------------------------------------------------------------------
# named-scope-on-collectives
# ---------------------------------------------------------------------------


@rule(
    "named-scope-on-collectives",
    "public functions in comm/collectives.py that issue a lax collective "
    "must be wrapped in a named scope (profiler attribution)",
    path_matcher("dgraph_tpu/comm/collectives.py"),
    scope="comm/collectives.py",
)
def check_named_scope(relpath: str, tree: ast.AST, lines: list):
    findings = []
    for node in tree.body:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name.startswith("_"):
            continue
        issues = [
            sub.lineno
            for sub in ast.walk(node)
            if isinstance(sub, ast.Call)
            and _last_segment(sub.func) in COLLECTIVE_CALLS
        ]
        if not issues:
            continue
        scoped = any(
            _last_segment(dec.func if isinstance(dec, ast.Call) else dec)
            in ("named_scope", "_scoped")
            for dec in node.decorator_list
        )
        if not scoped:
            findings.append(Finding(
                "named-scope-on-collectives", relpath, node.lineno,
                f"public collective {node.name!r} (issues a collective at "
                f"line {issues[0]}) is not wrapped in a named scope",
            ))
    return findings


# ---------------------------------------------------------------------------
# no-unchecked-shard-map
# ---------------------------------------------------------------------------


@rule(
    "no-unchecked-shard-map",
    "every shard_map call site routes its replication-check kwargs through "
    "comm.collectives.shard_map_checks(...): a raw check_vma/check_rep "
    "kwarg (or a blanket **RELAXED_CHECKS splat) silently disables the one "
    "checker that catches a wrong out-spec before XLA materializes an "
    "accidental all-gather",
    path_matcher("dgraph_tpu/"),
    scope="dgraph_tpu/",
)
def check_unchecked_shard_map(relpath: str, tree: ast.AST, lines: list):
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _last_segment(node.func) != "shard_map":
            continue
        for kw in node.keywords:
            if kw.arg in ("check_vma", "check_rep"):
                findings.append(Finding(
                    "no-unchecked-shard-map", relpath, kw.value.lineno,
                    f"raw {kw.arg}= kwarg at a shard_map call site: route "
                    f"check kwargs through comm.collectives."
                    f"shard_map_checks(...) so relaxing the replication "
                    f"checker stays one greppable, reasoned decision",
                ))
            elif kw.arg is None:  # **splat
                v = kw.value
                if (
                    isinstance(v, ast.Call)
                    and _last_segment(v.func) == "shard_map_checks"
                ):
                    continue
                findings.append(Finding(
                    "no-unchecked-shard-map", relpath, v.lineno,
                    f"shard_map kwargs splatted from "
                    f"{_dotted(v) or ast.dump(v)[:40]!r} — only "
                    f"**shard_map_checks(...) may carry check kwargs into "
                    f"a shard_map call",
                ))
    return findings


# ---------------------------------------------------------------------------
# no-nondeterminism-in-plan
# ---------------------------------------------------------------------------

SEEDED_RNG_CONSTRUCTORS = frozenset({
    "default_rng", "Generator", "RandomState", "SeedSequence", "Random",
    "PRNGKey", "key",
})
WALL_CLOCK_CALLS = frozenset({
    "time", "time_ns", "perf_counter", "perf_counter_ns", "monotonic", "now",
    "utcnow", "today",
})


@rule(
    "no-nondeterminism-in-plan",
    "plan/partition builds must be deterministic in (graph, seed): no "
    "unseeded RNG and no wall-clock reads (plans are content-addressed "
    "into the cache and signed by the tuner)",
    path_matcher(
        "dgraph_tpu/plan.py", "dgraph_tpu/partition.py",
        "dgraph_tpu/tune/signature.py",
    ),
    scope="plan.py, partition.py, tune/signature.py",
)
def check_plan_determinism(relpath: str, tree: ast.AST, lines: list):
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        last = dotted.rsplit(".", 1)[-1] if dotted else ""
        if ".random." in f".{dotted}" or dotted.startswith("random."):
            if last in SEEDED_RNG_CONSTRUCTORS:
                if not node.args and not node.keywords:
                    findings.append(Finding(
                        "no-nondeterminism-in-plan", relpath, node.lineno,
                        f"'{dotted}()' with no seed in a plan-build path",
                    ))
            else:
                findings.append(Finding(
                    "no-nondeterminism-in-plan", relpath, node.lineno,
                    f"unseeded module-level RNG call '{dotted}' in a "
                    f"plan-build path (use a seeded default_rng)",
                ))
        elif (
            last in WALL_CLOCK_CALLS
            and dotted.split(".", 1)[0] in ("time", "datetime", "dt")
        ):
            findings.append(Finding(
                "no-nondeterminism-in-plan", relpath, node.lineno,
                f"wall-clock read '{dotted}' in a plan-build path",
            ))
    return findings


# ---------------------------------------------------------------------------
# no-monolithic-plan-pickle
# ---------------------------------------------------------------------------

PLAN_BUILDERS = frozenset({
    "build_edge_plan", "build_edge_plan_sharded", "cached_edge_plan",
    "_finalize_plan", "assemble_plan", "load_sharded_plan",
})


def _mentions_plan(expr: ast.AST) -> Optional[str]:
    """The identifier that makes ``expr`` plan-shaped (a name/attribute
    containing 'plan', or a direct plan-builder call), else None."""
    for node in ast.walk(expr):
        name = None
        if isinstance(node, ast.Name):
            name = node.id
        elif isinstance(node, ast.Attribute):
            name = node.attr
        elif isinstance(node, ast.Call):
            name = _last_segment(node.func)
            if name in PLAN_BUILDERS:
                return name
        if name and "plan" in name.lower():
            return name
    return None


@rule(
    "no-monolithic-plan-pickle",
    "no atomic_pickle_dump of a whole EdgePlan outside the shard writer "
    "(plan_shards.py): the monolithic plan pickle is the ~40+ GB "
    "all-or-nothing artifact that OOM-killed the papers100M build — plans "
    "persist as per-rank shards + a checksummed manifest (cache format v8)",
    lambda relpath: (
        relpath.startswith("dgraph_tpu/")
        and relpath != "dgraph_tpu/plan_shards.py"
    ),
    scope="dgraph_tpu/ except plan_shards.py",
)
def check_monolithic_plan_pickle(relpath: str, tree: ast.AST, lines: list):
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _last_segment(node.func) != "atomic_pickle_dump":
            continue
        payloads = list(node.args[1:]) + [k.value for k in node.keywords]
        for payload in payloads:
            why = _mentions_plan(payload)
            if why:
                findings.append(Finding(
                    "no-monolithic-plan-pickle", relpath, node.lineno,
                    f"atomic_pickle_dump of plan-shaped payload ({why!r}) "
                    f"outside the shard writer: persist plans as per-rank "
                    f"shards + manifest (plan_shards.PlanShardWriter / "
                    f"plan.build_plan_shards), not one monolithic pickle",
                ))
                break
    return findings


# ---------------------------------------------------------------------------
# no-unpriced-wire-cast
# ---------------------------------------------------------------------------

# dtypes narrower than fp32 whose literal spelling in a cast marks a
# deliberate narrowing (a cast to ``x.dtype`` / a widening to f32 never
# matches)
NARROW_DTYPES = frozenset({
    "bfloat16", "float16", "float8_e4m3fn", "float8_e5m2", "int8", "uint8",
})


def _narrow_dtype_literal(node) -> Optional[str]:
    """The narrow dtype a cast argument names literally, else None."""
    if isinstance(node, ast.Constant) and node.value in NARROW_DTYPES:
        return str(node.value)
    if isinstance(node, ast.Attribute) and node.attr in NARROW_DTYPES:
        return node.attr
    if isinstance(node, ast.Name) and node.id in NARROW_DTYPES:
        return node.id
    return None


@rule(
    "no-unpriced-wire-cast",
    "no literal dtype-narrowing astype/convert_element_type in a function "
    "that puts operands on the wire (issues a lax collective): an ad-hoc "
    "cast ships bytes the footprint model, trace/HLO auditors and tuner "
    "never price — narrowing wire payloads is "
    "dgraph_tpu.wire's job (encode/decode pairs, priced end to end)",
    path_matcher("dgraph_tpu/comm/", "dgraph_tpu/ops/"),
    scope="comm/, ops/",
)
def check_unpriced_wire_cast(relpath: str, tree: ast.AST, lines: list):
    findings = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        issues = [
            sub.lineno for sub in ast.walk(fn)
            if isinstance(sub, ast.Call)
            and _last_segment(sub.func) in COLLECTIVE_CALLS
        ]
        if not issues:
            continue
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            last = _last_segment(sub.func)
            arg = None
            if last == "astype" and sub.args:
                arg = sub.args[0]
            elif last == "convert_element_type":
                cands = list(sub.args[1:]) + [
                    k.value for k in sub.keywords if k.arg == "new_dtype"
                ]
                arg = cands[0] if cands else None
            dt = _narrow_dtype_literal(arg) if arg is not None else None
            if dt:
                findings.append(Finding(
                    "no-unpriced-wire-cast", relpath, sub.lineno,
                    f"literal narrowing cast to {dt!r} inside {fn.name!r} "
                    f"(line {fn.lineno}), which puts operands on the wire "
                    f"(exchange call at line {issues[0]}): those bytes are "
                    f"invisible to footprint/trace/tuner — route narrowing "
                    f"through dgraph_tpu.wire (make_wire_transform / "
                    f"make_*_codec) so the encoded payload is priced and "
                    f"verified end to end",
                ))
    return findings


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------


def repo_root() -> str:
    """The directory containing the ``dgraph_tpu`` package."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def iter_source_files(root: str):
    pkg = os.path.join(root, "dgraph_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def lint_file(path: str, root: str, rules=None) -> list:
    """Run every applicable rule over one file; returns unsuppressed
    findings."""
    relpath = os.path.relpath(path, root).replace(os.sep, "/")
    source = open(path).read()
    lines = source.splitlines()
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding("syntax", relpath, e.lineno or 0, f"unparseable: {e}")]
    findings = []
    for r in (rules or RULES).values():
        if not r.applies(relpath):
            continue
        if r.name == "jax-free-module":
            got = r.check(relpath, tree, lines, root=root)
        else:
            got = r.check(relpath, tree, lines)
        findings.extend(
            f for f in got if not _suppressed(lines, f.line, f.rule)
        )
    return findings


def run_lint(root: Optional[str] = None, rules=None) -> dict:
    """Lint the whole ``dgraph_tpu`` tree; returns a JSON-able report."""
    root = root or repo_root()
    findings, n_files = [], 0
    for path in iter_source_files(root):
        n_files += 1
        findings.extend(lint_file(path, root, rules))
    findings.sort(key=lambda f: (f.path, f.line))
    per_rule: dict = {}
    for f in findings:
        per_rule[f.rule] = per_rule.get(f.rule, 0) + 1
    return {
        "kind": "lint_report",
        "root": root,
        "files_checked": n_files,
        "rules": sorted(RULES),
        "findings": [f.to_dict() for f in findings],
        "per_rule": per_rule,
        "ok": not findings,
    }
