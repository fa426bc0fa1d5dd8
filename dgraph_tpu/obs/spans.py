"""Flight recorder: hierarchical host-side span tracing with Perfetto export.

Until this module, the repo's three stacks each emitted *isolated* JSONL —
a serve request, a supervisor restart attempt, and a bench probe shared no
ID, so "where did the time go" was unanswerable across train/serve/bench.
Spans are the join key: every record carries a ``trace`` id (one per
logical run, inherited across process boundaries via the environment) and
a ``span``/``parent`` pair (one per timed operation), so restart chains,
request lifecycles, and probe histories line up in one timeline.

Design rules (the :mod:`dgraph_tpu.obs.metrics` discipline):

- **Zero overhead when disabled.** :func:`span` on a disabled tracer is
  ONE attribute read returning the shared no-op span — no allocation, no
  clock read, no I/O, and (because this module never touches jax) zero
  recompiles. Pinned by ``tests/test_spans.py``.
- **Host boundaries only.** Spans must never appear inside traced code —
  a host clock read inside a jit/shard_map/scan body times *tracing*, not
  execution, and a span id would freeze into the cached executable. The
  ``no-span-in-trace`` lint rule (:mod:`dgraph_tpu.analysis.lint`)
  machine-checks this.
- **jax-free module.** The train supervisor and bench's standalone loader
  import this file on machines where any jax call can hang (wedged
  lease); module level is pure stdlib, enforced by the ``jax-free-module``
  lint rule.

- **On the profiler's clock.** An enabled, context-managed span also
  holds a ``jax.profiler.TraceAnnotation`` of its own name from enter to
  exit, so under ``jax.profiler.trace`` the program's spans lie on the
  host line of the same trace as the device operations and an idle gap
  can be put down to one of them. The class is taken from
  ``sys.modules`` once jax is loaded (never imported here); a span that
  is ended by hand, possibly on another thread, gets no annotation.
- **Stages are always on.** :func:`stage` is the entry point for work
  done once per launch (partition, plan, shard, place, init): it keeps
  (count, total, max, last) seconds per name in an in-process table
  (:func:`stage_totals`) whether or not the tracer is enabled, and is a
  normal span besides when it is. Per-step and per-request spans stay
  opt-in through :func:`span`.

One finished span -> one JSONL record (``kind="span"``), written through
any sink with a ``write(dict)`` method (:class:`~dgraph_tpu.utils.logging.
ExperimentLog` works as-is) or a plain path.  ``python -m
dgraph_tpu.obs.spans --export perfetto --input logs/spans.jsonl`` converts
a span log to Chrome trace JSON loadable in https://ui.perfetto.dev.

Cross-process lineage: a parent process calls :func:`child_env` and merges
the result into the child's environment; the child's tracer auto-enables
with the SAME trace id (``DGRAPH_TRACE_ID``) and roots its spans under the
parent's span (``DGRAPH_TRACE_PARENT``) — this is how one supervised train
run's restart attempts land under one trace (``train.supervise``).
"""

from __future__ import annotations

import contextvars
import dataclasses
import json
import os
import sys
import threading
import time
from typing import Any, Optional

SPAN_SCHEMA_VERSION = 1

ENV_ENABLE = "DGRAPH_TRACE"  # "1"/"true" auto-enables the default tracer
ENV_TRACE_ID = "DGRAPH_TRACE_ID"  # inherited trace id (parent -> child)
ENV_PARENT = "DGRAPH_TRACE_PARENT"  # inherited root-parent span id
ENV_PATH = "DGRAPH_TRACE_PATH"  # sink path (default logs/spans.jsonl)
DEFAULT_PATH = "logs/spans.jsonl"

# the ambient innermost OPEN span of this thread/context (set by
# Span.__enter__ only; manually-ended spans never occupy it)
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "dgraph_span", default=None
)


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def _trace_annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation(name)``, or None while jax
    is not loaded in this process (this module never imports it)."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    ann = profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


class _FileSink:
    """Plain JSONL appender (stdlib-only; the jax-free stand-in for
    ExperimentLog). The file is opened lazily on first write so an
    enabled-but-idle tracer leaves no artifact behind."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()

    def write(self, rec: dict) -> None:
        with self._lock:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            with open(self.path, "a") as fh:
                fh.write(json.dumps(rec, default=str) + "\n")


class _NoopSpan:
    """The shared disabled span: every method is a no-op, identity is the
    pin (``span(...) is NOOP_SPAN`` when tracing is off)."""

    __slots__ = ()

    trace_id: Optional[str] = None
    span_id: Optional[str] = None
    parent_id: Optional[str] = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **attrs) -> None:
        pass

    def end(self, error: Optional[str] = None, **attrs) -> None:
        pass

    def __bool__(self) -> bool:
        return False


NOOP_SPAN = _NoopSpan()


def _span_record(trace_id, span_id, parent_id, name: str, start_unix: float,
                 dur_s: float, error: Optional[str], attrs: dict) -> dict:
    """The one JSONL shape of a finished span."""
    rec = {
        "kind": "span",
        "schema": SPAN_SCHEMA_VERSION,
        "trace": trace_id,
        "span": span_id,
        "parent": parent_id,
        "name": name,
        "ts_unix": round(start_unix, 6),
        "dur_ms": round(dur_s * 1e3, 3),
        "status": "error" if error else "ok",
        "pid": os.getpid(),
        "tid": threading.get_ident() & 0x7FFFFFFF,
        "thread": threading.current_thread().name,
    }
    if error:
        rec["error"] = str(error)[:500]
    if attrs:
        rec["attrs"] = attrs
    return rec


class Span:
    """One timed operation: started at construction, sealed by :meth:`end`
    (or context-manager exit, which also maintains the ambient
    current-span used for implicit parenting).

    Works across threads: construct on one thread (e.g. a serve request's
    submit), pass the object along, and ``end()`` wherever the operation
    completes — parenting for cross-thread spans is explicit via the
    ``parent=`` argument to :func:`span`.
    """

    __slots__ = (
        "_tracer", "name", "trace_id", "span_id", "parent_id", "attrs",
        "_t0_wall", "_t0", "_token", "_done", "_annotation",
    )

    def __init__(self, tracer: "Tracer", name: str,
                 parent: Optional[str], attrs: dict):
        self._tracer = tracer
        self.name = name
        self.trace_id = tracer.trace_id
        self.span_id = _new_id(4)
        self.parent_id = parent
        self.attrs = attrs
        self._t0_wall = time.time()
        self._t0 = time.perf_counter()
        self._token = None
        self._done = False
        self._annotation = None

    def annotate(self, **attrs) -> None:
        """Attach attributes after construction (stage timings, outcomes)."""
        self.attrs.update(attrs)

    def end(self, error: Optional[str] = None, **attrs) -> None:
        """Seal the span and write its record; idempotent (the first end
        wins — a double end from an exception path plus a finally block
        must not duplicate the record)."""
        if self._done:
            return
        self._done = True
        if attrs:
            self.attrs.update(attrs)
        self._tracer._write(_span_record(
            self.trace_id, self.span_id, self.parent_id, self.name,
            self._t0_wall, time.perf_counter() - self._t0, error, self.attrs,
        ))

    def __enter__(self) -> "Span":
        self._token = _CURRENT.set(self)
        self._annotation = _trace_annotation(self.name)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        if self._token is not None:
            _CURRENT.reset(self._token)
            self._token = None
        self.end(
            error=f"{exc_type.__name__}: {exc}" if exc_type else None
        )
        return False

    def __bool__(self) -> bool:
        return True


class Tracer:
    """Span factory bound to one trace id and one sink.

    Disabled by default; :meth:`enable` (or the ``DGRAPH_TRACE=1``
    environment, read once at import) turns it on. The hot call is
    :meth:`span`: disabled, it is one attribute read returning
    :data:`NOOP_SPAN`.
    """

    def __init__(self):
        self._enabled = False
        self.trace_id: Optional[str] = None
        self._root_parent: Optional[str] = None
        self._sink = None
        self._sink_path: Optional[str] = None

    # --- lifecycle ---

    def enable(self, sink=None, trace_id: Optional[str] = None,
               parent_id: Optional[str] = None) -> str:
        """Turn tracing on; returns the active trace id.

        ``sink`` is a path, a ``write(dict)`` object (ExperimentLog), or a
        callable taking the record dict; None keeps/creates the default
        file sink (``DGRAPH_TRACE_PATH`` or ``logs/spans.jsonl``).
        ``trace_id=None`` keeps the current id (or mints one);
        ``parent_id`` roots this process's parentless spans under an
        inherited span (cross-process lineage)."""
        if sink is not None:
            self._set_sink(sink)
        elif self._sink is None:
            self._set_sink(os.environ.get(ENV_PATH) or DEFAULT_PATH)
        if trace_id is not None:
            self.trace_id = trace_id
        elif self.trace_id is None:
            self.trace_id = _new_id(8)
        if parent_id is not None:
            self._root_parent = parent_id or None
        self._enabled = True
        return self.trace_id

    def disable(self) -> None:
        """Turn tracing off (the hot path reverts to the no-op span) and
        drop the trace context so a later enable() starts fresh."""
        self._enabled = False
        self.trace_id = None
        self._root_parent = None
        self._sink = None
        self._sink_path = None

    @property
    def enabled(self) -> bool:
        return self._enabled

    def _set_sink(self, sink) -> None:
        if isinstance(sink, str):
            self._sink = _FileSink(sink)
            self._sink_path = sink
        else:
            self._sink = sink
            self._sink_path = getattr(sink, "path", None)

    def configure_from_env(self, environ=None) -> bool:
        """Enable iff ``DGRAPH_TRACE`` is truthy in ``environ`` (default
        ``os.environ``) — the child-process half of :func:`child_env`."""
        if environ is None:
            environ = os.environ
        if str(environ.get(ENV_ENABLE, "")).lower() not in ("1", "true", "on"):
            return False
        self.enable(
            sink=environ.get(ENV_PATH) or DEFAULT_PATH,
            trace_id=environ.get(ENV_TRACE_ID) or None,
            parent_id=environ.get(ENV_PARENT) or None,
        )
        return True

    # --- the hot call ---

    def span(self, name: str, parent=None, **attrs):
        """Start a span. Disabled: one attribute read, returns the shared
        no-op. ``parent`` accepts a Span, a span-id string, or None (the
        ambient current span, else the inherited cross-process root)."""
        if not self._enabled:
            return NOOP_SPAN
        if parent is None:
            cur = _CURRENT.get()
            parent_id = cur.span_id if cur is not None else self._root_parent
        elif isinstance(parent, str):
            parent_id = parent
        else:
            parent_id = getattr(parent, "span_id", None)
        return Span(self, name, parent_id, dict(attrs))

    def record_span(self, name: str, start_unix: float, end_unix: float,
                    **attrs) -> None:
        """Write a span that someone else timed (JAX's compile events
        arrive finished, with their own start and end): parented like
        :meth:`span`, no annotation. Disabled: nothing."""
        if not self._enabled:
            return
        cur = _CURRENT.get()
        self._write(_span_record(
            self.trace_id, _new_id(4),
            cur.span_id if cur is not None else self._root_parent,
            name, start_unix, end_unix - start_unix, None, attrs,
        ))

    def _write(self, rec: dict) -> None:
        sink = self._sink
        if sink is None:
            return
        try:
            if callable(sink) and not hasattr(sink, "write"):
                sink(rec)
            else:
                sink.write(rec)
        except Exception:  # tracing must never take down the traced run
            pass

    # --- cross-process lineage ---

    def child_env(self, parent=None) -> dict:
        """Environment fragment that makes a child process join this
        trace: empty when disabled (children inherit the off state), else
        ``DGRAPH_TRACE``/``_ID``/``_PARENT``/``_PATH``. ``parent`` pins
        the child's root parent (default: the ambient current span)."""
        if not self._enabled:
            return {}
        if parent is None:
            parent = _CURRENT.get()
        parent_id = getattr(parent, "span_id", None) or (
            parent if isinstance(parent, str) else None
        )
        env = {ENV_ENABLE: "1", ENV_TRACE_ID: self.trace_id or ""}
        env[ENV_PARENT] = parent_id or ""
        if self._sink_path:
            env[ENV_PATH] = self._sink_path
        return env


# the process-wide default tracer; auto-enabled when the parent process
# exported DGRAPH_TRACE=1 (see child_env)
default_tracer = Tracer()
default_tracer.configure_from_env()


def span(name: str, parent=None, **attrs):
    """Module-level :meth:`Tracer.span` on the default tracer (the form
    call sites use; one attr read when disabled)."""
    return default_tracer.span(name, parent=parent, **attrs)


def record_span(name: str, start_unix: float, end_unix: float,
                **attrs) -> None:
    """Module-level :meth:`Tracer.record_span` on the default tracer."""
    default_tracer.record_span(name, start_unix, end_unix, **attrs)


# name -> [count, total seconds, max seconds, last seconds]: the always-on
# table of once-per-launch stages. (obs.metrics' registry would hold it, but
# that module imports dgraph_tpu.plan, hence jax; this one may not.)
_STAGES: dict = {}
_STAGES_LOCK = threading.Lock()


class _Stage:
    """A once-per-launch stage: its seconds always go to the table; the
    span underneath is the tracer's (the no-op when tracing is off)."""

    __slots__ = ("name", "_span", "_t0")

    def __init__(self, name: str, span):
        self.name = name
        self._span = span

    def annotate(self, **attrs) -> None:
        self._span.annotate(**attrs)

    def __enter__(self) -> "_Stage":
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dt = time.perf_counter() - self._t0
        with _STAGES_LOCK:
            row = _STAGES.setdefault(self.name, [0, 0.0, 0.0, 0.0])
            row[0] += 1
            row[1] += dt
            row[2] = max(row[2], dt)
            row[3] = dt
        return self._span.__exit__(exc_type, exc, tb)


def stage(name: str, **attrs) -> _Stage:
    """Context manager around work done once per launch. Always on: on
    exit its seconds are added under ``name`` to the in-process table
    :func:`stage_totals` reads. With the tracer enabled it is a normal
    span too (record + profiler annotation) carrying ``attrs``, the
    numbers that size the work. Never for per-step or per-request work
    (that is :func:`span`, opt-in), never inside traced code."""
    return _Stage(name, default_tracer.span(name, **attrs))


def stage_totals() -> dict:
    """A copy of the stage table: ``{name: {"count", "total_s", "max_s",
    "last_s"}}`` for every stage this process has finished."""
    with _STAGES_LOCK:
        return {
            name: {"count": c, "total_s": t, "max_s": m, "last_s": last}
            for name, (c, t, m, last) in _STAGES.items()
        }


def enable(sink=None, trace_id: Optional[str] = None,
           parent_id: Optional[str] = None) -> str:
    return default_tracer.enable(sink, trace_id, parent_id)


def disable() -> None:
    default_tracer.disable()


def enabled() -> bool:
    return default_tracer.enabled


def current_span():
    """The innermost open context-managed span of this thread, or None."""
    return _CURRENT.get()


def current_trace_id() -> Optional[str]:
    """The active trace id: the default tracer's when enabled, else the
    inherited ``DGRAPH_TRACE_ID`` (a child whose own tracing is off still
    reports the lineage id), else None."""
    if default_tracer.enabled:
        return default_tracer.trace_id
    return os.environ.get(ENV_TRACE_ID) or None


def child_env(parent=None) -> dict:
    return default_tracer.child_env(parent)


# ---------------------------------------------------------------------------
# Perfetto (Chrome trace JSON) export
# ---------------------------------------------------------------------------


def read_spans(path: str) -> list:
    """Span records from a JSONL file (non-span kinds and unparseable
    lines are skipped — span logs interleave with other records when the
    sink is a shared ExperimentLog)."""
    out = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line or not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("kind") == "span":
                out.append(rec)
    return out


def export_perfetto(records, out_path: Optional[str] = None) -> dict:
    """Convert span records to Chrome trace JSON (the Perfetto / chrome://
    tracing format): one complete event (``ph="X"``) per span, wall-clock
    microsecond timestamps, pid/tid preserved so supervisor and child
    processes land on separate tracks. ``records`` is a list of span
    dicts or a JSONL path; ``out_path`` writes the JSON too."""
    if isinstance(records, str):
        records = read_spans(records)
    events = []
    procs = set()
    for r in records:
        if r.get("kind") != "span":
            continue
        attrs = dict(r.get("attrs") or {})
        pid = int(r.get("pid", 0))
        tid = int(r.get("tid", 0))
        args = {
            "trace": r.get("trace"),
            "span": r.get("span"),
            "parent": r.get("parent"),
            "status": r.get("status", "ok"),
            **attrs,
        }
        if r.get("error"):
            args["error"] = r["error"]
        events.append({
            "ph": "X",
            "name": r.get("name", "?"),
            "cat": str(attrs.get("component", r.get("name", "span"))
                       ).split(".")[0],
            "ts": round(float(r.get("ts_unix", 0.0)) * 1e6, 3),
            "dur": max(round(float(r.get("dur_ms", 0.0)) * 1e3, 3), 0.0),
            "pid": pid,
            "tid": tid,
            "args": args,
        })
        procs.add(pid)
    for pid in sorted(procs):
        events.append({
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": f"dgraph pid {pid}"},
        })
    trace = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"producer": "dgraph_tpu.obs.spans",
                      "schema": SPAN_SCHEMA_VERSION},
    }
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as fh:
            json.dump(trace, fh)
    return trace


# ---------------------------------------------------------------------------
# CLI: --export perfetto + the compile-free selftest scripts/check.py runs
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Config:
    """Span tracing CLI (``--export perfetto`` converts a span JSONL to
    Chrome trace JSON; ``--selftest`` is the compile-free tier-1 smoke)."""

    selftest: bool = False
    export: str = ""  # "perfetto"
    input: str = DEFAULT_PATH
    output: str = ""  # default: <input>.perfetto.json
    indent: int = 0


def _selftest() -> dict:
    failures: list = []

    def check(cond, msg):
        if not cond:
            failures.append(msg)

    t = Tracer()
    # disabled == the shared no-op, before AND after an enable/disable
    # round trip (one attr read is the whole cost)
    check(t.span("x") is NOOP_SPAN, "disabled tracer did not return the "
                                    "shared no-op span")
    recs: list = []
    tid = t.enable(sink=recs.append, trace_id="feedbeef00000000")
    check(tid == "feedbeef00000000", "enable() did not adopt the trace id")
    with t.span("outer", stage="s0") as outer:
        with t.span("inner") as inner:
            check(inner.parent_id == outer.span_id,
                  "nested span did not parent to the enclosing span")
        manual = t.span("manual", parent=outer)
        manual.end(error="boom", n=3)
    check(len(recs) == 3, f"expected 3 span records, got {len(recs)}")
    by_name = {r["name"]: r for r in recs}
    check(set(by_name) == {"outer", "inner", "manual"}, "span names lost")
    check(all(r["trace"] == tid for r in recs), "trace id not propagated")
    check(by_name["outer"]["parent"] is None, "root span grew a parent")
    check(by_name["manual"]["status"] == "error"
          and by_name["manual"]["attrs"]["n"] == 3,
          "manual end(error=..., **attrs) not recorded")
    check(by_name["inner"]["dur_ms"] <= by_name["outer"]["dur_ms"],
          "child span outlasted its parent")
    # cross-process lineage: a child tracer built from child_env joins
    with t.span("parent-of-child") as pspan:
        env = t.child_env()
    child = Tracer()
    check(child.configure_from_env(env), "child_env did not enable the child")
    child._set_sink(recs.append)
    child.span("child-root").end()
    check(recs[-1]["trace"] == tid and recs[-1]["parent"] == pspan.span_id,
          "child tracer did not join the parent trace/span")
    t.disable()
    check(t.span("x") is NOOP_SPAN, "disable() did not restore the no-op")
    check(t.child_env() == {}, "disabled child_env must be empty")
    # perfetto export: valid Chrome trace shape
    trace = export_perfetto(recs)
    check(isinstance(trace["traceEvents"], list), "no traceEvents list")
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    check(len(xs) == len(recs), "X-event count != span count")
    check(all(
        {"name", "ts", "dur", "pid", "tid", "args"} <= set(e) for e in xs
    ), "X event missing required fields")
    json.dumps(trace)  # must be serializable as-is
    return {"kind": "spans_selftest", "failures": failures,
            "spans_checked": len(recs)}


def main(cfg: Config) -> dict:
    if cfg.selftest:
        out = _selftest()
        print(json.dumps(out, indent=cfg.indent or None))
        if out["failures"]:
            raise SystemExit(
                "spans selftest FAILED: " + "; ".join(out["failures"])
            )
        return out
    if cfg.export:
        if cfg.export != "perfetto":
            raise SystemExit(f"unknown export format {cfg.export!r} "
                             "(supported: perfetto)")
        out_path = cfg.output or cfg.input + ".perfetto.json"
        trace = export_perfetto(cfg.input, out_path)
        traces = sorted({
            e["args"].get("trace") for e in trace["traceEvents"]
            if e["ph"] == "X"
        } - {None})
        summary = {
            "kind": "perfetto_export",
            "input": cfg.input,
            "output": out_path,
            "events": sum(1 for e in trace["traceEvents"] if e["ph"] == "X"),
            "traces": traces,
        }
        print(json.dumps(summary, indent=cfg.indent or None))
        return summary
    raise SystemExit("nothing to do: pass --export perfetto or --selftest")


if __name__ == "__main__":
    from dgraph_tpu.utils.cli import parse_config

    main(parse_config(Config))
