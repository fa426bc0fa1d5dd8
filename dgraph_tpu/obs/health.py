"""Structured run/probe health diagnostics.

BENCH_r05.json was ``value: null`` after seven wedged-lease probes, and the
only evidence was free-text stderr.  :class:`RunHealth` is the structured
replacement: one JSON-able record accumulating probe attempts (with
wall-times and outcomes), a backend/topology snapshot, and a wedge
classification — embedded in bench.py's output on every exit path and
written by the experiment CLIs at startup, so a dead run is diagnosable
from its artifact alone.

Wedge classes (``classify_wedge``):

- ``none``            — no error.
- ``init_wedge``      — backend init probes HANG (PJRT waits forever
                        on a device it cannot reach).
- ``init_failure``    — probes fail fast with an error (bad platform,
                        missing plugin) — recoverable by config, not time.
- ``dispatch_wedge``  — backend came up but a device op hung (lease wedged
                        after init; the r1/r2 probe-then-hang pattern).
- ``backend_lost``    — the backend is not the one the run needs: it
                        initialized then disappeared (child lost its lease
                        between probe and run) or came up on the wrong
                        platform (the silent CPU-fallback signature). Both
                        exit fail-fast and are retried by respawn.
- ``watchdog_timeout``— the run's own deadline fired mid-stage.
- ``interrupted``     — an outer signal (timeout wrapper, ^C) ended it.
- ``stage_failure``   — device work ran but a stage raised.
- ``unknown``         — anything else; the error text is still recorded.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Optional

SCHEMA_VERSION = 1

WEDGE_KINDS = (
    "none",
    "init_wedge",
    "init_failure",
    "dispatch_wedge",
    "backend_lost",
    "watchdog_timeout",
    "interrupted",
    "stage_failure",
    "unknown",
)

# env prefixes worth snapshotting (flags that change behavior; no secrets)
_ENV_PREFIXES = ("JAX_", "DGRAPH_", "XLA_FLAGS", "TPU_")


def classify_wedge(error: Optional[str], probes: Optional[list] = None) -> str:
    """Map an exit-path error string + probe history to a wedge class."""
    if not error:
        return "none"
    e = error.lower()
    probes = probes or []
    hung_probes = any(p.get("outcome") == "hang" for p in probes)
    # FIRST: the literal phrase bench's _emit_json_and_exit produces for a
    # stage exception ("gcn stage failed: <arbitrary exception text>").
    # The interpolated text can contain any of the substrings the generic
    # scans below look for ("hung", "interrupt", ...), and a stage crash
    # must never be misread as a lease wedge.
    if "stage failed" in e:
        return "stage_failure"
    if "watchdog" in e and "past its own watchdog" not in e:
        return "watchdog_timeout"
    if "never initialized" in e or "backend init failed" in e:
        return "init_wedge" if hung_probes else "init_failure"
    # platform-mismatch must be checked BEFORE the substring-'wedge' scan:
    # bench's "backend is 'cpu', need 'tpu' (... wedged lease?)" is a
    # fail-fast config problem, and calling it a wedge would tell the
    # operator to wait for a recovery that can never come
    if "backend is" in e or ("backend" in e and "lost" in e):
        return "backend_lost"
    if "hung" in e or "wedge" in e:
        return "dispatch_wedge"
    if "signal" in e or "interrupt" in e:
        return "interrupted"
    return "unknown"


def _active_trace_id() -> Optional[str]:
    """The ambient span-trace id (:mod:`dgraph_tpu.obs.spans`), so health
    records are joinable against span/step JSONL across a restart chain.
    Looked up via sys.modules — never imported — for the same reason as
    the chaos field: bench's supervisor loads this file standalone (by
    path, registering the spans twin as ``_dgraph_obs_spans``), and that
    load must never trigger the package ``__init__``'s jax import. The
    env var is the fallback for children that inherit a trace without
    ever importing the tracer."""
    import sys

    for name in ("dgraph_tpu.obs.spans", "_dgraph_obs_spans"):
        mod = sys.modules.get(name)
        if mod is not None:
            try:
                return mod.current_trace_id()
            except Exception:  # diagnostics must never break the run
                return None
    return os.environ.get("DGRAPH_TRACE_ID") or None


_GIT_REV: Optional[str] = None


def git_rev() -> str:
    """The current ``git rev-parse --short HEAD`` of the repo this file
    lives in, or ``"unknown"`` (no git, no .git dir, detached tarball —
    never an exception). Cached per process; stamped into every
    :class:`RunHealth` record and bench round JSON so a perf artifact is
    attributable to a commit (the ledger keys on it; any bisect wants
    it)."""
    global _GIT_REV
    if _GIT_REV is None:
        import subprocess

        try:
            p = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, timeout=5,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            rev = (p.stdout or "").strip()
            _GIT_REV = rev if p.returncode == 0 and rev else "unknown"
        except Exception:
            _GIT_REV = "unknown"
    return _GIT_REV


def _host_snapshot() -> dict:
    import platform
    import socket

    return {
        "hostname": socket.gethostname(),
        "pid": os.getpid(),
        "platform": platform.platform(),
        "python": platform.python_version(),
    }


def _env_snapshot() -> dict:
    out = {}
    for k, v in os.environ.items():
        if any(k.startswith(p) for p in _ENV_PREFIXES):
            out[k] = v
    # the ACTIVE fault-injection spec, not just the env var: chaos can be
    # armed programmatically, and a perf artifact produced under injected
    # faults must be identifiable from its health record alone. Looked up
    # via sys.modules, NOT imported: bench.py's supervisor loads this file
    # standalone precisely so it never triggers the package __init__'s jax
    # import, and that must stay true (the env var is the fallback there).
    import sys

    chaos_mod = sys.modules.get("dgraph_tpu.chaos")
    try:
        out["chaos"] = (
            chaos_mod.active_spec() if chaos_mod is not None
            else (os.environ.get("DGRAPH_CHAOS") or None)
        )
    except Exception:  # never let diagnostics break the diagnosed run
        out["chaos"] = None
    return out


@dataclasses.dataclass
class RunHealth:
    """Accumulating health record for one run component (supervisor,
    bench child, or an experiment CLI). All fields JSON-serializable."""

    component: str
    started_at: str
    host: dict
    env: dict
    probes: list = dataclasses.field(default_factory=list)
    # structured lifecycle events (membership rank_lost/membership_changed,
    # shrink adoption, ...) — additive to schema 1, readers ignore it
    events: list = dataclasses.field(default_factory=list)
    backend: Optional[dict] = None
    wedge: str = "none"
    error: Optional[str] = None
    wall_s: Optional[float] = None
    # the active span-trace id (obs.spans) when tracing is on — the join
    # key against supervise_lineage / span / step JSONL; None otherwise.
    # Additive to schema 1 (readers ignore unknown fields).
    trace_id: Optional[str] = None
    # the commit the record was produced at (git_rev(); "unknown" outside
    # a checkout) — the ledger's bisect key. Additive to schema 1.
    git_rev: Optional[str] = None
    schema: int = SCHEMA_VERSION
    _t0: float = dataclasses.field(default=0.0, repr=False)

    @classmethod
    def begin(cls, component: str) -> "RunHealth":
        return cls(
            component=component,
            started_at=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            host=_host_snapshot(),
            env=_env_snapshot(),
            trace_id=_active_trace_id(),
            git_rev=git_rev(),
            _t0=time.perf_counter(),
        )

    def record_probe(
        self, attempt: int, wall_s: float, outcome: str, detail: str = ""
    ) -> None:
        """outcome: 'ok' | 'error' | 'hang'."""
        self.probes.append(
            {
                "attempt": int(attempt),
                "wall_s": round(float(wall_s), 2),
                "outcome": outcome,
                "detail": detail[-500:],
            }
        )

    def record_event(self, rec: dict) -> None:
        """Append one structured lifecycle event (a ``.record()`` dict —
        membership's ``rank_lost``/``membership_changed``, shrink-to-fit
        adoption, ...) so the health artifact alone tells the recovery
        story. Bounded: after 200 events the oldest are dropped (a flapping
        member must not grow the record without bound)."""
        self.events.append(rec)
        if len(self.events) > 200:
            del self.events[: len(self.events) - 200]

    def snapshot_backend(self) -> Optional[dict]:
        """Best-effort jax backend/topology snapshot. Initializes the
        backend if it isn't already — only call where device work is about
        to happen anyway. Never raises; failure is itself recorded."""
        try:
            # the ONE sanctioned jax touch in this module: callers opt in
            # to a backend dial; module import and every other path stay
            # jax-free (bench's standalone loader depends on it)
            import jax  # lint: allow(jax-free-module)

            devs = jax.devices()
            self.backend = {
                "platform": jax.default_backend(),
                "jax_version": jax.__version__,
                "device_count": len(devs),
                "device_kinds": sorted({d.device_kind for d in devs}),
                "process_index": jax.process_index(),
                "process_count": jax.process_count(),
            }
        except Exception as e:  # a dead backend is exactly what we record
            self.backend = {"error": f"{type(e).__name__}: {e}"}
        return self.backend

    def finish(
        self, error: Optional[str] = None, wedge: Optional[str] = None
    ) -> dict:
        """Seal the record: stamp wall time, classify, return to_dict()."""
        self.error = error
        self.wedge = wedge if wedge is not None else classify_wedge(
            error, self.probes
        )
        self.wall_s = round(time.perf_counter() - self._t0, 1)
        return self.to_dict()

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.pop("_t0")
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "RunHealth":
        known = {f.name for f in dataclasses.fields(cls)} - {"_t0"}
        return cls(**{k: v for k, v in d.items() if k in known})


def startup_record(component: str, *, snapshot_backend: bool = True) -> dict:
    """The one-line health record every experiment CLI writes on startup
    (kind="run_health"): host/env/topology context for the JSONL that
    follows. ``snapshot_backend=False`` keeps host-only flows (offline
    plan builds) from ever dialing the accelerator."""
    h = RunHealth.begin(component)
    if snapshot_backend:
        h.snapshot_backend()
    return {"kind": "run_health", **h.finish()}
