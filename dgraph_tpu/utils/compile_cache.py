"""Where the persistent XLA compilation cache lives — decided in one place.

The cache directory is part of the cache key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR``, when the environment sets it, is the
whole answer and nothing is set in code (JAX reads the variable itself);
otherwise the cache sits at ``<checkout>/cache/xla`` (``cache/`` is
gitignored). Called by ``chip_smoke.py``, ``bench.py``,
``utils.cli.parse_config`` (every experiment CLI) and ``tests/conftest.py``.

The same call installs, once, the listeners that count what JAX reports of
its own compiles (``jax.monitoring``) into the metrics registry:
``compile.count`` (backend compiles, loads from the persistent cache among
them), ``compile.trace_s`` / ``compile.lower_s`` / ``compile.backend_s``
(seconds; a nested trace is counted in its caller's too),
``compile.cache_hits`` / ``compile.cache_misses`` (the persistent cache's).
:func:`compile_totals` returns them: ``fit()`` reads ``compile.count`` from
it for ``train.recompile``, and the experiment CLIs log it whole as
``"compiles"`` beside ``"stages"`` (how much of a launch was trace, lower and
compile, and whether the persistent cache served it). With the span tracer
on, every such event is also one span, ``compile.trace`` / ``compile.lower``
/ ``compile.backend``, at JAX's own start and end, with JAX's ``fun_name``.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_CHECKOUT, "cache", "xla")


# jax.monitoring event -> (registry counter of its seconds, span name)
_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("compile.trace_s", "compile.trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("compile.lower_s", "compile.lower"),
    "/jax/core/compile/backend_compile_duration":
        ("compile.backend_s", "compile.backend"),
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}
_listening = False


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one location and
    return that directory; install the compile listeners (idempotent).
    Call before the first compile."""
    _listen_to_compiles()
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def compile_totals() -> dict:
    """The ``compile.*`` counters as the listeners have them now (empty
    where :func:`enable_compile_cache` was never called, or nothing
    compiled yet)."""
    from dgraph_tpu.obs.metrics import default_registry

    counters = default_registry.snapshot()["counters"]
    return {k: v for k, v in counters.items() if k.startswith("compile.")}


def _listen_to_compiles() -> None:
    global _listening
    if _listening:
        return
    _listening = True
    import jax.monitoring

    from dgraph_tpu.obs import spans
    from dgraph_tpu.obs.metrics import default_registry

    def on_duration(event, secs, **_):
        names = _DURATION_EVENTS.get(event)
        if names:
            default_registry.counter(names[0], secs)
            if names[0] == "compile.backend_s":  # one a backend compile
                default_registry.counter("compile.count")

    def on_time_span(event, start, end, fun_name="", **_):
        names = _DURATION_EVENTS.get(event)
        if names:
            spans.record_span(names[1], start, end, fun_name=fun_name)

    def on_event(event, **_):
        name = _CACHE_EVENTS.get(event)
        if name:
            default_registry.counter(name)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_time_span_listener(on_time_span)
    jax.monitoring.register_event_listener(on_event)
