"""Where the persistent XLA compilation cache lives — decided in one place.

The cache directory is part of the cache key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR``, when the environment sets it, is the
whole answer and nothing is set in code (JAX reads the variable itself);
otherwise the cache sits at ``<checkout>/cache/xla`` (``cache/`` is
gitignored). Called by ``chip_smoke.py``, ``bench.py``,
``utils.cli.parse_config`` (every experiment CLI) and ``tests/conftest.py``.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_CHECKOUT, "cache", "xla")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one location and
    return that directory. Call before the first compile."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
