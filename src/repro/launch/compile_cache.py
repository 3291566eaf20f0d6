"""Where JAX keeps its persistent compilation cache.

The launchers (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once at start-up; nothing calls it at
import time. A cache entry is keyed partly by its directory, so the
location is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (JAX reads that variable itself, and nothing else is set), otherwise
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                        "..", ".."))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
