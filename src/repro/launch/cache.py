"""Where JAX keeps its persistent compilation cache, for every entry point.

A cold run on the chip spends minutes compiling; the persistent cache lets
the next process skip that.  The directory is part of the cache's key, so it
must not move from run to run: no temporary, pid- or time-derived path.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache"]

# <checkout>/src/repro/launch/cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing.  Otherwise, on an accelerator, the cache is
    ``<checkout>/.jax_cache``; on the CPU backend no cache is set (CPU
    compiles are short, and XLA:CPU logs an error-level warning on every
    cache hit).  Call it before the process compiles anything; returns ""
    when no cache is in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    if jax.default_backend() == "cpu":
        return ""
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
