"""JAX's persistent compilation cache, placed the same way by every entry
point (``chip_smoke.py``, ``repro.launch.train`` and the benchmarks).

A cold run on a fresh machine otherwise compiles every step program from
scratch. The directory is part of what makes a cache hit, so it never
comes from a tempdir, a pid or the clock.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (listed in .gitignore); this file is
# <checkout>/src/repro/launch/cache.py
DEFAULT_DIR = str(Path(__file__).absolute().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and the
    cache lives there: no other path is set. Otherwise the cache goes to
    ``DEFAULT_DIR`` inside the checkout."""
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
