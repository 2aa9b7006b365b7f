"""Where JAX keeps its persistent compilation cache.

Entry points that compile large programs (``chip_smoke.py``, the
benchmarks) call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
helper sets no other path, so the cache can be placed from outside.
Otherwise the cache goes to ``.jax_cache/`` at the root of the checkout: a
fixed path, because the path is part of the cache key and a directory that
moves never hits.  ``.gitignore`` lists it.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: Cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is not set.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
