"""JAX's persistent compilation cache, placed the same way by every entry point.

A restarted fleet worker, a resumed bulk job or the next smoke run would
otherwise re-lower and re-compile every rung of its bucket ladder: pure
cold-start tax, since the shapes are identical across restarts. Every
entry point (``serve.py``, ``fleet.worker``, ``chip_smoke.py``, the bench
scripts) calls :func:`enable_compile_cache` before its first compile.

The directory is part of what makes an entry findable again, so it never
moves between processes or runs:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  module sets nothing;
* otherwise the cache is ``<checkout>/.jax_cache`` (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compilecache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
