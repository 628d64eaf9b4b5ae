"""Persistent XLA compile cache, switched on by every entry point.

One rule, so that every process of a run — launcher, workers, replica
servers, tests, ``chip_smoke.py``, ``benchmark/`` — shares one cache and a
second run deserializes executables instead of recompiling (the
flagship train step is a multi-minute compile):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; no
  directory is set in code, so whoever runs the program places the
  cache.
- unset: ``<checkout>/.jax_cache`` — a fixed path, because the path is
  part of the cache key and a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

_CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Switch the persistent compilation cache on (see module
    docstring for where it lives); returns the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return jax.config.jax_compilation_cache_dir
