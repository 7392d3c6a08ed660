"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles for a device (``chip_smoke.py``,
``repro.launch.serve``, the benchmarks) calls ``enable_compile_cache()``
before its first compile, so a second run in the same checkout reads the
programs the first one wrote.

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself; nothing is set in
  code.
* otherwise: ``<repo>/.jax_cache`` (git-ignored).  The path is fixed on
  purpose: the cache is keyed by it, so a per-run temp, pid or time-based
  directory would never hit.

Either way every program is cached, not only those that took jax's default
one second to compile: a serving run also compiles dozens of small
programs (parameter init, casts), and with the default a warm
``chip_smoke.py`` run on one v5e still compiled for 21 s of the cold run's
53 s.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
