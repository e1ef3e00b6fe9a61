"""JAX's persistent compilation cache for the accelerator entry points.

Scripts that drive the system on a chip (``chip_smoke.py``, the
benchmarks, the examples) call :func:`enable_compile_cache` once at start;
library code and the tests never do.  The directory is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself, and
nothing here overrides it), else :data:`CACHE_DIR`, a fixed path inside
the checkout — the path is part of every cache key, so a temp or
per-process directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``)
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  The
    minimum compile time drops to zero so that the ~1 s Pallas kernel
    compiles are kept, not just the long ones."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
