"""Where the entry points keep JAX's persistent compilation cache.

`enable_compile_cache` is called by the entry points (``chip_smoke.py``,
`repro.launch.serve`, `repro.launch.train`, the benchmarks), never when the
package is imported, so library users and the tests stay cache-free.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout root (src/repro/launch/ -> three levels up). A fixed path: the
# cache directory is part of what makes a later process find its entries.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is set here. Otherwise the cache goes to ``.jax_cache/`` at the
    root of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)

