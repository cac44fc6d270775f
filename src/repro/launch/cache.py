"""JAX's persistent compilation cache, placed by an entry point.

The kernel programs key on their static shapes, so a cold process pays
every compile again. An entry point (``chip_smoke.py``,
``benchmarks/run.py``) calls ``enable_compile_cache()`` before its first
jit; the library never does it on import.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
there and nothing else is set here. Otherwise the cache lives at the
fixed path ``<checkout>/.jax_cache`` (gitignored): the path is part of
the cache's key, so a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Every
    compile is kept, since the scan kernels compile in well under JAX's
    default one-second floor."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir()
