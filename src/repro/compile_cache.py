"""JAX's persistent compilation cache, in one place for every entry point.

``chip_smoke.py``, ``python -m repro.serve`` and ``benchmarks/run.py`` call
``enable_compile_cache()`` before their first compile, so a second process
on the same machine reads the executables the first one wrote instead of
compiling them again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and this
helper sets nothing. Otherwise the cache goes to ``.jax_cache/`` at the
root of the checkout holding this package (listed in ``.gitignore``): a
fixed path, because the path is part of the cache key.
"""
from __future__ import annotations

import os
from pathlib import Path

#: Default cache directory: ``<checkout>/.jax_cache`` (src layout).
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
