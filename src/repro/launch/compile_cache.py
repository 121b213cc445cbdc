"""JAX's persistent compilation cache, placed once for every entry point.

Entry points (``launch/train.py``, ``launch/fleet.py``, ``chip_smoke.py``)
call ``enable_compile_cache()`` before their first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets no
other directory. Otherwise the cache lives at a fixed path inside the
checkout (``<repo>/.jax_cache``, git-ignored), never under a temporary or
per-process name: the next process must find what this one wrote. A
failure to set the cache up is an error, never a silent cold run.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every executable; returns its dir."""
    import jax
    from_env = os.environ.get(ENV_VAR)
    path = from_env or str(DEFAULT_DIR)
    os.makedirs(path, exist_ok=True)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
