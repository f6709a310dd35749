"""JAX's persistent compilation cache, placed from outside or at the repo root.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and no other
directory is set here.  Otherwise the cache goes to ``.jax_cache/`` at the
root of the checkout: a fixed path, because the path is part of what a later
run must find again.  Nothing runs at import; the entry points
(``chip_smoke.py``, ``python -m repro.launch.serve``, ``python -m
benchmarks.run``) call :func:`enable_compile_cache` once, before their first
compile.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every program, however quickly it
    compiles, print the directory it uses, and return that directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"[compile-cache] {path}", flush=True)
    return path
