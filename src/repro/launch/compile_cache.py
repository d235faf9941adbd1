"""JAX's persistent compilation cache for the entry points.

A 32-layer step program takes the TPU compiler from a quarter of a
minute to over a minute; the cache lets a second process (or a second
run of the same command) load it instead. Its key includes the cache
path, so the path is fixed: ``.jax_cache/`` at the root of the checkout,
found from this file's own location, unless ``JAX_COMPILATION_CACHE_DIR``
is set, in which case JAX reads that variable itself and nothing here
overrides it.

Entry points call :func:`enable_compile_cache` once, before their first
compile (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``). Tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

#: the checkout root: src/repro/launch/compile_cache.py -> parents[3]
REPO_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> Optional[str]:
    """The directory this module would set: None when
    ``JAX_COMPILATION_CACHE_DIR`` is set (JAX already uses it), else the
    fixed ``.jax_cache/`` inside the checkout."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return str(REPO_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir` (when
    the environment has not chosen one) and return the directory in
    use."""
    import jax

    path = compile_cache_dir()
    if path is None:
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", path)
    return path
