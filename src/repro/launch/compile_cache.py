"""Persistent JAX compilation cache for the entry points.

``launch/serve.py``, ``chip_smoke.py`` and ``benchmarks/run.py`` call
:func:`enable` before they compile anything. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
is changed. Otherwise the cache goes to one fixed directory inside the
checkout (``.jax_cache/``, git-ignored): a cache entry is found again
only under the same path, so the path never comes from a temporary
name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root is parents[3].
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir(environ: Mapping[str, str] = os.environ) -> str:
    """The directory the cache uses: ``$JAX_COMPILATION_CACHE_DIR`` when
    set and non-empty, else the checkout's fixed ``.jax_cache/``."""
    return environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
