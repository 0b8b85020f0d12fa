"""JAX's persistent compilation cache, kept in one fixed directory.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: JAX reads it
and nothing here overrides it. Otherwise ``use_compile_cache`` points the
cache at the caller's fixed directory (``chip_smoke.py`` and
``benchmarks/run.py`` pass ``<checkout>/.jax_cache``, which ``.gitignore``
lists). The path must not move between runs: a directory built from a
temp name, a PID or the time never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(default_dir: str | os.PathLike) -> str:
    """Turn on the persistent compilation cache; return its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(pathlib.Path(default_dir).resolve())
        jax.config.update("jax_compilation_cache_dir", path)
    return path
