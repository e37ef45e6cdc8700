"""JAX's persistent compilation cache, in one fixed place.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, the benchmarks)
call ``enable_compile_cache()`` from their ``main``; importing this module
changes nothing. The cache key includes the directory, so the directory
must not move between runs: it is either ``JAX_COMPILATION_CACHE_DIR``
(which JAX reads itself) or ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses that
    directory and nothing is changed here."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
