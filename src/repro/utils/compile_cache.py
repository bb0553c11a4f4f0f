"""JAX's persistent compilation cache, kept in one fixed place.

A cold process can spend much of a short run compiling. JAX keeps
compiled programs across processes in a persistent cache whose directory
is part of every entry's key, so the directory must not move between
runs. `enable_compile_cache` is the one place that chooses it; the
launch CLIs, `chip_smoke.py` and the benchmarks call it before they
compile anything (the cache is looked up once per process, at the first
compilation).
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    With JAX_COMPILATION_CACHE_DIR set, JAX reads that variable itself
    and nothing is set here. Otherwise the cache is the repository's
    `.jax_cache/`.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return REPO_CACHE
