"""Where JAX keeps its persistent compilation cache.

The serving engine compiles many programs (prefill length buckets x batch
buckets x decode megasteps), so a process that starts cold spends much of
its start-up compiling. JAX reads ``JAX_COMPILATION_CACHE_DIR`` from the
environment by itself; when it is set this module leaves it alone. When it
is not, the cache goes to one fixed directory inside the checkout
(``.jax_cache``, git-ignored): the path is part of what the cache is keyed
on, so it never depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory. Call before the first compile of the process."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
