"""Where the persistent XLA compile cache lives.

One rule for every process that compiles (worker, ``run``, ``chipbench/``,
tools, ``chip_smoke.py``): ``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself,
wins and nothing is set in code; without it the cache is
``<checkout>/.jax_cache``. The path is part of the cache key, so it must
not move between runs. JAX's own 1.0 s minimum compile time decides what
is worth keeping.
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory in use. Imports nothing, so a parent process that
    must stay off JAX can ask where its children will cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)


def configure_compile_cache() -> str:
    """Point JAX at the cache directory (before the first compile) and
    return it."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
