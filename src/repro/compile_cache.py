"""Where JAX keeps its persistent compilation cache for this repository.

A cold start at the paper's scale compiles for minutes (the staged update and
cleanup programs alone take about two), so entry points that compile the
main path turn the persistent cache on before their first compile. Tests do
not: they compile many small programs that are not worth keeping.
"""

from __future__ import annotations

import os
import pathlib

import jax

# Fixed, inside the checkout (listed in .gitignore): the path is part of what
# a cache hit needs, so it must not move between runs.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    If JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and nothing is
    set here. Otherwise the cache goes to `DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
