"""Where JAX's persistent compilation cache lives for the entry points.

Called by the programs that run on a chip (``chip_smoke.py``,
``benchmarks/run.py``) before they compile anything; never at library
import. A cache directory is part of each entry's key, so it must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads the variable itself, and no other path is set in code), else a
fixed ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(checkout: str) -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``checkout`` is the root of the repository checkout the caller runs
    from; it is only used when ``JAX_COMPILATION_CACHE_DIR`` is unset."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = os.path.join(os.path.abspath(checkout), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only those that took over a second: the
    # chunk programs of a mining run are many and individually quick
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
