"""One place that decides where JAX keeps its persistent compile cache.

Entry-point scripts (``chip_smoke.py``, ``benchmark/run.py``, ``tools/``,
``examples/``) call :func:`enable_compile_cache` before their first jit.
Library code never does: a library that picks a cache directory for its
caller cannot be overruled by the environment.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# the directory is part of the cache key, so it must not move between
# runs: a fixed path inside the checkout (git-ignored), never one built
# from a temp name, a pid or a time
_CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Return the compile-cache directory in use, configuring it only
    when the environment has not: with ``JAX_COMPILATION_CACHE_DIR``
    set, JAX reads the variable itself and nothing is set in code;
    otherwise the cache goes to ``<checkout>/.jax_cache``."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
