"""Where compiled programs are kept between runs.

Every entry point (the recipes, ``scripts/serve_lm.py``, ``bench.py``,
``chip_smoke.py``, the test session) calls ``enable_compile_cache`` once
before its first compile.  The directory is part of the cache key, so it
never moves: no temporary name, no pid, no time.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache — git-ignored.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it by itself and this
    function sets no directory in code, so a cache placed from outside
    (a benchmark machine's warm cache) is the one used.  Unset: the one
    fixed path inside the checkout."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
