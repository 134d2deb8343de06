"""The one place that decides where JAX's persistent compile cache lives.

Every entry point that compiles (``train.main``, ``train_diloco.main``,
``fleet.replica.main``, ``chip_smoke.py``, ``bench.py``, the measuring
scripts) calls :func:`enable_compile_cache` once, from its ``main``. It is
never called at import, so the test suite's compiles stay uncached.
"""

from __future__ import annotations

import os

import jax

# the path is part of the cache key, so it must not move between runs: a
# fixed, git-ignored directory at the root of the checkout
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """-> the cache directory in effect.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this sets no
    other directory. Unset: :data:`REPO_CACHE_DIR`."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
