"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the directory, so a path that moves between runs
never hits: it is either what ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads
that variable itself, and nothing is set here), or ``.jax_cache`` at the
root of the checkout, which ``.gitignore`` lists.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile
    and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
