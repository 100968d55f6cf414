"""Where jax keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax reads it itself and
this module sets nothing.  Otherwise the cache goes to one fixed directory
inside the checkout, ``<repo>/.jax_cache`` (git-ignored).  The path is
part of every cache key, so it is never built from a temporary name, a
process id or the time: a path that moves never hits.
"""

from __future__ import annotations

import os

import jax

#: the checkout root: src/repro/launch/cache.py -> three levels up
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")


def use_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory (see the
    module docstring) and return that directory.  Entry points call this
    once, before their first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
