"""Where JAX's persistent compilation cache lives.

Entry points (``launch.serve``, ``launch.train``, ``chip_smoke.py``) call
:func:`enable_compile_cache` once at start-up; importing this module changes
nothing, and the tests never turn the cache on.
"""

from __future__ import annotations

import os

import jax

# <repo>/.jax_cache: a fixed path, because a cache directory that moves
# between runs is never hit again
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    no other directory is configured. Otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
