"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compilation_cache` from their ``main`` (never at
import), before the first compile.  A directory given from outside through
``JAX_COMPILATION_CACHE_DIR`` wins, and JAX reads it itself; otherwise the
cache lives at ``<checkout>/.jax_cache``, a path fixed by this package's own
location so that every run of one checkout finds the entries of the last.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir
