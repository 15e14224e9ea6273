"""Where JAX keeps its persistent compilation cache, and how often it compiles.

Entry points call :func:`use_compilation_cache` from their ``main`` (never at
import), before the first compile.  A directory given from outside through
``JAX_COMPILATION_CACHE_DIR`` wins, and JAX reads it itself; otherwise the
cache lives at ``<checkout>/.jax_cache``, a path fixed by this package's own
location so that every run of one checkout finds the entries of the last.

It also starts the compile counter (:func:`count_compiles`): a
``jax.monitoring`` listener that JAX calls only when it builds an executable,
so the hot path pays nothing.  :func:`compile_stats` reads it.
"""
from __future__ import annotations

import os
import threading

import jax
from jax import monitoring

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

# JAX's event names (jax/_src/dispatch.py, compiler.py, compilation_cache.py)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

# One count per process, as JAX's listeners are; threads may compile at once.
_stats = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
          "cache_misses": 0, "cache_retrieval_s": 0.0}
_lock = threading.Lock()
_counting = False


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == BACKEND_COMPILE:
        with _lock:
            _stats["compiles"] += 1
            _stats["compile_s"] += seconds
    elif event == CACHE_RETRIEVAL:
        with _lock:
            _stats["cache_retrieval_s"] += seconds


def _on_event(event: str, **_) -> None:
    if event in (CACHE_HIT, CACHE_MISS):
        with _lock:
            _stats["cache_hits" if event == CACHE_HIT else "cache_misses"] += 1


def count_compiles() -> None:
    """Start counting compiles in this process; a second call does nothing."""
    global _counting
    with _lock:
        if not _counting:
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            _counting = True


def compile_stats() -> dict:
    """A snapshot of the counts since :func:`count_compiles`.

    ``compiles``: executables built, each a backend compile or a load from the
    persistent cache (an in-memory hit of a jit's own cache is not one);
    ``compile_s``: their seconds, a load's retrieval included;
    ``cache_hits``: loads from the persistent cache, which took
    ``cache_retrieval_s``; ``cache_misses``: executables compiled and written
    to it.
    """
    with _lock:
        return dict(_stats)


def use_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its directory, start the
    compile counter, and return the directory."""
    count_compiles()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir
