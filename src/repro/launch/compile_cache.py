"""JAX's persistent compile cache, kept in one fixed place per checkout.

A directory that moves between runs never hits, so the cache lives where
``JAX_COMPILATION_CACHE_DIR`` says when that is set (JAX reads it itself and
nothing here overrides it), and otherwise at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import collections
import os
import pathlib

import jax

#: the checkout's cache directory, used when the environment names none
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_counts: collections.Counter = collections.Counter()


def _count(event: str, **_kwargs) -> None:
    if event in (_HIT, _MISS):
        _counts[event] += 1


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return its directory.

    Call before the first compile. Idempotent."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    if not _counts:
        _counts[_HIT] = _counts[_MISS] = 0
        jax.monitoring.register_event_listener(_count)
    return jax.config.jax_compilation_cache_dir


def cache_stats() -> dict:
    """Persistent-cache hits and misses since :func:`enable_compile_cache`."""
    return {"hits": _counts[_HIT], "misses": _counts[_MISS]}
