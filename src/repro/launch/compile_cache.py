"""JAX's persistent compilation cache, placed by the entry points.

Only entry points call ``use_compile_cache`` (``chip_smoke.py``,
``repro.launch.ppr_run``, ``benchmarks.run``), before anything compiles;
importing the library never touches the cache setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout: the directory is part of what a later
# run's lookups hit, so it never comes from a temp name, a pid or the time.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_WRITE = "/jax/compilation_cache/cache_misses"   # recorded when an entry is written


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is set here; otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


class CacheEvents:
    """Counts persistent-cache hits and writes in this process."""

    def __init__(self):
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_kw) -> None:
        if name == _HIT:
            self.hits += 1
        elif name == _WRITE:
            self.writes += 1
