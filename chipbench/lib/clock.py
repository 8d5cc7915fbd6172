"""Compilation as JAX reports it, and the persistent compilation cache."""
from __future__ import annotations

import os

# The cache's home when JAX_COMPILATION_CACHE_DIR is not set: a fixed path
# inside the checkout (the path is part of the cache's key, so it must not
# move between runs).
CACHE_SUBDIR = ".jax_cache"


def use_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache for every program, small
    ones included; returns its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, CACHE_SUBDIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Backend compiles (count and seconds) and persistent-cache hits and
    misses, from JAX's own monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self) -> "CompileClock":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1
        elif event == self.MISS:
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
