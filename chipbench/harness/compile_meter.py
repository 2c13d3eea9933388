"""Compile seconds and persistent-cache traffic, from JAX's monitoring
events (copied from ``chip_smoke.py``'s ``CompileMeter``)."""
from __future__ import annotations

import os

from chipbench.harness.registry import ROOT

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def enable_cache(jax) -> str:
    """The persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache``, a path that never moves.  Every
    program is cached, however quickly it compiled, so a second run of a
    cell compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileMeter:
    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration_secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}

    def programs_loaded(self) -> int:
        """Programs compiled or read from the cache so far (JAX reports a
        backend compile for each, a cache hit included)."""
        return self.compiles
