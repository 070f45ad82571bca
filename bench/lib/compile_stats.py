"""Backend compiles and persistent-cache traffic, from JAX's own
monitoring events."""
from __future__ import annotations


class CompileStats:
    """``seconds`` sums backend compile time (a program read back from
    the persistent cache adds none); ``requests`` counts every compile
    that consulted the cache, ``hits`` those it answered and ``writes``
    the programs it stored (JAX stores one only when it compiled for at
    least ``jax_persistent_cache_min_compile_time_secs``)."""

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.compiles = 0
        self.requests = self.hits = self.writes = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "compile_requests": self.requests, "cache_hits": self.hits,
                "cache_writes": self.writes}
