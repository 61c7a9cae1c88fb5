"""Counts of what JAX compiled or loaded, so that a window that compiles
shows. (``CacheEvents`` is ``chip_smoke.py``'s, with the compile requests
beside the persistent cache's hits.)"""

from __future__ import annotations

import jax


class CompileEvents:
    """``requests``: programs handed to the backend (each either compiles or
    loads from the persistent cache); ``hits``: loaded from the cache;
    ``seconds``: time spent in either."""

    def __init__(self):
        self.requests = self.hits = 0
        self.seconds = 0.0
        self.longest = []            # the five longest, seconds
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_secs(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs
            self.longest = sorted(self.longest + [round(secs, 1)])[-5:]

    def snapshot(self):
        return {"requests": self.requests, "hits": self.hits,
                "seconds": self.seconds, "longest": list(self.longest)}
