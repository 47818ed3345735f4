"""jax's own compile counters, read through ``jax.monitoring``.

Every compilation that can use the persistent cache fires
``compile_requests_use_cache``; the ones the cache answered also fire
``cache_hits``. A request inside the measured window is a shape the
warm-up missed, whether or not the cache answered it. (The method is
``chip_smoke.CompileCounter``'s, copied: the yardstick does not import
the program's tools.)
"""
from __future__ import annotations


class CompileCounter:
    def __init__(self):
        import jax

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return {"requests": self.requests, "hits": self.hits}
