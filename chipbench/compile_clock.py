"""jax's own compile-pipeline events, accumulated while the block is open.
A copy of ``chip_smoke.CompileClock`` (the original stays with the start-up
check): the backend event wraps the persistent-cache lookup, so on a warm cache
it measures the retrieval; tracing and lowering are python-side, never cached.
"""
from __future__ import annotations

import threading

_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")


class CompileClock:
    """``lap()`` returns what was added since the last lap.  Listeners fire on
    whichever thread compiles (the server's lanes included), hence the lock."""

    def __init__(self):
        self._lock = threading.Lock()
        self._tot = self._zero()

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
        return False

    @staticmethod
    def _zero():
        return {"compile_s": 0.0, "trace_lower_s": 0.0, "compiles": 0,
                "cache_hits": 0, "cache_misses": 0}

    def _on_duration(self, event, secs, **_kw):
        with self._lock:
            if event == _BACKEND_EVENT:
                self._tot["compile_s"] += secs
                self._tot["compiles"] += 1
            elif event in _TRACE_EVENTS:
                self._tot["trace_lower_s"] += secs

    def _on_event(self, event, **_kw):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self._tot["cache_hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self._tot["cache_misses"] += 1

    def lap(self):
        with self._lock:
            out, self._tot = self._tot, self._zero()
        out["compile_s"] = round(out["compile_s"], 3)
        out["trace_lower_s"] = round(out["trace_lower_s"], 3)
        return out
