"""Counts what the host does besides the work inside a window: the
executables XLA hands out and the jaxprs JAX traces (through
`jax.monitoring`), and the pauses of Python's garbage collector."""
from __future__ import annotations

import gc
import threading
import time

import jax

# Fires once per executable acquired, whether compiled afresh or loaded
# from the persistent cache; a cache load also fires CACHE_HIT.
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
# Fires once per function traced to a jaxpr: a jitted call whose cached
# trace missed.
JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"


class CompileCounter:
    """Counts executables acquired (any thread) and the persistent-cache
    hits among them, jaxprs traced, and garbage-collector pauses. An
    executable or a trace inside a measured window means a shape was not
    warmed up."""

    def __init__(self):
        self._lock = threading.Lock()
        self.acquired = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self.names = []           # program name of each acquisition
        self.traced = 0
        self.trace_seconds = 0.0
        self.gc_pauses = []       # (generation, seconds) of each collection
        self._gc_start = None

    @property
    def compiled(self) -> int:
        """Executables compiled afresh (not found in the cache)."""
        return self.acquired - self.cache_hits

    def _duration(self, event: str, duration_secs: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            with self._lock:
                self.acquired += 1
                self.seconds += duration_secs
                self.names.append(str(kw.get("fun_name", "?")))
        elif event == JAXPR_TRACE:
            with self._lock:
                self.traced += 1
                self.trace_seconds += duration_secs

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.gc_pauses.append((info["generation"],
                                   time.perf_counter() - self._gc_start))
            self._gc_start = None

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        gc.callbacks.remove(self._gc)
