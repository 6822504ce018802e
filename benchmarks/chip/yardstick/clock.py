"""Counts and times JAX's compilations, from any thread.

Copied from the program's ``chip_smoke.py``.  Set-up reports the seconds
JAX spent tracing, lowering and compiling (or reading the persistent
cache); the window reports how many programs were lowered inside it, which
should be none."""
from __future__ import annotations

import threading

import jax

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.secs = 0.0
        self.lowered = 0
        self.compiled = 0
        self.cache_hits = 0

    def on_duration(self, event: str, secs: float, **_) -> None:
        if not event.startswith("/jax/core/compile/"):
            return
        with self._lock:
            self.secs += secs
            if event == LOWER_EVENT:
                self.lowered += 1
            elif event == BACKEND_EVENT:
                self.compiled += 1

    def on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def install(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self
