"""Count the executables JAX obtains, so a run can show that nothing
compiled inside its measured window.

``/jax/core/compile/backend_compile_duration`` fires once for every
executable a process obtains, whether the backend compiled it or loaded it
from the persistent cache; ``/jax/compilation_cache/cache_hits`` fires for
the loads alone.  Adapted from ``chip_smoke.CompileClock``.
"""

from __future__ import annotations

import threading

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
COMPILE_PHASES = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    BACKEND_COMPILE,
)
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Running totals of executables obtained, cache loads among them, and
    seconds spent tracing, lowering and compiling."""

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.programs = 0
        self.cache_loads = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in COMPILE_PHASES:
            with self._lock:
                self.seconds += duration
                if event == BACKEND_COMPILE:
                    self.programs += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            with self._lock:
                self.cache_loads += 1

    def snapshot(self) -> tuple:
        with self._lock:
            return self.programs, self.cache_loads, self.seconds
