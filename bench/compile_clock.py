"""Counts JAX's compile work (copied from the repo's ``chip_smoke.py``).

Sums the durations of the trace, lowering and backend-compile events
(a backend compile that the persistent cache answers still reports one)
and counts persistent-cache hits, so that compiles inside the measured
window show.
"""
from __future__ import annotations

import jax

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


class CompileClock:
    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0          # backend compiles, cache hits included
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event in EVENTS:
            self.seconds += duration
            if event == EVENTS[2]:
                self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
