"""Count the programs JAX builds (a copy kept with the benchmark, so that
the yardstick cannot change under it), and the collector's pauses.

``/jax/core/compile/jaxpr_to_mlir_module_duration`` fires once for every
program lowered: each new program, whether XLA then compiles it or loads
it from the persistent cache.  ``backend_compile_duration`` fires for a
real compilation only.  jax.monitoring has no way to remove a listener,
so one is installed on first use and counts for the life of the process.
"""
from __future__ import annotations

import gc
import time

_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        import jax.monitoring
        self.count = 0          # programs built (compiled or loaded)
        self.compiled = 0       # of them, compiled by XLA
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == _LOWER:
            self.count += 1
        elif event == _COMPILE:
            self.compiled += 1


class GcPauses:
    """Pauses of the cyclic collector while installed: the longest, and
    how many full (generation 2) collections ran."""

    def __init__(self):
        self.longest_s, self.full, self._t = 0.0, 0, 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
            return
        self.longest_s = max(self.longest_s, time.perf_counter() - self._t)
        self.full += info["generation"] == 2

    def remove(self) -> dict:
        gc.callbacks.remove(self._on_gc)
        return {"gc_longest_pause_ms": 1e3 * self.longest_s,
                "gc_full_collections": self.full}
