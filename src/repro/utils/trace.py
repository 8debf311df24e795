"""Host spans of the serving path, and the collector's.

A span is a `jax.profiler.TraceAnnotation`.  With a profiler session open
it lands on the trace's host plane, on the same clock as the device
plane, so a reader can put each idle stretch of the device down to what
the host was doing in it.  With no session open it costs one TraceMe
check.  Nothing is buffered or exported here: the profiler session is
the recorder.

The spans (PERF.md, section 3, names the metric each feeds):

- ``dse.form``: batch forming (`ServeFrontend` former, `DSEServer.step`);
- ``dse.dispatch_wait``: the dispatcher waiting for a formed batch;
- ``dse.execute``: the engine call of one batch (`DSEServer.execute_batch`),
  holding ``dse.gfwd`` (input encoding and the G forward's enqueue),
  ``dse.select`` (the fused select up to its enqueue), ``dse.sync`` (the
  host waiting for the device) and ``dse.host_tail`` (float64 rescoring
  and Selection building);
- ``dse.publish``: cache, responses and future callbacks of one batch;
- ``py.gc``: a collection of generation 1 or 2 (`GcSpans`).

The spans of one batch carry its sequence number as ``batch`` metadata.
"""
from __future__ import annotations

import contextlib
import gc
import threading
from typing import Iterator, Optional

import jax

_BOUND = threading.local()


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """The host span ``name``.  It carries ``ids``, and the ids that
    `bind` holds on this thread, as trace metadata."""
    bound = getattr(_BOUND, "ids", None)
    return jax.profiler.TraceAnnotation(name, **({**bound, **ids} if bound
                                                 else ids))


@contextlib.contextmanager
def bind(**ids) -> Iterator[None]:
    """Every span this thread opens inside the block carries ``ids``: the
    engine's spans so carry the batch id that only the server knows."""
    prev = getattr(_BOUND, "ids", None)
    _BOUND.ids = {**(prev or {}), **ids}
    try:
        yield
    finally:
        _BOUND.ids = prev


class GcSpans:
    """A ``py.gc`` span around each collection of generation 1 or 2 while
    installed (generation 0 runs every few hundred allocations and is
    short).  A collection runs on one thread with the interpreter lock
    held, so its start and stop arrive in pairs."""

    def __init__(self):
        self._open: Optional[jax.profiler.TraceAnnotation] = None

    def install(self) -> None:
        gc.callbacks.append(self._on_gc)

    def remove(self) -> None:
        gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] < 1:
            return
        if phase == "start":
            self._open = span("py.gc", generation=info["generation"])
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
