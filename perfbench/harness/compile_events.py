"""Compile seconds and counts from ``jax.monitoring`` duration events
(the same events ``chip_smoke.py`` reads): tracing and lowering, which
no cache removes, and the backend compile or persistent-cache read.
``mark()`` lets a runner ask what happened since a point in time, so
that a compilation inside the measured window is seen.
"""

from __future__ import annotations

import collections
import threading

TRACE_LOWER_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Sums of the duration events, by name, over all threads."""

    def __init__(self):
        self._lock = threading.Lock()
        self._seconds = collections.Counter()
        self._counts = collections.Counter()

    def install(self) -> "CompileLog":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, seconds, **kw):
        with self._lock:
            self._seconds[event] += seconds
            self._counts[event] += 1

    def mark(self) -> dict:
        with self._lock:
            return {"seconds": self._seconds.copy(),
                    "counts": self._counts.copy()}

    def since(self, mark: dict) -> dict:
        now = self.mark()
        return {"seconds": now["seconds"] - mark["seconds"],
                "counts": now["counts"] - mark["counts"]}
