"""Counts XLA backend compiles, with the time each ended.

Copied from the program's ``chip_smoke._compile_clock`` (a
``jax.monitoring`` listener on the backend-compile duration event). A
persistent-cache hit still fires the event, timed as its retrieval, so a
program loaded from the cache inside the window counts too.
"""
from __future__ import annotations

import threading
import time
from typing import List, Tuple

EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.events: List[Tuple[float, float]] = []   # (end time, seconds)
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        if event == EVENT:
            with self._lock:
                self.events.append((time.perf_counter(), float(duration)))

    def between(self, t0: float, t1: float) -> Tuple[int, float]:
        """(compiles that ended in [t0, t1], their seconds)."""
        with self._lock:
            inside = [d for t, d in self.events if t0 <= t <= t1]
        return len(inside), sum(inside)

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._listen)
