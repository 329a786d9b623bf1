"""Counts XLA backend compiles, with the time each ended and the thread
that compiled (an engine's worker thread is ``engine-<instance>``).

Copied from the program's ``chip_smoke._compile_clock`` (a
``jax.monitoring`` listener on the backend-compile duration event). A
persistent-cache hit still fires the event, timed as its retrieval, so a
program loaded from the cache inside the window counts too.
"""
from __future__ import annotations

import threading
import time
from collections import Counter
from typing import Dict, List, Tuple

EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    def __init__(self):
        import jax
        self._lock = threading.Lock()
        # (end time, seconds, thread name)
        self.events: List[Tuple[float, float, str]] = []
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        if event == EVENT:
            with self._lock:
                self.events.append((time.perf_counter(), float(duration),
                                    threading.current_thread().name))

    def between(self, t0: float, t1: float) -> Tuple[int, float]:
        """(compiles that ended in [t0, t1], their seconds)."""
        with self._lock:
            inside = [d for t, d, _ in self.events if t0 <= t <= t1]
        return len(inside), sum(inside)

    def by_thread(self, t0: float, t1: float) -> Dict[str, int]:
        """Compiles that ended in [t0, t1], by the thread that compiled."""
        with self._lock:
            return dict(Counter(n for t, _, n in self.events
                                if t0 <= t <= t1))

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._listen)
