"""The system under test, built as ``launch/serve.py`` builds its thread-mode
served path, and the closed loops that drive it through ``AsyncServer.submit``.

One one-chip replica per device given: ``InstancePool`` with engines
``inst0..inst{n-1}``, each committing its own copy of one parameter tree to
its device and running the paper's JCT profile, as ``make_pool`` places
them -> ``AsyncServer`` with the configuration's router, MIL admission from
the engines' ``MemoryModel``, idempotent retry and the JCT-deadline
watchdog, as ``serve_trace`` sets them. Options that a deployment sets (the
prefix-cache size, the watchdog's floor, the profiled lengths, engine
options) come from the configuration's file.
"""
from __future__ import annotations

import dataclasses
import math
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import jax

from repro.configs.base import ModelConfig
from repro.core.engine import EngineConfig, PrefillOnlyEngine
from repro.core.kv_policy import MemoryModel
from repro.runtime.fault_tolerance import InstancePool, JCTDeadlineWatchdog
from repro.serving import (AdmissionController, AsyncServer, Rejected,
                           RetryPolicy, SpanTracer, get_router)

STEP_SPAN = "bench_step"


class TracedEngine(PrefillOnlyEngine):
    """The program's engine with each step that has work inside a profiler
    span, opened from the benchmark's side of the call. A step that raises
    is reported on standard error (the server fails the instance on it)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.errors: List[str] = []

    def step(self):
        if not self.queue:
            return super().step()
        try:
            with jax.profiler.TraceAnnotation(STEP_SPAN):
                return super().step()
        except Exception as e:
            self.errors.append(f"{type(e).__name__}: {str(e)[:2000]}")
            raise


def model_config(cfg: Dict) -> ModelConfig:
    return ModelConfig(**cfg["model"])


def engine_config(cfg: Dict, cache_tokens: int) -> EngineConfig:
    opts = {k: tuple(v) if isinstance(v, list) else v
            for k, v in cfg.get("engine", {}).items()}
    return EngineConfig(cache_capacity_tokens=cache_tokens, **opts)


@dataclasses.dataclass
class Sent:
    """One request as the client saw it (perf_counter seconds)."""
    tokens: List[int]
    n_input: int
    sent: float = 0.0
    done: float = math.inf
    result: Any = None

    @property
    def ok(self) -> bool:
        return (self.result is not None
                and not isinstance(self.result, Rejected)
                and not self.result.get("corrupt"))


class Served:
    """``devices[i]`` holds replica ``inst{i}`` (a device may hold several
    off the chip)."""

    def __init__(self, cfg: Dict, params, devices: Sequence,
                 engine_cls=TracedEngine):
        mcfg = model_config(cfg)
        srv = cfg["server"]
        self.cache_tokens = int(cfg["cache_tokens"])
        ecfg = engine_config(cfg, self.cache_tokens)
        names = [f"inst{i}" for i in range(len(devices))]
        device_of = dict(zip(names, devices))
        self.built_s: Dict[str, float] = {}

        def make_engine(name: str) -> PrefillOnlyEngine:
            t = time.perf_counter()
            eng = engine_cls(mcfg, params, dataclasses.replace(ecfg),
                             device=device_of[name])
            eng.profile(tuple(srv["profile_lengths"]))
            self.built_s[name] = time.perf_counter() - t
            return eng

        self.pool = InstancePool(make_engine)
        self.pool.scale_to(names)
        self.engines: Dict[str, PrefillOnlyEngine] = {
            n: self.pool.engines[n] for n in names}
        first = self.engines[names[0]]
        kv_keep = first.ecfg.kv_keep_tokens
        ctrl = AdmissionController(
            max_input_tokens=None,
            memory_model=MemoryModel(first.cfg, first.chip),
            kv_keep=None if kv_keep >= 10**9 else kv_keep)
        self.tracer = SpanTracer(capacity=1 << 16, batch_capacity=1 << 16)
        self.server = AsyncServer(
            self.pool, router=get_router(srv["router"]), admission=ctrl,
            retry=RetryPolicy(budget=int(srv["retry_budget"])),
            watchdog=JCTDeadlineWatchdog(
                factor=float(srv["watchdog_factor"]),
                min_deadline=float(srv["watchdog_min_deadline"])),
            tracer=self.tracer).start()
        self.done_q: "queue.Queue[Sent]" = queue.Queue()
        self._lock = threading.Lock()
        self.in_flight = 0

    # ---- submission -----------------------------------------------------
    def submit(self, tokens: List[int], user: str,
               labels: Sequence[int]) -> Sent:
        s = Sent(tokens, len(tokens))
        with self._lock:
            self.in_flight += 1

        def finished(fut, s=s):
            s.result = fut.result()
            s.done = time.perf_counter()
            with self._lock:
                self.in_flight -= 1
            self.done_q.put(s)

        s.sent = time.perf_counter()
        self.server.submit(user, tokens, allowed_tokens=list(labels)
                           ).add_done_callback(finished)
        return s

    def wait_idle(self, until: float) -> None:
        """Block until nothing is in flight or ``until`` passes."""
        while time.perf_counter() < until:
            with self._lock:
                if self.in_flight == 0:
                    return
            try:
                self.done_q.get(timeout=0.05)
            except queue.Empty:
                pass

    def _drain_q(self) -> None:
        while True:
            try:
                self.done_q.get_nowait()
            except queue.Empty:
                return

    # ---- loops ----------------------------------------------------------
    def serial(self, prompts, labels, timeout: float) -> List[Sent]:
        """One request at a time (set-up)."""
        out = []
        for p in prompts:
            out.append(self.submit(p.tokens, _user(p), labels))
            self.wait_idle(time.perf_counter() + timeout)
        self._drain_q()
        return out

    def batch(self, prompts, labels, outstanding: int,
              timeout: float) -> List[Sent]:
        """A closed loop over a fixed list (set-up's warm-up stream)."""
        todo, out = list(prompts), []
        deadline = time.perf_counter() + timeout
        self._drain_q()
        while todo or self.in_flight:
            while todo and self.in_flight < outstanding:
                p = todo.pop(0)
                out.append(self.submit(p.tokens, _user(p), labels))
            if time.perf_counter() > deadline:
                break
            try:
                self.done_q.get(timeout=0.05)
            except queue.Empty:
                pass
        return out

    def closed_window(self, mix, labels, outstanding: int,
                      t1: float) -> List[Sent]:
        """Closed loop: ``outstanding`` callers, each sending its next request
        when its last one returns, until the window closes."""
        self._drain_q()
        out = []
        for _ in range(outstanding):
            p = mix.next_prompt()
            out.append(self.submit(p.tokens, _user(p), labels))
        while True:
            left = t1 - time.perf_counter()
            if left <= 0:
                return out
            try:
                s = self.done_q.get(timeout=min(left, 0.05))
            except queue.Empty:
                continue
            # a caller whose request failed stops (the run is then not
            # correct); it must not spin resubmitting into a dead server
            if s.ok and time.perf_counter() < t1:
                p = mix.next_prompt()
                out.append(self.submit(p.tokens, _user(p), labels))

    def close(self) -> None:
        self.server.shutdown(drain=False)


def _user(p) -> str:
    return f"user{p.group}" if p.group >= 0 else f"doc{p.index}"


def device_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = []
    for d in devices:
        stats: Optional[Dict] = d.memory_stats()
        peaks.append(int((stats or {}).get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)
