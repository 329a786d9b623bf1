"""Crash-safe cross-process serving plane: RemoteEngine + WorkerSupervisor.

Promotes ``InstancePool`` members from in-process engines to supervised
engine WORKER PROCESSES behind the ``serving.rpc`` boundary, without
changing ``AsyncServer`` at all: ``RemoteEngine`` implements the engine
protocol the server's worker threads, router, watchdog, and retry stack
already speak (``lock/queue/results/submit/step/shed_expired/pending_jct/
predict_jct/cached_prefix_len/inflight_snapshot/...``), so every existing
recovery path — idempotent retry, confiscation tombstones, JCT watchdog,
brownout — now exercises REAL process death (kill -9, SIGSTOP, dropped RPC
responses) instead of simulated exceptions.

Why exactly-once survives a kill -9 with no distributed log:

  * req_ids are assigned in the FRONTEND process (one shared counter), so a
    rid is globally unique across workers and restarts; workers dedupe
    submits by rid, making blind re-send on connection errors safe.
  * stepping is PULL-model: the frontend drives ``step()`` over RPC. An
    instance whose step call failed is marked failed and never stepped or
    harvested again, so results stranded in a zombie worker can never be
    delivered — a restarted worker is a fresh process with an empty queue.
  * ``RemoteEngine`` keeps a client-side SHADOW QUEUE of submitted-but-
    unserved requests. On death, ``InstancePool._drain`` re-homes the
    shadow to healthy peers (futures intact); the subset the last heartbeat
    reported IN-FLIGHT is excluded from the drain and handed to the
    server's ``_handle_lost`` instead — the two recovery paths are disjoint
    by construction, so a request is re-owned exactly once.

Failure detection is heartbeat leases: the supervisor beats every worker at
``heartbeat_interval``; ``miss_budget`` consecutive misses (or process
exit) declares death. Death means SIGKILL FIRST — a SIGSTOPped worker
gives no TCP reset until it dies, and that reset is what unblocks a
frontend thread mid-``step`` — then the death callback (``mark_failed``),
then a scheduled restart with exponential backoff under a crash-loop
budget. The lease is symmetric: a worker that stops hearing heartbeats
(orphaned by a dead supervisor) self-exits.

Heartbeats also carry the worker's ``inflight_snapshot`` (ids, predicted
JCT, elapsed-at-send), so the JCTDeadlineWatchdog scan works across the
process boundary: the frontend re-anchors ``t0 = recv - elapsed`` on its
own clock (error = one-way transit, which only makes the batch look
OLDER — the safe direction), and a frozen worker's snapshot goes stale
while its elapsed keeps growing, which is exactly what trips the scan.
"""
from __future__ import annotations

import dataclasses
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.core.scheduler import Request, _req_counter
from repro.runtime.fault_tolerance import InstancePool
from repro.runtime.hw import chip_for
from repro.serving.rpc import (RpcClient, RpcDropped, RpcError,
                               RpcRemoteError)
from repro.serving.tracing import BatchRecord

_BATCH_FIELDS = {f.name for f in dataclasses.fields(BatchRecord)}


class _ECfg:
    __slots__ = ("block_size",)

    def __init__(self, block_size: int):
        self.block_size = block_size


class RemoteEngine:
    """Client-side proxy speaking the engine protocol for one worker.

    The shadow queue (``self.queue`` + ``self._shadow``) mirrors every
    request this proxy believes is queued worker-side; harvest/shed/cancel
    remove mirrors, death hands them to ``drain_queue``. Probe results are
    cached for ``probe_ttl`` so router scans cost at most one RPC per
    instance per staleness window instead of three per candidate.
    """

    def __init__(self, name: str, client: RpcClient, *,
                 block_size: int = 16, step_timeout: float = 300.0,
                 submit_timeout: float = 30.0, probe_timeout: float = 5.0,
                 probe_ttl: float = 0.05):
        self.name = name
        self.rpc = client
        self.ecfg = _ECfg(block_size)
        self.step_timeout = step_timeout
        self.submit_timeout = submit_timeout
        self.probe_timeout = probe_timeout
        self.probe_ttl = probe_ttl
        self.lock = threading.RLock()
        self.queue: List[Request] = []        # shadow mirror (ordered)
        self.results: Dict[int, Dict] = {}
        self._shadow: Dict[int, Request] = {}
        self._last: List[int] = []
        self._dead = False
        self._crash_inflight: List[int] = []
        self._hb: Tuple[List[int], float, float] = ([], 0.0, 0.0)
        self._pending = 0.0
        self._pending_t = -1e9
        self._probe_cache: Dict[Tuple, Tuple] = {}
        self._stats: Dict = {}
        self._step_compiled = False
        self._metrics = None
        self._tracer = None
        self.offload = False          # set from the worker's hello
        self.chip = None              # the worker's ChipSpec, from hello
        self._host_kv: Dict = {}      # tier occupancy from the heartbeat

    # ---- engine protocol: submission ------------------------------------
    def _wire_req(self, r: Request, now: float) -> Dict:
        return {"rid": r.req_id, "tokens": list(r.tokens or []),
                "allowed_tokens": (list(r.allowed_tokens)
                                   if r.allowed_tokens else None),
                "user_id": r.user_id,
                # deltas, not absolutes: perf_counter origins differ per
                # process. Transit shrinks the remaining budget — the
                # conservative direction for deadline feasibility.
                "deadline_delta": (None if r.deadline is None
                                   else r.deadline - now),
                "arrival_age": max(0.0, now - r.arrival)}

    def submit(self, tokens: Sequence[int], allowed_tokens=None, *,
               user_id=None, now: Optional[float] = None,
               deadline: Optional[float] = None, chain=None) -> int:
        if self._dead:
            raise RpcError(f"{self.name}: worker dead")
        arrival = time.perf_counter() if now is None else now
        rid = next(_req_counter)     # frontend-assigned: unique across pool
        r = Request(n_input=len(tokens), arrival=arrival,
                    chain=tuple(chain or ()), tokens=list(tokens),
                    req_id=rid, user_id=user_id,
                    allowed_tokens=(tuple(allowed_tokens)
                                    if allowed_tokens else None),
                    deadline=deadline)
        # pre-register the mirror: a concurrent step() may harvest this rid
        # the instant the worker enqueues it, and step's shadow filter must
        # recognize it as ours. Forgotten again on every failure path.
        with self.lock:
            self.queue.append(r)
            self._shadow[rid] = r
        try:
            self.rpc.call("submit", self._wire_req(r, time.perf_counter()),
                          timeout=self.submit_timeout, retries=2)
        except RpcDropped:
            # unknown outcome: the worker may have enqueued. Best-effort
            # reclaim; if it serves anyway, step's shadow filter drops the
            # orphan result at the boundary.
            self._forget(rid)
            try:
                self.rpc.call("cancel", {"rid": rid}, timeout=1.0)
            except RpcError:
                pass
            raise
        except Exception:
            self._forget(rid)
            raise
        return rid

    def requeue(self, reqs: Sequence[Request]) -> List[int]:
        """Batch re-home from a dead peer (InstancePool._drain hook). The
        worker dedupes by rid, so connection-level retries are safe."""
        if self._dead:
            raise RpcError(f"{self.name}: worker dead")
        now = time.perf_counter()
        with self.lock:                  # pre-register: see submit()
            for r in reqs:
                self.queue.append(r)
                self._shadow[r.req_id] = r
        try:
            self.rpc.call("requeue",
                          {"requests": [self._wire_req(r, now)
                                        for r in reqs]},
                          timeout=self.submit_timeout, retries=2)
        except Exception:
            for r in reqs:
                self._forget(r.req_id)
            raise
        return [r.req_id for r in reqs]

    def cancel(self, rid: int):
        with self.lock:
            r = self._shadow.get(rid)
        if r is None or self._dead:
            return None
        try:
            out = self.rpc.call("cancel", {"rid": rid},
                                timeout=self.probe_timeout)
        except RpcError:
            return None     # unknown — assume a step owns it (tombstones
        if not out.get("found"):   # make a late result safe either way)
            return None
        self._forget(rid)
        return r

    def shed_expired(self, now: Optional[float] = None) -> List[Request]:
        with self.lock:
            if self._dead or not any(r.deadline is not None
                                     for r in self._shadow.values()):
                return []    # zero RPCs on the idle/deadline-free hot loop
        try:
            out = self.rpc.call("shed_expired", timeout=self.probe_timeout)
        except RpcError:
            return []
        shed = []
        for row in out.get("shed", []):
            r = self._forget(int(row["rid"]))
            if r is not None:
                shed.append(r)
        return shed

    def _forget(self, rid: int) -> Optional[Request]:
        with self.lock:
            r = self._shadow.pop(rid, None)
            if r is not None:
                try:
                    self.queue.remove(r)
                except ValueError:
                    pass
            return r

    # ---- engine protocol: stepping --------------------------------------
    def step(self) -> Optional[int]:
        if self._dead:
            raise RpcError(f"{self.name}: worker dead")
        try:
            out = self.rpc.call("step", timeout=self.step_timeout)
        except RpcError:
            # death mid-step (SIGKILL / freeze-then-kill / dropped
            # response): confiscate the heartbeat-known in-flight mirrors
            # so the pool drain (queued work) and the server's retry path
            # (in-flight work) each own a DISJOINT set
            self._confiscate_inflight()
            raise
        recv = time.perf_counter()
        if out.get("crashed"):
            with self.lock:
                self._crash_inflight = [
                    i for i in out.get("inflight", []) if i in self._shadow]
                for i in self._crash_inflight:
                    self._forget(i)
            raise RpcRemoteError(
                f"{self.name}: engine crashed mid-step: {out['crashed']}")
        off = recv - float(out["now"])   # worker clock -> frontend clock
        rid = out.get("rid")
        with self.lock:
            self._crash_inflight = []
            self._hb = ([], 0.0, 0.0)          # the batch is over
            self._pending = float(out.get("pending_jct", 0.0))
            self._pending_t = recv
            self._step_compiled = bool(out.get("compiled"))
            served = out.get("served") or []
            # harvest ONLY rids still in our shadow: a rid drained off this
            # instance (mark_failed while the worker was frozen mid-step —
            # its REAL queue is unreachable, so only the shadow was cleared)
            # may still execute here if a thaw races the supervisor's kill;
            # the re-homed copy owns the future now, so this result is a
            # duplicate and must die at the boundary
            dropped = [int(i) for i, _ in served
                       if int(i) not in self._shadow]
            served = [(int(i), res) for i, res in served
                      if int(i) in self._shadow]
            self._last = [i for i, _ in served]
            for i, res in served:
                self._forget(i)
                if res is not None:
                    scores = res.get("scores")
                    if scores:     # JSON stringified the int keys
                        res["scores"] = {int(k): v
                                         for k, v in scores.items()}
                    self.results[i] = res
        if dropped and self._metrics is not None:
            for _ in dropped:
                self._metrics.counter("drained_results_dropped",
                                      self.name).inc()
        self._replay_telemetry(out, off)
        return rid

    @property
    def last_step_ids(self) -> List[int]:
        with self.lock:
            return list(self._last)

    @property
    def _inflight(self) -> List[int]:
        """What the server confiscates after a step() exception."""
        with self.lock:
            return list(self._crash_inflight)

    def _confiscate_inflight(self) -> None:
        with self.lock:
            ids = [i for i in self._hb[0] if i in self._shadow]
            for i in ids:
                self._forget(i)
            self._crash_inflight = ids

    # ---- engine protocol: probes ----------------------------------------
    def probe(self, n_input: int, chain=()) -> Tuple[float, float, int]:
        chain = tuple(chain or ())
        key = (n_input, chain)
        now = time.perf_counter()
        with self.lock:
            hit = self._probe_cache.get(key)
            if hit is not None and now - hit[0] <= self.probe_ttl:
                return hit[1], hit[2], hit[3]
            if self._dead:
                return self._pending, 0.0, 0
        try:
            out = self.rpc.call("probe", {"n_input": n_input,
                                          "chain": list(chain)},
                                timeout=self.probe_timeout)
        except RpcError:
            with self.lock:
                hit = self._probe_cache.get(key)
                if hit is not None:
                    return hit[1], hit[2], hit[3]
                return self._pending, 0.0, 0
        trip = (float(out["pending_jct"]), float(out["predict_jct"]),
                int(out["cached_prefix_len"]))
        with self.lock:
            self._probe_cache[key] = (now,) + trip
            if len(self._probe_cache) > 256:
                self._probe_cache.pop(next(iter(self._probe_cache)))
            self._pending, self._pending_t = trip[0], now
        return trip

    def pending_jct(self, now: Optional[float] = None) -> float:
        t = time.perf_counter()
        with self.lock:
            if self._dead or t - self._pending_t <= self.probe_ttl:
                return self._pending
        return self.probe(0)[0]

    def predict_jct(self, n: int, chain=()) -> float:
        return self.probe(n, chain)[1]

    def cached_prefix_len(self, chain) -> int:
        return self.probe(0, chain)[2]

    # ---- heartbeat-fed state --------------------------------------------
    def on_heartbeat(self, out: Dict, recv: Optional[float] = None) -> None:
        recv = time.perf_counter() if recv is None else recv
        with self.lock:
            ids = [i for i in out.get("inflight", []) if i in self._shadow]
            if ids:
                # t0 on OUR clock: error is one-way transit, which only
                # ages the batch — the watchdog trips sooner, never later
                self._hb = (ids, float(out.get("inflight_pred", 0.0)),
                            recv - float(out.get("inflight_elapsed", 0.0)))
            else:
                self._hb = ([], 0.0, 0.0)
            self._pending = float(out.get("pending_jct", 0.0))
            self._pending_t = recv
            if out.get("stats") is not None:
                self._stats = out["stats"]
            if out.get("host_kv") is not None:
                self._host_kv = out["host_kv"]
            m = self._metrics
        rows = out.get("metrics")
        if m is not None and rows:
            # worker-emitted series (jct_*, pack_*, batch_wall_seconds, ...)
            # are disjoint from frontend series by name: overwrite-merge
            m.merge_state(rows, instance=self.name)

    def inflight_snapshot(self) -> Tuple[List[int], float, float]:
        with self.lock:
            ids, pred, t0 = self._hb
            return list(ids), pred, t0

    # ---- telemetry bridge ------------------------------------------------
    def bind_telemetry(self, metrics=None, instance: str = "",
                       tracer=None) -> None:
        self._metrics = metrics
        self._tracer = tracer

    def _replay_telemetry(self, out: Dict, off: float) -> None:
        tr = self._tracer
        if tr is None:
            return
        for row in out.get("orphans") or []:
            rid, t, name, attrs = row
            attrs = dict(attrs or {})
            if name.startswith("span:"):
                t0 = float(attrs.pop("_t0", t))
                tr.ingest_span(int(rid), name[5:], t0 + off,
                               float(t) + off, **attrs)
            else:
                tr.ingest_event(int(rid), float(t) + off, name, **attrs)
        for b in out.get("batches") or []:
            kw = {k: v for k, v in b.items() if k in _BATCH_FIELDS}
            kw["ts"] = float(kw.get("ts", 0.0)) + off
            kw["instance"] = self.name
            kw["req_ids"] = tuple(kw.get("req_ids") or ())
            kw["jit_key"] = tuple(
                tuple(x) if isinstance(x, list) else x
                for x in (kw.get("jit_key") or ()))
            tr.record_batch(BatchRecord(**kw))

    # ---- lifecycle hooks -------------------------------------------------
    def drain_queue(self) -> List[Request]:
        """InstancePool._drain hook: hand over (and clear) the shadow."""
        with self.lock:
            pending = list(self.queue)
            self.queue.clear()
            self._shadow.clear()
        return pending

    def mark_dead(self) -> None:
        with self.lock:
            self._dead = True
            self._hb = ([], 0.0, 0.0)

    def reset_for_restart(self) -> None:
        with self.lock:
            self._dead = False
            self._crash_inflight = []
            self._hb = ([], 0.0, 0.0)
            self.queue.clear()
            self._shadow.clear()
            self._probe_cache.clear()
            self._pending, self._pending_t = 0.0, -1e9
            self._step_compiled = False
            self._host_kv = {}

    def set_degraded(self, flag: bool) -> None:
        if self._dead:
            return
        try:
            self.rpc.call("set_degraded", {"flag": bool(flag)},
                          timeout=self.probe_timeout)
        except RpcError:
            pass     # brownout is advisory; a dead worker restarts fresh

    # ---- offload tier (paper §9) -----------------------------------------
    def restore_estimate(self, chain) -> Dict:
        """Restorable host-tier prefix priced by the worker. Zeros when the
        worker has no tier (hello said so — no RPC spent) or is dead."""
        zeros = {"device_blocks": 0, "blocks": 0, "bytes": 0,
                 "restore_s": 0.0}
        if not self.offload or self._dead:
            return zeros
        try:
            out = self.rpc.call("prefetch",
                                {"chain": list(chain or ()),
                                 "estimate": True},
                                timeout=self.probe_timeout)
        except RpcError:
            return zeros
        return {k: out.get(k, zeros[k]) for k in zeros}

    def prefetch_prefix(self, chain, rid: Optional[int] = None) -> int:
        """Kick the worker's async host->device prefetch. Advisory like
        set_degraded: a failed RPC means the execute path restores instead."""
        if not self.offload or self._dead:
            return 0
        try:
            out = self.rpc.call("prefetch",
                                {"chain": list(chain or ()), "rid": rid},
                                timeout=self.probe_timeout)
        except RpcError:
            return 0
        return int(out.get("blocks", 0))

    def stats(self) -> Dict:
        if not self._dead:
            try:
                out = self.rpc.call("stats", timeout=self.probe_timeout)
                with self.lock:
                    self._stats = out.get("stats") or {}
            except RpcError:
                pass
        with self.lock:
            out = dict(self._stats) if self._stats else {}
            if self._host_kv:
                out.setdefault("host_kv", self._host_kv)
            return out


def _chip_env(slot: int) -> Dict[str, str]:
    """libtpu visibility for the worker in ``slot``: one chip per process.

    A TPU chip belongs to one process; workers spawned from one environment
    would otherwise all reach for every local chip. Worker ``slot`` sees
    only the ``slot``-th chip the frontend may use (``TPU_VISIBLE_CHIPS``
    when the caller restricted it, else every chip of the host), as a
    one-chip slice of its own. Off TPU, libtpu is not loaded and these
    variables are inert.
    """
    allowed = os.environ.get("TPU_VISIBLE_CHIPS")
    if allowed:
        chips = [c.strip() for c in allowed.split(",") if c.strip()]
        if slot >= len(chips):
            raise RuntimeError(
                f"worker slot {slot} has no chip: TPU_VISIBLE_CHIPS="
                f"{allowed!r} allows {len(chips)} worker(s), one per chip")
        chip = chips[slot]
    else:
        chip = str(slot)
    return {"TPU_VISIBLE_CHIPS": chip,
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(8476 + slot)}


class WorkerHandle:
    """One supervised worker process and its client-side plumbing."""

    def __init__(self, name: str, spec: Dict, slot: int = 0):
        self.name = name
        self.spec = spec
        self.slot = slot              # the chip this worker owns on a TPU
        self.proc: Optional[subprocess.Popen] = None
        self.pid: Optional[int] = None
        self.port: Optional[int] = None
        self.port_file: Optional[str] = None
        self.client: Optional[RpcClient] = None
        self.remote: Optional[RemoteEngine] = None
        self.misses = 0
        self.deaths = 0
        self.dead = False
        self.permafailed = False
        self.restarting = False
        self.restart_due: Optional[float] = None
        self.restart_times: List[float] = []


class WorkerSupervisor:
    """Spawns workers, beats their hearts, declares death, restarts.

    Death = ``miss_budget`` consecutive heartbeat failures OR process exit.
    The declaration sequence is ordered for correctness under SIGSTOP:
    SIGKILL first (produces the TCP reset that unblocks any frontend thread
    parked in a ``step`` RPC on the frozen worker), then ``on_death`` (the
    server re-homes the shadow queue), then a restart scheduled with
    exponential backoff — bounded by a crash-loop budget of
    ``max_restarts`` within ``restart_window`` seconds, after which the
    instance is permanently failed rather than flapping forever.
    """

    def __init__(self, *, lease: float = 3.0,
                 heartbeat_interval: float = 0.25, miss_budget: int = 4,
                 restart_backoff: float = 0.25,
                 restart_backoff_cap: float = 4.0, max_restarts: int = 5,
                 restart_window: float = 30.0, drain_grace: float = 5.0,
                 spawn_timeout: float = 120.0, step_timeout: float = 300.0,
                 log_dir: Optional[str] = None,
                 rpc_fault_hook: Optional[Callable] = None,
                 on_death: Optional[Callable[[str], None]] = None,
                 on_restart: Optional[Callable[[str], None]] = None,
                 metrics=None, verbose: bool = False):
        self.lease = lease
        self.heartbeat_interval = heartbeat_interval
        self.miss_budget = miss_budget
        self.restart_backoff = restart_backoff
        self.restart_backoff_cap = restart_backoff_cap
        self.max_restarts = max_restarts
        self.restart_window = restart_window
        self.drain_grace = drain_grace
        self.spawn_timeout = spawn_timeout
        self.step_timeout = step_timeout
        self.log_dir = log_dir or os.environ.get(
            "REPRO_WORKER_LOG_DIR") or tempfile.mkdtemp(prefix="repro-wk-")
        self.rpc_fault_hook = rpc_fault_hook
        self.on_death = on_death
        self.on_restart = on_restart
        self.metrics = metrics
        self.verbose = verbose
        # frontend health map (pool.healthy, wired by wire_supervisor): an
        # instance the SERVER marked failed — dropped/timed-out step RPC,
        # engine exception inside a live worker — is dead to the plane even
        # though the process is up; the beat loop converts that verdict
        # into a kill+restart so the instance re-enters the pool
        self.health_view: Optional[Dict[str, bool]] = None
        self.handles: Dict[str, WorkerHandle] = {}
        self._stop = threading.Event()
        self._beat_thread: Optional[threading.Thread] = None

    def _log(self, msg: str) -> None:
        if self.verbose:
            print(f"[supervisor] {msg}", flush=True)

    # ---- spawning --------------------------------------------------------
    def _launch(self, h: WorkerHandle) -> None:
        os.makedirs(self.log_dir, exist_ok=True)
        h.port_file = os.path.join(self.log_dir, f"{h.name}.port.json")
        try:
            os.unlink(h.port_file)
        except FileNotFoundError:
            pass
        cmd = [sys.executable, "-m", "repro.serving.worker",
               "--name", h.name, "--spec", json.dumps(h.spec),
               "--port-file", h.port_file, "--lease", str(self.lease),
               "--drain-grace", str(self.drain_grace)]
        env = dict(os.environ)
        src = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        env.update(_chip_env(h.slot))
        # append mode: a restarted worker's logs continue the same files —
        # the CI chaos soak uploads these on failure
        with open(os.path.join(self.log_dir, f"{h.name}.out.log"),
                  "ab") as out, \
                open(os.path.join(self.log_dir, f"{h.name}.err.log"),
                     "ab") as err:
            h.proc = subprocess.Popen(cmd, stdout=out, stderr=err, env=env)
        deadline = time.monotonic() + self.spawn_timeout
        while True:
            rc = h.proc.poll()
            if rc is not None:
                raise RuntimeError(
                    f"worker {h.name} exited rc={rc} before listening "
                    f"(logs under {self.log_dir})")
            try:
                with open(h.port_file) as f:
                    info = json.load(f)
                h.port, h.pid = int(info["port"]), int(info["pid"])
                break
            except (FileNotFoundError, json.JSONDecodeError, KeyError,
                    ValueError):
                pass
            if time.monotonic() > deadline:
                h.proc.kill()
                raise RuntimeError(f"worker {h.name} did not listen within "
                                   f"{self.spawn_timeout}s")
            time.sleep(0.02)
        h.misses = 0

    def spawn(self, name: str, spec: Dict) -> WorkerHandle:
        taken = {o.slot for o in self.handles.values()}
        h = WorkerHandle(name, spec,
                         slot=min(set(range(len(taken) + 1)) - taken))
        self.handles[name] = h
        self._launch(h)
        hook = None
        if self.rpc_fault_hook is not None:
            hook = (lambda op, _n=name: self.rpc_fault_hook(_n, op))
        h.client = RpcClient("127.0.0.1", h.port, fault_hook=hook)
        h.remote = RemoteEngine(name, h.client,
                                step_timeout=self.step_timeout)
        hello = h.client.call("hello", timeout=15.0)
        h.remote.ecfg.block_size = int(hello["block_size"])
        h.remote.offload = bool(hello.get("offload"))
        if hello.get("device"):
            h.remote.chip = chip_for(*hello["device"])
        self._log(f"worker {name}: pid={h.pid} port={h.port} "
                  f"block_size={h.remote.ecfg.block_size} "
                  f"offload={h.remote.offload}")
        return h

    def pid_of(self, name: str) -> Optional[int]:
        h = self.handles.get(name)
        return None if h is None or h.dead else h.pid

    # ---- heartbeat loop --------------------------------------------------
    def start(self) -> "WorkerSupervisor":
        if self._beat_thread is None:
            self._beat_thread = threading.Thread(
                target=self._beat_loop, name="worker-heartbeat", daemon=True)
            self._beat_thread.start()
        return self

    def _beat_loop(self) -> None:
        while not self._stop.wait(self.heartbeat_interval):
            now = time.monotonic()
            for h in list(self.handles.values()):
                if h.dead:
                    if (not h.permafailed and not h.restarting
                            and h.restart_due is not None
                            and now >= h.restart_due):
                        h.restarting = True
                        threading.Thread(target=self._restart, args=(h,),
                                         daemon=True).start()
                    continue
                if (self.health_view is not None
                        and self.health_view.get(h.name) is False):
                    self._declare_dead(h, "frontend marked instance failed")
                    continue
                exited = h.proc.poll() is not None
                if not exited:
                    try:
                        out = h.client.call(
                            "heartbeat",
                            {"lease": self.lease, "want_stats": True},
                            timeout=max(0.5, self.heartbeat_interval * 2))
                        h.misses = 0
                        h.remote.on_heartbeat(out)
                        continue
                    except RpcError:
                        h.misses += 1
                        if self.metrics is not None:
                            self.metrics.counter("worker_heartbeat_misses",
                                                 h.name).inc()
                if exited or h.misses >= self.miss_budget:
                    why = (f"exited rc={h.proc.returncode}" if exited
                           else f"{h.misses} consecutive missed heartbeats")
                    self._declare_dead(h, why)

    def _declare_dead(self, h: WorkerHandle, why: str) -> None:
        h.dead = True
        h.deaths += 1
        self._log(f"worker {h.name} DEAD: {why}")
        if self.metrics is not None:
            self.metrics.counter("worker_deaths", h.name).inc()
            self.metrics.gauge("worker_up", h.name).set(0)
        # SIGKILL before anything else: a frozen (SIGSTOP) worker emits no
        # TCP reset until it actually dies, and that reset is what unblocks
        # a frontend thread currently parked inside a step RPC
        if h.pid is not None:
            try:
                os.kill(h.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        h.remote.mark_dead()
        if self.on_death is not None:
            # off-thread: mark_failed re-homes the shadow over RPC to
            # peers; that must not stall the other workers' heartbeats
            threading.Thread(target=self._run_on_death, args=(h.name,),
                             daemon=True).start()
        now = time.monotonic()
        h.restart_times = [t for t in h.restart_times
                           if now - t <= self.restart_window]
        if len(h.restart_times) >= self.max_restarts:
            h.permafailed = True
            h.restart_due = None
            self._log(f"worker {h.name}: crash-loop budget exhausted "
                      f"({self.max_restarts} restarts/{self.restart_window}s"
                      f") — permanently failed")
            if self.metrics is not None:
                self.metrics.counter("worker_crashloop_permafail",
                                     h.name).inc()
            return
        backoff = min(self.restart_backoff_cap,
                      self.restart_backoff * (2 ** len(h.restart_times)))
        h.restart_due = now + backoff

    def _run_on_death(self, name: str) -> None:
        try:
            self.on_death(name)
        except Exception:
            pass

    def _restart(self, h: WorkerHandle) -> None:
        try:
            if self._stop.is_set():
                return
            try:
                h.proc.wait(timeout=5.0)     # reap the corpse
            except Exception:
                pass
            self._launch(h)
            if self._stop.is_set():
                # shutdown raced the restart: don't leak the fresh process
                try:
                    h.proc.kill()
                    h.proc.wait(timeout=5.0)
                except Exception:
                    pass
                return
            h.client.retarget("127.0.0.1", h.port)
            hello = h.client.call("hello", timeout=15.0)
            h.remote.offload = bool(hello.get("offload"))
            h.remote.reset_for_restart()
            h.restart_times.append(time.monotonic())
            h.restart_due = None
            h.dead = False
            self._log(f"worker {h.name} RESTARTED: pid={h.pid} "
                      f"port={h.port}")
            if self.metrics is not None:
                self.metrics.counter("worker_restarts", h.name).inc()
                self.metrics.gauge("worker_up", h.name).set(1)
            if self.on_restart is not None:
                try:
                    self.on_restart(h.name)
                except Exception:
                    pass
        except Exception as e:
            self._log(f"worker {h.name} restart FAILED: {e}")
            h.restart_times.append(time.monotonic())
            now = time.monotonic()
            recent = [t for t in h.restart_times
                      if now - t <= self.restart_window]
            if len(recent) >= self.max_restarts:
                h.permafailed = True
                h.restart_due = None
            else:
                h.restart_due = now + min(
                    self.restart_backoff_cap,
                    self.restart_backoff * (2 ** len(recent)))
        finally:
            h.restarting = False

    # ---- shutdown --------------------------------------------------------
    def stop(self, graceful: bool = True,
             timeout: Optional[float] = None) -> None:
        self._stop.set()
        if self._beat_thread is not None:
            self._beat_thread.join(timeout=5.0)
            self._beat_thread = None
        if timeout is None:
            timeout = self.drain_grace + 2.0 if graceful else 2.0
        sig = signal.SIGTERM if graceful else signal.SIGKILL
        for h in self.handles.values():
            if h.proc is None or h.proc.poll() is not None:
                continue
            try:
                os.kill(h.pid, signal.SIGCONT)   # a frozen worker cannot
            except (ProcessLookupError, PermissionError):  # run SIGTERM
                pass
            try:
                h.proc.send_signal(sig)
            except (ProcessLookupError, OSError):
                pass
        deadline = time.monotonic() + timeout
        for h in self.handles.values():
            if h.proc is None:
                continue
            try:
                h.proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                h.proc.kill()
                try:
                    h.proc.wait(timeout=5.0)
                except Exception:
                    pass
            if h.remote is not None:    # spawn may have died pre-handshake
                h.remote.mark_dead()
            if h.client is not None:
                h.client.close()


def make_process_pool(specs: Dict[str, Dict], **sup_kwargs
                      ) -> Tuple[InstancePool, WorkerSupervisor]:
    """Spawn one worker per spec (in parallel — real engines pay a model
    build each) and assemble an ``InstancePool`` of RemoteEngines. The
    caller starts the supervisor's heartbeat loop (``sup.start()``) once
    the death/restart callbacks are wired (see ``wire_supervisor``)."""
    sup = WorkerSupervisor(**sup_kwargs)
    errors: Dict[str, Exception] = {}

    def _one(n: str) -> None:
        try:
            sup.spawn(n, specs[n])
        except Exception as e:      # noqa: BLE001 — surfaced below
            errors[n] = e

    threads = [threading.Thread(target=_one, args=(n,), daemon=True)
               for n in sorted(specs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        sup.stop(graceful=False)
        raise RuntimeError(f"worker spawn failed: {errors}")

    def _fixed(name: str):
        raise RuntimeError("process pool is fixed-size; restarts are the "
                           "supervisor's job, not make_engine's")

    pool = InstancePool(_fixed)
    for n in sorted(specs):
        pool.engines[n] = sup.handles[n].remote
        pool.healthy[n] = True
    return pool, sup


def wire_supervisor(sup: WorkerSupervisor, server) -> None:
    """Connect death/restart to the AsyncServer's health machinery: death
    re-homes the shadow queue through ``mark_failed`` (exactly the path
    thread-mode crashes take); restart flips the instance healthy and
    wakes its parked worker thread."""
    sup.metrics = server.metrics

    def on_death(name: str) -> None:
        server.mark_failed(name)

    def on_restart(name: str) -> None:
        server.pool.healthy[name] = True
        server._bind_engines()
        server._start_worker(name)
        server._events.setdefault(name, threading.Event()).set()

    sup.on_death = on_death
    sup.on_restart = on_restart
    # bidirectional health: the server's own failure verdicts (step RPC
    # dropped/timed out, engine crash in a live worker) become supervisor
    # deaths, so the process is killed and restarted instead of lingering
    # outside the pool forever
    sup.health_view = server.pool.healthy
    if sup.metrics is not None:
        for h in sup.handles.values():
            sup.metrics.gauge("worker_up", h.name).set(
                0 if h.dead else 1)
