"""Engine worker process — one engine, one RPC listener, one lease.

``python -m repro.serving.worker --name inst0 --port-file /tmp/p.json
--spec '{"kind": "fake", ...}'`` owns ONE engine instance and serves the
cross-process plane's ops over the length-prefixed protocol in
``serving.rpc``. The frontend (``serving.supervisor.RemoteEngine``) drives
it exactly like an in-process engine: the AsyncServer worker thread calls
``step`` over the wire, the router probes over the wire, the supervisor
heartbeats over the wire. The worker is PASSIVE — it never steps itself —
so a worker that is never stepped again (marked failed after a dropped
response) can never double-deliver: exactly-once is structural, not
cooperative.

Crash-safety contract:
  * req_ids are CLIENT-assigned (one counter per frontend process), carried
    in the submit payload. ``submit`` dedupes by rid, so the client may
    blindly re-send on connection errors — prefill-only idempotence end to
    end (paper §2: one stateless forward, one token).
  * deadlines cross the boundary as DELTAS (seconds-from-now), because
    ``time.perf_counter`` origins differ per process; the worker re-anchors
    them on its own clock. Transit time only shrinks the remaining budget —
    the conservative direction.
  * every response that carries timestamps also carries ``now`` (the
    worker's clock at response build), so the client can map worker times
    onto its own clock with a one-way-transit error bound.
  * SIGTERM = graceful drain: stop accepting submits, keep serving step/
    harvest RPCs until the queue and in-flight work are empty (bounded by
    ``--drain-grace``), exit 0.
  * lease: if no supervisor heartbeat arrives for ``--lease`` seconds the
    worker self-exits — an orphaned worker (supervisor SIGKILLed) must not
    linger and serve stale state to a restarted plane.

Telemetry crosses the boundary in two export queues: the worker-side
``SpanTracer`` never binds a request (the frontend owns the timelines), so
every engine span/event lands in its orphan buffer, which ``step`` drains
into the response for frontend replay; the worker-side ``MetricsRegistry``
rides the heartbeat as a ``dump_state`` snapshot the frontend merges.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import sys
import threading
import time
from typing import Dict, List, Optional

from repro.serving.rpc import recv_msg, send_msg


# ---- engines ----------------------------------------------------------------

class FakeWorkerEngine:
    """Deterministic protocol double (no jax import): step() sleeps
    ``sec_per_token`` per queued token. Mirrors the serving tests' fake so
    process-plane chaos tests measure the PLANE, not model compute."""

    class _ECfg:
        def __init__(self, block_size: int = 16):
            self.block_size = block_size

    def __init__(self, name: str, sec_per_token: float = 2e-4,
                 block_size: int = 16):
        self.name = name
        self.ecfg = self._ECfg(block_size)
        self.lock = threading.RLock()
        self.queue: List = []
        self.results: Dict[int, Dict] = {}
        self._last: List[int] = []
        self.a = sec_per_token
        self.steps = 0
        self._inflight: List[int] = []
        self._inflight_pred = 0.0
        self._inflight_t0 = 0.0
        self._step_compiled = False
        self.degraded = False

    def cancel(self, rid: int):
        with self.lock:
            for i, r in enumerate(self.queue):
                if r.req_id == rid:
                    return self.queue.pop(i)
        return None

    def shed_expired(self, now: Optional[float] = None) -> List:
        now = time.perf_counter() if now is None else now
        shed: List = []
        with self.lock:
            keep = []
            for r in self.queue:
                doomed = (r.deadline is not None
                          and now + self.a * r.n_input > r.deadline)
                (shed if doomed else keep).append(r)
            self.queue[:] = keep
        return shed

    def pending_jct(self, now: Optional[float] = None) -> float:
        with self.lock:
            queued = sum(self.a * r.n_input for r in self.queue)
            running = 0.0
            if self._inflight:
                running = max(0.0, self._inflight_pred - (
                    time.perf_counter() - self._inflight_t0))
            return queued + running

    def predict_jct(self, n: int, chain=()) -> float:
        return self.a * n

    def cached_prefix_len(self, chain) -> int:
        return 0

    def probe(self, n_input: int, chain=()):
        return self.pending_jct(), self.predict_jct(n_input, chain), 0

    def inflight_snapshot(self):
        with self.lock:
            return (list(self._inflight), self._inflight_pred,
                    self._inflight_t0)

    def set_degraded(self, flag: bool) -> None:
        self.degraded = bool(flag)

    def step(self) -> Optional[int]:
        with self.lock:
            if not self.queue:
                return None
            r = self.queue.pop(0)
            self._inflight = [r.req_id]
            self._inflight_pred = self.a * r.n_input
            self._inflight_t0 = time.perf_counter()
        time.sleep(self.a * r.n_input)
        r.finish_time = time.perf_counter()
        with self.lock:
            res = {"req_id": r.req_id, "latency": r.latency, "n_cached": 0,
                   "n_input": r.n_input, "deadline": r.deadline, "token": 5}
            if r.allowed_tokens:
                res["scores"] = {int(t): 1.0 / len(r.allowed_tokens)
                                 for t in r.allowed_tokens}
            self.results[r.req_id] = res
            self._last = [r.req_id]
            self._inflight = []
            self._inflight_pred = 0.0
            self.steps += 1
        return r.req_id

    @property
    def last_step_ids(self) -> List[int]:
        return list(self._last)

    def stats(self) -> Dict:
        return {"steps": self.steps}


def build_engine(name: str, spec: Dict):
    """Engine from a JSON spec. ``fake`` is import-light (tests of the
    plane itself); ``engine`` builds the real PrefillOnly engine the way
    ``launch.serve.make_pool`` does (jax imported lazily here so fake
    workers start in milliseconds)."""
    kind = spec.get("kind", "fake")
    if kind == "fake":
        return FakeWorkerEngine(
            name, sec_per_token=float(spec.get("sec_per_token", 2e-4)),
            block_size=int(spec.get("block_size", 16)))
    assert kind == "engine", kind
    import jax
    import jax.numpy as jnp

    from repro.configs import serving_config
    from repro.core.engine import EngineConfig, PrefillOnlyEngine
    from repro.models.model import build
    from repro.runtime.sharding import materialize

    cfg = serving_config(spec.get("arch", "qwen1.5-0.5b"),
                         bool(spec.get("published_widths", False)))
    api = build(cfg)
    params = materialize(jax.random.PRNGKey(int(spec.get("seed", 0))),
                         api.defs(), jnp.float32)
    overrides = dict(spec.get("ecfg") or {})
    for k, v in overrides.items():       # JSON has no tuples
        if isinstance(v, list):
            overrides[k] = tuple(v)
    kw = {"policy": spec.get("policy", "srjf_calibrated"),
          "lam": float(spec.get("lam", 0.05)),
          "cache_capacity_tokens": int(spec.get("cache_tokens", 4096))}
    kw.update(overrides)                 # spec["ecfg"] wins over shorthands
    eng = PrefillOnlyEngine(cfg, params, EngineConfig(**kw))
    if spec.get("profile"):
        eng.profile(tuple(spec.get("profile_lengths", (32, 64, 128))))
    return eng


# ---- the worker -------------------------------------------------------------

class EngineWorker:
    """One engine behind one listener; see the module docstring."""

    def __init__(self, name: str, engine, *, lease: float = 30.0,
                 drain_grace: float = 5.0, host: str = "127.0.0.1"):
        self.name = name
        self.engine = engine
        self.lease = lease
        self.drain_grace = drain_grace
        self._draining = False
        self._drain_t0 = 0.0
        self._last_beat = time.perf_counter()
        self._exit = threading.Event()
        self._seen_rids: set = set()
        self._seen_order: List[int] = []       # FIFO bound on the dedupe set
        self._sub_lock = threading.Lock()
        # telemetry export queues (worker side of the bridge)
        from repro.serving.metrics import MetricsRegistry
        from repro.serving.tracing import SpanTracer
        self.registry = MetricsRegistry()
        self.tracer = SpanTracer(capacity=16, batch_capacity=1024,
                                 orphan_capacity=8192)
        bind = getattr(engine, "bind_telemetry", None)
        if bind is not None:
            bind(metrics=self.registry, instance=name, tracer=self.tracer)
        self.srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.srv.bind((host, 0))
        self.srv.listen(64)
        self.port = self.srv.getsockname()[1]

    # ---- ops -------------------------------------------------------------
    def _mk_request(self, p: Dict, now: float):
        """A Request mirroring the client's, re-anchored on this clock:
        rid comes FROM the payload (client-assigned — never the shared
        counter, which would collide across worker processes), the deadline
        from its delta, the arrival from its age (so the scheduler's
        starvation offset keeps crediting time queued elsewhere)."""
        from repro.core.prefix_cache import token_chain
        from repro.core.scheduler import Request
        tokens = list(p["tokens"])
        bs = self.engine.ecfg.block_size
        chain = (tuple(token_chain(tokens, bs))
                 if getattr(self.engine, "cache", None) is not None else ())
        allowed = p.get("allowed_tokens")
        deadline = (None if p.get("deadline_delta") is None
                    else now + float(p["deadline_delta"]))
        return Request(
            n_input=len(tokens),
            arrival=now - float(p.get("arrival_age", 0.0) or 0.0),
            chain=chain, tokens=tokens, req_id=int(p["rid"]),
            user_id=p.get("user_id"),
            allowed_tokens=tuple(allowed) if allowed else None,
            deadline=deadline)

    def _enqueue_one(self, p: Dict, now: float) -> bool:
        """Dedupe + enqueue. False = duplicate rid (idempotent replay)."""
        rid = int(p["rid"])
        with self._sub_lock:
            if rid in self._seen_rids:
                return False
            self._seen_rids.add(rid)
            self._seen_order.append(rid)
            if len(self._seen_order) > 65536:
                self._seen_rids.discard(self._seen_order.pop(0))
        r = self._mk_request(p, now)
        eng = self.engine
        with eng.lock:
            cache = getattr(eng, "cache", None)
            if cache is not None:
                # probe, don't match: on a tiered cache an eager match here
                # would restore host blocks inside the submit RPC
                r.n_cached_at_arrival = (
                    cache.probe_len(r.chain)
                    if hasattr(cache, "probe_len")
                    else cache.match_len(r.chain))
            eng.queue.append(r)
        return True

    def _op_submit(self, p: Dict) -> Dict:
        if self._draining:
            raise RuntimeError("draining: worker refuses new work")
        now = time.perf_counter()
        fresh = self._enqueue_one(p, now)
        return {"rid": int(p["rid"]), "dup": not fresh, "now": now}

    def _op_requeue(self, p: Dict) -> Dict:
        """Batch re-home from a dead peer's shadow queue. Same dedupe as
        submit (re-homing is a re-send of work this worker may have seen)."""
        if self._draining:
            raise RuntimeError("draining: worker refuses new work")
        now = time.perf_counter()
        accepted = [int(q["rid"]) for q in p["requests"]
                    if self._enqueue_one(q, now)]
        return {"accepted": accepted, "now": now}

    def _op_cancel(self, p: Dict) -> Dict:
        r = self.engine.cancel(int(p["rid"]))
        return {"found": r is not None,
                "user_id": getattr(r, "user_id", None)}

    def _op_shed_expired(self, p: Dict) -> Dict:
        shed = self.engine.shed_expired()
        return {"shed": [{"rid": r.req_id, "user_id": r.user_id}
                         for r in shed]}

    def _op_step(self, p: Dict) -> Dict:
        eng = self.engine
        t0 = time.perf_counter()
        try:
            rid = eng.step()
        except Exception as e:      # engine crash != protocol crash: report
            return {"crashed": f"{type(e).__name__}: {e}",
                    "inflight": list(getattr(eng, "_inflight", [])),
                    "now": time.perf_counter()}
        out: Dict = {"rid": rid,
                     "step_seconds": time.perf_counter() - t0,
                     "compiled": bool(getattr(eng, "_step_compiled", False))}
        served = []
        if rid is not None:
            with eng.lock:
                served = [[i, eng.results.pop(i, None)]
                          for i in eng.last_step_ids]
                out["depth"] = len(eng.queue)
        else:
            with eng.lock:
                out["depth"] = len(eng.queue)
        out["served"] = served
        out["pending_jct"] = eng.pending_jct()
        out["orphans"] = [[r, t, n, a]
                          for r, t, n, a in self.tracer.drain_orphans()]
        out["batches"] = [b.to_dict() for b in self.tracer.drain_batches()]
        out["now"] = time.perf_counter()
        return out

    def _op_probe(self, p: Dict) -> Dict:
        eng = self.engine
        n_input = int(p.get("n_input", 0))
        # chains are hash chains over int tuples — Python int/tuple hashing
        # is NOT seed-salted, so a chain cut in the frontend process is
        # valid here as long as the block sizes agree (hello reports ours)
        chain = tuple(p.get("chain") or ())
        if not chain and p.get("tokens") \
                and getattr(eng, "cache", None) is not None:
            from repro.core.prefix_cache import token_chain
            chain = tuple(token_chain(list(p["tokens"]),
                                      eng.ecfg.block_size))
        probe = getattr(eng, "probe", None)
        if probe is not None:
            pending, predict, cached = probe(n_input, chain)
        else:
            pending = eng.pending_jct()
            predict = eng.predict_jct(n_input, chain)
            cached = eng.cached_prefix_len(chain)
        return {"pending_jct": pending, "predict_jct": predict,
                "cached_prefix_len": cached, "now": time.perf_counter()}

    def _op_heartbeat(self, p: Dict) -> Dict:
        self._last_beat = time.perf_counter()
        if p.get("lease") is not None:
            self.lease = float(p["lease"])
        eng = self.engine
        snap = getattr(eng, "inflight_snapshot", None)
        ids, pred, t0 = snap() if snap is not None else ([], 0.0, 0.0)
        now = time.perf_counter()
        out = {"pid": os.getpid(), "now": now, "name": self.name,
               "inflight": list(ids), "inflight_pred": pred,
               "inflight_elapsed": (now - t0) if ids else 0.0,
               "pending_jct": eng.pending_jct(),
               "draining": self._draining}
        with eng.lock:
            out["depth"] = len(eng.queue)
        host = getattr(getattr(eng, "cache", None), "host", None)
        if host is not None:     # tier occupancy rides every heartbeat
            out["host_kv"] = host.stats()
        if p.get("want_metrics", True):
            out["metrics"] = self.registry.dump_state()
        if p.get("want_stats"):
            try:
                out["stats"] = eng.stats()
            except Exception:
                out["stats"] = None
        return out

    def _op_prefetch(self, p: Dict) -> Dict:
        """Router-time offload-tier ops: ``estimate`` prices the restorable
        host prefix (admission), otherwise kick the async host->device
        prefetch. No-ops (zeros) on engines without a tier."""
        eng = self.engine
        chain = tuple(p.get("chain") or ())
        if p.get("estimate"):
            est_fn = getattr(eng, "restore_estimate", None)
            est = (est_fn(chain) if est_fn is not None
                   else {"device_blocks": 0, "blocks": 0, "bytes": 0,
                         "restore_s": 0.0})
            est["now"] = time.perf_counter()
            return est
        pf = getattr(eng, "prefetch_prefix", None)
        rid = p.get("rid")
        blocks = pf(chain, rid=int(rid) if rid is not None else None) \
            if pf is not None else 0
        return {"blocks": int(blocks), "now": time.perf_counter()}

    def _op_set_degraded(self, p: Dict) -> Dict:
        set_deg = getattr(self.engine, "set_degraded", None)
        if set_deg is not None:
            set_deg(bool(p.get("flag")))
        return {}

    def _op_stats(self, p: Dict) -> Dict:
        return {"stats": self.engine.stats(),
                "metrics": self.registry.dump_state(),
                "now": time.perf_counter()}

    def _op_hello(self, p: Dict) -> Dict:
        # offload: duck-typed (a tiered cache carries a host store) so the
        # fake engine stays import-light; the frontend uses the flag to
        # skip prefetch/estimate RPCs entirely on un-tiered workers. The
        # device rides along so the frontend can price admission against
        # the worker's chip without touching a JAX backend itself.
        dev = getattr(self.engine, "device", None)
        return {"pid": os.getpid(), "name": self.name,
                "block_size": self.engine.ecfg.block_size,
                "offload": getattr(
                    getattr(self.engine, "cache", None), "host", None)
                is not None,
                "device": (None if dev is None
                           else [dev.platform, dev.device_kind]),
                "now": time.perf_counter()}

    def _op_shutdown(self, p: Dict) -> Dict:
        self.begin_drain()
        return {"draining": True}

    # ---- serving loop ----------------------------------------------------
    _OPS = {"hello": _op_hello, "submit": _op_submit,
            "requeue": _op_requeue, "cancel": _op_cancel,
            "shed_expired": _op_shed_expired, "step": _op_step,
            "probe": _op_probe, "heartbeat": _op_heartbeat,
            "prefetch": _op_prefetch,
            "set_degraded": _op_set_degraded, "stats": _op_stats,
            "shutdown": _op_shutdown}

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            while True:
                msg = recv_msg(conn)
                op = msg.get("op", "")
                fn = self._OPS.get(op)
                if fn is None:
                    send_msg(conn, {"ok": False,
                                    "error": f"unknown op {op!r}"})
                    continue
                try:
                    out = fn(self, msg)
                except Exception as e:
                    send_msg(conn, {"ok": False,
                                    "error": f"{type(e).__name__}: {e}"})
                    continue
                send_msg(conn, {"ok": True, "out": out})
        except Exception:
            pass      # peer gone / torn frame: this connection is done
        finally:
            conn.close()

    def _accept_loop(self) -> None:
        while not self._exit.is_set():
            try:
                conn, _ = self.srv.accept()
            except OSError:
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def begin_drain(self) -> None:
        if not self._draining:
            self._draining = True
            self._drain_t0 = time.perf_counter()

    def _drained(self) -> bool:
        eng = self.engine
        with eng.lock:
            empty = not eng.queue and not getattr(eng, "_inflight", [])
        return empty

    def run(self, port_file: Optional[str] = None) -> int:
        """Serve until drained (SIGTERM) or orphaned (lease expiry)."""
        signal.signal(signal.SIGTERM, lambda *_: self.begin_drain())
        threading.Thread(target=self._accept_loop, daemon=True).start()
        if port_file:
            tmp = port_file + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"port": self.port, "pid": os.getpid(),
                           "name": self.name}, f)
            os.replace(tmp, port_file)    # atomic: readers never see a torn file
        print(f"worker {self.name}: pid={os.getpid()} port={self.port}",
              flush=True)
        while True:
            time.sleep(0.05)
            now = time.perf_counter()
            if self._draining:
                if self._drained() or (now - self._drain_t0
                                       > self.drain_grace):
                    print(f"worker {self.name}: drained, exiting",
                          flush=True)
                    return 0
            if self.lease > 0 and now - self._last_beat > self.lease:
                print(f"worker {self.name}: lease expired "
                      f"({self.lease:.1f}s without heartbeat) — orphaned, "
                      f"exiting", file=sys.stderr, flush=True)
                return 2


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--name", required=True)
    ap.add_argument("--spec", default='{"kind": "fake"}',
                    help="engine spec JSON (kind: fake | engine)")
    ap.add_argument("--port-file", default=None,
                    help="write {port, pid} JSON here once listening")
    ap.add_argument("--lease", type=float, default=30.0,
                    help="self-exit after this many heartbeat-less seconds "
                         "(0 disables)")
    ap.add_argument("--drain-grace", type=float, default=5.0,
                    help="max seconds to wait out the queue after SIGTERM")
    args = ap.parse_args()
    spec = json.loads(args.spec)
    if spec.get("kind", "fake") == "engine":     # fakes never import jax
        from repro.runtime.compile_cache import enable_compile_cache
        enable_compile_cache()
    engine = build_engine(args.name, spec)
    worker = EngineWorker(args.name, engine, lease=args.lease,
                          drain_grace=args.drain_grace)
    return worker.run(args.port_file)


if __name__ == "__main__":
    sys.exit(main())
