"""Serving driver: async PrefillOnly instance pool + trace replay.

The paper's deployment shape (§7.1): N single-model-copy engine instances
behind a router, each running Algorithm-1 scheduling with continuous JCT
calibration and suffix-KV discard. Since PR 2 the driver is ASYNC: an
``AsyncServer`` runs one worker thread per engine, the submitting thread
replays the trace open-loop in real time (sleep to each arrival, submit,
move on — no polling step loop), and every request resolves through a
``Future`` to either a scored result or a typed ``Rejected``.

Routing is pluggable (``--router user_hash`` is the paper's rendezvous user
hash; ``--router least_backlog`` routes on predicted-JCT backlog with
cache-affinity tie-break — exploiting the JCT predictability that is the
paper's whole point). Admission control (MIL + deadline feasibility) and
in-queue deadline shedding are on by default when ``--deadline`` is given.

Every instance runs REAL forwards. By default it serves the reduced CPU
preset (the test configuration); ``--published-widths`` serves the
architecture at its published widths, as on a TPU chip. Each instance owns
one device: thread-mode replicas are placed round-robin over the local
devices, and each process-mode worker is given its own chip. In process mode
the frontend never initializes a JAX backend.

    python -m repro.launch.serve --published-widths --max-requests 8
    python -m repro.launch.serve --published-widths --workers 1

Exits non-zero when any request ends as ``Rejected("error")`` in a run
without chaos: a run that fails its requests never reports success.
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path
from typing import Dict, Optional

import jax
import numpy as np

from repro.configs import get_config, serving_config
from repro.core.engine import EngineConfig, PrefillOnlyEngine
from repro.core.kv_policy import MemoryModel
from repro.data.workloads import get_trace
from repro.models.model import build
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.fault_tolerance import (InstancePool,
                                           JCTDeadlineWatchdog,
                                           PreemptionHandler)
from repro.runtime.sharding import materialize
from repro.serving import (AdmissionController, AsyncServer,
                           BrownoutController, ChaosConfig, FaultPlan,
                           Rejected, RetryPolicy, SpanTracer, get_router,
                           make_process_pool, wire_supervisor, wrap_pool,
                           wrap_pool_processes)


def make_pool(arch: str, n_instances: int = 2, *,
              published_widths: bool = False,
              policy: str = "srjf_calibrated", lam: float = 0.05,
              cache_tokens: int = 4096, seed: int = 0,
              profile: bool = False, offload: bool = False,
              host_cache_mb: int = 256,
              profile_lengths=(32, 64, 128)) -> InstancePool:
    """Build N engine instances over ONE set of materialized weights.

    ``profile=True`` runs the paper's profile step per instance: fits the
    JCT linear proxy on measured forwards (so routing/admission predictions
    start calibrated, not from the generic default) and auto-tunes the
    prepacking budget from the fitted curve. ``offload=True`` gives every
    instance the DRAM KV tier (``host_cache_mb`` per instance): evicted
    prefix blocks demote to host memory and restore instead of recomputing.

    Instances are placed round-robin over ``jax.local_devices()``: on a
    four-chip host, four instances are four one-chip replicas, each holding
    its own parameters and KV.
    """
    cfg = serving_config(arch, published_widths)
    api = build(cfg)
    # the compute dtype directly (the values the engine's cast would give):
    # the pool's closure keeps this copy alive, so no f32 copy on the device
    params = materialize(jax.random.PRNGKey(seed), api.defs(), cfg.dtype)
    devices = jax.local_devices()
    placed = itertools.count()

    def make_engine(name: str) -> PrefillOnlyEngine:
        eng = PrefillOnlyEngine(cfg, params, EngineConfig(
            policy=policy, lam=lam, cache_capacity_tokens=cache_tokens,
            offload=offload, host_cache_bytes=host_cache_mb << 20),
            device=devices[next(placed) % len(devices)])
        if profile:
            eng.profile(profile_lengths)
        return eng

    pool = InstancePool(make_engine)
    pool.scale_to([f"inst{i}" for i in range(n_instances)])
    return pool


def make_worker_pool(arch: str, n_workers: int, *,
                     published_widths: bool = False,
                     policy: str = "srjf_calibrated", lam: float = 0.05,
                     cache_tokens: int = 4096, seed: int = 0,
                     profile: bool = False, offload: bool = False,
                     host_cache_mb: int = 256,
                     rpc_fault_hook=None,
                     drain_grace: float = 30.0):
    """Process-mode pool: one supervised engine WORKER PROCESS per instance
    (each builds its own weights — crash isolation is the point), plus the
    supervisor that heartbeats, declares death, and restarts them. The
    supervision constants are sized for real engines on CPU: a jit compile
    can hold the GIL for seconds, so the miss budget tolerates ~6s of
    unanswered beats before declaring a freeze. ``offload`` rides the spec
    into each worker's EngineConfig; the worker's hello reports the tier
    back so the frontend only spends prefetch RPCs on tiered workers."""
    ecfg = ({"offload": True, "host_cache_bytes": host_cache_mb << 20}
            if offload else {})
    specs = {f"inst{i}": {"kind": "engine", "arch": arch,
                          "published_widths": published_widths,
                          "policy": policy, "lam": lam,
                          "cache_tokens": cache_tokens, "seed": seed,
                          "profile": profile, "ecfg": ecfg}
             for i in range(n_workers)}
    return make_process_pool(
        specs, lease=30.0, heartbeat_interval=0.5, miss_budget=12,
        restart_backoff=0.5, restart_backoff_cap=8.0,
        drain_grace=drain_grace, spawn_timeout=600.0, step_timeout=300.0,
        rpc_fault_hook=rpc_fault_hook)


def start_metrics_server(registry, port: int = 0, host: str = "127.0.0.1",
                         tracer=None) -> ThreadingHTTPServer:
    """Plain-HTTP observability endpoint over a ``MetricsRegistry`` (and,
    when a ``SpanTracer`` is given, its trace rings).

    GET /metrics           Prometheus text exposition
    GET /trace             finished request timelines + batch records, JSONL
    GET /trace.chrome.json Chrome-trace JSON (open in Perfetto / about:tracing)

    Anything else is 404. Runs in a daemon thread; ``port=0`` binds an
    ephemeral port (read it back from ``server.server_address``). Call
    ``server.shutdown()`` to stop.
    """

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):                          # noqa: N802 (stdlib API)
            path = self.path.rstrip("/")
            if path in ("", "/metrics"):
                body = registry.render_prometheus().encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            elif path == "/trace" and tracer is not None:
                body = tracer.dump_jsonl().encode()
                ctype = "application/x-ndjson; charset=utf-8"
            elif path == "/trace.chrome.json" and tracer is not None:
                body = json.dumps(tracer.chrome_trace()).encode()
                ctype = "application/json; charset=utf-8"
            else:
                self.send_error(404)
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):                 # keep stdout clean
            pass

    server = ThreadingHTTPServer((host, port), Handler)
    threading.Thread(target=server.serve_forever, daemon=True,
                     name="metrics-http").start()
    return server


def write_trace_dump(tracer, path) -> Path:
    """Write the JSONL dump to ``path`` plus the Chrome-trace JSON next to
    it (``<stem>.chrome.json``). Returns the chrome-trace path."""
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(tracer.dump_jsonl())
    cp = p.with_suffix(".chrome.json")
    cp.write_text(json.dumps(tracer.chrome_trace()))
    return cp


def serve_trace(arch: str = "qwen1.5-0.5b",
                trace_name: str = "post_recommendation",
                qps: float = 5.0, n_instances: int = 2, workers: int = 0,
                scale_tokens: float = 0.02, policy: str = "srjf_calibrated",
                lam: float = 0.05, seed: int = 0,
                max_requests: Optional[int] = None,
                router: str = "least_backlog",
                deadline: Optional[float] = None,
                admission: bool = True,
                max_input_tokens: Optional[int] = None,
                profile: bool = False,
                pool: Optional[InstancePool] = None,
                trace_kw: Optional[Dict] = None,
                metrics_port: Optional[int] = None,
                retry_budget: int = 2,
                watchdog: bool = True,
                watchdog_factor: float = 4.0,
                watchdog_min_deadline: float = 1.0,
                brownout: bool = False,
                chaos: Optional[ChaosConfig] = None,
                drain_timeout: Optional[float] = 30.0,
                trace_dump: Optional[str] = None,
                trace_capacity: int = 4096,
                offload: bool = False,
                host_cache_mb: int = 256,
                cache_tokens: int = 4096,
                published_widths: bool = False) -> Dict:
    """Replay a paper workload through the AsyncServer. Returns latency
    stats over SERVED requests plus rejection counts and a telemetry dump.

    ``deadline`` is seconds after each request's arrival; with
    ``admission=True`` doomed requests are rejected/shed instead of blowing
    out the tail. ``pool=None`` builds a fresh pool (pass one to reuse
    warmed engines across runs). ``metrics_port`` starts a plain-HTTP
    Prometheus scrape endpoint (GET /metrics) for the duration of the
    replay; 0 picks an ephemeral port.

    Robustness: the JCT-deadline watchdog and idempotent retry are ON by
    default (``watchdog=False`` / ``retry_budget=0`` disable); ``brownout``
    arms the graceful-degradation ladder; ``chaos`` wraps the pool in the
    seeded fault injector (``serving.chaos``). SIGTERM/SIGINT during the
    replay stops submitting and drains in-flight work for up to
    ``drain_timeout`` seconds instead of dying mid-batch.

    ``workers=N`` runs PROCESS mode: N supervised engine worker processes
    behind the RPC boundary instead of N in-process engine threads. Chaos
    in process mode injects the process/RPC fault kinds (``kill``,
    ``freeze``, ``rpc_drop``, ``rpc_delay``); the in-process step/submit
    kinds only apply in thread mode.

    ``published_widths`` serves ``arch`` at its published widths instead of
    the reduced CPU preset (``configs.serving_config``).
    """
    plan = FaultPlan(chaos) if chaos is not None else None
    sup = None
    if workers and pool is None:
        pool, sup = make_worker_pool(
            arch, workers, published_widths=published_widths,
            policy=policy, lam=lam, seed=seed,
            profile=profile, offload=offload, host_cache_mb=host_cache_mb,
            cache_tokens=cache_tokens,
            rpc_fault_hook=plan.rpc_fault if plan is not None else None,
            drain_grace=min(drain_timeout or 30.0, 30.0))
    elif pool is None:
        pool = make_pool(arch, n_instances,
                         published_widths=published_widths,
                         policy=policy, lam=lam,
                         seed=seed, profile=profile, offload=offload,
                         host_cache_mb=host_cache_mb,
                         cache_tokens=cache_tokens)
    if plan is not None and sup is None:
        wrap_pool(pool, plan)
    ctrl = None
    if admission:
        # MIL from the engines' own model config unless given explicitly —
        # the same closed form the profile run sizes the KV budget with.
        # Remote engines hold no model config frontend-side; rebuild the
        # (weights-free) config the workers were spawned with. The chip is
        # the engine's own (a worker reports its device in its hello).
        any_eng = next(iter(pool.engines.values()))
        eng_cfg = getattr(any_eng, "cfg", None)
        if eng_cfg is None:
            eng_cfg = serving_config(arch, published_widths)
        # price the engines' actual KV lifecycle into the MIL gate: finite
        # kv_keep means peak-layer suffix footprint, not all-layers
        kv_keep = getattr(getattr(any_eng, "ecfg", None),
                          "kv_keep_tokens", None)
        if kv_keep is not None and kv_keep >= 10**9:
            kv_keep = None
        ctrl = AdmissionController(max_input_tokens=max_input_tokens,
                                   memory_model=MemoryModel(
                                       eng_cfg, any_eng.chip,
                                       state_stride=getattr(
                                           any_eng, "snap_every", 0)),
                                   kv_keep=kv_keep)
    # always-on request-lifecycle tracing: the ring bounds memory and the
    # per-event cost is one lock + list append (<3% on the packing
    # benchmark — see BENCH_packing.json), so the replay always records
    # full timelines; --trace-dump / the /trace endpoint just export them
    tracer = SpanTracer(capacity=trace_capacity)
    server = AsyncServer(
        pool, router=get_router(router), admission=ctrl,
        retry=RetryPolicy(budget=retry_budget),
        watchdog=(JCTDeadlineWatchdog(factor=watchdog_factor,
                                      min_deadline=watchdog_min_deadline)
                  if watchdog else None),
        brownout=BrownoutController() if brownout else None,
        tracer=tracer)
    if sup is not None:
        wire_supervisor(sup, server)
        if plan is not None:
            wrap_pool_processes(pool, plan, sup)
    server.start()
    if sup is not None:
        sup.start()
        print(f"workers: " + " ".join(
            f"{n}=pid:{sup.handles[n].pid}" for n in sorted(sup.handles)),
            flush=True)
    exporter = None
    # SIGTERM/SIGINT -> drain instead of dying mid-batch (satellite of the
    # chaos-hardening PR: a preempted serve CLI must resolve every future)
    handler = PreemptionHandler().install()
    if metrics_port is not None:
        exporter = start_metrics_server(server.metrics, metrics_port,
                                        tracer=tracer)
        print(f"metrics: http://{exporter.server_address[0]}:"
              f"{exporter.server_address[1]}/metrics  "
              f"(+ /trace, /trace.chrome.json)")
    try:
        out = _replay(server, arch, trace_name, qps, scale_tokens, seed,
                      max_requests, deadline, pool, trace_kw,
                      stop=lambda: handler.requested,
                      drain_timeout=drain_timeout)
        if plan is not None:
            out["faults_injected"] = plan.counts()
        if trace_dump:
            cp = write_trace_dump(tracer, trace_dump)
            print(f"trace dump: {trace_dump} + {cp}")
        return out
    finally:
        handler.uninstall()
        if sup is not None:
            sup.stop(graceful=True)
        # shutdown() stops serve_forever; server_close() releases the bound
        # socket — without it a second serve_trace on the same port (the
        # documented warmed-pool reuse pattern) dies with EADDRINUSE
        if exporter is not None:
            exporter.shutdown()
            exporter.server_close()


def _replay(server, arch, trace_name, qps, scale_tokens, seed, max_requests,
            deadline, pool, trace_kw, stop=None,
            drain_timeout=None) -> Dict:
    trace = get_trace(trace_name, qps, scale_tokens=scale_tokens,
                      materialize_tokens=True,
                      vocab=min(512, get_config(arch).vocab_size), seed=seed,
                      **(trace_kw or {}))
    requests = trace.requests[:max_requests] if max_requests else trace.requests
    yes_no = (5, 9)

    t0 = time.perf_counter()
    futures = []
    preempted = False
    for r in requests:                      # open loop: real-time arrivals
        # sleep to the arrival in short slices so a SIGTERM mid-gap stops
        # the replay within ~100ms, not after the longest arrival gap
        while True:
            if stop is not None and stop():
                preempted = True
                break
            delay = t0 + r.arrival - time.perf_counter()
            if delay <= 0:
                break
            time.sleep(min(delay, 0.1))
        if preempted:
            break
        futures.append(server.submit(
            r.user_id, r.tokens, allowed_tokens=yes_no,
            deadline=(t0 + r.arrival + deadline) if deadline else None))
    server.drain(timeout=drain_timeout)
    wall = time.perf_counter() - t0
    # if the drain timed out, shutdown resolves the stragglers Rejected
    # ("shutdown") — a preempted/overloaded replay still resolves every
    # future before reporting
    server.shutdown(drain=True, timeout=1.0 if drain_timeout else None)

    outcomes = [f.result() for f in futures]
    served = [o for o in outcomes if not isinstance(o, Rejected)]
    rejected = [o for o in outcomes if isinstance(o, Rejected)]
    # no fabricated samples: a fully-shed run reports NaN latency, not a
    # vacuous 0.0 that would read as a perfect tail
    lats = np.array([o["latency"] for o in served]) if served \
        else np.array([np.nan])
    hit = sum(o["n_cached"] for o in served)
    tot = sum(o["n_input"] for o in served)
    reasons: Dict[str, int] = {}
    for o in rejected:
        reasons[o.reason] = reasons.get(o.reason, 0) + 1
    return {
        "requests": len(outcomes),
        "served": len(served),
        "rejected": len(rejected),
        "reject_reasons": reasons,
        "preempted": preempted,
        "retried": server.metrics.total("requests_retried"),
        "watchdog_trips": server.metrics.total("watchdog_trips"),
        "wall_seconds": wall,
        "throughput_rps": len(served) / wall,
        "mean_latency": float(lats.mean()),
        "p50_latency": float(np.percentile(lats, 50)),
        "p99_latency": float(np.percentile(lats, 99)),
        "token_hit_rate": hit / max(tot, 1),
        # JCT-calibration fit per instance: coefficients, residual p50/p95,
        # refit counts — readable from results without scraping Prometheus
        "jct_fit": {n: e.stats().get("jct")
                    for n, e in pool.engines.items()},
        "trace": (server.tracer.stats()
                  if server.tracer is not None else None),
        "metrics": server.metrics.render(),
        "per_instance": {n: e.stats() for n, e in pool.engines.items()},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--published-widths", action="store_true",
                    help="serve --arch at its published widths (default: "
                         "the reduced CPU test preset)")
    ap.add_argument("--trace", default="post_recommendation")
    ap.add_argument("--qps", type=float, default=5.0)
    ap.add_argument("--instances", type=int, default=2)
    ap.add_argument("--workers", type=int, default=0, metavar="N",
                    help="process mode: N supervised engine worker "
                         "PROCESSES behind the RPC boundary (0 = classic "
                         "in-process thread mode with --instances engines)")
    ap.add_argument("--policy", default="srjf_calibrated",
                    choices=["fifo", "srjf", "srjf_calibrated"])
    ap.add_argument("--router", default="least_backlog",
                    choices=["user_hash", "least_backlog"])
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline, seconds after arrival")
    ap.add_argument("--no-admission", action="store_true")
    ap.add_argument("--profile", action="store_true",
                    help="run the JCT profile fit per instance first")
    ap.add_argument("--lam", type=float, default=0.05)
    ap.add_argument("--scale-tokens", type=float, default=0.02)
    ap.add_argument("--max-requests", type=int, default=60)
    ap.add_argument("--dump-metrics", action="store_true")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text metrics on this port "
                         "(GET /metrics, /trace, /trace.chrome.json) "
                         "during the replay; 0 = ephemeral")
    ap.add_argument("--trace-dump", default=None, metavar="PATH",
                    help="write request/batch timelines as JSONL to PATH "
                         "(+ PATH stem .chrome.json for Perfetto) on exit")
    ap.add_argument("--retry-budget", type=int, default=2,
                    help="idempotent re-submissions per lost request "
                         "(0 disables retry)")
    ap.add_argument("--no-watchdog", action="store_true",
                    help="disable the JCT-deadline hang watchdog")
    ap.add_argument("--watchdog-factor", type=float, default=4.0,
                    help="trip when an in-flight batch exceeds this "
                         "multiple of its predicted JCT")
    ap.add_argument("--watchdog-min-deadline", type=float, default=1.0,
                    help="absolute floor on the per-batch deadline, sec")
    ap.add_argument("--brownout", action="store_true",
                    help="arm the graceful-degradation ladder")
    ap.add_argument("--offload", action="store_true",
                    help="DRAM KV tier: evicted prefix blocks demote to "
                         "host memory and restore (or router-prefetch) "
                         "instead of recomputing")
    ap.add_argument("--host-cache-mb", type=int, default=256,
                    help="DRAM tier capacity per instance, MiB")
    ap.add_argument("--cache-tokens", type=int, default=4096,
                    help="device prefix-KV cache capacity per instance, "
                         "tokens")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    help="max seconds to drain on completion or SIGTERM")
    chaos = ap.add_argument_group(
        "chaos", "seeded fault injection (any rate > 0 wraps the pool)")
    chaos.add_argument("--chaos-seed", type=int, default=0)
    chaos.add_argument("--chaos-step-error", type=float, default=0.0,
                       help="P(step crashes after the forward, results lost)")
    chaos.add_argument("--chaos-hang", type=float, default=0.0,
                       help="P(step hangs past the watchdog deadline)")
    chaos.add_argument("--chaos-hang-seconds", type=float, default=1.0)
    chaos.add_argument("--chaos-straggler", type=float, default=0.0,
                       help="P(step dawdles below the watchdog deadline)")
    chaos.add_argument("--chaos-straggler-seconds", type=float, default=0.1)
    chaos.add_argument("--chaos-nan", type=float, default=0.0,
                       help="P(step results corrupted to non-finite scores)")
    chaos.add_argument("--chaos-submit-error", type=float, default=0.0,
                       help="P(submit raises transiently)")
    chaos.add_argument("--chaos-max-faults", type=int, default=None,
                       help="total fault budget across the run")
    chaos.add_argument("--chaos-kill", type=float, default=0.0,
                       help="process mode: P(SIGKILL the worker mid-batch)")
    chaos.add_argument("--chaos-freeze", type=float, default=0.0,
                       help="process mode: P(SIGSTOP-freeze the worker)")
    chaos.add_argument("--chaos-freeze-seconds", type=float, default=1.0)
    chaos.add_argument("--chaos-rpc-drop", type=float, default=0.0,
                       help="process mode: P(drop a submit/step response)")
    chaos.add_argument("--chaos-rpc-delay", type=float, default=0.0,
                       help="process mode: P(delay a submit/step response)")
    chaos.add_argument("--chaos-rpc-delay-seconds", type=float,
                       default=0.05)
    args = ap.parse_args()
    enable_compile_cache()
    chaos_cfg = None
    if any(r > 0 for r in (args.chaos_step_error, args.chaos_hang,
                           args.chaos_straggler, args.chaos_nan,
                           args.chaos_submit_error, args.chaos_kill,
                           args.chaos_freeze, args.chaos_rpc_drop,
                           args.chaos_rpc_delay)):
        chaos_cfg = ChaosConfig(
            seed=args.chaos_seed, step_error=args.chaos_step_error,
            hang=args.chaos_hang, hang_seconds=args.chaos_hang_seconds,
            straggler=args.chaos_straggler,
            straggler_seconds=args.chaos_straggler_seconds,
            nan_score=args.chaos_nan,
            submit_error=args.chaos_submit_error,
            max_faults=args.chaos_max_faults,
            kill=args.chaos_kill, freeze=args.chaos_freeze,
            freeze_seconds=args.chaos_freeze_seconds,
            rpc_drop=args.chaos_rpc_drop, rpc_delay=args.chaos_rpc_delay,
            rpc_delay_seconds=args.chaos_rpc_delay_seconds)
    out = serve_trace(args.arch, args.trace, qps=args.qps,
                      n_instances=args.instances, workers=args.workers,
                      policy=args.policy,
                      lam=args.lam, scale_tokens=args.scale_tokens,
                      max_requests=args.max_requests, router=args.router,
                      deadline=args.deadline,
                      admission=not args.no_admission, profile=args.profile,
                      metrics_port=args.metrics_port,
                      retry_budget=args.retry_budget,
                      watchdog=not args.no_watchdog,
                      watchdog_factor=args.watchdog_factor,
                      watchdog_min_deadline=args.watchdog_min_deadline,
                      brownout=args.brownout, chaos=chaos_cfg,
                      drain_timeout=args.drain_timeout,
                      trace_dump=args.trace_dump,
                      offload=args.offload,
                      host_cache_mb=args.host_cache_mb,
                      cache_tokens=args.cache_tokens,
                      published_widths=args.published_widths)
    for k, v in out.items():
        if k == "metrics":
            if args.dump_metrics:
                print("--- metrics ---")
                print(v)
        elif k not in ("per_instance", "jct_fit"):
            print(f"{k}: {v}")
    for n, fit in sorted((out.get("jct_fit") or {}).items()):
        if fit:
            print(f"jct_fit[{n}]: a={fit['a']:.3g} b={fit['b']:.3g} "
                  f"r={fit['pearson_r']:.3f} "
                  f"resid_p50={fit['residual_p50']:.4f} "
                  f"resid_p95={fit['residual_p95']:.4f} "
                  f"refits={fit['refits']}+{fit['drift_refits']}")
    errors = out["reject_reasons"].get("error", 0)
    if errors and chaos_cfg is None:
        # without injected faults, an errored request is a broken engine
        print(f"FAILED: {errors} request(s) ended Rejected('error')",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
