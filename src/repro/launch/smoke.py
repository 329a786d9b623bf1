"""Serve-smoke validator: boot a tiny pool, scrape /metrics + /trace, and
check the observability plane end to end.

CI runs this after the plain serve soak. It validates, with hard exits:

  * the Prometheus payload PARSES (strict line-format check: HELP/TYPE
    comments, sample syntax, cumulative ``le`` buckets ending ``+Inf``,
    ``_count`` == the ``+Inf`` bucket) and contains the JCT-calibration
    series (``jct_coef_a`` gauge, ``jct_residual_seconds`` histogram);
  * the /trace JSONL dump contains at least one COMPLETE submit→deliver
    timeline (submit, route, enqueue, finish events; queue + execute
    spans) for a delivered request;
  * /trace.chrome.json is valid JSON whose phase spans nest inside their
    request's umbrella span (what Perfetto renders as containment).

``--jsonl FILE`` instead validates an existing ``--trace-dump`` file pair
written by a prior ``repro.launch.serve`` run (used by CI to check the CLI
path produced a loadable dump).

The pool is deliberately solo-packing with same-length requests: after the
first (compile) step every step is warm, so the JCT monitor has observed
samples and the residual histograms are non-empty by scrape time.
"""
from __future__ import annotations

import argparse
import json
import math
import re
import sys
import urllib.request
from pathlib import Path
from typing import Dict, List

_SAMPLE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>[^}]*)\})? '
    r'(?P<value>[^ ]+)$')
_LABEL = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"$')


def parse_prometheus(text: str) -> Dict[str, List[Dict]]:
    """Strict parse of the text exposition format; raises ValueError on any
    malformed line. Returns {metric_name: [{labels, value}, ...]} keyed by
    the SAMPLE name (``foo_bucket`` etc., not the family name)."""
    series: Dict[str, List[Dict]] = {}
    typed = set()
    for ln, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            parts = line.split(" ", 3)
            if len(parts) < 4 or not parts[2]:
                raise ValueError(f"line {ln}: malformed comment: {line!r}")
            if parts[1] == "TYPE":
                if parts[3] not in ("counter", "gauge", "histogram",
                                    "summary", "untyped"):
                    raise ValueError(f"line {ln}: bad TYPE {parts[3]!r}")
                typed.add(parts[2])
            continue
        if line.startswith("#"):
            raise ValueError(f"line {ln}: unknown comment: {line!r}")
        m = _SAMPLE.match(line)
        if m is None:
            raise ValueError(f"line {ln}: malformed sample: {line!r}")
        labels = {}
        if m.group("labels"):
            for pair in re.split(r',(?=[a-zA-Z_])', m.group("labels")):
                if not _LABEL.match(pair):
                    raise ValueError(f"line {ln}: bad label {pair!r}")
                k, v = pair.split("=", 1)
                labels[k] = v[1:-1]
        try:
            value = float(m.group("value"))
        except ValueError:
            raise ValueError(f"line {ln}: bad value {m.group('value')!r}")
        family = re.sub(r'_(bucket|sum|count)$', '', m.group("name"))
        if family not in typed and m.group("name") not in typed:
            raise ValueError(f"line {ln}: sample {m.group('name')!r} has "
                             f"no preceding # TYPE")
        series.setdefault(m.group("name"), []).append(
            {"labels": labels, "value": value})
    return series


def validate_histograms(series: Dict[str, List[Dict]]) -> List[str]:
    """Cumulative-bucket + _sum/_count consistency across every histogram
    family in a parsed exposition. Returns the family names checked."""
    fams = sorted({n[:-len("_bucket")] for n in series if
                   n.endswith("_bucket")})
    for fam in fams:
        by_inst: Dict[str, List[Dict]] = {}
        for s in series[fam + "_bucket"]:
            by_inst.setdefault(s["labels"].get("instance", ""),
                               []).append(s)
        for inst, buckets in by_inst.items():
            les = [b["labels"].get("le") for b in buckets]
            if "+Inf" not in les:
                raise ValueError(f"{fam}{{{inst}}}: no +Inf bucket")
            if les[-1] != "+Inf":
                raise ValueError(f"{fam}{{{inst}}}: +Inf not last")
            vals = [b["value"] for b in buckets]
            if vals != sorted(vals):
                raise ValueError(f"{fam}{{{inst}}}: buckets not cumulative")
            count = [s["value"] for s in series.get(fam + "_count", [])
                     if s["labels"].get("instance", "") == inst]
            if not count or count[0] != vals[-1]:
                raise ValueError(f"{fam}{{{inst}}}: _count != +Inf bucket")
            ssum = [s["value"] for s in series.get(fam + "_sum", [])
                    if s["labels"].get("instance", "") == inst]
            if not ssum or not math.isfinite(ssum[0]):
                raise ValueError(f"{fam}{{{inst}}}: bad _sum")
    return fams


def validate_trace_jsonl(text: str) -> Dict:
    """Require one complete submit→deliver timeline; returns that record."""
    requests = []
    batches = 0
    for ln, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        row = json.loads(line)
        if row.get("type") == "request":
            requests.append(row)
        elif row.get("type") == "batch":
            batches += 1
    delivered = [r for r in requests if r.get("outcome") == "delivered"]
    if not delivered:
        raise ValueError(f"no delivered request in trace dump "
                         f"({len(requests)} requests, {batches} batches)")
    for r in delivered:
        events = [e["name"] for e in r["events"]]
        spans = {s["name"] for s in r["spans"]}
        missing = {"submit", "route", "enqueue", "finish"} - set(events)
        if not missing and {"queue", "execute"} <= spans:
            ts = [e["t"] for e in r["events"]]
            if ts != sorted(ts):
                raise ValueError(f"req {r['req_id']}: events out of order")
            for s in r["spans"]:
                if s["t1"] < s["t0"]:
                    raise ValueError(f"req {r['req_id']}: negative span "
                                     f"{s['name']}")
            return r
    raise ValueError(
        "no delivered request has a complete timeline; first delivered "
        f"has events={delivered[0]['events']} spans={delivered[0]['spans']}")


def validate_chrome(obj: Dict) -> int:
    """Perfetto-loadability proxy: the JSON parsed, every event carries the
    required keys, and each phase span nests inside a request umbrella span
    on the same (pid, tid). Returns the number of nested phase spans."""
    events = obj["traceEvents"]
    umbrellas = [e for e in events if e["ph"] == "X"
                 and e["name"].startswith("request ")]
    if not umbrellas:
        raise ValueError("no request umbrella spans")
    nested = 0
    for e in events:
        if e["ph"] not in ("X", "i", "M"):
            raise ValueError(f"unknown phase {e['ph']!r}")
        if e["ph"] == "X" and (e["ts"] < 0 or e["dur"] <= 0):
            raise ValueError(f"bad X event timing: {e}")
        if (e["ph"] == "X" and not e["name"].startswith("request ")
                and not e["name"].startswith("step ")):
            host = [u for u in umbrellas
                    if u["pid"] == e["pid"] and u["tid"] == e["tid"]
                    and u["ts"] <= e["ts"] + 1e-6
                    and e["ts"] + e["dur"] <= u["ts"] + u["dur"] + 1e-3]
            if not host:
                raise ValueError(f"span {e['name']!r} (tid {e['tid']}) not "
                                 f"nested in any request span")
            nested += 1
    return nested


def _fetch(url: str) -> str:
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read().decode()


def run_live_smoke(n_requests: int = 12, arch: str = "qwen1.5-0.5b",
                   workers: int = 0) -> None:
    """In-process end-to-end: pool -> AsyncServer(+tracer) -> HTTP scrape.

    ``workers=N`` runs the SAME strict validation against the process-mode
    plane: N supervised engine worker processes behind the RPC boundary.
    The scrape then exercises the full telemetry bridge — worker-side JCT
    series ride the heartbeat ``dump_state`` merge, spans/batches are
    replayed off step responses — and every validator (prometheus line
    discipline, complete submit→deliver timelines, chrome nesting) must
    hold with the engines in separate processes.
    """
    import numpy as np

    from repro.configs import serving_config
    from repro.launch.serve import start_metrics_server
    from repro.serving import AsyncServer, SpanTracer

    cfg = serving_config(arch)
    sup = None
    # tier-exercising engine shape: the device cache holds only 4 blocks
    # (64 tokens — two 40-token requests' kept KV), so the first submission
    # round FORCES evictions into the DRAM tier; re-submitting the same
    # token lists then restores/prefetches from host. offload_host_bw is
    # pinned huge because worth_restoring prices the TARGET chip's
    # recompute rate, which this CPU box can't approach.
    tier_ecfg = {"max_pack_requests": 1, "cache_capacity_tokens": 64,
                 "offload": True, "offload_host_bw": 1e18,
                 "prefix_bucket_blocks": 1}
    if workers:
        from repro.serving import make_process_pool, wire_supervisor
        # solo packing + same-length requests below: after the first
        # (compile) step every step is warm -> JCT monitor has samples
        specs = {f"inst{i}": {"kind": "engine", "arch": arch, "seed": 0,
                              "ecfg": dict(tier_ecfg)}
                 for i in range(workers)}
        pool, sup = make_process_pool(
            specs, lease=30.0, heartbeat_interval=0.4, miss_budget=12,
            spawn_timeout=600.0, step_timeout=300.0, drain_grace=30.0)
    else:
        import jax
        import jax.numpy as jnp

        from repro.core.engine import EngineConfig, PrefillOnlyEngine
        from repro.models.model import build
        from repro.runtime.fault_tolerance import InstancePool
        from repro.runtime.sharding import materialize

        api = build(cfg)
        params = materialize(jax.random.PRNGKey(0), api.defs(), jnp.float32)

        def make_engine(name: str) -> PrefillOnlyEngine:
            return PrefillOnlyEngine(cfg, params, EngineConfig(**tier_ecfg))

        pool = InstancePool(make_engine)
        pool.scale_to(["inst0"])
    tracer = SpanTracer()
    server = AsyncServer(pool, tracer=tracer).start()
    if sup is not None:
        import os as _os
        wire_supervisor(sup, server)
        sup.start()
        pids = {h.pid for h in sup.handles.values()}
        assert _os.getpid() not in pids, \
            f"worker pids overlap the frontend: {pids}"
        print(f"process mode: {len(pids)} worker processes "
              f"{sorted(pids)} (frontend pid {_os.getpid()})")
    exporter = start_metrics_server(server.metrics, 0, tracer=tracer)
    host, port = exporter.server_address
    base = f"http://{host}:{port}"
    try:
        rng = np.random.default_rng(0)
        token_lists = [rng.integers(0, cfg.vocab_size, 40).tolist()
                       for _ in range(n_requests)]
        # round 1: distinct 40-token requests overflow the 4-block device
        # cache -> evictions demote kept KV into the host tier
        futs = [server.submit(f"u{i}", toks, allowed_tokens=(5, 9))
                for i, toks in enumerate(token_lists)]
        assert server.drain(timeout=600.0 if workers else 120.0), \
            "drain timed out"
        # round 2: the SAME token lists — their prefixes now live host-side,
        # so submits trigger router-time prefetch and executes restore
        futs += [server.submit(f"u{i}", toks, allowed_tokens=(5, 9))
                 for i, toks in enumerate(token_lists)]
        assert server.drain(timeout=600.0 if workers else 120.0), \
            "drain timed out (round 2)"
        results = [f.result() for f in futs]
        delivered = [r for r in results if isinstance(r, dict)]
        assert delivered, f"nothing delivered: {results}"
        if sup is not None:
            # worker-side JCT series arrive on the NEXT heartbeat after the
            # final warm step; wait out one beat cycle before scraping
            import time as _time
            _time.sleep(3 * sup.heartbeat_interval)

        prom = _fetch(base + "/metrics")
        series = parse_prometheus(prom)
        fams = validate_histograms(series)
        for needed in ("prefillonly_jct_coef_a", "prefillonly_jct_coef_b",
                       "prefillonly_jct_pearson_r"):
            assert needed in series, f"missing gauge {needed}"
        assert "prefillonly_jct_residual_seconds" in fams, \
            f"jct_residual_seconds histogram absent (families: {fams})"
        print(f"metrics ok: {len(series)} series, "
              f"{len(fams)} histogram families")

        # hierarchical KV memory: the 4-block device cache must have
        # demoted blocks host-side in round 1, and round 2 must have
        # brought some back (execute-path restore and/or router prefetch)
        def _total(name: str) -> float:
            return sum(s["value"] for s in series.get(name, []))
        offloaded = _total("prefillonly_kv_offload_blocks")
        restored = _total("prefillonly_kv_restore_blocks")
        prefetched = _total("prefillonly_kv_prefetch_blocks")
        assert offloaded > 0, "no KV blocks demoted to the host tier"
        assert restored + prefetched > 0, \
            "no KV blocks came back from the host tier"
        assert "prefillonly_host_kv_used_bytes" in series, \
            "host tier occupancy gauge absent"
        triggers = _total("prefillonly_prefetches_triggered")
        print(f"offload tier ok: {offloaded:.0f} blocks demoted, "
              f"{restored:.0f} restored + {prefetched:.0f} prefetched "
              f"({triggers:.0f} router-time prefetch triggers)")

        timeline = validate_trace_jsonl(_fetch(base + "/trace"))
        print(f"trace ok: complete submit→deliver timeline for req "
              f"{timeline['req_id']} ({len(timeline['events'])} events, "
              f"{len(timeline['spans'])} spans)")

        nested = validate_chrome(
            json.loads(_fetch(base + "/trace.chrome.json")))
        print(f"chrome trace ok: {nested} phase spans nested")
    finally:
        server.shutdown(drain=False)
        if sup is not None:
            sup.stop(graceful=True)
        exporter.shutdown()
        exporter.server_close()


def validate_dump_files(jsonl_path: str) -> None:
    p = Path(jsonl_path)
    timeline = validate_trace_jsonl(p.read_text())
    print(f"trace dump ok: complete timeline for req "
          f"{timeline['req_id']}")
    cp = p.with_suffix(".chrome.json")
    nested = validate_chrome(json.loads(cp.read_text()))
    print(f"chrome dump ok ({cp}): {nested} phase spans nested")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--workers", type=int, default=0, metavar="N",
                    help="process mode: validate against N supervised "
                         "engine worker processes (0 = in-process pool)")
    ap.add_argument("--jsonl", default=None, metavar="FILE",
                    help="validate an existing --trace-dump file pair "
                         "instead of running the live smoke")
    args = ap.parse_args()
    try:
        if args.jsonl:
            validate_dump_files(args.jsonl)
        else:
            from repro.runtime.compile_cache import enable_compile_cache
            enable_compile_cache()
            run_live_smoke(args.requests, args.arch, workers=args.workers)
    except (AssertionError, ValueError, KeyError) as e:
        print(f"SMOKE FAILED: {e}", file=sys.stderr)
        sys.exit(1)
    print("serve smoke: OK")


if __name__ == "__main__":
    main()
