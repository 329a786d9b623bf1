"""One run of one cell: set-up, the measured window, the readings, the check.

End-to-end metrics are computed here, from the client's side. The window
sends requests for ``--seconds``; then it sends nothing more, waits for all
that it sent, and reads the clock: all of that work counts, over all of
that time.

``scored_rps``           requests sent in the window and scored / seconds
                         from the window's start to the last answer
``prompt_tokens_per_s``  whole-prompt tokens (cached or not) of those
                         requests / the same seconds
``setup_s``              process start to the window's start: JAX start-up,
                         weights, engine and profile run, compiles, the
                         mix's set-up traffic

A traced run (per-layer metrics) sends for at most ``TRACE_SECONDS``, all of
it under the profiler: the profiler's stop takes time in proportion to the
device operations traced, and a whole window's would outlast the time a run
may take.
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List

import numpy as np

import check
import peaks
import trace_reduce
from compile_clock import CompileClock
from registry import Registry
from workload import Mix

WAIT_AFTER_S = 60.0        # how long answers sent in the window are awaited
TRACE_SECONDS = 15.0       # the longest window a traced run sends in


class NoChip(RuntimeError):
    pass


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (no interpolation across an infinite tail)."""
    v = sorted(values)
    if not v:
        return math.inf
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def end_to_end(name: str, sent: List, t0: float, t_end: float,
               setup_s: float) -> float:
    """``sent``: the window's requests; ``t_end``: when the last returned."""
    secs = t_end - t0
    scored = [s for s in sent if s.ok and t0 <= s.done <= t_end]
    if name == "setup_s":
        return setup_s
    if name == "scored_rps":
        return len(scored) / secs
    if name == "prompt_tokens_per_s":
        return sum(s.n_input for s in scored) / secs
    raise KeyError(f"no end-to-end metric {name!r}")


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True, registry=None,
        engine_cls=None, with_control: bool = False, log=None) -> Dict:
    """Run ``workload`` once; returns the result object. Raises ``NoChip``
    before any work when ``require_chip`` and JAX finds no TPU, or fewer
    chips than the cell asks for. The cell runs one one-chip replica per
    chip it asks for; off the chip the replicas are placed round-robin over
    the devices JAX has. ``with_control`` also reads the fp8 control on the
    same sampled prompts (``result["control"]``), for setting limits; the
    benchmark's own runs never do."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    reg = registry or Registry.from_file(root / "BENCHMARK.json")
    cell = reg.cell(workload)
    cfg = reg.config(cell["config"])
    spec = reg.traffic(cell["traffic"])
    limits = reg.limits(workload)
    arch = reg.arch(cfg["arch"])

    import jax
    devices = jax.devices()
    chips = int(cell["chips"])
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    placed = [devices[i % len(devices)] for i in range(chips)]
    devices = list(dict.fromkeys(placed))
    import served as S
    clock = CompileClock()
    m = cfg["model"]
    dtype = m.get("param_dtype", "bfloat16")
    marks = [("start", t_start)]
    params = arch.make_params(m, cfg["weights"], seed, dtype)
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    mix = Mix(spec, m["vocab_size"], seed)
    labels = mix.labels
    sv = S.Served(cfg, params, placed,
                  engine_cls=engine_cls or S.TracedEngine)
    del params
    marks.append(("engines+profile", time.perf_counter()))
    for name, eng in sv.engines.items():
        log(f"{name} on {eng.device}: built and profiled in "
            f"{sv.built_s[name]:.2f}s; cache_tokens {sv.cache_tokens}; "
            f"pack_token_budget={eng.ecfg.pack_token_budget} "
            f"max_pack_requests={eng.ecfg.max_pack_requests} "
            f"pack_prefix_budget={eng.ecfg.pack_prefix_budget}")

    # ---- set-up traffic ------------------------------------------------
    # the engine's reuse step and suffix buckets: the solo cache-hit
    # programs are keyed on them
    ecfg = next(iter(sv.engines.values())).ecfg
    prompts = mix.warm_prompts(ecfg.block_size * ecfg.prefix_bucket_blocks,
                               ecfg.suffix_buckets)
    log(f"set-up: {len(prompts)} requests one at a time")
    warm = sv.serial(prompts, labels, timeout=600.0)
    marks.append(("warm prompts", time.perf_counter()))
    n_warm = int(spec.get("warm_requests", 0))
    if n_warm:
        k = int(spec["loop"].get("outstanding", 1))
        warm += sv.batch(mix.warm_stream(n_warm), labels, k, timeout=600.0)
        marks.append(("warm stream", time.perf_counter()))
    log("set-up s: " + ", ".join(f"{n} {b - a:.2f}" for (_, a), (n, b)
                                 in zip(marks, marks[1:])))
    warm_bad = sum(1 for s in warm if not s.ok)
    sv.tracer.drain_batches()
    trace_dir = root / ".bench_trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False   # no reading uses it
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    gc.collect()

    # ---- the measured window -------------------------------------------
    t0 = time.perf_counter()
    t1 = t0 + seconds
    setup_s = t0 - t_start
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        sent = sv.closed_window(mix, labels, int(spec["loop"]["outstanding"]),
                                t1)
        t_closed = time.perf_counter()
        sv.wait_idle(t1 + WAIT_AFTER_S)
        t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
        log(f"trace: stopped {time.perf_counter() - t_end:.2f}s after "
            f"the last answer")
    n_comp, comp_s = clock.between(t0, t_end)
    comp_by_thread = clock.by_thread(t0, t_end)
    clock.close()
    batches = sv.tracer.drain_batches()
    mem_peak = S.device_peak_bytes(devices)
    log(f"device memory: peak {mem_peak} of "
        f"{(devices[0].memory_stats() or {}).get('bytes_limit')} bytes")
    path_of = {rid: b.jit_path for b in batches for rid in b.req_ids}
    inst_of = {rid: b.instance for b in batches for rid in b.req_ids}
    sv.close()
    trips = sv.server.metrics.total("watchdog_trips")
    retried = sv.server.metrics.total("requests_retried")
    eng_stats = {n: e.stats() for n, e in sv.engines.items()}
    errors = list(dict.fromkeys(x for e in sv.engines.values()
                                for x in e.errors))
    built_s = dict(sv.built_s)
    del sv
    gc.collect()

    # ---- readings --------------------------------------------------------
    window = [s for s in sent if s.sent < t1]
    failed = sum(1 for s in window if not s.ok)
    rows = []
    for s in window:
        if not s.ok:
            continue
        rid = s.result["req_id"]
        rows.append({"n_input": s.n_input, "n_cached": s.result["n_cached"],
                     "path": path_of.get(rid, "unknown"),
                     "instance": inst_of.get(rid, "unknown"),
                     "sent": s, "done": s.done})
    metrics = {}
    units = {e["name"]: e["unit"] for e in reg.bench["end_to_end"]}
    for e in reg.end_to_end(workload):
        v = end_to_end(e["name"], sent, t0, t_end, setup_s)
        metrics[e["name"]] = {"value": v, "unit": units[e["name"]]}
    kind = devices[0].device_kind
    result: Dict = {"correct": False, "attempted": len(window),
                    "failed": failed}
    lat = sorted((s.done - s.sent) if s.ok else math.inf for s in window)
    log(f"window {seconds}s: attempted {len(window)} failed {failed} "
        f"scored {sum(1 for r in rows if t0 <= r['done'] <= t_end)}; "
        f"set-up requests failed {warm_bad}; compiles in window {n_comp} "
        f"({comp_s:.3f}s); sending stopped {t_closed - t1:.4f}s late, "
        f"last answer {t_end - t1:.4f}s after")
    reasons: Dict[str, int] = {}
    for s in window + warm:
        if not s.ok:
            r = s.result
            why = (f"rejected:{r.reason}" if r is not None and hasattr(
                r, "reason") else "corrupt" if r is not None else "unfinished")
            reasons[why] = reasons.get(why, 0) + 1
    if reasons:
        log(f"not ok: {reasons}")
    log(f"watchdog trips {trips}; retried {retried}")
    for e in errors:
        log(f"engine step raised: {e}")
    log(f"latency s (sent -> result): p50 {percentile(lat, 50):.4f} "
        f"p90 {percentile(lat, 90):.4f} max {lat[-1] if lat else 0:.4f}")
    log("steps by path: " + ", ".join(
        f"{p}={sum(1 for b in batches if b.jit_path == p and t0 <= b.ts <= t_end)}"
        for p in sorted({b.jit_path for b in batches})))
    replicas = {}
    for name, st in eng_stats.items():
        replicas[name] = {
            "scored": sum(1 for r in rows if r["instance"] == name
                          and t0 <= r["done"] <= t_end),
            "compiles_in_window": comp_by_thread.get(f"engine-{name}", 0),
            "built_s": built_s[name]}
        log(f"{name}: scored {replicas[name]['scored']}, compiles in window "
            f"{replicas[name]['compiles_in_window']}, hit_rate "
            f"{st['hit_rate']:.4f} packed_steps {st['packed_steps']} "
            f"cache {st['cache']}")

    breakdown = None
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    if trace:
        red = None
        pbs = sorted(trace_dir.glob("**/*.xplane.pb"))
        if pbs:
            t_read = time.perf_counter()
            planes = trace_reduce.load(str(pbs[-1]))
            t_red = time.perf_counter()
            red = trace_reduce.reduce(planes)
            log(f"trace: {pbs[-1].stat().st_size} bytes, "
                f"{sum(len(ln['events']) for pl in planes for ln in pl['lines'])}"
                f" events read in {t_red - t_read:.2f}s, reduced in "
                f"{time.perf_counter() - t_red:.2f}s")
            del planes
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            log(f"trace: busy {red['busy_s']:.4f}s of {red['window_s']:.4f}s;"
                f" steps {red['step_s']:.4f}s, busy in steps "
                f"{red['busy_in_steps_s']:.4f}s")
        ctx = SimpleNamespace(
            cell=cell, config=cfg, traffic=spec, t0=t0, t1=t_end,
            requests=rows,
            batches=[b for b in batches if t0 <= b.ts <= t_end],
            trace=red, compiles=(n_comp, comp_s),
            peak=peaks.peak(kind) if devices[0].platform == "tpu" else None,
            request_flops=lambda c, p: arch.request_flops(m, c, p))
        metrics = {}
        for pl in reg.per_layer(workload):
            v = reg.metric(pl["name"])(ctx)
            if v is not None:
                metrics[pl["name"]] = {"value": v, "unit": pl["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown

    # ---- the check against the reference -----------------------------------
    picked = check.sample(rows, int(spec["check_requests"]), seed)
    ref = arch.label_logits(cfg, seed, [r["sent"].tokens for r in picked],
                            labels) if picked else np.zeros((0, 0))
    per = [check.served_numbers(r["sent"].result["scores"],
                                r["sent"].result["token"], labels, ref[i])
           for i, r in enumerate(picked)]
    log("checked: " + ", ".join(f"{r['path']}:{r['n_input']}"
                                for r in picked))
    ctl = []
    if with_control and picked:
        ctrl = arch.label_logits(
            cfg, seed, [r["sent"].tokens for r in picked], labels,
            quant="fp8")
        ctl = [check.control_numbers(ctrl[i], ref[i])
               for i in range(len(picked))]
        result["control"] = check.summary(ctl)
    for i, r in enumerate(picked):
        log(f"request {r['instance']} {r['path']}:{r['n_input']} reference "
            f"{ref[i].tolist()} program {per[i]}"
            + (f" control {ctl[i]}" if ctl else ""))
    readings = check.summary(per)
    log("read (compared where a limit is named): " + ", ".join(
        f"{k} {v!r}" for k, v in readings.items()))
    verdict = check.verdict(readings, limits, len(picked), failed)
    result["correct"] = check.is_correct(verdict)
    result["readings"] = readings
    result["replicas"] = replicas
    result["checks"] = verdict
    for line in check.describe(verdict):
        log(line)
    return result

