#!/usr/bin/env python3
"""Chip smoke: serve qwen1.5-0.5b at published widths on a TPU, end to end.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four one-chip replicas behind the router

One chip runs two phases, each in a process of its own (a chip belongs to one
process at a time, and this parent never imports JAX):

  serve    the thread-mode served path (``launch.serve.make_pool`` ->
           ``AsyncServer`` with the ``least_backlog`` router) on random
           weights from ``--seed``, hybrid prefilling on. Traffic: one user
           profile shared by several posts (prefix hits and packed hits),
           short unshared requests (packed misses) and one unshared request
           longer than ``hybrid_chunk`` (chunked hybrid prefill). It fails
           unless every request is served with finite scores, each of the
           four step paths (fresh, suffix, packed miss, packed hit) ran, and
           every hit or packed request's P(yes) is within 2e-2 of a solo
           fresh forward of the same tokens on the same chip.
  workers  ``python -m repro.launch.serve --published-widths --workers 1``:
           the same path behind the worker-process boundary. It fails unless
           the CLI exits 0 with every request served.

``--chips 4`` runs only the replica phase: four thread-mode replicas in one
process, each with its parameters and KV on its own chip, routed by
``least_backlog``; every replica must serve and hold its arrays on its own
device, and the scores must match one replica on chip 0 within 2e-2.

The last line of standard output is the verdict, printed only on success:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU (or without the repository's ``src/`` beside this file) the
script exits non-zero and prints no verdict.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen1.5-0.5b"
YES, NO = 5, 9
PARITY_TOL = 2e-2                  # the repo's packed/hit-vs-solo bound
PATHS = ("fresh", "suffix", "packed_miss", "packed_hit")
BUDGET_S = 1150.0                  # whole script, compilation included
RESULT = "RESULT "                 # prefix of a phase's JSON result line


class SmokeFailure(Exception):
    pass


# ---- traffic ----------------------------------------------------------------

def make_waves(vocab: int, seed: int, profile_len: int, long_len: int,
               n_posts: int = 10, n_short: int = 16):
    """(user, tokens) waves, in order: the long unshared request; the first
    post over the profile (caches it); a second post (solo prefix hit); the
    short unshared requests (packed misses); the remaining posts (packed
    hits). Each wave is queued whole before the engine forms its next batch.

    A profile on a prefix bucket (1024 tokens) packs its hits with no prefix
    padding; at 1216 tokens each hit row pads to the 2048 bucket, and the
    cost model prices that padding above the step cost packing saves, so
    every hit runs solo."""
    import numpy as np
    rng = np.random.default_rng(seed)

    def toks(n):
        return rng.integers(0, vocab, int(n)).tolist()

    profile = toks(profile_len)
    posts = [("profile-user", profile + toks(rng.integers(90, 111)))
             for _ in range(n_posts)]
    shorts = [(f"short-{i}", toks(rng.integers(40, 201)))
              for i in range(n_short)]
    return [[("long-user", toks(long_len))], posts[:1], posts[1:2], shorts,
            posts[2:]]


# ---- in-process phases (run in a child) ---------------------------------------

def _device_report(require_tpu: bool):
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: {dev}", flush=True)
    if require_tpu and dev["platform"] != "tpu":
        raise SmokeFailure(f"no accelerator: JAX found {dev['platform']!r}")
    return dev


def _compile_clock():
    """Sum of XLA compile seconds from now on (persistent-cache hits count
    only their retrieval), and the number of compiles."""
    import jax
    acc = {"seconds": 0.0, "count": 0}

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            acc["seconds"] += duration
            acc["count"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return acc


def _serve_waves(pool, waves):
    """Submit each wave through an AsyncServer (least_backlog router) and
    drain it. Submitting under each engine's lock queues the whole wave
    before that engine forms its next batch."""
    from contextlib import ExitStack

    from repro.serving import AsyncServer, SpanTracer, get_router
    server = AsyncServer(pool, router=get_router("least_backlog"),
                         tracer=SpanTracer()).start()
    futures = []
    try:
        for wave in waves:
            with ExitStack() as locks:
                for eng in pool.engines.values():
                    locks.enter_context(eng.lock)
                futures += [(user, toks, server.submit(
                    user, toks, allowed_tokens=(YES, NO)))
                    for user, toks in wave]
            if not server.drain(timeout=600.0):
                raise SmokeFailure("drain timed out")
        quarantined = server.metrics.total("results_quarantined")
    finally:
        server.shutdown(drain=False)
    return [(user, toks, f.result()) for user, toks, f in futures], \
        quarantined


def _check_served(outcomes, quarantined):
    import math

    from repro.serving import Rejected
    bad = [(u, o) for u, _, o in outcomes if isinstance(o, Rejected)
           or o.get("corrupt")
           or not all(math.isfinite(p) for p in o["scores"].values())]
    if bad or quarantined:
        raise SmokeFailure(f"{len(bad)} request(s) not served cleanly, "
                           f"{quarantined} quarantined: {bad[:3]}")


def _solo_p_yes(eng, tokens_list):
    """P(yes) of a solo fresh forward per token list, on ``eng``'s device
    with ``eng``'s parameters, padded to the engine's suffix buckets."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.kv_policy import bucket
    from repro.models import transformer as tfm
    cfg = eng.cfg
    fwd = jax.jit(lambda p, t, li: tfm.prefill(
        p, cfg, {"tokens": t}, last_index=li)[0])
    out = []
    for toks in tokens_list:
        S = bucket(len(toks), eng.ecfg.suffix_buckets)
        padded = np.zeros((1, S), np.int32)
        padded[0, :len(toks)] = toks
        logits = np.asarray(fwd(eng.params, padded,
                                np.asarray([len(toks) - 1], np.int32))[0],
                            np.float64)[[YES, NO]]
        p = np.exp(logits - logits.max())
        out.append(float(p[0] / p.sum()))
    return out


def phase_serve(*, published_widths: bool = True, require_tpu: bool = True,
                seed: int = 0, profile_len: int = 1024, long_len: int = 4500,
                cache_tokens: int = 16384) -> dict:
    """Thread-mode served path on one device; raises SmokeFailure."""
    dev = _device_report(require_tpu)
    from repro.launch.serve import make_pool
    from repro.runtime.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = _compile_clock()
    t0 = time.perf_counter()
    pool = make_pool(ARCH, 1, published_widths=published_widths, seed=seed,
                     cache_tokens=cache_tokens)
    eng = pool.engines["inst0"]
    cfg = eng.cfg
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} hybrid_chunk={cfg.hybrid_chunk}",
          flush=True)
    if long_len <= cfg.hybrid_chunk:
        raise SmokeFailure("the long request must exceed hybrid_chunk")
    outcomes, quarantined = _serve_waves(
        pool, make_waves(cfg.vocab_size, seed, profile_len, long_len))
    serve_s = time.perf_counter() - t0
    _check_served(outcomes, quarantined)

    steps = {p: 0 for p in PATHS}
    via = {}                                   # req_id -> step path
    for rec in eng.batch_records:
        steps[rec.jit_path] += 1
        for rid in rec.req_ids:
            via[rid] = rec.jit_path
    missing = [p for p in PATHS if not steps[p]]
    if missing:
        raise SmokeFailure(f"step paths never ran: {missing} ({steps})")
    checked = [(toks, o) for _, toks, o in outcomes
               if o["n_cached"] > 0 or via.get(o["req_id"], "fresh")
               != "fresh"]
    ref = _solo_p_yes(eng, [toks for toks, _ in checked])
    dev_max = max(abs(o["scores"][YES] - r)
                  for (_, o), r in zip(checked, ref))
    stats = eng.stats()
    out = {"device": dev, "compile_s": clock["seconds"],
           "compiles": clock["count"], "serve_s": serve_s,
           "served": len(outcomes), "hit_tokens": eng.hit_tokens,
           "steps": steps, "checked": len(checked),
           "max_score_dev": dev_max,
           "packed_requests": stats["packed_requests"],
           "packed_hit_requests": stats["packed_hit_requests"]}
    print(f"compile seconds: {clock['seconds']:.1f} ({clock['count']} "
          f"compiles); served: {len(outcomes)}; hit tokens: "
          f"{eng.hit_tokens}; steps per path: {steps}; max score "
          f"deviation: {dev_max:.3g} over {len(checked)} hit/packed "
          f"requests", flush=True)
    if dev_max > PARITY_TOL:
        raise SmokeFailure(f"score deviation {dev_max:.3g} > {PARITY_TOL}")
    return out


def _arrays_on(tree, device) -> bool:
    import jax
    return all(leaf.devices() == {device}
               for leaf in jax.tree_util.tree_leaves(tree))


def phase_replicas(*, n: int = 4, published_widths: bool = True,
                   require_tpu: bool = True, seed: int = 0,
                   n_requests: int = 24, cache_tokens: int = 16384) -> dict:
    """``n`` one-device replicas behind least_backlog against one replica on
    device 0, same requests (one wave of short unshared requests, so each
    replica compiles few step shapes), same process; raises SmokeFailure."""
    dev = _device_report(require_tpu)
    if dev["count"] < n:
        raise SmokeFailure(f"{n} replicas need {n} devices, "
                           f"found {dev['count']}")
    from repro.launch.serve import make_pool
    from repro.runtime.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = _compile_clock()
    scores = {}
    served_by = {}
    for size in (n, 1):
        pool = make_pool(ARCH, size, published_widths=published_widths,
                         seed=seed, cache_tokens=cache_tokens)
        cfg = next(iter(pool.engines.values())).cfg
        wave = make_waves(cfg.vocab_size, seed, 64, 0,
                          n_short=n_requests)[3]
        outcomes, quarantined = _serve_waves(pool, [wave])
        _check_served(outcomes, quarantined)
        scores[size] = [o["scores"][YES] for _, _, o in outcomes]
        devices = set()
        for name, eng in pool.engines.items():
            payloads = [b.payload for b in eng.cache.blocks.values()
                        if b.payload is not None]
            if not (_arrays_on(eng.params, eng.device)
                    and _arrays_on(payloads, eng.device)):
                raise SmokeFailure(f"{name}: arrays off its device "
                                   f"{eng.device}")
            devices.add(eng.device)
            served_by[f"{size}x{name}"] = sum(
                r.n_requests for r in eng.batch_records)
        if len(devices) != size:
            raise SmokeFailure(f"{size} replicas share devices: {devices}")
        if size == n and not all(served_by[f"{n}x{k}"]
                                 for k in pool.engines):
            raise SmokeFailure(f"a replica served nothing: {served_by}")
        del pool
    dev_max = max(abs(a - b) for a, b in zip(scores[n], scores[1]))
    print(f"compile seconds: {clock['seconds']:.1f} ({clock['count']} "
          f"compiles); served per replica: {served_by}; max score "
          f"deviation {n} replicas vs 1: {dev_max:.3g}", flush=True)
    if dev_max > PARITY_TOL:
        raise SmokeFailure(f"score deviation {dev_max:.3g} > {PARITY_TOL}")
    return {"device": dev, "compile_s": clock["seconds"],
            "served_per_replica": served_by, "max_score_dev": dev_max}


def _run_phase(name: str, seed: int) -> int:
    try:
        fn = phase_replicas if name == "replicas" else phase_serve
        out = fn(seed=seed)
    except SmokeFailure as e:
        print(f"SMOKE FAILED ({name}): {e}", file=sys.stderr, flush=True)
        return 1
    print(RESULT + json.dumps(out), flush=True)
    return 0


# ---- the parent: no JAX here ----------------------------------------------------

def _run(cmd, timeout: float):
    """Run ``cmd`` in its own session; on timeout kill the whole group (a
    serve frontend's workers included). Returns (rc, stdout)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=max(timeout, 1.0))
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        print(out, end="", flush=True)
        return 124, out
    print(out, end="", flush=True)
    return proc.returncode, out


def _phase_result(name: str, seed: int, timeout: float) -> dict:
    rc, out = _run([sys.executable, str(Path(__file__).resolve()),
                    "--phase", name, "--seed", str(seed)], timeout)
    lines = [ln for ln in out.splitlines() if ln.startswith(RESULT)]
    if rc != 0 or not lines:
        raise SmokeFailure(f"phase {name} exited {rc}")
    return json.loads(lines[-1][len(RESULT):])


def _workers_phase(timeout: float) -> None:
    rc, out = _run([sys.executable, "-m", "repro.launch.serve",
                    "--published-widths", "--workers", "1",
                    "--max-requests", "12", "--qps", "8",
                    "--cache-tokens", "16384"], timeout)
    fields = dict(ln.split(": ", 1) for ln in out.splitlines()
                  if ": " in ln)
    served, requests = fields.get("served"), fields.get("requests")
    if rc != 0 or served is None or served != requests:
        raise SmokeFailure(f"serve --workers 1 exited {rc}, served "
                           f"{served} of {requests}")
    print(f"workers phase: served {served} of {requests}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=("serve", "replicas"),
                    help=argparse.SUPPRESS)       # a child's entry point
    args = ap.parse_args(argv)
    if args.phase:
        sys.path.insert(0, str(ROOT / "src"))
        return _run_phase(args.phase, args.seed)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"SMOKE FAILED: no repository source under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    t_end = time.monotonic() + BUDGET_S
    try:
        if args.chips == 4:
            res = _phase_result("replicas", args.seed, t_end - time.monotonic())
        else:
            res = _phase_result("serve", args.seed,
                                t_end - time.monotonic() - 300.0)
            _workers_phase(t_end - time.monotonic())
    except SmokeFailure as e:
        print(f"SMOKE FAILED: {e}", file=sys.stderr)
        return 1
    dev = res["device"]
    if dev["platform"] != "tpu" or dev["count"] < args.chips:
        print(f"SMOKE FAILED: device {dev}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
