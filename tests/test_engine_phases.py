"""Engine-step phases: a step with work times each host phase into its
``BatchRecord.phases`` and opens one profiler span per phase
(``engine.<phase>``) inside ``engine.step``; an idle poll opens none; the
phases cross the process-mode worker's RPC."""
import json
import socket
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.core.engine import EngineConfig, PrefillOnlyEngine
from repro.models.model import build
from repro.runtime.sharding import materialize
from repro.serving import SpanTracer
from repro.serving.rpc import recv_msg, send_msg

LEAVES = {"form_batch", "cache_match", "kv_gather", "dispatch",
          "device_wait", "kv_insert", "score", "record"}
EVERY_STEP = LEAVES - {"kv_gather"}
AFTER_WALL = {"score", "record"}     # run after the step's wall closes


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_config(get_config("qwen1.5-0.5b"), hybrid_chunk=0)
    api = build(cfg)
    params = materialize(jax.random.PRNGKey(0), api.defs(), jnp.float32)
    rng = np.random.default_rng(21)
    profile = rng.integers(0, cfg.vocab_size, 80).tolist()
    sufs = [rng.integers(0, cfg.vocab_size, 20).tolist() for _ in range(5)]
    return cfg, params, profile, sufs


def _engine(setup):
    cfg, params, _, _ = setup
    return PrefillOnlyEngine(cfg, params, EngineConfig(pack_token_budget=512))


def _three_steps(eng, profile, sufs):
    """A fresh miss, a solo cache hit, then three hits in one packed step;
    each step's record and the seconds ``step()`` took."""
    out = []
    for reqs in ([sufs[0]], [sufs[1]], sufs[2:5]):
        for s in reqs:
            eng.submit(profile + s, allowed_tokens=(5, 9))
        t0 = time.perf_counter()
        assert eng.step() is not None
        out.append((eng.batch_records[-1], time.perf_counter() - t0))
    return out


def test_steps_time_their_phases(setup):
    _, _, profile, sufs = setup
    eng = _engine(setup)
    tracer = SpanTracer()
    eng.bind_telemetry(tracer=tracer)
    steps = _three_steps(eng, profile, sufs)
    assert [r.jit_path for r, _ in steps] == ["fresh", "suffix", "packed_hit"]
    for rec, took in steps:
        assert EVERY_STEP <= set(rec.phases) <= LEAVES, rec.phases
        assert all(v >= 0 for v in rec.phases.values())
        assert sum(v for k, v in rec.phases.items()
                   if k not in AFTER_WALL) <= rec.wall
        assert sum(rec.phases.values()) <= took
    assert "kv_gather" not in steps[0][0].phases
    assert all("kv_gather" in r.phases for r, _ in steps[1:])
    # the exporters carry them: JSONL batch rows and the Perfetto step args
    rows = [json.loads(ln) for ln in tracer.dump_jsonl().splitlines()]
    batches = [r for r in rows if r["type"] == "batch"]
    assert [b["phases"] for b in batches] == [r.phases for r, _ in steps]
    args = [e["args"] for e in tracer.chrome_trace()["traceEvents"]
            if e["name"].startswith("step ")]
    assert [a["phases"] for a in args] == [r.phases for r, _ in steps]


def test_phase_spans_nest_in_the_step_on_the_profiler_clock(setup, tmp_path):
    _, _, profile, sufs = setup
    eng = _engine(setup)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert eng.step() is None            # an idle poll: no span
        _three_steps(eng, profile, sufs)
    finally:
        jax.profiler.stop_trace()
    pd = jax.profiler.ProfileData.from_file(
        str(sorted(tmp_path.glob("**/*.xplane.pb"))[-1]))
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events if e.name.startswith("engine.")]
             for plane in pd.planes for line in plane.lines]
    lines = [ln for ln in lines if ln]
    assert len(lines) == 1                   # all on the engine's thread
    spans = sorted(lines[0], key=lambda e: e[1])
    steps = [s for s in spans if s[0] == "engine.step"]
    leaves = [s for s in spans if s[0] != "engine.step"]
    assert len(steps) == 3
    assert {n[len("engine."):] for n, _, _ in leaves} <= LEAVES
    for (_, _, end), (_, start, _) in zip(leaves, leaves[1:]):
        assert end <= start                  # leaves never overlap
    for _, a, b in leaves:
        assert any(s <= a and b <= e for _, s, e in steps)
    per_step = [sum(1 for _, a, _ in leaves if s <= a <= e)
                for _, s, e in steps]
    assert per_step == [7, 8, 8]             # one span per phase


def test_phases_cross_the_worker_rpc(setup):
    """The worker's step reply carries the record's phases through the
    wire codec, and the frontend's replay keeps them."""
    from repro.serving.supervisor import RemoteEngine
    from repro.serving.worker import EngineWorker
    _, _, profile, sufs = setup
    eng = _engine(setup)
    worker = EngineWorker("w0", eng)
    a, b = socket.socketpair()
    try:
        eng.submit(profile + sufs[0], allowed_tokens=(5, 9))
        send_msg(a, worker._op_step({}))
        out = recv_msg(b)
    finally:
        a.close()
        b.close()
        worker.srv.close()
    want = eng.batch_records[-1].phases
    assert EVERY_STEP <= set(want)
    front = RemoteEngine("w0", client=None)
    tracer = SpanTracer()
    front.bind_telemetry(tracer=tracer)
    front._replay_telemetry(out, off=0.0)
    (rec,) = tracer.drain_batches()
    assert rec.instance == "w0" and rec.phases == pytest.approx(want)
