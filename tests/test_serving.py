"""Async serving subsystem: metrics histograms, router determinism +
cache affinity, admission reject/shed, concurrent-submit soak (every request
resolves exactly once), engine cancel/shed/backlog probes, and a real-engine
end-to-end smoke through AsyncServer."""
import threading
import time

import numpy as np
import pytest

from repro.core.prefix_cache import token_chain
from repro.core.scheduler import Request
from repro.runtime.fault_tolerance import InstancePool
from repro.serving import (AdmissionController, AsyncServer, Histogram,
                           MetricsRegistry, Rejected, get_router)
from repro.serving.router import LeastBacklogRouter, UserHashRouter


# ---- metrics ----------------------------------------------------------------

def test_histogram_percentiles_uniform():
    h = Histogram(bounds=tuple(np.linspace(0.01, 1.0, 100)))
    rng = np.random.default_rng(0)
    xs = rng.uniform(0, 1, 20_000)
    for x in xs:
        h.observe(x)
    # fixed-bucket interpolation: within one bucket width of the truth
    assert abs(h.percentile(0.50) - 0.50) < 0.02
    assert abs(h.percentile(0.95) - 0.95) < 0.02
    assert abs(h.percentile(0.99) - 0.99) < 0.02
    assert h.count == 20_000
    assert abs(h.mean - 0.5) < 0.01


def test_histogram_small_sample_clamps_to_observed():
    h = Histogram(bounds=(0.1, 1.0, 10.0))
    h.observe(0.35)
    assert h.percentile(0.5) == pytest.approx(0.35)
    assert h.percentile(0.99) == pytest.approx(0.35)
    h2 = Histogram(bounds=(0.1, 1.0, 10.0))
    assert np.isnan(h2.percentile(0.5))


def test_histogram_merge_and_registry_aggregation():
    reg = MetricsRegistry(buckets=(0.1, 1.0, 10.0))
    reg.histogram("lat", "a").observe(0.05)
    reg.histogram("lat", "b").observe(5.0)
    merged = reg.merged_histogram("lat")
    assert merged.count == 2
    assert merged.min == pytest.approx(0.05)
    assert merged.max == pytest.approx(5.0)
    reg.counter("served", "a").inc(3)
    reg.counter("served", "b").inc(4)
    assert reg.total("served") == 7
    text = reg.render()
    assert "served{a} 3" in text and "lat{ALL}" in text


def test_counter_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("n")
    threads = [threading.Thread(target=lambda: [c.inc() for _ in range(1000)])
               for _ in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert c.value == 8000


# ---- fake engine (protocol double for router/server tests) ------------------

class FakeEngine:
    """Implements the engine surface AsyncServer/routers rely on; step()
    sleeps sec_per_token per input token."""

    class _ECfg:
        block_size = 16

    ecfg = _ECfg()

    def __init__(self, name, sec_per_token=5e-5, cached_chains=()):
        self.name = name
        self.lock = threading.RLock()
        self.queue = []
        self.results = {}
        self._last = []
        self.a = sec_per_token
        self.cached = {tuple(c) for c in cached_chains}
        self.steps = 0

    def submit(self, tokens, allowed_tokens=None, user_id=None, now=None,
               deadline=None, chain=None):
        r = Request(n_input=len(tokens), arrival=time.perf_counter(),
                    chain=chain or token_chain(tokens,
                                               self.ecfg.block_size),
                    tokens=list(tokens), user_id=user_id, deadline=deadline)
        with self.lock:
            self.queue.append(r)
        return r.req_id

    def cancel(self, rid):
        with self.lock:
            for i, r in enumerate(self.queue):
                if r.req_id == rid:
                    return self.queue.pop(i)
        return None

    def shed_expired(self, now=None):
        now = time.perf_counter() if now is None else now
        shed = []
        with self.lock:
            keep = []
            for r in self.queue:
                doomed = (r.deadline is not None
                          and now + self.a * r.n_input > r.deadline)
                (shed if doomed else keep).append(r)
            self.queue[:] = keep
        return shed

    def pending_jct(self, now=None):
        with self.lock:
            return sum(self.a * r.n_input for r in self.queue)

    def predict_jct(self, n, chain=()):
        return self.a * (n - self.cached_prefix_len(chain))

    def cached_prefix_len(self, chain):
        return (self.ecfg.block_size * len(chain)
                if tuple(chain) in self.cached else 0)

    def step(self):
        with self.lock:
            if not self.queue:
                return None
            r = self.queue.pop(0)
        time.sleep(self.a * r.n_input)
        r.finish_time = time.perf_counter()
        with self.lock:
            self.results[r.req_id] = {
                "req_id": r.req_id, "latency": r.latency, "n_cached": 0,
                "n_input": r.n_input, "token": 0}
            self._last = [r.req_id]
            self.steps += 1
        return r.req_id

    @property
    def last_step_ids(self):
        return list(self._last)

    def stats(self):
        return {"steps": self.steps}


def _fake_pool(n=2, **kw):
    pool = InstancePool(lambda name: FakeEngine(name, **kw))
    pool.scale_to([f"i{k}" for k in range(n)])
    return pool


# ---- router -----------------------------------------------------------------

def test_user_hash_router_deterministic_and_matches_rendezvous():
    from repro.runtime.fault_tolerance import rendezvous_hash
    pool = _fake_pool(3)
    r = UserHashRouter()
    engines = {n: pool.engines[n] for n in pool.live_names()}
    for u in range(20):
        picks = {r.route(user_id=f"u{u}", n_input=10, chain=(),
                         instances=engines) for _ in range(5)}
        assert len(picks) == 1
        assert picks.pop() == rendezvous_hash(f"u{u}", sorted(engines))


def test_least_backlog_routes_to_min_predicted_backlog():
    pool = _fake_pool(2)
    engines = {n: pool.engines[n] for n in pool.live_names()}
    # load i0 with 3 queued requests -> backlog 3*100*a
    for _ in range(3):
        engines["i0"].submit(list(range(100)))
    r = LeastBacklogRouter()
    picks = [r.route(user_id="u", n_input=50, chain=(), instances=engines)
             for _ in range(5)]
    assert picks == ["i1"] * 5                      # deterministic, min backlog


def test_least_backlog_cache_affinity_tie_break():
    tokens = list(range(64))
    chain = token_chain(tokens, 16)
    pool = InstancePool(lambda name: FakeEngine(
        name, cached_chains=[chain] if name == "i1" else []))
    pool.scale_to(["i0", "i1", "i2"])
    engines = {n: pool.engines[n] for n in pool.live_names()}
    r = LeastBacklogRouter()
    # all backlogs equal (empty): the instance holding the prefix wins,
    # repeatably
    assert [r.route(user_id="u9", n_input=64, chain=chain,
                    instances=engines) for _ in range(5)] == ["i1"] * 5
    # unknown chain: falls back to rendezvous (deterministic across calls)
    picks = {r.route(user_id="u9", n_input=64, chain=(), instances=engines)
             for _ in range(5)}
    assert len(picks) == 1


def test_get_router_factory():
    assert isinstance(get_router("user_hash"), UserHashRouter)
    assert isinstance(get_router("least_backlog"), LeastBacklogRouter)
    with pytest.raises(KeyError):
        get_router("nope")


# ---- admission --------------------------------------------------------------

def test_admission_mil_reject():
    ctrl = AdmissionController(max_input_tokens=100)
    rej = ctrl.check(101, None, 0.0, 0.0, 0.0, user_id="u")
    assert rej is not None and rej.reason == "infeasible"
    assert ctrl.check(100, None, 0.0, 0.0, 0.0) is None
    assert ctrl.rejected_infeasible == 1


def test_admission_mil_from_memory_model():
    from repro.configs import get_config
    from repro.core.kv_policy import MemoryModel
    from repro.runtime.hw import TPU_V5E
    mm = MemoryModel(get_config("llama3.1-8b"), TPU_V5E)
    ctrl = AdmissionController(memory_model=mm)
    assert ctrl.max_input_tokens == mm.max_input_length("hybrid", 2048)
    assert ctrl.check(ctrl.max_input_tokens + 1, None, 0, 0, 0).reason \
        == "infeasible"


def test_admission_deadline_reject_and_slack():
    ctrl = AdmissionController()
    now = 100.0
    # predicted wait 2 + jct 1 = finish at 103 > deadline 102 -> reject
    rej = ctrl.check(10, 102.0, now, 2.0, 1.0)
    assert rej is not None and rej.reason == "deadline"
    assert rej.predicted_wait == 2.0 and rej.predicted_jct == 1.0
    # feasible deadline admits
    assert ctrl.check(10, 104.0, now, 2.0, 1.0) is None
    # slack 2.0 doubles the predicted time -> 104 no longer feasible
    tight = AdmissionController(deadline_slack=2.0)
    assert tight.check(10, 104.0, now, 2.0, 1.0) is not None


# ---- server (fake engines) --------------------------------------------------

def test_server_serves_and_rejects_typed():
    pool = _fake_pool(2)
    srv = AsyncServer(pool, router=get_router("least_backlog"),
                      admission=AdmissionController(max_input_tokens=500))
    srv.start()
    try:
        ok = [srv.submit(f"u{i}", list(range(20 + i))) for i in range(10)]
        bad = srv.submit("big", list(range(501)))
        late = srv.submit("late", list(range(50)),
                          deadline=time.perf_counter() - 1.0)
        assert srv.drain(timeout=10)
        for f in ok:
            res = f.result(timeout=1)
            assert not isinstance(res, Rejected) and "latency" in res
        assert bad.result(timeout=1).reason == "infeasible"
        assert late.result(timeout=1).reason == "deadline"
        assert srv.metrics.total("requests_served") == 10
        assert srv.metrics.total("requests_rejected") == 2
    finally:
        srv.shutdown()


def test_server_sheds_queued_requests_whose_deadline_becomes_unreachable():
    # slow engine: 10ms/token, one instance -> queue builds
    pool = _fake_pool(1, sec_per_token=1e-2)
    srv = AsyncServer(pool, router=get_router("user_hash"),
                      admission=AdmissionController())
    srv.start()
    try:
        now = time.perf_counter()
        # each takes 1s; deadline 1.5s from now: the first is feasible at
        # admission (wait 0), the rest become doomed once the queue builds
        futs = [srv.submit("u", list(range(100)), deadline=now + 1.5)
                for _ in range(4)]
        assert srv.drain(timeout=15)
        outcomes = [f.result(timeout=1) for f in futs]
        served = [o for o in outcomes if not isinstance(o, Rejected)]
        rejected = [o for o in outcomes if isinstance(o, Rejected)]
        assert served and rejected
        assert {o.reason for o in rejected} <= {"shed", "deadline"}
    finally:
        srv.shutdown()


def test_server_cancel_queued_request():
    pool = _fake_pool(1, sec_per_token=1e-2)
    srv = AsyncServer(pool, router=get_router("user_hash"))
    srv.start()
    try:
        futs = [srv.submit("u", list(range(100))) for _ in range(3)]
        with pool.engines["i0"].lock:
            queued = [r.req_id for r in pool.engines["i0"].queue]
        assert queued and srv.cancel(queued[-1])
        assert srv.drain(timeout=15)
        outcomes = [f.result(timeout=1) for f in futs]
        cancelled = [o for o in outcomes if isinstance(o, Rejected)]
        assert len(cancelled) == 1 and cancelled[0].reason == "cancelled"
    finally:
        srv.shutdown()


def test_server_mark_failed_requeues_to_peers():
    pool = _fake_pool(3, sec_per_token=2e-3)
    srv = AsyncServer(pool, router=get_router("user_hash"))
    srv.start()
    try:
        futs = [srv.submit(f"u{i}", list(range(60))) for i in range(24)]
        victim = pool.live_names()[0]
        srv.mark_failed(victim)
        assert srv.drain(timeout=20)
        for f in futs:
            res = f.result(timeout=1)
            assert not isinstance(res, Rejected)
    finally:
        srv.shutdown()


def test_mark_failed_with_no_peers_rejects_stranded_futures():
    """Failing the LAST instance must resolve its queued futures as
    Rejected('no_instances') instead of hanging drain() forever."""
    pool = _fake_pool(1, sec_per_token=1e-2)
    srv = AsyncServer(pool, router=get_router("user_hash"))
    srv.start()
    try:
        futs = [srv.submit("u", list(range(100))) for _ in range(4)]
        srv.mark_failed("i0")
        assert srv.drain(timeout=10)
        outcomes = [f.result(timeout=5) for f in futs]
        rejected = [o for o in outcomes if isinstance(o, Rejected)]
        assert rejected and all(o.reason == "no_instances" for o in rejected)
    finally:
        srv.shutdown()


def test_server_worker_crash_fails_instance_and_requeues():
    """An engine raising inside step() must not strand futures: the worker
    marks the instance failed; queued work requeues to the healthy peer."""
    pool = _fake_pool(2, sec_per_token=5e-3)

    class Boom(Exception):
        pass

    crashing = pool.engines["i0"]
    orig_step = crashing.step

    def bad_step():
        if crashing.queue:
            raise Boom("chip fell over")
        return orig_step()

    crashing.step = bad_step
    srv = AsyncServer(pool, router=get_router("user_hash"))
    srv.start()
    try:
        futs = [srv.submit(f"u{i}", list(range(40))) for i in range(12)]
        assert srv.drain(timeout=20)
        outcomes = [f.result(timeout=1) for f in futs]
        assert all(not isinstance(o, Rejected) for o in outcomes)
        assert "i0" not in pool.live_names()
        assert srv.metrics.total("engine_errors") == 1
    finally:
        srv.shutdown()


def test_server_scale_down_rehomes_queued_requests():
    """Shrinking the pool must re-home queued work to survivors — every
    future still resolves with a served result, none re-routed back onto
    the instance being removed."""
    pool = _fake_pool(2, sec_per_token=1e-2)
    srv = AsyncServer(pool, router=get_router("user_hash"))
    srv.start()
    try:
        futs = [srv.submit(f"u{i}", list(range(50))) for i in range(8)]
        srv.scale_to(["i0"])
        assert "i1" not in pool.engines
        assert srv.drain(timeout=20)
        for f in futs:
            assert not isinstance(f.result(timeout=1), Rejected)
    finally:
        srv.shutdown()


def test_server_scale_to_empty_rejects_stranded_futures():
    """Removing the LAST instance must resolve its queued futures as
    Rejected('no_instances') instead of hanging drain() forever."""
    pool = _fake_pool(1, sec_per_token=1e-2)
    srv = AsyncServer(pool, router=get_router("user_hash"))
    srv.start()
    try:
        futs = [srv.submit("u", list(range(100))) for _ in range(4)]
        srv.scale_to([])
        assert srv.drain(timeout=10)
        outcomes = [f.result(timeout=5) for f in futs]
        rejected = [o for o in outcomes if isinstance(o, Rejected)]
        assert rejected and all(o.reason == "no_instances" for o in rejected)
    finally:
        srv.shutdown()


def test_submit_chain_cut_at_routed_engines_block_size():
    """Heterogeneous pool: the enqueued request's prefix chain must be cut
    at the CHOSEN engine's block size, not an arbitrary peer's."""
    from repro.runtime.fault_tolerance import rendezvous_hash
    pool = _fake_pool(2)
    pool.engines["i1"].ecfg = _BS8()      # i0 keeps block_size 16
    uid = next(u for u in (f"u{i}" for i in range(50))
               if rendezvous_hash(u, ["i0", "i1"]) == "i1")
    srv = AsyncServer(pool, router=get_router("user_hash"))
    srv._accepting = True                 # accept without starting workers
    tokens = list(range(32))
    srv.submit(uid, tokens)
    r = pool.engines["i1"].queue[0]
    assert tuple(r.chain) == token_chain(tokens, 8)


class _BS8:
    block_size = 8


def test_least_backlog_probes_with_per_blocksize_chains():
    """Heterogeneous pool: each engine must be probed with the chain cut at
    ITS block size, or the warm instance's cache match never fires."""
    tokens = list(range(64))
    pool = _fake_pool(2)
    warm = pool.engines["i1"]
    warm.ecfg = _BS8()                    # i0 keeps block_size 16
    chain8 = token_chain(tokens, 8)
    warm.cached.add(tuple(chain8))
    engines = {n: pool.engines[n] for n in pool.live_names()}
    chains = {16: token_chain(tokens, 16), 8: chain8}
    r = LeastBacklogRouter()
    assert r.route(user_id="u", n_input=64, chain=chains[16],
                   instances=engines, chains=chains) == "i1"
    # probed with only the bs-16 chain, i1's cache would never match
    assert warm.cached_prefix_len(chains[16]) == 0
    assert warm.cached_prefix_len(chain8) == 64


def test_drain_rechains_requests_across_block_sizes():
    """A request re-homed onto a peer with a different block size must get
    its chain re-cut at the peer's block size (a stale-granularity chain
    would corrupt the peer's prefix cache)."""
    pool = _fake_pool(2, sec_per_token=1e-2)
    pool.engines["i1"].ecfg = _BS8()
    tokens = list(range(32))
    pool.engines["i0"].submit(tokens, chain=token_chain(tokens, 16))
    pool.mark_failed("i0")
    r = pool.engines["i1"].queue[0]
    assert tuple(r.chain) == token_chain(tokens, 8)


def test_server_shutdown_drain_timeout_rejects_queued():
    """shutdown(drain=True, timeout=...) whose drain times out must still
    resolve every queued future (Rejected('shutdown')), not strand them."""
    pool = _fake_pool(1, sec_per_token=1e-2)
    srv = AsyncServer(pool, router=get_router("user_hash"))
    srv.start()
    futs = [srv.submit("u", list(range(100))) for _ in range(6)]
    srv.shutdown(drain=True, timeout=0.05)
    outcomes = [f.result(timeout=5) for f in futs]
    assert all(f.done() for f in futs)
    assert any(isinstance(o, Rejected) and o.reason == "shutdown"
               for o in outcomes)


def test_server_shutdown_without_drain_rejects_queued():
    pool = _fake_pool(1, sec_per_token=1e-2)
    srv = AsyncServer(pool, router=get_router("user_hash"))
    srv.start()
    futs = [srv.submit("u", list(range(100))) for _ in range(5)]
    srv.shutdown(drain=False)
    outcomes = [f.result(timeout=5) for f in futs]
    assert any(isinstance(o, Rejected) and o.reason == "shutdown"
               for o in outcomes)
    # post-shutdown submits reject immediately
    assert srv.submit("u", [1, 2]).result(timeout=1).reason == "shutdown"


def test_concurrent_submit_soak_every_request_resolves_exactly_once():
    """4 submitter threads x 60 requests against 3 instances; every future
    resolves exactly once with a result or a typed rejection."""
    pool = _fake_pool(3, sec_per_token=2e-5)
    srv = AsyncServer(pool, router=get_router("least_backlog"),
                      admission=AdmissionController(max_input_tokens=400))
    srv.start()
    resolutions = []
    res_lock = threading.Lock()
    futs = []
    futs_lock = threading.Lock()

    def on_done(f):
        with res_lock:
            resolutions.append(f.result(timeout=0))

    def submitter(tid):
        rng = np.random.default_rng(tid)
        for i in range(60):
            n = int(rng.integers(10, 300))
            if i % 17 == 0:
                n = 450                     # infeasible -> typed reject
            deadline = (time.perf_counter() - 1.0) if i % 23 == 0 else None
            f = srv.submit(f"u{tid}_{i % 7}", list(range(n)),
                           deadline=deadline)
            f.add_done_callback(on_done)
            with futs_lock:
                futs.append(f)

    threads = [threading.Thread(target=submitter, args=(t,))
               for t in range(4)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    try:
        assert srv.drain(timeout=30), "soak drain timed out"
        assert len(futs) == 240
        for f in futs:
            assert f.done()
        # exactly once: every future fired its done-callback exactly once
        assert len(resolutions) == 240
        served = [r for r in resolutions if not isinstance(r, Rejected)]
        rejected = [r for r in resolutions if isinstance(r, Rejected)]
        assert len(served) + len(rejected) == 240
        assert len(rejected) >= 4 * (60 // 17)      # at least the infeasibles
        assert srv.metrics.total("requests_served") == len(served)
        assert srv.metrics.total("requests_rejected") == len(rejected)
    finally:
        srv.shutdown()


# ---- engine-level serving hooks (real engine) -------------------------------

@pytest.fixture(scope="module")
def setup():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, reduce_config
    from repro.models.model import build
    from repro.runtime.sharding import materialize
    cfg = reduce_config(get_config("qwen1.5-0.5b"), hybrid_chunk=0)
    api = build(cfg)
    params = materialize(jax.random.PRNGKey(0), api.defs(), jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    from repro.core.engine import EngineConfig, PrefillOnlyEngine
    return PrefillOnlyEngine(cfg, params, EngineConfig(**kw))


def test_engine_config_not_shared_between_engines(setup):
    cfg, params = setup
    from repro.core.engine import PrefillOnlyEngine
    a = PrefillOnlyEngine(cfg, params)
    b = PrefillOnlyEngine(cfg, params)
    assert a.ecfg is not b.ecfg
    a.ecfg.pack_token_budget = 1
    assert b.ecfg.pack_token_budget != 1


def test_engine_cancel_and_shed(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    eng.jct_model.a, eng.jct_model.b = 1e-3, 0.0
    rid1 = eng.submit(list(range(40)))
    rid2 = eng.submit(list(range(40)), deadline=time.perf_counter() - 1.0)
    rid3 = eng.submit(list(range(40)),
                      deadline=time.perf_counter() + 1000.0)
    assert eng.cancel(rid1) is not None
    assert eng.cancel(rid1) is None                  # already gone
    shed = eng.shed_expired()
    assert [r.req_id for r in shed] == [rid2]
    assert [r.req_id for r in eng.queue] == [rid3]


def test_engine_pending_and_predict_jct_track_cache(setup):
    cfg, params = setup
    eng = _engine(cfg, params, cache_capacity_tokens=4096)
    eng.jct_model.a, eng.jct_model.b = 1.0, 0.0
    toks = list(np.random.default_rng(0).integers(0, cfg.vocab_size, 80))
    chain = token_chain(toks, eng.ecfg.block_size)
    assert eng.predict_jct(80, chain) == pytest.approx(80.0)
    eng.submit(toks)
    assert eng.pending_jct() == pytest.approx(80.0)
    eng.step()                                       # now the prefix is cached
    assert eng.cached_prefix_len(chain) == 80
    # hit-aware probe: predicts against the USABLE prefix a forward would
    # reuse (reuse granularity 4 blocks = 64 tokens, never the whole
    # request), not the raw 80-token match — the truthful backlog signal
    assert eng.predict_jct(80, chain) == pytest.approx(16.0)
    assert eng.pending_jct() == pytest.approx(0.0)   # queue empty


def test_engine_autotune_packing_formula(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    # a*S + b <= inflation * (a*ref + b) with inflation=2, ref=512:
    # S <= 1024 + b/a = 1024 + 1000 -> largest bucket <= 2024 is 1024
    eng.jct_model.a, eng.jct_model.b = 1e-4, 1e-1
    budget, n_max = eng.autotune_packing(ref_len=512)
    assert budget == 1024
    assert n_max == 1024 // 64
    assert eng.ecfg.pack_token_budget == 1024
    # overhead-free host: budget collapses to the inflation bound
    eng.jct_model.b = 0.0
    budget, _ = eng.autotune_packing(ref_len=512)
    assert budget == 1024                            # S <= 2*512


def test_async_server_end_to_end_real_engines(setup):
    cfg, params = setup
    pool = InstancePool(lambda name: _engine(cfg, params,
                                             cache_capacity_tokens=2048))
    pool.scale_to(["a", "b"])
    srv = AsyncServer(pool, router=get_router("least_backlog"),
                      admission=AdmissionController())
    srv.start()
    try:
        rng = np.random.default_rng(1)
        futs = [srv.submit(f"u{i % 3}",
                           rng.integers(0, cfg.vocab_size, 48).tolist(),
                           allowed_tokens=(5, 9)) for i in range(6)]
        assert srv.drain(timeout=120)
        for f in futs:
            res = f.result(timeout=1)
            assert not isinstance(res, Rejected)
            assert set(res["scores"]) == {5, 9}
            assert abs(sum(res["scores"].values()) - 1.0) < 1e-6
        assert srv.metrics.total("requests_served") == 6
        assert srv.metrics.merged_histogram("latency_seconds").count == 6
    finally:
        srv.shutdown()


# ---- admission feedback loop ------------------------------------------------

def test_admission_slack_tightens_on_shed_rate():
    reg = MetricsRegistry()
    ctrl = AdmissionController(deadline_slack=1.0, adapt_window=10,
                               shed_target=0.1, adapt_rate=2.0,
                               max_slack=4.0, metrics=reg)
    # 8 served + 2 shed = 20% shed rate over the window -> tighten
    for _ in range(8):
        ctrl.record_outcome(shed=False)
    for _ in range(2):
        ctrl.record_outcome(shed=True)
    assert ctrl.deadline_slack == pytest.approx(2.0)
    assert ctrl.slack_adjustments == 1
    assert reg.counter("admission_slack_tightened").value == 1
    assert reg.gauge("admission_deadline_slack").value == pytest.approx(2.0)
    # window cleared: the same burst is not double-counted
    assert len(ctrl._outcomes) == 0
    # a clean window relaxes back toward the configured floor (never below)
    for _ in range(10):
        ctrl.record_outcome(shed=False)
    assert ctrl.deadline_slack == pytest.approx(1.0)
    for _ in range(10):
        ctrl.record_outcome(shed=False)
    assert ctrl.deadline_slack == pytest.approx(1.0)   # floor holds
    assert reg.counter("admission_slack_relaxed").value == 1


def test_admission_slack_respects_max_and_disabled():
    ctrl = AdmissionController(deadline_slack=3.0, adapt_window=4,
                               shed_target=0.0, adapt_rate=10.0,
                               max_slack=4.0)
    for _ in range(8):
        ctrl.record_outcome(shed=True)
    assert ctrl.deadline_slack == pytest.approx(4.0)   # clamped at max
    off = AdmissionController(deadline_slack=1.0, adapt=False,
                              adapt_window=2)
    for _ in range(10):
        off.record_outcome(shed=True)
    assert off.deadline_slack == 1.0                    # feedback disabled


def test_server_feeds_shed_outcomes_back_to_admission(setup):
    """End-to-end: a served with-deadline request reports shed=False; a
    queued request shed by the worker reports shed=True, and enough sheds
    in the window tighten ``deadline_slack`` (counter + gauge recorded)."""
    cfg, params = setup
    pool = InstancePool(lambda name: _engine(cfg, params))
    pool.scale_to(["a"])
    ctrl = AdmissionController(adapt_window=2, shed_target=0.0,
                               adapt_rate=1.5)
    srv = AsyncServer(pool, admission=ctrl)
    assert ctrl.metrics is srv.metrics       # registry auto-attached
    eng = pool.engines["a"]
    srv.start()
    try:
        rng = np.random.default_rng(2)
        f = srv.submit("u", rng.integers(0, cfg.vocab_size, 32).tolist(),
                       allowed_tokens=(5, 9),
                       deadline=time.perf_counter() + 300.0)
        assert srv.drain(timeout=120)
        assert not isinstance(f.result(timeout=1), Rejected)
        assert list(ctrl._outcomes) == [False]
        # already-expired requests enqueued behind the server's back (no
        # admission gate) are shed in-queue and recorded as shed=True:
        # window [served, shed] -> 50% shed rate -> tighten
        for _ in range(2):
            eng.submit(rng.integers(0, cfg.vocab_size, 16).tolist(),
                       deadline=time.perf_counter() - 1.0)
        stop = time.time() + 30
        while ctrl.slack_adjustments == 0 and time.time() < stop:
            time.sleep(0.01)
        assert ctrl.slack_adjustments >= 1
        assert ctrl.deadline_slack > 1.0
        assert srv.metrics.counter("admission_slack_tightened").value >= 1
        assert srv.metrics.gauge("admission_deadline_slack").value > 1.0
    finally:
        srv.shutdown(drain=False)


# ---- Prometheus exposition --------------------------------------------------

def test_render_prometheus_format():
    reg = MetricsRegistry(buckets=(0.1, 1.0))
    reg.counter("requests_served", "a").inc(3)
    reg.counter("requests_served", "b").inc(2)
    reg.gauge("queue_depth", "a").set(5)
    reg.counter("requests_rejected").inc()          # global, unlabelled
    h = reg.histogram("latency_seconds", "a")
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    txt = reg.render_prometheus()
    assert "# TYPE prefillonly_requests_served counter" in txt
    assert 'prefillonly_requests_served{instance="a"} 3' in txt
    assert 'prefillonly_requests_served{instance="b"} 2' in txt
    assert "prefillonly_requests_rejected 1" in txt  # no instance label
    assert "# TYPE prefillonly_latency_seconds histogram" in txt
    # cumulative buckets: 1 below 0.1, 2 below 1.0, all 3 at +Inf
    assert 'prefillonly_latency_seconds_bucket{instance="a",le="0.1"} 1' in txt
    assert 'prefillonly_latency_seconds_bucket{instance="a",le="1"} 2' in txt
    assert ('prefillonly_latency_seconds_bucket{instance="a",le="+Inf"} 3'
            in txt)
    assert 'prefillonly_latency_seconds_count{instance="a"} 3' in txt
    assert txt.endswith("\n")


def test_metrics_http_endpoint():
    import urllib.request
    from repro.launch.serve import start_metrics_server
    reg = MetricsRegistry()
    reg.counter("requests_served", "a").inc(7)
    server = start_metrics_server(reg, port=0)
    try:
        host, port = server.server_address
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=5) as resp:
            assert resp.status == 200
            assert "text/plain" in resp.headers["Content-Type"]
            body = resp.read().decode()
        assert 'prefillonly_requests_served{instance="a"} 7' in body
        # non-metrics paths 404
        import urllib.error
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://{host}:{port}/nope", timeout=5)
    finally:
        server.shutdown()
        server.server_close()   # release the socket, not just the loop
