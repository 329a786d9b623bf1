"""Hierarchical KV memory, end to end: layer-wise discard arithmetic
(KVLifecycle / MemoryModel kv_keep pricing) and the DRAM offload tier
driven through the REAL engine — demote on eviction, restore on re-match,
score parity against pure recompute, break-even honored on a slow link."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduce_config
from repro.core.engine import EngineConfig, PrefillOnlyEngine
from repro.core.kv_policy import KVLifecycle, MemoryModel
from repro.core.offload import OffloadPolicy, TieredPrefixCache
from repro.models.model import build
from repro.runtime.hw import TPU_V5E
from repro.runtime.sharding import materialize

# 4-block device cache + solo packing + fine reuse granularity: two
# 40-token requests fill it, so a handful of distinct submissions force
# evictions into the host tier. offload_host_bw is pinned huge because
# worth_restoring prices the TARGET chip's recompute rate, which this
# CPU box can't approach (see EngineConfig.offload_host_bw).
TIER = dict(cache_capacity_tokens=64, offload=True, offload_host_bw=1e18,
            prefix_bucket_blocks=1, max_pack_requests=1)


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_config(get_config("qwen1.5-0.5b"), hybrid_chunk=0)
    api = build(cfg)
    params = materialize(jax.random.PRNGKey(0), api.defs(), jnp.float32)
    return cfg, params


def _flood(eng, cfg, seed, n=6, length=40):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        eng.submit(rng.integers(0, cfg.vocab_size, length).tolist(),
                   allowed_tokens=(5, 9))
    eng.run_until_drained()


def test_demote_restore_round_trip_scores(setup):
    cfg, params = setup
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, 40).tolist()

    eng = PrefillOnlyEngine(cfg, params, EngineConfig(**TIER))
    eng.submit(toks, allowed_tokens=(5, 9))
    eng.run_until_drained()
    _flood(eng, cfg, seed=1)                 # evict toks' kept KV host-side
    host = eng.cache.host
    assert host.offloads > 0, "device eviction never reached the host tier"
    # demoted payloads live as HOST numpy, not device arrays
    assert all(isinstance(arr, np.ndarray)
               for p in host._store.values() for arr in p)
    assert eng.cache.probe_blocks(_chain(eng, toks)) > 0

    r0 = eng.cache.restored_blocks
    i = eng.submit(toks, allowed_tokens=(5, 9))
    eng.run_until_drained()
    assert eng.cache.restored_blocks > r0, "re-match did not restore"
    got = eng.results[i]["scores"]

    cold = PrefillOnlyEngine(cfg, params,
                             EngineConfig(cache_capacity_tokens=0))
    j = cold.submit(toks, allowed_tokens=(5, 9))
    cold.run_until_drained()
    ref = cold.results[j]["scores"]
    for t in ref:                            # ISSUE acceptance: < 2e-2
        assert abs(ref[t] - got[t]) < 2e-2


def _chain(eng, toks):
    from repro.core.prefix_cache import token_chain
    return token_chain(toks, eng.ecfg.block_size)


def test_probe_is_side_effect_free_across_tiers(setup):
    cfg, params = setup
    rng = np.random.default_rng(2)
    toks = rng.integers(0, cfg.vocab_size, 40).tolist()
    eng = PrefillOnlyEngine(cfg, params, EngineConfig(**TIER))
    eng.submit(toks, allowed_tokens=(5, 9))
    eng.run_until_drained()
    _flood(eng, cfg, seed=3)
    chain = _chain(eng, toks)
    before = (eng.cache.host.restores, eng.cache.restored_blocks)
    n = eng.cache.probe_blocks(chain)        # scheduling/routing probe
    assert n > 0, "host-resident prefix invisible to probes"
    assert (eng.cache.host.restores, eng.cache.restored_blocks) == before


def test_slow_link_breakeven_prefers_recompute(setup):
    cfg, params = setup
    rng = np.random.default_rng(4)
    toks = rng.integers(0, cfg.vocab_size, 40).tolist()
    slow = dict(TIER, offload_host_bw=1e3)   # ~KB/s fake PCIe
    eng = PrefillOnlyEngine(cfg, params, EngineConfig(**slow))
    eng.submit(toks, allowed_tokens=(5, 9))
    eng.run_until_drained()
    _flood(eng, cfg, seed=5)
    assert eng.cache.host.offloads > 0       # demotion still happens
    i = eng.submit(toks, allowed_tokens=(5, 9))
    eng.run_until_drained()
    assert eng.cache.restored_blocks == 0, \
        "restored despite recompute being cheaper than the link"
    assert len(eng.results[i]["scores"]) == 2   # request still correct


def test_restore_estimate_prices_the_host_prefix(setup):
    cfg, params = setup
    rng = np.random.default_rng(6)
    toks = rng.integers(0, cfg.vocab_size, 40).tolist()
    eng = PrefillOnlyEngine(cfg, params, EngineConfig(**TIER))
    eng.submit(toks, allowed_tokens=(5, 9))
    eng.run_until_drained()
    _flood(eng, cfg, seed=7)
    est = eng.restore_estimate(_chain(eng, toks))
    assert est["blocks"] > 0 and est["bytes"] > 0
    assert est["restore_s"] == pytest.approx(
        est["bytes"] / eng.cache.policy.host_bw)


def test_prefetch_upgrades_host_blocks_to_device(setup):
    cfg, params = setup
    rng = np.random.default_rng(8)
    toks = rng.integers(0, cfg.vocab_size, 40).tolist()
    eng = PrefillOnlyEngine(cfg, params, EngineConfig(**TIER))
    eng.submit(toks, allowed_tokens=(5, 9))
    eng.run_until_drained()
    _flood(eng, cfg, seed=9)
    chain = _chain(eng, toks)
    n = eng.prefetch_prefix(chain)
    assert n > 0
    deadline = 50
    while eng.cache.probe_blocks(chain) == 0 and deadline:
        import time as _t
        _t.sleep(0.05)
        deadline -= 1
    assert eng.cache.probe_blocks(chain) > 0
    # the async worker upgrades payloads in place to device arrays
    for _ in range(100):
        blks = [eng.cache.blocks.get(h) for h in chain]
        blks = [b for b in blks if b is not None and b.payload is not None]
        if blks and all(not isinstance(b.payload[0], np.ndarray)
                        for b in blks):
            break
        import time as _t
        _t.sleep(0.05)
    assert blks and all(not isinstance(b.payload[0], np.ndarray)
                        for b in blks)


def test_pinned_blocks_survive_tiered_eviction():
    from repro.core.prefix_cache import token_chain
    c = TieredPrefixCache(2, 4, policy=OffloadPolicy(TPU_V5E))
    a = token_chain([1, 2, 3, 4, 5, 6, 7, 8], 4)
    c.insert(a, 8, payloads=[(np.ones((2, 4), np.float32),)] * 2)
    c.pin(a, 2)                              # running request holds it
    b = token_chain([9, 10, 11, 12, 13, 14, 15, 16], 4)
    c.insert(b, 8, now=1.0,
             payloads=[(np.zeros((2, 4), np.float32),)] * 2)
    assert all(h in c.blocks for h in a), "eviction dropped a pinned block"
    assert c.probe_blocks(a) == 2
    c.unpin(a, 2)


# ---- layer-wise discard arithmetic -----------------------------------------

def test_kv_lifecycle_keep_arithmetic():
    kv = KVLifecycle(block_size=16, kv_keep_tokens=40)
    assert kv.keep(100) == 40 and kv.keep(24) == 24
    assert kv.keep_aligned(100) == 32        # whole blocks only
    assert kv.resident(2, 100) and not kv.resident(1, 100)
    assert kv.keep_new(100, 16, 1) == 16     # one block reused, one new
    assert kv.keep_new(100, 32, 2) == 0      # already resident
    assert kv.suffix_keep_new(40, 32, 60) == 8
    assert kv.insertable_tokens(40, 32, 60) == 8
    assert kv.keep_pad(40, 2048) == 64       # bucketed jit key
    assert kv.keep_pad(40, 48) == 48         # clamped to padded S


def test_memory_model_kv_keep_prices_peak_layer():
    cfg = get_config("llama3.1-8b")
    mm = MemoryModel(cfg, TPU_V5E)
    S = 1 << 16
    unpriced = mm.peak_bytes(S, "hybrid")
    capped = mm.peak_bytes(S, "hybrid", kv_keep=1024)
    full = mm.peak_bytes(S, "hybrid", kv_keep=S)
    assert unpriced < capped < full
    # kept slice saturates at kv_keep: constant beyond the knee
    assert (mm.peak_bytes(2 * S, "hybrid", kv_keep=1024) - capped
            == pytest.approx(mm.peak_bytes(2 * S, "hybrid") - unpriced))


def test_memory_model_mil_knee_and_prefix_budget():
    cfg = get_config("llama3.1-8b")
    # fp8 weights — the paper's quantized serving setup; fp16 weights alone
    # would exceed the default chip's HBM and zero out every MIL
    mm = MemoryModel(cfg, TPU_V5E, weight_bytes_per_param=1)
    mil_all = mm.max_input_length("hybrid", kv_keep=1 << 30)  # keep all
    mil_cap = mm.max_input_length("hybrid", kv_keep=1024)
    mil_un = mm.max_input_length("hybrid")
    assert mil_all <= mil_cap <= mil_un
    # discard bound honored: serving at mil_cap with the capped keep fits
    assert mm.peak_bytes(mil_cap, "hybrid", kv_keep=1024) <= mm.budget_bytes()
    # peak-layer pricing shrinks the reservation -> larger device cache:
    # at the SAME serving length, a capped kept slice reserves less HBM
    # than keeping every input token's KV, so more is left for the cache
    S = mil_all
    budget_cap = mm.prefix_budget_tokens(S, kv_keep=1024)
    budget_all = mm.prefix_budget_tokens(S, kv_keep=S)
    assert budget_cap > budget_all
    assert budget_cap > 0
