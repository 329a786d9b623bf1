"""Host-KV offload tier (paper §9): evict -> offload -> restore-on-match."""
import numpy as np

from repro.configs import get_config
from repro.core.offload import HostKVStore, OffloadPolicy, TieredPrefixCache
from repro.core.prefix_cache import token_chain
from repro.runtime.hw import TPU_V5E

BLOCK = 4
CFG = get_config("llama3.1-8b")


def _chain(n, seed=0):
    toks = [(seed * 997 + i) % 89 for i in range(n)]
    return token_chain(toks, BLOCK)


def _payloads(chain):
    return [(np.full((2, BLOCK), i, np.float32),) for i in range(len(chain))]


def test_evicted_blocks_land_in_host_store():
    c = TieredPrefixCache(2, BLOCK, cfg=CFG,
                          policy=OffloadPolicy(TPU_V5E))
    a = _chain(8, seed=1)
    c.insert(a, 8, payloads=_payloads(a))
    b = _chain(8, seed=2)
    c.insert(b, 8, now=1.0, payloads=_payloads(b))   # evicts a's blocks
    assert c.host.offloads >= 1
    assert any(h in c.host for h in a)


def test_match_restores_from_host():
    c = TieredPrefixCache(2, BLOCK, cfg=CFG,
                          policy=OffloadPolicy(TPU_V5E))
    a = _chain(8, seed=1)
    c.insert(a, 8, payloads=_payloads(a))
    b = _chain(8, seed=2)
    c.insert(b, 8, now=1.0, payloads=_payloads(b))
    assert super(TieredPrefixCache, c).match_blocks(a) == 0  # device miss
    m = c.match_len(a, now=2.0)                              # host restore
    assert m > 0
    assert c.host.restores >= 1
    # restored payload is intact
    payloads = c.match_payloads(a, now=3.0)
    assert payloads and payloads[0][0][0, 0] == 0.0


def test_host_store_capacity_lru():
    payload_bytes = 2 * BLOCK * 4
    s = HostKVStore(capacity_bytes=2 * payload_bytes)   # fits 2 payloads
    for i in range(4):
        s.put(i, (np.zeros((2, BLOCK), np.float32),))
    assert s.used_bytes <= s.capacity_bytes
    assert s.host_evictions >= 2
    assert 3 in s and 0 not in s


def test_policy_breakeven():
    pol = OffloadPolicy(TPU_V5E)
    # an 8B model: restoring a 16-token block (~2 MB) beats recomputing
    assert pol.worth_restoring(CFG, 16, 2 * 2**20)
    # absurdly slow link -> recompute wins
    slow = OffloadPolicy(TPU_V5E, host_bw=1e3)
    assert not slow.worth_restoring(CFG, 16, 2 * 2**20)
