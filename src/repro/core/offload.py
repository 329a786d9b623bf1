"""Host-memory KV offload tier — paper §9 "Offloading the KV caches to CPU".

The base engine DISCARDS suffix KV (and evicted prefix blocks) outright.
This tier gives the cache a second chance: blocks evicted from the
device-resident ``PrefixCache`` drop into a host-RAM store (LMCache-style);
a later match restores them instead of recomputing. The paper leaves this
as future work — here it is a first-class, bounded, LRU-managed tier.

Economics (why restoring beats recomputing): restoring a block moves
``kv_bytes_per_token * block_size`` over PCIe/DMA (~10-100 GB/s), while
recomputing it costs ``2 * N_active * block_size`` FLOPs — for an 8B model
that is ~1000x more work per token than the transfer, so offload wins
whenever host RAM is available. ``OffloadPolicy.worth_restoring`` encodes
the break-even; its constants come from ``runtime/hw.py`` (the same
``ChipSpec`` that drives the MIL memory model), and the engine's
``profile()`` fit can override ``host_bw`` with a measured value.
"""
from __future__ import annotations

import dataclasses
import sys
from collections import OrderedDict
from typing import Any, Dict, List, Optional

import numpy as np

from repro.configs.base import ModelConfig
from repro.core.prefix_cache import Chain, PrefixCache
from repro.runtime.hw import ChipSpec


def _nbytes(payload: Any) -> int:
    total = 0
    for leaf in (payload if isinstance(payload, (tuple, list)) else [payload]):
        if hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
        else:
            total += sys.getsizeof(leaf)
    return total


def to_host(payload: Any) -> Any:
    """Force a (possibly jax device-array) payload onto host numpy.

    ``np.asarray`` materializes device buffers off-accelerator; without it a
    "host" store would keep the payload pinned in HBM, defeating the tier.
    """
    if payload is None:
        return None
    if isinstance(payload, (tuple, list)):
        return tuple(np.asarray(p) for p in payload)
    return np.asarray(payload)


@dataclasses.dataclass
class OffloadPolicy:
    """Transfer-vs-recompute break-even for the DRAM tier.

    Defaults are sourced from the ``ChipSpec`` of the device the engine
    runs on (``runtime/hw.py``) rather than re-hardcoded here;
    ``host_bw``/``peak_flops`` accept explicit overrides (e.g. a measured
    PCIe bandwidth from ``profile()``).
    """
    chip: ChipSpec
    host_bw: Optional[float] = None      # bytes/s device<->host
    peak_flops: Optional[float] = None   # FLOP/s
    efficiency: float = 0.5

    def __post_init__(self):
        if self.host_bw is None:
            self.host_bw = self.chip.host_bw
        if self.peak_flops is None:
            self.peak_flops = self.chip.peak_flops_bf16

    def restore_seconds(self, payload_bytes: int) -> float:
        return payload_bytes / self.host_bw

    def recompute_seconds(self, cfg: ModelConfig, n_tokens: int) -> float:
        return (2.0 * cfg.active_param_count() * n_tokens
                / (self.peak_flops * self.efficiency))

    def worth_restoring(self, cfg: ModelConfig, n_tokens: int,
                        payload_bytes: int) -> bool:
        return (self.restore_seconds(payload_bytes)
                < self.recompute_seconds(cfg, n_tokens))


class HostKVStore:
    """Bounded LRU store of per-block KV payloads in host memory."""

    def __init__(self, capacity_bytes: int = 1 << 30):
        self.capacity_bytes = capacity_bytes
        self._store: "OrderedDict[int, Any]" = OrderedDict()
        self._bytes: Dict[int, int] = {}
        self.used_bytes = 0
        self.offloads = 0
        self.restores = 0
        self.host_evictions = 0
        self.offload_bytes = 0
        self.restore_bytes = 0

    def put(self, block_hash: int, payload: Any):
        if payload is None:
            return
        if block_hash in self._store:
            self._store.move_to_end(block_hash)
            return
        # device -> host copy FIRST, then account post-conversion bytes —
        # the device view may be a lazy slice whose materialized size differs
        host_payload = to_host(payload)
        nb = _nbytes(host_payload)
        if nb > self.capacity_bytes:
            return
        while self.used_bytes + nb > self.capacity_bytes and self._store:
            h, _ = self._store.popitem(last=False)
            self.used_bytes -= self._bytes.pop(h)
            self.host_evictions += 1
        self._store[block_hash] = host_payload
        self._bytes[block_hash] = nb
        self.used_bytes += nb
        self.offloads += 1
        self.offload_bytes += nb

    def get(self, block_hash: int) -> Optional[Any]:
        if block_hash not in self._store:
            return None
        self._store.move_to_end(block_hash)
        self.restores += 1
        self.restore_bytes += self._bytes[block_hash]
        return self._store[block_hash]

    def nbytes_of(self, block_hash: int) -> int:
        """Stored size of a block WITHOUT touching LRU order or counters."""
        return self._bytes.get(block_hash, 0)

    def __contains__(self, block_hash: int) -> bool:
        return block_hash in self._store

    def __len__(self) -> int:
        return len(self._store)

    def stats(self) -> Dict[str, float]:
        return {"used_bytes": self.used_bytes,
                "capacity_bytes": self.capacity_bytes,
                "blocks": len(self._store),
                "offloads": self.offloads, "restores": self.restores,
                "host_evictions": self.host_evictions,
                "offload_bytes": self.offload_bytes,
                "restore_bytes": self.restore_bytes}


class TieredPrefixCache(PrefixCache):
    """PrefixCache whose evictions offload to a HostKVStore and whose misses
    consult it — drop-in replacement for the engine's cache.

    Tier vocabulary: a block is ``device`` (resident, payload usable by the
    forward), ``host`` (evicted into the DRAM store, restorable when
    ``OffloadPolicy.worth_restoring`` wins), or absent (recompute)."""

    def __init__(self, capacity_blocks: int, block_size: int = 16,
                 host_store: Optional[HostKVStore] = None,
                 cfg: Optional[ModelConfig] = None, *,
                 policy: OffloadPolicy):
        super().__init__(capacity_blocks, block_size)
        self.host = host_store or HostKVStore()
        self.cfg = cfg
        self.policy = policy
        self.restored_blocks = 0

    def _remove(self, h: int):
        blk = self.blocks.get(h)
        if blk is not None and blk.payload is not None:
            self.host.put(h, blk.payload)          # offload, don't discard
        super()._remove(h)

    def _restorable(self, h: int) -> bool:
        if h not in self.host:
            return False
        if self.cfg is None:
            return True
        return self.policy.worth_restoring(
            self.cfg, self.block_size, self.host.nbytes_of(h))

    def match_tiers(self, chain: Chain) -> List[str]:
        """Per-block tier of the longest serveable prefix: ``device`` blocks
        first, then the ``host`` continuation that the policy would restore.
        Read-only — no LRU touch, no restore."""
        tiers: List[str] = []
        for h in chain:
            if h in self.blocks:
                tiers.append("device")
            else:
                break
        for h in chain[len(tiers):]:
            if not self._restorable(h):
                break
            tiers.append("host")
        return tiers

    def probe_blocks(self, chain: Chain) -> int:
        """Serveable prefix = device run + restorable host continuation,
        side-effect free (no LRU touch, no restore — see base docstring)."""
        return len(self.match_tiers(chain))

    def restore_estimate(self, chain: Chain) -> Dict[str, float]:
        """Restorable host continuation of ``chain``'s device run, priced at
        the policy's effective host bandwidth. Read-only; used by admission
        to fold restore latency into the JCT estimate and by the router-time
        prefetch to decide whether a transfer is worth starting."""
        n_dev = super().match_blocks(chain)
        blocks = 0
        nbytes = 0
        for h in chain[n_dev:]:
            if not self._restorable(h):
                break
            blocks += 1
            nbytes += self.host.nbytes_of(h)
        return {"device_blocks": n_dev, "blocks": blocks, "bytes": nbytes,
                "restore_s": self.policy.restore_seconds(nbytes)
                if nbytes else 0.0}

    def match_blocks(self, chain: Chain, now: float = 0.0,
                     touch: bool = False) -> int:
        """Device hits first; then extend the run with host-restorable
        blocks (restored into the device cache on the spot when worth it)."""
        n = super().match_blocks(chain, now, touch)
        restored = 0
        for h in chain[n:]:
            if not self._restorable(h):
                break
            payload = self.host.get(h)
            if payload is None:
                break
            # reinsert this block at the tail of the resident chain
            got = self.insert(chain[: n + restored + 1],
                              (n + restored + 1) * self.block_size,
                              now=now,
                              payloads=None)
            if got < n + restored + 1:
                break
            self.blocks[h].payload = payload
            restored += 1
        self.restored_blocks += restored
        return n + restored

    def stats(self) -> Dict[str, float]:
        out = super().stats()
        out["restored_blocks"] = self.restored_blocks
        out["host"] = self.host.stats()
        return out
