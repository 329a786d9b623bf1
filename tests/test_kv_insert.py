"""The KV insert cuts fresh KV into cache blocks with a few jitted split
programs (``PrefillOnlyEngine._split_blocks``). Its payloads must be
bit-identical to the eager per-block slices it replaced, on every step path
and at the array's end, where a dynamic slice clamps; the split program is
keyed on the KV shape alone; and the engine counts the blocks it cuts and
the programs it dispatches."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.core import engine as engine_mod
from repro.core.engine import EngineConfig, PrefillOnlyEngine
from repro.models.model import build
from repro.runtime.sharding import materialize
from repro.serving import MetricsRegistry

BS = 16


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_config(get_config("qwen1.5-0.5b"), hybrid_chunk=0)
    api = build(cfg)
    params = materialize(jax.random.PRNGKey(0), api.defs(), jnp.float32)
    return cfg, params


def eager_blocks(kv, start, n_blocks):
    """The insert as it was: two eager slices per block."""
    return [(kv["k"][:, :, lo:lo + BS], kv["v"][:, :, lo:lo + BS])
            for lo in range(start, start + n_blocks * BS, BS)]


def assert_same_blocks(got, want):
    assert len(got) == len(want)
    for (gk, gv), (wk, wv) in zip(got, want):
        for g, w in ((gk, wk), (gv, wv)):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class CheckedEngine(PrefillOnlyEngine):
    """Checks every cut against the eager slices of the same KV and notes
    the step path, the KV length and the block count."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.cuts = []

    def _split_blocks(self, kv, start, n_blocks):
        got = super()._split_blocks(kv, start, n_blocks)
        if n_blocks:
            assert_same_blocks(got, eager_blocks(kv, start, n_blocks))
            self.cuts.append((self._last_jit[0], kv["k"].shape[2], n_blocks))
        return got


class EagerEngine(PrefillOnlyEngine):
    """The reference run: inserts with the eager per-block slices."""

    def _split_blocks(self, kv, start, n_blocks):
        return eager_blocks(kv, start, n_blocks) if n_blocks else []


def _kv(T, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    shape = (2, 1, T, 2, 4)
    return {"k": jax.random.normal(ks[0], shape, jnp.float32),
            "v": jax.random.normal(ks[1], shape, jnp.float32)}


@pytest.mark.parametrize("T,start,n_blocks,split", [
    (128, 0, 7, 3),      # block count not a multiple of G
    (48, 0, 3, 64),      # KV shorter than one chunk: G = T // bs
    (64, 0, 4, 3),       # last call's surplus runs past the end (clamps)
    (64, 32, 2, 3),      # a window at an offset that ends at the array's end
    (100, 0, 6, 4),      # KV length not a whole number of blocks
    (128, 48, 5, 2),     # offset start, several calls
])
def test_split_matches_eager_slices(setup, monkeypatch, T, start, n_blocks,
                                    split):
    monkeypatch.setattr(engine_mod, "SPLIT_BLOCKS", split)
    cfg, params = setup
    eng = PrefillOnlyEngine(cfg, params)
    kv = _kv(T)
    got = eng._split_blocks(kv, start, n_blocks)
    assert_same_blocks(got, eager_blocks(kv, start, n_blocks))
    G = min(split, T // BS)
    assert eng.kv_insert_programs == math.ceil(n_blocks / G)
    assert eng.kv_insert_blocks == n_blocks


def test_split_refuses_blocks_outside_the_kv(setup):
    cfg, params = setup
    eng = PrefillOnlyEngine(cfg, params)
    assert eng._split_blocks(_kv(64), 0, 0) == []
    with pytest.raises(ValueError):
        eng._split_blocks(_kv(64), 16, 4)


def _serve_trace(eng, cfg):
    """A fresh miss, a solo cache hit, two misses packed, three hits packed
    and one more miss; each step's path and the scores of every request."""
    rng = np.random.default_rng(5)
    profile = rng.integers(0, cfg.vocab_size, 80).tolist()
    groups = [
        [profile + rng.integers(0, cfg.vocab_size, 37).tolist()],
        [profile + rng.integers(0, cfg.vocab_size, 20).tolist()],
        [rng.integers(0, cfg.vocab_size, n).tolist() for n in (40, 45)],
        [profile + rng.integers(0, cfg.vocab_size, n).tolist()
         for n in (20, 30, 25)],
        [rng.integers(0, cfg.vocab_size, 150).tolist()],
    ]
    paths, ids = [], []
    for reqs in groups:
        ids += [eng.submit(t, allowed_tokens=(5, 9)) for t in reqs]
        assert eng.step() is not None
        assert not eng.queue
        paths.append(eng.batch_records[-1].jit_path)
    return paths, [eng.results[i]["scores"] for i in ids]


def test_paths_insert_the_eager_payloads(setup, monkeypatch):
    """Fresh, solo-suffix, packed-miss and packed-hit inserts cut the same
    payloads as the eager slices, the packed miss's window ending at the
    KV's end; scores and the cache afterwards equal an eager run's."""
    monkeypatch.setattr(engine_mod, "SPLIT_BLOCKS", 3)
    cfg, params = setup
    ecfg = EngineConfig(pack_token_budget=512)
    eng = CheckedEngine(cfg, params, ecfg)
    ref = EagerEngine(cfg, params, EngineConfig(pack_token_budget=512))
    paths, scores = _serve_trace(eng, cfg)
    ref_paths, ref_scores = _serve_trace(ref, cfg)
    assert paths == ref_paths == ["fresh", "suffix", "packed_miss",
                                  "packed_hit", "fresh"]
    assert [p for p, _, _ in eng.cuts] == paths
    # the packed miss keeps 32 + 32 tokens in a 64-token KV: its last split
    # call's surplus runs past the end
    assert eng.cuts[2][1:] == (64, 4)
    assert scores == ref_scores
    assert eng.cache.blocks.keys() == ref.cache.blocks.keys()
    for h, blk in ref.cache.blocks.items():
        assert_same_blocks([eng.cache.blocks[h].payload], [blk.payload])
    assert eng.kv_insert_blocks == sum(n for _, _, n in eng.cuts)


def test_split_program_keyed_by_shape_alone(setup, monkeypatch):
    """Requests with different block counts under one KV shape compile one
    split program; each insert dispatches ceil(blocks / G) of them, and the
    counters read the same in stats() and in the metrics registry."""
    monkeypatch.setattr(engine_mod, "SPLIT_BLOCKS", 3)
    cfg, params = setup
    eng = PrefillOnlyEngine(cfg, params, EngineConfig(max_pack_requests=1))
    reg = MetricsRegistry()
    eng.bind_telemetry(metrics=reg, instance="i0")
    rng = np.random.default_rng(9)
    for n in (70, 90, 110, 125):           # 4 to 7 blocks, all in the
        blocks0 = eng.kv_insert_blocks     # 128-token bucket
        programs0 = eng.kv_insert_programs
        eng.submit(rng.integers(0, cfg.vocab_size, n).tolist())
        eng.run_until_drained()
        assert eng.batch_records[-1].jit_path == "fresh"
        assert eng.kv_insert_blocks - blocks0 == n // BS
        assert eng.kv_insert_programs - programs0 == math.ceil(n // BS / 3)
    assert len(eng._split_fns) == 1
    (fn,) = eng._split_fns.values()
    assert fn._cache_size() == 1
    stats = eng.stats()
    assert stats["kv_insert_blocks"] == eng.cache.stats()["used_blocks"] == 22
    assert stats["kv_insert_programs"] == 2 + 2 + 2 + 3
    assert reg.total("kv_insert_blocks") == 22
    assert reg.total("kv_insert_programs") == 9
