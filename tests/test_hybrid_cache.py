"""The published hybrid pattern (granite-4.0-h-micro) in the engine and its
cache, on the CPU at a tiny size: the configuration's real figures (and
Zamba-2's, unchanged); the program's snapshots against the token-by-token
recurrence and against a fresh forward; the cache's byte accounting with
snapshots; the KV/state keep rule; the engine refusing a hybrid that may
pack; a hit resuming from a snapshot held in a cache block."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.configs.base import ModelConfig
from repro.core.engine import EngineConfig, PrefillOnlyEngine
from repro.core.kv_policy import KVLifecycle, MemoryModel
from repro.core.prefix_cache import PrefixCache
from repro.models import hybrid
from repro.models.mamba2 import ssd_scan
from repro.runtime.hw import TPU_V5E
from repro.runtime.sharding import materialize, param_count

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


def tiny(**kw) -> ModelConfig:
    base = dict(name="tiny-hybrid", family="hybrid", num_layers=10,
                d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                d_ff=128, vocab_size=256, ssm_state=16, ssm_headdim=16,
                ssm_chunk=8, layer_types=PERIOD, embedding_multiplier=12.0,
                residual_multiplier=0.22, logits_scaling=8.0,
                attention_multiplier=1 / 16, position_embedding="nope",
                norm_eps=1e-5, hybrid_chunk=0, dtype="float32",
                param_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = materialize(jax.random.PRNGKey(0), hybrid.model_defs(cfg),
                         jnp.float32)
    # zero-initialized gains and biases would hide a wrong layer order
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                              a.shape), params)
    return cfg, params


def test_granite_figures_count_the_pattern():
    cfg = get_config("granite-4.0-h-micro")
    assert (cfg.n_attention_layers, cfg.n_mamba_layers) == (4, 36)
    assert cfg.param_count() == 3_191_396_096
    assert cfg.param_count() == param_count(hybrid.model_defs(cfg))
    # 4 layers x (K + V) x 8 heads x 64 x 2 bytes
    assert cfg.kv_bytes_per_token() == 8192
    # 36 x (64 x 64 x 128 x 4 bytes of SSD state + 3 x 4352 x 2 of conv)
    assert cfg.state_bytes() == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    small = reduce_config(cfg)
    assert small.layer_types == PERIOD and small.num_layers == 10


def test_zamba2_figures_stay_as_they_were():
    z = get_config("zamba2-2.7b")
    assert (z.param_count(), z.kv_bytes_per_token()) == (2_340_462_528,
                                                         92_160)
    zr = reduce_config(z)
    assert (zr.param_count(), zr.kv_bytes_per_token()) == (653_824, 1024)
    assert z.n_attention_layers == 9 and not z.layer_types


def test_layer_types_must_match_the_depth():
    with pytest.raises(ValueError):
        tiny(layer_types=PERIOD[:9])
    with pytest.raises(ValueError):
        tiny(layer_types=("mamba",) * 9 + ("mlp",))


def test_scan_snapshots_are_the_token_recurrence():
    """The program's chunked scan, read every 16 tokens, against the state
    of the plain recurrence after those tokens."""
    rng = np.random.default_rng(0)
    B, S, H, P, N = 1, 40, 3, 4, 5
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(B, S, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, snaps = ssd_scan(x, dt * A, Bm, Cm, dt, chunk=8, snap_every=16)
        y0, _ = ssd_scan(x, dt * A, Bm, Cm, dt, chunk=8)
    h = np.zeros((H, P, N))
    want, ys = [], []
    for t in range(S):
        h = (np.exp(np.asarray(dt[0, t] * A))[:, None, None] * h
             + np.einsum("h,hp,n->hpn", np.asarray(dt[0, t]),
                         np.asarray(x[0, t]), np.asarray(Bm[0, t])))
        ys.append(np.einsum("hpn,n->hp", h, np.asarray(Cm[0, t])))
        if (t + 1) % 16 == 0:
            want.append(h.copy())
    # float32 sums over 40 steps of O(1) terms: 1e-4 is rounding, a wrong
    # decay or a state read one chunk late is off by O(0.1)
    assert snaps.shape == (B, 3, H, P, N)
    np.testing.assert_allclose(np.asarray(snaps[0, :2]), np.stack(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y[0]), np.stack(ys), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))


@pytest.mark.parametrize("prefix", [16, 32, 48])
def test_resume_from_a_snapshot_equals_the_fresh_forward(model, prefix):
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 64), 0,
                              cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        lg, aux = hybrid.pattern_prefill(params, cfg, toks, kv_keep=64,
                                         snap_every=16, n_snap=4,
                                         last_index=jnp.array([50]))
        j = prefix // 16 - 1
        lg2, aux2 = hybrid.pattern_prefill(
            params, cfg, toks[:, prefix:], kv_keep=64 - prefix,
            snap_every=16, n_snap=(64 - prefix) // 16, prefix_len=prefix,
            prefix_kv={"k": aux["k"][:, :, :prefix],
                       "v": aux["v"][:, :, :prefix]},
            state0={"ssm": aux["ssm"][:, :, j], "conv": aux["conv"][:, :, j]},
            last_index=jnp.array([50 - prefix]))
    assert aux["ssm"].shape == (9, 1, 4, 8, 16, 16)
    assert aux["k"].shape == (1, 1, 64, 2, 16)
    # float32 at HIGHEST, the same operations in another grouping: a resume
    # from the wrong boundary or without the conv state is off by O(0.1)
    np.testing.assert_allclose(np.asarray(lg2), np.asarray(lg), atol=1e-5)
    np.testing.assert_allclose(np.asarray(aux2["k"]),
                               np.asarray(aux["k"][:, :, prefix:]),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(aux2["ssm"]),
                               np.asarray(aux["ssm"][:, :, j + 1:]),
                               rtol=1e-4, atol=1e-4)


def test_the_cache_counts_snapshot_bytes():
    """A block inserted with more units takes that share of the capacity;
    evicting it, leaf first, gives them back."""
    c = PrefixCache(capacity_blocks=20, block_size=4)
    chain = tuple(range(1, 9))
    units = [1, 1, 1, 6, 1, 1, 1, 6]
    assert c.insert(chain, 32, payloads=[("k", "v")] * 8, units=units) == 8
    assert (c.used_blocks, c.used_units) == (8, 18)
    # a second chain of 3 one-unit blocks needs 1 unit more than is left:
    # the LRU leaf (the first chain's snapshot block, 6 units) goes
    other = (101, 102, 103)
    assert c.insert(other, 12, now=1.0) == 3
    assert chain[-1] not in c.blocks and c.used_units == 15
    assert c.match_blocks(chain) == 7
    assert c.stats()["used_units"] == 15


def test_the_keep_rule_covers_state():
    kv = KVLifecycle(block_size=16, kv_keep_tokens=17408,
                     buckets=(64, 2048), state_stride=4096)
    assert kv.snapshot_slots(32768) == 4          # bounded by the keep
    assert kv.snapshot_slots(16384) == 4
    assert kv.snapshot_slots(2048) == 0
    assert kv.snapshots_kept(12288 + 160) == 3
    assert KVLifecycle().snapshot_slots(32768) == 0


def test_the_memory_model_prices_state():
    cfg = get_config("granite-4.0-h-micro")
    plain = MemoryModel(cfg, TPU_V5E)
    strided = MemoryModel(cfg, TPU_V5E, state_stride=4096)
    assert plain.state_per_token == 0
    assert strided.state_per_token == cfg.state_bytes() / 4096
    assert strided.cache_per_token == 8192 + cfg.state_bytes() / 4096
    assert (strided.peak_bytes(16384, "hybrid", kv_keep=16384)
            - plain.peak_bytes(16384, "hybrid", kv_keep=16384)
            == pytest.approx(4 * cfg.state_bytes()))
    assert (strided.prefix_budget_tokens(17408, kv_keep=17408)
            < plain.prefix_budget_tokens(17408, kv_keep=17408))


def test_the_engine_refuses_a_hybrid_that_may_pack(model):
    cfg, params = model
    with pytest.raises(ValueError, match="packs of one"):
        PrefillOnlyEngine(cfg, params, EngineConfig(block_size=4,
                                                    prefix_bucket_blocks=4))
    with pytest.raises(ValueError, match="SSD chunk"):
        PrefillOnlyEngine(cfg, params, EngineConfig(
            block_size=4, prefix_bucket_blocks=3, max_pack_requests=1))
    with pytest.raises(ValueError, match="no 'ssm' model"):
        PrefillOnlyEngine(reduce_config(get_config("mamba2-130m")), params)


def _engine(cfg, params, cache_tokens=8000):
    ecfg = EngineConfig(block_size=4, prefix_bucket_blocks=4,
                        max_pack_requests=1,
                        cache_capacity_tokens=cache_tokens,
                        suffix_buckets=(16, 32, 64, 128))
    return PrefillOnlyEngine(cfg, params, ecfg)


def test_a_hit_resumes_from_the_snapshot_in_the_cache(model):
    """The second request shares 53 tokens: it resumes from the snapshot
    at 48, held in the block that ends there, and scores as a fresh
    forward does; the step's counters say so."""
    cfg, params = model
    eng = _engine(cfg, params)
    eng.profile((16, 32))
    assert eng.ecfg.max_pack_requests == 1        # autotune packs nothing
    rng = np.random.default_rng(3)
    base = rng.integers(0, cfg.vocab_size, 70).tolist()
    hit = base[:53] + rng.integers(0, cfg.vocab_size, 9).tolist()
    labels = (3, 7, 11)
    eng.submit(base, allowed_tokens=labels)
    eng.run_until_drained()
    snaps = [b for b in eng.cache.blocks.values() if len(b.payload) == 4]
    assert len(snaps) == 4 and all(b.units == 1 + eng.snapshot_units
                                   for b in snaps)
    fresh = eng.batch_records[-1]
    assert fresh.jit_path == "fresh" and "state_insert" in fresh.phases
    i = eng.submit(hit, allowed_tokens=labels)
    eng.run_until_drained()
    got = eng.results[i]
    rec = eng.batch_records[-1]
    assert got["n_cached"] == 48
    assert (rec.matched_tokens, rec.resumed_tokens) == (52, 48)
    cold = _engine(cfg, params, cache_tokens=0)
    j = cold.submit(hit, allowed_tokens=labels)
    cold.run_until_drained()
    want = cold.results[j]
    assert want["n_cached"] == 0
    # float32 weights, two groupings of the same sums: 1e-5 is rounding
    for t in labels:
        assert abs(got["scores"][t] - want["scores"][t]) < 1e-5
