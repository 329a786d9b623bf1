"""Prefix-aware packed prefill (the packed cache-HIT path): kernel ->
oracle -> transformer -> engine equivalence against the solo suffix path,
the prefix-tile-skip guarantee, and the engine's {solo suffix, packed miss,
packed hit} cost model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.core.engine import EngineConfig, PrefillOnlyEngine
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention as raw_flash
from repro.models import transformer as tfm
from repro.models.layers import PAD_POS, blocked_attention
from repro.models.model import build
from repro.runtime.sharding import materialize


def _layout(plens, slens, B=1):
    """Packed arrays for suffixes ``slens`` over cached prefixes ``plens``:
    (seg, pos) for the fresh side, (pseg, ppos) for the prefix buffer."""
    S, P = sum(slens), sum(plens)
    seg = np.full((B, S), -1, np.int32)
    pos = np.zeros((B, S), np.int32)
    pseg = np.full((B, max(P, 1)), -1, np.int32)[:, :P]
    ppos = np.full((B, max(P, 1)), PAD_POS, np.int32)[:, :P]
    off = 0
    for n, L in enumerate(slens):
        seg[:, off:off + L] = n
        pos[:, off:off + L] = plens[n] + np.arange(L)
        off += L
    off = 0
    for n, L in enumerate(plens):
        pseg[:, off:off + L] = n
        ppos[:, off:off + L] = np.arange(L)
        off += L
    return (jnp.asarray(seg), jnp.asarray(pos), jnp.asarray(pseg),
            jnp.asarray(ppos))


# --------------------------------------------------------------------------
# kernel layer
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("plens,slens,H,KV,d,window,softcap", [
    ((32, 0, 48), (20, 30, 10), 4, 4, 16, 0, 0.0),   # MHA, one miss segment
    ((32, 16, 48), (20, 30, 10), 4, 2, 16, 0, 0.0),  # GQA, all hits
    ((48, 32), (25, 13), 4, 2, 16, 13, 0.0),         # GQA + SWA
    ((16, 64), (33, 30), 8, 2, 32, 0, 50.0),         # softcap (gemma2)
    ((0, 0, 0), (40, 30, 26), 2, 1, 8, 0, 0.0),      # degenerate: no prefix
])
def test_prefix_kernel_matches_ref(plens, slens, H, KV, d, window, softcap,
                                   dtype):
    S, P = sum(slens), sum(plens)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (2, S, H, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (2, S, KV, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (2, S, KV, d), jnp.float32).astype(dtype)
    pk = jax.random.normal(ks[3], (2, max(P, 1), KV, d),
                           jnp.float32).astype(dtype)[:, :P]
    pv = jax.random.normal(ks[4], (2, max(P, 1), KV, d),
                           jnp.float32).astype(dtype)[:, :P]
    seg, pos, pseg, ppos = _layout(plens, slens, B=1)
    seg, pos = (jnp.broadcast_to(a, (2, S)) for a in (seg, pos))
    pseg, ppos = (jnp.broadcast_to(a, (2, P)) for a in (pseg, ppos))
    got = ops.packed_flash_attention(
        q, k, v, seg, window=window, softcap=softcap, prefix_k=pk,
        prefix_v=pv, prefix_seg=pseg, positions=pos, prefix_positions=ppos,
        block_q=32, block_k=32)
    want = ref.packed_prefix_attention_ref(
        q.transpose(0, 2, 1, 3),
        jnp.concatenate([pk, k], axis=1).transpose(0, 2, 1, 3),
        jnp.concatenate([pv, v], axis=1).transpose(0, 2, 1, 3),
        seg, jnp.concatenate([pseg, seg], axis=1),
        pos, jnp.concatenate([ppos, pos], axis=1),
        window=window, softcap=softcap).transpose(0, 2, 1, 3)
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_prefix_kernel_segments_match_independent_prefix_attention():
    """Each packed segment's rows equal a standalone call over
    concat(its own prefix, its own suffix) — the hit-path ground truth."""
    plens, slens = (32, 48, 0), (20, 12, 30)
    S = sum(slens)
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (1, S, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, S, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, S, 2, 16), jnp.float32)
    pk = jax.random.normal(ks[3], (1, sum(plens), 2, 16), jnp.float32)
    pv = jax.random.normal(ks[4], (1, sum(plens), 2, 16), jnp.float32)
    seg, pos, pseg, ppos = _layout(plens, slens)
    got = ops.packed_flash_attention(
        q, k, v, seg, prefix_k=pk, prefix_v=pv, prefix_seg=pseg,
        positions=pos, prefix_positions=ppos, block_q=32, block_k=32)
    off = 0
    for n, L in enumerate(slens):
        poff = sum(plens[:n])
        pl_ = plens[n]
        ksolo = jnp.concatenate([pk[:, poff:poff + pl_], k[:, off:off + L]],
                                axis=1)
        vsolo = jnp.concatenate([pv[:, poff:poff + pl_], v[:, off:off + L]],
                                axis=1)
        solo = blocked_attention(q[:, off:off + L], ksolo, vsolo,
                                 q_offset=pl_, q_block=32, kv_block=32)
        np.testing.assert_allclose(np.asarray(got[:, off:off + L]),
                                   np.asarray(solo), atol=2e-4, rtol=2e-4)
        off += L


def test_prefix_tiles_of_other_segments_are_skipped():
    """The tile map proves a query block never executes another segment's
    prefix tiles — 0-FLOP structural skip over the gathered prefix buffer,
    not just element masking."""
    plens, slens = (64, 64), (32, 32)
    S, P = sum(slens), sum(plens)
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q = jax.random.normal(ks[0], (1, 4, S, 16), jnp.float32)
    kf = jax.random.normal(ks[1], (1, 2, P + S, 16), jnp.float32)
    vf = jax.random.normal(ks[2], (1, 2, P + S, 16), jnp.float32)
    seg, pos, pseg, ppos = _layout(plens, slens)
    seg_k = jnp.concatenate([pseg, seg], axis=1)
    pos_k = jnp.concatenate([ppos, pos], axis=1)
    _, tmap = raw_flash(q, kf, vf, causal=True, seg_q=seg, seg_k=seg_k,
                        pos_q=pos, pos_k=pos_k, block_q=32, block_k=32,
                        debug_tile_map=True)
    tmap = np.asarray(tmap[0])
    seg_q_np, seg_k_np = np.asarray(seg[0]), np.asarray(seg_k[0])
    pos_q_np, pos_k_np = np.asarray(pos[0]), np.asarray(pos_k[0])
    for i in range(tmap.shape[0]):
        for j in range(tmap.shape[1]):
            qs = seg_q_np[i * 32:(i + 1) * 32]
            kss = seg_k_np[j * 32:(j + 1) * 32]
            causal_live = (pos_k_np[j * 32:(j + 1) * 32].min()
                           <= pos_q_np[i * 32:(i + 1) * 32].max())
            overlap = (qs.min() <= kss.max()) and (qs.max() >= kss.min())
            assert tmap[i, j] == int(causal_live and overlap), (i, j, tmap)
    # segment 0's q-block (0) must skip segment 1's prefix tiles (2, 3) and
    # segment 1's q-block (1) must skip segment 0's prefix tiles (0, 1)
    assert tmap[0, 2] == 0 and tmap[0, 3] == 0
    assert tmap[1, 0] == 0 and tmap[1, 1] == 0
    # ...while each hits its OWN prefix tiles
    assert tmap[0, 0] == 1 and tmap[0, 1] == 1
    assert tmap[1, 2] == 1 and tmap[1, 3] == 1


# --------------------------------------------------------------------------
# model oracle layer
# --------------------------------------------------------------------------

@pytest.mark.parametrize("window,softcap", [(0, 0.0), (13, 0.0), (0, 50.0)])
def test_blocked_attention_prefix_matches_ref(window, softcap):
    plens, slens = (32, 16, 0), (20, 30, 10)
    S, P = sum(slens), sum(plens)
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (1, S, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, S, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, S, 2, 16), jnp.float32)
    pk = jax.random.normal(ks[3], (1, P, 2, 16), jnp.float32)
    pv = jax.random.normal(ks[4], (1, P, 2, 16), jnp.float32)
    seg, pos, pseg, ppos = _layout(plens, slens)
    k_full = jnp.concatenate([pk, k], axis=1)
    v_full = jnp.concatenate([pv, v], axis=1)
    seg_k = jnp.concatenate([pseg, seg], axis=1)
    pos_k = jnp.concatenate([ppos, pos], axis=1)
    got = blocked_attention(q, k_full, v_full, window=window,
                            softcap=softcap, seg_ids=seg, seg_ids_k=seg_k,
                            pos_q=pos, pos_k=pos_k, q_block=32, kv_block=32)
    want = ref.packed_prefix_attention_ref(
        q.transpose(0, 2, 1, 3), k_full.transpose(0, 2, 1, 3),
        v_full.transpose(0, 2, 1, 3), seg, seg_k, pos, pos_k,
        window=window, softcap=softcap).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


# --------------------------------------------------------------------------
# transformer layer: prefill_packed_with_prefix == N x prefill_with_prefix
# --------------------------------------------------------------------------

def _softcap_cfg(cfg):
    """Dense config with both softcaps on — exercises the capped-logit path
    without the local/global stack (which the suffix path doesn't cover)."""
    return dataclasses.replace(cfg, attn_softcap=30.0, final_softcap=10.0,
                               name=cfg.name + "-softcap")


def _batched_layout(plens, slens, pmax, smax):
    """Engine-style batched-hit arrays: (prefix_pos, seg_qidx, inv_idx,
    packed positions) for suffixes ``slens`` over prefixes ``plens``."""
    from repro.models.layers import PAD_POS as _PP
    N, S = len(slens), sum(slens)
    pos = np.zeros((1, S), np.int32)
    ppos = np.full((N, pmax), _PP, np.int32)
    seg_qidx = np.full((N, smax), -1, np.int32)
    inv_idx = np.zeros((S,), np.int32)
    off = 0
    for n, (p, s) in enumerate(zip(plens, slens)):
        pos[0, off:off + s] = p + np.arange(s)
        ppos[n, :p] = np.arange(p)
        seg_qidx[n, :s] = off + np.arange(s)
        inv_idx[off:off + s] = n * smax + np.arange(s)
        off += s
    return (jnp.asarray(pos), jnp.asarray(ppos), jnp.asarray(seg_qidx),
            jnp.asarray(inv_idx))


@pytest.mark.parametrize("variant", ["dense", "softcap"])
def test_prefill_packed_with_prefix_matches_solo_suffix(variant):
    cfg = reduce_config(get_config("qwen1.5-0.5b"), hybrid_chunk=0,
                        dtype="float32", param_dtype="float32")
    if variant == "softcap":
        cfg = _softcap_cfg(cfg)
    api = build(cfg)
    params = materialize(jax.random.PRNGKey(0), api.defs(), jnp.float32)
    rng = np.random.default_rng(0)
    plens, slens = (32, 0, 48), (21, 30, 9)
    pmax, smax = 64, 32        # padded rows, engine-style
    reqs = [rng.integers(0, cfg.vocab_size, p + s).tolist()
            for p, s in zip(plens, slens)]
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    prefix_kvs = []
    for t, p in zip(reqs, plens):
        if p:
            _, kv = tfm.prefill(
                params, cfg, {"tokens": jnp.asarray([t[:p]], jnp.int32)},
                kv_keep=p)
        else:
            kv = {"k": jnp.zeros((cfg.num_layers, 1, 0, KV, hd)),
                  "v": jnp.zeros((cfg.num_layers, 1, 0, KV, hd))}
        prefix_kvs.append(kv)
    S = sum(slens)
    pos, ppos, seg_qidx, inv_idx = _batched_layout(plens, slens, pmax, smax)
    toks = np.zeros((1, S), np.int32)
    last = np.zeros((len(reqs),), np.int32)
    off = 0
    for n, (t, p, s) in enumerate(zip(reqs, plens, slens)):
        toks[0, off:off + s] = t[p:]
        last[n] = off + s - 1
        off += s
    pk = jnp.concatenate(
        [jnp.pad(kv["k"], ((0, 0), (0, 0), (0, pmax - p), (0, 0), (0, 0)))
         for kv, p in zip(prefix_kvs, plens)], axis=1)
    pv = jnp.concatenate(
        [jnp.pad(kv["v"], ((0, 0), (0, 0), (0, pmax - p), (0, 0), (0, 0)))
         for kv, p in zip(prefix_kvs, plens)], axis=1)
    logits, kv = tfm.prefill_packed_with_prefix(
        params, cfg, jnp.asarray(toks), pos, jnp.asarray(last),
        {"k": pk, "v": pv}, ppos, seg_qidx, inv_idx,
        kv_indices=jnp.arange(S, dtype=jnp.int32))
    assert logits.shape == (len(reqs), cfg.vocab_size)
    off = 0
    for n, (t, p, s) in enumerate(zip(reqs, plens, slens)):
        if p:
            want, solo_kv = tfm.prefill_with_prefix(
                params, cfg, {"tokens": jnp.asarray([t[p:]], jnp.int32)},
                prefix_kvs[n], p, kv_keep=p + s)
        else:
            want, solo_kv = tfm.prefill(
                params, cfg, {"tokens": jnp.asarray([t], jnp.int32)},
                kv_keep=s)
        np.testing.assert_allclose(np.asarray(logits[n], np.float32),
                                   np.asarray(want[0], np.float32),
                                   atol=2e-3, rtol=2e-3)
        # packed fresh-KV slices == the solo suffix KV the cache stores
        for key in solo_kv:
            np.testing.assert_allclose(
                np.asarray(kv[key][:, :, off:off + s], np.float32),
                np.asarray(solo_kv[key], np.float32), atol=2e-3, rtol=2e-3)
        off += s


# --------------------------------------------------------------------------
# engine layer
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = reduce_config(get_config("qwen1.5-0.5b"), hybrid_chunk=0)
    api = build(cfg)
    params = materialize(jax.random.PRNGKey(0), api.defs(), jnp.float32)
    return cfg, params


def test_cached_sharers_copack_and_match_solo(setup):
    """Prefix sharers whose shared prefix is ALREADY cached co-pack into one
    packed-hit step and score identically to cold solo runs."""
    cfg, params = setup
    rng = np.random.default_rng(10)
    profile = rng.integers(0, cfg.vocab_size, 80).tolist()
    sufs = [rng.integers(0, cfg.vocab_size, 20).tolist() for _ in range(3)]
    eng = PrefillOnlyEngine(cfg, params, EngineConfig(pack_token_budget=512))
    eng.submit(profile + sufs[0], allowed_tokens=(5, 9))
    eng.run_until_drained()          # warm: inserts the shared profile KV
    assert eng.packed_steps == 0
    ids = [eng.submit(profile + s, allowed_tokens=(5, 9)) for s in sufs]
    eng.run_until_drained()
    assert eng.packed_steps == 1                 # one packed-hit step
    assert eng.packed_hit_requests == 3
    for i in ids:
        assert eng.results[i]["n_cached"] == 64  # all rode the cached prefix
    cold = PrefillOnlyEngine(cfg, params,
                             EngineConfig(max_pack_requests=1,
                                          cache_capacity_tokens=0))
    ids2 = [cold.submit(profile + s, allowed_tokens=(5, 9)) for s in sufs]
    cold.run_until_drained()
    for i, j in zip(ids, ids2):
        a, b = eng.results[i]["scores"], cold.results[j]["scores"]
        for t in a:
            assert abs(a[t] - b[t]) < 2e-2


def test_uncached_sharers_still_run_sequentially(setup):
    """A miss sharing a prefix root must NOT co-pack — running sequentially
    lets the later request hit the earlier one's freshly inserted KV."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    profile = rng.integers(0, cfg.vocab_size, 80).tolist()
    eng = PrefillOnlyEngine(cfg, params, EngineConfig(pack_token_budget=512))
    a = eng.submit(profile + rng.integers(0, cfg.vocab_size, 20).tolist())
    b = eng.submit(profile + rng.integers(0, cfg.vocab_size, 20).tolist())
    eng.run_until_drained()
    assert eng.packed_steps == 0
    assert eng.results[b]["n_cached"] > 0


def test_mixed_hit_miss_batch_matches_solo(setup):
    """One packed step carrying a cache hit AND unrelated cache misses
    produces solo-path scores for every member, and every member's KV lands
    in the cache under its own chain."""
    cfg, params = setup
    rng = np.random.default_rng(12)
    profile = rng.integers(0, cfg.vocab_size, 80).tolist()
    hit_req = profile + rng.integers(0, cfg.vocab_size, 20).tolist()
    miss1 = rng.integers(0, cfg.vocab_size, 40).tolist()
    miss2 = rng.integers(0, cfg.vocab_size, 30).tolist()
    eng = PrefillOnlyEngine(cfg, params, EngineConfig(pack_token_budget=512))
    eng.submit(profile, allowed_tokens=(5, 9))
    eng.run_until_drained()                      # warm the shared prefix
    ids = [eng.submit(t, allowed_tokens=(5, 9))
           for t in (hit_req, miss1, miss2)]
    eng.run_until_drained()
    assert eng.packed_steps == 1
    assert eng.packed_hit_requests == 1
    assert eng.results[ids[0]]["n_cached"] == 64
    assert eng.results[ids[1]]["n_cached"] == 0
    cold = PrefillOnlyEngine(cfg, params,
                             EngineConfig(max_pack_requests=1,
                                          cache_capacity_tokens=0))
    ids2 = [cold.submit(t, allowed_tokens=(5, 9))
            for t in (hit_req, miss1, miss2)]
    cold.run_until_drained()
    for i, j in zip(ids, ids2):
        a, b = eng.results[i]["scores"], cold.results[j]["scores"]
        for t in a:
            assert abs(a[t] - b[t]) < 2e-2
    # the hit's chain extended past the prefix, and the misses inserted too
    from repro.core.prefix_cache import token_chain
    for t in (hit_req, miss1, miss2):
        chain = token_chain(t, eng.ecfg.block_size)
        assert eng.cache.match_len(chain) >= (len(t) // 16) * 16 - 16


def test_packed_hit_kv_insert_serves_later_hits(setup):
    """Suffix KV gathered out of a packed-hit forward must be genuine: a
    later request extending one co-packed sharer's tokens hits the deeper
    cache entry and still scores like a cold run."""
    cfg, params = setup
    rng = np.random.default_rng(13)
    profile = rng.integers(0, cfg.vocab_size, 64).tolist()
    sufs = [rng.integers(0, cfg.vocab_size, 32).tolist() for _ in range(2)]
    eng = PrefillOnlyEngine(cfg, params,
                            EngineConfig(pack_token_budget=512,
                                         prefix_bucket_blocks=2))
    eng.submit(profile)
    eng.run_until_drained()
    eng.submit(profile + sufs[0])
    eng.submit(profile + sufs[1])
    eng.run_until_drained()
    assert eng.packed_hit_requests == 2
    ext = profile + sufs[0] + rng.integers(0, cfg.vocab_size, 16).tolist()
    k = eng.submit(ext, allowed_tokens=(5, 9))
    eng.run_until_drained()
    assert eng.results[k]["n_cached"] > 64      # hit past the shared prefix
    cold = PrefillOnlyEngine(cfg, params,
                             EngineConfig(max_pack_requests=1,
                                          cache_capacity_tokens=0))
    j = cold.submit(ext, allowed_tokens=(5, 9))
    cold.run_until_drained()
    for t in cold.results[j]["scores"]:
        assert abs(cold.results[j]["scores"][t]
                   - eng.results[k]["scores"][t]) < 2e-2


def test_cost_model_rejects_bucket_tipping_candidate(setup):
    """A candidate that tips the packed forward into the next bucket while
    saving no step overhead must be left for a sequential run."""
    cfg, params = setup
    eng = PrefillOnlyEngine(cfg, params, EngineConfig(
        pack_token_budget=4096, max_pack_requests=8, lam=0.0))
    eng.jct_model.a, eng.jct_model.b = 1.0, 0.0    # zero per-step overhead
    eng.jct_model.refit_every = 10**9
    rng = np.random.default_rng(14)
    r1 = eng.submit(rng.integers(0, cfg.vocab_size, 60).tolist())
    r2 = eng.submit(rng.integers(0, cfg.vocab_size, 60).tolist())
    eng.step()
    # bucket(120) = 128 = bucket(60) + bucket(60): tie admits -> packed
    assert eng.packed_requests == 2
    eng.run_until_drained()
    r3 = eng.submit(rng.integers(0, cfg.vocab_size, 60).tolist())
    r4 = eng.submit(rng.integers(0, cfg.vocab_size, 80).tolist())
    eng.step()
    # anchor 60 + cand 80 -> bucket(140) = 256 > bucket(60)+bucket(80) = 192
    # with b = 0: packing strictly loses, candidate must be rejected
    assert eng.packed_steps == 1                   # no second packed step
    assert (r3 in eng.results) != (r4 in eng.results)
    eng.run_until_drained()


def test_long_prefix_candidate_does_not_inflate_batch_pmax(setup):
    """A hit candidate whose cached prefix dwarfs the batch's computed work
    must NOT co-pack: the batched hit forward pads EVERY row's prefix
    attention to the batch max, a cost the token-linear JCT fit can't see."""
    cfg, params = setup
    rng = np.random.default_rng(17)
    small = rng.integers(0, cfg.vocab_size, 64).tolist()      # 64-tok prefix
    big = rng.integers(0, cfg.vocab_size, 640).tolist()       # 640-tok prefix
    eng = PrefillOnlyEngine(cfg, params, EngineConfig(
        pack_token_budget=512, pack_prefix_budget=10**6,
        cache_capacity_tokens=32768))
    eng.submit(small)
    eng.submit(big)
    eng.run_until_drained()                    # warm both prefixes
    a = eng.submit(small + rng.integers(0, cfg.vocab_size, 20).tolist())
    b = eng.submit(small + rng.integers(0, cfg.vocab_size, 24).tolist())
    c = eng.submit(big + rng.integers(0, cfg.vocab_size, 20).tolist())
    eng.run_until_drained()
    # the two small-prefix hits co-pack; the 640-token-prefix hit runs
    # alone — admitting it would raise pmax to 1024 for every row, and the
    # shape model's marginal price for that padding exceeds its solo cost
    # (the priced rule that replaced the old pb > 2*pmax_b heuristic)
    assert eng.packed_steps == 1
    assert eng.packed_hit_requests == 2
    assert a in eng.results and b in eng.results and c in eng.results
    assert eng.results[c]["n_cached"] >= 576


def test_jct_observes_computed_tokens_on_hit_path(setup):
    """Packed-hit steps must calibrate on COMPUTED (suffix) tokens, not the
    total packed token count — a hit's cached prefix costs ~nothing."""
    cfg, params = setup
    rng = np.random.default_rng(15)
    profile = rng.integers(0, cfg.vocab_size, 128).tolist()
    eng = PrefillOnlyEngine(cfg, params, EngineConfig(pack_token_budget=512))
    eng.jct_model.refit_every = 10**9              # inspect raw samples
    eng.submit(profile)
    eng.run_until_drained()
    sufs = [rng.integers(0, cfg.vocab_size, 24).tolist() for _ in range(2)]
    # rep 0 compiles the insert-path shape, rep 1 the resident-fast-path
    # shape (K=0 — nothing left to insert); rep 2 is warm and observes
    for rep in range(3):
        for s in sufs:
            eng.submit(profile + s)
        eng.run_until_drained()
    assert eng.packed_hit_requests >= 2
    assert eng.jct_model._recent, "warm packed step must observe"
    n_obs, cached_obs, _ = eng.jct_model._recent[-1]
    # 2 suffixes of (128+24) - 128 cached = 24+24 computed tokens
    assert n_obs == 48 and cached_obs == 0


def test_probes_are_hit_aware(setup):
    """predict_jct / pending_jct must predict against the bucketed USABLE
    prefix (what a forward actually reuses), not the raw token match."""
    cfg, params = setup
    from repro.core.prefix_cache import token_chain
    eng = PrefillOnlyEngine(cfg, params, EngineConfig())
    eng.jct_model.a, eng.jct_model.b = 1e-3, 0.0
    eng.jct_model.refit_every = 10**9
    rng = np.random.default_rng(16)
    toks = rng.integers(0, cfg.vocab_size, 80).tolist()
    eng.submit(toks)
    eng.run_until_drained()
    chain = token_chain(toks + [1] * 40, eng.ecfg.block_size)
    # raw match = 64 tokens (4 blocks); usable (gran 4 blocks) = 64 -> same
    assert eng.predict_jct(120, chain) == pytest.approx(1e-3 * (120 - 64))
    # raw match on the request ITSELF would consume every token; usable
    # prefix backs off so the last token's logits are still computed
    own = token_chain(toks, eng.ecfg.block_size)
    assert eng.predict_jct(80, own) == pytest.approx(
        1e-3 * (80 - 64))                          # not a * 0
    # pending_jct applies the same arithmetic to the arrival-time match
    eng.submit(toks)
    assert eng.pending_jct(now=0.0) == pytest.approx(1e-3 * (80 - 64))
    eng.queue.clear()
