"""The padded solo forward computes nothing past the prompt's last token:
``blocked_attention`` skips query tiles that start past ``q_valid`` real
rows, ``chunked_map`` skips chunks that start past ``n_valid``, and the
engine's steps serve and cache exactly what the full padded forward gives.
``BatchRecord.skipped_tokens`` counts the skipped slots."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.configs.base import ModelConfig
from repro.core.engine import EngineConfig, PrefillOnlyEngine
from repro.core.hybrid_prefill import chunked_map, skipped_slots
from repro.models import hybrid, transformer as tfm
from repro.models.layers import blocked_attention
from repro.models.model import build
from repro.runtime.sharding import materialize
from repro.serving.tracing import BatchRecord, SpanTracer

QB = KB = 16


@pytest.mark.parametrize("q_offset", [0, 40])
@pytest.mark.parametrize("q_valid", [20, 32, 64])
@pytest.mark.parametrize("head_scale", [None, 1 / 64])
def test_attention_skips_query_tiles_past_the_prompt(q_offset, q_valid,
                                                     head_scale):
    """64 query rows in 16-row tiles, after ``q_offset`` prefix keys: 20
    real rows leave a partly valid tile and two dead ones, 32 a tile
    boundary, 64 every row. Real rows read as without ``q_valid``; rows of
    dead tiles are zeros."""
    Sq = 64
    ks = jax.random.split(jax.random.PRNGKey(q_offset + q_valid), 3)
    q = jax.random.normal(ks[0], (2, Sq, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, q_offset + Sq, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, q_offset + Sq, 2, 16), jnp.float32)
    kw = dict(q_offset=q_offset, q_block=QB, kv_block=KB,
              head_scale=head_scale)
    want = blocked_attention(q, k, v, **kw)
    got = jax.jit(lambda n: blocked_attention(q, k, v, q_valid=n, **kw))(
        jnp.int32(q_valid))
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got[:, :q_valid], want[:, :q_valid],
                               rtol=1e-6, atol=1e-7)
    assert np.isfinite(got).all()
    live = -(-q_valid // QB) * QB
    assert not got[:, live:].any()


@pytest.mark.parametrize("n_valid,calls", [(1, 1), (16, 1), (17, 2),
                                           (40, 3), (64, 4)])
def test_chunked_map_skips_chunks_past_n_valid(n_valid, calls):
    """Chunks of 16 over 64 rows: only the chunks that start before
    ``n_valid`` call ``fn`` (a host callback counts them), their rows read
    as the unchunked map's, and the rest are the zeros ``skipped_slots``
    counts."""
    x = jax.random.normal(jax.random.PRNGKey(n_valid), (2, 64, 8))
    w = jax.random.normal(jax.random.PRNGKey(7), (8, 5))
    ran = []

    def fn(c):
        jax.debug.callback(lambda: ran.append(1))
        return jnp.tanh(c @ w) + 2.0

    got = jax.jit(lambda n: chunked_map(fn, x, 16, n_valid=n))(
        jnp.int32(n_valid))
    jax.effects_barrier()
    assert len(ran) == calls
    live = 64 - skipped_slots(64, n_valid, 16)
    assert live == 16 * calls
    got = np.asarray(got)
    np.testing.assert_allclose(got[:, :live], np.tanh(x @ w)[:, :live] + 2.0,
                               rtol=1e-6)
    assert not got[:, live:].any()


@pytest.mark.parametrize("S,n_valid,chunk,skipped", [
    (16, 5, 16, 0),           # S at the chunk: one chunk, never skipped
    (8, 5, 16, 0),            # S under the chunk
    (64, 20, 16, 32),         # over: two chunks of 16 past 32
    (64, 64, 16, 0),          # every row real
    (50, 10, 16, 34),         # S no multiple of the chunk
    (64, 20, 0, 0),           # chunking off
    (32768, 16400, 2048, 14336),
])
def test_skipped_slots_arithmetic(S, n_valid, chunk, skipped):
    assert skipped_slots(S, n_valid, chunk) == skipped


def _dense():
    cfg = reduce_config(get_config("qwen1.5-0.5b"), hybrid_chunk=128)
    params = materialize(jax.random.PRNGKey(0), build(cfg).defs(),
                         jnp.float32)
    return cfg, params, dict(block_size=16, prefix_bucket_blocks=4)


def _hybrid():
    cfg = ModelConfig(
        name="tiny-hybrid", family="hybrid", num_layers=10, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
        ssm_state=16, ssm_headdim=16, ssm_chunk=8,
        layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, attention_multiplier=1 / 16,
        position_embedding="nope", norm_eps=1e-5, hybrid_chunk=128,
        dtype="float32", param_dtype="float32")
    params = materialize(jax.random.PRNGKey(0), hybrid.model_defs(cfg),
                         jnp.float32)
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                              a.shape), params)
    return cfg, params, dict(block_size=4, prefix_bucket_blocks=4)


def _serve(cfg, params, eng_kw, prompts):
    eng = PrefillOnlyEngine(cfg, params, EngineConfig(
        max_pack_requests=1, autotune_pack=False, suffix_buckets=(64, 1024),
        cache_capacity_tokens=8000, **eng_kw))
    ids = []
    for p in prompts:
        ids.append(eng.submit(p, allowed_tokens=(3, 7, 11)))
        eng.run_until_drained()
    return eng, [eng.results[i] for i in ids]


@pytest.mark.parametrize("make", [_dense, _hybrid], ids=["dense", "hybrid"])
def test_a_padded_prompt_serves_as_the_full_forward(make, monkeypatch):
    """A 400-token miss in the 1,024 bucket, then a hit whose suffix of 72
    or 80 tokens pads to 1,024 too: scores, cached KV blocks and (hybrid)
    snapshots equal those of the forward that computes every padded row."""
    cfg, params, eng_kw = make()
    rng = np.random.default_rng(4)
    base = rng.integers(0, cfg.vocab_size, 400).tolist()
    hit = base[:330] + rng.integers(0, cfg.vocab_size, 60).tolist()
    eng, got = _serve(cfg, params, eng_kw, [base, hit])
    with monkeypatch.context() as m:
        m.setattr(tfm, "valid_rows", lambda last_index: None)
        m.setattr(hybrid, "valid_rows", lambda last_index: None)
        ref, want = _serve(cfg, params, eng_kw, [base, hit])
    recs = eng.batch_records
    assert [r.jit_path for r in recs] == ["fresh", "suffix"]
    assert [r.S for r in recs] == [1024, 1024]
    suffix = 390 - got[1]["n_cached"]
    assert [r.skipped_tokens for r in recs] == [
        1024 - 512, 1024 - -(-suffix // 128) * 128]
    for g, w in zip(got, want):
        assert g["n_cached"] == w["n_cached"]
        assert g["scores"] == w["scores"]
    assert eng.cache.blocks.keys() == ref.cache.blocks.keys()
    if cfg.layer_types:
        assert any(len(b.payload) == 4 for b in eng.cache.blocks.values())
    for h, blk in ref.cache.blocks.items():
        mine = eng.cache.blocks[h].payload
        assert len(mine) == len(blk.payload)
        for a, b in zip(mine, blk.payload):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_skipped_tokens_cross_the_exporters_and_the_replay():
    """The counter rides in ``to_dict`` (JSONL rows), the Perfetto step
    args and the process-mode replay of a worker's records."""
    from repro.serving.supervisor import RemoteEngine
    rec = BatchRecord(step=3, ts=10.0, kind="solo", req_ids=(5,),
                      padded_tokens=32768, skipped_tokens=14336, wall=0.5)
    assert rec.to_dict()["skipped_tokens"] == 14336
    tracer = SpanTracer()
    tracer.record_batch(rec)
    rows = [json.loads(ln) for ln in tracer.dump_jsonl().splitlines()]
    assert [r["skipped_tokens"] for r in rows if r["type"] == "batch"] == [
        14336]
    args = [e["args"] for e in tracer.chrome_trace()["traceEvents"]
            if e["name"].startswith("step ")]
    assert [a["skipped_tokens"] for a in args] == [14336]
    front = RemoteEngine("w0", client=None)
    replayed = SpanTracer()
    front.bind_telemetry(tracer=replayed)
    front._replay_telemetry({"batches": [rec.to_dict()]}, off=0.0)
    (back,) = replayed.drain_batches()
    assert back.skipped_tokens == 14336 and back.instance == "w0"
