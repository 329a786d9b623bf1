"""The hybrid architecture file (``arch/granite_hybrid.py``) against the
program, on the CPU at a tiny size in the published layer pattern (10
layers, d_model 64, d_state 16, chunk 8): its parameter tree is the
program's; its chunked scan is the token-by-token recurrence; the program's
scores, served through the engine and its cache, match its reference for a
fresh prompt, for a hit resumed from each snapshot boundary, and for hits
after the snapshot-bearing tail was evicted, while the fp8 control does
not; and a tiny hybrid cell runs correct through the harness and reports
the state metrics."""
import json
import math
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny as tiny
import check
import harness
import trace_reduce
from registry import Registry
from repro.configs.base import ModelConfig
from repro.core.engine import EngineConfig, PrefillOnlyEngine
from repro.core.prefix_cache import token_chain
from repro.models import hybrid
from repro.runtime.sharding import abstract_params

CHIP = Path(__file__).resolve().parents[1]
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
MODEL = {"name": "tiny-hybrid", "family": "hybrid", "num_layers": 10,
         "d_model": 64, "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
         "d_ff": 128, "vocab_size": 512, "ssm_state": 16, "ssm_expand": 2,
         "ssm_headdim": 16, "ssm_conv_width": 4, "ssm_chunk": 8,
         "layer_types": PERIOD, "embedding_multiplier": 12.0,
         "residual_multiplier": 0.22, "logits_scaling": 8.0,
         "attention_multiplier": 0.0625, "position_embedding": "nope",
         "norm_eps": 1e-5, "tie_embeddings": True, "dtype": "bfloat16",
         "param_dtype": "bfloat16", "hybrid_chunk": 0}
WEIGHTS = {"embed_std": 0.1, "norm_std": 0.1, "conv_std": 0.29,
           "bias_std": 0.29}
CFG = {"name": "tiny-hybrid", "model": MODEL, "arch": "granite_hybrid",
       "weights": WEIGHTS, "cache_tokens": 5600,
       "engine": {"block_size": 4, "prefix_bucket_blocks": 16,
                  "max_pack_requests": 1, "autotune_pack": False},
       "server": dict(tiny.CONFIG["server"])}
SEED = 2 ** 33 + 5
LABELS = [3, 41, 77, 150, 301, 499]
# root mean square of the log-odds error over the compared requests: bf16
# weights and activations read 0.00114 to 0.00178 here, the fp8 control
# 0.00634 to 0.01032 (seeds 2**33 + 5 to + 8, CPU); the limit lies between
LIMIT = 0.004
CELL = "tiny-hybrid.rec"
STATE_METRICS = ("state_insert_ms.fresh", "idle_in_state_insert.offline",
                 "state_recompute_share")


@pytest.fixture(scope="module")
def arch(tmp_path_factory):
    base = tiny.layout(tmp_path_factory.mktemp("hybrid"))
    return Registry(tiny.bench(), base).arch("granite_hybrid")


def test_make_params_is_the_program_tree(arch):
    cfg = ModelConfig(**MODEL)
    want = abstract_params(hybrid.model_defs(cfg), jnp.bfloat16)
    got = arch.make_params(MODEL, WEIGHTS, SEED, "bfloat16")
    shape = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)
    assert shape(got) == shape(want)
    # A = -exp(A_log) in [-16, -1]; dt = softplus(dt_bias) in [1e-3, 0.1]
    a = np.exp(np.asarray(got["mamba_layers"]["mamba"]["A_log"], np.float32))
    dt = np.log1p(np.exp(np.asarray(got["mamba_layers"]["mamba"]["dt_bias"],
                                    np.float32)))
    assert 0.99 <= a.min() and a.max() <= 16.1
    assert 0.99e-3 <= dt.min() and dt.max() <= 0.101


def test_the_chunked_scan_is_the_token_recurrence(arch):
    rng = np.random.default_rng(0)
    T, H, P, N = 256, 4, 8, 16
    x = jnp.asarray(rng.normal(size=(T, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.3, size=(T, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(T, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(T, N)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y1, h1 = arch.ssd_tokens(x, dt, A, Bm, Cm)
        y2, h2 = arch.ssd_chunked(x, dt, A, Bm, Cm, chunk=64)
    # float32 sums of O(1) terms in two orders: 1e-4 is rounding; a decay
    # applied one token late or a dropped state term is off by O(0.1)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h1), rtol=1e-4,
                               atol=1e-4)


def _serve(arch, seed):
    """A fresh 70-token prompt, hits that resume from each snapshot
    boundary (16, 32, 48, 64), then, with the blocks past token 40 evicted,
    a hit that falls back to the boundary at 32, and, with that boundary's
    snapshot gone too, one that falls back to 16."""
    eng = PrefillOnlyEngine(ModelConfig(**MODEL),
                            arch.make_params(MODEL, WEIGHTS, seed,
                                             "bfloat16"),
                            EngineConfig(block_size=4, prefix_bucket_blocks=4,
                                         max_pack_requests=1,
                                         cache_capacity_tokens=8000,
                                         suffix_buckets=(16, 32, 64, 128)))
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 512, 70).tolist()
    new = lambda n: rng.integers(0, 512, n).tolist()
    prompts = [base] + [base[:b + 3] + new(9) for b in (16, 32, 48, 64)]
    served = []
    for p in prompts:
        rid = eng.submit(p, allowed_tokens=LABELS)
        eng.run_until_drained()
        served.append(eng.results[rid])
    chain = token_chain(base, 4)

    def hit(n_new):
        prompts.append(base[:60] + new(n_new))
        rid = eng.submit(prompts[-1], allowed_tokens=LABELS)
        eng.run_until_drained()
        served.append(eng.results[rid])

    def evict_past_40():                    # LRU leaves until token 40 goes
        while chain[10] in eng.cache.blocks:
            assert eng.cache._evict_one()

    evict_past_40()
    hit(5)
    evict_past_40()
    blk = eng.cache.blocks[chain[7]]        # the block that ends at 32
    blk.payload = blk.payload[:2]           # its snapshot gone
    hit(6)
    return prompts, served


@pytest.mark.parametrize("seed", [SEED, SEED + 1])
def test_the_program_matches_the_reference_through_the_cache(arch, seed):
    prompts, served = _serve(arch, seed)
    assert [s["n_cached"] for s in served] == [0, 16, 32, 48, 64, 32, 16]
    cfg = {"model": MODEL, "weights": WEIGHTS}
    ref = arch.label_logits(cfg, seed, prompts, LABELS)
    ctl = arch.label_logits(cfg, seed, prompts, LABELS, quant="fp8")
    prog = check.summary([check.served_numbers(s["scores"], s["token"],
                                               LABELS, r)
                          for s, r in zip(served, ref)])
    fp8 = check.summary([check.control_numbers(c, r)
                         for c, r in zip(ctl, ref)])
    assert prog["logodds_rms"] < LIMIT < fp8["logodds_rms"], (prog, fp8)


def test_request_flops_counts_the_pattern(arch):
    """The second computed token adds each layer's token-wise matmuls and
    conv once, the SSD's state read and carry, its causal pairs within the
    chunk (1 -> 3) and attention's (1 -> 3); a cached prefix adds only
    attention over it."""
    D, F, di, N, Hs, P, W, H, KV, hd = 64, 128, 128, 16, 8, 16, 4, 4, 2, 16
    mamba = (2 * (D * (2 * di + 2 * N + Hs) + di * D) + 2 * W * (di + 2 * N)
             + 4 * Hs * P * N + 2 * (N + Hs * P) * 2)
    attn = 2 * (2 * D * H * hd + 2 * D * KV * hd) + 4 * H * hd * 2
    mlp = 2 * 3 * D * F
    step = arch.request_flops(MODEL, 2, 0) - arch.request_flops(MODEL, 1, 0)
    assert step == 9 * mamba + attn + 10 * mlp
    assert (arch.request_flops(MODEL, 1, 100) - arch.request_flops(MODEL, 1, 0)
            == 4 * H * hd * 100)


def test_a_tiny_hybrid_cell_runs_correct_with_state_metrics(tmp_path):
    base = tiny.layout(tmp_path)
    (base / "configs" / "tiny-hybrid.json").write_text(json.dumps(CFG))
    (base / "limits" / f"{CELL}.json").write_text(
        json.dumps({"logodds_rms": LIMIT}))
    per_layer = [{"name": n, "unit": "%", "better": "lower",
                  "source": "program_counter", "layer": "KV tiers",
                  "moves": "prompt_tokens_per_s", "workloads": [CELL]}
                 for n in STATE_METRICS + ("prefix_hit_share",)]
    reg = Registry(tiny.bench(cells=[(CELL, "tiny-hybrid", "tiny_rec")],
                              per_layer=per_layer), base)
    res = harness.run(base, CELL, SEED, 1.0, True, time.perf_counter(),
                      require_chip=False, registry=reg, log=lambda *a: None)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    # the CPU's trace has no device plane: the device-trace metric finds
    # nothing here (the next test reads it from a recorded partition)
    assert "idle_in_state_insert.offline" not in res["metrics"]
    for name in ("state_insert_ms.fresh", "state_recompute_share"):
        v = res["metrics"][name]["value"]
        assert math.isfinite(v) and v >= 0, name
    assert res["metrics"]["prefix_hit_share"]["value"] > 0


def test_idle_in_state_insert_reads_its_span():
    """Two steps of 80 ns on a TPU plane: 20 ns idle under
    ``engine.state_insert``, 6 under ``engine.kv_insert``."""
    plane = lambda name, lines: {"name": name, "lines": [
        {"name": n, "events": ev} for n, ev in lines]}
    planes = [
        plane("/host:CPU", [
            ("main", [("bench_window", 0, 200)]),
            ("engine", [("bench_step", 10, 80), ("engine.step", 11, 78),
                        ("engine.kv_insert", 62, 6),
                        ("bench_step", 110, 80), ("engine.step", 111, 78),
                        ("engine.state_insert", 130, 20)])]),
        plane("/device:TPU:0", [
            ("XLA Ops", [("fusion.1", 15, 47), ("fusion.2", 68, 22),
                         ("fusion.3", 110, 20), ("fusion.4", 150, 40)]),
            ("XLA Modules", [("jit_a", 15, 75), ("jit_b", 110, 20),
                             ("jit_c", 150, 40)])]),
    ]
    red = trace_reduce.reduce(planes)
    read = Registry({}, CHIP).metric("idle_in_state_insert.offline")
    assert read(SimpleNamespace(trace=red)) == pytest.approx(100 * 20 / 160)
    assert read(SimpleNamespace(trace=None)) is None
