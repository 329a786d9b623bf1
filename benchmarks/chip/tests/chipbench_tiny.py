"""A tiny cell for CPU tests: a 2-layer dense configuration at d_model 64 and
a post-recommendation-shaped mix with 3 users, in a directory laid out as
``benchmarks/chip`` is (configs/, traffic/, limits/, metrics/, arch/)."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]

MODEL = {"name": "tiny", "family": "dense", "num_layers": 2, "d_model": 64,
         "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
         "vocab_size": 512, "qkv_bias": True, "rope_theta": 10000.0,
         "tie_embeddings": True, "dtype": "bfloat16",
         "param_dtype": "bfloat16", "hybrid_chunk": 0}

CONFIG = {"name": "tiny", "model": MODEL, "rms_norm_eps": 1e-6,
          "arch": "dense",
          "weights": {"embed_std": 0.25, "norm_std": 0.1, "qk_gain": 1.7,
                      "bias_std": 0.1},
          "cache_tokens": 600, "engine": {},
          "server": {"router": "least_backlog", "retry_budget": 2,
                     "watchdog_factor": 4.0, "watchdog_min_deadline": 30.0,
                     "profile_lengths": [32, 64]}}

REC = {"loop": {"kind": "closed", "outstanding": 4},
       "groups": {"count": 3, "prefix_tokens": {
           "kind": "normal", "mean": 260, "std": 40, "min": 200,
           "max": 320}},
       "body_tokens": {"kind": "uniform", "min": 12, "max": 30},
       "labels": 2, "pattern_seed": 0, "warm_requests": 0,
       "check_requests": 6}

CELL = "tiny.rec"

# set from CPU readings at this size, as the chip cells' limits are: the
# program's root mean square log-odds error 0.010 to 0.025; the fp8
# control's 0.19 to 0.51; the planted faults' 0.28 to 1.99
LIMITS = {"logodds_rms": 0.08}


def bench(cells=((CELL, "tiny", "tiny_rec"),), per_layer=(),
          chips: int = 1) -> dict:
    return {
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": chips,
                       "why": "test"} for n, c, t in cells],
        "end_to_end": [
            {"name": "scored_rps", "unit": "req/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "prompt_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": list(per_layer),
    }


def layout(base: Path) -> Path:
    """Write the tiny cell's files under ``base``, with the benchmark's own
    metric readers and architectures beside them."""
    for d in ("configs", "traffic", "limits"):
        (base / d).mkdir(parents=True, exist_ok=True)
    for d in ("metrics", "arch"):
        shutil.copytree(CHIP / d, base / d, dirs_exist_ok=True)
    (base / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (base / "traffic" / "tiny_rec.json").write_text(json.dumps(REC))
    (base / "limits" / f"{CELL}.json").write_text(json.dumps(LIMITS))
    return base
