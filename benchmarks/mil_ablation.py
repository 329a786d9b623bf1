"""Fig 10: how each PrefillOnly ingredient moves MIL (Qwen-32B-fp8-on-A100 in
the paper; llama3.1-8b-fp8-on-v5e here).

Steps: paged -> +KV discard (naive, §2.6: marginal) -> +hybrid chunking ->
+output-preallocation/in-place (§4.3).
"""
from __future__ import annotations

import dataclasses

from repro.configs import get_config
from repro.core.kv_policy import MemoryModel
from repro.runtime.hw import TPU_V5E

ARCH = "llama3.1-8b"


def run(emit):
    cfg = get_config(ARCH)
    naive = MemoryModel(cfg, TPU_V5E, weight_bytes_per_param=1.0,
                        output_prealloc=False, inplace=False)
    opt = MemoryModel(cfg, TPU_V5E, weight_bytes_per_param=1.0)
    steps = [
        ("paged_baseline", naive.max_input_length("paged")),
        ("+kv_discard", naive.max_input_length("discard")),
        ("+hybrid_chunking", naive.max_input_length("hybrid")),
        ("+prealloc_inplace", opt.max_input_length("hybrid")),
    ]
    base = max(steps[0][1], 1)
    for name, mil in steps:
        emit(f"mil_ablation/{name}", 0.0, f"MIL={mil} gain={mil/base:.2f}x")
    return steps
