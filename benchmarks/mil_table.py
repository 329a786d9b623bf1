"""Table 2 analog: maximum input length (MIL) per technique on TPU v5e-16GB.

The paper's table covers L4/A100/H100 x {PagedAttention, chunked prefill,
PP-2, TP-2, PrefillOnly}; our hardware rows are v5e with bf16 and fp8
weights. WL1 = post recommendation (max ~19k tokens), WL2 = credit
verification (max 60k tokens); ✗ = workload infeasible for that engine.
"""
from __future__ import annotations

from repro.configs import get_config
from repro.core.kv_policy import MemoryModel
from repro.runtime.hw import TPU_V5E

WL1_MAX = 19_000
WL2_MAX = 60_000

TECHS = ("paged", "chunked", "pp", "tp", "hybrid")
LABEL = {"paged": "PagedAttention", "chunked": "Chunked Prefill",
         "pp": "Pipeline Parallel-2", "tp": "Tensor Parallel-2",
         "hybrid": "PrefillOnly (ours)", "discard": "naive KV discard"}


def run(emit):
    rows = []
    for arch, wbytes in (("llama3.1-8b", 1.0), ("llama3.1-8b", 2.0),
                         ("qwen1.5-0.5b", 2.0), ("granite-3-8b", 1.0)):
        cfg = get_config(arch)
        mm = MemoryModel(cfg, TPU_V5E, weight_bytes_per_param=wbytes)
        mil = mm.mil_table()
        for t in TECHS:
            wl1 = "Y" if mil[t] >= WL1_MAX else "x"
            wl2 = "Y" if mil[t] >= WL2_MAX else "x"
            name = f"mil/{arch}-{'fp8' if wbytes == 1 else 'bf16'}/{t}"
            emit(name, 0.0, f"MIL={mil[t]} WL1={wl1} WL2={wl2}")
            rows.append((arch, wbytes, t, mil[t], wl1, wl2))
        ours, paged = mil["hybrid"], max(mil["paged"], 1)
        emit(f"mil/{arch}-{'fp8' if wbytes == 1 else 'bf16'}/gain",
             0.0, f"hybrid_vs_paged={ours / paged:.1f}x")
    return rows
