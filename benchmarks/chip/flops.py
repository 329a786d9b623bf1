"""Operations a scored request needs, from the configuration's widths.

Counts what the algorithm needs, not what the program computes: no padding,
no recomputation, no LM-head rows beyond the one scored position. A request
with ``c`` computed tokens over ``p`` cached prefix tokens needs

    2 * N * c                          token-wise matmuls (N = non-embedding
                                       matmul parameters)
    + 2 * D * V                        one LM-head row
    + L * 4 * H * hd * (c*p + c*(c+1)/2)   attention scores and values, causal
"""
from __future__ import annotations

from typing import Dict


def matmul_params_per_layer(m: Dict) -> int:
    D, F = m["d_model"], m["d_ff"]
    H, KV = m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or D // H
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def request_flops(m: Dict, computed: int, cached: int) -> float:
    """FLOPs one scored request needs (``m`` is the configuration's model
    block)."""
    D, V, L, H = m["d_model"], m["vocab_size"], m["num_layers"], m["num_heads"]
    hd = m.get("head_dim") or D // H
    c, p = int(computed), int(cached)
    linear = 2.0 * L * matmul_params_per_layer(m) * c
    head = 2.0 * D * V
    attn = L * 4.0 * H * hd * (c * p + c * (c + 1) / 2.0)
    return linear + head + attn
