"""Architecture registry: ``--arch <id>`` resolution + reduced smoke configs."""
from __future__ import annotations

import dataclasses
from typing import Dict, List

from repro.configs.base import ModelConfig

from repro.configs.internvl2_2b import CONFIG as _internvl2
from repro.configs.qwen1_5_0_5b import CONFIG as _qwen
from repro.configs.phi3_mini_3_8b import CONFIG as _phi3
from repro.configs.gemma2_9b import CONFIG as _gemma2
from repro.configs.granite_3_8b import CONFIG as _granite
from repro.configs.mamba2_130m import CONFIG as _mamba2
from repro.configs.musicgen_large import CONFIG as _musicgen
from repro.configs.zamba2_2_7b import CONFIG as _zamba2
from repro.configs.mixtral_8x22b import CONFIG as _mixtral
from repro.configs.llama4_scout_17b_a16e import CONFIG as _llama4
from repro.configs.llama3_1_8b import CONFIG as _llama31
from repro.configs.granite_4_0_h_micro import CONFIG as _granite4h

ASSIGNED: Dict[str, ModelConfig] = {
    "internvl2-2b": _internvl2,
    "qwen1.5-0.5b": _qwen,
    "phi3-mini-3.8b": _phi3,
    "gemma2-9b": _gemma2,
    "granite-3-8b": _granite,
    "mamba2-130m": _mamba2,
    "musicgen-large": _musicgen,
    "zamba2-2.7b": _zamba2,
    "mixtral-8x22b": _mixtral,
    "llama4-scout-17b-a16e": _llama4,
}

EXTRA: Dict[str, ModelConfig] = {
    "llama3.1-8b": _llama31,
    "granite-4.0-h-micro": _granite4h,
}

REGISTRY: Dict[str, ModelConfig] = {**ASSIGNED, **EXTRA}


def get_config(arch: str) -> ModelConfig:
    if arch not in REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(REGISTRY)}")
    return REGISTRY[arch]


def list_archs(assigned_only: bool = True) -> List[str]:
    return sorted(ASSIGNED if assigned_only else REGISTRY)


def serving_config(arch: str, published_widths: bool = False) -> ModelConfig:
    """The config an entry point serves ``arch`` with: its published widths
    (hybrid prefilling on, as configured), or the reduced CPU preset with
    hybrid prefilling off. The one owner of that choice."""
    cfg = get_config(arch)
    return cfg if published_widths else reduce_config(cfg, hybrid_chunk=0)


def reduce_config(cfg: ModelConfig, **overrides) -> ModelConfig:
    """Shrink a config to a CPU-smoke-testable size, same family/features.

    Keeps every structural feature (GQA ratio, softcaps, SWA, MoE top-k, SSD
    state) while cutting width/depth/vocab so a forward+train step runs on one
    CPU core in seconds.
    """
    small = dict(
        num_layers=min(cfg.num_layers, 4),
        d_model=128,
        vocab_size=min(cfg.vocab_size, 512),
        hybrid_chunk=32,
        logits_chunk=64,
        ssm_chunk=16,
    )
    if cfg.num_heads:
        small["num_heads"] = 4
        small["num_kv_heads"] = max(1, 4 * cfg.num_kv_heads // cfg.num_heads)
        small["head_dim"] = 32
    if cfg.d_ff:
        small["d_ff"] = 256
    if cfg.sliding_window:
        small["sliding_window"] = 16
    if cfg.is_moe:
        small["num_experts"] = min(cfg.num_experts, 4)
        small["num_experts_per_tok"] = min(cfg.num_experts_per_tok, 2)
    if cfg.has_ssm:
        small["ssm_state"] = 16
        small["ssm_headdim"] = 16
    if cfg.attn_every:
        small["attn_every"] = 2
    if cfg.local_global:
        small["num_layers"] = 4  # two (local, global) pairs
    if cfg.layer_types:                  # one whole period of the pattern
        small["num_layers"] = cfg.pattern_period
        small["layer_types"] = cfg.layer_types[:cfg.pattern_period]
    small.update(overrides)
    small["name"] = cfg.name + "-smoke"
    return dataclasses.replace(cfg, **small)
