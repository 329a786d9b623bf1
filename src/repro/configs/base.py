"""Model / workload configuration for the PrefillOnly reproduction.

Every assigned architecture is expressed as a ``ModelConfig``. The config is a
frozen dataclass so it can be hashed into jit caches and closed over safely.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters (decoder-only LM backbone).

    ``family`` drives block selection:
      dense   - transformer blocks (attention + SwiGLU MLP)
      moe     - transformer blocks with mixture-of-experts MLP
      ssm     - Mamba2 (SSD) blocks, attention-free
      hybrid  - Mamba2 backbone + shared attention block every ``attn_every``
                (Zamba-2), or, with ``layer_types``, a published per-layer
                pattern of Mamba2 and attention mixers, each layer followed
                by its own MLP (GraniteMoeHybrid)
      vlm     - dense backbone fed precomputed patch embeddings (frontend stub)
      audio   - dense backbone over codec tokens (frontend stub)
    """

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                  # 0 -> d_model // num_heads

    # --- attention features ---
    rope_theta: float = 10_000.0
    qkv_bias: bool = False
    sliding_window: int = 0            # 0 = full attention
    local_global: bool = False         # gemma2: alternate local(SWA)/global
    attn_softcap: float = 0.0          # gemma2: tanh softcap on attn logits
    final_softcap: float = 0.0         # gemma2: tanh softcap on LM logits

    # --- MoE ---
    num_experts: int = 0
    num_experts_per_tok: int = 0
    shared_expert: bool = False        # llama4-style always-on expert
    capacity_factor: float = 1.25

    # --- SSM (mamba2) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_conv_width: int = 4
    ssm_chunk: int = 256               # SSD chunk length
    attn_every: int = 0                # hybrid: shared attn block cadence
    layer_types: Tuple[str, ...] = ()  # hybrid: "mamba" | "attention" per
                                       # layer, as published (GraniteMoeHybrid)

    # --- published multipliers (GraniteMoeHybrid; 1.0 / 0 = unused) ---
    embedding_multiplier: float = 1.0  # token embeddings times this
    residual_multiplier: float = 1.0   # each branch's output times this
    logits_scaling: float = 1.0        # LM logits divided by this
    attention_multiplier: float = 0.0  # score scale; 0 -> 1/sqrt(head_dim)
    position_embedding: str = "rope"   # "rope" | "nope" (no positions)
    norm_eps: float = 1e-6             # RMSNorm epsilon of the pattern
                                       # hybrid and its Mamba gated norm

    # --- embeddings / io ---
    embed_inputs: bool = True          # False: inputs arrive as embeddings (vlm)
    tie_embeddings: bool = True

    # --- execution ---
    packed_attention: bool = False     # exact-causal tile packing (perf)
    dtype: str = "bfloat16"            # activations / compute
    param_dtype: str = "bfloat16"      # stored weights (serving); train uses fp32 master
    hybrid_chunk: int = 2048           # PrefillOnly hybrid prefilling chunk (0 = off)
    remat: bool = True                 # activation checkpointing for train
    logits_chunk: int = 2048           # chunked LM-head/xent (0 = off)

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        # a configuration file gives lists; the config is hashed into jits
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.layer_types:
            if len(self.layer_types) != self.num_layers:
                raise ValueError(f"{len(self.layer_types)} layer_types for "
                                 f"{self.num_layers} layers")
            bad = set(self.layer_types) - {"mamba", "attention"}
            if bad:
                raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.position_embedding not in ("rope", "nope"):
            raise ValueError(
                f"unknown position embedding {self.position_embedding!r}")

    # ---- derived quantities ----
    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def has_attention(self) -> bool:
        return self.family != "ssm"

    @property
    def has_ssm(self) -> bool:
        return self.family in ("ssm", "hybrid")

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def n_attention_layers(self) -> int:
        """Layers that hold attention KV: the pattern's attention layers, or
        Zamba-2's applications of its shared block."""
        if self.layer_types:
            return self.layer_types.count("attention")
        if self.family == "ssm":
            return 0
        if self.family == "hybrid":
            return max(1, self.num_layers // max(self.attn_every, 1))
        return self.num_layers

    @property
    def n_mamba_layers(self) -> int:
        if self.layer_types:
            return self.layer_types.count("mamba")
        return self.num_layers if self.has_ssm else 0

    @property
    def pattern_period(self) -> int:
        """Length of the shortest repeating unit of ``layer_types``."""
        t = self.layer_types
        return next(p for p in range(1, len(t) + 1)
                    if len(t) % p == 0 and t == t[:p] * (len(t) // p))

    @property
    def attn_head_scale(self) -> Optional[float]:
        """The attention score scale, or None for 1/sqrt(head_dim)."""
        return self.attention_multiplier or None

    def param_count(self) -> int:
        """Analytic parameter count (used by roofline + MIL model)."""
        D, F, V, L = self.d_model, self.d_ff, self.vocab_size, self.num_layers
        H, KV, hd = self.num_heads, self.num_kv_heads, self.head_dim
        embed = V * D
        lm_head = 0 if self.tie_embeddings else V * D
        attn = D * (H * hd) + 2 * D * (KV * hd) + (H * hd) * D
        mlp = 3 * D * F
        if self.is_moe:
            mlp = mlp * self.num_experts + D * self.num_experts  # + router
            if self.shared_expert:
                mlp += 3 * D * F
        if self.layer_types:
            return self._pattern_param_count()
        ssm = 0
        if self.has_ssm:
            di, N, Hs = self.d_inner, self.ssm_state, self.ssm_heads
            # in_proj -> (z, x, B, C, dt), conv, A/D, norm, out_proj
            ssm = D * (2 * di + 2 * N + Hs) + self.ssm_conv_width * (di + 2 * N)
            ssm += 2 * Hs + di + di * D
        per_layer = 0
        norms = 2 * D
        if self.family == "ssm":
            per_layer = ssm + D
        elif self.family == "hybrid":
            n_attn = max(1, self.num_layers // max(self.attn_every, 1))
            per_layer = ssm + D
            # shared attention block counted once (shared weights)
            shared = attn + 3 * D * self.d_ff_shared + norms
            return embed + lm_head + L * per_layer + shared + D
        else:
            per_layer = attn + mlp + norms
        return embed + lm_head + L * per_layer + D

    def _pattern_param_count(self) -> int:
        """Exact count of the layer pattern (``models.hybrid.model_defs``):
        each layer two norms, its mixer and its MLP."""
        D, V = self.d_model, self.vocab_size
        H, KV, hd = self.num_heads, self.num_kv_heads, self.head_dim
        di, N, Hs, W = self.d_inner, self.ssm_state, self.ssm_heads, \
            self.ssm_conv_width
        conv = di + 2 * N
        mamba = (D * (2 * di + 2 * N + Hs) + (W + 1) * conv + 3 * Hs + di
                 + di * D)
        attn = D * (H * hd) + 2 * D * (KV * hd) + (H * hd) * D
        layer = 2 * D + 3 * D * self.d_ff
        lm_head = 0 if self.tie_embeddings else V * D
        return (V * D + lm_head + D + self.n_mamba_layers * (layer + mamba)
                + self.n_attention_layers * (layer + attn))

    def state_bytes(self, act_bytes: int = 2) -> int:
        """Bytes of one snapshot of the recurrent state of every Mamba
        layer: the float32 SSD state (heads x head dim x d_state) and the
        causal conv's last ``ssm_conv_width - 1`` inputs, in the activation
        type."""
        if not self.has_ssm:
            return 0
        ssd = self.ssm_heads * self.ssm_headdim * self.ssm_state * 4
        conv = (self.ssm_conv_width - 1) * (self.d_inner
                                            + 2 * self.ssm_state) * act_bytes
        return self.n_mamba_layers * (ssd + conv)

    @property
    def d_ff_shared(self) -> int:
        """FFN width of the shared attention block (hybrid family)."""
        return self.d_ff if self.d_ff else 4 * self.d_model

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only routed experts)."""
        if not self.is_moe:
            return self.param_count()
        D, F, L = self.d_model, self.d_ff, self.num_layers
        total = self.param_count()
        all_expert = L * (3 * D * F) * self.num_experts
        active_expert = L * (3 * D * F) * self.num_experts_per_tok
        return total - all_expert + active_expert

    def kv_bytes_per_token(self, bytes_per_el: int = 2) -> int:
        """KV-cache bytes per token across all layers (full attention view)."""
        return (self.n_attention_layers * 2 * self.num_kv_heads
                * self.head_dim * bytes_per_el)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """An assigned (workload) input shape."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", 4_096, 256, "train"),
    ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    ShapeConfig("decode_32k", 32_768, 128, "decode"),
    ShapeConfig("long_500k", 524_288, 1, "decode"),
)

SHAPE_BY_NAME = {s.name: s for s in SHAPES}


def shape(name: str) -> ShapeConfig:
    return SHAPE_BY_NAME[name]


def long_context_capable(cfg: ModelConfig) -> bool:
    """long_500k runs only for sub-quadratic archs (SSM / hybrid / all-SWA)."""
    if cfg.family in ("ssm", "hybrid"):
        return True
    # all-layers sliding-window attention bounds the KV working set
    if cfg.sliding_window > 0 and not cfg.local_global:
        return True
    return False


def cell_is_runnable(cfg: ModelConfig, shp: ShapeConfig) -> Tuple[bool, str]:
    """Whether an (arch x shape) dry-run cell runs, and the reason if not."""
    if shp.name == "long_500k" and not long_context_capable(cfg):
        return False, ("skip: pure full-attention arch (quadratic attention / "
                       "unbounded KV) — per assignment rules")
    return True, ""
