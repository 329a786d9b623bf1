"""Decoder-only transformer covering the dense / moe / vlm / audio families,
including gemma2's alternating local(SWA)/global attention + logit softcaps.

All models scan over layer-stacked parameters so HLO size (and therefore
compile time on this 1-core container) is independent of depth.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.hybrid_prefill import (chunked_softmax_xent,
                                       last_token_logits,
                                       packed_last_logits, valid_rows)
from repro.models import layers as L
from repro.models.moe import moe_defs, moe_apply
from repro.runtime.sharding import pdef, ParamDef, is_paramdef_leaf


# --------------------------------------------------------------------------
# parameter definitions
# --------------------------------------------------------------------------

def stack_defs(defs: Any, n: int) -> Any:
    return jax.tree_util.tree_map(
        lambda d: ParamDef((n,) + d.shape, ("layers",) + d.axes, d.init, d.scale),
        defs, is_leaf=is_paramdef_leaf)


def block_defs(cfg: ModelConfig) -> Dict:
    D = cfg.d_model
    out = {
        "ln1": pdef((D,), ("d_model",), init="zeros"),
        "ln2": pdef((D,), ("d_model",), init="zeros"),
        "attn": L.attention_defs(cfg),
    }
    if cfg.is_moe:
        out["moe"] = moe_defs(cfg)
    else:
        out["mlp"] = L.mlp_defs(D, cfg.d_ff)
    return out


def model_defs(cfg: ModelConfig) -> Dict:
    out: Dict[str, Any] = {"embed": L.embed_defs(cfg)}
    if cfg.local_global:
        half = cfg.num_layers // 2
        out["blocks_local"] = stack_defs(block_defs(cfg), half)
        out["blocks_global"] = stack_defs(block_defs(cfg), half)
    else:
        out["blocks"] = stack_defs(block_defs(cfg), cfg.num_layers)
    out["final_norm"] = pdef((cfg.d_model,), ("d_model",), init="zeros")
    if not cfg.tie_embeddings:
        out["lm_head"] = pdef((cfg.d_model, cfg.vocab_size),
                              ("d_model", "vocab"), init="scaled")
    return out


def _remat_groups(n_layers: int) -> int:
    """Largest divisor of n_layers that is <= ~sqrt(n_layers)*1.5."""
    best = 1
    limit = int(math.sqrt(n_layers) * 1.5)
    for g in range(2, n_layers):
        if n_layers % g == 0 and g <= limit:
            best = g
    return best


def head_weight(params: Dict, cfg: ModelConfig) -> jax.Array:
    w = (params["embed"]["tok"].T if cfg.tie_embeddings
         else params["lm_head"])
    dt = jnp.dtype(cfg.dtype)
    return w.astype(dt) if w.dtype != dt else w


# --------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# --------------------------------------------------------------------------

def _cast_block(bp: Dict, dtype) -> Dict:
    """Per-layer weight cast (fp8 storage -> compute dtype); no-op at bf16."""
    dt = jnp.dtype(dtype)
    return jax.tree_util.tree_map(
        lambda a: a.astype(dt) if a.dtype != dt else a, bp)


def _block_full(bp: Dict, x: jax.Array, cfg: ModelConfig, *,
                positions: jax.Array, window: int, chunk: int,
                num_shards: int, seg_ids: Optional[jax.Array] = None,
                n_valid: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    bp = _cast_block(bp, cfg.dtype)
    h = L.rms_norm(x, bp["ln1"])
    attn, k, v = L.attention_prefill(bp["attn"], h, cfg, positions=positions,
                                     window=window, chunk=chunk,
                                     seg_ids=seg_ids, n_valid=n_valid)
    x = x + attn
    h = L.rms_norm(x, bp["ln2"])
    if cfg.is_moe:
        m = moe_apply(bp["moe"], h, cfg, num_shards=num_shards,
                      hybrid_chunk=chunk)
    else:
        m = L.mlp_apply(bp["mlp"], h, chunk=chunk, n_valid=n_valid)
    return x + m, (k, v)


def forward_full(params: Dict, cfg: ModelConfig, *,
                 tokens: Optional[jax.Array] = None,
                 embeds: Optional[jax.Array] = None,
                 kv_keep: int = 0, num_shards: int = 1,
                 remat: bool = False,
                 positions: Optional[jax.Array] = None,
                 seg_ids: Optional[jax.Array] = None,
                 kv_indices: Optional[jax.Array] = None,
                 n_valid: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Optional[Dict]]:
    """Returns (final-normed hidden (B,S,D), kv tree or None).

    ``kv_keep`` is the PrefillOnly prefix budget: only the first ``kv_keep``
    tokens' KV leave each layer (suffix KV discard — the rest is freed by XLA
    as soon as the layer's attention is done, because it is not a scan
    output). This is the LAYER-WISE discard the memory hierarchy is built
    on: at any instant at most ONE layer's full-length K/V is live, so peak
    prefill memory prices one transient layer plus the kept slice —
    ``core.kv_policy.KVLifecycle`` owns the keep arithmetic callers pass in
    here, and ``MemoryModel.peak_bytes(..., kv_keep=...)`` prices exactly
    this shape.

    Prepacked prefill: ``positions`` (B, S) overrides the default arange —
    packed batches restart RoPE positions at every segment boundary — and
    ``seg_ids`` (B, S) restricts attention to same-segment pairs.
    ``kv_indices`` (K,) generalizes the prefix budget for packed batches:
    each layer's KV scan output is the GATHER of those token positions
    instead of a prefix slice, so per-segment keep windows scattered through
    the packed sequence cost K stacked tokens, not S (suffix discard keeps
    its memory bound under packing). Overrides ``kv_keep`` when given.

    ``n_valid`` (traced scalar): only the first ``n_valid`` tokens are real,
    the rest padding after the prompt. The blocked attention's query tiles
    and the token-wise chunks that start past it are not computed, so the
    hidden rows and KV there are not the padding's: zeros or stale values.
    """
    dtype = jnp.dtype(cfg.dtype)
    if embeds is None:
        x = L.embed_apply(params["embed"], tokens, dtype)
        if cfg.local_global:           # gemma-style embedding scale
            x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    else:
        x = embeds.astype(dtype)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    chunk = cfg.hybrid_chunk
    keep = min(kv_keep, S)
    if kv_indices is not None:
        keep = kv_indices.shape[0]     # drives only the kv-is-kept checks

    def run_block(x, bp, window):
        x, (k, v) = _block_full(bp, x, cfg, positions=positions,
                                window=window, chunk=chunk,
                                num_shards=num_shards, seg_ids=seg_ids,
                                n_valid=n_valid)
        # keep the prefix KV in compute dtype — rope's f32 internals must
        # not leak into the (layers, B, keep, KV, hd) scan output stack
        if kv_indices is not None:
            kv = (jnp.take(k, kv_indices, axis=1).astype(dtype),
                  jnp.take(v, kv_indices, axis=1).astype(dtype))
        elif keep > 0:
            kv = (k[:, :keep].astype(dtype), v[:, :keep].astype(dtype))
        else:
            kv = None
        return x, kv

    if cfg.local_global:
        def pair(x, lps):
            lp_local, lp_global = lps
            fn1 = lambda x: run_block(x, lp_local, cfg.sliding_window)
            fn2 = lambda x: run_block(x, lp_global, 0)
            if remat:
                fn1, fn2 = jax.checkpoint(fn1), jax.checkpoint(fn2)
            x, kv_l = fn1(x)
            x, kv_g = fn2(x)
            return x, (kv_l, kv_g)

        x, kvs = jax.lax.scan(pair, x,
                              (params["blocks_local"], params["blocks_global"]))
        kv = None if keep == 0 else {
            "local_k": kvs[0][0], "local_v": kvs[0][1],
            "global_k": kvs[1][0], "global_v": kvs[1][1]}
    else:
        def body(x, bp):
            fn = lambda x: run_block(x, bp, cfg.sliding_window)
            if remat:
                fn = jax.checkpoint(fn)
            return fn(x)

        if jnp.dtype(cfg.param_dtype).itemsize == 1 and not remat:
            # fp8 serving: index layers from the closure so the per-layer
            # upcast's operand is loop-VARIANT — scanning over the stacked
            # weights as xs lets XLA hoist the cast and materialize a full
            # bf16 copy of the model (measured +16 GB on granite prefill)
            def body_idx(x, l):
                bp = jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, False),
                    params["blocks"])
                return run_block(x, bp, cfg.sliding_window)

            x, kvs = jax.lax.scan(body_idx, x,
                                  jnp.arange(cfg.num_layers))
            kv = None if keep == 0 else {"k": kvs[0], "v": kvs[1]}
            return L.rms_norm(x, params["final_norm"]), kv

        G = _remat_groups(cfg.num_layers) if (remat and keep == 0) else 1
        if G > 1:
            # 2-level remat: only G ~ sqrt(L) group inputs are saved across
            # the forward; each group recomputes its K layers (which are
            # themselves block-checkpointed) during backward. Cuts the
            # dominant (L, B, S, D) saved-activation stack by K.
            K = cfg.num_layers // G
            grouped = jax.tree_util.tree_map(
                lambda a: a.reshape(G, K, *a.shape[1:]), params["blocks"])

            @jax.checkpoint
            def group_fn(x, gp):
                x, _ = jax.lax.scan(body, x, gp)
                return x, None

            x, _ = jax.lax.scan(group_fn, x, grouped)
            kv = None
        else:
            x, kvs = jax.lax.scan(body, x, params["blocks"])
            kv = None if keep == 0 else {"k": kvs[0], "v": kvs[1]}

    return L.rms_norm(x, params["final_norm"]), kv


def train_loss(params: Dict, cfg: ModelConfig, batch: Dict,
               num_shards: int = 1) -> jax.Array:
    hidden, _ = forward_full(params, cfg, tokens=batch.get("tokens"),
                             embeds=batch.get("embeds"), kv_keep=0,
                             num_shards=num_shards, remat=cfg.remat)
    loss, cnt = chunked_softmax_xent(hidden, head_weight(params, cfg),
                                     batch["labels"], cfg.logits_chunk,
                                     final_softcap=cfg.final_softcap)
    return loss / jnp.maximum(cnt, 1.0)


def prefill(params: Dict, cfg: ModelConfig, batch: Dict, *,
            kv_keep: int = 0, num_shards: int = 1,
            last_index: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Optional[Dict]]:
    """PrefillOnly serving prefill: (last-token logits (B, V), prefix KV).

    With ``last_index``, tokens past it are padding: the forward computes
    no query tile and no token-wise chunk that starts past it
    (``forward_full``'s ``n_valid``), and their KV is not theirs."""
    hidden, kv = forward_full(params, cfg, tokens=batch.get("tokens"),
                              embeds=batch.get("embeds"), kv_keep=kv_keep,
                              num_shards=num_shards,
                              n_valid=valid_rows(last_index))
    logits = last_token_logits(hidden, head_weight(params, cfg),
                               last_index=last_index,
                               final_softcap=cfg.final_softcap)
    return logits, kv


def prefill_packed(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                   seg_ids: jax.Array, positions: jax.Array,
                   last_indices: jax.Array, *, kv_keep: int = 0,
                   num_shards: int = 1,
                   kv_indices: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Optional[Dict]]:
    """Prepacked prefill: N requests packed into ONE contiguous sequence.

    tokens/seg_ids/positions: (1, S) — the packed sequence, its per-token
    segment index (negative = padding slack), and per-token positions that
    restart at 0 on every segment boundary (RoPE sees each request at its
    own offsets). ``last_indices``: (N,) packed index of each segment's last
    token. Returns (per-segment last-token logits (N, V), KV tree: the first
    ``kv_keep`` packed tokens, or — preferred for suffix discard, which is
    per-segment rather than a packed-sequence prefix — the gather of
    ``kv_indices`` (K,) packed positions, which the caller slices per
    segment for cache inserts at solo-path memory cost (K kept tokens, not
    S).

    Attention is causal within each segment and zero across segments, so the
    result matches N independent ``prefill`` calls while the MXU sees one
    dense sequence (prepacking, arXiv:2404.09529): padding-bucket waste is
    recovered as throughput, which PrefillOnly's single-token output makes
    safe — each request needs only its own last-row logits.
    """
    hidden, kv = forward_full(params, cfg, tokens=tokens, kv_keep=kv_keep,
                              num_shards=num_shards, positions=positions,
                              seg_ids=seg_ids, kv_indices=kv_indices)
    logits = packed_last_logits(hidden, head_weight(params, cfg),
                                last_indices,
                                final_softcap=cfg.final_softcap)
    return logits, kv


def prefill_with_prefix(params: Dict, cfg: ModelConfig, batch: Dict,
                        prefix_kv: Dict, prefix_len: int, *,
                        kv_keep: int = 0, num_shards: int = 1,
                        last_index: Optional[jax.Array] = None):
    """Prefill of a SUFFIX given a cached prefix's KV (prefix-cache hit path).

    tokens/embeds cover positions [prefix_len, prefix_len+S); every layer
    attends over concat(prefix KV, fresh suffix KV). Returns last-token
    logits + the suffix KV to extend the cache with (up to ``kv_keep`` total
    tokens — suffix discard). Dense/vlm/audio/moe families, full attention
    (window archs take the full-attention path here; engine demos are dense).
    Suffix tokens past ``last_index`` are padding, skipped as in ``prefill``.
    """
    dtype = jnp.dtype(cfg.dtype)
    if batch.get("embeds") is None:
        x = L.embed_apply(params["embed"], batch["tokens"], dtype)
    else:
        x = batch["embeds"].astype(dtype)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(prefix_len + jnp.arange(S, dtype=jnp.int32),
                                 (B, S))
    chunk = cfg.hybrid_chunk
    keep_new = max(0, min(kv_keep, prefix_len + S) - prefix_len)
    n_valid = valid_rows(last_index)

    def body(x, xs):
        bp, pk, pv = xs
        h = L.rms_norm(x, bp["ln1"])
        q, k, v = L._qkv_project(bp["attn"], h, cfg, positions, chunk,
                                 n_valid)
        k_full = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        v_full = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        out = L.blocked_attention(q, k_full, v_full, window=cfg.sliding_window,
                                  softcap=cfg.attn_softcap, q_offset=prefix_len,
                                  q_valid=n_valid)
        out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
        out = out @ bp["attn"]["wo"]
        x = x + out
        h = L.rms_norm(x, bp["ln2"])
        if cfg.is_moe:
            m = moe_apply(bp["moe"], h, cfg, num_shards=num_shards,
                          hybrid_chunk=chunk)
        else:
            m = L.mlp_apply(bp["mlp"], h, chunk=chunk, n_valid=n_valid)
        return x + m, (k[:, :keep_new], v[:, :keep_new])

    x, kvs = jax.lax.scan(body, x, (params["blocks"], prefix_kv["k"],
                                    prefix_kv["v"]))
    hidden = L.rms_norm(x, params["final_norm"])
    logits = last_token_logits(hidden, head_weight(params, cfg),
                               last_index=last_index,
                               final_softcap=cfg.final_softcap)
    return logits, {"k": kvs[0], "v": kvs[1]}


def prefill_packed_with_prefix(params: Dict, cfg: ModelConfig,
                               tokens: jax.Array, positions: jax.Array,
                               last_indices: jax.Array, prefix_kv: Dict,
                               prefix_pos: jax.Array, seg_qidx: jax.Array,
                               inv_idx: jax.Array, *,
                               num_shards: int = 1,
                               kv_indices: Optional[jax.Array] = None
                               ) -> Tuple[jax.Array, Optional[Dict]]:
    """Prepacked prefill of N SUFFIXES, each over its own cached prefix KV
    (the packed cache-HIT path: prefix sharers / hit requests co-packed).

    Hybrid layout — prepacking's win is in the token-wise (linear) layers,
    so they run on the packed (1, S) sequence; attention runs BATCHED per
    segment, (N, smax) queries against each segment's own gathered
    (N, pmax) prefix KV plus its own fresh tokens, as a handful of dense
    einsums. (A flat segment-masked formulation — see the Pallas kernel and
    ``blocked_attention``'s positioned mode — computes q-block x
    whole-prefix-buffer tiles: with short suffixes every q block spans many
    segments, no prefix tile can skip, and XLA-on-CPU tile overhead
    dominates. The batched form does exactly sum-of-segment work.)

    tokens (1, S): packed suffix tokens. ``positions`` (1, S): per-token
    RoPE positions restarting at each segment's own ``prefix_len`` (RoPE
    sees every suffix at its true offsets — per-segment q offsets).
    ``last_indices`` (N,): packed index of each segment's last token.
    ``prefix_kv``: {"k","v"} (L, N, pmax, KV, hd) — segment n's cached
    prefix KV in row n, zero-padded to pmax. ``prefix_pos`` (N, pmax):
    absolute positions of the prefix tokens, padding = a huge value (killed
    by the causal mask). ``seg_qidx`` (N, smax): packed index of segment
    n's j-th suffix token, -1 = padding. ``inv_idx`` (S,): flat
    (n * smax + slot) of each packed position (scatter-back map; slack
    positions may point anywhere). The result matches N independent
    ``prefill_with_prefix`` calls.

    Returns (per-segment last-token logits (N, V), fresh-KV tree gathered
    at ``kv_indices`` (K,) packed positions — the per-segment suffix keep
    windows, which the caller slices for cache inserts at solo-path memory
    cost). Dense/vlm/audio/moe families (same coverage as
    ``prefill_with_prefix``).
    """
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_apply(params["embed"], tokens, dtype)
    B, S, _ = x.shape
    N, smax = seg_qidx.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // KV
    chunk = cfg.hybrid_chunk
    scale = hd ** -0.5
    window = cfg.sliding_window
    softcap = cfg.attn_softcap
    idx = jnp.clip(seg_qidx, 0, S - 1)             # (N, smax)
    kvalid = seg_qidx >= 0                         # padded slots: dead keys
    posb = positions[0][idx]                       # (N, smax) abs positions
    ppos = prefix_pos.astype(jnp.int32)            # (N, pmax)
    # masks are layer-invariant: build once
    mask_p = posb[:, :, None] >= ppos[:, None, :]  # (N, smax, pmax)
    mask_f = ((posb[:, :, None] >= posb[:, None, :])
              & kvalid[:, None, :])                # (N, smax, smax)
    if window > 0:
        mask_p &= (posb[:, :, None] - ppos[:, None, :]) < window
        mask_f &= (posb[:, :, None] - posb[:, None, :]) < window

    def body(x, xs):
        bp, pk, pv = xs
        h = L.rms_norm(x, bp["ln1"])
        q, k, v = L._qkv_project(bp["attn"], h, cfg, positions, chunk)
        qb = q[0][idx].reshape(N, smax, KV, G, hd)
        qb = qb.astype(jnp.float32) * scale
        kb, vb = k[0][idx], v[0][idx]              # (N, smax, KV, hd)
        s_p = jnp.einsum("nqkgd,npkd->nkgqp", qb,
                         pk.astype(jnp.float32))   # (N,KV,G,smax,pmax)
        s_f = jnp.einsum("nqkgd,nskd->nkgqs", qb,
                         kb.astype(jnp.float32))   # (N,KV,G,smax,smax)
        if softcap:
            s_p = softcap * jnp.tanh(s_p / softcap)
            s_f = softcap * jnp.tanh(s_f / softcap)
        s_p = jnp.where(mask_p[:, None, None], s_p, L.NEG_INF)
        s_f = jnp.where(mask_f[:, None, None], s_f, L.NEG_INF)
        m = jnp.maximum(jnp.max(s_p, axis=-1), jnp.max(s_f, axis=-1))
        p_p = jnp.exp(s_p - m[..., None])
        p_f = jnp.exp(s_f - m[..., None])
        l = jnp.sum(p_p, axis=-1) + jnp.sum(p_f, axis=-1)
        o = (jnp.einsum("nkgqp,npkd->nkgqd", p_p, pv.astype(jnp.float32))
             + jnp.einsum("nkgqs,nskd->nkgqd", p_f, vb.astype(jnp.float32)))
        o = o / jnp.maximum(l, 1e-30)[..., None]
        flat = o.transpose(0, 3, 1, 2, 4).reshape(N * smax, H * hd)
        out = flat[inv_idx][None].astype(x.dtype)  # back to packed (1, S, .)
        out = out @ bp["attn"]["wo"]
        x = x + out
        h = L.rms_norm(x, bp["ln2"])
        if cfg.is_moe:
            mo = moe_apply(bp["moe"], h, cfg, num_shards=num_shards,
                           hybrid_chunk=chunk)
        else:
            mo = L.mlp_apply(bp["mlp"], h, chunk=chunk)
        if kv_indices is not None:
            kv = (jnp.take(k, kv_indices, axis=1).astype(dtype),
                  jnp.take(v, kv_indices, axis=1).astype(dtype))
        else:
            kv = (jnp.zeros((B, 0) + k.shape[2:], dtype),
                  jnp.zeros((B, 0) + v.shape[2:], dtype))
        return x + mo, kv

    x, kvs = jax.lax.scan(body, x, (params["blocks"], prefix_kv["k"],
                                    prefix_kv["v"]))
    hidden = L.rms_norm(x, params["final_norm"])
    logits = packed_last_logits(hidden, head_weight(params, cfg),
                                last_indices,
                                final_softcap=cfg.final_softcap)
    kv = None if kv_indices is None else {"k": kvs[0], "v": kvs[1]}
    return logits, kv


# --------------------------------------------------------------------------
# decode (one token against a KV cache)
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               abstract: bool = False) -> Dict:
    """KV cache tree. SWA-only archs get a ring buffer bounded by the window
    (this is what makes mixtral's long_500k cell runnable); gemma2 gets a
    ring for local layers + full cache for global layers."""
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    dtype = jnp.dtype(cfg.dtype)

    def mk(shape):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jnp.zeros(shape, dtype)

    if cfg.local_global:
        half = cfg.num_layers // 2
        w = min(cfg.sliding_window, max_len)
        return {"local_k": mk((half, batch, w, KV, hd)),
                "local_v": mk((half, batch, w, KV, hd)),
                "global_k": mk((half, batch, max_len, KV, hd)),
                "global_v": mk((half, batch, max_len, KV, hd))}
    s = max_len
    if cfg.sliding_window:
        s = min(cfg.sliding_window, max_len)
    return {"k": mk((cfg.num_layers, batch, s, KV, hd)),
            "v": mk((cfg.num_layers, batch, s, KV, hd))}


def cache_axes(cfg: ModelConfig) -> Dict:
    """Logical sharding axes matching ``init_cache``'s tree structure."""
    kv = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    if cfg.local_global:
        return {"local_k": kv, "local_v": kv, "global_k": kv, "global_v": kv}
    return {"k": kv, "v": kv}


def _block_decode(bp: Dict, x: jax.Array, cfg: ModelConfig, *,
                  position: jax.Array, kc: jax.Array, vc: jax.Array,
                  ring: bool, num_shards: int):
    bp = _cast_block(bp, cfg.dtype)
    h = L.rms_norm(x, bp["ln1"])
    attn, kc, vc = L.attention_decode(bp["attn"], h, cfg, position=position,
                                      k_cache=kc, v_cache=vc, ring=ring)
    x = x + attn
    h = L.rms_norm(x, bp["ln2"])
    if cfg.is_moe:
        m = moe_apply(bp["moe"], h, cfg, num_shards=num_shards)
    else:
        m = L.mlp_apply(bp["mlp"], h)
    return x + m, kc, vc


def decode_step(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                cache: Dict, position: jax.Array, *, num_shards: int = 1
                ) -> Tuple[jax.Array, Dict]:
    """tokens: (B,) int32; position: (B,) int32 (uniform). -> (logits, cache)."""
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_apply(params["embed"], tokens[:, None], dtype)
    if cfg.local_global:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    ring = bool(cfg.sliding_window)

    # The cache rides in the scan CARRY with per-layer dynamic updates, NOT
    # as xs/ys — scan ys are double-buffered by XLA, which would cost a full
    # extra cache copy per step (measured: 2.6x cache in temp).
    def upd(buf, sl, l):
        return jax.lax.dynamic_update_index_in_dim(buf, sl, l, 0)

    if cfg.local_global:
        def pair(carry, xs):
            x, l, lk_a, lv_a, gk_a, gv_a = carry
            lp_l, lp_g = xs
            x, lk, lv = _block_decode(
                lp_l, x, cfg, position=position,
                kc=jax.lax.dynamic_index_in_dim(lk_a, l, 0, False),
                vc=jax.lax.dynamic_index_in_dim(lv_a, l, 0, False),
                ring=True, num_shards=num_shards)
            x, gk, gv = _block_decode(
                lp_g, x, cfg, position=position,
                kc=jax.lax.dynamic_index_in_dim(gk_a, l, 0, False),
                vc=jax.lax.dynamic_index_in_dim(gv_a, l, 0, False),
                ring=False, num_shards=num_shards)
            return (x, l + 1, upd(lk_a, lk, l), upd(lv_a, lv, l),
                    upd(gk_a, gk, l), upd(gv_a, gv, l)), None

        init = (x, 0, cache["local_k"], cache["local_v"],
                cache["global_k"], cache["global_v"])
        (x, _, lk_a, lv_a, gk_a, gv_a), _ = jax.lax.scan(
            pair, init, (params["blocks_local"], params["blocks_global"]))
        new_cache = {"local_k": lk_a, "local_v": lv_a,
                     "global_k": gk_a, "global_v": gv_a}
    else:
        def body(carry, bp):
            x, l, k_a, v_a = carry
            x, kc, vc = _block_decode(
                bp, x, cfg, position=position,
                kc=jax.lax.dynamic_index_in_dim(k_a, l, 0, False),
                vc=jax.lax.dynamic_index_in_dim(v_a, l, 0, False),
                ring=ring, num_shards=num_shards)
            return (x, l + 1, upd(k_a, kc, l), upd(v_a, vc, l)), None

        # weights stay scan-xs: slices are loop-variant so the per-layer fp8
        # upcast in _block_decode cannot be hoisted; closure-capture instead
        # makes them loop INVARIANTS, which XLA COPIES into the loop state
        # (measured +15.7 GB on mixtral decode)
        (x, _, k_a, v_a), _ = jax.lax.scan(
            body, (x, 0, cache["k"], cache["v"]), params["blocks"])
        new_cache = {"k": k_a, "v": v_a}

    hidden = L.rms_norm(x, params["final_norm"])
    logits = last_token_logits(hidden, head_weight(params, cfg),
                               final_softcap=cfg.final_softcap)
    return logits, new_cache
