"""Layer library: RMSNorm, RoPE, blocked attention (GQA/SWA/softcap), SwiGLU.

Design rules
  * pure functions over param dicts (ParamDef-declared, see runtime/sharding)
  * fp32 softmax/norm internals, activations in cfg.dtype
  * attention is BLOCKED (flash-style online softmax via lax.map/scan) so the
    lowered HLO never materializes (S, S) logits — required for the 32k/500k
    dry-run cells to fit HBM
  * every token-wise op optionally runs under hybrid prefilling
    (core.hybrid_prefill.chunked_map)
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.hybrid_prefill import chunked_map
# PAD_POS: padding-kv position sentinel, shared with the Pallas kernel (the
# oracle and kernel must agree on what "huge" means for the causal skip)
from repro.kernels.flash_attention import PAD_POS  # noqa: F401  (re-export)
from repro.runtime.sharding import constrain, pdef

NEG_INF = -1e30


# --------------------------------------------------------------------------
# norms / rope
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def rope_apply(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, d), positions: (B, S) int32. Split-half RoPE."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs     # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# blocked attention (pure-JAX flash; the Pallas kernel mirrors this oracle)
# --------------------------------------------------------------------------

def _apply_mask(logits: jax.Array, qpos: jax.Array, kpos: jax.Array,
                kv_len: Optional[jax.Array], window: int) -> jax.Array:
    """logits: (..., qb, kb); qpos (qb,), kpos (kb,) absolute positions."""
    mask = qpos[:, None] >= kpos[None, :]
    if window > 0:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    if kv_len is not None:
        mask &= kpos[None, :] < kv_len
    return jnp.where(mask, logits, NEG_INF)


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0,
                      softcap: float = 0.0, q_offset: int = 0,
                      q_block: int = 512, kv_block: int = 1024,
                      head_scale: Optional[float] = None,
                      seg_ids: Optional[jax.Array] = None,
                      seg_ids_k: Optional[jax.Array] = None,
                      pos_q: Optional[jax.Array] = None,
                      pos_k: Optional[jax.Array] = None) -> jax.Array:
    """Flash-style attention. q: (B,Sq,H,d), k/v: (B,Skv,KV,d) -> (B,Sq,H,d).

    Online-softmax over KV blocks (lax.scan) x lax.map over Q blocks: the HLO
    holds at most (qb, kb) logits per (batch, head) at a time.

    ``seg_ids`` (B, S) int32 enables prepacked prefill: attention is
    restricted to same-segment (q, k) pairs, so N packed requests attend only
    to themselves (negative ids mark padding). Self-attention (Sq==Skv) with
    packed positions, which agree with per-segment positions because segments
    are contiguous — unless ``seg_ids_k`` is also given.

    ``seg_ids_k`` (B, Skv): KV-side segment ids when the KV side differs from
    the query side — the prefix-aware packed path, where KV is
    concat(gathered per-segment CACHED prefix KV, fresh packed KV). Then
    ``pos_q``/``pos_k`` (B, Sq)/(B, Skv) per-token ABSOLUTE positions replace
    the structural causal/window positions (each query sits at
    prefix_len + local offset; its prefix tokens at [0, prefix_len)), and the
    causal tile skip becomes a dynamic min/max position range test, so a
    query block never computes another segment's prefix tiles.
    """
    B, Sq, H, d = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = head_scale if head_scale is not None else 1.0 / math.sqrt(d)
    if seg_ids is not None and seg_ids_k is None:
        assert Sq == Skv, "segment-restricted attention is self-attention"
        seg_ids_k = seg_ids
    positioned = pos_q is not None
    assert positioned == (pos_k is not None), "pos_q and pos_k come together"
    assert not positioned or seg_ids is not None, \
        "per-token positions require segment ids"

    qb = min(q_block, Sq)
    kb = min(kv_block, Skv)
    # pad to block multiples (masked out below via absolute positions)
    pad_q = (-Sq) % qb
    pad_k = (-Skv) % kb
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    seg_q = seg_k = None
    if seg_ids is not None:
        seg_q = jnp.pad(seg_ids.astype(jnp.int32), ((0, 0), (0, pad_q)),
                        constant_values=-1)
        seg_k = jnp.pad(seg_ids_k.astype(jnp.int32), ((0, 0), (0, pad_k)),
                        constant_values=-1)
    pq_full = pk_full = None
    if positioned:
        pq_full = jnp.pad(pos_q.astype(jnp.int32), ((0, 0), (0, pad_q)))
        pk_full = jnp.pad(pos_k.astype(jnp.int32), ((0, 0), (0, pad_k)),
                          constant_values=PAD_POS)
    nq, nk = q.shape[1] // qb, k.shape[1] // kb
    qg = q.reshape(B, nq, qb, KV, G, d)
    kv_len = jnp.asarray(Skv)  # mask out k-padding

    def one_q_block(i):
        q_blk = qg[:, i].astype(jnp.float32) * scale      # (B,qb,KV,G,d)
        qpos = q_offset + i * qb + jnp.arange(qb)
        sq_blk = (jax.lax.dynamic_slice_in_dim(seg_q, i * qb, qb, axis=1)
                  if seg_q is not None else None)
        pq_blk = (jax.lax.dynamic_slice_in_dim(pq_full, i * qb, qb, axis=1)
                  if positioned else None)

        def kv_step(carry, j):
            kpos = j * kb + jnp.arange(kb)
            sk_blk = (jax.lax.dynamic_slice_in_dim(seg_k, j * kb, kb, axis=1)
                      if sq_blk is not None else None)
            pk_blk = (jax.lax.dynamic_slice_in_dim(pk_full, j * kb, kb, axis=1)
                      if positioned else None)

            def compute(carry):
                # K/V slices live INSIDE the branch: a skipped tile must not
                # even pay the (B, kb, KV, d) copies out of the full buffer
                # (they dominate the dead-tile cost of long gathered-prefix
                # buffers; the id/position slices above are kb ints each)
                k_j = jax.lax.dynamic_slice_in_dim(k, j * kb, kb, axis=1)
                v_j = jax.lax.dynamic_slice_in_dim(v, j * kb, kb, axis=1)
                m, l, acc = carry
                s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk,
                               k_j.astype(jnp.float32))    # (B,KV,G,qb,kb)
                if softcap:
                    s = softcap * jnp.tanh(s / softcap)
                if positioned:
                    pmask = jnp.ones((B, qb, kb), jnp.bool_)
                    if causal:
                        pmask &= pq_blk[:, :, None] >= pk_blk[:, None, :]
                    if window > 0:
                        pmask &= (pq_blk[:, :, None]
                                  - pk_blk[:, None, :]) < window
                    pmask &= (kpos < kv_len)[None, None, :]
                    s = jnp.where(pmask[:, None, None], s, NEG_INF)
                elif causal:
                    s = _apply_mask(s, qpos, kpos, kv_len, window)
                else:
                    s = jnp.where((kpos < kv_len)[None, :], s, NEG_INF)
                if sq_blk is not None:
                    segm = ((sq_blk[:, :, None] == sk_blk[:, None, :])
                            & (sk_blk[:, None, :] >= 0))   # (B, qb, kb)
                    s = jnp.where(segm[:, None, None], s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                pv = jnp.einsum("bkgqs,bskd->bkgqd", p,
                                v_j.astype(jnp.float32))
                acc_new = acc * corr[..., None] + pv
                return m_new, l_new, acc_new

            # tile-level skipping (XLA twin of the Pallas kernel's pl.when):
            # a tile that the causal/window/kv-padding/segment masks would
            # fully erase contributes exactly nothing to the online softmax
            # (exp underflows to 0 against any live row max), so branch it
            # out with lax.cond — fully-masked tiles cost 0 FLOPs. This is
            # what turns prepacked batches into sum-of-segment attention
            # cost instead of quadratic-in-packed-length.
            live = jnp.asarray(True)
            if positioned:
                # dynamic position ranges stand in for the structural causal
                # skip; PAD_POS on padded kv keeps pure-padding tiles dead
                if causal:
                    live = live & (jnp.min(pk_blk) <= jnp.max(pq_blk))
                if window > 0:
                    live = live & (jnp.max(pk_blk) > jnp.min(pq_blk) - window)
            else:
                if causal:
                    live = live & (j * kb <= qpos[-1])
                if window > 0:
                    live = live & (j * kb + kb - 1 > qpos[0] - window)
            live = live & (j * kb < kv_len)
            if sq_blk is not None:
                live = live & (jnp.min(sq_blk) <= jnp.max(sk_blk))
                live = live & (jnp.max(sq_blk) >= jnp.min(sk_blk))
                live = live & (jnp.max(sk_blk) >= 0)
            return jax.lax.cond(live, compute, lambda c: c, carry), None

        m0 = jnp.full((B, KV, G, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qb), jnp.float32)
        a0 = jnp.zeros((B, KV, G, qb, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]       # (B,KV,G,qb,d)
        return out.transpose(0, 3, 1, 2, 4)                # (B,qb,KV,G,d)

    outs = jax.lax.map(one_q_block, jnp.arange(nq))        # (nq,B,qb,KV,G,d)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * qb, H, d)
    if pad_q:
        out = out[:, :Sq]
    return out.astype(q.dtype)


def packed_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            softcap: float = 0.0, q_offset: int = 0,
                            block: int = 512,
                            head_scale: Optional[float] = None) -> jax.Array:
    """Causal attention with EXACT lower-triangle FLOPs (tile pair-packing).

    The naive blocked schedule computes all nq*nk tiles and masks half of
    them away — 2x wasted MXU work. Here q-block pairs (p, nq-1-p) share one
    scan of nq+1 tile-steps: step t serves (q=p, kv=t) while t<=p and
    (q=nq-1-p, kv=t-p-1) after, so every executed tile lies in the lower
    triangle: nq/2 * (nq+1) tiles == the triangle exactly. This is the
    "balanced causal swizzle" used by splash-style TPU kernels, expressed at
    the XLA level so the dry-run FLOP counts reflect it.
    """
    B, Sq, H, d = q.shape
    _, Skv, KV, _ = k.shape
    assert Sq == Skv, "packed schedule assumes self-attention"
    G = H // KV
    scale = head_scale if head_scale is not None else 1.0 / math.sqrt(d)
    bb = min(block, Sq)
    pad = (-Sq) % bb
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n = q.shape[1] // bb
    if n % 2 == 1:                     # need an even number of q blocks
        q = jnp.pad(q, ((0, 0), (0, bb), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, bb), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, bb), (0, 0), (0, 0)))
        n += 1
    S_pad = n * bb
    qg = q.reshape(B, n, bb, KV, G, d)
    kv_valid = jnp.asarray(Skv)

    def one_pair(p):
        lo, hi = p, n - 1 - p
        q_lo = qg[:, lo].astype(jnp.float32) * scale   # (B,bb,KV,G,d)
        q_hi = qg[:, hi].astype(jnp.float32) * scale

        def step(carry, t):
            m, l, acc = carry                          # (2,B,KV,G,bb[,d])
            use_hi = t > p
            qi = jnp.where(use_hi, hi, lo)
            kj = jnp.where(use_hi, t - p - 1, t)
            slot = use_hi.astype(jnp.int32)
            q_blk = jnp.where(use_hi, q_hi, q_lo)
            k_j = jax.lax.dynamic_slice_in_dim(k, kj * bb, bb, axis=1)
            v_j = jax.lax.dynamic_slice_in_dim(v, kj * bb, bb, axis=1)
            qpos = q_offset + qi * bb + jnp.arange(bb)
            kpos = kj * bb + jnp.arange(bb)
            s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk,
                           k_j.astype(jnp.float32))
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            s = _apply_mask(s, qpos, kpos, kv_valid, 0)
            m_prev = m[slot]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            pmat = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l[slot] * corr + jnp.sum(pmat, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", pmat,
                            v_j.astype(jnp.float32))
            acc_new = acc[slot] * corr[..., None] + pv
            m = m.at[slot].set(m_new)
            l = l.at[slot].set(l_new)
            acc = acc.at[slot].set(acc_new)
            return (m, l, acc), None

        m0 = jnp.full((2, B, KV, G, bb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((2, B, KV, G, bb), jnp.float32)
        a0 = jnp.zeros((2, B, KV, G, bb, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(n + 1))
        out = acc / jnp.maximum(l, 1e-30)[..., None]   # (2,B,KV,G,bb,d)
        return out.transpose(0, 1, 4, 2, 3, 5)          # (2,B,bb,KV,G,d)

    outs = jax.lax.map(one_pair, jnp.arange(n // 2))   # (n/2,2,B,bb,KV,G,d)
    # reassemble: pair p produced q-blocks p (slot 0) and n-1-p (slot 1)
    lo_blocks = outs[:, 0]                              # (n/2, B, bb, ...)
    hi_blocks = outs[:, 1][::-1]                        # block n/2 .. n-1
    full = jnp.concatenate([lo_blocks, hi_blocks], axis=0)
    out = jnp.moveaxis(full, 0, 1).reshape(B, S_pad, H, d)
    return out[:, :Sq].astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_len: jax.Array, *, softcap: float = 0.0,
                     ring: bool = False,
                     head_scale: Optional[float] = None) -> jax.Array:
    """One-token attention. q: (B,1,H,d); caches: (B,S,KV,d).

    ``ring=True`` means the cache is a sliding-window ring buffer: every slot
    with index < min(kv_len, S) is valid and window semantics are implicit.
    """
    B, _, H, d = q.shape
    _, S, KV, _ = k_cache.shape
    G = H // KV
    scale = head_scale if head_scale is not None else 1.0 / math.sqrt(d)
    # keep K/V in cache dtype with f32 ACCUMULATION — an explicit
    # .astype(f32) on the cache gets hoisted into a full-stack f32 copy of
    # the carried cache inside the decode loop (measured on mixtral decode)
    qh = (q.reshape(B, KV, G, d) * jnp.asarray(scale, q.dtype))
    s = jnp.einsum("bkgd,bskd->bkgs", qh, k_cache,
                   preferred_element_type=jnp.float32)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    slots = jnp.arange(S)
    valid = slots < jnp.minimum(kv_len, S) if ring else slots < kv_len
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, d).astype(q.dtype)


# --------------------------------------------------------------------------
# attention block (projections + rope + attention)
# --------------------------------------------------------------------------

def attention_defs(cfg: ModelConfig) -> Dict:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    defs = {
        "wq": pdef((D, H * hd), ("d_model", "qkv"), init="scaled"),
        "wk": pdef((D, KV * hd), ("d_model", "qkv"), init="scaled"),
        "wv": pdef((D, KV * hd), ("d_model", "qkv"), init="scaled"),
        "wo": pdef((H * hd, D), ("qkv", "d_model"), init="scaled"),
    }
    if cfg.qkv_bias:
        defs["bq"] = pdef((H * hd,), ("qkv",), init="zeros")
        defs["bk"] = pdef((KV * hd,), ("qkv",), init="zeros")
        defs["bv"] = pdef((KV * hd,), ("qkv",), init="zeros")
    return defs


def _qkv_project(p: Dict, x: jax.Array, cfg: ModelConfig,
                 positions: jax.Array, chunk: int):
    """Token-wise QKV projection + RoPE (none under NoPE), chunked under
    hybrid prefilling."""
    B, S, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def proj(xc):
        q = xc @ p["wq"]
        k = xc @ p["wk"]
        v = xc @ p["wv"]
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        return jnp.concatenate([q, k, v], axis=-1)

    qkv = chunked_map(proj, x, chunk)
    q, k, v = jnp.split(qkv, [H * hd, (H + KV) * hd], axis=-1)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.position_embedding == "rope":
        q = rope_apply(q, positions, cfg.rope_theta)
        k = rope_apply(k, positions, cfg.rope_theta)
    # "attn_seq" (not "seq"): under sequence parallelism the residual
    # stream is seq-sharded but attention needs the full sequence — XLA
    # inserts the Megatron-SP all-gather here and the reduce-scatter after
    # the output projection.
    q = constrain(q, ("batch", "attn_seq", "heads", "head_dim"))
    k = constrain(k, ("batch", "attn_seq", "kv_heads", "head_dim"))
    v = constrain(v, ("batch", "attn_seq", "kv_heads", "head_dim"))
    return q, k, v


def _context_parallel_attention(q, k, v, *, window: int, softcap: float,
                                mesh, seq_axis: str = "model",
                                batch_axes=("pod", "data")) -> jax.Array:
    """Explicit context parallelism: queries stay seq-sharded, K/V are
    all-gathered per layer (small under GQA), attention is computed locally
    per seq shard with the right positional offset. shard_map pins this
    schedule — letting SPMD partition the blocked-attention scan instead
    replicates the compute across the seq axis (measured 10x)."""
    from jax.sharding import PartitionSpec as P
    import jax

    b_axes = tuple(a for a in batch_axes if a in mesh.shape)
    spec = P(b_axes if b_axes else None, seq_axis, None, None)

    def local_fn(ql, kl, vl):
        k_full = jax.lax.all_gather(kl, seq_axis, axis=1, tiled=True)
        v_full = jax.lax.all_gather(vl, seq_axis, axis=1, tiled=True)
        q_off = jax.lax.axis_index(seq_axis) * ql.shape[1]
        return blocked_attention(ql, k_full, v_full, window=window,
                                 softcap=softcap, q_offset=q_off)

    from repro.runtime.sharding import shard_map
    return shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def attention_prefill(p: Dict, x: jax.Array, cfg: ModelConfig, *,
                      positions: jax.Array, window: int = 0,
                      chunk: int = 0, seg_ids: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence attention. Returns (out, k, v) — the caller decides how
    much of (k, v) to keep (suffix KV discard happens there).

    ``seg_ids`` selects the prepacked path: segment-restricted blocked
    attention (single instance — the context-parallel and tile-packing
    schedules assume one contiguous causal sequence)."""
    from repro.runtime.sharding import _CTX
    B, S, D = x.shape
    q, k, v = _qkv_project(p, x, cfg, positions, chunk)
    rules = _CTX.rules or {}
    cp = (_CTX.mesh is not None and rules.get("attn_seq") == "model"
          and S % _CTX.mesh.shape.get("model", 1) == 0)
    if seg_ids is not None:
        # segment-scale tiles: tile-level skipping only pays off when blocks
        # are no bigger than typical packed segments — with the default
        # (512, 1024) blocks a 1k packed batch is ONE tile and nothing skips
        out = blocked_attention(q, k, v, window=window,
                                softcap=cfg.attn_softcap, seg_ids=seg_ids,
                                q_block=128, kv_block=128,
                                head_scale=cfg.attn_head_scale)
    elif cp:
        out = _context_parallel_attention(
            q, k, v, window=window, softcap=cfg.attn_softcap, mesh=_CTX.mesh)
    elif cfg.packed_attention and window == 0:
        out = packed_causal_attention(q, k, v, softcap=cfg.attn_softcap,
                                      head_scale=cfg.attn_head_scale)
    else:
        out = blocked_attention(q, k, v, window=window,
                                softcap=cfg.attn_softcap,
                                head_scale=cfg.attn_head_scale)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    out = chunked_map(lambda oc: oc @ p["wo"], out, chunk)
    return constrain(out, ("batch", "seq", "d_model")), k, v


def attention_decode(p: Dict, x: jax.Array, cfg: ModelConfig, *,
                     position: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, ring: bool = False
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token attention step. x: (B,1,D). Returns (out, k_cache, v_cache)
    with the new token written at ``position`` (mod window when ring)."""
    B, _, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    S = k_cache.shape[1]
    q = (x @ p["wq"])
    k = (x @ p["wk"])
    v = (x @ p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, 1, H, hd)
    k = k.reshape(B, 1, KV, hd)
    v = v.reshape(B, 1, KV, hd)
    pos2d = position.reshape(B, 1)
    q = rope_apply(q, pos2d, cfg.rope_theta)
    k = rope_apply(k, pos2d, cfg.rope_theta)
    # uniform decode: all batch rows share the step position (slot from row 0)
    slot = position[0] % S if ring else position[0]
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), slot, axis=1)
    kv_len = position[0] + 1
    out = decode_attention(q, k_cache, v_cache, kv_len,
                           softcap=cfg.attn_softcap, ring=ring)
    out = out.reshape(B, 1, H * hd) @ p["wo"]
    return out.astype(x.dtype), k_cache, v_cache


# --------------------------------------------------------------------------
# SwiGLU MLP
# --------------------------------------------------------------------------

def mlp_defs(d_model: int, d_ff: int) -> Dict:
    return {
        "w_gate": pdef((d_model, d_ff), ("d_model", "d_ff"), init="scaled"),
        "w_up": pdef((d_model, d_ff), ("d_model", "d_ff"), init="scaled"),
        "w_down": pdef((d_ff, d_model), ("d_ff", "d_model"), init="scaled"),
    }


def mlp_apply(p: Dict, x: jax.Array, chunk: int = 0) -> jax.Array:
    """SwiGLU MLP; the (tokens, d_ff) intermediate is the paper's memory
    villain — chunked under hybrid prefilling."""

    def f(xc):
        g = xc @ p["w_gate"]
        u = xc @ p["w_up"]
        return (jax.nn.silu(g.astype(jnp.float32)).astype(xc.dtype) * u) @ p["w_down"]

    out = chunked_map(f, x, chunk)
    return constrain(out, ("batch", "seq", "d_model"))


# --------------------------------------------------------------------------
# embedding
# --------------------------------------------------------------------------

def embed_defs(cfg: ModelConfig) -> Dict:
    return {"tok": pdef((cfg.vocab_size, cfg.d_model), ("vocab", "d_model"))}


def embed_apply(p: Dict, tokens: jax.Array, dtype) -> jax.Array:
    out = jnp.take(p["tok"], tokens, axis=0).astype(dtype)
    return constrain(out, ("batch", "seq", "d_model"))
