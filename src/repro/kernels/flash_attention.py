"""Pallas TPU kernel: blocked causal flash attention (GQA / SWA / softcap /
segment-restricted prepacking).

Hybrid prefilling's counterpart guarantee (paper §4): attention is NOT
chunked — each (q-block, kv-block) tile streams through VMEM with online
softmax, so the (S, S) logits never exist and kernel efficiency is intact
(the paper's complaint about chunked prefill is precisely that it degrades
the attention kernel).

GQA without materializing repeated KV: the kv-head index of each q head is
resolved in the BlockSpec index_map (h // group), so HBM holds only
``num_kv_heads`` K/V copies.

Grid: (B, H, nq, nk), kv innermost. Causal + sliding-window block skipping
happens via ``pl.when`` on whole tiles — off-diagonal masked tiles cost 0
FLOPs (the structural half-compute win the dry-run hillclimb measures).

Prepacked prefill (arXiv:2404.09529 / BatchLLM): optional per-token
``seg_q``/``seg_k`` id arrays restrict attention to same-segment pairs so N
short requests share one contiguous forward. Tile skipping extends to
segments: a (q-block, kv-block) tile whose segment-id *ranges* cannot
intersect is skipped by the same ``pl.when`` mechanism as the causal skip,
so cross-segment tiles also cost 0 FLOPs. Padding tokens carry a negative
segment id, which doubles as the padded-KV mask (``kv_valid`` handles the
unsegmented case).

Prefix-aware packing (cache-HIT co-packing): optional per-token ``pos_q``/
``pos_k`` absolute-position arrays generalize the structural causal/window
masks. The KV side may then be the concatenation of a *gathered per-segment
cached-prefix KV buffer* and the fresh packed KV: prefix tokens carry their
segment's id and their absolute positions [0, prefix_len), fresh tokens carry
positions [prefix_len, n_input) — so each packed query segment attends
causally over its own cached prefix plus its own fresh tokens and nothing
else. Tile skipping stays intact: the causal skip becomes a dynamic
min/max-position range test (same pl.when mechanism), composed with the
segment-range skip, so a query block never touches another segment's prefix
tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# padding-kv position sentinel (shared with the model-layer oracle and the
# engine): huge, power of two (f32-exact for the tile-skip reductions), so
# causal masks kill padded tokens and pure-padding tiles never run
PAD_POS = 1 << 30


def _make_kernel(bq, bk, nk, window, softcap, scale, causal, kv_valid,
                 segmented, positioned, tile_map):
    def kernel(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref = next(it), next(it), next(it)
        sq_ref = next(it) if segmented else None
        sk_ref = next(it) if segmented else None
        pq_ref = next(it) if positioned else None
        pk_ref = next(it) if positioned else None
        o_ref = next(it)
        map_ref = next(it) if tile_map else None
        m_ref, l_ref, acc_ref = next(it), next(it), next(it)

        i = pl.program_id(2)
        j = pl.program_id(3)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        run = jnp.asarray(True)
        if positioned:
            # Per-token absolute positions (prefix-aware packing: the KV side
            # may concatenate a gathered prefix buffer with the fresh packed
            # tokens, so structural tile positions are meaningless). The
            # causal/window skips become dynamic range tests over the tiles'
            # position min/max — padding kv tokens carry a huge position so
            # pure-padding tiles fail the causal test and never run.
            # f32 reductions: Mosaic has no integer reduce_min/max; positions
            # (< 2^24, plus the power-of-two pad value) are f32-exact.
            pq = pq_ref[0, 0].astype(jnp.float32)           # (bq,)
            pk = pk_ref[0, 0].astype(jnp.float32)           # (bk,)
            if causal:
                run = run & (jnp.min(pk) <= jnp.max(pq))
            if window > 0:
                run = run & (jnp.max(pk) >= jnp.min(pq) - window + 1)
        else:
            if causal:
                run = run & (j * bk <= i * bq + bq - 1)
            if window > 0:
                run = run & (j * bk + bk - 1 >= i * bq - window + 1)
        if kv_valid is not None:
            run = run & (j * bk < kv_valid)
        if segmented:
            # Packed layouts keep each segment contiguous, so a tile computes
            # real work only if the q-block's and kv-block's segment-id ranges
            # intersect AND the kv-block holds at least one real (id >= 0)
            # token. Data-dependent, but pl.when lowers it to a branch the
            # same way as the structural causal skip. (f32 reductions: see
            # above — segment ids are small ints, exactly representable.)
            sq = sq_ref[0, 0].astype(jnp.float32)           # (bq,)
            sk = sk_ref[0, 0].astype(jnp.float32)           # (bk,)
            run = run & (jnp.min(sq) <= jnp.max(sk))
            run = run & (jnp.max(sq) >= jnp.min(sk))
            run = run & (jnp.max(sk) >= 0)

        if tile_map:
            map_ref[0, 0, 0] = run.astype(jnp.int32)

        @pl.when(run)
        def _compute():
            q = q_ref[0, 0].astype(jnp.float32) * scale     # (bq, d)
            k = k_ref[0, 0].astype(jnp.float32)             # (bk, d)
            v = v_ref[0, 0].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            if positioned:
                qpos = jnp.broadcast_to(pq_ref[0, 0][:, None], (bq, bk))
                kpos = jnp.broadcast_to(pk_ref[0, 0][None, :], (bq, bk))
            else:
                qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = jnp.ones((bq, bk), jnp.bool_)
            if causal:
                mask &= qpos >= kpos
            if window > 0:
                mask &= (qpos - kpos) < window
            if kv_valid is not None:
                struct_k = j * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                mask &= struct_k < kv_valid
            if segmented:
                sq = sq_ref[0, 0]
                sk = sk_ref[0, 0]
                mask &= sq[:, None] == sk[None, :]
                mask &= sk[None, :] >= 0
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[...]                              # (bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)                   # (bq, 1)
            l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[...] = (acc_ref[...] * corr
                            + jax.lax.dot_general(
                                p.astype(v.dtype), v,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))
            m_ref[...] = m_new

        @pl.when(j == nk - 1)
        def _flush():
            denom = jnp.maximum(l_ref[...], 1e-30)
            o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)

    return kernel


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: float | None = None,
                    kv_valid: int | None = None,
                    seg_q: jax.Array | None = None,
                    seg_k: jax.Array | None = None,
                    pos_q: jax.Array | None = None,
                    pos_k: jax.Array | None = None,
                    block_q: int = 256, block_k: int = 256,
                    debug_tile_map: bool = False,
                    interpret: bool = True):
    """q: (B, H, Sq, d); k/v: (B, KV, Sk, d) with H % KV == 0 -> (B, H, Sq, d).

    ``kv_valid``: number of real kv columns (static); columns >= kv_valid are
    padding and are masked regardless of ``causal`` (ops.py pads to block
    multiples). ``seg_q``/``seg_k``: (B, Sq)/(B, Sk) int32 per-token segment
    ids for prepacked batches; attention is restricted to ``seg_q == seg_k``
    (composed with causal/window, which use *packed* positions — valid within
    a segment because segments are contiguous). Negative ids mark padding.

    ``pos_q``/``pos_k``: (B, Sq)/(B, Sk) int32 per-token ABSOLUTE positions —
    the prefix-aware packed path, where the KV side is concat(gathered
    per-segment prefix KV, fresh packed KV) and structural indices no longer
    encode order. Causal/window masks (and their tile skips, now dynamic
    min/max range tests) use these instead. Padding kv tokens should carry a
    huge position (and segment id -1) so they are masked and their tiles
    skipped. Requires ``seg_q``/``seg_k``.

    ``debug_tile_map=True`` additionally returns a (B, nq, nk) int32 map of
    tiles that executed (1) vs were skipped (0) — test/diagnostic only.

    Caller guarantees Sq % block_q == 0 and Sk % block_k == 0."""
    B, H, Sq, d = q.shape
    _, KV, Sk, _ = k.shape
    group = H // KV
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    segmented = seg_q is not None
    assert segmented == (seg_k is not None), "seg_q and seg_k come together"
    positioned = pos_q is not None
    assert positioned == (pos_k is not None), "pos_q and pos_k come together"
    assert not positioned or segmented, "per-token positions require segments"
    nq, nk = Sq // bq, Sk // bk
    if scale is None:
        scale = d ** -0.5
    if kv_valid is not None and kv_valid >= Sk:
        kv_valid = None                     # no padded kv columns: no masking
    kernel = _make_kernel(bq, bk, nk, window, softcap, scale, causal,
                          kv_valid, segmented, positioned, debug_tile_map)
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda b, h, i, j, g=group: (b, h // g, j, 0)),
    ]
    args = [q, k, v]
    # per-token ids ride as (B, 1, S) in (1, 1, block) tiles: the last two
    # block dims are then (full, multiple of 128) for any B, which Mosaic
    # requires (a (1, block) tile of a (B, S) array is refused for B > 1)
    q_ids = pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, 0, i))
    k_ids = pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j))

    def ids(x):
        return x.astype(jnp.int32).reshape(x.shape[0], 1, x.shape[1])

    if segmented:
        in_specs += [q_ids, k_ids]
        args += [ids(seg_q), ids(seg_k)]
    if positioned:
        in_specs += [q_ids, k_ids]
        args += [ids(pos_q), ids(pos_k)]
    out_specs = pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0))
    out_shape = jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype)
    if debug_tile_map:
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1, 1), lambda b, h, i, j: (b, i, j))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, nq, nk), jnp.int32)]
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    if debug_tile_map:
        return out[0], out[1]
    return out
