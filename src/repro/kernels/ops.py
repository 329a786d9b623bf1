"""jit'd wrappers around the Pallas kernels: padding to block/MXU multiples,
GQA layout, backend selection (compiled on TPU, interpreted on the CPU test
platform, refused anywhere else).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention as _decode_kernel
from repro.kernels.flash_attention import PAD_POS
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.fused_mlp import fused_mlp as _mlp_kernel
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm_kernel


def _interpret() -> bool:
    """Mosaic kernels compile on TPU; the CPU runs them in interpret mode
    (tests). Any other backend is refused rather than silently interpreted."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas TPU kernels cannot run on backend "
                           f"{backend!r} (tpu compiles, cpu interprets)")
    return backend == "cpu"


def _pad_dim(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_t", "block_f"))
def fused_mlp(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
              w_down: jax.Array, *, block_t: int = 256,
              block_f: int = 512) -> jax.Array:
    """x: (..., T, D) -> (..., T, D); pads T to block_t and F to block_f."""
    lead = x.shape[:-2]
    T, D = x.shape[-2:]
    xf = x.reshape(-1, D)
    bt = min(block_t, max(8, xf.shape[0]))
    xp = _pad_dim(xf, 0, bt)
    bf = min(block_f, w_gate.shape[1])
    wg = _pad_dim(w_gate, 1, bf)
    wu = _pad_dim(w_up, 1, bf)
    wd = _pad_dim(w_down, 0, bf)
    out = _mlp_kernel(xp, wg, wu, wd, block_t=bt, block_f=bf,
                      interpret=_interpret())
    return out[: xf.shape[0]].reshape(*lead, T, D)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 256,
                    block_k: int = 256) -> jax.Array:
    """Layout: q (B, Sq, H, d), k/v (B, Sk, KV, d) — model-layer layout;
    transposed to the kernel's (B, heads, S, d) internally."""
    B, Sq, H, d = q.shape
    Sk = k.shape[1]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    qt = _pad_dim(qt, 2, bq)
    kt = _pad_dim(kt, 2, bk)
    vt = _pad_dim(vt, 2, bk)
    # padded kv columns must not contribute: mask them explicitly via
    # kv_valid — the causal mask alone covers them only when causal=True
    # (padded k rows have kpos > every real qpos), not for causal=False
    out = _flash_kernel(qt, kt, vt, causal=causal, window=window,
                        softcap=softcap, scale=d ** -0.5, kv_valid=Sk,
                        block_q=bq, block_k=bk, interpret=_interpret())
    return out[:, :, :Sq].transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=(
    "window", "softcap", "block_q", "block_k"))
def packed_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           seg_ids: jax.Array, *, window: int = 0,
                           softcap: float = 0.0,
                           prefix_k: jax.Array | None = None,
                           prefix_v: jax.Array | None = None,
                           prefix_seg: jax.Array | None = None,
                           positions: jax.Array | None = None,
                           prefix_positions: jax.Array | None = None,
                           block_q: int = 256,
                           block_k: int = 256) -> jax.Array:
    """Segment-restricted causal self-attention over a prepacked sequence.

    Layout: q (B, S, H, d), k/v (B, S, KV, d), seg_ids (B, S) int32 — the
    per-token segment index of each packed request (negative = padding).
    Attention is causal *within* each segment and zero across segments;
    cross-segment tiles are skipped inside the kernel (0 FLOPs).

    Prefix-aware packing (cache-HIT co-packing): ``prefix_k``/``prefix_v``
    (B, P, KV, d) is a gathered buffer of each segment's CACHED prefix KV,
    ``prefix_seg`` (B, P) the owning segment of each prefix token (negative =
    padding), ``positions`` (B, S) each packed token's absolute position in
    its own request (restarting at prefix_len per segment), and
    ``prefix_positions`` (B, P) the prefix tokens' absolute positions. The
    kernel attends over concat(prefix KV, fresh KV) with per-token position
    masks; a query block skips another segment's prefix tiles the same way it
    skips its fresh tiles.
    """
    B, Sq, H, d = q.shape
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    seg = seg_ids.astype(jnp.int32)
    with_prefix = prefix_k is not None
    if with_prefix:
        assert prefix_v is not None and prefix_seg is not None
        assert positions is not None and prefix_positions is not None
        kt = jnp.concatenate([prefix_k.transpose(0, 2, 1, 3), kt], axis=2)
        vt = jnp.concatenate([prefix_v.transpose(0, 2, 1, 3), vt], axis=2)
    Sk = kt.shape[2]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    qt = _pad_dim(qt, 2, bq)
    kt = _pad_dim(kt, 2, bk)
    vt = _pad_dim(vt, 2, bk)
    # pad segment ids with -1: padded tokens match nothing (real ids >= 0)
    seg_q = jnp.pad(seg, ((0, 0), (0, qt.shape[2] - Sq)),
                    constant_values=-1)
    seg_kv = (jnp.concatenate([prefix_seg.astype(jnp.int32), seg], axis=1)
              if with_prefix else seg)
    seg_k = jnp.pad(seg_kv, ((0, 0), (0, kt.shape[2] - Sk)),
                    constant_values=-1)
    pos_q = pos_k = None
    if with_prefix:
        pos = positions.astype(jnp.int32)
        pos_q = jnp.pad(pos, ((0, 0), (0, qt.shape[2] - Sq)))
        pos_kv = jnp.concatenate([prefix_positions.astype(jnp.int32), pos],
                                 axis=1)
        pos_k = jnp.pad(pos_kv, ((0, 0), (0, kt.shape[2] - Sk)),
                        constant_values=PAD_POS)
    out = _flash_kernel(qt, kt, vt, causal=True, window=window,
                        softcap=softcap, scale=d ** -0.5,
                        seg_q=seg_q, seg_k=seg_k, pos_q=pos_q, pos_k=pos_k,
                        block_q=bq, block_k=bk,
                        interpret=_interpret())
    return out[:, :, :Sq].transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("softcap", "block_s"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_len: jax.Array, *, softcap: float = 0.0,
                     block_s: int = 512) -> jax.Array:
    """q: (B, 1, H, d), caches: (B, S, KV, d), kv_len: (B,) -> (B, 1, H, d)."""
    B, _, H, d = q.shape
    S = k_cache.shape[1]
    KV = k_cache.shape[2]
    G = H // KV
    qh = q.reshape(B, KV, G, d)
    bs = min(block_s, S)
    kc = _pad_dim(k_cache, 1, bs)
    vc = _pad_dim(v_cache, 1, bs)
    out = _decode_kernel(qh, kc, vc, kv_len.astype(jnp.int32),
                         softcap=softcap, block_s=bs,
                         interpret=_interpret())
    return out.reshape(B, 1, H, d)


@functools.partial(jax.jit, static_argnames=("eps", "block_t"))
def rmsnorm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6,
            block_t: int = 256) -> jax.Array:
    """x: (..., D) -> (..., D); pads the token dim to block_t."""
    lead = x.shape[:-1]
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    bt = min(block_t, max(8, xf.shape[0]))
    xp = _pad_dim(xf, 0, bt)
    out = _rmsnorm_kernel(xp, weight, eps=eps, block_t=bt,
                          interpret=_interpret())
    return out[: xf.shape[0]].reshape(*lead, D)
