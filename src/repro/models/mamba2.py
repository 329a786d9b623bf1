"""Mamba2 (SSD — state-space duality) blocks. [arXiv:2405.21060]

Chunked SSD: within a chunk the recurrence is evaluated as a (Q, Q) masked
attention-like product; across chunks a scan carries the (H, P, N) state.
The scan processes ONE chunk at a time so the (B, H, Q, Q) intra-chunk matrix
never exists for more than one chunk — the SSM analogue of hybrid prefilling.

PrefillOnly applicability (DESIGN.md §Arch-applicability): attention-free —
no KV cache exists, so suffix-KV discard is vacuous; the O(1) per-layer state
doubles as the prefix cache (state checkpoints at block boundaries). The
in/out projections are token-wise and run under hybrid chunking.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.hybrid_prefill import chunked_map
from repro.runtime.sharding import constrain, pdef


def mamba_defs(cfg: ModelConfig) -> Dict:
    D, di, N = cfg.d_model, cfg.d_inner, cfg.ssm_state
    H, W = cfg.ssm_heads, cfg.ssm_conv_width
    conv_dim = di + 2 * N
    return {
        "in_z": pdef((D, di), ("d_model", "ssm_inner"), init="scaled"),
        "in_x": pdef((D, di), ("d_model", "ssm_inner"), init="scaled"),
        "in_B": pdef((D, N), ("d_model", "state"), init="scaled"),
        "in_C": pdef((D, N), ("d_model", "state"), init="scaled"),
        "in_dt": pdef((D, H), ("d_model", "ssm_heads"), init="scaled"),
        "conv_w": pdef((W, conv_dim), ("conv", "ssm_inner"), init="scaled"),
        "conv_b": pdef((conv_dim,), ("ssm_inner",), init="zeros"),
        "A_log": pdef((H,), ("ssm_heads",), init="zeros"),
        "D": pdef((H,), ("ssm_heads",), init="ones"),
        "dt_bias": pdef((H,), ("ssm_heads",), init="zeros"),
        "norm": pdef((di,), ("ssm_inner",), init="zeros"),
        "out": pdef((di, D), ("ssm_inner", "d_model"), init="scaled"),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (B, S, C), w: (W, C)."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(W):  # W is tiny (4): unrolled taps beat a conv op on TPU
        out = out + xp[:, i:i + x.shape[1], :].astype(jnp.float32) * w[i].astype(jnp.float32)
    return out + b.astype(jnp.float32)


def ssd_scan(x: jax.Array, dA: jax.Array, Bm: jax.Array, Cm: jax.Array,
             dt: jax.Array, chunk: int,
             h0: Optional[jax.Array] = None,
             snap_every: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD. x: (B,S,H,P), dA: (B,S,H) (negative log-decay increments),
    Bm/Cm: (B,S,N), dt: (B,S,H). Returns (y: (B,S,H,P), final state (B,H,P,N)).

    ``snap_every`` (a multiple of the chunk): return in place of the final
    state the state after every ``snap_every`` tokens, (B, ceil(S /
    snap_every), H, P, N), read from the carry of an outer scan over groups
    of chunks, so only those states are ever stored. Chunks past the end
    are zeros, which leave the state as it is."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // Q

    k = 0
    if snap_every:
        assert snap_every % Q == 0, (snap_every, Q)
        k = snap_every // Q
        extra = (-nc) % k
        if extra:
            grow = lambda a: jnp.pad(a, ((0, 0), (0, extra * Q))
                                     + ((0, 0),) * (a.ndim - 2))
            x, dA, Bm, Cm, dt = map(grow, (x, dA, Bm, Cm, dt))
            nc += extra

    def to_chunks(a):
        return jnp.moveaxis(a.reshape(B, nc, Q, *a.shape[2:]), 1, 0)

    xs = (to_chunks(x), to_chunks(dA), to_chunks(Bm), to_chunks(Cm), to_chunks(dt))
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)

    def step(h, inp):
        x_c, dA_c, B_c, C_c, dt_c = inp          # (B,Q,...)
        cum = jnp.cumsum(dA_c, axis=1)           # (B,Q,H)
        # contribution of the incoming state (inter-chunk)
        y_off = jnp.einsum("bqn,bhpn,bqh->bqhp", C_c.astype(jnp.float32), h,
                           jnp.exp(cum))
        # intra-chunk masked "attention"
        seg = cum[:, :, None, :] - cum[:, None, :, :]           # (B,Q,Q,H) i-j
        tri = jnp.tril(jnp.ones((Q, Q), bool))
        L = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)
        scores = jnp.einsum("bqn,bsn->bqs", C_c.astype(jnp.float32),
                            B_c.astype(jnp.float32))
        M = scores[..., None] * L * dt_c[:, None, :, :]          # dt at source
        y_diag = jnp.einsum("bqsh,bshp->bqhp", M, x_c.astype(jnp.float32))
        # state handoff
        decay_end = jnp.exp(cum[:, -1:, :] - cum)                # (B,Q,H)
        h_new = (h * jnp.exp(cum[:, -1])[:, :, None, None]
                 + jnp.einsum("bqn,bqh,bqhp->bhpn", B_c.astype(jnp.float32),
                              dt_c * decay_end, x_c.astype(jnp.float32)))
        return h_new, y_off + y_diag

    if k:
        def group(h, xs_g):
            h, ys_g = jax.lax.scan(step, h, xs_g)
            return h, (ys_g, h)

        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape(nc // k, k, *a.shape[1:]), xs)
        _, (ys, snaps) = jax.lax.scan(group, h0, grouped)
        ys = ys.reshape(nc, *ys.shape[2:])
        h_out = jnp.moveaxis(snaps, 0, 1)                    # (B, n, H, P, N)
    else:
        h_out, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, nc * Q, H, P)
    if y.shape[1] != S:
        y = y[:, :S]
    return y, h_out


def mamba_prefill(p: Dict, u: jax.Array, cfg: ModelConfig, *,
                  chunk: int = 0, h0: Optional[jax.Array] = None,
                  conv0: Optional[jax.Array] = None, snap_every: int = 0,
                  n_snap: int = 0
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence Mamba2 block. u: (B, S, D).
    Returns (out, final_ssm_state (B,H,P,N), final_conv_state (B,W-1,conv_dim)).

    With ``n_snap`` > 0 the two states are instead the snapshots after
    tokens ``snap_every * (j + 1)``, j < n_snap, of this call, stacked on
    axis 1: (B, n_snap, H, P, N) and (B, n_snap, W-1, conv_dim). Resuming
    from one (``h0``, ``conv0``) continues the sequence exactly there.
    """
    B, S, D = u.shape
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    W = cfg.ssm_conv_width

    def in_proj(uc):
        return jnp.concatenate(
            [uc @ p["in_z"], uc @ p["in_x"], uc @ p["in_B"], uc @ p["in_C"],
             uc @ p["in_dt"]], axis=-1)

    zxbcdt = chunked_map(in_proj, u, chunk)
    z, xr, Bm, Cm, dt = jnp.split(zxbcdt, [di, 2 * di, 2 * di + N, 2 * di + 2 * N],
                                  axis=-1)
    xBC = jnp.concatenate([xr, Bm, Cm], axis=-1)
    if conv0 is not None:
        xBC_ext = jnp.concatenate([conv0.astype(xBC.dtype), xBC], axis=1)
        conv_out = _causal_conv(xBC_ext, p["conv_w"], p["conv_b"])[:, W - 1:]
    else:
        conv_out = _causal_conv(xBC, p["conv_w"], p["conv_b"])
        # left-pad so the returned conv state is always (B, W-1, Cd)
        xBC_ext = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    if n_snap:
        # xBC_ext[t + i] is input t - (W-1) + i: the conv's window at t
        conv_state = jnp.stack([xBC_ext[:, t:t + W - 1, :] for t in range(
            snap_every, snap_every * (n_snap + 1), snap_every)], axis=1)
    else:
        conv_state = xBC_ext[:, -(W - 1):, :]
    xBC = jax.nn.silu(conv_out).astype(u.dtype)
    xr, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)
    xh = xr.reshape(B, S, H, P)
    xh = constrain(xh, ("batch", "seq", "ssm_heads", None))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                 # (H,)
    y, h_final = ssd_scan(xh, dt * A, Bm, Cm, dt, cfg.ssm_chunk, h0=h0,
                          snap_every=snap_every if n_snap else 0)
    if n_snap:
        h_final = h_final[:, :n_snap]
    y = y + (p["D"].astype(jnp.float32))[None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B, S, di)
    from repro.models.layers import rms_norm
    y = rms_norm((y * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype),
                 p["norm"], cfg.norm_eps)
    out = chunked_map(lambda yc: yc @ p["out"], y, chunk)
    return constrain(out, ("batch", "seq", "d_model")), h_final, conv_state.astype(u.dtype)


def mamba_decode(p: Dict, u: jax.Array, cfg: ModelConfig, *,
                 h: jax.Array, conv_state: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token step. u: (B, 1, D); h: (B,H,P,N); conv_state: (B,W-1,Cd)."""
    B = u.shape[0]
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    W = cfg.ssm_conv_width
    u1 = u[:, 0, :]
    z = u1 @ p["in_z"]
    xr = u1 @ p["in_x"]
    Bm = u1 @ p["in_B"]
    Cm = u1 @ p["in_C"]
    dt = u1 @ p["in_dt"]
    xBC = jnp.concatenate([xr, Bm, Cm], axis=-1)                 # (B, Cd)
    window = jnp.concatenate([conv_state, xBC[:, None, :]], axis=1)  # (B,W,Cd)
    conv_out = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32),
                          p["conv_w"].astype(jnp.float32)) + p["conv_b"].astype(jnp.float32)
    new_conv_state = window[:, 1:, :].astype(conv_state.dtype)
    xBC = jax.nn.silu(conv_out)
    xr, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)
    xh = xr.reshape(B, H, P)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))  # (B,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    decay = jnp.exp(dt * A)                                      # (B,H)
    h_new = (h * decay[:, :, None, None]
             + jnp.einsum("bn,bh,bhp->bhpn", Bm, dt, xh))
    y = jnp.einsum("bn,bhpn->bhp", Cm, h_new)
    y = y + p["D"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(B, di)
    from repro.models.layers import rms_norm
    y = rms_norm((y * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype), p["norm"])
    out = (y @ p["out"])[:, None, :]
    return out.astype(u.dtype), h_new, new_conv_state
