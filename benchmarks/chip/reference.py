"""Plain float32 reference of the dense decoder block, as a configuration
states it.

Imports nothing of the program and takes nothing it made: weights come from
``weights.py`` and the seed, one layer at a time, upcast to float32. Every
matrix product runs at ``Precision.HIGHEST``. Per layer: RMSNorm (gain
``1 + offset``, the configuration's epsilon), Q/K/V projections with bias
where the configuration sets it, split-half RoPE, causal grouped-query
attention with softmax in float32, output projection, RMSNorm, SwiGLU MLP,
residual adds; then a final RMSNorm and the tied embedding as the LM head,
read at the last prompt position only. Attention runs one block of queries
at a time against all keys under the causal mask, so a 32k-token prompt
fits.

``quant="fp8"`` is the control: the same computation with every matmul
operand (weights and activations of the linear layers and the LM head) and
the attention keys and values rounded to float8 e4m3 with one scale per
tensor, the step below the bfloat16 the configurations serve in.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def padded_len(n: int) -> int:
    """Prompt length padded to a few shapes, each compiled once per checkout
    (padding sits after the last real token, so causal attention leaves the
    real positions untouched)."""
    step = 4096 if n > 4096 else 1024
    return -(-n // step) * step


def q8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, quant):
    if quant:
        a, b = q8(a), q8(b)
    return jnp.matmul(a, b, precision=HI)


def _rms(x, gain_offset, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gain_offset)


def _rope(x, theta):
    """x: (T, heads, hd); rotate-half RoPE at positions 0..T-1."""
    T, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _block_fn(T: int, D: int, H: int, KV: int, hd: int, theta: float,
              eps: float, bias: bool, quant: bool, q_block: int):
    G = H // KV
    qb = math.gcd(q_block, T)

    @jax.jit
    def block(lw, x):
        h = _rms(x, lw["ln1"], eps)
        q = _mm(h, lw["wq"], quant)
        k = _mm(h, lw["wk"], quant)
        v = _mm(h, lw["wv"], quant)
        if bias:
            q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
        q = _rope(q.reshape(T, H, hd), theta)
        k = _rope(k.reshape(T, KV, hd), theta)
        v = v.reshape(T, KV, hd)
        if quant:
            k, v = q8(k), q8(v)
        q = (q * (hd ** -0.5)).reshape(T // qb, qb, KV, G, hd)

        def attend(i):
            s = jnp.einsum("qkgd,skd->kgqs", q[i], k, precision=HI)
            causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(T)
            s = jnp.where(causal[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)
            return o.reshape(qb, H * hd)

        o = jax.lax.map(attend, jnp.arange(T // qb)).reshape(T, H * hd)
        x = x + _mm(o, lw["wo"], quant)
        h = _rms(x, lw["ln2"], eps)
        g = _mm(h, lw["w_gate"], quant)
        u = _mm(h, lw["w_up"], quant)
        return x + _mm(jax.nn.silu(g) * u, lw["w_down"], quant)

    return block


def label_logits(cfg: Dict, seed: int, prompts: Sequence[Sequence[int]],
                 labels: Sequence[int], quant: Optional[str] = None,
                 q_block: int = 512) -> np.ndarray:
    """Logits of ``labels`` at the last position of each prompt:
    (len(prompts), len(labels)) float64. ``cfg`` is a configuration file's
    contents."""
    m, ws = cfg["model"], cfg["weights"]
    D, H, KV, hd, _, _, L = W.dims(m)
    dtype = jnp.dtype(m.get("param_dtype", "bfloat16"))
    eps, theta = float(cfg["rms_norm_eps"]), float(m["rope_theta"])
    bias, use_q = bool(m.get("qkv_bias")), quant == "fp8"
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown control precision {quant!r}")
    key = W.seed_key(seed)
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), tree)
    glob = f32(jax.jit(lambda k: W.make_globals(m, ws, k, dtype))(key))
    layer_fn = jax.jit(lambda k, l: W.make_layer(m, ws, k, l, dtype))
    xs: List[jax.Array] = []
    for p in prompts:
        T = padded_len(len(p))
        toks = np.zeros((T,), np.int32)
        toks[:len(p)] = p
        xs.append(jnp.take(glob["embed"], jnp.asarray(toks), axis=0))
    for l in range(L):
        lw = f32(layer_fn(key, l))
        for i, x in enumerate(xs):
            blk = _block_fn(x.shape[0], D, H, KV, hd, theta, eps, bias,
                            use_q, q_block)
            xs[i] = blk(lw, x)
        del lw
    head = glob["embed"][jnp.asarray(list(labels))]          # (K, D)
    out = []
    for p, x in zip(prompts, xs):
        h = _rms(x[len(p) - 1], glob["final_norm"], eps)
        out.append(np.asarray(_mm(h[None], head.T, use_q)[0], np.float64))
    return np.stack(out)
