"""The GraniteMoeHybrid block with no experts (granite-4.0-h-micro): a
published pattern of Mamba-2 and NoPE grouped-query attention mixers, each
layer followed by its own SwiGLU MLP.

Per layer: RMSNorm, mixer, residual add of ``residual_multiplier`` times
the mixer's output; RMSNorm, MLP, residual add of ``residual_multiplier``
times its output. Embeddings are multiplied by ``embedding_multiplier``; the
LM head is the tied embedding over ``logits_scaling``. Attention has no
position embedding and scores scaled by ``attention_multiplier``. The
Mamba-2 mixer (arXiv:2405.21060, one group): in-projection to z, x, B, C
and dt; a causal depthwise conv of width ``ssm_conv_width`` with bias over
(x, B, C), then SiLU; dt = softplus(dt + dt_bias), A = -exp(A_log); the
scan h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t; a
gated RMSNorm of y * SiLU(z); out-projection.

``make_params`` gives the program's tree (``models.hybrid`` layout);
``label_logits`` is the plain float32 reference, which imports nothing of
the program and makes its weights layer by layer from the same draws, at
``Precision.HIGHEST``. Its scan is a chunked form of the recurrence
(``ssd_chunked``), tied to the token-by-token recurrence (``ssd_tokens``)
by a CPU test. ``quant="fp8"`` is the control: every matmul operand and the
attention keys and values rounded to float8 e4m3 with one scale a tensor.

Weights (the configuration's ``weights`` block): embeddings ``embed_std``;
RMSNorm gains ``1 + norm_std * z``; projections ``1 / sqrt(fan_in)``; the
conv ``conv_std``, its bias ``bias_std``; A uniform in [1, 16] and dt
log-uniform in [0.001, 0.1] (Mamba-2's init ranges; dt_bias is softplus's
inverse of dt), drawn on the host in float64; D ones.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
REF_CHUNK = 128          # the reference's scan chunk (the program's is 256)

LEAF_IDS = {"embed": 1, "final_norm": 2, "ln1": 3, "ln2": 4, "wq": 5,
            "wk": 6, "wv": 7, "wo": 8, "w_gate": 12, "w_up": 13,
            "w_down": 14, "in_z": 21, "in_x": 22, "in_B": 23, "in_C": 24,
            "in_dt": 25, "conv_w": 26, "conv_b": 27, "A_log": 28,
            "dt_bias": 29, "norm": 30, "out": 31}
MAMBA = ("in_z", "in_x", "in_B", "in_C", "in_dt", "conv_w", "conv_b",
         "A_log", "D", "dt_bias", "norm", "out")


def dims(m: Dict) -> Dict[str, int]:
    D = m["d_model"]
    di = m.get("ssm_expand", 2) * D
    P = m.get("ssm_headdim", 64)
    return {"D": D, "H": m["num_heads"], "KV": m["num_kv_heads"],
            "hd": m.get("head_dim") or D // m["num_heads"], "F": m["d_ff"],
            "V": m["vocab_size"], "di": di, "N": m["ssm_state"], "P": P,
            "Hs": di // P, "W": m.get("ssm_conv_width", 4),
            "Q": m.get("ssm_chunk", 256)}


def layer_numbers(m: Dict, kind: str) -> List[int]:
    """Global layer numbers of the layers of one kind, in order."""
    return [i for i, t in enumerate(m["layer_types"]) if t == kind]


def _shapes(m: Dict, w: Dict, kind: str) -> Dict[str, Tuple[tuple, float]]:
    """Integer-drawn leaves of one layer -> (shape, scale)."""
    d = dims(m)
    D, F, di, N, Hs = d["D"], d["F"], d["di"], d["N"], d["Hs"]
    out = {"ln1": ((D,), w["norm_std"]), "ln2": ((D,), w["norm_std"]),
           "w_gate": ((D, F), 1 / math.sqrt(D)),
           "w_up": ((D, F), 1 / math.sqrt(D)),
           "w_down": ((F, D), 1 / math.sqrt(F))}
    if kind == "attention":
        H, KV, hd = d["H"], d["KV"], d["hd"]
        out.update({"wq": ((D, H * hd), 1 / math.sqrt(D)),
                    "wk": ((D, KV * hd), 1 / math.sqrt(D)),
                    "wv": ((D, KV * hd), 1 / math.sqrt(D)),
                    "wo": ((H * hd, D), 1 / math.sqrt(H * hd))})
    else:
        conv = di + 2 * N
        out.update({"in_z": ((D, di), 1 / math.sqrt(D)),
                    "in_x": ((D, di), 1 / math.sqrt(D)),
                    "in_B": ((D, N), 1 / math.sqrt(D)),
                    "in_C": ((D, N), 1 / math.sqrt(D)),
                    "in_dt": ((D, Hs), 1 / math.sqrt(D)),
                    "conv_w": ((d["W"], conv), w["conv_std"]),
                    "conv_b": ((conv,), w["bias_std"]),
                    "norm": ((di,), w["norm_std"]),
                    "out": ((di, D), 1 / math.sqrt(di))})
    return out


def _draw(key, leaf: str, layer, shape, scale, dtype):
    """``weights._draw`` with this module's leaf ids: integers times one
    float32 scale, rounded once to ``dtype``."""
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[leaf]), layer)
    bits = jax.random.bits(k, shape, jnp.uint32)
    s = sum(((bits >> (8 * i)) & 255).astype(jnp.int32) for i in range(4))
    return ((s - 510).astype(jnp.float32)
            * jnp.float32(scale / W._BYTE_SUM_STD)).astype(dtype)


def _layer(m: Dict, w: Dict, key, layer, kind: str, dtype) -> Dict:
    return {n: _draw(key, n, layer, shape, scale, dtype)
            for n, (shape, scale) in _shapes(m, w, kind).items()}


def _decay_params(m: Dict, key, layers: Sequence[int]) -> Dict[str, np.ndarray]:
    """A_log and dt_bias of the given layers, (len(layers), heads) float64:
    uniform draws mapped through Mamba-2's init on the host."""
    Hs = dims(m)["Hs"]
    out = {}
    for leaf in ("A_log", "dt_bias"):
        rows = []
        for l in layers:
            k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[leaf]), l)
            u = (np.asarray(jax.random.bits(k, (Hs,), jnp.uint32),
                            np.float64) + 0.5) / 2.0 ** 32
            if leaf == "A_log":
                rows.append(np.log(1.0 + 15.0 * u))
            else:
                dt = np.exp(np.log(1e-3) + u * (np.log(1e-1) - np.log(1e-3)))
                rows.append(dt + np.log(-np.expm1(-dt)))
        out[leaf] = np.stack(rows)
    return out


def _globals(m: Dict, w: Dict, key, dtype) -> Dict:
    d = dims(m)
    return {"embed": _draw(key, "embed", 0, (d["V"], d["D"]), w["embed_std"],
                           dtype),
            "final_norm": _draw(key, "final_norm", 0, (d["D"],),
                                w["norm_std"], dtype)}


def make_params(m: Dict, w: Dict, seed: int, dtype) -> Dict:
    """The program's parameter tree (``models.hybrid`` pattern layout), on
    the default device: each kind's layers made one after another in one
    jitted call."""
    key = W.seed_key(seed)
    dtype = jnp.dtype(dtype)

    def stack(kind):
        nums = jnp.asarray(layer_numbers(m, kind), jnp.int32)
        return jax.jit(lambda k, ls: jax.lax.map(
            lambda l: _layer(m, w, k, l, kind, dtype), ls))(key, nums)

    mam, att = stack("mamba"), stack("attention")
    dec = _decay_params(m, key, layer_numbers(m, "mamba"))
    mlp = lambda t: {k: t[k] for k in ("w_gate", "w_up", "w_down")}
    g = jax.jit(lambda k: _globals(m, w, k, dtype))(key)
    Hs = dims(m)["Hs"]
    mixer = {k: mam[k] for k in MAMBA if k in mam}
    mixer["A_log"] = jnp.asarray(dec["A_log"], dtype)
    mixer["dt_bias"] = jnp.asarray(dec["dt_bias"], dtype)
    mixer["D"] = jnp.ones((len(layer_numbers(m, "mamba")), Hs), dtype)
    return {
        "embed": {"tok": g["embed"]},
        "mamba_layers": {"ln1": mam["ln1"], "mamba": mixer,
                         "ln2": mam["ln2"], "mlp": mlp(mam)},
        "attn_layers": {"ln1": att["ln1"],
                        "attn": {k: att[k] for k in ("wq", "wk", "wv", "wo")},
                        "ln2": att["ln2"], "mlp": mlp(att)},
        "final_norm": g["final_norm"],
    }


# ---- the plain float32 reference ---------------------------------------

def padded_len(n: int) -> int:
    """Prompt length padded to a few shapes (padding follows the last real
    token, so causal attention and the causal scan leave it untouched).
    A call pads every prompt to its longest's shape: one compile a kind."""
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


def ssd_tokens(x, dt, A, Bm, Cm, h0=None):
    """The recurrence one token at a time. x: (T, H, P), dt: (T, H), A:
    (H,), Bm/Cm: (T, N). Returns (y (T, H, P), final state (H, P, N))."""
    T, H, P = x.shape
    h0 = jnp.zeros((H, P, Bm.shape[-1]), jnp.float32) if h0 is None else h0

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = (jnp.exp(dtt * A)[:, None, None] * h
             + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        return h, jnp.einsum("hpn,n->hp", h, ct, precision=HI)

    h, y = jax.lax.scan(step, h0, (x, dt, Bm, Cm))
    return y, h


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int = REF_CHUNK, h0=None):
    """The same recurrence in chunks of ``chunk`` tokens: within a chunk as
    a masked product over (target, source) pairs, across chunks through the
    carried state. T must be a multiple of ``chunk``."""
    T, H, P = x.shape
    N = Bm.shape[-1]
    n = T // chunk
    h0 = jnp.zeros((H, P, N), jnp.float32) if h0 is None else h0
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(h, inp):
        xc, dtc, bc, cc = inp                        # (Q, ...)
        cum = jnp.cumsum(dtc * A, axis=0)            # (Q, H), log decay
        gap = jnp.where(causal[:, :, None], cum[:, None, :] - cum[None, :, :],
                        -jnp.inf)                    # (t, s, H)
        cb = jnp.einsum("tn,sn->ts", cc, bc, precision=HI)
        wts = cb[:, :, None] * jnp.exp(gap) * dtc[None, :, :]
        y = jnp.einsum("tsh,shp->thp", wts, xc, precision=HI)
        y = y + jnp.exp(cum)[:, :, None] * jnp.einsum(
            "tn,hpn->thp", cc, h, precision=HI)
        to_end = jnp.exp(cum[-1][None, :] - cum) * dtc     # (Q, H)
        h = (jnp.exp(cum[-1])[:, None, None] * h
             + jnp.einsum("sh,shp,sn->hpn", to_end, xc, bc, precision=HI))
        return h, y

    split = lambda a: a.reshape(n, chunk, *a.shape[1:])
    h, ys = jax.lax.scan(step, h0, tuple(map(split, (x, dt, Bm, Cm))))
    return ys.reshape(T, H, P), h


@functools.lru_cache(maxsize=None)
def _mamba_fn(T: int, dkey: Tuple, eps: float, rm: float, quant: bool):
    d = dict(dkey)
    di, N, Hs, P, Wc = d["di"], d["N"], d["Hs"], d["P"], d["W"]

    @jax.jit
    def block(lw, x):
        h = _rms(x, lw["ln1"], eps)
        z = _mm(h, lw["in_z"], quant)
        xBC = jnp.concatenate([_mm(h, lw["in_x"], quant),
                               _mm(h, lw["in_B"], quant),
                               _mm(h, lw["in_C"], quant)], axis=-1)
        dt = _mm(h, lw["in_dt"], quant)
        padded = jnp.pad(xBC, ((Wc - 1, 0), (0, 0)))
        conv = lw["conv_b"] + sum(padded[i:i + T] * lw["conv_w"][i]
                                  for i in range(Wc))
        xBC = jax.nn.silu(conv)
        xs, Bm, Cm = xBC[:, :di], xBC[:, di:di + N], xBC[:, di + N:]
        dt = jax.nn.softplus(dt + lw["dt_bias"])
        A = -jnp.exp(lw["A_log"])
        xh = xs.reshape(T, Hs, P)
        y, _ = ssd_chunked(xh, dt, A, Bm, Cm)
        y = (y + lw["D"][None, :, None] * xh).reshape(T, di)
        y = _rms(y * jax.nn.silu(z), lw["norm"], eps)
        x = x + rm * _mm(y, lw["out"], quant)
        return _mlp(lw, x, eps, rm, quant)

    return block


def _mlp(lw, x, eps, rm, quant):
    h = _rms(x, lw["ln2"], eps)
    g = _mm(h, lw["w_gate"], quant)
    u = _mm(h, lw["w_up"], quant)
    return x + rm * _mm(jax.nn.silu(g) * u, lw["w_down"], quant)


@functools.lru_cache(maxsize=None)
def _attn_fn(T: int, dkey: Tuple, eps: float, rm: float, scale: float,
             quant: bool, q_block: int):
    d = dict(dkey)
    H, KV, hd = d["H"], d["KV"], d["hd"]
    G = H // KV
    qb = math.gcd(q_block, T)

    @jax.jit
    def block(lw, x):
        h = _rms(x, lw["ln1"], eps)
        q = _mm(h, lw["wq"], quant).reshape(T, H, hd)
        k = _mm(h, lw["wk"], quant).reshape(T, KV, hd)
        v = _mm(h, lw["wv"], quant).reshape(T, KV, hd)
        if quant:
            k, v = q8(k), q8(v)
        q = (q * scale).reshape(T // qb, qb, KV, G, hd)

        def attend(i):
            s = jnp.einsum("qkgd,skd->kgqs", q[i], k, precision=HI)
            causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(T)
            s = jnp.where(causal[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)
            return o.reshape(qb, H * hd)

        o = jax.lax.map(attend, jnp.arange(T // qb)).reshape(T, H * hd)
        x = x + rm * _mm(o, lw["wo"], quant)
        return _mlp(lw, x, eps, rm, quant)

    return block


def label_logits(cfg: Dict, seed: int, prompts: Sequence[Sequence[int]],
                 labels: Sequence[int], quant: Optional[str] = None,
                 q_block: int = 512) -> np.ndarray:
    """Logits of ``labels`` at the last position of each prompt:
    (len(prompts), len(labels)) float64. ``cfg`` is a configuration file's
    contents."""
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown control precision {quant!r}")
    m, ws = cfg["model"], cfg["weights"]
    d = dims(m)
    dkey = tuple(sorted(d.items()))
    dtype = jnp.dtype(m.get("param_dtype", "bfloat16"))
    eps = float(m["norm_eps"])
    rm = float(m["residual_multiplier"])
    scale = float(m.get("attention_multiplier") or d["hd"] ** -0.5)
    use_q = quant == "fp8"
    key = W.seed_key(seed)
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), tree)
    glob = f32(jax.jit(lambda k: _globals(m, ws, k, dtype))(key))
    dec = _decay_params(m, key, range(m["num_layers"]))
    layer_fn = {kind: jax.jit(lambda k, l, kind=kind: _layer(
        m, ws, k, l, kind, dtype)) for kind in ("mamba", "attention")}
    xs: List[jax.Array] = []
    T = max((padded_len(len(p)) for p in prompts), default=0)
    for p in prompts:
        toks = np.zeros((T,), np.int32)
        toks[:len(p)] = p
        xs.append(jnp.take(glob["embed"], jnp.asarray(toks), axis=0)
                  * float(m["embedding_multiplier"]))
    with jax.default_matmul_precision("highest"):
        for l, kind in enumerate(m["layer_types"]):
            lw = f32(layer_fn[kind](key, l))
            if kind == "mamba":
                # the program stores these in its parameter type: round alike
                for leaf in ("A_log", "dt_bias"):
                    lw[leaf] = jnp.asarray(jnp.asarray(
                        dec[leaf][l], dtype), jnp.float32)
                lw["D"] = jnp.ones((d["Hs"],), jnp.float32)
            for i, x in enumerate(xs):
                T = x.shape[0]
                blk = (_mamba_fn(T, dkey, eps, rm, use_q) if kind == "mamba"
                       else _attn_fn(T, dkey, eps, rm, scale, use_q,
                                     q_block))
                xs[i] = blk(lw, x)
            del lw
        head = glob["embed"][jnp.asarray(list(labels))]          # (K, D)
        out = []
        for p, x in zip(prompts, xs):
            h = _rms(x[len(p) - 1], glob["final_norm"], eps)
            out.append(np.asarray(_mm(h[None], head.T, use_q)[0], np.float64)
                       / float(m["logits_scaling"]))
    return np.stack(out)


# ---- operations a scored request needs -----------------------------------

def request_flops(m: Dict, computed: int, cached: int) -> float:
    """FLOPs one scored request needs with ``computed`` tokens over
    ``cached`` prefix tokens: the in/out projections, conv and SSD of each
    Mamba layer (the scan by chunks of the published chunk: the causal half
    of each chunk's (target, source) pairs, and the state read and carried
    per token), each attention layer's projections and causal scores and
    values, every layer's MLP, and one LM-head row."""
    d = dims(m)
    D, F, V = d["D"], d["F"], d["V"]
    di, N, Hs, P, Wc, Q = d["di"], d["N"], d["Hs"], d["P"], d["W"], d["Q"]
    H, KV, hd = d["H"], d["KV"], d["hd"]
    c, p = int(computed), int(cached)
    n_m = len(layer_numbers(m, "mamba"))
    n_a = len(layer_numbers(m, "attention"))
    proj = D * (2 * di + 2 * N + Hs) + di * D
    full, rem = divmod(c, Q)
    pairs = full * Q * (Q + 1) / 2.0 + rem * (rem + 1) / 2.0
    ssd = 2.0 * (N + Hs * P) * pairs + 4.0 * Hs * P * N * c
    mamba = 2.0 * proj * c + 2.0 * Wc * (di + 2 * N) * c + ssd
    attn = (2.0 * (2 * D * H * hd + 2 * D * KV * hd) * c
            + 4.0 * H * hd * (c * p + c * (c + 1) / 2.0))
    mlp = 2.0 * 3 * D * F * c
    return n_m * mamba + n_a * attn + (n_m + n_a) * mlp + 2.0 * D * V
