"""Seeded weights of a dense-block configuration, made on the device.

The layout is the benchmark's own: per-layer leaves stacked on a leading
layer axis. Each leaf of each layer is drawn from its own key,
``fold_in(fold_in(seed_key, leaf), layer)``, so one layer can be made alone
(the reference makes them one at a time) with the very values the whole
model gets. Each value is an integer (the centred sum of the four bytes of
a uniform 32-bit word, close to normal) times one float32 scale, stored in
the type it is served in.

Scales (the configuration's ``weights`` block): token embeddings
``embed_std``; RMSNorm gains ``1 + norm_std * z``; query and key
projections ``qk_gain / sqrt(fan_in)``, which sets how peaked attention is;
other projections ``1 / sqrt(fan_in)``; QKV biases ``bias_std``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

LEAF_IDS = {"embed": 1, "final_norm": 2, "ln1": 3, "ln2": 4, "wq": 5,
            "wk": 6, "wv": 7, "wo": 8, "bq": 9, "bk": 10, "bv": 11,
            "w_gate": 12, "w_up": 13, "w_down": 14}


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (wider than 32 bits too)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 62) & 0x7FFFFFFF)


def dims(m: Dict) -> Tuple[int, ...]:
    D, H, KV = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or D // H
    return D, H, KV, hd, m["d_ff"], m["vocab_size"], m["num_layers"]


def layer_shapes(m: Dict, w: Dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Per-layer leaf -> (shape, scale); RMSNorm gains are offsets from 1.
    ``m`` is the configuration's model block, ``w`` its weights block."""
    D, H, KV, hd, F, _, _ = dims(m)
    out = {
        "ln1": ((D,), w["norm_std"]),
        "ln2": ((D,), w["norm_std"]),
        "wq": ((D, H * hd), w["qk_gain"] / math.sqrt(D)),
        "wk": ((D, KV * hd), w["qk_gain"] / math.sqrt(D)),
        "wv": ((D, KV * hd), 1.0 / math.sqrt(D)),
        "wo": ((H * hd, D), 1.0 / math.sqrt(H * hd)),
        "w_gate": ((D, F), 1.0 / math.sqrt(D)),
        "w_up": ((D, F), 1.0 / math.sqrt(D)),
        "w_down": ((F, D), 1.0 / math.sqrt(F)),
    }
    if m.get("qkv_bias"):
        out["bq"] = ((H * hd,), w["bias_std"])
        out["bk"] = ((KV * hd,), w["bias_std"])
        out["bv"] = ((KV * hd,), w["bias_std"])
    return out


# the sum of the four bytes of a uniform 32-bit word, centred: an integer
# in [-510, 510], approximately normal with this standard deviation
_BYTE_SUM_STD = math.sqrt(4 * (256 ** 2 - 1) / 12)


def _draw(key, leaf: str, layer, shape, scale, dtype):
    """Integer draws times one float32 scale, rounded once to ``dtype``: no
    transcendental function, so the values do not depend on how the
    compiler fuses the call."""
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[leaf]), layer)
    bits = jax.random.bits(k, shape, jnp.uint32)
    s = sum(((bits >> (8 * i)) & 255).astype(jnp.int32) for i in range(4))
    return ((s - 510).astype(jnp.float32)
            * jnp.float32(scale / _BYTE_SUM_STD)).astype(dtype)


def make_layer(m: Dict, w: Dict, key: jax.Array, layer,
               dtype) -> Dict[str, jax.Array]:
    """One layer's leaves (traceable in ``layer``)."""
    return {name: _draw(key, name, layer, shape, scale, dtype)
            for name, (shape, scale) in layer_shapes(m, w).items()}


def make_globals(m: Dict, w: Dict, key: jax.Array,
                 dtype) -> Dict[str, jax.Array]:
    D, _, _, _, _, V, _ = dims(m)
    return {"embed": _draw(key, "embed", 0, (V, D), w["embed_std"], dtype),
            "final_norm": _draw(key, "final_norm", 0, (D,), w["norm_std"],
                                dtype)}


def make_all(m: Dict, w: Dict, seed: int, dtype) -> Dict[str, jax.Array]:
    """The whole model in one jitted call on the default device: globals plus
    every per-layer leaf stacked (layers made one after another, so no
    float32 copy of a whole stack is ever live)."""
    L = m["num_layers"]

    @jax.jit
    def fn(key):
        layers = jax.lax.map(lambda l: make_layer(m, w, key, l, dtype),
                             jnp.arange(L))
        return {**make_globals(m, w, key, dtype), **layers}

    return fn(seed_key(seed))


def to_program_tree(w: Dict[str, jax.Array], qkv_bias: bool) -> Dict:
    """The benchmark's layout as the program's parameter tree
    (``models.transformer.model_defs``; its RMSNorm computes
    ``x * (1 + weight)``, so the gain offsets map over unchanged)."""
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    if qkv_bias:
        attn.update({k: w[k] for k in ("bq", "bk", "bv")})
    return {
        "embed": {"tok": w["embed"]},
        "blocks": {"ln1": w["ln1"], "ln2": w["ln2"], "attn": attn,
                   "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")}},
        "final_norm": w["final_norm"],
    }
