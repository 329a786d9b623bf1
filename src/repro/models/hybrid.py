"""Hybrid Mamba2 + attention stacks, in two layouts.

Zamba2-style (``attn_every``): Mamba2 backbone + ONE shared attention block
applied every ``attn_every`` layers (shared weights, distinct KV per
application). Structure: ``num_layers`` mamba blocks grouped as (G groups x
attn_every); after each group the shared attention+MLP block runs.
Simplification vs the released checkpoints (concat-with-embedding input,
per-application LoRA) is recorded in DESIGN.md — the systems-relevant
property (shared weights, hybrid KV/state caching) is preserved.

Published pattern (``layer_types``, GraniteMoeHybrid): each layer is
RMSNorm -> mixer (Mamba2 or attention) -> residual, RMSNorm -> SwiGLU MLP ->
residual, each branch scaled by ``residual_multiplier``; embeddings times
``embedding_multiplier``, logits over ``logits_scaling``. ``pattern_prefill``
serves it: the fresh forward, or the resume of a suffix from a cached
prefix (the attention layers' prefix KV and one snapshot of every Mamba
layer's state), returning the kept attention KV and the Mamba states at
every ``snap_every`` tokens for the prefix cache.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.hybrid_prefill import (chunked_map, chunked_softmax_xent,
                                       last_token_logits, valid_rows)
from repro.models import layers as L
from repro.models.mamba2 import mamba_defs, mamba_prefill, mamba_decode
from repro.models.transformer import stack_defs, head_weight
from repro.runtime.sharding import pdef


def _n_groups(cfg: ModelConfig) -> int:
    assert cfg.num_layers % cfg.attn_every == 0, (cfg.num_layers, cfg.attn_every)
    return cfg.num_layers // cfg.attn_every


def model_defs(cfg: ModelConfig) -> Dict:
    if cfg.layer_types:
        return _pattern_defs(cfg)
    mamba_block = {
        "ln": pdef((cfg.d_model,), ("d_model",), init="zeros"),
        "mamba": mamba_defs(cfg),
    }
    shared = {
        "ln1": pdef((cfg.d_model,), ("d_model",), init="zeros"),
        "ln2": pdef((cfg.d_model,), ("d_model",), init="zeros"),
        "attn": L.attention_defs(cfg),
        "mlp": L.mlp_defs(cfg.d_model, cfg.d_ff_shared),
    }
    out: Dict[str, Any] = {
        "embed": L.embed_defs(cfg),
        # grouped (G, attn_every, ...) for the nested scan
        "blocks": stack_defs(stack_defs(mamba_block, cfg.attn_every),
                             _n_groups(cfg)),
        "shared": shared,
        "final_norm": pdef((cfg.d_model,), ("d_model",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = pdef((cfg.d_model, cfg.vocab_size),
                              ("d_model", "vocab"), init="scaled")
    return out


def _shared_attn_full(params: Dict, x: jax.Array, cfg: ModelConfig,
                      positions: jax.Array, kv_keep: int):
    sp = params["shared"]
    h = L.rms_norm(x, sp["ln1"])
    attn, k, v = L.attention_prefill(sp["attn"], h, cfg, positions=positions,
                                     chunk=cfg.hybrid_chunk)
    x = x + attn
    h = L.rms_norm(x, sp["ln2"])
    x = x + L.mlp_apply(sp["mlp"], h, chunk=cfg.hybrid_chunk)
    kv = (k[:, :kv_keep], v[:, :kv_keep]) if kv_keep > 0 else None
    return x, kv


def forward_full(params: Dict, cfg: ModelConfig, *,
                 tokens: Optional[jax.Array] = None,
                 embeds: Optional[jax.Array] = None,
                 kv_keep: int = 0, collect_state: bool = False,
                 remat: bool = False) -> Tuple[jax.Array, Optional[Dict]]:
    dtype = jnp.dtype(cfg.dtype)
    x = (L.embed_apply(params["embed"], tokens, dtype)
         if embeds is None else embeds.astype(dtype))
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    keep = min(kv_keep, S)

    def mamba_one(x, bp):
        def fn(x):
            h = L.rms_norm(x, bp["ln"])
            out, hf, cf = mamba_prefill(bp["mamba"], h, cfg,
                                        chunk=cfg.hybrid_chunk)
            return x + out, (hf, cf)
        if remat:
            fn = jax.checkpoint(fn)
        x, st = fn(x)
        return x, st if collect_state else None

    def group(x, gp):
        x, states = jax.lax.scan(mamba_one, x, gp)      # inner: attn_every
        fn = lambda xx: _shared_attn_full(params, xx, cfg, positions, keep)
        if remat:
            fn = jax.checkpoint(fn)                     # shared block too
        x, kv = fn(x)
        return x, (states, kv)

    x, (states, kvs) = jax.lax.scan(group, x, params["blocks"])
    aux: Optional[Dict] = None
    if collect_state or keep > 0:
        aux = {}
        if collect_state:
            aux["ssm"], aux["conv"] = states[0], states[1]
        if keep > 0:
            aux["k"], aux["v"] = kvs[0], kvs[1]          # (G, B, keep, KV, hd)
    return L.rms_norm(x, params["final_norm"]), aux


def train_loss(params: Dict, cfg: ModelConfig, batch: Dict,
               num_shards: int = 1) -> jax.Array:
    if cfg.layer_types:
        raise NotImplementedError("the layer pattern serves prefill only")
    hidden, _ = forward_full(params, cfg, tokens=batch.get("tokens"),
                             embeds=batch.get("embeds"), remat=cfg.remat)
    loss, cnt = chunked_softmax_xent(hidden, head_weight(params, cfg),
                                     batch["labels"], cfg.logits_chunk)
    return loss / jnp.maximum(cnt, 1.0)


def prefill(params: Dict, cfg: ModelConfig, batch: Dict, *,
            kv_keep: int = 0, num_shards: int = 1):
    if cfg.layer_types:
        return pattern_prefill(params, cfg, batch["tokens"], kv_keep=kv_keep)
    hidden, aux = forward_full(params, cfg, tokens=batch.get("tokens"),
                               embeds=batch.get("embeds"), kv_keep=kv_keep,
                               collect_state=True)
    logits = last_token_logits(hidden, head_weight(params, cfg))
    return logits, aux


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               abstract: bool = False) -> Dict:
    if cfg.layer_types:
        raise NotImplementedError("the layer pattern serves prefill only")
    G = _n_groups(cfg)
    H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    conv_dim = cfg.d_inner + 2 * cfg.ssm_state
    W = cfg.ssm_conv_width
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    shapes = {
        "ssm": ((G, cfg.attn_every, batch, H, P, N), jnp.float32),
        "conv": ((G, cfg.attn_every, batch, W - 1, conv_dim),
                 jnp.dtype(cfg.dtype)),
        "k": ((G, batch, max_len, KV, hd), jnp.dtype(cfg.dtype)),
        "v": ((G, batch, max_len, KV, hd), jnp.dtype(cfg.dtype)),
    }
    if abstract:
        return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in shapes.items()}
    return {k: jnp.zeros(s, d) for k, (s, d) in shapes.items()}


def cache_axes(cfg: ModelConfig) -> Dict:
    return {
        "ssm": ("layers", "layers", "batch", "ssm_heads", None, None),
        "conv": ("layers", "layers", "batch", None, "ssm_inner"),
        "k": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
        "v": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
    }


def decode_step(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                cache: Dict, position: jax.Array, *, num_shards: int = 1):
    if cfg.layer_types:
        raise NotImplementedError("the layer pattern serves prefill only")
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_apply(params["embed"], tokens[:, None], dtype)
    sp = params["shared"]

    def mamba_one(x, xs):
        bp, h, conv = xs
        hdd = L.rms_norm(x, bp["ln"])
        out, h, conv = mamba_decode(bp["mamba"], hdd, cfg, h=h, conv_state=conv)
        return x + out, (h, conv)

    def group(carry, xs):
        x, g, k_all, v_all = carry
        gp, h_g, conv_g = xs
        x, (h_g, conv_g) = jax.lax.scan(mamba_one, x, (gp, h_g, conv_g))
        h = L.rms_norm(x, sp["ln1"])
        # attention KV cache carried + updated in place (see transformer)
        kc = jax.lax.dynamic_index_in_dim(k_all, g, 0, False)
        vc = jax.lax.dynamic_index_in_dim(v_all, g, 0, False)
        attn, kc, vc = L.attention_decode(sp["attn"], h, cfg,
                                          position=position, k_cache=kc,
                                          v_cache=vc, ring=False)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, kc, g, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, vc, g, 0)
        x = x + attn
        h = L.rms_norm(x, sp["ln2"])
        x = x + L.mlp_apply(sp["mlp"], h)
        return (x, g + 1, k_all, v_all), (h_g, conv_g)

    (x, _, k_all, v_all), ys = jax.lax.scan(
        group, (x, 0, cache["k"], cache["v"]),
        (params["blocks"], cache["ssm"], cache["conv"]))
    new_cache = {"ssm": ys[0], "conv": ys[1], "k": k_all, "v": v_all}
    hidden = L.rms_norm(x, params["final_norm"])
    logits = last_token_logits(hidden, head_weight(params, cfg))
    return logits, new_cache


# --------------------------------------------------------------------------
# published layer pattern (GraniteMoeHybrid)
# --------------------------------------------------------------------------

def _pattern_runs(cfg: ModelConfig) -> List[Tuple[str, int]]:
    """One period of ``layer_types`` as runs: [("mamba", 5), ("attention",
    1), ("mamba", 4)] for granite-4.0-h-micro."""
    runs: List[Tuple[str, int]] = []
    for kind in cfg.layer_types[:cfg.pattern_period]:
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return runs


def _pattern_defs(cfg: ModelConfig) -> Dict:
    """Layers of each kind stacked in layer order: ``mamba_layers`` (one
    per "mamba" entry) and ``attn_layers`` (one per "attention" entry),
    each with its two norms and its MLP."""
    def layer(mixer: str, defs: Dict) -> Dict:
        return {"ln1": pdef((cfg.d_model,), ("d_model",), init="zeros"),
                mixer: defs,
                "ln2": pdef((cfg.d_model,), ("d_model",), init="zeros"),
                "mlp": L.mlp_defs(cfg.d_model, cfg.d_ff)}

    out: Dict[str, Any] = {
        "embed": L.embed_defs(cfg),
        "mamba_layers": stack_defs(layer("mamba", mamba_defs(cfg)),
                                   cfg.n_mamba_layers),
        "attn_layers": stack_defs(layer("attn", L.attention_defs(cfg)),
                                  cfg.n_attention_layers),
        "final_norm": pdef((cfg.d_model,), ("d_model",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = pdef((cfg.d_model, cfg.vocab_size),
                              ("d_model", "vocab"), init="scaled")
    return out


def _pattern_forward(params: Dict, cfg: ModelConfig, tokens: jax.Array, *,
                     prefix_len: int = 0, prefix_kv: Optional[Dict] = None,
                     state0: Optional[Dict] = None, kv_keep: int = 0,
                     snap_every: int = 0, n_snap: int = 0,
                     n_valid: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, Dict]:
    """Final-normed hidden (B, S, D) of ``tokens`` at positions
    ``prefix_len + [0, S)``, and {"k", "v": (attention layers, B, keep, KV,
    hd) of the first ``kv_keep`` tokens here; "ssm", "conv": (Mamba layers,
    B, n_snap, ...) snapshots after every ``snap_every`` tokens here}.

    A resume gives ``prefix_kv`` {"k", "v": (attention layers, B,
    prefix_len, KV, hd)} and ``state0`` {"ssm": (Mamba layers, B, H, P, N),
    "conv": (Mamba layers, B, W-1, conv_dim)}, the states after the prefix.
    The layers run as a scan over periods of the pattern; inside a period
    each run of Mamba layers is a scan of its own.

    ``n_valid`` (traced scalar): tokens at or past it are padding. The
    attention layers and the MLPs skip what starts past it, as the dense
    ``forward_full`` does; the Mamba mixers run every row, and no state
    past the prompt is kept (``KVLifecycle.snapshots_kept``)."""
    dtype = jnp.dtype(cfg.dtype)
    rm = jnp.asarray(cfg.residual_multiplier, dtype)
    eps, chunk = cfg.norm_eps, cfg.hybrid_chunk
    x = L.embed_apply(params["embed"], tokens, dtype)
    x = x * jnp.asarray(cfg.embedding_multiplier, dtype)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(
        prefix_len + jnp.arange(S, dtype=jnp.int32), (B, S))
    keep = min(kv_keep, S)
    runs = _pattern_runs(cfg)
    n_periods = cfg.num_layers // cfg.pattern_period
    m_per = sum(n for kind, n in runs if kind == "mamba")
    a_per = sum(n for kind, n in runs if kind == "attention")

    def per_period(tree, n):
        return jax.tree_util.tree_map(
            lambda a: a.reshape(n_periods, n, *a.shape[1:]), tree)

    xs = {"m": per_period(params["mamba_layers"], m_per),
          "a": per_period(params["attn_layers"], a_per)}
    if state0 is not None:
        xs["h0"] = per_period(state0["ssm"], m_per)
        xs["c0"] = per_period(state0["conv"], m_per)
    if prefix_kv is not None:
        xs["pk"] = per_period(prefix_kv["k"], a_per)
        xs["pv"] = per_period(prefix_kv["v"], a_per)

    def mlp(x, lp):
        return x + rm * L.mlp_apply(lp["mlp"], L.rms_norm(x, lp["ln2"], eps),
                                    chunk=chunk, n_valid=n_valid)

    def mamba_layer(x, inp):
        lp, h0, c0 = inp
        out, hs, cs = mamba_prefill(lp["mamba"], L.rms_norm(x, lp["ln1"], eps),
                                    cfg, chunk=chunk, h0=h0, conv0=c0,
                                    snap_every=snap_every, n_snap=n_snap)
        x = mlp(x + rm * out, lp)
        return x, ((hs, cs) if n_snap else None)

    def attn_layer(x, lp, pk, pv):
        h = L.rms_norm(x, lp["ln1"], eps)
        if pk is None:
            out, k, v = L.attention_prefill(lp["attn"], h, cfg,
                                            positions=positions, chunk=chunk,
                                            n_valid=n_valid)
        else:
            q, k, v = L._qkv_project(lp["attn"], h, cfg, positions, chunk,
                                     n_valid)
            out = L.blocked_attention(
                q, jnp.concatenate([pk.astype(k.dtype), k], axis=1),
                jnp.concatenate([pv.astype(v.dtype), v], axis=1),
                q_offset=prefix_len, head_scale=cfg.attn_head_scale,
                q_valid=n_valid)
            out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
            out = chunked_map(lambda oc: oc @ lp["attn"]["wo"], out, chunk,
                              n_valid=n_valid)
        x = mlp(x + rm * out, lp)
        kv = ((k[:, :keep].astype(dtype), v[:, :keep].astype(dtype))
              if keep else None)
        return x, kv

    def period(x, p):
        mi = ai = 0
        states, kvs = [], []
        for kind, n in runs:
            if kind == "mamba":
                take = lambda a, lo=mi: a[lo:lo + n]
                inp = (jax.tree_util.tree_map(take, p["m"]),
                       take(p["h0"]) if "h0" in p else None,
                       take(p["c0"]) if "c0" in p else None)
                x, st = jax.lax.scan(mamba_layer, x, inp)
                states.append(st)
                mi += n
                continue
            for _ in range(n):
                lp = jax.tree_util.tree_map(lambda a: a[ai], p["a"])
                x, kv = attn_layer(x, lp, p["pk"][ai] if "pk" in p else None,
                                   p["pv"][ai] if "pv" in p else None)
                kvs.append(kv)
                ai += 1
        cat = lambda *a: jnp.concatenate(a, axis=0)
        st = jax.tree_util.tree_map(cat, *states) if n_snap else None
        kv = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *kvs) \
            if keep else None
        return x, (st, kv)

    x, (st, kv) = jax.lax.scan(period, x, xs)
    flat = lambda a: a.reshape(-1, *a.shape[2:])
    aux: Dict[str, Any] = {}
    if keep:
        aux["k"], aux["v"] = flat(kv[0]), flat(kv[1])
    if n_snap:
        aux["ssm"], aux["conv"] = flat(st[0]), flat(st[1])
    return L.rms_norm(x, params["final_norm"], eps), aux


def pattern_prefill(params: Dict, cfg: ModelConfig, tokens: jax.Array, *,
                    kv_keep: int = 0, snap_every: int = 0, n_snap: int = 0,
                    prefix_len: int = 0, prefix_kv: Optional[Dict] = None,
                    state0: Optional[Dict] = None,
                    last_index: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Dict]:
    """PrefillOnly serving prefill of the layer pattern: (logits (B, V) at
    ``last_index``, the cache's share of the forward as
    ``_pattern_forward`` returns it). Fresh when ``prefix_len`` is 0;
    otherwise ``tokens`` is the suffix after a cached prefix of
    ``prefix_len`` tokens, a multiple of ``snap_every`` when snapshots are
    asked for, so that theirs fall on the same boundaries. Tokens past
    ``last_index`` are padding, skipped where ``_pattern_forward`` can."""
    hidden, aux = _pattern_forward(
        params, cfg, tokens, prefix_len=prefix_len, prefix_kv=prefix_kv,
        state0=state0, kv_keep=kv_keep, snap_every=snap_every, n_snap=n_snap,
        n_valid=valid_rows(last_index))
    logits = last_token_logits(hidden, head_weight(params, cfg),
                               last_index=last_index)
    return logits / cfg.logits_scaling, aux
