"""Hybrid prefilling (paper §4) — chunk non-attention layers, not attention.

The paper's observation: peak prefill memory is dominated by the ``(seq,
d_ff)`` intermediates of the MLP (≈14x one layer's KV), not by the KV cache.
Chunking *only* the token-wise (linear) layers bounds those intermediates at
``(chunk, d_ff)`` while attention still sees the whole sequence — so attention
kernel efficiency is untouched and the request finishes in ONE forward pass
(the property that makes suffix-KV discard possible). The discard itself is
layer-wise and structural — see ``models/transformer.forward_full`` (the KV
keep-slice is the only scan output) and ``core.kv_policy.KVLifecycle``, the
single owner of the keep arithmetic.

TPU/XLA realization: ``lax.map`` (a scan) over sequence chunks. XLA's buffer
assignment then keeps exactly one chunk of intermediates live, and the scan
writes every chunk's result straight into the preallocated stacked output —
the paper's "output preallocation" optimization falls out of the IR for free.
The Pallas ``fused_mlp`` kernel (kernels/fused_mlp) is the stronger in-VMEM
form of the same idea and is selectable per-block.

Everything here is position-independent-exact: chunking a token-wise function
along the sequence axis never changes results (tested by property tests).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp


def _pad_to_multiple(x: jax.Array, multiple: int, axis: int) -> Tuple[jax.Array, int]:
    size = x.shape[axis]
    rem = size % multiple
    if rem == 0:
        return x, 0
    pad = multiple - rem
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def chunked_map(fn: Callable[[jax.Array], jax.Array], x: jax.Array,
                chunk: int, axis: int = 1,
                n_valid: Optional[jax.Array] = None) -> jax.Array:
    """Apply a token-wise ``fn`` over ``axis`` in chunks via ``lax.map``.

    ``fn`` maps (..., chunk, ...) -> (..., chunk, ...); it must be
    position-independent along ``axis`` (true for every linear/MLP/norm
    layer). Peak live intermediates inside ``fn`` are bounded by one chunk.

    ``n_valid`` (traced scalar): rows at or past it are padding. A chunk
    that starts there returns zeros and never runs ``fn``; a partly valid
    chunk runs whole. ``skipped_slots`` is the host's count of those rows.
    """
    if chunk <= 0 or x.shape[axis] <= chunk:
        return fn(x)
    axis = axis % x.ndim
    x, pad = _pad_to_multiple(x, chunk, axis)
    n = x.shape[axis] // chunk

    def run(i):
        return fn(jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk, axis=axis))

    def body(i):
        if n_valid is None:
            return run(i)
        # the slice lives inside the branch, so a dead chunk copies nothing;
        # zeros, not garbage: a pad row must stay finite, since a later PV
        # product weighs it by 0 and 0 x NaN is NaN
        out = jax.eval_shape(run, i)
        return jax.lax.cond(i * chunk < n_valid, run,
                            lambda _: jnp.zeros(out.shape, out.dtype), i)

    ys = jax.lax.map(body, jnp.arange(n))          # (n, ..., chunk, ...)
    ys = jnp.moveaxis(ys, 0, axis)                 # (..., n, chunk, ...)
    new_shape = ys.shape[:axis] + (n * chunk,) + ys.shape[axis + 2:]
    ys = ys.reshape(new_shape)
    if pad:
        ys = jax.lax.slice_in_dim(ys, 0, new_shape[axis] - pad, axis=axis)
    return ys


def valid_rows(last_index: Optional[jax.Array]) -> Optional[jax.Array]:
    """Real rows of a padded solo forward, through its last scored token:
    the ``n_valid`` of ``chunked_map`` (None without ``last_index``)."""
    return None if last_index is None else jnp.max(last_index) + 1


def skipped_slots(S: int, n_valid: int, chunk: int) -> int:
    """Rows of an ``S``-row forward that ``chunked_map(..., n_valid=)``
    skips: those of the chunks that start at or past ``n_valid``."""
    if chunk <= 0 or S <= chunk:
        return 0
    return max(0, S - -(-n_valid // chunk) * chunk)


def chunked_softmax_xent(hidden: jax.Array, w_head: jax.Array,
                         labels: jax.Array, chunk: int,
                         final_softcap: float = 0.0,
                         valid: Optional[jax.Array] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy without ever materializing ``(B, S, vocab)`` logits.

    Beyond-paper but a direct extension of hybrid prefilling: the LM head is
    the largest linear layer of all (vocab up to 256k here), so we fold the
    loss into the chunked pass. Uses one-hot contraction instead of gather so
    a vocab-sharded head needs only a psum. Returns (sum_loss, num_tokens).
    """
    B, S, D = hidden.shape
    V = w_head.shape[-1]
    if valid is None:
        valid = jnp.ones((B, S), dtype=jnp.float32)

    # remat: recompute the (chunk, vocab) logits in the backward pass — the
    # whole point of chunking the loss is that logits never persist.
    @jax.checkpoint
    def piece(h, lab, msk):
        # operands stay in model dtype; f32 accumulation via the MXU — an
        # f32 upcast of w_head would materialize (and all-gather) a full
        # fp32 copy of the largest matrix in the model
        logits = jnp.einsum("bcd,dv->bcv", h, w_head,
                            preferred_element_type=jnp.float32)
        if final_softcap:
            logits = final_softcap * jnp.tanh(logits / final_softcap)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(lab, V, dtype=jnp.bfloat16)
        gold = jnp.einsum("bcv,bcv->bc", logits, onehot,
                          preferred_element_type=jnp.float32)
        return jnp.sum((logz - gold) * msk), jnp.sum(msk)

    if chunk <= 0 or S <= chunk:
        return piece(hidden, labels, valid)

    hidden, pad = _pad_to_multiple(hidden, chunk, 1)
    labels, _ = _pad_to_multiple(labels, chunk, 1)
    valid, _ = _pad_to_multiple(valid, chunk, 1)
    n = hidden.shape[1] // chunk

    def body(carry, i):
        h = jax.lax.dynamic_slice_in_dim(hidden, i * chunk, chunk, axis=1)
        lab = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
        msk = jax.lax.dynamic_slice_in_dim(valid, i * chunk, chunk, axis=1)
        loss, cnt = piece(h, lab, msk)
        return (carry[0] + loss, carry[1] + cnt), None

    (loss, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        jnp.arange(n))
    return loss, cnt


def packed_last_logits(hidden: jax.Array, w_head: jax.Array,
                       last_indices: jax.Array,
                       final_softcap: float = 0.0) -> jax.Array:
    """Prefill-only LM head for a PREPACKED batch: one logits row per packed
    segment. ``last_indices`` (N,) are flat indices into the flattened
    (B*S,) token axis — for the engine's B==1 layout, simply each segment's
    last packed position. Projects only N rows (N << S)."""
    B, S, D = hidden.shape
    flat = hidden.reshape(B * S, D)
    last = jnp.take(flat, last_indices.astype(jnp.int32), axis=0)   # (N, D)
    logits = jnp.einsum("nd,dv->nv", last, w_head,
                        preferred_element_type=jnp.float32)
    if final_softcap:
        logits = final_softcap * jnp.tanh(logits / final_softcap)
    return logits


def last_token_logits(hidden: jax.Array, w_head: jax.Array,
                      last_index: Optional[jax.Array] = None,
                      final_softcap: float = 0.0) -> jax.Array:
    """Prefill-only LM head: project ONLY the last position.

    For a prefill-only request the other ``seq-1`` rows of logits are dead
    compute (``seq x vocab`` of it); this is the serving-side twin of
    ``chunked_softmax_xent``.
    """
    B, S, D = hidden.shape
    if last_index is None:
        last = hidden[:, -1, :]
    else:
        last = jnp.take_along_axis(
            hidden, last_index.reshape(B, 1, 1).astype(jnp.int32), axis=1
        )[:, 0, :]
    logits = jnp.einsum("bd,dv->bv", last, w_head,
                        preferred_element_type=jnp.float32)
    if final_softcap:
        logits = final_softcap * jnp.tanh(logits / final_softcap)
    return logits
