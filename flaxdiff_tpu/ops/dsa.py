"""A learned selection of keys (DeepSeek sparse attention's lightning
indexer): which keys a query's softmax runs over is decided at run time
by a small scorer, one selection a sequence shared by every head.

For one sequence, index queries `q` [T, H, D] (H small heads), ONE index
key a token `k` [T, D] and per-head weights `w` [T, H] (float32):

    I_ts = H^-1/2 D^-1/2 sum_j w_tj relu(q_tj . k_s)
    S_t  = every s <= t                      while t + 1 <= top_k
           the top_k keys s <= t of largest I_ts    beyond

`index_scores` is the first line as an XLA composition, queries in
blocks of `SCORE_BLOCK` so that the [H, block, T] products of a block
stand alone (all of them at once are H x T^2 float32: 2.2 GB a sequence
at 4,174 tokens and 32 heads). `select` is the second: the mask
[T, T] of `S`, exact. The k-th largest score of a row is found by a
search over the bits of its order-preserving integer image
(`kth_largest`: 32 counting passes, each a compare and a row sum that
XLA fuses), not by `lax.top_k`, which at a k in the thousands is a sort
on a TPU; the A/B in the serving round program is in docs/KERNELS.md. A
tie at the k-th score keeps every key that ties (float32 scores of real
activations: none to speak of).

No approximation anywhere: the core's softmax runs over `S_t` exactly
(`ops/flash_attention.py` takes the mask as data). The backward of both
is XLA's own (the mask is piecewise constant: no gradient reaches the
indexer through the selection, as published: the indexer is trained by
its own loss).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SCORE_BLOCK = 512       # queries of one block of `index_scores`


def index_scores(q: jax.Array, k: jax.Array, w: jax.Array) -> jax.Array:
    """`q` [B, T, H, D], `k` [B, T, D], `w` [B, T, H] -> I [B, T, T]
    float32 (every pair, the causal half among them: `select` masks)."""
    b, t, h, d = q.shape
    scale = (h * d) ** -0.5
    w = w.astype(jnp.float32) * scale

    def block(args):
        qb, wb = args                               # [B, Q, H, D], [B, Q, H]
        s = jnp.einsum("bqhd,bsd->bqhs", qb, k,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("bqhs,bqh->bqs", jax.nn.relu(s), wb)

    if t <= SCORE_BLOCK:
        return block((q, w))
    pad = (-t) % SCORE_BLOCK
    n = (t + pad) // SCORE_BLOCK

    def blocks(x):      # [B, T, ...] -> [n, B, SCORE_BLOCK, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(
            x.reshape((b, n, SCORE_BLOCK) + x.shape[2:]), 1, 0)

    out = jax.lax.map(block, (blocks(q), blocks(w)))    # [n, B, Q, T]
    return jnp.moveaxis(out, 0, 1).reshape(b, n * SCORE_BLOCK, t)[:, :t]


def _ordered(x: jax.Array) -> jax.Array:
    """float32 -> uint32 with the same order (a total one: -0 < +0)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    neg = bits >> 31 == 1
    return jnp.where(neg, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest(keys: jax.Array, k: int) -> jax.Array:
    """The k-th largest entry of each row of `keys` [..., N] uint32
    (1 <= k <= N), exactly: the largest value v with at least k entries
    >= v, built a bit at a time from the top."""
    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, prefix)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32))


def select(scores: jax.Array, top_k: int) -> jax.Array:
    """`scores` [B, T, T] float32 -> the mask [B, T, T] bool of `S`:
    `keep[b, t, s]` says query t reads key s. Rows of at most `top_k`
    visible keys keep them all, and the search runs over the others
    only (a static slice)."""
    t = scores.shape[-1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    if t <= top_k:
        return jnp.broadcast_to(causal, scores.shape)
    # a masked pair orders below every score
    late = jnp.where(causal[top_k:], _ordered(scores[:, top_k:]),
                     jnp.uint32(0))
    kth = kth_largest(late, top_k)
    keep_late = causal[top_k:] & (late >= kth[..., None])
    early = jnp.broadcast_to(causal[:top_k],
                             (scores.shape[0], top_k, t))
    return jnp.concatenate([early, keep_late], axis=1)


def selected_pairs(t: int, top_k: int) -> int:
    """(query, key) pairs `select` keeps in a sequence of `t` tokens
    when no score ties: the closed form the counters are held to."""
    full = min(t, top_k)
    return full * (full + 1) // 2 + max(0, t - top_k) * top_k
