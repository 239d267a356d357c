"""Gated power retention: causal linear attention whose similarity is an
even power of the scaled dot product, with a data-dependent decay.

For one row, `q` [T, H, D], `k` / `v` [T, KV, D] (query head i reads
key/value head i // (H / KV)) and a gate `log_g` [T, KV] <= 0 (float32):

    G_t  = sum_{r <= t} log_g_r
    w_ts = (q_t . k_s / sqrt(D))^p * exp(G_t - G_s)    s <= t, else 0
    o_t  = sum_s w_ts v_s / (sum_s w_ts + eps)

`p` even (2 here), so no weight is negative: no softmax, no running
maximum, the normaliser a plain sum; 4 D operations a (query, key) pair
and query head over the causal half. `_pairwise_xla` is this as an XLA
composition, a key/value head at a time (`lax.map`) so that the [T, T]
weights of a serving round's evaluations x heads never stand at once, and
queries in blocks of `PAIRWISE_BLOCK` over the keys they can see on long
rows. XLA fuses the power, the decay and the sum into a head's two small
products: in the serving round program at 654 tokens it is as fast as a
Pallas tiling of the same form was (191.3 against 191.7 ms a guided
bucket-8 step, one v5e, PR 38; PERF.md section 6), so there is no kernel.

The same function is a state recurrence over `phi`, the symmetric square
(`S_t = g_t S_{t-1} + phi(k_t) v_t^T`): `tests/test_brumby.py` holds it,
token by token and chunked, as a witness of this form. What would make
either a kernel worth having (rows of 8k tokens and more, a state kept in
VMEM) is ROADMAP M6 / M11 and docs/KERNELS.md.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30
PAIRWISE_BLOCK = 1024   # the composition's query block, beyond twice that


def _power(s: jax.Array, degree: int) -> jax.Array:
    return s * s if degree == 2 else jax.lax.integer_pow(s, degree)


def _by_kv_head(q, k, v, log_g):
    """[B, T, H, D], [B, T, KV, D] x 2, [B, T, KV] -> the same with the
    key/value head leading and the query heads grouped under it:
    [KV, B, T, G, D], [KV, B, T, D] x 2, [KV, B, T] float32."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    return (q.reshape(b, t, kv, h // kv, d).transpose(2, 0, 1, 3, 4),
            k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3),
            log_g.astype(jnp.float32).transpose(2, 0, 1))


def _from_kv_head(out, b, t, h, d):
    """[KV, B, T, G, D] -> [B, T, H, D]."""
    return out.transpose(1, 2, 0, 3, 4).reshape(b, t, h, d)


def _pairs(q, k, v, gq, gk, first, scale, degree):
    """One key/value head, queries [B, Q, G, D] at positions `first`...
    against keys [B, S, D] at 0..S-1; `gq` [B, Q] / `gk` [B, S] are G.
    Returns (numerator [B, Q, G, D] float32, normaliser [B, Q, G])."""
    s = jnp.einsum("bqgd,bsd->bgqs", q, k,
                   preferred_element_type=jnp.float32) * scale
    seen = (jnp.arange(k.shape[1])[None, :]
            <= first + jnp.arange(q.shape[1])[:, None])
    decay = jnp.exp(jnp.where(seen, gq[:, :, None] - gk[:, None, :], NEG))
    w = _power(s, degree) * decay[:, None]
    num = jnp.einsum("bgqs,bsd->bqgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return num, w.sum(-1).transpose(0, 2, 1)


def _pairwise_xla(q, k, v, log_g, degree: int = 2, eps: float = 1e-6):
    """The pairwise form as an XLA composition."""
    b, t, h, d = q.shape
    block = t if t <= 2 * PAIRWISE_BLOCK else PAIRWISE_BLOCK
    scale = 1.0 / d ** 0.5

    def head(args):
        qh, kh, vh, lg = args
        g = jnp.cumsum(lg, axis=1)
        outs = []
        for lo in range(0, t, block):       # static: a block sees keys
            hi = min(lo + block, t)         # 0..hi-1 and no further
            num, den = _pairs(qh[:, lo:hi], kh[:, :hi], vh[:, :hi],
                              g[:, lo:hi], g[:, :hi], lo, scale, degree)
            outs.append(num / (den + eps)[..., None])
        return jnp.concatenate(outs, axis=1).astype(q.dtype)

    return _from_kv_head(jax.lax.map(head, _by_kv_head(q, k, v, log_g)),
                         b, t, h, d)


def power_retention(q: jax.Array, k: jax.Array, v: jax.Array,
                    log_g: jax.Array, *, degree: int = 2,
                    eps: float = 1e-6) -> jax.Array:
    """Gated power retention over `q` [B, T, H, D], `k` / `v`
    [B, T, KV, D] and `log_g` [B, T, KV] (the log of a gate in (0, 1],
    float32): [B, T, H, D] in `q`'s type."""
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"{q.shape[2]} query heads are not a multiple of "
                         f"{k.shape[2]} key/value heads")
    if degree % 2:
        raise ValueError(f"degree {degree} is odd: a weight could be "
                         "negative, and the normaliser is a plain sum")
    with jax.named_scope("fdt_power_pairwise"):
        return _pairwise_xla(q, k, v, log_g, degree, eps)
