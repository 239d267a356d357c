"""Wrapper over JAX's prebuilt TPU flash-attention kernel.

The reference calls this exact kernel
(reference flaxdiff/models/attention.py:14-17,100-102); our first-party
kernel (ops/flash_attention.py) replaces it. This wrapper makes the
prebuilt kernel a dispatchable backend ("prebuilt") so both run through
an identical code path and dispatch can route to either
(FLAXDIFF_FLASH_IMPL=prebuilt). Which is faster in a step: not measured
(ROADMAP D2).

Layout: the prebuilt kernel grids over [batch, heads, seq, head_dim]
(BHLD). Sequence lengths must divide the block sizes, so both are padded
to block multiples here; padded KV positions are masked via SegmentIds
(real tokens id 0, padding id 1). Padded *q* rows are left unmasked on
purpose: they attend to real keys and produce finite garbage that the
caller slices off, and their cotangents are zero (the slice's VJP
zero-pads), so ds = p*(dp-delta) = 0 — they contribute nothing to
dk/dv. Fully-masked q rows, by contrast, would hit the kernel's
mask-value path and are not worth the risk.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp


@functools.cache
def _mod():
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    return fa


def _pad_len(n: int, block: int) -> int:
    return -(-n // block) * block


def _choose_blocks(lq: int, lk: int):
    """(block_q, block_k) for the prebuilt kernel: large sequence-capped
    blocks (the same policy our first-party kernel settled on after the
    r4 on-chip tune — 512x1024 beat 128x128 by 5.5x), env-overridable
    for on-chip A/B without a rebuild."""
    bq = int(os.environ.get("FLAXDIFF_PREBUILT_BLOCK_Q", "512"))
    bk = int(os.environ.get("FLAXDIFF_PREBUILT_BLOCK_K", "1024"))
    bq = min(bq, _pad_len(lq, 128))
    bk = min(bk, _pad_len(lk, 128))
    return bq, bk


def prebuilt_flash_attention_bhld(q: jax.Array, k: jax.Array, v: jax.Array,
                                  scale: Optional[float] = None) -> jax.Array:
    """Prebuilt TPU flash attention over [B, H, L, D] operands, fwd+bwd.

    Handles arbitrary sequence lengths by padding to block multiples
    (segment-id masking for padded KV — exact, not approximate). The
    caller handles head_dim padding policy (ops/attention.py
    _maybe_pad_head_dim) so the two flash implementations share it.
    """
    fa = _mod()
    b, h, lq, d = q.shape
    lk = k.shape[2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bq, bk = _choose_blocks(lq, lk)
    lq_p, lk_p = _pad_len(lq, bq), _pad_len(lk, bk)

    def pad_seq(x, n):
        if x.shape[2] == n:
            return x
        return jnp.pad(x, ((0, 0), (0, 0), (0, n - x.shape[2]), (0, 0)))

    qp, kp, vp = pad_seq(q, lq_p), pad_seq(k, lk_p), pad_seq(v, lk_p)

    seg = None
    if lk_p != lk:
        # mask padded keys only; padded q rows stay live (see module doc)
        q_ids = jnp.zeros((b, lq_p), jnp.int32)
        kv_ids = (jnp.arange(lk_p, dtype=jnp.int32) >= lk).astype(jnp.int32)
        seg = fa.SegmentIds(q=q_ids, kv=jnp.broadcast_to(kv_ids, (b, lk_p)))

    bs = fa.BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk,
        block_k_dkv=bk, block_q_dkv=bq,
        block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq,
    )
    out = fa.flash_attention(qp, kp, vp, segment_ids=seg,
                             sm_scale=float(scale), block_sizes=bs)
    return out[:, :, :lq, :]


def prebuilt_available() -> bool:
    try:
        _mod()
    except ImportError:
        return False
    return jax.devices()[0].platform == "tpu"
