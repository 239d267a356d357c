"""Shared transformer-model layers: patch embedding, RoPE, AdaLN-Zero.

Capability parity with reference flaxdiff/models/vit_common.py:20-261
(PatchEmbedding, PositionalEncoding, RotaryEmbedding/RoPEAttention,
AdaLNZero/AdaLNParams). TPU-first choices:

- RoPE tables are computed from static shapes at trace time and become XLA
  constants — no max_seq_len precompute/cache or dynamic extension needed
  (the reference carries a 4096-entry table and a fallback path,
  vit_common.py:86-117).
- RoPE is applied in [B, S, H, D] layout directly (the layout DenseGeneral
  produces and the attention op consumes); no transpose round-trip
  (the reference permutes b s h d -> b h s d and back, vit_common.py:159-171).
- Attention goes through the ops-layer dispatcher so the Pallas flash path
  and the XLA fallback share one call site.
"""
from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

import os

from ..ops.attention import attend
from ..typing import Dtype
from .attention import head_out_projection, head_projection
from .common import FourierEmbedding, TimeProjection
from .sfc import (
    build_2d_sincos_pos_embed,
    hilbert_indices,
    sfc_patchify,
    zigzag_indices,
)


class PatchEmbedding(nn.Module):
    """Non-overlapping conv patchify -> [B, N, D] (reference vit_common.py:20-37)."""

    patch_size: int
    embedding_dim: int
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, h, w, c = x.shape
        p = self.patch_size
        if h % p or w % p:
            raise ValueError(f"image {h}x{w} not divisible by patch size {p}")
        x = nn.Conv(self.embedding_dim, (p, p), strides=(p, p),
                    dtype=self.dtype, precision=self.precision,
                    name="proj")(x)
        return x.reshape(b, -1, self.embedding_dim)


class PositionalEncoding(nn.Module):
    """Learned additive positional table (reference vit_common.py:40-49)."""

    max_len: int
    embedding_dim: int

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        pe = self.param("pos_encoding", nn.initializers.normal(stddev=0.02),
                        (1, self.max_len, self.embedding_dim))
        n = x.shape[1]
        if n > self.max_len:
            raise ValueError(f"sequence {n} exceeds max_len {self.max_len}")
        return x + pe[:, :n, :].astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_frequencies(dim: int, seq_len: int, base: float = 10000.0
                     ) -> Tuple[jax.Array, jax.Array]:
    """(cos, sin) tables of shape [seq_len, dim//2]; constant-folded under jit
    because seq_len/dim are static (reference vit_common.py:86-117)."""
    if dim % 2:
        raise ValueError(f"RoPE head dim must be even, got {dim}")
    inv_freq = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def identity_rope(dim: int, seq_len: int) -> Tuple[jax.Array, jax.Array]:
    """cos=1 / sin=0 tables that make RoPE a no-op — used by non-raster scan
    orders where sequence index is not a 2D position (reference
    simple_dit.py:282-284)."""
    shape = (seq_len, dim // 2)
    return jnp.ones(shape, jnp.float32), jnp.zeros(shape, jnp.float32)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               bhld: bool = False) -> jax.Array:
    """Rotate-half RoPE with tables [S, D//2] (reference
    vit_common.py:56-84). Position-elementwise, so it applies in either
    layout: [B, S, H, D] (default) or [B, H, S, D] (bhld=True)."""
    if bhld:
        cos = jnp.concatenate([cos, cos], axis=-1)[None, None, :, :]
        sin = jnp.concatenate([sin, sin], axis=-1)[None, None, :, :]
    else:
        cos = jnp.concatenate([cos, cos], axis=-1)[None, :, None, :]
        sin = jnp.concatenate([sin, sin], axis=-1)[None, :, None, :]
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * cos + rotated * sin).astype(x.dtype)


class RoPEAttention(nn.Module):
    """Multi-head attention with rotary embeddings on q/k
    (reference vit_common.py:123-183)."""

    heads: int
    dim_head: int
    backend: str = "auto"
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None
    use_bias: bool = True
    force_fp32_for_softmax: bool = True
    # None: read FLAXDIFF_ATTN_BHLD (models/attention.py AttentionLayer
    # rationale — RoPE is position-elementwise, so it rotates in either
    # layout and the DiT family gets the transpose-free kernel path too)
    bhld: Optional[bool] = None
    out_kernel_init: Optional[nn.initializers.Initializer] = None

    @nn.compact
    def __call__(self, x: jax.Array, context: Optional[jax.Array] = None,
                 freqs_cis: Optional[Tuple[jax.Array, jax.Array]] = None
                 ) -> jax.Array:
        spatial = x.ndim == 4
        if spatial:
            b, h, w, c = x.shape
            x = x.reshape(b, h * w, c)
        context = x if context is None else context
        bhld = (self.bhld if self.bhld is not None
                else os.environ.get("FLAXDIFF_ATTN_BHLD") == "1")
        # shared layout-dispatching constructors (models/attention.py):
        # same init in both layouts — here DenseGeneral's lecun default
        proj = lambda name: head_projection(
            bhld, heads=self.heads, dim_head=self.dim_head,
            use_bias=self.use_bias, dtype=self.dtype,
            precision=self.precision,
            kernel_init=nn.linear.default_kernel_init, name=name)
        q = proj("to_q")(x)
        k = proj("to_k")(context)
        v = proj("to_v")(context)
        seq_axis = 2 if bhld else 1
        if freqs_cis is None:
            # Size the default table to the longest sequence so cross-attention
            # with a longer context gets valid positions for every key.
            cos, sin = rope_frequencies(
                self.dim_head, max(q.shape[seq_axis], k.shape[seq_axis]))
        else:
            cos, sin = freqs_cis
        q = apply_rope(q, cos[: q.shape[seq_axis]],
                       sin[: q.shape[seq_axis]], bhld=bhld)
        k = apply_rope(k, cos[: k.shape[seq_axis]],
                       sin[: k.shape[seq_axis]], bhld=bhld)
        out_init = (self.out_kernel_init if self.out_kernel_init is not None
                    else nn.linear.default_kernel_init)
        out = attend(q, k, v, bhld=bhld, backend=self.backend,
                     force_fp32_for_softmax=self.force_fp32_for_softmax)
        out = head_out_projection(
            bhld, features=x.shape[-1], heads=self.heads,
            dim_head=self.dim_head, use_bias=self.use_bias,
            dtype=self.dtype, precision=self.precision,
            kernel_init=out_init)(out)
        if spatial:
            out = out.reshape(b, h, w, c)
        return out


# ---------------------------------------------------------------------------
# Shared embed / conditioning stanzas (used by DiT, U-DiT, hybrid SSM-DiT)
# ---------------------------------------------------------------------------

def scan_rope(dim_head: int, seq_len: int, scan_order: str
              ) -> Tuple[jax.Array, jax.Array]:
    """RoPE tables for a scan order: real frequencies for raster, identity
    for hilbert/zigzag where sequence index is not a 2D position
    (reference simple_dit.py:282-284)."""
    if scan_order == "raster":
        return rope_frequencies(dim_head, seq_len)
    return identity_rope(dim_head, seq_len)


class ScanPatchEmbed(nn.Module):
    """Patch embedding with a selectable scan order.

    raster: conv patch embed. hilbert/zigzag: raw patch extraction + Dense
    (conv patchify doesn't compose with post-conv reordering). Optionally
    adds the fixed 2D sin-cos table permuted into scan order so every token
    carries its true 2D position regardless of sequence position.

    Returns (tokens [B,N,D], inv_idx or None) — inv_idx restores row-major
    order for unpatchify (reference simple_dit.py:219-255).
    """

    patch_size: int
    embedding_dim: int
    scan_order: str = "raster"
    add_sincos: bool = True
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None

    @nn.compact
    def __call__(self, x: jax.Array):
        b, h, w, c = x.shape
        p = self.patch_size
        hp, wp = h // p, w // p
        if self.scan_order == "hilbert":
            idx = hilbert_indices(hp, wp)
        elif self.scan_order == "zigzag":
            idx = zigzag_indices(hp, wp)
        elif self.scan_order == "raster":
            idx = None
        else:
            raise ValueError(f"unknown scan_order {self.scan_order!r}")

        if idx is not None:
            raw, inv_idx = sfc_patchify(x, p, idx)
            tokens = nn.Dense(self.embedding_dim, dtype=self.dtype,
                              precision=self.precision,
                              name="scan_proj")(raw)
        else:
            inv_idx = None
            tokens = PatchEmbedding(
                patch_size=p, embedding_dim=self.embedding_dim,
                dtype=self.dtype, precision=self.precision,
                name="patch_embed")(x)

        if self.add_sincos:
            pos = jnp.asarray(build_2d_sincos_pos_embed(
                self.embedding_dim, hp, wp))
            if idx is not None:
                pos = pos[jnp.asarray(idx)]
            tokens = tokens + pos[None].astype(tokens.dtype)
        return tokens, inv_idx


class TimeTextEmbedding(nn.Module):
    """Pooled conditioning vector: Fourier time MLP plus mean-pooled
    projected text (reference simple_dit.py:259-270)."""

    features: int
    mlp_ratio: int = 4
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None

    @nn.compact
    def __call__(self, temb: jax.Array,
                 textcontext: Optional[jax.Array] = None) -> jax.Array:
        t = FourierEmbedding(features=self.features, name="t_fourier")(temb)
        t = TimeProjection(features=self.features * self.mlp_ratio,
                           name="t_proj")(t)
        cond = nn.Dense(self.features, dtype=self.dtype,
                        precision=self.precision, name="t_out")(t)
        if textcontext is not None:
            text = nn.Dense(self.features, dtype=self.dtype,
                            precision=self.precision,
                            name="text_proj")(textcontext)
            cond = cond + jnp.mean(text, axis=1)
        return cond


# ---------------------------------------------------------------------------
# AdaLN-Zero conditioning
# ---------------------------------------------------------------------------

def modulate(x: jax.Array, scale: jax.Array, shift: jax.Array) -> jax.Array:
    """DiT modulation: x * (1 + scale) + shift."""
    return x * (1.0 + scale) + shift


class AdaLNParams(nn.Module):
    """Zero-init projection of a conditioning vector to 6 modulation params
    per feature (scale/shift/gate for attention and MLP paths) —
    reference vit_common.py:240-261."""

    features: int
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None

    @nn.compact
    def __call__(self, conditioning: jax.Array) -> jax.Array:
        if conditioning.ndim == 2:
            conditioning = conditioning[:, None, :]
        return nn.Dense(6 * self.features, dtype=self.dtype,
                        precision=self.precision,
                        kernel_init=nn.initializers.zeros,
                        name="ada_proj")(conditioning)


class AdaLNZero(nn.Module):
    """Norm + modulate in one module: returns (x_attn, gate_attn, x_mlp,
    gate_mlp) — reference vit_common.py:189-238.

    Note: DiTBlock modulates two separate (pre-attn / pre-MLP) norms via
    AdaLNParams directly, matching the reference DiT wiring
    (simple_dit.py:42-95); this single-norm variant is the alternative
    conditioning surface the reference also exposes.

    With `fused_epilogues` (default) the LayerNorm + BOTH modulated
    views run as ONE fused Pallas pass on TPU — x is read once
    (ops/fused_adaln.py fused_ln_modulate2; clip stays in XLA so its
    VJP semantics are exact). Off-TPU the exact composition below runs
    (bit-identical to the pre-fusion model).
    """

    features: int
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None
    norm_epsilon: float = 1e-5
    fused_epilogues: bool = True

    @nn.compact
    def __call__(self, x: jax.Array, conditioning: jax.Array):
        from ..ops.fused_adaln import fused_adaln_active, fused_ln_modulate2
        params = AdaLNParams(self.features, dtype=self.dtype,
                             precision=self.precision, name="params")(conditioning)
        s_mlp, b_mlp, g_mlp, s_attn, b_attn, g_attn = jnp.split(params, 6, axis=-1)
        s_mlp = jnp.clip(s_mlp, -10.0, 10.0)
        b_mlp = jnp.clip(b_mlp, -10.0, 10.0)
        if self.fused_epilogues and fused_adaln_active():
            x_attn, x_mlp = fused_ln_modulate2(
                x, s_attn, b_attn, s_mlp, b_mlp, self.norm_epsilon)
            return x_attn, g_attn, x_mlp, g_mlp
        norm_x = nn.LayerNorm(epsilon=self.norm_epsilon, use_scale=False,
                              use_bias=False, dtype=jnp.float32,
                              name="norm")(x)
        return (modulate(norm_x, s_attn, b_attn), g_attn,
                modulate(norm_x, s_mlp, b_mlp), g_mlp)
