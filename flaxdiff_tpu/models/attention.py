"""Attention modules built on the ops-layer dispatcher.

Capability parity with reference flaxdiff/models/attention.py:34-380
(EfficientAttention/NormalAttention -> one AttentionLayer with a backend
switch; FlaxGEGLU/FlaxFeedForward -> GEGLUFeedForward; BasicTransformerBlock;
TransformerBlock with optional projection). The flash path is the
first-party Pallas kernel in ops/flash_attention.py.
"""
from __future__ import annotations

import os
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attend
from ..typing import Dtype
from .common import kernel_init


class _ProjToHeads(nn.Module):
    """[B, L, C] -> [B, H, L, D] projection whose params are
    shape/name-identical to `nn.DenseGeneral((H, D))` (kernel (C,H,D),
    bias (H,D)) — checkpoints swap freely between layouts. The output
    permutation is folded into the projection dot_general itself, so no
    separate transpose op ever exists for XLA to materialize."""

    heads: int
    dim_head: int
    use_bias: bool = True
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None
    kernel_init: Callable = kernel_init(1.0)

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c = x.shape[-1]
        # init on the FLATTENED (C, H*D) shape exactly as
        # nn.DenseGeneral((H, D)) does (its kernel_init_wrap): a
        # variance-scaling init drawn directly on (C, H, D) would see
        # fan_in=H*C / fan_out=D*C and start ~sqrt(H)x narrower than
        # the layout-independent checkpoint contract promises
        kernel = self.param(
            "kernel",
            lambda key, shape, dtype=jnp.float32: self.kernel_init(
                key, (c, self.heads * self.dim_head), dtype
            ).reshape(shape),
            (c, self.heads, self.dim_head))
        bias = (self.param("bias", nn.initializers.zeros,
                           (self.heads, self.dim_head))
                if self.use_bias else None)
        x, kernel, bias = nn.dtypes.promote_dtype(
            x, kernel, bias, dtype=self.dtype)
        y = jnp.einsum("blc,chd->bhld", x, kernel,
                       precision=self.precision)
        if bias is not None:
            y = y + bias[None, :, None, :]
        return y


class _ProjFromHeads(nn.Module):
    """[B, H, L, D] -> [B, L, C]; params identical to
    `nn.DenseGeneral(C, axis=(-2, -1))` on a [B, L, H, D] input
    (kernel (H,D,C), bias (C,))."""

    features: int
    heads: int
    dim_head: int
    use_bias: bool = True
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None
    kernel_init: Callable = kernel_init(1.0)

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        # flattened-shape init matching nn.DenseGeneral(C, axis=(-2,-1))
        # (see _ProjToHeads)
        kernel = self.param(
            "kernel",
            lambda key, shape, dtype=jnp.float32: self.kernel_init(
                key, (self.heads * self.dim_head, self.features), dtype
            ).reshape(shape),
            (self.heads, self.dim_head, self.features))
        bias = (self.param("bias", nn.initializers.zeros,
                           (self.features,))
                if self.use_bias else None)
        x, kernel, bias = nn.dtypes.promote_dtype(
            x, kernel, bias, dtype=self.dtype)
        y = jnp.einsum("bhld,hdc->blc", x, kernel,
                       precision=self.precision)
        if bias is not None:
            y = y + bias
        return y


def head_projection(bhld: bool, *, heads: int, dim_head: int,
                    use_bias: bool, dtype, precision, kernel_init,
                    name: str) -> nn.Module:
    """The q/k/v projection for a layout: [B,L,C]->[B,L,H,D]
    (DenseGeneral) or ->[B,H,L,D] (_ProjToHeads). One constructor shared
    by every attention module so the two layouts cannot drift (same
    param names/shapes AND the caller's exact init in both)."""
    if bhld:
        return _ProjToHeads(heads=heads, dim_head=dim_head,
                            use_bias=use_bias, dtype=dtype,
                            precision=precision, kernel_init=kernel_init,
                            name=name)
    return nn.DenseGeneral((heads, dim_head), use_bias=use_bias,
                           dtype=dtype, precision=precision,
                           kernel_init=kernel_init, name=name)


def head_out_projection(bhld: bool, *, features: int, heads: int,
                        dim_head: int, use_bias: bool, dtype, precision,
                        kernel_init, name: str = "to_out") -> nn.Module:
    """The output projection back to [B,L,C] for either layout."""
    if bhld:
        return _ProjFromHeads(features=features, heads=heads,
                              dim_head=dim_head, use_bias=use_bias,
                              dtype=dtype, precision=precision,
                              kernel_init=kernel_init, name=name)
    return nn.DenseGeneral(features, axis=(-2, -1), use_bias=use_bias,
                           dtype=dtype, precision=precision,
                           kernel_init=kernel_init, name=name)


class AttentionLayer(nn.Module):
    """Multi-head self/cross attention over [B, L, C] (+[B,H,W,C] auto-flatten).

    backend: "auto" | "flash" | "xla".
    bhld: project q/k/v straight into the flash kernel's native
    [B, H, L, D] layout — the head permutation is folded into the
    projection matmuls, so the per-operand transposes (and XLA's
    materialized copies around the pallas custom call) disappear;
    whether the step is faster for it is not measured (ROADMAP D2).
    None (default) reads FLAXDIFF_ATTN_BHLD at trace time, an A/B
    without a model rebuild — in MULTI-HOST runs that env var must be
    identical on every host or the hosts compile divergent programs and
    hang at the first collective; set it from a shared launcher
    (train.py --attn_bhld) or pass bhld explicitly.
    Parameters are layout-independent (same names and shapes).
    """

    heads: int = 4
    dim_head: int = 64
    backend: str = "auto"
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None
    use_bias: bool = True
    force_fp32_for_softmax: bool = True
    bhld: Optional[bool] = None
    kernel_init: Callable = kernel_init(1.0)

    @nn.compact
    def __call__(self, x: jax.Array, context: Optional[jax.Array] = None) -> jax.Array:
        spatial = x.ndim == 4
        if spatial:
            b, h, w, c = x.shape
            x = x.reshape(b, h * w, c)
        context = x if context is None else context
        bhld = (self.bhld if self.bhld is not None
                else os.environ.get("FLAXDIFF_ATTN_BHLD") == "1")
        proj = lambda name: head_projection(
            bhld, heads=self.heads, dim_head=self.dim_head,
            use_bias=self.use_bias, dtype=self.dtype,
            precision=self.precision, kernel_init=self.kernel_init,
            name=name)
        q = proj("to_q")(x)
        k = proj("to_k")(context)
        v = proj("to_v")(context)
        out = attend(q, k, v, bhld=bhld, backend=self.backend,
                     force_fp32_for_softmax=self.force_fp32_for_softmax)
        out = head_out_projection(
            bhld, features=x.shape[-1], heads=self.heads,
            dim_head=self.dim_head, use_bias=self.use_bias,
            dtype=self.dtype, precision=self.precision,
            kernel_init=self.kernel_init)(out)
        if spatial:
            out = out.reshape(b, h, w, c)
        return out


class GEGLUFeedForward(nn.Module):
    """GEGLU-gated MLP (reference attention.py:179-238).

    With `fused` (default) the split + gelu + multiply over the packed
    [.., 2F] projection runs as one Pallas pass on TPU
    (ops/fused_adaln.py fused_geglu; FLAXDIFF_FUSED_ADALN=xla|interpret
    A/B); off-TPU the exact composition below runs."""

    dim_out: int
    mult: int = 4
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None
    fused: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from ..ops.fused_adaln import fused_adaln_active, fused_geglu
        inner = self.dim_out * self.mult
        proj = nn.Dense(inner * 2, dtype=self.dtype, precision=self.precision,
                        name="proj_in")(x)
        if self.fused and fused_adaln_active() and proj.ndim == 3:
            x = fused_geglu(proj)
        else:
            gate, val = jnp.split(proj, 2, axis=-1)
            x = val * jax.nn.gelu(gate)
        return nn.Dense(self.dim_out, dtype=self.dtype,
                        precision=self.precision, name="proj_out")(x)


class BasicTransformerBlock(nn.Module):
    """self-attn -> cross-attn -> GEGLU FF, pre-LN (reference 240-303)."""

    heads: int = 4
    dim_head: int = 64
    backend: str = "auto"
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None
    use_bias: bool = True
    force_fp32_for_softmax: bool = True
    only_pure_attention: bool = False
    use_cross_only: bool = False
    bhld: Optional[bool] = None
    kernel_init: Callable = kernel_init(1.0)

    @nn.compact
    def __call__(self, x: jax.Array, context: Optional[jax.Array] = None) -> jax.Array:
        attn = lambda name: AttentionLayer(
            heads=self.heads, dim_head=self.dim_head, backend=self.backend,
            dtype=self.dtype, precision=self.precision, use_bias=self.use_bias,
            force_fp32_for_softmax=self.force_fp32_for_softmax,
            bhld=self.bhld, kernel_init=self.kernel_init, name=name)
        ln = lambda name: nn.LayerNorm(dtype=jnp.float32, name=name)
        if self.only_pure_attention:
            return attn("attn1")(ln("norm1")(x),
                                 context if self.use_cross_only else None)
        x = x + attn("attn1")(ln("norm1")(x),
                              context if self.use_cross_only else None)
        if context is not None and not self.use_cross_only:
            x = x + attn("attn2")(ln("norm2")(x), context)
        x = x + GEGLUFeedForward(x.shape[-1], dtype=self.dtype,
                                 precision=self.precision, name="ff")(
            ln("norm3")(x))
        return x


class TransformerBlock(nn.Module):
    """Outer wrapper: optional in/out projection + residual around N basic
    blocks (reference attention.py:305-380)."""

    heads: int = 4
    dim_head: int = 64
    depth: int = 1
    backend: str = "auto"
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None
    use_projection: bool = False
    use_linear_attention: bool = True  # linear (Dense) vs conv projection
    only_pure_attention: bool = False
    use_self_and_cross: bool = True
    force_fp32_for_softmax: bool = True
    bhld: Optional[bool] = None
    kernel_init: Callable = kernel_init(1.0)

    @nn.compact
    def __call__(self, x: jax.Array, context: Optional[jax.Array] = None) -> jax.Array:
        spatial = x.ndim == 4
        inner = self.heads * self.dim_head
        residual = x
        if spatial:
            b, h, w, c = x.shape
            x = x.reshape(b, h * w, c)
        else:
            c = x.shape[-1]
        if self.use_projection:
            x = nn.Dense(inner, dtype=self.dtype, precision=self.precision,
                         name="proj_in")(x)
        for i in range(self.depth):
            x = BasicTransformerBlock(
                heads=self.heads, dim_head=self.dim_head, backend=self.backend,
                dtype=self.dtype, precision=self.precision,
                force_fp32_for_softmax=self.force_fp32_for_softmax,
                only_pure_attention=self.only_pure_attention,
                use_cross_only=not self.use_self_and_cross and context is not None,
                bhld=self.bhld, kernel_init=self.kernel_init,
                name=f"block_{i}")(
                x, context=context)
        if self.use_projection:
            x = nn.Dense(c, dtype=self.dtype, precision=self.precision,
                         kernel_init=kernel_init(0.0), name="proj_out")(x)
        if spatial:
            x = x.reshape(b, h, w, c)
        return x + residual
