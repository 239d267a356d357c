"""Shared model layers (NHWC, bf16-compute/f32-param by default).

Capability parity with reference flaxdiff/models/common.py:13-337
(TimeEmbedding, FourierEmbedding, TimeProjection, WeightStandardizedConv,
SeparableConv, ConvLayer, PixelShuffle, Upsample, Downsample, ResidualBlock)
— redesigned for TPU: NHWC layouts feed the MXU's native conv tiling, norms
compute in f32 and cast back, and the resblock epilogue is fusable by XLA
(or the Pallas fused GroupNorm+SiLU kernel in ops/fused_norm.py).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..typing import Dtype


def kernel_init(scale: float = 1.0, mode: str = "fan_avg") -> Callable:
    """Variance-scaling init; scale<=0 means exact zeros (zero-init layers).

    The reference clamps scale to 1e-10 (flaxdiff/models/common.py:13-15),
    leaving "zero"-init outputs slightly nonzero; true zeros are the standard
    semantics for zero-init output convs / AdaLN-Zero and what we use here.
    """
    if scale <= 0.0:
        return nn.initializers.zeros_init()
    return nn.initializers.variance_scaling(scale, mode=mode, distribution="truncated_normal")


class TimeEmbedding(nn.Module):
    """Sinusoidal timestep embedding (reference common.py:81-95)."""

    features: int
    max_period: float = 10000.0

    @nn.compact
    def __call__(self, t: jax.Array) -> jax.Array:
        half = self.features // 2
        freqs = jnp.exp(-jnp.log(self.max_period)
                        * jnp.arange(half, dtype=jnp.float32) / half)
        args = t.astype(jnp.float32)[:, None] * freqs[None, :]
        emb = jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)
        if self.features % 2:
            emb = jnp.pad(emb, [(0, 0), (0, 1)])
        return emb


class FourierEmbedding(nn.Module):
    """Random-Fourier timestep embedding with a FIXED (non-learned) projection.

    The fixed PRNGKey(42) projection is a deliberate reference quirk kept for
    checkpoint compatibility (reference common.py:97-108, SURVEY.md §7.4).
    """

    features: int
    scale: float = 16.0

    def setup(self):
        self.freqs = jax.random.normal(
            jax.random.PRNGKey(42), (self.features // 2,)) * self.scale

    def __call__(self, t: jax.Array) -> jax.Array:
        args = t.astype(jnp.float32)[:, None] * self.freqs[None, :] * 2 * jnp.pi
        return jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)


class TimeProjection(nn.Module):
    """2-layer MLP over the time embedding (reference common.py:110-124)."""

    features: int
    activation: Callable = jax.nn.gelu
    dtype: Optional[Dtype] = None
    kernel_init: Callable = kernel_init(1.0)

    @nn.compact
    def __call__(self, emb: jax.Array) -> jax.Array:
        emb = nn.Dense(self.features, dtype=self.dtype, kernel_init=self.kernel_init)(emb)
        emb = self.activation(emb)
        emb = nn.Dense(self.features, dtype=self.dtype, kernel_init=self.kernel_init)(emb)
        return emb


class WeightStandardizedConv(nn.Module):
    """Conv with weight standardization (reference common.py:18-66).

    Standardization runs in f32 regardless of compute dtype — the mean/var
    of bf16 weights underflows otherwise.
    """

    features: int
    kernel_size: Tuple[int, int] = (3, 3)
    strides: Union[int, Tuple[int, int]] = 1
    padding: Union[str, int] = "SAME"
    dtype: Optional[Dtype] = None
    param_dtype: Dtype = jnp.float32
    precision: Optional[jax.lax.Precision] = None
    kernel_init: Callable = kernel_init(1.0)

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        conv = nn.Conv(
            self.features, self.kernel_size, strides=self.strides,
            padding=self.padding, dtype=self.dtype, param_dtype=self.param_dtype,
            precision=self.precision, kernel_init=self.kernel_init, name="conv")

        def std_kernel(variables):
            k = variables["params"]["kernel"].astype(jnp.float32)
            mean = jnp.mean(k, axis=(0, 1, 2), keepdims=True)
            var = jnp.var(k, axis=(0, 1, 2), keepdims=True)
            k = (k - mean) / jnp.sqrt(var + 1e-5)
            new = dict(variables)
            new["params"] = dict(variables["params"])
            new["params"]["kernel"] = k.astype(variables["params"]["kernel"].dtype)
            return new

        return nn.map_variables(conv, "params", std_kernel, init=self.is_initializing())(x)


class SeparableConv(nn.Module):
    """Depthwise + pointwise conv (reference common.py:126-153)."""

    features: int
    kernel_size: Tuple[int, int] = (3, 3)
    strides: Union[int, Tuple[int, int]] = 1
    padding: Union[str, int] = "SAME"
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None
    use_bias: bool = False
    kernel_init: Callable = kernel_init(1.0)

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        in_features = x.shape[-1]
        depthwise = nn.Conv(
            in_features, self.kernel_size, strides=self.strides,
            padding=self.padding, feature_group_count=in_features,
            use_bias=self.use_bias, dtype=self.dtype, precision=self.precision,
            kernel_init=self.kernel_init, name="depthwise")(x)
        pointwise = nn.Conv(
            self.features, (1, 1), use_bias=self.use_bias, dtype=self.dtype,
            precision=self.precision, kernel_init=self.kernel_init,
            name="pointwise")(depthwise)
        return pointwise


class ConvLayer(nn.Module):
    """Conv dispatcher: conv / w_conv / separable / conv_transpose
    (reference common.py:155-201)."""

    conv_type: str
    features: int
    kernel_size: Tuple[int, int] = (3, 3)
    strides: Union[int, Tuple[int, int]] = 1
    padding: Union[str, int] = "SAME"
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None
    kernel_init: Callable = kernel_init(1.0)

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if self.conv_type == "conv":
            return nn.Conv(self.features, self.kernel_size, strides=self.strides,
                           padding=self.padding, dtype=self.dtype,
                           precision=self.precision, kernel_init=self.kernel_init)(x)
        if self.conv_type == "w_conv":
            return WeightStandardizedConv(
                self.features, self.kernel_size, strides=self.strides,
                padding=self.padding, dtype=self.dtype, precision=self.precision,
                kernel_init=self.kernel_init)(x)
        if self.conv_type == "separable":
            return SeparableConv(self.features, self.kernel_size,
                                 strides=self.strides, padding=self.padding,
                                 dtype=self.dtype, precision=self.precision,
                                 kernel_init=self.kernel_init)(x)
        if self.conv_type == "conv_transpose":
            return nn.ConvTranspose(self.features, self.kernel_size,
                                    strides=(2, 2), padding=self.padding,
                                    dtype=self.dtype, precision=self.precision,
                                    kernel_init=self.kernel_init)(x)
        raise ValueError(f"Unknown conv_type {self.conv_type!r}")


class PixelShuffle(nn.Module):
    """Depth-to-space upscale (reference common.py:68-79)."""

    scale: int

    def __call__(self, x: jax.Array) -> jax.Array:
        b, h, w, c = x.shape
        s = self.scale
        x = x.reshape(b, h, w, s, s, c // (s * s))
        x = x.transpose(0, 1, 3, 2, 4, 5)
        return x.reshape(b, h * s, w * s, c // (s * s))


class Upsample(nn.Module):
    """Nearest-resize + conv (reference common.py:203-226)."""

    features: int
    scale: int = 2
    activation: Callable = jax.nn.swish
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None
    kernel_init: Callable = kernel_init(1.0)

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, h, w, c = x.shape
        x = jax.image.resize(x, (b, h * self.scale, w * self.scale, c), "nearest")
        return ConvLayer("conv", self.features, (3, 3), 1, dtype=self.dtype,
                         precision=self.precision, kernel_init=self.kernel_init)(x)


class Downsample(nn.Module):
    """Stride-2 conv (reference common.py:228-249)."""

    features: int
    scale: int = 2
    activation: Callable = jax.nn.swish
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None
    kernel_init: Callable = kernel_init(1.0)

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        return ConvLayer("conv", self.features, (3, 3), (self.scale, self.scale),
                         dtype=self.dtype, precision=self.precision,
                         kernel_init=self.kernel_init)(x)


def _norm_factory(norm_groups: int, dtype) -> Callable[[], nn.Module]:
    if norm_groups > 0:
        return lambda name: nn.GroupNorm(norm_groups, dtype=jnp.float32, name=name)
    return lambda name: nn.RMSNorm(dtype=jnp.float32, name=name)


class FusedGroupNormSiLU(nn.Module):
    """GroupNorm + SiLU through ops/fused_norm.py: the fused Pallas
    kernels at sampling batches, the XLA composition at training batches
    (multiples of 16), picked there from the input's shape.

    Param names match nn.GroupNorm ('scale'/'bias'), so checkpoints are
    interchangeable with the unfused (norm, swish) pair.
    """

    groups: int = 8
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from ..ops.fused_norm import fused_groupnorm_silu
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        return fused_groupnorm_silu(x, scale, bias, groups=self.groups,
                                    eps=self.eps)


class ResidualBlock(nn.Module):
    """GroupNorm(/RMSNorm) -> swish -> conv -> +temb -> norm -> swish -> conv
    -> +skip(1x1) (reference common.py:258-337).

    Norms run in f32; convs in `dtype` (bf16 on TPU). The (norm, swish, conv)
    prologue is the Pallas fusion target (ops/fused_norm.py).
    """

    conv_type: str = "conv"
    features: int = 64
    kernel_size: Tuple[int, int] = (3, 3)
    strides: Union[int, Tuple[int, int]] = 1
    padding: Union[str, int] = "SAME"
    activation: Callable = jax.nn.swish
    norm_groups: int = 8
    dtype: Optional[Dtype] = None
    precision: Optional[jax.lax.Precision] = None
    kernel_init: Callable = kernel_init(1.0)

    @nn.compact
    def __call__(self, x: jax.Array, temb: Optional[jax.Array] = None,
                 extra_features: Optional[jax.Array] = None) -> jax.Array:
        # swish IS jax.nn.silu (alias), so the fused GroupNorm+SiLU Pallas
        # path engages for the default config.
        fused = (self.norm_groups > 0
                 and self.activation in (jax.nn.swish, jax.nn.silu))

        def norm_act(h, name):
            if fused:
                return FusedGroupNormSiLU(self.norm_groups, name=name)(h)
            norm = _norm_factory(self.norm_groups, self.dtype)
            return self.activation(norm(name)(h))

        residual = x
        h = norm_act(x, "norm1")
        h = ConvLayer(self.conv_type, self.features, self.kernel_size,
                      self.strides, padding=self.padding, dtype=self.dtype,
                      precision=self.precision, kernel_init=self.kernel_init,
                      name="conv1")(h)
        if temb is not None:
            temb_proj = nn.Dense(self.features, dtype=self.dtype,
                                 kernel_init=self.kernel_init, name="temb_proj")(
                self.activation(temb))
            h = h + temb_proj[:, None, None, :]
        h = norm_act(h, "norm2")
        h = ConvLayer(self.conv_type, self.features, self.kernel_size, 1,
                      padding=self.padding, dtype=self.dtype,
                      precision=self.precision,
                      kernel_init=kernel_init(0.0), name="conv2")(h)
        if residual.shape[-1] != self.features:
            residual = ConvLayer("conv", self.features, (1, 1), 1,
                                 dtype=self.dtype, precision=self.precision,
                                 kernel_init=self.kernel_init,
                                 name="skip_proj")(residual)
        out = h + residual
        if extra_features is not None:
            out = jnp.concatenate([out, extra_features], axis=-1)
        return out
