"""What the decoder-layer denoiser trunks share (`models/cohere2_moe.py`,
`models/brumby.py`, `models/glm_moe_dsa.py`, `models/smallthinker.py`):
the token layout `[time token; text tokens; patch tokens]`, its
embedding, a bare weight, the two rotations (interleaved pairs and
half-split), and the patch head.

The time token is the sinusoidal timestep embedding (`TIME_FEATURES`
features) through the two-layer `TimeProjection` to `hidden_size`; text
is `Dense(features -> hidden_size)`; patches are `PatchEmbedding` (patch
`patch_size`, raster order). Positions are indices in this sequence. The
conditioning comes first, so under a causal layer every patch token sees
all of it. The head reads the patch tokens only: a norm the trunk names,
`Dense(hidden -> patch^2 * output_channels)`, unpatchify.

Module and leaf names here are part of the served weights:
`benchmark/harness/weights.py` fills each leaf from the hash of its path.
"""
from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..typing import Dtype
from .common import TimeEmbedding, TimeProjection
from .vit_common import PatchEmbedding

TIME_FEATURES = 256     # sinusoidal features ahead of the time MLP


def sequence_tokens(sample_shape, patch_size: int,
                    context_tokens: int) -> int:
    """Tokens of one sample of `sample_shape` [H, W, C]: the time token,
    the text tokens, the patch tokens."""
    return 1 + context_tokens + (sample_shape[0] // patch_size) * (
        sample_shape[1] // patch_size)


def rope_interleaved(x: jax.Array, theta: float) -> jax.Array:
    """Interleaved RoPE (`rope_gptj`, `rope_interleave`) over
    [B, S, H, D]: the pairs (x[2i], x[2i+1]) rotated by position *
    theta^(-2i/D), position = index in the sequence."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)     # [S, D/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                    axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rope_half_split(x: jax.Array, theta: float) -> jax.Array:
    """Half-split RoPE (rotate-half, the Llama lineage's pairing) over
    [B, S, H, D]: (x[i], x[i + D/2]) rotated by position *
    theta^(-2i/D), position = index in the sequence."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)     # [S, D/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    lo, hi = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1).astype(x.dtype)


class Kernel(nn.Module):
    """A bare weight named `kernel` (no bias): a stack of experts'
    matrices [experts, in, out], or a router's [in, experts]."""

    shape: Tuple[int, ...]
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self) -> jax.Array:
        init = nn.initializers.lecun_normal(
            in_axis=-2, out_axis=-1,
            batch_axis=tuple(range(len(self.shape) - 2)))
        return self.param("kernel", init, self.shape, self.param_dtype)


class SequenceEmbed(nn.Module):
    """[B, H, W, C], [B], [B, L, F] -> [B, 1 + L + patches, hidden]
    float32: the time token, the text tokens, the patch tokens."""

    hidden_size: int
    patch_size: int
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x, temb, textcontext=None):
        d = self.hidden_size
        t = TimeProjection(features=d, dtype=self.dtype, name="t_proj")(
            TimeEmbedding(features=TIME_FEATURES)(temb))
        seq = [t[:, None, :]]
        if textcontext is not None:
            seq.append(nn.Dense(d, dtype=self.dtype,
                                name="text_proj")(textcontext))
        seq.append(PatchEmbedding(patch_size=self.patch_size,
                                  embedding_dim=d, dtype=self.dtype,
                                  name="patch_embed")(x))
        return jnp.concatenate([s.astype(jnp.float32) for s in seq], axis=1)


def patch_head(tokens: jax.Array, norm: nn.Module, sample_shape,
               patch_size: int, output_channels: int) -> jax.Array:
    """The patch tokens of [B, S, hidden] through `norm` and the
    float32 `final_proj` to [B, H, W, output_channels]. Called inside the
    trunk's compact `__call__`, so `final_proj` is the trunk's child."""
    p = patch_size
    b, hgt, wid = sample_shape[:3]
    n_patch = (hgt // p) * (wid // p)
    out = nn.Dense(p * p * output_channels, dtype=jnp.float32,
                   name="final_proj")(norm(tokens[:, -n_patch:]))
    out = out.reshape(b, hgt // p, wid // p, p, p, output_channels)
    return out.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, hgt, wid, output_channels)
