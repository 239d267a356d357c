"""The few layers both references share, with the precision hook."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# the precision the products are computed in: "f32" (the reference),
# or a lower one for the control ("bf16", "fp8")
_PRECISION = ["f32"]


class precision:
    """`with precision("fp8"):` computes every product of the reference
    with operands rounded to that type (accumulation stays float32)."""

    def __init__(self, name: str):
        if name not in ("f32", "bf16", "fp8"):
            raise ValueError(f"unknown precision {name!r}")
        self.name = name

    def __enter__(self):
        _PRECISION.append(self.name)

    def __exit__(self, *exc):
        _PRECISION.pop()


def _round(x):
    """Operand of a product in the current precision. The rounding is
    straight-through (the gradient passes as if it were the identity),
    as in a low-precision matmul path whose backward operands are
    rounded the same way but whose gradients are kept in float32: a
    cotangent cast to fp8 would underflow to zero."""
    p = _PRECISION[-1]
    if p == "bf16":
        low = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif p == "fp8":
        # e4m3 with a per-tensor scale, as an fp8 matmul path would use
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
        s = 240.0 / amax
        low = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    else:
        return x
    return x + jax.lax.stop_gradient(low - x)


def einsum(spec, a, b):
    return jnp.einsum(spec, _round(a.astype(jnp.float32)),
                      _round(b.astype(jnp.float32)),
                      precision=jax.lax.Precision.HIGHEST)


def dense(p, x):
    return einsum("...i,io->...o", x, p["kernel"]) + p["bias"]


def conv(p, x, stride=1):
    y = jax.lax.conv_general_dilated(
        _round(x.astype(jnp.float32)), _round(p["kernel"].astype(jnp.float32)),
        (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    return y + p["bias"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def layer_norm(x, eps, p=None):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps)
    if p is not None:
        y = y * p["scale"] + p["bias"]
    return y


def group_norm(p, x, groups, eps=1e-6):
    b, h, w, c = x.shape
    g = x.reshape(b, h * w, groups, c // groups)
    mean = jnp.mean(g, axis=(1, 3), keepdims=True)
    var = jnp.mean((g - mean) ** 2, axis=(1, 3), keepdims=True)
    y = ((g - mean) / jnp.sqrt(var + eps)).reshape(b, h, w, c)
    return y * p["scale"] + p["bias"]


def fourier_embedding(t, features, scale=16.0):
    """Random-Fourier time features with the fixed PRNGKey(42)
    projection the architecture specifies."""
    freqs = jax.random.normal(jax.random.PRNGKey(42), (features // 2,)) * scale
    args = t.astype(jnp.float32)[:, None] * freqs[None, :] * 2 * jnp.pi
    return jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)


def attention(q, k, v):
    """softmax(q k^T / sqrt(d)) v over [B, L, H, D] operands."""
    d = q.shape[-1]
    logits = einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    probs = jax.nn.softmax(logits, axis=-1)
    return einsum("bhqk,bkhd->bqhd", probs, v)


def heads_in(p, x):
    return einsum("blc,chd->blhd", x, p["kernel"]) + p["bias"]


def heads_out(p, x):
    return einsum("blhd,hdc->blc", x, p["kernel"]) + p["bias"]
