"""A `brumby` decoder layer stack as a denoiser trunk.

The published block (Manifest AI `Brumby-14B-Base`, `model_type` `brumby`:
the Qwen3-14B layer with its softmax attention replaced by gated power
retention) under this repository's diffusion interface `model(x, temb,
textcontext)`. The dataclass fields ARE the source's `config.json` keys
under the source's names; the fields below `# the program's own` are
this repository's.

Sequence: `[time token; text tokens; patch tokens]`, embedded and read
out as `models/trunk.py` sets out (shared with `models/cohere2_moe.py`);
positions are indices in it; float32 residual stream, weights in `dtype`.

Block (sequential, pre-norm), for layer input `x`:

    h  = RMSNorm(x; rms_norm_eps)
    q  = RoPE(RMSNorm_head(W_q h))  [T, heads, head_dim]
    k  = RoPE(RMSNorm_head(W_k h))  [T, kv heads, head_dim]
    v  = W_v h                      [T, kv heads, head_dim]
    log g_t = log_sigmoid(W_g h_t + b_g)    [T, kv heads], float32
    G_t = sum_{r <= t} log g_r      (query head i reads kv head i // group)
    w_ts = (q_t . k_s / sqrt(head_dim))^2 * exp(G_t - G_s)   s <= t, else 0
    o_t  = sum_s w_ts v_s / (sum_s w_ts + eps_n)
    a  = x + W_o o
    y  = a + W_down(silu(W_gate n) * (W_up n)),   n = RMSNorm(a)

No bias on q, k, v, o (`attention_bias`); `RMSNorm_head` is over
`head_dim` with one weight vector for all heads; RoPE is the half-split
rotation (`x[:D/2]`, `x[D/2:]`) at `rope_theta` over the whole head. Out:
a final RMSNorm and the patch head. The retention is
`ops/power_retention.py` (degree `POWER_DEGREE`, `NORM_EPS`).

What the source's `config.json` does not give and is assumed (the
benchmark's configuration lists each with its referent): the power's
degree 2 and the gated, sum-normalised form; the gate a Dense from the
hidden size to one scalar a KEY/VALUE head, with bias, through
`log_sigmoid` in float32; `eps_n` 1e-6; per-head q/k RMSNorm and
half-split RoPE (the Qwen3 lineage). `sliding_window`,
`use_sliding_window` and `max_window_layers` are held and unused: the
published values switch every window off.
"""
from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import power_retention as retention
from ..typing import Dtype
from .trunk import SequenceEmbed, patch_head, rope_half_split

POWER_DEGREE = 2
NORM_EPS = 1e-6         # eps_n, beside the retention's normaliser


def _rms(eps: float, param_dtype, name: str) -> nn.Module:
    return nn.RMSNorm(epsilon=eps, dtype=jnp.float32,
                      param_dtype=param_dtype, name=name)


class BrumbyBlock(nn.Module):
    """One sequential pre-norm block over a float32 residual stream."""

    head_dim: int
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int
    attention_bias: bool
    rms_norm_eps: float
    rope_theta: float
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dt = self.dtype or jnp.float32      # products AND the weights held
        d = x.shape[-1]
        h32 = _rms(self.rms_norm_eps, dt, "norm")(x)
        h = h32.astype(dt)

        def proj(name, heads):
            return nn.DenseGeneral((heads, self.head_dim),
                                   use_bias=self.attention_bias, dtype=dt,
                                   param_dtype=dt, name=name)(h)
        # norm and rotation in float32, rounded to the products' type once
        q = rope_half_split(_rms(self.rms_norm_eps, dt, "q_norm")(
            proj("to_q", self.num_attention_heads)), self.rope_theta)
        k = rope_half_split(_rms(self.rms_norm_eps, dt, "k_norm")(
            proj("to_k", self.num_key_value_heads)), self.rope_theta)
        v = proj("to_v", self.num_key_value_heads)
        log_g = jax.nn.log_sigmoid(nn.Dense(
            self.num_key_value_heads, dtype=jnp.float32, name="to_gate")(h32))
        o = retention.power_retention(
            q.astype(dt), k.astype(dt), v, log_g,
            degree=POWER_DEGREE, eps=NORM_EPS)
        a = x + nn.DenseGeneral(
            d, axis=(-2, -1), use_bias=self.attention_bias, dtype=dt,
            param_dtype=dt, name="to_out")(o).astype(jnp.float32)

        n = _rms(self.rms_norm_eps, dt, "mlp_norm")(a).astype(dt)

        def dense(name, width):
            return nn.Dense(width, use_bias=False, dtype=dt, param_dtype=dt,
                            name=name)
        gate = dense("mlp_gate", self.intermediate_size)(n).astype(
            jnp.float32)
        mid = (gate * jax.nn.sigmoid(gate)
               * dense("mlp_up", self.intermediate_size)(n)).astype(dt)
        return a + dense("mlp_down", d)(mid).astype(jnp.float32)


class BrumbyDenoiser(nn.Module):
    """`[time; text; patches]` through `num_hidden_layers` blocks; see
    the module docstring for the equations."""

    # -- the source's keys, under the source's names
    attention_bias: bool = False
    head_dim: int = 128
    hidden_act: str = "silu"
    hidden_size: int = 5120
    intermediate_size: int = 17408
    max_position_embeddings: int = 32768
    max_window_layers: int = 40
    model_type: str = "brumby"
    num_attention_heads: int = 40
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    rms_norm_eps: float = 1e-6
    rope_scaling: Any = None
    rope_theta: float = 1000000.0
    sliding_window: Optional[int] = None
    tie_word_embeddings: bool = False
    use_sliding_window: bool = False
    vocab_size: int = 151936            # no vocabulary row is held
    # -- the program's own
    patch_size: int = 2
    output_channels: int = 4
    dtype: Optional[Dtype] = jnp.bfloat16   # products AND the weights held

    def __post_init__(self):
        super().__post_init__()
        published = {"hidden_act": "silu", "rope_scaling": None,
                     "use_sliding_window": False, "model_type": "brumby"}
        for key, want in published.items():
            if getattr(self, key) != want:
                raise ValueError(
                    f"brumby_dn: {key}={getattr(self, key)!r} is not "
                    f"built; only the published {want!r} is")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads are not a "
                f"multiple of {self.num_key_value_heads} key/value heads")

    @nn.compact
    def __call__(self, x: jax.Array, temb: jax.Array,
                 textcontext: Optional[jax.Array] = None) -> jax.Array:
        tokens = SequenceEmbed(self.hidden_size, self.patch_size,
                               self.dtype, name="embed")(x, temb,
                                                         textcontext)
        for i in range(self.num_hidden_layers):
            tokens = BrumbyBlock(
                head_dim=self.head_dim,
                num_attention_heads=self.num_attention_heads,
                num_key_value_heads=self.num_key_value_heads,
                intermediate_size=self.intermediate_size,
                attention_bias=self.attention_bias,
                rms_norm_eps=self.rms_norm_eps,
                rope_theta=self.rope_theta, dtype=self.dtype,
                name=f"layer_{i}")(tokens)
        return patch_head(
            tokens, _rms(self.rms_norm_eps, jnp.float32, "final_norm"),
            x.shape, self.patch_size, self.output_channels)
