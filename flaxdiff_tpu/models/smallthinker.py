"""A `smallthinker` decoder layer stack as a denoiser trunk.

The published block (PowerInfer `SmallThinker-21BA3B-Instruct`) under
this repository's diffusion interface `model(x, temb, textcontext)`. The
dataclass fields ARE the source's `config.json` keys under the source's
names; the fields below `# the program's own` are this repository's.

Sequence: `[time token; text tokens; patch tokens]`, embedded and read
out as `models/trunk.py` sets out; positions are indices in it; a
float32 residual stream, weights in `dtype`; causal as published.

Block (sequential, two RMSNorms), for layer `l` with input `x`:

    r  = W_r x                          the router reads the layer's
                                        INPUT, before the attention
    picks = the `moe_num_active_primary_experts` largest of r
    w  = softmax over the picked logits (`moe_primary_router_apply_softmax`;
         false: sigmoid of the picked logits, normalised over them where
         `norm_topk_prob`), float32
    x' = x + W_o Attn(q, k, v),  q, k, v from RMSNorm(x; rms_norm_eps)
    y  = x' + sum over the picks HELD HERE of
         w_i W_down,i (relu(W_gate,i h) * W_up,i h),  h = RMSNorm(x')

`Attn`: `num_attention_heads` query and `num_key_value_heads` key/value
heads of `head_dim` (query head i reads key/value head i // group), no
bias, scores over sqrt(head_dim), causal. Where
`sliding_window_layout[l]` is 1 query i sees keys j with
`i - sliding_window_size < j <= i`; where `rope_layout[l]` is 1 q and k
are rotated (half-split pairs, `rope_theta`, `rope_scaling` null). Each
list is read for what it names (the published lists agree: a full layer
has no positions). Experts: `moe_ffn_hidden_size` wide, ReLU-gated, no
shared expert, no dense layer. The layer holds `moe_num_primary_experts`
of the router's `router_experts` experts, from `first_expert`, and
computes their part only (`ops/moe.py`); what absent experts would add
is left out.

Assumed, the source's `config.json` giving none of them (the benchmark's
configuration lists each): that the router reads `x` and not
`RMSNorm(x)`; the float32 router and softmax; the half-split RoPE
pairing of the Llama lineage.

`return_tally=True` also returns what the serving path counts, by name:
`picks` [B, layers, held] and `fitted` [B, layers] int32, as
`models/cohere2_moe.py`. The attention's pairs (`attn/pairs_read`,
`attn/pairs_causal`, docs/OBSERVABILITY.md) are host arithmetic in
`tally_counters`.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import moe
from ..ops.attention import attend
from ..typing import Dtype
from .trunk import (Kernel, SequenceEmbed, patch_head, rope_half_split,
                    sequence_tokens)


def _rms(eps: float, param_dtype, name: str) -> nn.Module:
    return nn.RMSNorm(epsilon=eps, dtype=jnp.float32,
                      param_dtype=param_dtype, name=name)


def visible_pairs(tokens: int, window: Optional[int]) -> int:
    """(query, key) pairs a causal layer over `tokens` tokens reads:
    every causal pair, or under a window that binds the window's own
    triangle and `window` a query beyond it."""
    if window is None or window >= tokens:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


class SmallThinkerBlock(nn.Module):
    """One block over a float32 residual stream. Returns (y, held picks
    [B, held] int32, the held picks the routed layer's first pass served
    [B] int32)."""

    head_dim: int
    num_attention_heads: int
    num_key_value_heads: int
    moe_ffn_hidden_size: int
    moe_num_primary_experts: int
    moe_num_active_primary_experts: int
    moe_primary_router_apply_softmax: bool
    norm_topk_prob: bool
    router_experts: int
    first_expert: int
    rms_norm_eps: float
    rope_theta: float
    rope: bool                  # `rope_layout[l]`
    window: Optional[int]       # `sliding_window_size` where the layout says
    dtype: Optional[Dtype] = None
    backend: str = "auto"

    @nn.compact
    def __call__(self, x: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        dt = self.dtype or jnp.float32      # products AND the weights held
        b, s, d = x.shape
        held, f = self.moe_num_primary_experts, self.moe_ffn_hidden_size

        # -- the router stands before the attention and reads the input
        idx, weights = moe.route(
            x.reshape(b * s, d),
            Kernel((d, self.router_experts), name="router")(),
            self.moe_num_active_primary_experts, self.norm_topk_prob,
            weigh=("softmax_picked" if self.moe_primary_router_apply_softmax
                   else "sigmoid"))
        # the held picks of each batch row: [B, held]
        local, picks = jax.vmap(
            lambda i: moe.held_picks(i, self.first_expert, held))(
            idx.reshape(b, -1, idx.shape[-1]))
        local = local.reshape(idx.shape)

        # -- attention: grouped queries, causal; the layer's two bits
        h = _rms(self.rms_norm_eps, dt, "norm")(x).astype(dt)

        def proj(name, heads):
            return nn.DenseGeneral((heads, self.head_dim), use_bias=False,
                                   dtype=dt, param_dtype=dt, name=name)(h)
        q = proj("to_q", self.num_attention_heads)
        k = proj("to_k", self.num_key_value_heads)
        v = proj("to_v", self.num_key_value_heads)
        if self.rope:
            q = rope_half_split(q, self.rope_theta)
            k = rope_half_split(k, self.rope_theta)
        a = attend(q, k, v, backend=self.backend, causal=True,
                   window=self.window)
        x = x + nn.DenseGeneral(d, axis=(-2, -1), use_bias=False, dtype=dt,
                                param_dtype=dt, name="to_out")(a).astype(
            jnp.float32)

        # -- the picked experts held here, ReLU-gated, on the second norm
        def kernel(name, *shape):
            return Kernel(shape, dt, name=name)()
        tokens = _rms(self.rms_norm_eps, dt, "mlp_norm")(x).astype(dt)
        routed, fitted = moe.routed_experts(
            tokens.reshape(b * s, d), local, weights,
            kernel("experts_gate", held, d, f),
            kernel("experts_up", held, d, f),
            kernel("experts_down", held, f, d), self.router_experts, "relu")
        return (x + routed.reshape(b, s, d), picks,
                jnp.sum(fitted.reshape(b, -1), axis=1))


class SmallThinkerDenoiser(nn.Module):
    """`[time; text; patches]` through `num_hidden_layers` blocks; see
    the module docstring for the equations."""

    # -- the source's keys, under the source's names
    head_dim: int = 128
    hidden_size: int = 2560
    moe_ffn_hidden_size: int = 768
    moe_num_active_primary_experts: int = 6
    moe_num_primary_experts: int = 64   # routed experts HELD here
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    num_attention_heads: int = 28
    num_hidden_layers: int = 52
    num_key_value_heads: int = 4
    rms_norm_eps: float = 1e-6
    rope_layout: Tuple[int, ...] = ()
    rope_scaling: Any = None
    rope_theta: float = 1500000.0
    sliding_window_layout: Tuple[int, ...] = ()
    sliding_window_size: int = 4096
    # -- the program's own
    patch_size: int = 2
    output_channels: int = 4
    router_experts: int = 64            # the router's published width
    first_expert: int = 0               # the first routed expert held
    dtype: Optional[Dtype] = jnp.bfloat16   # products AND the weights held
    backend: str = "auto"

    # a row of a 1536 x 1536 image is 9,294 tokens: a guided turn's two
    # sequences fill the MXU alone, and a round's rows cost their sum. So
    # a serving round evaluates this model a row at a time
    # (`samplers/common.py` `rows_apart`) and is served in rounds of one.
    serve_rows_apart = True

    def __post_init__(self):
        super().__post_init__()
        if self.rope_scaling is not None:
            raise ValueError(f"smallthinker_dn: rope_scaling="
                             f"{self.rope_scaling!r} is not built; only the "
                             "published null is")
        for key in ("rope_layout", "sliding_window_layout"):
            bits = tuple(getattr(self, key))
            if len(bits) != self.num_hidden_layers or set(bits) - {0, 1}:
                raise ValueError(
                    f"{key} is {bits}: one 0 or 1 a layer, "
                    f"num_hidden_layers is {self.num_hidden_layers}")
        if self.first_expert + self.moe_num_primary_experts \
                > self.router_experts:
            raise ValueError("the experts held lie outside the router")

    def _windows(self) -> Tuple[Optional[int], ...]:
        return tuple(self.sliding_window_size if bit else None
                     for bit in self.sliding_window_layout)

    @property
    def tally_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """What one evaluation counts, by name, which the serving path
        carries with a row: the held picks by layer and expert, and by
        layer those the routed layer's first pass served."""
        return {"picks": (self.num_hidden_layers,
                          self.moe_num_primary_experts),
                "fitted": (self.num_hidden_layers,)}

    def tally_counters(self, tally, evaluations: int, sample_shape,
                       context_tokens: int) -> Dict[str, int]:
        """The telemetry counters a finished request adds, from its
        tally over `evaluations` evaluations of a sample of
        `sample_shape` [H, W, C]: the `moe/picks_*` four, and the
        attention's (query, key) pairs, host arithmetic: those the
        layers' masks let a query read (`attn/pairs_read`; a full layer
        reads its causal pairs) over the causal pairs
        (`attn/pairs_causal`), summed over the layers."""
        t = sequence_tokens(sample_shape, self.patch_size, context_tokens)
        out = moe.pick_counters(
            tally["picks"],
            evaluations * t * self.moe_num_active_primary_experts
            * self.num_hidden_layers, tally["fitted"])
        out["attn/pairs_read"] = evaluations * sum(
            visible_pairs(t, w) for w in self._windows())
        out["attn/pairs_causal"] = (evaluations * self.num_hidden_layers
                                    * visible_pairs(t, None))
        return out

    @nn.compact
    def __call__(self, x: jax.Array, temb: jax.Array,
                 textcontext: Optional[jax.Array] = None,
                 return_tally: bool = False):
        tokens = SequenceEmbed(self.hidden_size, self.patch_size,
                               self.dtype, name="embed")(x, temb,
                                                         textcontext)
        picks, fitted = [], []
        for i, (rope, window) in enumerate(zip(self.rope_layout,
                                               self._windows())):
            tokens, n, fit = SmallThinkerBlock(
                head_dim=self.head_dim,
                num_attention_heads=self.num_attention_heads,
                num_key_value_heads=self.num_key_value_heads,
                moe_ffn_hidden_size=self.moe_ffn_hidden_size,
                moe_num_primary_experts=self.moe_num_primary_experts,
                moe_num_active_primary_experts=(
                    self.moe_num_active_primary_experts),
                moe_primary_router_apply_softmax=(
                    self.moe_primary_router_apply_softmax),
                norm_topk_prob=self.norm_topk_prob,
                router_experts=self.router_experts,
                first_expert=self.first_expert,
                rms_norm_eps=self.rms_norm_eps,
                rope_theta=self.rope_theta, rope=bool(rope), window=window,
                dtype=self.dtype, backend=self.backend,
                name=f"layer_{i}")(tokens)
            picks.append(n)
            fitted.append(fit)
        out = patch_head(
            tokens, _rms(self.rms_norm_eps, jnp.float32, "final_norm"),
            x.shape, self.patch_size, self.output_channels)
        if return_tally:
            return out, {"picks": jnp.stack(picks, axis=1),
                         "fitted": jnp.stack(fitted, axis=1)}
        return out
