"""A `cohere2_moe` decoder layer stack as a denoiser trunk.

The published block (CohereLabs `command-a-plus-05-2026`, `model_type`
`cohere2_moe`) under this repository's diffusion interface
`model(x, temb, textcontext)`. The dataclass fields ARE the source's
`config.json` keys under the source's names, so a configuration file
that holds those keys builds the model with no translation; the fields
below `# the program's own` are this repository's.

Sequence: `[time token; text tokens; patch tokens]`, embedded and read
out as `models/trunk.py` sets out (shared with `models/brumby.py`).
Positions are indices in this sequence. The conditioning comes first, so
under the published causal mask every patch token sees all of it (the
published block has no AdaLN: conditioning is in context, as in
`models/uvit.py`).

Block (`use_parallel_block`): `h = LayerNorm(x)` (mean-subtracting, a
weight and no bias, eps `layer_norm_eps`); `y = x + Attn(h) + MoE(h)`.

`Attn`: `q = h Wq` (`num_attention_heads` x `head_dim`), `k = h Wk`,
`v = h Wv` (`num_key_value_heads` x `head_dim`), no bias
(`attention_bias`), no q/k norm (`use_qk_norm`); query head i reads
key/value head `i // (heads / kv heads)`; scores over `sqrt(head_dim)`.
On a `sliding_attention` layer RoPE on q and k (`rope_gptj`: interleaved
pairs, `rope_theta`, `rotary_pct` 1) and query i sees keys j with
`i - sliding_window < j <= i`; on a `full_attention` layer no positional
term at all and `j <= i`. `Wo`: heads x head_dim -> hidden.

`MoE`: `s = sigmoid(h Wr)` over ALL `router_experts` experts (float32);
the `num_experts_per_tok` largest; `w_e = s_e / sum of those`
(`norm_topk_prob`); `E(h) = Wdown (silu(Wgate h) * Wup h)` at width
`intermediate_size` (`use_gated_activation`, `hidden_act`); routed = sum
over the selected experts THAT ARE HELD HERE (`num_experts` of them,
from `first_expert`) of `w_e E_e(h)`; shared = the mean of the
`num_shared_experts` shared experts
(`shared_expert_combination_strategy` average); `MoE(h) = routed +
shared`. What the absent experts would add is left out: this chip's
share of a deployment that divides each layer by expert parallelism.

Out: final LayerNorm, `Dense(hidden -> patch^2 * output_channels)` on
the patch tokens, unpatchify.

`return_tally=True` also returns what the serving path counts
(`moe/picks_*`, docs/OBSERVABILITY.md), by name: `picks`, the held
picks by layer and expert, `[B, layers, num_experts]` int32, and
`fitted` [B, layers] int32, those of them the routed layer's first pass
served (`ops/moe.py`).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import moe
from ..ops.attention import attend
from ..typing import Dtype
from .trunk import (Kernel, SequenceEmbed, patch_head, rope_interleaved,
                    sequence_tokens)


def _norm(eps: float, param_dtype, name: str) -> nn.Module:
    """The published LayerNorm: mean-subtracting, a weight, no bias;
    computed in float32."""
    return nn.LayerNorm(epsilon=eps, use_bias=False, dtype=jnp.float32,
                        param_dtype=param_dtype, name=name)


class Cohere2MoEBlock(nn.Module):
    """One parallel block: y = x + Attn(LN(x)) + MoE(LN(x)) over a
    float32 residual stream. Returns (y, held picks [B, num_experts]
    int32, the held picks the routed layer's first pass served [B]
    int32)."""

    head_dim: int
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_shared_experts: int
    router_experts: int
    first_expert: int
    norm_topk_prob: bool
    attention_bias: bool
    layer_norm_eps: float
    rope_theta: float
    window: Optional[int]       # None on a full_attention layer: no RoPE
    dtype: Optional[Dtype] = None
    backend: str = "auto"

    @nn.compact
    def __call__(self, x: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        dt = self.dtype or jnp.float32      # products AND the weights held
        b, s, d = x.shape
        h32 = _norm(self.layer_norm_eps, dt, "norm")(x)
        h = h32.astype(dt)

        # -- attention: grouped queries, causal, a window on sliding layers
        def proj(name, heads):
            return nn.DenseGeneral((heads, self.head_dim),
                                   use_bias=self.attention_bias, dtype=dt,
                                   param_dtype=dt, name=name)(h)
        q = proj("to_q", self.num_attention_heads)
        k = proj("to_k", self.num_key_value_heads)
        v = proj("to_v", self.num_key_value_heads)
        if self.window is not None:
            q = rope_interleaved(q, self.rope_theta)
            k = rope_interleaved(k, self.rope_theta)
        a = attend(q, k, v, backend=self.backend, causal=True,
                   window=self.window)
        a = nn.DenseGeneral(d, axis=(-2, -1), use_bias=self.attention_bias,
                            dtype=dt, param_dtype=dt, name="to_out")(a)

        # -- experts: the router at its published width, the experts held
        f, held, n_sh = (self.intermediate_size, self.num_experts,
                         self.num_shared_experts)

        def kernel(name, *shape):
            return Kernel(shape, dt, name=name)()
        tokens32 = h32.reshape(b * s, d)
        idx, weights = moe.route(
            tokens32, Kernel((d, self.router_experts), name="router")(),
            self.num_experts_per_tok, self.norm_topk_prob)
        # the held picks of each batch row: [B, held]
        local, picks = jax.vmap(
            lambda i: moe.held_picks(i, self.first_expert, held))(
            idx.reshape(b, -1, idx.shape[-1]))
        local = local.reshape(idx.shape)
        tokens = tokens32.astype(dt)
        routed, fitted = moe.routed_experts(
            tokens, local, weights, kernel("experts_gate", held, d, f),
            kernel("experts_up", held, d, f),
            kernel("experts_down", held, f, d), self.router_experts)
        gate = jnp.einsum("nd,edf->enf", tokens,
                          kernel("shared_experts_gate", n_sh, d, f),
                          preferred_element_type=jnp.float32)
        up = jnp.einsum("nd,edf->enf", tokens,
                        kernel("shared_experts_up", n_sh, d, f),
                        preferred_element_type=jnp.float32)
        mid = (gate * jax.nn.sigmoid(gate) * up).astype(dt)
        shared = jnp.einsum("enf,efd->nd", mid,
                            kernel("shared_experts_down", n_sh, f, d),
                            preferred_element_type=jnp.float32) / n_sh
        m = (routed + shared).reshape(b, s, d)
        return (x + a.astype(jnp.float32) + m, picks,
                jnp.sum(fitted.reshape(b, -1), axis=1))


class Cohere2MoEDenoiser(nn.Module):
    """`[time; text; patches]` through `num_hidden_layers` parallel
    blocks; see the module docstring for the equations."""

    # -- the source's keys, under the source's names
    hidden_size: int = 4096
    head_dim: int = 128
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    intermediate_size: int = 4096
    num_hidden_layers: int = 32
    layer_types: Tuple[str, ...] = ()
    sliding_window: int = 4096
    num_experts: int = 128              # routed experts HELD here
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    expert_selection_fn: str = "sigmoid"
    norm_topk_prob: bool = True
    shared_expert_combination_strategy: str = "average"
    use_gated_activation: bool = True
    hidden_act: str = "silu"
    use_parallel_block: bool = True
    use_qk_norm: bool = False
    attention_bias: bool = False
    layer_norm_eps: float = 1e-5
    rms_norm_eps: Optional[float] = None
    position_embedding_type: str = "rope_gptj"
    rope_theta: float = 50000.0
    rotary_pct: float = 1.0
    first_k_dense_replace: int = 0
    # read only where first_k_dense_replace > 0: the dense prefix layers
    prefix_dense_intermediate_size: int = 16384
    prefix_dense_sliding_window_pattern: int = 1
    # -- the program's own
    patch_size: int = 2
    output_channels: int = 4
    router_experts: int = 128           # the router's published width
    first_expert: int = 0               # the first routed expert held
    dtype: Optional[Dtype] = jnp.bfloat16   # products AND the weights held
    backend: str = "auto"

    def __post_init__(self):
        super().__post_init__()
        published = {
            "expert_selection_fn": "sigmoid", "use_parallel_block": True,
            "use_gated_activation": True, "hidden_act": "silu",
            "shared_expert_combination_strategy": "average",
            "use_qk_norm": False, "position_embedding_type": "rope_gptj",
            "rotary_pct": 1, "first_k_dense_replace": 0,
            "rms_norm_eps": None}
        for key, want in published.items():
            if getattr(self, key) != want:
                raise ValueError(
                    f"cohere2_moe_dn: {key}={getattr(self, key)!r} is not "
                    f"built; only the published {want!r} is")
        if len(self._kinds()) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types lists {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        if self.first_expert + self.num_experts > self.router_experts:
            raise ValueError("the experts held lie outside the router")

    def _kinds(self) -> Tuple[str, ...]:
        return tuple(self.layer_types) or (
            ("full_attention",) * self.num_hidden_layers)

    @property
    def tally_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """What one evaluation counts, by name, which the serving path
        carries with a row: the held picks by layer and expert, and by
        layer those the routed layer's first pass served."""
        return {"picks": (self.num_hidden_layers, self.num_experts),
                "fitted": (self.num_hidden_layers,)}

    def routed_picks(self, sample_shape, context_tokens: int) -> int:
        """Token-picks the routers make in ONE evaluation of one sample
        of `sample_shape` [H, W, C], wherever the experts are."""
        tokens = sequence_tokens(sample_shape, self.patch_size,
                                 context_tokens)
        return tokens * self.num_experts_per_tok * self.num_hidden_layers

    def tally_counters(self, tally, evaluations: int, sample_shape,
                       context_tokens: int) -> Dict[str, int]:
        """The telemetry counters a finished request adds, from its
        tally over `evaluations` evaluations."""
        return moe.pick_counters(
            tally["picks"],
            evaluations * self.routed_picks(sample_shape, context_tokens),
            tally["fitted"])

    @nn.compact
    def __call__(self, x: jax.Array, temb: jax.Array,
                 textcontext: Optional[jax.Array] = None,
                 return_tally: bool = False):
        tokens = SequenceEmbed(self.hidden_size, self.patch_size,
                               self.dtype, name="embed")(x, temb,
                                                         textcontext)
        picks, fitted = [], []
        for i, kind in enumerate(self._kinds()):
            tokens, n, fit = Cohere2MoEBlock(
                head_dim=self.head_dim,
                num_attention_heads=self.num_attention_heads,
                num_key_value_heads=self.num_key_value_heads,
                intermediate_size=self.intermediate_size,
                num_experts=self.num_experts,
                num_experts_per_tok=self.num_experts_per_tok,
                num_shared_experts=self.num_shared_experts,
                router_experts=self.router_experts,
                first_expert=self.first_expert,
                norm_topk_prob=self.norm_topk_prob,
                attention_bias=self.attention_bias,
                layer_norm_eps=self.layer_norm_eps,
                rope_theta=self.rope_theta,
                window=(self.sliding_window if kind == "sliding_attention"
                        else None),
                dtype=self.dtype, backend=self.backend,
                name=f"layer_{i}")(tokens)
            picks.append(n)
            fitted.append(fit)
        out = patch_head(
            tokens, _norm(self.layer_norm_eps, jnp.float32, "final_norm"),
            x.shape, self.patch_size, self.output_channels)
        if return_tally:
            return out, {"picks": jnp.stack(picks, axis=1),
                         "fitted": jnp.stack(fitted, axis=1)}
        return out
