"""A `glm_moe_dsa` decoder layer stack as a denoiser trunk.

The published block (zai-org `GLM-5.2`, `model_type` `glm_moe_dsa`: the
DeepSeek-V3 layer, latent attention beside bias-corrected sigmoid
routing, with DeepSeek sparse attention's learned selection of keys
shared between layers) under this repository's diffusion interface
`model(x, temb, textcontext)`. The dataclass fields ARE the source's
`config.json` keys under the source's names; the fields below `# the
program's own` are this repository's.

Sequence: `[time token; text tokens; patch tokens]`, embedded and read
out as `models/trunk.py` sets out; positions are indices in it; float32
residual stream, weights and products in `dtype`. Causal as published:
the conditioning comes first, so every patch token sees all of it.

Block (sequential, pre-norm, RMSNorm eps `rms_norm_eps`), layer input
`x`, `h = RMSNorm(x)`:

    c_q = RMSNorm(h W_qa)                         [T, q_lora_rank]
    q   = c_q W_qb -> heads x qk_head_dim = [q_nope; q_rope]
    [c; k_r] = h W_kva                            [T, kv_lora_rank + rope]
    c_kv = RMSNorm(c)
    k_nope_j = c_kv W_kb,j;  v_j = c_kv W_vb,j    a head j (the published
                                                  W_kvb's two halves, held
                                                  apart: `to_k_b`, `to_v_b`)
    q_j = [q_nope_j; R_t q_rope_j],  k_j,s = [k_nope_j,s; R_s k_r,s]
        (ONE rotated key part for every head; R rotates interleaved
        pairs, `rope_interleave`, theta `rope_parameters.rope_theta`)
    a_j,t = sum over s in S_t of softmax_s(q_j,t . k_j,s / sqrt qk_head_dim) v_j,s
    x   = x + concat_j(a_j) W_o

MLA runs in its EXPANDED form (keys and values made per head from the
latent): a denoiser re-reads its whole sequence every step, so there is
no cache of latents for the absorbed form to pay for.

`S_t` (`ops/dsa.py`), on a layer whose `indexer_types` entry is `full`:
index queries `c_q WI_q` (`index_n_heads` x `index_head_dim`), ONE index
key a token `LayerNorm(h WI_k)`, the first `qk_rope_head_dim` of each
rotated, head weights `h WI_w` (float32); `I_ts = (heads x dim)^-1/2
sum_j w_tj relu(qI_tj . kI_s)`; every `s <= t` while `t + 1 <=
index_topk`, else the `index_topk` keys `s <= t` of largest `I_ts`. On a
`shared` layer `S` is the nearest earlier `full` layer's and the layer
holds no indexer weight (IndexShare). `indexer_types` is the authority;
it is checked against `index_topk_freq` / `index_skip_topk_offset` at
the layers' published indices (`first_layer` is the first held one's).

Feed-forward, `n = RMSNorm(x)`: `mlp_layer_types` `dense`: a SwiGLU at
`intermediate_size`. `sparse`: `sc = sigmoid(n W_r)` over ALL
`router_experts` (float32); the `num_experts_per_tok` largest of `sc +
b` (`b` a held correction bias that selects only: `topk_method`
`noaux_tc`; `n_group` = `topk_group` = 1: no group limit); `g_e =
routed_scaling_factor sc_e / sum over the picked of sc`; `y = sum over
the picked experts THAT ARE HELD HERE (`n_routed_experts` of them, from
`first_expert`) of g_e E_e(n) + E_shared(n)`, each `E` a SwiGLU at
`moe_intermediate_size` (the shared one at `n_shared_experts` times
that). What the absent experts would add is left out: this chip's share
of a deployment that divides each layer by expert parallelism.

`return_tally=True` also returns what the serving path counts
(docs/OBSERVABILITY.md): `picks` [B, sparse layers, n_routed_experts]
int32, the held picks by layer and expert, `fitted` [B, sparse layers]
int32, those of them the routed layer's first pass served
(`ops/moe.py`), and `keys` [B, layers] int32, the (query, key) pairs of
the mask each layer's core was handed.

What the source's `config.json` does not give and is assumed (the
benchmark's configuration lists each with its referent): the two
RMSNorms on `c_q` and `c_kv` and the pre-norm order (the DeepSeek-V3
lineage); the indexer's form (DeepSeek-V3.2-Exp's lightning indexer:
ReLU, per-head weights from the hidden state, a LayerNorm with a bias
on its one key at eps `INDEX_NORM_EPS`, the rotated part first, the two
scales), with no Hadamard rotation and no fp8; the softmax scale without
a YaRN factor (`rope_type` `default`).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import dsa, moe
from ..ops.attention import attend_selected
from ..ops.flash_attention import padded_length
from ..typing import Dtype
from .trunk import (Kernel, SequenceEmbed, patch_head, rope_interleaved,
                    sequence_tokens)

INDEX_NORM_EPS = 1e-6       # the index key's LayerNorm


def published_indexer_type(layer: int, offset: int, freq: int) -> str:
    """IndexShare's rule at a layer's PUBLISHED index: the first
    `offset` layers select for themselves, then every `freq`-th does
    and the `freq - 1` after it read its selection."""
    own = layer < offset or (layer - offset + 1) % freq == 0
    return "full" if own else "shared"


def _rms(eps: float, param_dtype, name: str) -> nn.Module:
    return nn.RMSNorm(epsilon=eps, dtype=jnp.float32,
                      param_dtype=param_dtype, name=name)


def _rotate_first(x: jax.Array, n: int, theta: float) -> jax.Array:
    """[B, S, H, D] with its first `n` entries rotated."""
    return jnp.concatenate(
        [rope_interleaved(x[..., :n], theta), x[..., n:]], axis=-1)


def _rotate_last(x: jax.Array, n: int, theta: float) -> jax.Array:
    """[B, H, S, D] with its LAST `n` entries rotated (interleaved
    pairs) over the whole head at once: the other lanes turn by angle 0,
    and a pair's partner comes from a product with a constant signed
    permutation (one term a lane: exact), which the matrix units do in
    passing. No array narrower than a head is made: on a TPU a 64- or
    192-wide minor axis makes XLA lay the sequence along the lanes
    instead, and the core then pays a transpose back (PERF.md PR 43)."""
    s, d = x.shape[2], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), jnp.repeat(inv, 2))
    ang = jnp.pad(ang, ((0, 0), (d - n, 0)))            # [S, D]
    lane = jnp.arange(d)
    # partner[2i] = -x[2i + 1], partner[2i + 1] = x[2i]
    swap = (jnp.zeros((d, d), x.dtype)
            .at[lane + 1 - 2 * (lane % 2), lane]
            .set(jnp.where(lane % 2 == 0, -1, 1).astype(x.dtype)))
    partner = jnp.einsum("bhsd,de->bhse", x, swap,
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * jnp.cos(ang)
            + partner * jnp.sin(ang)).astype(x.dtype)


class GlmMoeDsaBlock(nn.Module):
    """One sequential pre-norm block over a float32 residual stream:
    `(x, keep) -> (y, keep, (held picks [B, n_routed_experts], those the
    routed layer's first pass served [B]) or None)`;
    `keep` [B, T, T] bool is the selection, made here on a `full` layer
    and handed on unchanged by a `shared` one."""

    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    router_experts: int
    first_expert: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    attention_bias: bool
    rms_norm_eps: float
    rope_theta: float
    mlp_type: str               # "dense" | "sparse"
    indexer_type: str           # "full" | "shared"
    dtype: Optional[Dtype] = None
    backend: str = "auto"

    @nn.nowrap
    def _attention(self, h32, keep):
        dt = self.dtype or jnp.float32
        b, t, _ = h32.shape
        h = h32.astype(dt)
        heads, nope, rope = (self.num_attention_heads,
                             self.qk_nope_head_dim, self.qk_rope_head_dim)

        def dense(name, width, x):
            return nn.Dense(width, use_bias=self.attention_bias, dtype=dt,
                            param_dtype=dt, name=name)(x)

        def normed(name, x):    # float32 norm, rounded to the products' type
            return _rms(self.rms_norm_eps, dt, name)(x).astype(dt)

        # the core's operands are made where the kernel reads them:
        # head-major [B, heads, Tp, 256], from the latents padded to the
        # kernel's blocks (the padding is rows of the NARROW latents, not
        # copies of the wide operands), a head's rotated part in its last
        # lanes; nothing a head wide is transposed, padded, sliced or
        # joined between a projection and the core. The WEIGHTS come first
        # in each product: XLA then writes it head-major as it stands,
        # and with the activations first it writes the sequence along the
        # lanes and copies every operand on its way to the kernel (2.21
        # against 2.67 s a guided bucket-8 turn; PERF.md PR 43)
        t_pad = padded_length(t)
        rows = lambda x: jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
        with jax.named_scope("fdt_mla_proj"):
            c_q = normed("q_a_norm", dense("to_q_a", self.q_lora_rank, h))
            q = _rotate_last(jnp.einsum(
                "chd,btc->bhtd",
                Kernel((self.q_lora_rank, heads, nope + rope), dt,
                       name="to_q_b")(), rows(c_q)), rope, self.rope_theta)
            kv_a = dense("to_kv_a", self.kv_lora_rank + rope, h)
            c_kv = rows(normed("kv_a_norm", kv_a[..., :self.kv_lora_rank]))
            # ONE rotated key part for every head, in a head's last lanes
            k_r = jnp.pad(rope_interleaved(
                kv_a[:, :, None, self.kv_lora_rank:], self.rope_theta)[:, :, 0],
                ((0, 0), (0, t_pad - t), (nope, 0)))
            # each head's own key part, those lanes left zero
            k = jnp.einsum("chd,btc->bhtd", jnp.pad(
                Kernel((self.kv_lora_rank, heads, nope), dt, name="to_k_b")(),
                ((0, 0), (0, 0), (0, rope))), c_kv)
            v = jnp.einsum("chd,btc->bhtd", Kernel(
                (self.kv_lora_rank, heads, self.v_head_dim), dt,
                name="to_v_b")(), c_kv)
        if self.indexer_type == "full":
            with jax.named_scope("fdt_dsa_index"):
                q_i = _rotate_first(
                    dense("idx_q", self.index_n_heads * self.index_head_dim,
                          c_q).reshape(b, t, self.index_n_heads,
                                       self.index_head_dim),
                    rope, self.rope_theta)
                k_i = nn.LayerNorm(
                    epsilon=INDEX_NORM_EPS, dtype=jnp.float32,
                    param_dtype=dt, name="idx_k_norm")(
                    dense("idx_k", self.index_head_dim, h)).astype(dt)
                k_i = _rotate_first(k_i[:, :, None], rope,
                                    self.rope_theta)[:, :, 0]
                w_i = nn.Dense(self.index_n_heads, use_bias=False,
                               dtype=jnp.float32, name="idx_w")(h32)
                scores = dsa.index_scores(q_i, k_i, w_i)
            with jax.named_scope("fdt_dsa_select"):
                keep = dsa.select(scores, self.index_topk)
        with jax.named_scope("fdt_mla_core"):
            a = attend_selected(q, k, v, keep, k_shared=k_r,
                                backend=self.backend)[:, :, :t]
        out = jnp.einsum("bhtd,hdc->btc", a, Kernel(
            (heads, self.v_head_dim, h32.shape[-1]), dt, name="to_out")())
        return out, keep

    @nn.nowrap
    def _swiglu(self, n, width, prefix):
        dt = self.dtype or jnp.float32

        def dense(name, w):
            return nn.Dense(w, use_bias=False, dtype=dt, param_dtype=dt,
                            name=prefix + name)
        gate = dense("gate", width)(n).astype(jnp.float32)
        mid = (gate * jax.nn.sigmoid(gate) * dense("up", width)(n)).astype(dt)
        return dense("down", n.shape[-1])(mid).astype(jnp.float32)

    @nn.nowrap
    def _routed(self, n32):
        dt = self.dtype or jnp.float32
        b, t, d = n32.shape
        f, held = self.moe_intermediate_size, self.n_routed_experts
        tokens32 = n32.reshape(b * t, d)
        idx, weights = moe.route(
            tokens32, Kernel((d, self.router_experts), name="router")(),
            self.num_experts_per_tok, self.norm_topk_prob,
            select_bias=self.param("router_bias", nn.initializers.zeros,
                                   (self.router_experts,), jnp.float32),
            scale=self.routed_scaling_factor)
        local, picks = jax.vmap(
            lambda i: moe.held_picks(i, self.first_expert, held))(
            idx.reshape(b, -1, idx.shape[-1]))
        routed, fitted = moe.routed_experts(
            tokens32.astype(dt), local.reshape(idx.shape), weights,
            Kernel((held, d, f), dt, name="experts_gate")(),
            Kernel((held, d, f), dt, name="experts_up")(),
            Kernel((held, f, d), dt, name="experts_down")(),
            self.router_experts)
        return (routed.reshape(b, t, d),
                (picks, jnp.sum(fitted.reshape(b, t), axis=1)))

    @nn.compact
    def __call__(self, x: jax.Array, keep: Optional[jax.Array] = None):
        dt = self.dtype or jnp.float32
        if self.indexer_type == "shared" and keep is None:
            raise ValueError("a `shared` layer reads the selection of an "
                             "earlier `full` layer, and none came before")
        a, keep = self._attention(
            _rms(self.rms_norm_eps, dt, "norm")(x), keep)
        x = x + a.astype(jnp.float32)
        n32 = _rms(self.rms_norm_eps, dt, "mlp_norm")(x)
        n, picks = n32.astype(dt), None
        if self.mlp_type == "dense":
            y = self._swiglu(n, self.intermediate_size, "mlp_")
        else:
            routed, picks = self._routed(n32)
            y = routed + self._swiglu(
                n, self.moe_intermediate_size * self.n_shared_experts,
                "shared_")
        return x + y, keep, picks


class GlmMoeDsaDenoiser(nn.Module):
    """`[time; text; patches]` through `num_hidden_layers` blocks; see
    the module docstring for the equations."""

    # -- the source's keys, under the source's names
    attention_bias: bool = False
    ep_size: int = 1
    first_k_dense_replace: int = 3
    head_dim: int = 192                 # the source's: qk_nope_head_dim
    hidden_act: str = "silu"
    hidden_size: int = 6144
    index_head_dim: int = 128
    index_n_heads: int = 32
    index_share_for_mtp_iteration: bool = True   # no MTP module is held
    index_skip_topk_offset: int = 3
    index_topk: int = 2048
    index_topk_freq: int = 4
    index_topk_pattern: Any = None
    indexer_rope_interleave: bool = True
    indexer_types: Tuple[str, ...] = ()
    intermediate_size: int = 12288
    kv_lora_rank: int = 512
    max_position_embeddings: int = 1048576
    mlp_layer_types: Tuple[str, ...] = ()
    model_type: str = "glm_moe_dsa"
    moe_intermediate_size: int = 2048
    moe_layer_freq: int = 1
    n_group: int = 1
    n_routed_experts: int = 256         # routed experts HELD here
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    num_attention_heads: int = 64
    num_experts_per_tok: int = 8
    num_hidden_layers: int = 78
    num_key_value_heads: int = 64
    q_lora_rank: int = 2048
    qk_head_dim: int = 256
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    rms_norm_eps: float = 1e-5
    rope_interleave: bool = True
    rope_parameters: Any = None         # {"rope_theta", "rope_type"}
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    tie_word_embeddings: bool = False
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    v_head_dim: int = 256
    # -- the program's own
    patch_size: int = 2
    output_channels: int = 4
    router_experts: int = 256           # the router's published width
    first_expert: int = 0               # the first routed expert held
    first_layer: int = 0                # the first held layer's published index
    dtype: Optional[Dtype] = jnp.bfloat16   # products AND the weights held
    backend: str = "auto"

    def __post_init__(self):
        super().__post_init__()
        rope = dict(self.rope_parameters or {})
        published = {
            "hidden_act": "silu", "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
            "moe_layer_freq": 1, "rope_interleave": True,
            "indexer_rope_interleave": True, "index_topk_pattern": None,
            "model_type": "glm_moe_dsa", "attention_bias": False}
        for key, want in published.items():
            if getattr(self, key) != want:
                raise ValueError(
                    f"glm_moe_dsa_dn: {key}={getattr(self, key)!r} is not "
                    f"built; only the published {want!r} is")
        if rope.get("rope_type", "default") != "default" \
                or "rope_theta" not in rope:
            raise ValueError("rope_parameters has to give rope_theta at "
                             f"rope_type default, not {rope!r}")
        if self.head_dim != self.qk_nope_head_dim or self.qk_head_dim != (
                self.qk_nope_head_dim + self.qk_rope_head_dim):
            raise ValueError("head_dim is qk_nope_head_dim, and qk_head_dim "
                             "its sum with qk_rope_head_dim")
        if self.qk_head_dim != self.v_head_dim \
                or self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "the expanded core takes one head size for queries, keys "
                "and values and a key/value head a query head")
        layers = range(self.first_layer,
                       self.first_layer + self.num_hidden_layers)
        want = {
            "mlp_layer_types": tuple(
                "dense" if i < self.first_k_dense_replace else "sparse"
                for i in layers),
            "indexer_types": tuple(
                published_indexer_type(i, self.index_skip_topk_offset,
                                       self.index_topk_freq)
                for i in layers)}
        for key, kinds in want.items():
            if tuple(getattr(self, key)) != kinds:
                raise ValueError(
                    f"{key} is {tuple(getattr(self, key))} and layers "
                    f"{layers[0]}..{layers[-1]} of the published model are "
                    f"{kinds} (first_k_dense_replace, index_topk_freq, "
                    "index_skip_topk_offset, first_layer, "
                    "num_hidden_layers)")
        if self.indexer_types[0] != "full":
            raise ValueError("the first layer held has to select for "
                             "itself: its `full` layer is not held")
        if self.first_expert + self.n_routed_experts > self.router_experts:
            raise ValueError("the experts held lie outside the router")

    @property
    def tally_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """What one evaluation counts, by name, which the serving path
        carries with a row: the held picks by sparse layer and expert,
        by sparse layer those the routed layer's first pass served,
        and the selected (query, key) pairs by layer."""
        sparse = self.mlp_layer_types.count("sparse")
        return {"picks": (sparse, self.n_routed_experts),
                "fitted": (sparse,),
                "keys": (self.num_hidden_layers,)}

    # a guided bucket-8 turn is 16 sequences: at the published widths
    # their expanded heads and the dense layer's products do not stand
    # side by side on one chip (PERF.md section 4), and each row's tokens
    # fill the MXU alone. So a serving round evaluates this model a row
    # at a time (`samplers/common.py` `rows_apart`).
    serve_rows_apart = True

    def tally_counters(self, tally, evaluations: int, sample_shape,
                       context_tokens: int) -> Dict[str, int]:
        """The telemetry counters a finished request adds, from its
        tally over `evaluations` evaluations of a sample of
        `sample_shape` [H, W, C]: the `moe/picks_*` three, and
        `dsa/keys_selected` (from the device) over `dsa/keys_visible`
        (the causal pairs, host arithmetic)."""
        t = sequence_tokens(sample_shape, self.patch_size, context_tokens)
        sparse = self.mlp_layer_types.count("sparse")
        out = moe.pick_counters(
            tally["picks"],
            evaluations * t * self.num_experts_per_tok * sparse,
            tally["fitted"])
        out["dsa/keys_selected"] = int(tally["keys"].sum())
        out["dsa/keys_visible"] = (evaluations * self.num_hidden_layers
                                   * (t * (t + 1) // 2))
        return out

    @nn.compact
    def __call__(self, x: jax.Array, temb: jax.Array,
                 textcontext: Optional[jax.Array] = None,
                 return_tally: bool = False):
        tokens = SequenceEmbed(self.hidden_size, self.patch_size,
                               self.dtype, name="embed")(x, temb,
                                                         textcontext)
        keep, picks, keys = None, [], []
        for i, (mlp, indexer) in enumerate(zip(self.mlp_layer_types,
                                               self.indexer_types)):
            tokens, keep, n = GlmMoeDsaBlock(
                num_attention_heads=self.num_attention_heads,
                q_lora_rank=self.q_lora_rank,
                kv_lora_rank=self.kv_lora_rank,
                qk_nope_head_dim=self.qk_nope_head_dim,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim,
                index_n_heads=self.index_n_heads,
                index_head_dim=self.index_head_dim,
                index_topk=self.index_topk,
                intermediate_size=self.intermediate_size,
                moe_intermediate_size=self.moe_intermediate_size,
                n_routed_experts=self.n_routed_experts,
                n_shared_experts=self.n_shared_experts,
                num_experts_per_tok=self.num_experts_per_tok,
                router_experts=self.router_experts,
                first_expert=self.first_expert,
                norm_topk_prob=self.norm_topk_prob,
                routed_scaling_factor=self.routed_scaling_factor,
                attention_bias=self.attention_bias,
                rms_norm_eps=self.rms_norm_eps,
                rope_theta=float(self.rope_parameters["rope_theta"]),
                mlp_type=mlp, indexer_type=indexer, dtype=self.dtype,
                backend=self.backend, name=f"layer_{i}")(tokens, keep)
            if indexer == "full":   # a `shared` layer was handed the same
                selected = jnp.sum(keep, axis=(1, 2), dtype=jnp.int32)
            keys.append(selected)
            if n is not None:
                picks.append(n)
        out = patch_head(
            tokens, _rms(self.rms_norm_eps, jnp.float32, "final_norm"),
            x.shape, self.patch_size, self.output_channels)
        if return_tally:
            held, fitted = (
                tuple(jnp.stack(n, axis=1) for n in zip(*picks)) if picks
                else (jnp.zeros((x.shape[0],) + self.tally_shapes[name],
                                jnp.int32) for name in ("picks", "fitted")))
            return out, {"picks": held, "fitted": fitted,
                         "keys": jnp.stack(keys, axis=1)}
        return out
