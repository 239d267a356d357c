"""Plain float32 forward pass of a `brumby` decoder layer stack (Manifest
AI `Brumby-14B-Base`: the Qwen3-14B layer with gated power retention in
the place of softmax attention) as a denoiser trunk, as
`flaxdiff_tpu/models/brumby.py` specifies it. No kernel, no state, no
chunk: the PAIRWISE form is the definition, a query head at a time and
queries in blocks of `QUERY_BLOCK` so that a 16k-token row fits. `cfg` is
the effective `model` section: the source's keys under the source's
names with the harness's `model` group over them.

Sequence `[time; text; patch]` (654 tokens in the benchmark's cell: 1 +
77 + 576), positions are indices in it, float32 residual stream. The
embedding is the trunk's (`reference/cohere2_moe.py` `_embed`). For layer
input `x`:

    h  = RMSNorm(x; eps rms_norm_eps)
    q  = RoPE(RMSNorm_head(W_q h))   [T, 40, 128]
    k  = RoPE(RMSNorm_head(W_k h))   [T, 8, 128]
    v  = W_v h                       [T, 8, 128]
    log g_t = log_sigmoid(W_g h_t + b_g)   [T, 8], float32
    G_t = sum_{r <= t} log g_r        (query head i reads key/value head i // 5)
    w_ts = (q_t . k_s / sqrt(128))^2 * exp(G_t - G_s)   for s <= t, else 0
    o_t  = sum_s w_ts v_s / (sum_s w_ts + eps_n)
    a  = x + W_o o
    y  = a + W_down( silu(W_gate n) * (W_up n) ),  n = RMSNorm(a)

No bias on q, k, v, o (`attention_bias` false); `RMSNorm_head` over the
head's 128 entries with one weight vector for all heads; RoPE half-split
(`x[:64]`, `x[64:]`), theta `rope_theta`, whole head; after the last
layer a final RMSNorm and the patch head. Degree 2, so no weight is
negative and the normaliser is a plain sum.

The state form is the SAME function and is not computed here: with
`phi(u)` the symmetric square of `u` (`u_i u_j` for i <= j, times sqrt 2
where i < j: 128 x 129 / 2 = 8,256 entries, `phi(q) . phi(k) = (q . k)^2`),
`S_t = g_t S_{t-1} + phi(k_t) v_t^T` ([8256, 128] a key/value head),
`z_t = g_t z_{t-1} + phi(k_t)`, `o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t +
eps_n)`. A token and layer cost it about 101 MFLOP (40 reads and 8
updates of 2 x 8256 x 128) and the pairwise form 10,240 x T at the causal
half: they cross near 10k tokens. `forward_flops` counts the cheaper.

Assumed (the source's `config.json` gives none of them; the configuration
file lists each with its referent): degree 2 and the gated,
sum-normalised form; the gate a Dense from the hidden size to one scalar
a KEY/VALUE head, with bias, through `log_sigmoid`; `eps_n` 1e-6.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import nn
from .cohere2_moe import TIME_FEATURES, _embed

POWER_DEGREE = 2
NORM_EPS = 1e-6         # eps_n
QUERY_BLOCK = 1024


def _rms(x, eps, p):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"]


def _rope_half(x, theta):
    """(x[i], x[i + D/2]) of [B, S, H, D] rotated by position *
    theta^(-2i/D)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1)


def retention(q, k, v, log_g, eps=NORM_EPS, block=QUERY_BLOCK):
    """The pairwise definition over q [B, T, H, D], k / v [B, T, KV, D],
    log_g [B, T, KV]: [B, T, H, D]."""
    t, heads, d = q.shape[1], q.shape[2], q.shape[3]
    group = heads // k.shape[2]
    big_g = jnp.cumsum(log_g, axis=1)

    def head(i):
        qi = jnp.take(q, i, axis=2)
        ki, vi, gi = (jnp.take(a, i // group, axis=2)
                      for a in (k, v, big_g))
        outs = []
        for lo in range(0, t, block):
            hi = min(lo + block, t)
            s = nn.einsum("bqd,bsd->bqs", qi[:, lo:hi], ki[:, :hi]) \
                / jnp.sqrt(jnp.float32(d))
            seen = (jnp.arange(hi)[None, :]
                    <= jnp.arange(lo, hi)[:, None])
            w = s ** POWER_DEGREE * jnp.exp(jnp.where(
                seen, gi[:, lo:hi, None] - gi[:, None, :hi], -jnp.inf))
            outs.append(nn.einsum("bqs,bsd->bqd", w, vi[:, :hi])
                        / (jnp.sum(w, axis=-1, keepdims=True) + eps))
        return jnp.concatenate(outs, axis=1)

    out = jax.lax.map(head, jnp.arange(heads))          # [H, B, T, D]
    return out.transpose(1, 2, 0, 3)


def _layer(m, p, x):
    eps = float(m["rms_norm_eps"])
    theta = float(m["rope_theta"])
    h = _rms(x, eps, p["norm"])
    heads = lambda name: nn.einsum("blc,chd->blhd", h, p[name]["kernel"])
    q = _rope_half(_rms(heads("to_q"), eps, p["q_norm"]), theta)
    k = _rope_half(_rms(heads("to_k"), eps, p["k_norm"]), theta)
    log_g = jax.nn.log_sigmoid(nn.dense(p["to_gate"], h))
    o = retention(q, k, heads("to_v"), log_g)
    a = x + nn.einsum("blhd,hdc->blc", o, p["to_out"]["kernel"])
    n = _rms(a, eps, p["mlp_norm"])
    lin = lambda name, y: nn.einsum("blc,cf->blf", y, p[name]["kernel"])
    return a + lin("mlp_down", nn.silu(lin("mlp_gate", n)) * lin("mlp_up", n))


def _head(m, shape, p, tokens):
    p_, out_c = int(m["patch_size"]), int(m["output_channels"])
    b, hgt, wid, _ = shape
    hp, wp = hgt // p_, wid // p_
    tokens = _rms(tokens[:, -hp * wp:], float(m["rms_norm_eps"]),
                  p["final_norm"])
    y = nn.dense(p["final_proj"], tokens)
    y = y.reshape(b, hp, wp, p_, p_, out_c).transpose(0, 1, 3, 2, 4, 5)
    return y.reshape(b, hgt, wid, out_c)


def stages(cfg, shape):
    """The forward pass as ordered stages [(name, needs, apply)]: the
    embedding, a stage a layer, the head. Every layer shares ONE `apply`,
    so a caller that jits it compiles one layer."""
    def embed(parts, carry):
        return _embed(cfg, parts[0], carry)

    def layer(parts, tokens):
        return _layer(cfg, parts[0], tokens)

    def head(parts, tokens):
        return _head(cfg, shape, dict(zip(("final_norm", "final_proj"),
                                          parts)), tokens)

    return ([("embed", ("embed",), embed)]
            + [(f"layer_{i}", (f"layer_{i}",), layer)
               for i in range(int(cfg["num_hidden_layers"]))]
            + [("head", ("final_norm", "final_proj"), head)])


def forward(params, cfg, x, t, text):
    """params: tree of arrays; cfg: the effective `model` section;
    x [B,H,W,C], t [B], text [B,L,F] -> [B,H,W,out]. The fold over
    `stages`."""
    carry = {"x": x, "t": t, "text": text}
    for _, needs, apply in stages(cfg, x.shape):
        carry = apply(tuple(params[n] for n in needs), carry)
    return carry


def _sizes(cfg):
    m = cfg["model"]
    p = int(m["patch_size"])
    tokens = 1 + int(cfg["conditioning"]["tokens"]) + (
        int(cfg["input"]["resolution"]) // p) ** 2
    return m, p, tokens


def retention_flops(m, t: int) -> float:
    """Required operations of ONE layer's retention over a row of `t`
    tokens, at the cheaper of its two forms. Pairwise: scores and values,
    4 x head_dim a (query, key) pair and query head, the causal half.
    State: a read a query head and an update a key/value head, each
    2 x phi x head_dim a token and for S alone (z is a 128th of it)."""
    hd = int(m["head_dim"])
    n_q, n_kv = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    pairwise = 4.0 * hd * n_q * t * (t + 1) / 2.0
    state = t * (n_q + n_kv) * 2.0 * (hd * (hd + 1) // 2) * hd
    return min(pairwise, state)


def forward_flops(cfg) -> float:
    """Required operations of one image's forward pass: every product at
    its published width, the retention at the cheaper form for the row's
    length, nothing for what is masked or padded."""
    m, p, t = _sizes(cfg)
    d, f = int(m["hidden_size"]), int(m["intermediate_size"])
    hd = int(m["head_dim"])
    n_q, n_kv = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    res, ch = int(cfg["input"]["resolution"]), int(cfg["input"]["channels"])
    patches = (res // p) ** 2
    flops = 2.0 * patches * (p * p * ch) * d                # patch embed
    flops += 2.0 * (TIME_FEATURES * d + d * d)              # time MLP
    flops += 2.0 * int(cfg["conditioning"]["tokens"]) * int(
        cfg["conditioning"]["features"]) * d                # text
    layer = 2.0 * t * d * hd * (2 * n_q + 2 * n_kv)         # q, k, v, out
    layer += 2.0 * t * d * n_kv                             # the gate
    layer += 2.0 * t * 3 * d * f                            # SwiGLU
    layer += retention_flops(m, t)
    flops += int(m["num_hidden_layers"]) * layer
    flops += 2.0 * patches * d * p * p * int(m["output_channels"])
    return flops
