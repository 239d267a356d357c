"""Plain float32 forward pass of a `cohere2_moe` decoder layer stack
(CohereLabs `command-a-plus-05-2026`) as a denoiser trunk, as
`flaxdiff_tpu/models/cohere2_moe.py` specifies it. No kernel, no sort:
every expert held is evaluated densely for every token and masked by
the selection. `cfg` is the effective `model` section: the source's keys
under the source's names, with the harness's `model` group (patch size,
output channels, the router's published width `router_experts`, the
first expert held `first_expert`) over them.

Sequence: `[time token; text tokens; patch tokens]`; positions are
indices in it. Time token: sin/cos of t at 128 frequencies (max period
10000) -> Dense -> gelu -> Dense to `hidden_size`. Text: Dense. Patches:
a p x p convolution at stride p, raster order.

Block (`use_parallel_block`): `h = LayerNorm(x)` (mean-subtracting, a
weight, no bias, eps `layer_norm_eps`); `y = x + Attn(h) + MoE(h)`.

`Attn`: q = h Wq (`num_attention_heads` x `head_dim`), k = h Wk, v = h
Wv (`num_key_value_heads` x `head_dim`), no bias, no q/k norm; query
head i reads key/value head i // (heads / kv heads); scores over
sqrt(head_dim); on a `sliding_attention` layer RoPE on q and k
(`rope_gptj`: interleaved pairs, `rope_theta`) and query i sees keys j
with i - `sliding_window` < j <= i; on a `full_attention` layer no
positional term and j <= i; Wo.

`MoE`: s = sigmoid(h Wr) over all `router_experts`; the
`num_experts_per_tok` largest; w_e = s_e / sum of those
(`norm_topk_prob`); E(h) = Wdown (silu(Wgate h) * Wup h) at width
`intermediate_size`; routed = sum over the selected experts THAT ARE
HELD HERE (`num_experts` from `first_expert`) of w_e E_e(h); shared =
the mean of the `num_shared_experts` shared experts; MoE(h) = routed +
shared. What the absent experts would add is left out, here as in the
program: the chip's share of a layer divided by expert parallelism.

Out: final LayerNorm, Dense to p*p*`output_channels` on the patch
tokens, unpatchify.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import nn

TIME_FEATURES = 256


def _norm(x, eps, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"]


def _rope_gptj(x, theta):
    """Pairs (x[2i], x[2i+1]) of [B, S, H, D] rotated by position *
    theta^(-2i/D)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _attention(m, p, h, sliding):
    """`sliding` is a traced boolean (every layer shares one `apply`):
    RoPE and the window where it holds."""
    heads = lambda name: nn.einsum("blc,chd->blhd", h, p[name]["kernel"])
    q, k, v = heads("to_q"), heads("to_k"), heads("to_v")
    theta = float(m["rope_theta"])
    q = jnp.where(sliding, _rope_gptj(q, theta), q)
    k = jnp.where(sliding, _rope_gptj(k, theta), k)
    b, s, n_q, d = q.shape
    kv = k.shape[2]
    q = q.reshape(b, s, kv, n_q // kv, d)
    logits = nn.einsum("bqkgd,bskd->bkgqs", q, k) / jnp.sqrt(jnp.float32(d))
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    keep = (j <= i) & (~sliding | (j > i - int(m["sliding_window"])))
    probs = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
    out = nn.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(b, s, n_q, d)
    return nn.einsum("blhd,hdc->blc", out, p["to_out"]["kernel"])


def _experts(h, gate, up, down):
    """Every expert of the stack over every token: [E, N, D]."""
    g = nn.einsum("nd,edf->enf", h, gate)
    mid = nn.silu(g) * nn.einsum("nd,edf->enf", h, up)
    return nn.einsum("enf,efd->end", mid, down)


def _moe(m, p, h):
    n_tok, k = h.shape[0], int(m["num_experts_per_tok"])
    first, held = int(m.get("first_expert", 0)), int(m["num_experts"])
    scores = jax.nn.sigmoid(nn.einsum("nd,de->ne", h, p["router"]["kernel"]))
    top, idx = jax.lax.top_k(scores, k)
    if m.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    # [N, router_experts]: the weight of each expert for each token
    w = jnp.zeros_like(scores).at[jnp.arange(n_tok)[:, None], idx].set(top)
    routed = jnp.einsum(
        "end,ne->nd",
        _experts(h, p["experts_gate"]["kernel"], p["experts_up"]["kernel"],
                 p["experts_down"]["kernel"]),
        w[:, first:first + held], precision=jax.lax.Precision.HIGHEST)
    shared = jnp.mean(_experts(
        h, p["shared_experts_gate"]["kernel"],
        p["shared_experts_up"]["kernel"],
        p["shared_experts_down"]["kernel"]), axis=0)
    return routed + shared


def _layer(m, p, x, sliding):
    h = _norm(x, float(m["layer_norm_eps"]), p["norm"])
    b, s, d = h.shape
    return x + _attention(m, p, h, sliding) + _moe(
        m, p, h.reshape(b * s, d)).reshape(b, s, d)


def _embed(m, p, carry):
    x, t, text = carry["x"], carry["t"], carry["text"]
    p_, d = int(m["patch_size"]), int(m["hidden_size"])
    x = x.astype(jnp.float32)
    b, hgt, wid, c = x.shape
    hp, wp = hgt // p_, wid // p_
    half = TIME_FEATURES // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None, :]
    temb = jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)
    tp = p["t_proj"]
    temb = nn.dense(tp["Dense_1"], nn.gelu_tanh(nn.dense(tp["Dense_0"], temb)))
    ctx = nn.dense(p["text_proj"], text.astype(jnp.float32))
    pe = p["patch_embed"]["proj"]
    patches = x.reshape(b, hp, p_, wp, p_, c).transpose(0, 1, 3, 2, 4, 5)
    patches = patches.reshape(b, hp * wp, p_ * p_ * c)
    tokens = nn.einsum("bnk,kd->bnd", patches,
                       pe["kernel"].reshape(p_ * p_ * c, d)) + pe["bias"]
    return jnp.concatenate([temb[:, None, :], ctx, tokens], axis=1)


def _head(m, shape, p, tokens):
    p_, out_c = int(m["patch_size"]), int(m["output_channels"])
    b, hgt, wid, _ = shape
    hp, wp = hgt // p_, wid // p_
    tokens = _norm(tokens[:, -hp * wp:], float(m["layer_norm_eps"]),
                   p["final_norm"])
    y = nn.dense(p["final_proj"], tokens)
    y = y.reshape(b, hp, wp, p_, p_, out_c).transpose(0, 1, 3, 2, 4, 5)
    return y.reshape(b, hgt, wid, out_c)


def stages(cfg, shape):
    """The forward pass as ordered stages [(name, needs, apply)]: the
    embedding, a stage a layer, the head. Every layer shares ONE
    `apply`, so a caller that jits it compiles one layer: the layer's
    kind (sliding or full) is read from the carry's layer counter."""
    sliding = np.asarray([k == "sliding_attention"
                          for k in cfg["layer_types"]])

    def embed(parts, carry):
        return {"tokens": _embed(cfg, parts[0], carry),
                "layer": jnp.int32(0)}

    def layer(parts, carry):
        i = carry["layer"]
        return {"tokens": _layer(cfg, parts[0], carry["tokens"],
                                 jnp.asarray(sliding)[i]),
                "layer": i + 1}

    def head(parts, carry):
        return _head(cfg, shape, dict(zip(("final_norm", "final_proj"),
                                          parts)), carry["tokens"])

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


def forward_flops(cfg) -> float:
    """Required operations of one image's forward pass: the causal half
    of the scores, the picks that land on the experts held at their
    expectation (`num_experts_per_tok` x held / published experts a
    token a layer: 1 here), nothing for what is masked or padded."""
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
    window = int(m["sliding_window"])
    held_share = int(m["num_experts"]) / float(m.get("router_experts",
                                                     m["num_experts"]))
    picks = int(m["num_experts_per_tok"]) * held_share      # a token
    layer += 2.0 * t * d * int(m.get("router_experts", m["num_experts"]))
    layer += 2.0 * t * 3 * d * f * (picks + int(m["num_shared_experts"]))
    flops += int(m["num_hidden_layers"]) * layer
    for kind in m["layer_types"]:                           # scores, values
        flops += 4.0 * _visible_pairs(
            t, window if kind == "sliding_attention" else None) * hd * n_q
    flops += 2.0 * patches * d * p * p * int(m["output_channels"])
    return flops


def _visible_pairs(t: int, window) -> float:
    """(query, key) pairs under the causal mask, and the window."""
    if window is None or window >= t:
        return t * (t + 1) / 2.0
    return window * (window + 1) / 2.0 + (t - window) * float(window)


def kernel_costs(cfg):
    """Required operations and bytes of each named kernel for ONE model
    evaluation of ONE row: {kernel: {"flops", "bytes"}}.

    `fdt_flash_fwd`: the visible (query, key) pairs only, 4 x pairs x
    head_dim x query heads a layer; q read and the output written once,
    k and v once per KEY/VALUE head, in the model's type.

    `fdt_moe_gmm` (the gate/up and the down kernel together): 2 x 3 x
    hidden x width a held pick, at the picks' expectation; bytes: the
    picks' rows in and out of both kernels, and each held expert's three
    matrices once a CALL, which serves the 16 evaluations of a full
    round's step (8 rows, guided): a sixteenth of them an evaluation, the
    least any call reads."""
    m, _, t = _sizes(cfg)
    d, f = int(m["hidden_size"]), int(m["intermediate_size"])
    hd = int(m["head_dim"])
    n_q, n_kv = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    width = 4 if m.get("dtype") in (None, "float32") else 2
    window = int(m["sliding_window"])
    flash = {"flops": 0.0, "bytes": 0.0}
    for kind in m["layer_types"]:
        flash["flops"] += 4.0 * _visible_pairs(
            t, window if kind == "sliding_attention" else None) * hd * n_q
        flash["bytes"] += 2.0 * t * hd * (n_q + n_kv) * width
    layers, held = int(m["num_hidden_layers"]), int(m["num_experts"])
    picks = t * int(m["num_experts_per_tok"]) * held / float(
        m.get("router_experts", held))                      # a layer
    evals_a_call = 16.0
    gmm = {"flops": layers * picks * 2.0 * 3 * d * f,
           "bytes": layers * width * (
               picks * (d + f + f + d)
               + held * 3.0 * d * f / evals_a_call)}
    return {"fdt_flash_fwd": flash, "fdt_moe_gmm": gmm}
