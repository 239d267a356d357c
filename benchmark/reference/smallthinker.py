"""Plain float32 forward pass of a `smallthinker` decoder layer stack
(PowerInfer `SmallThinker-21BA3B-Instruct`) as a denoiser trunk, written
from the layer's equations (ISSUE 46; the configuration's file states
each assumption). No kernel: every expert held is walked in turn over
the tokens that picked it and weighted by the router's choice; query
heads and experts are walked one at a time so that a 9,294-token
sequence fits (a head's [2, 9294, 9294] float32 scores are 0.7 GB; all
28 at once would be 19 GB). `cfg` is the effective `model` section: the
source's keys under the source's names with the harness's `model` group
(patch size, output channels, the router's published width
`router_experts`, the first expert held `first_expert`) over them.

Sequence `[time; text; patch]` (9,294 tokens in the benchmark's cell: 1
+ 77 + 96 x 96), positions are indices in it, float32 residual stream,
causal as published. The embedding is the trunk's
(`reference/cohere2_moe.py` `_embed`). For layer l with input x:

    r  = x W_r                  over all `router_experts`: the router
                                stands BEFORE the attention and reads x
    the `moe_num_active_primary_experts` largest r are picked;
    w  = softmax over the picked r (`moe_primary_router_apply_softmax`;
         false: sigmoid of the picked r, over their sum where
         `norm_topk_prob`)
    h  = RMSNorm(x; rms_norm_eps)
    q, k, v = h W_q, h W_k, h W_v   `num_attention_heads` /
         `num_key_value_heads` heads of `head_dim`, no bias; query head
         i reads key/value head i // (heads / kv heads)
    where `rope_layout[l]`: q, k rotated, pairs (i, i + head_dim / 2) by
         position x `rope_theta`^(-2i / head_dim)
    a_i,t = sum over s <= t (and s > t - `sliding_window_size` where
         `sliding_window_layout[l]`) of softmax_s(q_i,t . k_s /
         sqrt head_dim) v_s
    x' = x + concat_i(a_i) W_o
    y  = x' + sum over the picked experts HELD HERE
         (`moe_num_primary_experts` from `first_expert`) of
         w_e W_down,e (relu(W_gate,e n) * W_up,e n),   n = RMSNorm(x')

No shared expert, no dense layer. What absent experts would add is left
out, here as in the program. Out: final RMSNorm, Dense to
p*p*`output_channels` on the patch tokens, unpatchify.

Departures from the published model, each also in the configuration's
file: patch embedding and patch head for the token embedding and the
vocabulary head; conditioning in context; no cache. Assumed: the router
reads x and not RMSNorm(x); the half-split RoPE pairing.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import nn
from .cohere2_moe import TIME_FEATURES, _embed


def _rms(x, eps, p):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"]


def _rope_half(x, theta):
    """Pairs (x[i], x[i + D/2]) of [B, S, H, D] rotated by position *
    theta^(-2i/D)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1)


def _route(m, p, x):
    """x [N, D] -> [N, router_experts]: each expert's weight for each
    token, zero where it is not picked."""
    k = int(m["moe_num_active_primary_experts"])
    logits = nn.einsum("nd,de->ne", x, p["router"]["kernel"])
    top, idx = jax.lax.top_k(logits, k)
    if m["moe_primary_router_apply_softmax"]:
        top = jax.nn.softmax(top, axis=-1)
    else:
        top = jax.nn.sigmoid(top)
        if m.get("norm_topk_prob", True):
            top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(logits).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)


def _attention(m, p, h, rope, window):
    """`rope` and `window` are traced booleans (every layer shares one
    `apply`): the layer's two layout bits. A query head at a time."""
    heads = lambda name: nn.einsum("blc,chd->blhd", h, p[name]["kernel"])
    q, k, v = heads("to_q"), heads("to_k"), heads("to_v")
    theta = float(m["rope_theta"])
    q = jnp.where(rope, _rope_half(q, theta), q)
    k = jnp.where(rope, _rope_half(k, theta), k)
    s, n_q, d = q.shape[1], q.shape[2], q.shape[3]
    group = n_q // k.shape[2]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    keep = (j <= i) & (~window | (j > i - int(m["sliding_window_size"])))

    def head(n):
        qh = jnp.take(q, n, axis=2)                     # [B, T, D]
        kh, vh = (jnp.take(a, n // group, axis=2) for a in (k, v))
        scores = nn.einsum("btd,bsd->bts", qh, kh) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return nn.einsum("bts,bsd->btd", probs, vh)

    out = jax.lax.map(head, jnp.arange(n_q)).transpose(1, 2, 0, 3)
    return nn.einsum("blhd,hdc->blc", out, p["to_out"]["kernel"])


PICKED_ROWS = 2048     # tokens of one expert evaluated at once


def _experts(m, p, n, w):
    """n [N, D], w [N, router_experts] -> the held experts' part of the
    routed sum: an expert at a time, over the tokens that picked it,
    `PICKED_ROWS` of them at a time; a token's sum runs over its experts
    in their order. Next to nothing is computed for a token that did not
    pick the expert: every expert over every token is ten times the
    products at 6 of 64."""
    first = int(m.get("first_expert", 0))
    held = int(m["moe_num_primary_experts"])
    if not held:
        return jnp.zeros_like(n)
    rows = n.shape[0]
    slots = -(-rows // PICKED_ROWS) * PICKED_ROWS

    def expert(e, acc):
        one = lambda name: jnp.take(p[name]["kernel"], e, axis=0)
        w_e = jnp.take(w, first + e, axis=1)
        picked = w_e > 0
        # the tokens that picked it first, in their order, and where
        # each of them stands among those
        at = jnp.pad(jnp.argsort(~picked, stable=True), (0, slots - rows))
        place = jnp.maximum(jnp.cumsum(picked) - 1, 0)

        def some(c, out):
            x = jnp.take(n, jax.lax.dynamic_slice(
                at, (c * PICKED_ROWS,), (PICKED_ROWS,)), axis=0)
            y = nn.einsum("nf,fd->nd", jax.nn.relu(
                nn.einsum("nd,df->nf", x, one("experts_gate")))
                * nn.einsum("nd,df->nf", x, one("experts_up")),
                one("experts_down"))
            return jax.lax.dynamic_update_slice(out, y, (c * PICKED_ROWS, 0))

        out = jax.lax.fori_loop(
            0, -(-jnp.sum(picked) // PICKED_ROWS), some,
            jnp.zeros((slots, n.shape[1]), n.dtype))
        return acc + jnp.where(picked[:, None], jnp.take(out, place, axis=0)
                               * w_e[:, None], 0.0)

    return jax.lax.fori_loop(0, held, expert, jnp.zeros_like(n))


def _layer(m, p, x, rope, window):
    eps = float(m["rms_norm_eps"])
    b, s, d = x.shape
    w = _route(m, p, x.reshape(b * s, d))
    x = x + _attention(m, p, _rms(x, eps, p["norm"]), rope, window)
    n = _rms(x, eps, p["mlp_norm"]).reshape(b * s, d)
    return x + _experts(m, p, n, w).reshape(b, s, d)


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
    embedding, a stage a layer, the head. Every layer shares ONE `apply`
    (a caller that jits it compiles one layer): the layer's two layout
    bits are data, read from the carry's layer counter."""
    rope = np.asarray(cfg["rope_layout"], bool)
    window = np.asarray(cfg["sliding_window_layout"], bool)

    def embed(parts, carry):
        return {"tokens": _embed(cfg, parts[0], carry),
                "layer": jnp.int32(0)}

    def layer(parts, carry):
        i = carry["layer"]
        return {"tokens": _layer(cfg, parts[0], carry["tokens"],
                                 jnp.asarray(rope)[i],
                                 jnp.asarray(window)[i]),
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


def _visible_pairs(t: int, window) -> float:
    """(query, key) pairs under the causal mask, and the window."""
    if window is None or window >= t:
        return t * (t + 1) / 2.0
    return window * (window + 1) / 2.0 + (t - window) * float(window)


def _windows(m):
    return [int(m["sliding_window_size"]) if bit else None
            for bit in m["sliding_window_layout"]]


def _held_picks(m) -> float:
    """Picks a token a layer that land on the experts held, at their
    expectation."""
    return int(m["moe_num_active_primary_experts"]) * int(
        m["moe_num_primary_experts"]) / float(
        m.get("router_experts", m["moe_num_primary_experts"]))


def forward_flops(cfg) -> float:
    """Required operations of one image's forward pass: every product at
    its published width, the scores and values over the pairs each
    layer's mask lets a query read, the picks that land on the experts
    held at their expectation (all of them here), nothing for what is
    masked or padded."""
    m, p, t = _sizes(cfg)
    d, f = int(m["hidden_size"]), int(m["moe_ffn_hidden_size"])
    hd = int(m["head_dim"])
    n_q, n_kv = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    res, ch = int(cfg["input"]["resolution"]), int(cfg["input"]["channels"])
    patches = (res // p) ** 2
    flops = 2.0 * patches * (p * p * ch) * d                # patch embed
    flops += 2.0 * (TIME_FEATURES * d + d * d)              # time MLP
    flops += 2.0 * int(cfg["conditioning"]["tokens"]) * int(
        cfg["conditioning"]["features"]) * d                # text
    layer = 2.0 * t * d * hd * (2 * n_q + 2 * n_kv)         # q, k, v, out
    layer += 2.0 * t * d * int(m.get("router_experts",
                                     m["moe_num_primary_experts"]))
    layer += 2.0 * t * 3 * d * f * _held_picks(m)
    flops += int(m["num_hidden_layers"]) * layer
    for window in _windows(m):                              # scores, values
        flops += 4.0 * _visible_pairs(t, window) * hd * n_q
    flops += 2.0 * patches * d * p * p * int(m["output_channels"])
    return flops


def kernel_costs(cfg):
    """Required operations and bytes of each named kernel for ONE model
    evaluation of ONE row: {kernel: {"flops", "bytes"}}.

    `fdt_flash_fwd` (every layer's call, whatever its name on the
    device): the visible (query, key) pairs only, 4 x pairs x head_dim x
    query heads a layer; q read and the output written once, k and v
    once per KEY/VALUE head, in the model's type.
    `fdt_flash_fwd_window`: the same of the windowed layers alone (the
    calls named so on the device, where the window binds).

    `fdt_moe_gmm` (the gate/up and the down kernel together): 2 x 3 x
    hidden x width a held pick, at the picks' expectation; bytes: the
    picks' rows in and out of both kernels, and each held expert's three
    matrices once a CALL, which serves the 2 evaluations of one guided
    row (the serving round evaluates this model a row at a time): half
    of them an evaluation, the least any call reads."""
    m, _, t = _sizes(cfg)
    d, f = int(m["hidden_size"]), int(m["moe_ffn_hidden_size"])
    hd = int(m["head_dim"])
    n_q, n_kv = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    width = 4 if m.get("dtype") in (None, "float32") else 2
    flash = {"flops": 0.0, "bytes": 0.0}
    windowed = {"flops": 0.0, "bytes": 0.0}
    for window in _windows(m):
        binds = window is not None and window < t
        for cost in (flash, windowed) if binds else (flash,):
            cost["flops"] += 4.0 * _visible_pairs(t, window) * hd * n_q
            cost["bytes"] += 2.0 * t * hd * (n_q + n_kv) * width
    layers, held = int(m["num_hidden_layers"]), int(
        m["moe_num_primary_experts"])
    picks = t * _held_picks(m)                              # a layer
    evals_a_call = 2.0
    gmm = {"flops": layers * picks * 2.0 * 3 * d * f,
           "bytes": layers * width * (
               picks * (d + f + f + d)
               + held * 3.0 * d * f / evals_a_call)}
    return {"fdt_flash_fwd": flash, "fdt_flash_fwd_window": windowed,
            "fdt_moe_gmm": gmm}
