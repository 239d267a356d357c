"""Plain float32 forward pass of DiT (Peebles & Xie 2023) as this
repository's `SimpleDiT` specifies it: conv patch embedding plus the
fixed 2D sin-cos table, a pooled time+text conditioning vector, AdaLN-
Zero blocks with rotary self-attention and a GELU MLP, a final
LayerNorm and projection, unpatchify. Departures from the paper are
listed in the configuration file."""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from . import nn


def _sincos_1d(dim, pos):
    omega = 1.0 / (10000.0 ** (np.arange(dim // 2, dtype=np.float64)
                               / (dim / 2.0)))
    out = np.einsum("p,f->pf", pos.astype(np.float64), omega)
    return np.concatenate([np.sin(out), np.cos(out)], axis=1)


def sincos_2d(dim, h, w):
    gy, gx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
    return np.concatenate([_sincos_1d(dim // 2, gy.reshape(-1)),
                           _sincos_1d(dim // 2, gx.reshape(-1))],
                          axis=1).astype(np.float32)


def _rope(x, base=10000.0):
    """Rotate-half rotary embedding over [B, S, H, D], position = token
    index in raster order."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[None, :, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def _block(p, x, cond, eps):
    ada = nn.dense(p["ada"]["ada_proj"], cond[:, None, :])
    s_mlp, b_mlp, g_mlp, s_attn, b_attn, g_attn = jnp.split(ada, 6, axis=-1)
    h = nn.layer_norm(x, eps) * (1.0 + s_attn) + b_attn
    a = p["attn"]
    q, k, v = (nn.heads_in(a["to_q"], h), nn.heads_in(a["to_k"], h),
               nn.heads_in(a["to_v"], h))
    h = nn.heads_out(a["to_out"], nn.attention(_rope(q), _rope(k), v))
    x = x + g_attn * h
    h = nn.layer_norm(x, eps) * (1.0 + s_mlp) + b_mlp
    h = nn.dense(p["mlp_out"], nn.gelu_tanh(nn.dense(p["mlp_in"], h)))
    return x + g_mlp * h


def _embed(m, p, carry):
    """patch tokens and the pooled conditioning vector; `p` holds the
    subtrees `embed` and `cond`."""
    x, t, text = carry["x"], carry["t"], carry["text"]
    p_, d = m["patch_size"], m["emb_features"]
    x = x.astype(jnp.float32)
    b, hgt, wid, c = x.shape
    hp, wp = hgt // p_, wid // p_

    # patch embedding: a p x p convolution at stride p is a product of
    # each flattened patch with the kernel reshaped to [p*p*c, d]
    pe = p["embed"]["patch_embed"]["proj"]
    patches = x.reshape(b, hp, p_, wp, p_, c).transpose(0, 1, 3, 2, 4, 5)
    patches = patches.reshape(b, hp * wp, p_ * p_ * c)
    tokens = nn.einsum("bnk,kd->bnd", patches,
                       pe["kernel"].reshape(p_ * p_ * c, d)) + pe["bias"]
    tokens = tokens + jnp.asarray(sincos_2d(d, hp, wp))[None]

    cp = p["cond"]
    temb = nn.fourier_embedding(t, d)
    temb = nn.dense(cp["t_proj"]["Dense_1"],
                    nn.gelu_tanh(nn.dense(cp["t_proj"]["Dense_0"], temb)))
    cond = nn.dense(cp["t_out"], temb)
    cond = cond + jnp.mean(nn.dense(cp["text_proj"],
                                    text.astype(jnp.float32)), axis=1)
    return {"tokens": tokens, "cond": cond}


def _head(m, shape, p, carry):
    p_, eps = m["patch_size"], m.get("norm_epsilon", 1e-5)
    b, hgt, wid, _ = shape
    hp, wp = hgt // p_, wid // p_
    tokens = nn.layer_norm(carry["tokens"], eps, p["final_norm"])
    tokens = nn.dense(p["final_proj"], tokens)
    out_c = m["output_channels"]
    if m.get("learn_sigma"):
        tokens, _ = jnp.split(tokens, 2, axis=-1)
    y = tokens.reshape(b, hp, wp, p_, p_, out_c).transpose(0, 1, 3, 2, 4, 5)
    return y.reshape(b, hgt, wid, out_c)


def stages(cfg, shape):
    """The forward pass as ordered stages [(name, needs, apply)]: the
    embedding, each block, the head. `needs` names the top-level
    parameter subtrees the stage reads, and `apply(parts, carry)` takes
    them as a tuple in that order. The first carry is {"x", "t", "text"}
    with x of `shape` [B,H,W,C]; the last stage returns the output.
    Every block's `apply` is one function over one subtree, so that a
    caller that jits it compiles one block."""
    eps = cfg.get("norm_epsilon", 1e-5)

    def embed(parts, carry):
        return _embed(cfg, dict(zip(("embed", "cond"), parts)), carry)

    def block(parts, carry):
        return dict(carry, tokens=_block(parts[0], carry["tokens"],
                                         carry["cond"], eps))

    def head(parts, carry):
        return _head(cfg, shape, dict(zip(("final_norm", "final_proj"),
                                          parts)), carry)

    return ([("embed", ("embed", "cond"), embed)]
            + [(f"block_{i}", (f"block_{i}",), block)
               for i in range(cfg["num_layers"])]
            + [("head", ("final_norm", "final_proj"), head)])


def forward(params, cfg, x, t, text):
    """params: tree of arrays; cfg: the configuration's `model` section;
    x [B,H,W,C], t [B], text [B,L,D] -> [B,H,W,out]. The fold over
    `stages`."""
    carry = {"x": x, "t": t, "text": text}
    for _, needs, apply in stages(cfg, x.shape):
        carry = apply(tuple(params[n] for n in needs), carry)
    return carry


def forward_flops(cfg) -> float:
    """Required operations of one image's forward pass (see
    `harness/flops.py`)."""
    m, res = cfg["model"], cfg["input"]["resolution"]
    ch = cfg["input"]["channels"]
    p, d, layers = m["patch_size"], m["emb_features"], m["num_layers"]
    ratio = m.get("mlp_ratio", 4)
    t = (res // p) ** 2
    tok_c, feat = cfg["conditioning"]["tokens"], cfg["conditioning"]["features"]
    out_dim = p * p * m["output_channels"] * (2 if m.get("learn_sigma") else 1)
    flops = 2.0 * t * (p * p * ch) * d                      # patch embed
    # conditioning: Fourier -> Dense(d, 4d) -> Dense(4d, 4d) -> Dense(4d, d),
    # and the text projection over every context token
    flops += 2.0 * (d * ratio * d + (ratio * d) ** 2 + ratio * d * d)
    flops += 2.0 * tok_c * feat * d
    block = 2.0 * t * (4 * d * d + 2 * ratio * d * d)       # qkv, out, mlp
    block += 4.0 * t * t * d                                # QK^T and AV
    block += 2.0 * d * 6 * d                                # AdaLN projection
    flops += layers * block
    flops += 2.0 * t * d * out_dim                          # final projection
    return flops


def kernel_costs(cfg):
    """Required operations and bytes of each named kernel for ONE model
    evaluation of ONE row: {kernel: {"flops", "bytes"}}. Nothing for
    padding (the head dimension as the model has it, not the lane width
    a kernel pads it to), nothing for recomputation.

    `fdt_flash_fwd`: per block, softmax(q k^T) v over T tokens and H
    heads of d: 2 T T d H for the logits and as much for the weighted
    sum; q, k, v read and the output written once each, in the model's
    type."""
    m, res = cfg["model"], cfg["input"]["resolution"]
    t = (res // m["patch_size"]) ** 2
    d, layers = m["emb_features"], m["num_layers"]
    width = 4 if m.get("dtype") in (None, "float32") else 2
    return {"fdt_flash_fwd": {"flops": layers * 4.0 * t * t * d,
                              "bytes": layers * 4.0 * t * d * width}}
