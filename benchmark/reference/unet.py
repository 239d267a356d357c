"""Plain float32 forward pass of the text-conditional UNet (FlaxDiff
`Unet`: res blocks with GroupNorm+SiLU, cross-attention transformer
blocks on the last block of the attention levels, middle res-attn-res,
skip concatenation on the way up), reading a parameter tree by the
architecture's layer names."""
from __future__ import annotations

import jax.numpy as jnp

from . import nn


def _res_block(p, x, temb, groups):
    h = nn.silu(nn.group_norm(p["norm1"], x, groups))
    h = nn.conv(p["conv1"]["Conv_0"], h)
    h = h + nn.dense(p["temb_proj"], nn.silu(temb))[:, None, None, :]
    h = nn.silu(nn.group_norm(p["norm2"], h, groups))
    h = nn.conv(p["conv2"]["Conv_0"], h)
    if "skip_proj" in p:
        x = nn.conv(p["skip_proj"]["Conv_0"], x)
    return h + x


def _attn(p, x, context):
    ctx = x if context is None else context
    q, k, v = (nn.heads_in(p["to_q"], x), nn.heads_in(p["to_k"], ctx),
               nn.heads_in(p["to_v"], ctx))
    return nn.heads_out(p["to_out"], nn.attention(q, k, v))


def _transformer(p, x, context, cross_only):
    b, h, w, c = x.shape
    t = x.reshape(b, h * w, c)
    blk = p["block_0"]
    ln = lambda name, y: nn.layer_norm(y, 1e-6, blk[name])
    t = t + _attn(blk["attn1"], ln("norm1", t),
                  context if cross_only else None)
    if not cross_only:
        t = t + _attn(blk["attn2"], ln("norm2", t), context)
    ff = nn.dense(blk["ff"]["proj_in"], ln("norm3", t))
    gate, val = jnp.split(ff, 2, axis=-1)
    t = t + nn.dense(blk["ff"]["proj_out"], val * nn.gelu_tanh(gate))
    return t.reshape(b, h, w, c) + x


def forward(params, cfg, x, t, text):
    """params: tree of arrays; cfg: the configuration's `model` section;
    x [B,H,W,C], t [B], text [B,L,D] -> [B,H,W,out]."""
    m = cfg
    depths = list(m["feature_depths"])
    attn = list(m.get("attention_configs") or [None] * len(depths))
    nres, nmid = m.get("num_res_blocks", 2), m.get("num_middle_res_blocks", 1)
    groups = m.get("norm_groups", 8)
    levels = len(depths)
    x = x.astype(jnp.float32)
    text = text.astype(jnp.float32)

    temb = nn.fourier_embedding(t, m["emb_features"])
    tp = params["TimeProjection_0"]
    temb = nn.dense(tp["Dense_1"], nn.gelu_tanh(nn.dense(tp["Dense_0"], temb)))

    x = nn.conv(params["conv_in"]["Conv_0"], x)
    first_skip, skips = x, []
    for level in range(levels):
        for b in range(nres):
            x = _res_block(params[f"down_{level}_res_{b}"], x, temb, groups)
            if attn[level] and b == nres - 1:
                x = _transformer(params[f"down_{level}_attn"], x, text, False)
            skips.append(x)
        if level < levels - 1:
            x = nn.conv(params[f"down_{level}_downsample"]["ConvLayer_0"]
                        ["Conv_0"], x, stride=2)
    for b in range(nmid):
        x = _res_block(params[f"mid_res1_{b}"], x, temb, groups)
        if attn[-1]:
            x = _transformer(params[f"mid_attn_{b}"], x, text, True)
        x = _res_block(params[f"mid_res2_{b}"], x, temb, groups)
    for level in reversed(range(levels)):
        for b in range(nres):
            x = jnp.concatenate([x, skips.pop()], axis=-1)
            x = _res_block(params[f"up_{level}_res_{b}"], x, temb, groups)
            if attn[level] and b == nres - 1:
                x = _transformer(params[f"up_{level}_attn"], x, text, False)
        if level > 0:
            x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
            x = nn.conv(params[f"up_{level}_upsample"]["ConvLayer_0"]
                        ["Conv_0"], x)
    x = nn.conv(params["conv_mid_out"]["Conv_0"], x)
    x = jnp.concatenate([x, first_skip], axis=-1)
    x = _res_block(params["final_res"], x, temb, groups)
    x = nn.silu(nn.group_norm(params["final_norm"], x, groups))
    return nn.conv(params["conv_out"]["Conv_0"], x)


def forward_flops(cfg) -> float:
    """Required operations of one image's forward pass (see
    `harness/flops.py`)."""
    m, res = cfg["model"], cfg["input"]["resolution"]
    ch_in = cfg["input"]["channels"]
    depths = list(m["feature_depths"])
    attn = list(m.get("attention_configs") or [None] * len(depths))
    nres = m.get("num_res_blocks", 2)
    nmid = m.get("num_middle_res_blocks", 1)
    emb = m["emb_features"]
    tok_c, feat = cfg["conditioning"]["tokens"], cfg["conditioning"]["features"]
    total = 0.0

    def conv(side, cin, cout, k=3):
        return 2.0 * side * side * k * k * cin * cout

    def resblock(side, cin, cout):
        f = conv(side, cin, cout) + conv(side, cout, cout) + 2.0 * emb * cout
        if cin != cout:
            f += conv(side, cin, cout, k=1)
        return f

    def tblock(side, c, a, cross_only=False):
        h, dh = a.get("heads", 4), a.get("dim_head", 64)
        inner, t = h * dh, side * side

        def attention(tkv, ckv):
            return (2.0 * t * c * inner + 2 * 2.0 * tkv * ckv * inner
                    + 4.0 * t * tkv * inner + 2.0 * t * inner * c)

        f = attention(tok_c, feat) if cross_only else attention(t, c)
        if not cross_only:
            f += attention(tok_c, feat)
        f += 2.0 * t * c * (8 * c) + 2.0 * t * (4 * c) * c   # GEGLU FF
        return f

    total += 2.0 * (emb * emb + emb * emb)                  # time MLP
    side = res
    total += conv(side, ch_in, depths[0])
    skips, c = [], depths[0]
    for level, feats in enumerate(depths):
        for b in range(nres):
            total += resblock(side, c, feats)
            c = feats
            if attn[level] and b == nres - 1:
                total += tblock(side, c, attn[level])
            skips.append(c)
        if level < len(depths) - 1:
            side //= 2
            total += conv(side, c, feats)
    for _ in range(nmid):
        total += resblock(side, c, depths[-1])
        c = depths[-1]
        if attn[-1]:
            total += tblock(side, c, attn[-1], cross_only=True)
        total += resblock(side, c, c)
    for rev, feats in enumerate(reversed(depths)):
        level = len(depths) - 1 - rev
        for b in range(nres):
            total += resblock(side, c + skips.pop(), feats)
            c = feats
            if attn[level] and b == nres - 1:
                total += tblock(side, c, attn[level])
        if level > 0:
            side *= 2
            total += conv(side, c, depths[level - 1])
            c = depths[level - 1]
    total += conv(side, c, depths[0])
    total += resblock(side, 2 * depths[0], depths[0])
    total += conv(side, depths[0], m["output_channels"])
    return total
