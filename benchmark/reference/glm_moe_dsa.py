"""Plain float32 forward pass of a `glm_moe_dsa` decoder layer stack
(zai-org `GLM-5.2`: the DeepSeek-V3 layer, latent attention beside
bias-corrected sigmoid routing, with DeepSeek sparse attention's learned
selection of keys shared between layers) as a denoiser trunk, as
`flaxdiff_tpu/models/glm_moe_dsa.py` specifies it. No kernel, no sort of
picks, no search: the selection is a row's k-th largest score read off a
sorted copy, every expert held is evaluated for every token and weighted
by the router's choice, heads and experts are walked one at a time so
that a 4,174-token sequence fits. `cfg` is the effective `model` section:
the source's keys under the source's names with the harness's `model`
group (patch size, output channels, the router's published width
`router_experts`, the first expert held `first_expert`, the first held
layer's published index `first_layer`) over them.

Sequence `[time; text; patch]` (4,174 tokens in the benchmark's cell: 1
+ 77 + 4,096), positions are indices in it, float32 residual stream,
causal as published. The embedding is the trunk's
(`reference/cohere2_moe.py` `_embed`). For layer input `x`, `h =
RMSNorm(x; rms_norm_eps)`:

    c_q = RMSNorm(h W_qa)                  [T, q_lora_rank]
    q   = c_q W_qb -> heads x qk_head_dim = [q_nope; q_rope]
    [c; k_r] = h W_kva;  c_kv = RMSNorm(c)
    k_nope_j = c_kv W_kb,j;  v_j = c_kv W_vb,j    a head j (W_kvb's two
                                           halves, held apart)
    q_j = [q_nope_j; R_t q_rope_j],  k_j,s = [k_nope_j,s; R_s k_r,s]
    a_j,t = sum over s in S_t of softmax_s(q_j,t . k_j,s / sqrt qk_head_dim) v_j,s
    x = x + concat_j(a_j) W_o

`R` rotates interleaved pairs (2i, 2i+1) (`rope_interleave`) at theta
`rope_parameters.rope_theta`; ONE rotated key part serves every head.

`S_t`, on a layer whose `indexer_types` entry is `full`: `qI = c_q WI_q`
(`index_n_heads` x `index_head_dim`), `kI = LayerNorm(h WI_k)` (one key a
token; weight and bias, eps 1e-6), the first `qk_rope_head_dim` of each
rotated, `w = h WI_w`; `I_ts = (heads x dim)^-1/2 sum_j w_tj relu(qI_tj .
kI_s)`; `S_t` = every `s <= t` while `t + 1 <= index_topk`, else the
`index_topk` keys `s <= t` of largest `I_ts` (a tie at the last place
keeps every key that ties). On a `shared` layer `S` is the nearest
earlier `full` layer's: the carry between stages holds it.

Feed-forward, `n = RMSNorm(x)`: `dense`: W_down(silu(W_gate n) * W_up n)
at `intermediate_size`. `sparse`: `sc = sigmoid(n W_r)` over all
`router_experts`; the `num_experts_per_tok` largest of `sc + b` (`b`
selects only); `g_e = routed_scaling_factor sc_e / sum over the picked of
sc`; `y = sum over the picked experts HELD HERE (`n_routed_experts` from
`first_expert`) of g_e E_e(n) + E_shared(n)`, each `E` a SwiGLU at
`moe_intermediate_size`. What the absent experts would add is left out,
here as in the program: the chip's share of a layer divided by expert
parallelism.

Departures from the published model, each also in the configuration's
file: patch embedding and patch head for the token embedding and the
vocabulary head, no multi-token-prediction module, conditioning in
context, no cache (so the expanded form of the latent attention).
Assumed, the source's `config.json` giving none of them: the RMSNorms on
`c_q` and `c_kv`, the pre-norm order (DeepSeek-V3); the indexer's form
(DeepSeek-V3.2-Exp's lightning indexer), without its Hadamard rotation
(orthogonal: the products are what they were) and without fp8.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import nn
from .cohere2_moe import TIME_FEATURES, _embed, _rope_gptj

INDEX_NORM_EPS = 1e-6


def _rms(x, eps, p):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"]


def _lin(p, x):
    return nn.einsum("...c,cf->...f", x, p["kernel"])


def _rotate_first(x, n, theta):
    """[B, S, H, D] with its first `n` entries rotated."""
    return jnp.concatenate([_rope_gptj(x[..., :n], theta), x[..., n:]],
                           axis=-1)


def index_scores(q_i, k_i, w):
    """q_i [B, T, H, D], k_i [B, T, D], w [B, T, H] -> I [B, T, T], a
    head at a time."""
    b, t, h, d = q_i.shape

    def head(j, acc):
        s = nn.einsum("btd,bsd->bts", jnp.take(q_i, j, axis=2), k_i)
        return acc + jax.nn.relu(s) * jnp.take(w, j, axis=2)[..., None]

    return jax.lax.fori_loop(0, h, head, jnp.zeros((b, t, t))) \
        * (h * d) ** -0.5


def selection(scores, top_k: int):
    """I [B, T, T] -> keep [B, T, T] bool: query t reads key s."""
    t = scores.shape[-1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    if t <= top_k:
        return jnp.broadcast_to(causal, scores.shape)
    seen = jnp.where(causal, scores, -jnp.inf)
    kth = jnp.sort(seen, axis=-1)[..., t - top_k]       # the k-th largest
    early = jnp.arange(t)[:, None] < top_k              # t + 1 <= top_k
    return causal & (early | (seen >= kth[..., None]))


def _selection(m, p, h, c_q):
    b, t, _ = h.shape
    heads, dim = int(m["index_n_heads"]), int(m["index_head_dim"])
    rope, theta = int(m["qk_rope_head_dim"]), float(
        m["rope_parameters"]["rope_theta"])
    q_i = _rotate_first(_lin(p["idx_q"], c_q).reshape(b, t, heads, dim),
                        rope, theta)
    k_i = nn.layer_norm(_lin(p["idx_k"], h), INDEX_NORM_EPS, p["idx_k_norm"])
    k_i = _rotate_first(k_i[:, :, None], rope, theta)[:, :, 0]
    return selection(index_scores(q_i, k_i, _lin(p["idx_w"], h)),
                     int(m["index_topk"]))


def attention(q, k, v, keep):
    """softmax over the kept keys, a head at a time: q / k / v
    [B, T, H, D], keep [B, T, T] -> [B, T, H, D]."""
    d = q.shape[-1]

    def head(args):
        qh, kh, vh = args                               # [B, T, D]
        s = nn.einsum("btd,bsd->bts", qh, kh) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return nn.einsum("bts,bsd->btd", probs, vh)

    out = jax.lax.map(head, tuple(a.transpose(2, 0, 1, 3)
                                  for a in (q, k, v)))  # [H, B, T, D]
    return out.transpose(1, 2, 0, 3)


def _attention(m, p, h, keep, full: bool):
    b, t, _ = h.shape
    eps = float(m["rms_norm_eps"])
    heads = int(m["num_attention_heads"])
    nope, rope = int(m["qk_nope_head_dim"]), int(m["qk_rope_head_dim"])
    rank, theta = int(m["kv_lora_rank"]), float(
        m["rope_parameters"]["rope_theta"])
    c_q = _rms(_lin(p["to_q_a"], h), eps, p["q_a_norm"])
    q = nn.einsum("btc,chd->bthd", c_q, p["to_q_b"]["kernel"])
    q = jnp.concatenate([q[..., :nope], _rope_gptj(q[..., nope:], theta)],
                        axis=-1)
    kv_a = _lin(p["to_kv_a"], h)
    c_kv = _rms(kv_a[..., :rank], eps, p["kv_a_norm"])
    k_r = _rope_gptj(kv_a[:, :, None, rank:], theta)
    k_nope = nn.einsum("btc,chd->bthd", c_kv, p["to_k_b"]["kernel"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (b, t, heads, rope))], axis=-1)
    v = nn.einsum("btc,chd->bthd", c_kv, p["to_v_b"]["kernel"])
    if full:
        keep = _selection(m, p, h, c_q)
    a = attention(q, k, v, keep)
    return nn.einsum("bthd,hdc->btc", a, p["to_out"]["kernel"]), keep


def _swiglu(p, prefix, n):
    return _lin(p[prefix + "down"],
                nn.silu(_lin(p[prefix + "gate"], n)) * _lin(p[prefix + "up"], n))


def _routed(m, p, n):
    """n [N, D] -> the held experts' part of the routed sum, an expert
    at a time."""
    n_tok, k = n.shape[0], int(m["num_experts_per_tok"])
    first, held = int(m.get("first_expert", 0)), int(m["n_routed_experts"])
    if not held:
        return jnp.zeros_like(n)
    scores = jax.nn.sigmoid(_lin(p["router"], n))
    _, idx = jax.lax.top_k(scores + p["router_bias"], k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    if m.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * float(m["routed_scaling_factor"])
    # [N, router_experts]: the weight of each expert for each token
    w = jnp.zeros_like(scores).at[jnp.arange(n_tok)[:, None], idx].set(top)

    def expert(e, acc):
        one = lambda name: jnp.take(p[name]["kernel"], e, axis=0)
        y = nn.einsum("nf,fd->nd", nn.silu(
            nn.einsum("nd,df->nf", n, one("experts_gate")))
            * nn.einsum("nd,df->nf", n, one("experts_up")),
            one("experts_down"))
        return acc + y * jnp.take(w, first + e, axis=1)[:, None]

    return jax.lax.fori_loop(0, held, expert, jnp.zeros_like(n))


def _layer(m, p, x, keep, sparse: bool, full: bool):
    eps = float(m["rms_norm_eps"])
    a, keep = _attention(m, p, _rms(x, eps, p["norm"]), keep, full)
    x = x + a
    n = _rms(x, eps, p["mlp_norm"])
    if not sparse:
        return x + _swiglu(p, "mlp_", n), keep
    b, t, d = n.shape
    return x + _routed(m, p, n.reshape(b * t, d)).reshape(b, t, d) \
        + _swiglu(p, "shared_", n), keep


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
    embedding, a stage a layer, the head. Layers of one structure (the
    two feed-forward kinds x the two indexer kinds) share ONE `apply`, so
    a caller that jits it compiles each structure once: the three
    `sparse` + `shared` layers of a period are one program. The carry
    between layers holds the tokens and the selection."""
    applies = {}

    def layer_of(sparse: bool, full: bool):
        if (sparse, full) not in applies:
            def layer(parts, carry):
                tokens, keep = _layer(cfg, parts[0], carry["tokens"],
                                      carry["keep"], sparse, full)
                return {"tokens": tokens, "keep": keep}
            applies[sparse, full] = layer
        return applies[sparse, full]

    def embed(parts, carry):
        tokens = _embed(cfg, parts[0], carry)
        t = tokens.shape[1]     # no selection yet: a `full` layer is first
        return {"tokens": tokens,
                "keep": jnp.zeros((tokens.shape[0], t, t), bool)}

    def head(parts, carry):
        return _head(cfg, shape, dict(zip(("final_norm", "final_proj"),
                                          parts)), carry["tokens"])

    kinds = list(zip(cfg["mlp_layer_types"], cfg["indexer_types"]))
    assert kinds[0][1] == "full", "the first layer held selects for itself"
    return ([("embed", ("embed",), embed)]
            + [(f"layer_{i}", (f"layer_{i}",),
                layer_of(mlp == "sparse", indexer == "full"))
               for i, (mlp, indexer) in enumerate(kinds)]
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


def selected_pairs(t: int, top_k: int) -> float:
    """(query, key) pairs a sequence of `t` tokens reads under the
    selection, no score tying: every causal pair of the first `top_k`
    queries, `top_k` a query beyond."""
    full = min(t, top_k)
    return full * (full + 1) / 2.0 + max(0, t - top_k) * float(top_k)


def forward_flops(cfg) -> float:
    """Required operations of one image's forward pass: every product at
    its published width, the core over the SELECTED pairs only, the
    indexer's scores over the causal pairs (each has to be scored to be
    selected from), the picks that land on the experts held at their
    expectation (`num_experts_per_tok` x held / published experts a token
    a sparse layer), nothing for what is masked or padded."""
    m, p, t = _sizes(cfg)
    d = int(m["hidden_size"])
    heads = int(m["num_attention_heads"])
    qk, vd = int(m["qk_head_dim"]), int(m["v_head_dim"])
    q_rank, kv_rank = int(m["q_lora_rank"]), int(m["kv_lora_rank"])
    rope, nope = int(m["qk_rope_head_dim"]), int(m["qk_nope_head_dim"])
    i_heads, i_dim = int(m["index_n_heads"]), int(m["index_head_dim"])
    f_dense, f_moe = int(m["intermediate_size"]), int(
        m["moe_intermediate_size"])
    res, ch = int(cfg["input"]["resolution"]), int(cfg["input"]["channels"])
    patches = (res // p) ** 2
    flops = 2.0 * patches * (p * p * ch) * d                # patch embed
    flops += 2.0 * (TIME_FEATURES * d + d * d)              # time MLP
    flops += 2.0 * int(cfg["conditioning"]["tokens"]) * int(
        cfg["conditioning"]["features"]) * d                # text
    attn = (d * q_rank + q_rank * heads * qk + d * (kv_rank + rope)
            + kv_rank * heads * (nope + vd) + heads * vd * d)
    index = q_rank * i_heads * i_dim + d * i_dim + d * i_heads
    routed = int(m["router_experts"])
    picks = int(m["num_experts_per_tok"]) * int(m["n_routed_experts"]) \
        / float(routed)                                     # a token
    sparse = d * routed + 3 * d * f_moe * (
        picks + int(m["n_shared_experts"]))
    core = 4.0 * selected_pairs(t, int(m["index_topk"])) * qk * heads
    for mlp, indexer in zip(m["mlp_layer_types"], m["indexer_types"]):
        weights = attn + (3 * d * f_dense if mlp == "dense" else sparse)
        if indexer == "full":
            weights += index
            flops += 2.0 * (t * (t + 1) / 2.0) * i_heads * i_dim
        flops += 2.0 * t * weights + core
    flops += 2.0 * patches * d * p * p * int(m["output_channels"])
    return flops


def kernel_costs(cfg):
    """Required operations and bytes of each named kernel for ONE model
    evaluation of ONE row: {kernel: {"flops", "bytes"}}.

    `fdt_flash_fwd` (the latent attention's expanded core): the SELECTED
    (query, key) pairs only, 4 x pairs x head size x heads a layer; q
    read and the output written once, k and v once a head (the expanded
    form: a head's keys are its own), in the model's type, and the mask
    a byte a causal pair, once for all heads.

    `fdt_moe_gmm` (the gate/up and the down kernel together): 2 x 3 x
    hidden x width a held pick, at the picks' expectation; bytes: the
    picks' rows in and out of both kernels, and each held expert's three
    matrices once a CALL, which serves the 2 evaluations of one guided
    row (the serving round evaluates this model a row at a time): half
    of them an evaluation, the least any call reads."""
    m, _, t = _sizes(cfg)
    d, f = int(m["hidden_size"]), int(m["moe_intermediate_size"])
    heads, qk = int(m["num_attention_heads"]), int(m["qk_head_dim"])
    width = 4 if m.get("dtype") in (None, "float32") else 2
    layers = len(m["mlp_layer_types"])
    flash = {"flops": layers * 4.0 * selected_pairs(
                 t, int(m["index_topk"])) * qk * heads,
             "bytes": layers * (4.0 * t * qk * heads * width
                                + t * (t + 1) / 2.0)}
    sparse, held = list(m["mlp_layer_types"]).count("sparse"), int(
        m["n_routed_experts"])
    picks = t * int(m["num_experts_per_tok"]) * held / float(
        m["router_experts"])                                # a layer
    evals_a_call = 2.0
    gmm = {"flops": sparse * picks * 2.0 * 3 * d * f,
           "bytes": sparse * width * (
               picks * (d + f + f + d)
               + held * 3.0 * d * f / evals_a_call)}
    return {"fdt_flash_fwd": flash, "fdt_moe_gmm": gmm}
