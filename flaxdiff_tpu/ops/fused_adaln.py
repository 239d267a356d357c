"""Fused AdaLN / GEGLU / gate-residual Pallas kernels (DiT epilogues).

The DiT-family hot path runs AdaLN modulate, the GEGLU activation, and
the gated residual as separate HBM-bound XLA ops (models/vit_common.py
AdaLNZero, models/dit.py DiTBlock, models/attention.py GEGLUFeedForward).
Each op is bandwidth-bound — reads and writes of [B, L, C] activations
dominating trivial VPU math — so the win is fewer HBM round trips, the
same lever ops/fused_norm.py pulled for the resblock prologue:

- ``fused_ln_modulate`` / ``fused_ln_modulate2``: LayerNorm (no affine)
  + ``modulate(norm_x, scale, shift)`` emitting one or BOTH modulated
  views (attn + mlp branches of AdaLNZero) from a single read of x.
  Unfused, the dual-view chain costs ~5 activation-sized transfers
  (norm write, two reads, two view writes) plus the x read; fused it is
  one read and two writes. Per-row (mean, rstd) are saved as [B, L, 1]
  f32 residuals and reused by the backward.
- ``fused_gate_residual``: ``x + gate * h`` with a per-sample [B, 1, C]
  gate; backward emits dh and the gate's L-reduction without an extra
  elementwise pass (dx is the cotangent itself, returned without a
  copy).
- ``fused_geglu``: ``val * gelu(gate)`` over the packed [B, L, 2F]
  GEGLU projection. The two halves stream through separate lane-block
  specs over the SAME array (block-index maps, not in-kernel lane
  slicing — the d<128 flash lesson), so the concatenated Dense output
  never round-trips through a split.

All three share the fused_norm dispatch conventions:
``FLAXDIFF_FUSED_ADALN=xla`` forces the XLA composition,
``=interpret`` runs the real kernels through the Pallas interpreter on
CPU, ``FLAXDIFF_FUSED_ADALN_BWD=xla`` swaps only the backward for
recompute-through-autodiff; neither A/B is measured on the chip
(ROADMAP D2). Off-TPU with no env set the wrappers return the exact XLA
composition (and the model layers don't even call them — see
``fused_adaln_active``), so CPU outputs are bit-identical to the
unfused code path.

Numerics: all norm/softening math is f32 regardless of input dtype;
modulated outputs follow jnp promotion (f32 norm x bf16 scale -> f32),
matching the unfused `nn.LayerNorm(dtype=f32)` + `modulate` chain.
Clipping of the AdaLN-Zero mlp pair stays OUTSIDE the kernel in XLA
(O(B*C), nothing to fuse) so `jnp.clip`'s exact VJP semantics are
preserved by construction.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Same VMEM budget rationale as fused_norm: ~1 MiB f32 blocks keep a
# handful of block-sized temporaries well under the ~16 MiB limit.
_BLOCK_BYTES = 1 << 20


def _block_rows(l: int, c: int, streams: int) -> int:
    """Rows per block given `streams` live block-sized f32 tensors."""
    rows = max(8, _BLOCK_BYTES // (4 * c * max(streams, 1)))
    rows = min(rows, l)
    return max(8, (rows // 8) * 8)


def _env_mode() -> Optional[str]:
    return os.environ.get("FLAXDIFF_FUSED_ADALN")


def _interpret_env() -> bool:
    """FLAXDIFF_FUSED_ADALN=interpret mirrors FLAXDIFF_FUSED_NORM: run
    the real Pallas kernels — fwd AND bwd — through the interpreter
    inside full models on CPU. One helper so fwd and bwd cannot read
    the env differently."""
    return _env_mode() == "interpret"


@functools.cache
def _on_tpu() -> bool:
    # no except: a backend that cannot initialise is an error, not "cpu"
    return jax.devices()[0].platform == "tpu"


def fused_adaln_active() -> bool:
    """Should model layers take the fused path? Default: yes on TPU, no
    elsewhere (the unfused composition is the off-TPU code path, so CPU
    outputs are bit-identical to the pre-fusion model). Env A/B:
    ``FLAXDIFF_FUSED_ADALN=xla`` forces off (in-context ablation),
    ``=interpret`` forces on through the interpreter (CPU CI)."""
    env = _env_mode()
    if env == "xla":
        return False
    if env == "interpret":
        return True
    return _on_tpu()


def _use_pallas(interpret: bool, force_pallas: bool) -> Tuple[bool, bool]:
    """(run_pallas, interpret) shared dispatch gate."""
    if _interpret_env():
        interpret = True
    if force_pallas:
        return True, interpret
    if _env_mode() == "xla":
        return False, interpret
    return (_on_tpu() or interpret), interpret


def _dispatch(fused, operands, xla, interpret: bool, force_pallas: bool):
    """Call a fused op whose operands ALL carry the batch on dim 0. On
    the Pallas path under an active multi-device mesh the kernels run
    on each device's batch shard (parallel/context.py
    per_device_over_batch); on the XLA path `fused` already is the
    composition GSPMD partitions."""
    if not _use_pallas(interpret, force_pallas)[0]:
        return fused(*operands)
    from ..parallel.context import per_device_over_batch
    return per_device_over_batch(fused, operands,
                                 (True,) * len(operands), xla)


def _pad_rows(x: jax.Array, blk: int) -> jax.Array:
    l = x.shape[1]
    pad = (-l) % blk
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# LayerNorm + modulate (one or two views)
# ---------------------------------------------------------------------------

def _xla_ln_modulate(x: jax.Array, pairs: Sequence[Tuple[jax.Array,
                                                         jax.Array]],
                     eps: float) -> Tuple[jax.Array, ...]:
    """The exact unfused composition: flax ``nn.LayerNorm(use_scale=
    False, use_bias=False, dtype=f32)`` (fast-variance form) followed by
    ``modulate(norm_x, s, b)`` per view."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.maximum(
        jnp.mean(xf * xf, axis=-1, keepdims=True) - mu * mu, 0.0)
    norm = (xf - mu) * jax.lax.rsqrt(var + eps)
    return tuple(norm * (1.0 + s) + b for s, b in pairs)


def _ln_mod_kernel(*refs, eps: float, nviews: int):
    x_ref = refs[0]
    s_refs = refs[1:1 + 2 * nviews:2]
    b_refs = refs[2:1 + 2 * nviews:2]
    out_refs = refs[1 + 2 * nviews:1 + 3 * nviews]
    mean_ref, rstd_ref = refs[1 + 3 * nviews:]

    xf = x_ref[0].astype(jnp.float32)                    # [blk, C]
    mu = jnp.mean(xf, axis=1, keepdims=True)             # [blk, 1]
    # fast-variance form to match flax's LayerNorm statistics; clamped
    # like flax so constant rows cannot produce a negative variance
    var = jnp.maximum(
        jnp.mean(xf * xf, axis=1, keepdims=True) - mu * mu, 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (xf - mu) * rstd
    for s_ref, b_ref, o_ref in zip(s_refs, b_refs, out_refs):
        s = s_ref[0].astype(jnp.float32)                 # [1, C]
        b = b_ref[0].astype(jnp.float32)
        o_ref[0] = (xhat * (1.0 + s) + b).astype(o_ref.dtype)
    mean_ref[0] = mu
    rstd_ref[0] = rstd


def _ln_mod_bwd_kernel(*refs, nviews: int):
    """One tiled pass over (x, g_i): dx (row reductions are per-row,
    so no cross-block merge is needed) plus per-block (db_i, ds_i)
    partials for the XLA finalize."""
    x_ref = refs[0]
    s_refs = refs[1:1 + nviews]
    mean_ref, rstd_ref = refs[1 + nviews:3 + nviews]
    g_refs = refs[3 + nviews:3 + 2 * nviews]
    dx_ref, psum_ref = refs[3 + 2 * nviews:]

    xf = x_ref[0].astype(jnp.float32)                    # [blk, C]
    mu = mean_ref[0].astype(jnp.float32)                 # [blk, 1]
    rstd = rstd_ref[0].astype(jnp.float32)
    xhat = (xf - mu) * rstd

    dxhat = None
    partials = []
    for s_ref, g_ref in zip(s_refs, g_refs):
        g = g_ref[0].astype(jnp.float32)
        s = s_ref[0].astype(jnp.float32)
        term = g * (1.0 + s)
        dxhat = term if dxhat is None else dxhat + term
        partials.append(jnp.sum(g, axis=0, keepdims=True))          # db_i
        partials.append(jnp.sum(g * xhat, axis=0, keepdims=True))   # ds_i
    m1 = jnp.mean(dxhat, axis=1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=1, keepdims=True)
    dx_ref[0] = (rstd * (dxhat - m1 - xhat * m2)).astype(dx_ref.dtype)
    psum_ref[0, 0] = jnp.concatenate(partials, axis=0)   # [2*nviews, C]


def _ln_mod_impl(x, pairs, eps, interpret, force_pallas, save_stats):
    """Returns (views tuple, mean, rstd); stats are None on the XLA
    fallback (its backward recomputes through autodiff)."""
    run_pallas, interpret = _use_pallas(interpret, force_pallas)
    if not run_pallas:
        return _xla_ln_modulate(x, pairs, eps), None, None

    b, l, c = x.shape
    nviews = len(pairs)
    # live streams: x + nviews outputs (+ xhat temp)
    blk = _block_rows(l, c, streams=nviews + 2)
    xr = _pad_rows(x, blk)
    l_pad = xr.shape[1]
    nblk = l_pad // blk

    out_dtype = jnp.result_type(jnp.float32,
                                *(p[0].dtype for p in pairs))
    in_specs = [pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0))]
    operands = [xr]
    for s, bsh in pairs:
        in_specs.append(pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)))
        in_specs.append(pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)))
        operands += [s, bsh]
    out_specs = [pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0))
                 for _ in range(nviews)]
    out_shape = [jax.ShapeDtypeStruct((b, l_pad, c), out_dtype)
                 for _ in range(nviews)]
    # per-row stats, [B, L, 1]: sublane-major column blocks the backward
    # re-broadcasts across lanes (w==1 lane-broadcast, never a lane
    # slice)
    out_specs += [pl.BlockSpec((1, blk, 1), lambda i, j: (i, j, 0))] * 2
    out_shape += [jax.ShapeDtypeStruct((b, l_pad, 1), jnp.float32)] * 2

    res = pl.pallas_call(
        functools.partial(_ln_mod_kernel, eps=eps, nviews=nviews),
        name="fdt_adaln_mod_fwd",
        grid=(b, nblk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    views = tuple(v[:, :l] for v in res[:nviews])
    mean, rstd = res[nviews], res[nviews + 1]
    return views, mean, rstd


def _ln_mod_bwd(x, pairs, mean, rstd, gs, interpret):
    """Pallas backward reusing the saved per-row stats. Returns
    (dx, [(ds_i, db_i), ...])."""
    b, l, c = x.shape
    nviews = len(pairs)
    blk = _block_rows(l, c, streams=2 * nviews + 2)
    # the saved stats were written at the FORWARD's block padding; they
    # are [B, L_pad_fwd, 1] — re-pad everything to THIS pass's block
    xr = _pad_rows(x, blk)
    l_pad = xr.shape[1]
    nblk = l_pad // blk
    mean_r = _pad_rows(mean[:, :l], blk)
    rstd_r = _pad_rows(rstd[:, :l], blk)
    gs_r = [_pad_rows(g.astype(jnp.float32), blk) for g in gs]

    in_specs = [pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0))]
    operands = [xr]
    for s, _ in pairs:
        in_specs.append(pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)))
        operands.append(s)
    in_specs += [pl.BlockSpec((1, blk, 1), lambda i, j: (i, j, 0))] * 2
    operands += [mean_r, rstd_r]
    in_specs += [pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0))
                 for _ in gs_r]
    operands += gs_r

    dx, psums = pl.pallas_call(
        functools.partial(_ln_mod_bwd_kernel, nviews=nviews),
        name="fdt_adaln_mod_bwd",
        grid=(b, nblk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 2 * nviews, c), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, l_pad, c), x.dtype),
            jax.ShapeDtypeStruct((b, nblk, 2 * nviews, c), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)

    merged = jnp.sum(psums, axis=1)          # [B, 2*nviews, C]
    grads = []
    for i, (s, bsh) in enumerate(pairs):
        db = merged[:, 2 * i, :][:, None, :].astype(bsh.dtype)
        ds = merged[:, 2 * i + 1, :][:, None, :].astype(s.dtype)
        grads.append((ds, db))
    return dx[:, :l], grads


def _make_ln_mod_vjp(nviews: int):
    """custom_vjp factory for the 1- and 2-view variants (fixed arity)."""

    def primal(x, *sb, eps, interpret, force_pallas):
        pairs = tuple((sb[2 * i], sb[2 * i + 1]) for i in range(nviews))
        views, _, _ = _ln_mod_impl(x, pairs, eps, interpret,
                                   force_pallas, save_stats=False)
        return views if nviews > 1 else views[0]

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
    def fn(eps, interpret, force_pallas, x, *sb):
        return primal(x, *sb, eps=eps, interpret=interpret,
                      force_pallas=force_pallas)

    def fwd(eps, interpret, force_pallas, x, *sb):
        pairs = tuple((sb[2 * i], sb[2 * i + 1]) for i in range(nviews))
        views, mean, rstd = _ln_mod_impl(x, pairs, eps, interpret,
                                         force_pallas, save_stats=True)
        out = views if nviews > 1 else views[0]
        return out, (x, sb, mean, rstd)

    def bwd(eps, interpret, force_pallas, res, g):
        x, sb, mean, rstd = res
        pairs = tuple((sb[2 * i], sb[2 * i + 1]) for i in range(nviews))
        gs = tuple(g) if nviews > 1 else (g,)
        if (mean is not None
                and os.environ.get("FLAXDIFF_FUSED_ADALN_BWD") != "xla"):
            if _interpret_env():
                interpret = True
            dx, grads = _ln_mod_bwd(x, pairs, mean, rstd, gs, interpret)
            flat = []
            for ds, db in grads:
                flat += [ds, db]
            return (dx, *flat)
        # XLA-path forward (no saved stats) or bwd A/B: recompute
        # through autodiff of the exact composition
        def f(x_, *sb_):
            ps = tuple((sb_[2 * i], sb_[2 * i + 1])
                       for i in range(nviews))
            out = _xla_ln_modulate(x_, ps, eps)
            return out if nviews > 1 else out[0]
        _, vjp = jax.vjp(f, x, *sb)
        return vjp(g)

    fn.defvjp(fwd, bwd)
    return fn


_ln_mod1 = _make_ln_mod_vjp(1)
_ln_mod2 = _make_ln_mod_vjp(2)


def _modulator_shapes_ok(x: jax.Array, *mods: jax.Array) -> bool:
    """The kernels assume per-sample [B, 1, C] modulators over a
    [B, L, C] token tensor (the AdaLN-Zero layout); anything else —
    per-token modulation, spatial tokens — takes the XLA composition."""
    if x.ndim != 3:
        return False
    b, _, c = x.shape
    return all(m.shape == (b, 1, c) for m in mods)


def fused_ln_modulate(x: jax.Array, scale: jax.Array, shift: jax.Array,
                      eps: float = 1e-5, interpret: bool = False,
                      force_pallas: bool = False) -> jax.Array:
    """``modulate(LayerNorm(x), scale, shift)`` in one HBM pass.
    x: [B, L, C]; scale/shift: [B, 1, C]. Differentiable; falls back to
    the exact XLA composition off-TPU / on unsupported shapes."""
    xla = lambda x_, s_, b_: _xla_ln_modulate(x_, ((s_, b_),), eps)[0]
    if not force_pallas and not _modulator_shapes_ok(x, scale, shift):
        return xla(x, scale, shift)
    return _dispatch(functools.partial(_ln_mod1, eps, interpret,
                                       force_pallas),
                     (x, scale, shift), xla, interpret, force_pallas)


def fused_ln_modulate2(x: jax.Array,
                       s1: jax.Array, b1: jax.Array,
                       s2: jax.Array, b2: jax.Array,
                       eps: float = 1e-5, interpret: bool = False,
                       force_pallas: bool = False
                       ) -> Tuple[jax.Array, jax.Array]:
    """Both AdaLN-Zero views — ``modulate(norm_x, s1, b1)`` and
    ``modulate(norm_x, s2, b2)`` — from ONE read of x (the attn and mlp
    branches share the same un-affined LayerNorm). Clip the mlp pair
    BEFORE calling (jnp.clip stays in XLA; its VJP chains through the
    custom_vjp boundary exactly)."""
    xla = lambda x_, s1_, b1_, s2_, b2_: _xla_ln_modulate(
        x_, ((s1_, b1_), (s2_, b2_)), eps)
    if not force_pallas and not _modulator_shapes_ok(x, s1, b1, s2, b2):
        return xla(x, s1, b1, s2, b2)
    return _dispatch(functools.partial(_ln_mod2, eps, interpret,
                                       force_pallas),
                     (x, s1, b1, s2, b2), xla, interpret, force_pallas)


# ---------------------------------------------------------------------------
# Gated residual: x + gate * h
# ---------------------------------------------------------------------------

def _gate_res_kernel(x_ref, g_ref, h_ref, o_ref):
    # native-dtype math so the result matches the XLA composition's
    # promotion exactly (bf16 x + g*h stays bf16)
    o_ref[0] = (x_ref[0] + g_ref[0] * h_ref[0]).astype(o_ref.dtype)


def _gate_res_bwd_kernel(g_ref, h_ref, dout_ref, dh_ref, pg_ref):
    dout = dout_ref[0]
    dh_ref[0] = (g_ref[0] * dout).astype(dh_ref.dtype)
    pg_ref[0, 0] = jnp.sum(
        dout.astype(jnp.float32) * h_ref[0].astype(jnp.float32),
        axis=0, keepdims=True)                           # [1, C]


def _gate_res_impl(x, gate, h, interpret, force_pallas):
    run_pallas, interpret = _use_pallas(interpret, force_pallas)
    if not run_pallas:
        return x + gate * h
    b, l, c = x.shape
    blk = _block_rows(l, c, streams=3)
    xr, hr = _pad_rows(x, blk), _pad_rows(h, blk)
    l_pad = xr.shape[1]
    out_dtype = jnp.result_type(x.dtype, gate.dtype, h.dtype)
    out = pl.pallas_call(
        _gate_res_kernel,
        name="fdt_adaln_gate_fwd",
        grid=(b, l_pad // blk),
        in_specs=[
            pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, l_pad, c), out_dtype),
        interpret=interpret,
    )(xr, gate, hr)
    return out[:, :l]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gate_res(x, gate, h, interpret, force_pallas):
    return _gate_res_impl(x, gate, h, interpret, force_pallas)


def _gate_res_fwd(x, gate, h, interpret, force_pallas):
    # zero-size dtype token: residuals must be jax types, and the bwd
    # only needs x's dtype (dx is the cotangent itself, recast)
    return (_gate_res_impl(x, gate, h, interpret, force_pallas),
            (gate, h, jnp.zeros((0,), x.dtype)))


def _gate_res_bwd(interpret, force_pallas, res, g):
    gate, h, x_token = res
    x_dtype = x_token.dtype
    run_pallas, interpret = _use_pallas(interpret, force_pallas)
    if (not run_pallas
            or os.environ.get("FLAXDIFF_FUSED_ADALN_BWD") == "xla"):
        dgate = jnp.sum(g.astype(jnp.float32) * h.astype(jnp.float32),
                        axis=1, keepdims=True).astype(gate.dtype)
        return g.astype(x_dtype), dgate, (gate * g).astype(h.dtype)
    b, l, c = h.shape
    blk = _block_rows(l, c, streams=3)
    hr, gr = _pad_rows(h, blk), _pad_rows(g, blk)
    l_pad = hr.shape[1]
    nblk = l_pad // blk
    dh, pg = pl.pallas_call(
        _gate_res_bwd_kernel,
        name="fdt_adaln_gate_bwd",
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
            # [B, nblk, 1, C]: the block's last two dims equal the
            # array's (the Pallas TPU block-shape rule; a (1, 1, C)
            # block of [B, nblk, C] does not lower once nblk > 1)
            pl.BlockSpec((1, 1, 1, c), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, l_pad, c), h.dtype),
            jax.ShapeDtypeStruct((b, nblk, 1, c), jnp.float32),
        ],
        interpret=interpret,
    )(gate, hr, gr)
    dgate = jnp.sum(pg, axis=1).astype(gate.dtype)      # [B, 1, C]
    # dx == the cotangent itself: no kernel, no copy
    return g.astype(x_dtype), dgate, dh[:, :l]


_gate_res.defvjp(_gate_res_fwd, _gate_res_bwd)


def fused_gate_residual(x: jax.Array, gate: jax.Array, h: jax.Array,
                        interpret: bool = False,
                        force_pallas: bool = False) -> jax.Array:
    """``x + gate * h`` — the AdaLN-Zero gated-residual epilogue.
    x/h: [B, L, C]; gate: [B, 1, C]. Differentiable (dgate's L-reduction
    rides the dh pass)."""
    xla = lambda x_, g_, h_: x_ + g_ * h_
    if not force_pallas and not (
            _modulator_shapes_ok(x, gate) and h.shape == x.shape):
        return xla(x, gate, h)
    return _dispatch(
        lambda x_, g_, h_: _gate_res(x_, g_, h_, interpret, force_pallas),
        (x, gate, h), xla, interpret, force_pallas)


# ---------------------------------------------------------------------------
# GEGLU: val * gelu(gate) over the packed [B, L, 2F] projection
# ---------------------------------------------------------------------------

def _gelu_tanh(x):
    """jax.nn.gelu(approximate=True): 0.5 x (1 + tanh(sqrt(2/pi)
    (x + 0.044715 x^3)))."""
    c = 0.7978845608028654  # sqrt(2/pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def _gelu_tanh_grad(x):
    c = 0.7978845608028654
    t = jnp.tanh(c * (x + 0.044715 * x ** 3))
    return (0.5 * (1.0 + t)
            + 0.5 * x * (1.0 - t * t) * c * (1.0 + 3 * 0.044715 * x * x))


def _geglu_kernel(gate_ref, val_ref, o_ref):
    g = gate_ref[0].astype(jnp.float32)
    v = val_ref[0].astype(jnp.float32)
    o_ref[0] = (v * _gelu_tanh(g)).astype(o_ref.dtype)


def _geglu_bwd_kernel(gate_ref, val_ref, dout_ref, dproj_ref):
    g = gate_ref[0].astype(jnp.float32)
    v = val_ref[0].astype(jnp.float32)
    dout = dout_ref[0].astype(jnp.float32)
    dgate = dout * v * _gelu_tanh_grad(g)
    dval = dout * _gelu_tanh(g)
    # one full-width store: the halves concatenate along lanes at the
    # F boundary (a lane-aligned multiple on real models), so every
    # element of the cotangent block is written exactly once
    dproj_ref[0] = jnp.concatenate([dgate, dval],
                                   axis=1).astype(dproj_ref.dtype)


def _xla_geglu(proj: jax.Array) -> jax.Array:
    gate, val = jnp.split(proj, 2, axis=-1)
    return val * jax.nn.gelu(gate)


def _geglu_impl(proj, interpret, force_pallas):
    run_pallas, interpret = _use_pallas(interpret, force_pallas)
    if not run_pallas:
        return _xla_geglu(proj)
    b, l, f2 = proj.shape
    f = f2 // 2
    blk = _block_rows(l, f2, streams=2)
    pr = _pad_rows(proj, blk)
    l_pad = pr.shape[1]
    # The two halves arrive as separate F-wide lane blocks of the SAME
    # array (block index 0 / 1 on the last dim): the split happens in
    # the block DMA, never as an in-kernel lane slice.
    half = lambda j: pl.BlockSpec((1, blk, f),
                                  lambda i, k, j=j: (i, k, j))
    out = pl.pallas_call(
        _geglu_kernel,
        name="fdt_adaln_geglu_fwd",
        grid=(b, l_pad // blk),
        in_specs=[half(0), half(1)],
        out_specs=pl.BlockSpec((1, blk, f), lambda i, k: (i, k, 0)),
        out_shape=jax.ShapeDtypeStruct((b, l_pad, f), proj.dtype),
        interpret=interpret,
    )(pr, pr)
    return out[:, :l]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _geglu(proj, interpret, force_pallas):
    return _geglu_impl(proj, interpret, force_pallas)


def _geglu_fwd(proj, interpret, force_pallas):
    return _geglu_impl(proj, interpret, force_pallas), proj


def _geglu_bwd(interpret, force_pallas, proj, g):
    run_pallas, interpret = _use_pallas(interpret, force_pallas)
    if (not run_pallas
            or os.environ.get("FLAXDIFF_FUSED_ADALN_BWD") == "xla"):
        _, vjp = jax.vjp(_xla_geglu, proj)
        return vjp(g)
    b, l, f2 = proj.shape
    f = f2 // 2
    blk = _block_rows(l, f2, streams=3)
    pr = _pad_rows(proj, blk)
    gr = _pad_rows(g, blk)
    l_pad = pr.shape[1]
    half = lambda j: pl.BlockSpec((1, blk, f),
                                  lambda i, k, j=j: (i, k, j))
    dproj = pl.pallas_call(
        _geglu_bwd_kernel,
        name="fdt_adaln_geglu_bwd",
        grid=(b, l_pad // blk),
        in_specs=[half(0), half(1),
                  pl.BlockSpec((1, blk, f), lambda i, k: (i, k, 0))],
        out_specs=pl.BlockSpec((1, blk, f2), lambda i, k: (i, k, 0)),
        out_shape=jax.ShapeDtypeStruct((b, l_pad, f2), proj.dtype),
        interpret=interpret,
    )(pr, pr, gr)
    return (dproj[:, :l],)


_geglu.defvjp(_geglu_fwd, _geglu_bwd)


def fused_geglu(proj: jax.Array, interpret: bool = False,
                force_pallas: bool = False) -> jax.Array:
    """``val * gelu(gate)`` where ``gate, val = split(proj, 2, -1)`` —
    the GEGLUFeedForward activation over the packed projection.
    proj: [B, L, 2F]. Differentiable; exact XLA composition off-TPU."""
    # Mosaic streams each half as its own lane block, so F must fill
    # whole 128-lane tiles; the interpreter has no such rule
    if not force_pallas and not (
            proj.ndim == 3 and proj.shape[-1] % 2 == 0
            and (_interpret_env() or interpret
                 or proj.shape[-1] % 256 == 0)):
        return _xla_geglu(proj)
    return _dispatch(lambda p_: _geglu(p_, interpret, force_pallas),
                     (proj,), _xla_geglu, interpret, force_pallas)
