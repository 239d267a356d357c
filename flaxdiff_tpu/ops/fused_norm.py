"""Fused GroupNorm + SiLU Pallas kernels (resblock prologue).

The reference runs GroupNorm and SiLU as separate XLA ops
(reference flaxdiff/models/common.py:283-334); on TPU the chain is
HBM-bandwidth bound, so the affine + activation are fused into the
normalization pass. Two tiled kernels (stats, then normalize) so samples
of any spatial size stream through VMEM in blocks:

- stats kernel: per (sample, hw-block) partial group sums/sumsqs, computed
  with 2D matmuls against a [C, G] membership mask (Mosaic can't reshape
  across the lane dim, and the mask matmul rides the MXU).
- normalize kernel: (x - mean) * rstd * scale + bias (+ SiLU) per block.

Backward (r5): dedicated Pallas kernels reusing the forward's saved
per-group stats — one stats pass over (x, g) producing the dx correction
terms and dscale/dbias partials, an O(B*G + C) XLA finalize, then the dx
pass. Falls back to XLA off-TPU.

The kernels are for small batches only (`_batch_fills_sublanes`): once
the batch fills a sublane tile, XLA's TPU convolutions keep a
`[B, H, W, C]` activation with the batch in the sublanes (physically
`[H, W, B, C]`), a `pallas_call` over `[B, HW, C]` is reached only
through a layout copy in and a copy out, and the custom call is a wall
that XLA cannot fuse the normalize into its neighbours across. There the
XLA composition is the faster program by a fifth of the UNet's train
step (docs/KERNELS.md has the chip readings), so the shape picks it.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Target f32 bytes for one [block_hw, C] input block in VMEM. The kernels
# keep ~3 block-sized f32 temporaries live, so 1 MiB blocks stay well
# under the ~16 MiB scoped-VMEM limit.
_BLOCK_BYTES = 1 << 20


def _block_hw(hw: int, c: int) -> int:
    rows = max(8, _BLOCK_BYTES // (4 * c))
    rows = min(rows, hw)
    # Round to a sublane-friendly multiple of 8.
    return max(8, (rows // 8) * 8)


def _fused_norm_interpret() -> bool:
    """FLAXDIFF_FUSED_NORM=interpret mirrors FLAXDIFF_FLASH_INTERPRET
    (ops/attention.py _flash_interpret): run the real Pallas kernels —
    fwd AND the r5 backward — through the interpreter inside full
    models on CPU. One helper so fwd and bwd cannot read the env
    differently (interpreted fwd + Mosaic bwd would crash)."""
    return os.environ.get("FLAXDIFF_FUSED_NORM") == "interpret"


def _use_pallas(interpret: bool, force_pallas: bool):
    """(run_pallas, interpret): Pallas on TPU or under the interpreter,
    the XLA composition elsewhere. FLAXDIFF_FUSED_NORM=xla takes the
    composition at every shape: the A/B hatch PR 28 read on the chip
    (docs/KERNELS.md), which is why `_batch_fills_sublanes` now picks
    the composition at a training batch."""
    if _fused_norm_interpret():
        interpret = True
    if force_pallas:
        return True, interpret
    if os.environ.get("FLAXDIFF_FUSED_NORM") == "xla":
        return False, interpret
    return (jax.devices()[0].platform == "tpu" or interpret), interpret


def _batch_fills_sublanes(shape) -> bool:
    """A batch that fills the bf16 sublane tile (a multiple of 16:
    training, per device under `shard_map`) takes the XLA composition,
    whatever the platform or `force_pallas`; solo and small-batch
    sampling keep the kernels. Nothing but the shape selects."""
    return shape[0] % 16 == 0


def _member_mask(c: int, groups: int) -> jnp.ndarray:
    cg = c // groups
    ch = jax.lax.broadcasted_iota(jnp.int32, (c, groups), 0)
    gi = jax.lax.broadcasted_iota(jnp.int32, (c, groups), 1)
    return (ch // cg == gi).astype(jnp.float32)


def _gn_stats_kernel(x_ref, o_ref, *, groups: int, hw: int, block_hw: int):
    i = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)  # [block_hw, C]
    c = x.shape[1]
    valid = (i * block_hw
             + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)) < hw
    x = jnp.where(valid, x, 0.0)
    member = _member_mask(c, groups)
    # HIGHEST precision: tiny [1,C]x[C,G] matmuls, but bf16 MXU rounding
    # here would corrupt the statistics.
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    colsum = jnp.sum(x, axis=0, keepdims=True)            # [1, C]
    gsum = dot(colsum, member, (((1,), (0,)), ((), ())))  # [1, G]
    # Shifted second moment: accumulate sum((x - block_mean)^2) instead of
    # sum(x^2), so large-mean activations don't cancel catastrophically in
    # the E[x^2]-E[x]^2 finalize (blocks are Welford-merged there).
    nb = jnp.minimum(block_hw, hw - i * block_hw).astype(jnp.float32)
    mean_g = gsum / (nb * (c // groups))                   # [1, G]
    mean_c = dot(mean_g, member, (((1,), (1,)), ((), ()))) # [1, C]
    xc = jnp.where(valid, x - mean_c, 0.0)
    colsq = jnp.sum(xc * xc, axis=0, keepdims=True)        # [1, C]
    gsq = dot(colsq, member, (((1,), (0,)), ((), ())))     # [1, G]
    o_ref[0, 0] = jnp.concatenate([gsum, gsq], axis=0)     # [2, G]


def _gn_norm_kernel(x_ref, mean_ref, rstd_ref, scale_ref, bias_ref, o_ref, *,
                    apply_silu: bool):
    x = x_ref[0].astype(jnp.float32)  # [block_hw, C]
    out = (x - mean_ref[0].astype(jnp.float32)) \
        * rstd_ref[0].astype(jnp.float32)
    out = out * scale_ref[...].astype(jnp.float32) \
        + bias_ref[...].astype(jnp.float32)
    if apply_silu:
        out = out * jax.nn.sigmoid(out)
    o_ref[0] = out.astype(o_ref.dtype)


def _bwd_dy(x, g, mean, rstd, scale, bias, apply_silu: bool):
    """(xhat, dy, dxhat) from loaded f32 blocks — the ONE copy of the
    normalize + SiLU-derivative recompute shared by both backward
    kernels (they must stay byte-identical or the stats pass and the
    dx pass silently disagree)."""
    xhat = (x - mean) * rstd
    if apply_silu:
        y = xhat * scale + bias
        sig = jax.nn.sigmoid(y)
        dy = g * sig * (1.0 + y * (1.0 - sig))
    else:
        dy = g
    return xhat, dy, dy * scale


def _gn_bwd_stats_kernel(x_ref, g_ref, mean_ref, rstd_ref, scale_ref,
                         bias_ref, gsums_ref, csums_ref, *,
                         groups: int, hw: int, block_hw: int,
                         apply_silu: bool):
    """Per-(sample, hw-block) backward partials in one read of (x, g):
    group sums of (dxhat, dxhat*xhat) for the dx correction terms and
    per-channel sums of (dy, dy*xhat) for dbias/dscale."""
    i = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)             # [block_hw, C]
    g = g_ref[0].astype(jnp.float32)
    c = x.shape[1]
    valid = (i * block_hw
             + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)) < hw
    x = jnp.where(valid, x, 0.0)
    g = jnp.where(valid, g, 0.0)

    mean = mean_ref[0].astype(jnp.float32)       # [1, C]
    rstd = rstd_ref[0].astype(jnp.float32)
    scale = scale_ref[...].astype(jnp.float32)
    bias = bias_ref[...].astype(jnp.float32)

    xhat, dy, dxhat = _bwd_dy(x, g, mean, rstd, scale, bias, apply_silu)

    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    member = _member_mask(c, groups)
    s1_c = jnp.sum(dxhat, axis=0, keepdims=True)           # [1, C]
    s2_c = jnp.sum(dxhat * xhat, axis=0, keepdims=True)    # [1, C]
    gsums_ref[0, 0] = jnp.concatenate(
        [dot(s1_c, member, (((1,), (0,)), ((), ()))),
         dot(s2_c, member, (((1,), (0,)), ((), ())))], axis=0)   # [2, G]
    csums_ref[0, 0] = jnp.concatenate(
        [jnp.sum(dy, axis=0, keepdims=True),
         jnp.sum(dy * xhat, axis=0, keepdims=True)], axis=0)     # [2, C]


def _gn_bwd_dx_kernel(x_ref, g_ref, mean_ref, rstd_ref, scale_ref,
                      bias_ref, s1_ref, s2_ref, dx_ref, *,
                      apply_silu: bool):
    """dx = rstd * (dxhat - mean_S(dxhat) - xhat * mean_S(dxhat*xhat))
    per block; the mean_S terms arrive per-channel-broadcast from the
    XLA finalize."""
    x = x_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    mean = mean_ref[0].astype(jnp.float32)
    rstd = rstd_ref[0].astype(jnp.float32)
    scale = scale_ref[...].astype(jnp.float32)
    bias = bias_ref[...].astype(jnp.float32)

    xhat, _dy, dxhat = _bwd_dy(x, g, mean, rstd, scale, bias, apply_silu)
    dx = rstd * (dxhat - s1_ref[0].astype(jnp.float32)
                 - xhat * s2_ref[0].astype(jnp.float32))
    dx_ref[0] = dx.astype(dx_ref.dtype)


def _pallas_gn_silu_bwd(x, scale, bias, mean_c, rstd_c, g, groups,
                        apply_silu, interpret):
    """Dedicated Pallas backward (VERDICT r4 #3): two tiled passes over
    (x, g) — partial sums, XLA finalize (O(B*G + C)), then dx — instead
    of re-running the whole forward chain through XLA autodiff. Returns
    (dx, dscale, dbias)."""
    orig_shape = x.shape
    b, c = x.shape[0], x.shape[-1]
    xr = x.reshape(b, -1, c)
    gr = g.reshape(b, -1, c)
    hw = xr.shape[1]
    # half the forward's block rows: these kernels stream TWO block-size
    # inputs (x and g) plus the xhat/y/sigmoid/dy temporaries, so the
    # forward's sizing would roughly double live VMEM
    blk = max(8, (_block_hw(hw, c) // 2) // 8 * 8)
    blk = min(blk, max(8, (hw // 8) * 8)) if hw >= 8 else 8
    nblk = pl.cdiv(hw, blk)
    cg = c // groups

    gsums, csums = pl.pallas_call(
        functools.partial(_gn_bwd_stats_kernel, groups=groups, hw=hw,
                          block_hw=blk, apply_silu=apply_silu),
        name="fdt_gn_silu_bwd_sums",
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, c), lambda i, j: (0, 0)),
            pl.BlockSpec((1, c), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 2, groups), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 2, c), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nblk, 2, groups), jnp.float32),
            jax.ShapeDtypeStruct((b, nblk, 2, c), jnp.float32),
        ],
        interpret=interpret,
    )(xr, gr, mean_c, rstd_c, scale.reshape(1, c), bias.reshape(1, c))

    # XLA finalize: merge blocks, normalize the group means, broadcast
    # back to per-channel [B, 1, C] for the dx pass.
    n = float(hw * cg)
    s1_g = jnp.sum(gsums[:, :, 0], axis=1) / n        # [B, G]
    s2_g = jnp.sum(gsums[:, :, 1], axis=1) / n
    s1_c = jnp.repeat(s1_g, cg, axis=-1)[:, None, :]  # [B, 1, C]
    s2_c = jnp.repeat(s2_g, cg, axis=-1)[:, None, :]
    dbias = jnp.sum(csums[:, :, 0], axis=(0, 1)).astype(bias.dtype)
    dscale = jnp.sum(csums[:, :, 1], axis=(0, 1)).astype(scale.dtype)

    dx = pl.pallas_call(
        functools.partial(_gn_bwd_dx_kernel, apply_silu=apply_silu),
        name="fdt_gn_silu_bwd_dx",
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, c), lambda i, j: (0, 0)),
            pl.BlockSpec((1, c), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hw, c), x.dtype),
        interpret=interpret,
    )(xr, gr, mean_c, rstd_c, scale.reshape(1, c), bias.reshape(1, c),
      s1_c, s2_c)
    return dx.reshape(orig_shape), dscale, dbias


def _xla_groupnorm_silu(x, scale, bias, groups, eps, apply_silu):
    b = x.shape[0]
    c = x.shape[-1]
    xf = x.astype(jnp.float32).reshape(b, -1, groups, c // groups)
    mean = jnp.mean(xf, axis=(1, 3), keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=(1, 3), keepdims=True)
    xn = ((xf - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    out = xn * scale + bias
    if apply_silu:
        out = jax.nn.silu(out)
    return out.astype(x.dtype)


def _impl_stats(x: jax.Array, scale: jax.Array, bias: jax.Array,
                groups: int, eps: float, apply_silu: bool,
                interpret: bool, force_pallas: bool):
    """(out, mean_c, rstd_c) — stats are None on the XLA fallback paths
    (their backward recomputes through XLA autodiff; the Pallas
    backward needs the saved stats)."""
    c = x.shape[-1]
    assert c % groups == 0, f"channels {c} not divisible by groups {groups}"
    orig_shape = x.shape
    b = x.shape[0]

    run_pallas, interpret = _use_pallas(interpret, force_pallas)
    if not run_pallas or _batch_fills_sublanes(x.shape):
        return (_xla_groupnorm_silu(x, scale, bias, groups, eps,
                                    apply_silu), None, None)

    xr = x.reshape(b, -1, c)
    hw = xr.shape[1]
    blk = _block_hw(hw, c)
    nblk = pl.cdiv(hw, blk)

    # Pass 1: per-block partial group sums -> [B, nblk, 2, G].
    sums = pl.pallas_call(
        functools.partial(_gn_stats_kernel, groups=groups, hw=hw,
                          block_hw=blk),
        name="fdt_gn_silu_stats",
        grid=(b, nblk),
        in_specs=[pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((1, 1, 2, groups), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nblk, 2, groups), jnp.float32),
        interpret=interpret,
    )(xr)

    # Finalize on XLA (O(B*G)): Welford merge of the per-block
    # (sum, shifted-M2) pairs — var stays stable for large-mean inputs.
    cg = c // groups
    n_rows = jnp.minimum(blk, hw - blk * jnp.arange(nblk)).astype(jnp.float32)
    n_b = n_rows[None, :, None] * cg            # [1, nblk, 1] counts
    n = float(hw * cg)
    gsum_b = sums[:, :, 0]                      # [B, nblk, G]
    m2_b = sums[:, :, 1]                        # [B, nblk, G]
    mean_g = jnp.sum(gsum_b, axis=1) / n        # [B, G]
    mean_b = gsum_b / n_b
    m2 = jnp.sum(m2_b + n_b * (mean_b - mean_g[:, None, :]) ** 2, axis=1)
    var_g = m2 / n
    rstd_g = jax.lax.rsqrt(jnp.maximum(var_g, 0.0) + eps)
    # [B, 1, C] so the per-sample block equals the array in the minor two
    # dims (Pallas TPU block-shape rule).
    mean_c = jnp.repeat(mean_g, c // groups, axis=-1)[:, None, :]
    rstd_c = jnp.repeat(rstd_g, c // groups, axis=-1)[:, None, :]

    # Pass 2: normalize + affine + SiLU per block.
    out = pl.pallas_call(
        functools.partial(_gn_norm_kernel, apply_silu=apply_silu),
        name="fdt_gn_silu_apply",
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, c), lambda i, j: (0, 0)),
            pl.BlockSpec((1, c), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk, c), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hw, c), x.dtype),
        interpret=interpret,
    )(xr, mean_c, rstd_c, scale.reshape(1, c), bias.reshape(1, c))
    return out.reshape(orig_shape), mean_c, rstd_c


def _impl(x: jax.Array, scale: jax.Array, bias: jax.Array,
          groups: int, eps: float, apply_silu: bool,
          interpret: bool, force_pallas: bool) -> jax.Array:
    return _impl_stats(x, scale, bias, groups, eps, apply_silu,
                       interpret, force_pallas)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_gn_silu(x, scale, bias, groups, eps, apply_silu, interpret,
                   force_pallas):
    return _impl(x, scale, bias, groups, eps, apply_silu, interpret,
                 force_pallas)


def _gn_fwd(x, scale, bias, groups, eps, apply_silu, interpret, force_pallas):
    out, mean_c, rstd_c = _impl_stats(x, scale, bias, groups, eps,
                                      apply_silu, interpret, force_pallas)
    return out, (x, scale, bias, mean_c, rstd_c)


def _gn_bwd(groups, eps, apply_silu, interpret, force_pallas, res, g):
    # Pallas-path backward: dedicated tiled kernels reusing the saved
    # per-group stats (VERDICT r4 #3) — two passes over (x, g) instead
    # of XLA re-deriving the whole forward chain (which recomputes the
    # statistics reduction as well). XLA-path forwards (no saved stats)
    # keep the recompute-through-autodiff backward.
    x, scale, bias, mean_c, rstd_c = res
    if mean_c is not None:
        # the env interpret hook must reach the backward too — a fwd
        # that ran interpreted would otherwise hand Mosaic a CPU build
        if _fused_norm_interpret():
            interpret = True
        return _pallas_gn_silu_bwd(x, scale, bias, mean_c, rstd_c, g,
                                   groups, apply_silu, interpret)
    _, vjp = jax.vjp(
        lambda x_, s_, b_: _xla_groupnorm_silu(x_, s_, b_, groups, eps,
                                               apply_silu), x, scale, bias)
    return vjp(g)


_fused_gn_silu.defvjp(_gn_fwd, _gn_bwd)


def fused_groupnorm_silu(x: jax.Array, scale: jax.Array, bias: jax.Array,
                         groups: int = 8, eps: float = 1e-6,
                         apply_silu: bool = True,
                         interpret: bool = False,
                         force_pallas: bool = False) -> jax.Array:
    """x: [B, H, W, C] (or [B, L, C]); scale/bias: [C]. Differentiable.
    Under an active multi-device mesh the kernels run on each device's
    batch shard (parallel/context.py per_device_over_batch)."""
    def fused(x_, s_, b_):
        return _fused_gn_silu(x_, s_, b_, groups, eps, apply_silu,
                              interpret, force_pallas)

    if not _use_pallas(interpret, force_pallas)[0]:
        return fused(x, scale, bias)    # the XLA composition: GSPMD's
    from ..parallel.context import per_device_over_batch
    return per_device_over_batch(
        fused, (x, scale, bias), (True, False, False),
        functools.partial(_xla_groupnorm_silu, groups=groups, eps=eps,
                          apply_silu=apply_silu))
