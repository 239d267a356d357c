"""Pallas kernel correctness vs XLA references (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.ops.attention import _xla_attention
from flaxdiff_tpu.ops.flash_attention import flash_attention
from flaxdiff_tpu.ops.fused_norm import _xla_groupnorm_silu, fused_groupnorm_silu


@pytest.mark.parametrize("lq,lk", [(128, 128), (256, 77), (100, 100)])
def test_flash_attention_matches_xla(lq, lk):
    key = jax.random.PRNGKey(0)
    b, h, d = 2, 2, 32
    q = jax.random.normal(key, (b, lq, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, lk, h, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, lk, h, d))
    out_flash = flash_attention(q, k, v, None, 64, 64, True)
    out_ref = _xla_attention(q, k, v)
    np.testing.assert_allclose(out_flash, out_ref, rtol=2e-3, atol=2e-3)


def test_flash_attention_grad_matches_xla():
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 64, 2, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 64, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 64, 2, 16))

    g_flash = jax.grad(lambda q_: jnp.sum(
        flash_attention(q_, k, v, None, 32, 32, True) ** 2))(q)
    g_ref = jax.grad(lambda q_: jnp.sum(_xla_attention(q_, k, v) ** 2))(q)
    np.testing.assert_allclose(g_flash, g_ref, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("lq,lk", [(128, 128), (200, 77)])
def test_flash_attention_all_grads_match_xla(lq, lk):
    """dq/dk/dv from the Pallas backward kernels vs the XLA VJP, including
    the cross-attention shape (padded kv with masked tail)."""
    key = jax.random.PRNGKey(11)
    b, h, d = 2, 2, 32
    q = jax.random.normal(key, (b, lq, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, lk, h, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, lk, h, d))
    g = jax.random.normal(jax.random.fold_in(key, 3), (b, lq, h, d))

    def loss(fn):
        return lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_) * g)

    flash = lambda q_, k_, v_: flash_attention(q_, k_, v_, None, 64, 64, True)
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(_xla_attention), (0, 1, 2))(q, k, v)
    for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b_, rtol=2e-3, atol=2e-3, err_msg=name)


def test_flash_attention_long_sequence_grad():
    """VERDICT r1 #2 done-criterion: gradients vs XLA at >= 8k tokens in
    interpret mode (blockwise backward, no [L, L] materialization)."""
    key = jax.random.PRNGKey(5)
    b, l, h, d = 1, 8192, 1, 64
    q = jax.random.normal(key, (b, l, h, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, l, h, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, l, h, d))
    g = jax.random.normal(jax.random.fold_in(key, 3), (b, l, h, d))

    flash = lambda q_, k_, v_: flash_attention(q_, k_, v_, None, 1024, 1024,
                                               True)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(flash(*a) * g),
                           (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(_xla_attention(*a) * g),
                            (0, 1, 2)))(q, k, v)
    for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b_, rtol=5e-3, atol=5e-3, err_msg=name)


def test_flash_attention_native_head_dim_hw_lanes(monkeypatch):
    """Native sub-128 head_dim with the HARDWARE 128-lane scratch layout
    (interpret mode normally shrinks lanes to 1, which is why the
    (128, 64)x(128, 0) broadcast bug in _bcast only surfaced on a real
    chip). Forward and all grads vs XLA at d=64 with full-width
    lane-replicated scratch."""
    from flaxdiff_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_FORCE_LANES", fa.LANES)
    key = jax.random.PRNGKey(7)
    b, l, h, d = 1, 256, 2, 64
    q = jax.random.normal(key, (b, l, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, l, h, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, l, h, d))
    g = jax.random.normal(jax.random.fold_in(key, 3), (b, l, h, d))

    flash = lambda q_, k_, v_: flash_attention(q_, k_, v_, None, 128, 128,
                                               True)
    np.testing.assert_allclose(flash(q, k, v), _xla_attention(q, k, v),
                               rtol=2e-3, atol=2e-3)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_xla_attention(*a) * g),
                    (0, 1, 2))(q, k, v)
    for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b_, rtol=2e-3, atol=2e-3, err_msg=name)


@pytest.mark.parametrize("d,lq,lk,dtype,bq,bk", [
    # sublane-minimum head dim, default sequence-capped blocks
    (8, 256, 256, "float32", None, None),
    # the flagship native shape (d=64) as CROSS-attention with a masked
    # kv tail, bf16 — the dtype the models run
    (64, 256, 77, "bfloat16", None, None),
    # d=64 self-attention at the DEFAULT 512x1024 blocks that
    # lowering failure ran with (multi-block q at a padded tail)
    (64, 300, 300, "float32", 128, 256),
])
def test_flash_attention_native_d_matrix(monkeypatch, d, lq, lk, dtype,
                                         bq, bk):
    """Native sub-128 head dims across the configs attnpad/flashtune
    will run on hardware, under the FORCED 128-lane scratch layout
    (ops/flash_attention.py _FORCE_LANES — the layout where the r3
    `(128, 64) x (128, 0)` _bcast bug lived). Guards the fix so the
    next TPU window can finally record flash_native_d64_ms."""
    from flaxdiff_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_FORCE_LANES", fa.LANES)
    jdt = jnp.dtype(dtype)
    key = jax.random.PRNGKey(13)
    q = jax.random.normal(key, (1, lq, 2, d), jdt)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, lk, 2, d), jdt)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, lk, 2, d), jdt)
    g = jax.random.normal(jax.random.fold_in(key, 3), (1, lq, 2, d), jdt)

    flash = lambda q_, k_, v_: flash_attention(q_, k_, v_, None, bq, bk,
                                               True)
    tol = 6e-2 if jdt == jnp.bfloat16 else 5e-3
    got = flash(q, k, v).astype(jnp.float32)
    want = _xla_attention(q, k, v).astype(jnp.float32)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    gf32 = g.astype(jnp.float32)
    dq = jax.grad(lambda q_: jnp.sum(
        flash(q_, k, v).astype(jnp.float32) * gf32))(q)
    dq_ref = jax.grad(lambda q_: jnp.sum(
        _xla_attention(q_, k, v).astype(jnp.float32) * gf32))(q)
    np.testing.assert_allclose(dq.astype(jnp.float32),
                               dq_ref.astype(jnp.float32),
                               rtol=tol * 4, atol=tol * 4)


@pytest.mark.parametrize("apply_silu", [True, False])
def test_fused_groupnorm_silu_matches_xla(apply_silu):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 8, 8, 32))
    scale = jax.random.normal(jax.random.fold_in(key, 1), (32,)) * 0.1 + 1.0
    bias = jax.random.normal(jax.random.fold_in(key, 2), (32,)) * 0.1
    out_pallas = fused_groupnorm_silu(x, scale, bias, groups=8,
                                      apply_silu=apply_silu, interpret=True,
                                      force_pallas=True)
    out_ref = _xla_groupnorm_silu(x, scale, bias, 8, 1e-5, apply_silu)
    np.testing.assert_allclose(out_pallas, out_ref, rtol=1e-4, atol=1e-4)


def test_fused_groupnorm_matches_flax_groupnorm():
    import flax.linen as nn
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 16))
    gn = nn.GroupNorm(num_groups=4)
    params = jax.jit(gn.init)(jax.random.PRNGKey(1), x)
    ref = jax.nn.silu(gn.apply(params, x))
    out = fused_groupnorm_silu(
        x, params["params"]["scale"], params["params"]["bias"], groups=4,
        interpret=True, force_pallas=True)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_fused_groupnorm_multiblock_partial(monkeypatch):
    """Force nblk > 1 with a non-multiple-of-8 hw: exercises the row mask,
    per-block partial sums, and the Welford merge in the finalize."""
    import flaxdiff_tpu.ops.fused_norm as fn
    monkeypatch.setattr(fn, "_BLOCK_BYTES", 8 * 16 * 4)  # 8-row blocks
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (2, 10, 10, 16))  # hw=100: 13 blocks, last partial
    scale = jnp.ones((16,))
    bias = jnp.zeros((16,))
    out = fn.fused_groupnorm_silu(x, scale, bias, groups=4, interpret=True,
                                  force_pallas=True)
    ref = fn._xla_groupnorm_silu(x, scale, bias, 4, 1e-5, True)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_fused_groupnorm_large_mean_stable(monkeypatch):
    """Large-mean activations: one-pass E[x^2]-E[x]^2 would cancel; the
    shifted per-block second moment must not."""
    import flaxdiff_tpu.ops.fused_norm as fn
    monkeypatch.setattr(fn, "_BLOCK_BYTES", 8 * 16 * 4)
    key = jax.random.PRNGKey(8)
    x = 1000.0 + jax.random.normal(key, (1, 16, 16, 16)) * 0.1
    scale = jnp.ones((16,))
    bias = jnp.zeros((16,))
    out = fn.fused_groupnorm_silu(x, scale, bias, groups=4, interpret=True,
                                  force_pallas=True)
    ref = fn._xla_groupnorm_silu(
        x.astype(jnp.float64) if jax.config.jax_enable_x64 else x,
        scale, bias, 4, 1e-5, True)
    np.testing.assert_allclose(out, ref, rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("apply_silu", [True, False])
def test_fused_groupnorm_pallas_backward_matches_xla(apply_silu):
    """The dedicated Pallas backward (r5: stats pass + finalize + dx
    pass reusing saved mean/rstd) must match XLA autodiff of the
    reference chain for dx, dscale, AND dbias."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2, 8, 8, 32))
    scale = jax.random.normal(jax.random.fold_in(key, 1), (32,)) * 0.1 + 1.0
    bias = jax.random.normal(jax.random.fold_in(key, 2), (32,)) * 0.1

    def loss_pallas(x, s, b):
        return jnp.sum(fused_groupnorm_silu(
            x, s, b, groups=8, apply_silu=apply_silu, interpret=True,
            force_pallas=True) ** 2)

    def loss_ref(x, s, b):
        return jnp.sum(_xla_groupnorm_silu(
            x, s, b, 8, 1e-6, apply_silu) ** 2)

    g_p = jax.grad(loss_pallas, argnums=(0, 1, 2))(x, scale, bias)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(x, scale, bias)
    for a, b_, name in zip(g_p, g_r, ("dx", "dscale", "dbias")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3, err_msg=name)


def test_fused_groupnorm_pallas_backward_multiblock(monkeypatch):
    """Grad correctness when hw spans multiple blocks with a partial
    tail — the backward stats pass has its own row mask + block merge."""
    import flaxdiff_tpu.ops.fused_norm as fn
    monkeypatch.setattr(fn, "_BLOCK_BYTES", 8 * 16 * 4)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 10, 10, 16))
    scale = jnp.ones((16,)) * 1.3
    bias = jnp.ones((16,)) * 0.2

    def loss_pallas(x):
        return jnp.sum(fn.fused_groupnorm_silu(
            x, scale, bias, groups=4, interpret=True,
            force_pallas=True) ** 3)

    def loss_xla(x):
        return jnp.sum(fn._xla_groupnorm_silu(
            x, scale, bias, 4, 1e-6, True) ** 3)

    g_pallas = jax.grad(loss_pallas)(x)
    g_xla = jax.grad(loss_xla)(x)
    np.testing.assert_allclose(np.asarray(g_pallas), np.asarray(g_xla),
                               rtol=2e-3, atol=2e-3)


def _gn_case(b, side, c, mean=0.0):
    key = jax.random.PRNGKey(11)
    x = mean + jax.random.normal(key, (b, side, side, c))
    scale = jax.random.normal(jax.random.fold_in(key, 1), (c,)) * 0.1 + 1.0
    bias = jax.random.normal(jax.random.fold_in(key, 2), (c,)) * 0.1
    return x, scale, bias


def _gn_forced(x, s, b, apply_silu=True):
    return fused_groupnorm_silu(x, s, b, groups=8, apply_silu=apply_silu,
                                interpret=True, force_pallas=True)


def _gn_kernel_names(*args):
    """Names of the `fdt_gn_silu_*` kernels in the jaxpr of the forward
    and its gradient, Pallas forced as far as the program lets it be."""
    import re
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(_gn_forced(*a)), argnums=(0, 1, 2)))(*args))
    return set(re.findall(r"fdt_gn_silu_\w+", jaxpr))


def _gn_grads(fn_, x, scale, bias):
    return jax.jit(jax.grad(lambda *a: jnp.sum(fn_(*a) ** 2),
                            argnums=(0, 1, 2)))(x, scale, bias)


@pytest.mark.parametrize("apply_silu", [True, False])
@pytest.mark.parametrize("c", [64, 128, 1024])
@pytest.mark.parametrize("b", [16, 32])
def test_fused_groupnorm_sublane_batch_takes_the_xla_composition(
        b, c, apply_silu):
    """Selection is by shape: a batch that fills the bf16 sublane tile
    runs no kernel at all, forward or backward, even with Pallas forced
    (on the chip the composition is the faster program there:
    docs/KERNELS.md), and is the composition to the last bit."""
    x, scale, bias = _gn_case(b, 4, c)
    assert _gn_kernel_names(x, scale, bias) == set()

    def forced(x, s, z):
        return _gn_forced(x, s, z, apply_silu)

    def ref(x, s, z):
        return _xla_groupnorm_silu(x, s, z, 8, 1e-6, apply_silu)

    np.testing.assert_array_equal(jax.jit(forced)(x, scale, bias),
                                  jax.jit(ref)(x, scale, bias))
    for a, r, name in zip(_gn_grads(forced, x, scale, bias),
                          _gn_grads(ref, x, scale, bias),
                          ("dx", "dscale", "dbias")):
        np.testing.assert_allclose(a, r, rtol=1e-6, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("shape", [(16, 16, 16, 16), (16, 256, 16)])
def test_fused_groupnorm_sublane_batch_large_mean_stable(shape):
    """The large-mean case of `test_fused_groupnorm_large_mean_stable` on
    the side of the choice that runs the composition, 4-D and 3-D."""
    x = 1000.0 + jax.random.normal(jax.random.PRNGKey(8), shape) * 0.1
    scale, bias = jnp.ones((16,)), jnp.zeros((16,))
    out = fused_groupnorm_silu(x, scale, bias, groups=4, interpret=True,
                               force_pallas=True)
    xf = np.asarray(x, np.float64).reshape(16, -1, 4, 4)
    want = (xf - xf.mean((1, 3), keepdims=True)) \
        / np.sqrt(xf.var((1, 3), keepdims=True) + 1e-6)
    want = want.reshape(shape)
    want = want / (1.0 + np.exp(-want))
    np.testing.assert_allclose(out, want, rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("b", [1, 8])
def test_fused_groupnorm_small_batch_keeps_the_kernels(b):
    """The other side of the choice: a batch that is no multiple of 16
    (solo and small-batch sampling) runs today's four kernels, forward
    and backward, and they agree with the composition."""
    x, scale, bias = _gn_case(b, 4, 64)
    assert _gn_kernel_names(x, scale, bias) == {
        "fdt_gn_silu_stats", "fdt_gn_silu_apply", "fdt_gn_silu_bwd_sums",
        "fdt_gn_silu_bwd_dx"}

    def ref(x, s, z):
        return _xla_groupnorm_silu(x, s, z, 8, 1e-6, True)

    np.testing.assert_allclose(jax.jit(_gn_forced)(x, scale, bias),
                               jax.jit(ref)(x, scale, bias),
                               rtol=1e-4, atol=1e-4)
    for a, r, name in zip(_gn_grads(_gn_forced, x, scale, bias),
                          _gn_grads(ref, x, scale, bias),
                          ("dx", "dscale", "dbias")):
        np.testing.assert_allclose(a, r, rtol=2e-3, atol=2e-3, err_msg=name)


def test_full_train_step_with_interpreted_kernels(monkeypatch):
    """BOTH kernel families' REAL code paths (flash fwd+bwd, fused-norm
    fwd + the r5 Pallas backward) inside one complete train step on CPU
    via the interpret dispatch hooks — the closest CI gets to the
    on-chip sweep configuration."""
    import flaxdiff_tpu.ops.flash_attention as fa
    import numpy as np
    import optax

    from flaxdiff_tpu.models.unet import Unet
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    monkeypatch.setenv("FLAXDIFF_FLASH_INTERPRET", "1")
    monkeypatch.setenv("FLAXDIFF_FUSED_NORM", "interpret")
    monkeypatch.setattr(fa, "_FORCE_LANES", fa.LANES)

    model = Unet(output_channels=1, emb_features=16,
                 feature_depths=(8,),
                 attention_configs=({"heads": 2, "dim_head": 8,
                                     "backend": "flash"},),
                 num_res_blocks=1, norm_groups=4)

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, 16, 16, 1)),
                          jnp.zeros((1,)))["params"]

    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
        schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(),
        mesh=create_mesh(axes={"data": -1}),
        config=TrainerConfig(uncond_prob=0.0, log_every=100))
    rng = np.random.default_rng(0)
    # ONE step: the interpreter compile dominates (~70 s for two steps
    # on CPU) and a second step only re-covers EMA/rng-fold paths other
    # tests already hold
    batch = {"sample": rng.standard_normal(
        (8, 16, 16, 1)).astype(np.float32)}
    loss = trainer.train_step(trainer.put_batch(batch))
    assert np.isfinite(float(jax.device_get(loss)))


# -- a key mask that is data (ops/dsa.py's selection) ------------------------

def _selection_mask(case, b, l):
    """[B, L, L] bool. `scattered`: a random half of each causal row (no
    tile of the kernel's grid is empty below the diagonal: block skipping
    idle); `blocks`: keys 16..47 read by nobody and keys 64..79 by the
    last queries alone (whole tiles empty, in the middle of a query
    block's row and at its start: block skipping at work); `ragged`: a
    length that is no multiple of a block."""
    causal = jnp.tril(jnp.ones((l, l), bool))
    keep = causal & (jax.random.uniform(jax.random.PRNGKey(9), (b, l, l))
                     < 0.5) | jnp.eye(l, dtype=bool)
    if case == "blocks":
        keep = keep.at[:, :, 16:48].set(False).at[:, :80, 64:80].set(False)
        keep = keep | jnp.eye(l, dtype=bool) & (jnp.arange(l) < 16)[:, None]
        keep = keep.at[:, 16:, 0].set(True)      # no query reads no key
    return keep


@pytest.mark.parametrize("case,l", [("scattered", 96), ("blocks", 96),
                                    ("ragged", 75)])
def test_flash_with_a_data_key_mask_in_interpret_mode(case, l):
    from flaxdiff_tpu.ops.flash_attention import (
        _mask_tiles, _selected_composition, flash_attention_selected)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    # [B, H, L, D] operands, plus a key part every head shares
    q, k, v = (jax.random.normal(ks[i], (2, 3, l, 32)) for i in range(3))
    shared = jax.random.normal(ks[4], (2, l, 32))
    cot = jax.random.normal(ks[3], q.shape)
    keep = _selection_mask(case, 2, l)
    n = -(-l // 16)
    _, counts, fetch = _mask_tiles(keep, n * 16, n * 16, 16, 16)
    counts, fetch = counts.reshape(2, n, n), fetch.reshape(2, n, n)
    assert int(counts.sum()) == int(keep.sum())
    empty_below = int(((counts == 0) & np.tril(np.ones((n, n), bool))).sum())
    assert (empty_below > 0) == (case == "blocks")
    # a skipped tile's copies are those of the nearest tile that is read
    live = np.asarray(counts) > 0
    assert (np.asarray(fetch)[live] == np.broadcast_to(
        np.arange(n), live.shape)[live]).all()
    assert live[np.arange(2)[:, None, None], np.arange(n)[None, :, None],
                np.asarray(fetch)].all()
    if case == "blocks":
        # what a skipped tile holds is never touched: keys nobody reads
        # may hold anything at all
        k = k.at[:, :, 16:48].set(jnp.nan)
        v = v.at[:, :, 16:48].set(jnp.inf)

    def flash(q, k, v, shared):
        return flash_attention_selected(q, k, v, keep, shared, None, 16, 16,
                                        True)

    def xla(q, k, v, shared):
        dead = ~keep.any(axis=1)[:, None, :, None]
        return _selected_composition(q, jnp.where(dead, 0.0, k),
                                     jnp.where(dead, 0.0, v), keep, shared,
                                     None)

    got = jax.jit(flash)(q, k, v, shared)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, jax.jit(xla)(q, k, v, shared), atol=2e-5,
                               rtol=2e-5)
    if case != "blocks":
        # the backward is the composition's, the mask's cotangent nobody's
        g = jax.jit(jax.grad(lambda *a: (flash(*a) * cot).sum(),
                             argnums=(0, 1, 2, 3)))(q, k, v, shared)
        w = jax.jit(jax.grad(lambda *a: (xla(*a) * cot).sum(),
                             argnums=(0, 1, 2, 3)))(q, k, v, shared)
        for a, b in zip(g, w):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_operands_made_at_the_padded_length_need_no_padding():
    """`padded_length`: operands a caller makes at a multiple of the
    blocks go to the kernel as they are; the rows past the mask's length
    are padding nobody reads, and come back zero."""
    from flaxdiff_tpu.ops.attention import attend_selected
    from flaxdiff_tpu.ops.flash_attention import (
        _selected_composition, flash_attention_selected, padded_length)
    assert padded_length(4174) == 4608 and padded_length(150) == 256
    l, lp = 150, padded_length(150)
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k, v = (jax.random.normal(ks[i], (1, 2, lp, 16)) for i in range(3))
    keep = _selection_mask("scattered", 1, l)
    got = flash_attention_selected(q, k, v, keep, None, None, None, None,
                                   True)
    want = _selected_composition(q, k, v, keep, None, None)
    assert got.shape == want.shape == (1, 2, lp, 16)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(got[:, :, l:]).max()) == 0.0
    # off the TPU and with no interpreter the dispatcher composes
    np.testing.assert_allclose(attend_selected(q, k, v, keep), want,
                               atol=1e-6)
    # a causal mask as data is causal attention
    causal = jnp.tril(jnp.ones((1, lp, lp), bool))
    np.testing.assert_allclose(
        attend_selected(q, k, v, causal).transpose(0, 2, 1, 3),
        _xla_attention(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)),
                       causal=True), atol=1e-5)


def test_route_without_bias_and_scale_is_the_call_it_was():
    from flaxdiff_tpu.ops import moe
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    h = jax.random.normal(ks[0], (50, 32))
    w = jax.random.normal(ks[1], (32, 16)) / 5

    def before(h32, router_kernel, top_k, norm_topk_prob=True):
        logits = jnp.dot(h32.astype(jnp.float32),
                         router_kernel.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        vals, idx = jax.lax.top_k(jax.nn.sigmoid(logits), top_k)
        if norm_topk_prob:
            vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), vals

    for norm in (True, False):
        want = jax.jit(lambda: before(h, w, 3, norm))()
        for got in (jax.jit(lambda: moe.route(h, w, 3, norm))(),
                    jax.jit(lambda: moe.route(h, w, 3, norm, None, 1.0))(),
                    jax.jit(lambda: moe.route(
                        h, w, 3, norm, jnp.zeros((16,)), 1.0))()):
            assert (np.asarray(got[0]) == np.asarray(want[0])).all()
            assert (np.asarray(got[1]) == np.asarray(want[1])).all()
    # a bias moves the selection only; the scale the weights only
    bias = jnp.zeros((16,)).at[5].set(10.0)
    idx, vals = moe.route(h, w, 3, True, bias, 2.5)
    assert bool((idx == 5).any(axis=1).all())
    scores = jax.nn.sigmoid(h @ w)
    picked = jnp.take_along_axis(scores, idx, axis=1)
    np.testing.assert_allclose(vals, 2.5 * picked / picked.sum(1)[:, None],
                               rtol=1e-5)


@pytest.mark.parametrize("window", [40, 200])   # binds / never reached
def test_seven_queries_a_kv_head_under_a_window_in_interpret_mode(window):
    """`group` = 7 (a q block of 7 x 32 rows, no power of two of heads)
    at 150 tokens, no multiple of either block (32 / 16), so both
    lengths are padded: against the XLA composition. A window the
    sequence never reaches reads every causal pair."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 150, 14, 16))
    k = jax.random.normal(ks[1], (2, 150, 2, 16))
    v = jax.random.normal(ks[2], (2, 150, 2, 16))
    got = jax.jit(lambda *a: flash_attention(*a, None, 32, 16, True, True,
                                             window))(q, k, v)
    want = jax.jit(lambda *a: _xla_attention(*a, causal=True,
                                             window=window))(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    causal = jax.jit(lambda *a: _xla_attention(*a, causal=True))(q, k, v)
    assert (np.abs(np.asarray(want - causal)).max() > 1e-3) == (window < 150)


def test_a_window_that_binds_has_a_kernel_name_of_its_own():
    """`fdt_flash_fwd_window` on the device where the window is shorter
    than the sequence, `fdt_flash_fwd` otherwise: a trace tells a
    windowed layer's time from a full layer's."""
    q = jax.ShapeDtypeStruct((1, 1024, 7, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1024, 1, 128), jnp.bfloat16)

    def lowered(window):
        return jax.export.export(jax.jit(lambda q, k, v: flash_attention(
            q, k, v, None, None, None, False, True, window)),
            platforms=["tpu"])(q, kv, kv).mlir_module()
    binds, idle, none = lowered(512), lowered(1024), lowered(None)
    assert "fdt_flash_fwd_window" in binds
    for text in (idle, none):
        assert "fdt_flash_fwd" in text and "fdt_flash_fwd_window" not in text
