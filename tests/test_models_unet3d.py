"""Tests for the 3D video UNet: temporal layers + full model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.models.unet3d import (
    TemporalAttention,
    TemporalConvLayer,
    UNet3D,
)

TINY = dict(output_channels=3, emb_features=32, feature_depths=(8, 16),
            attention_levels=(False, True), num_res_blocks=1, heads=2,
            norm_groups=4)


def test_temporal_conv_identity_at_init(rng):
    layer = TemporalConvLayer(features=8, norm_groups=4)
    x = jnp.asarray(rng.normal(size=(2 * 3, 4, 4, 8)), jnp.float32)  # B=2,F=3
    params = jax.jit(layer.init, static_argnums=2)(
        jax.random.PRNGKey(0), x, 3)
    out = jax.jit(layer.apply, static_argnums=2)(params, x, 3)
    # zero-init final conv -> exact identity at init
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-6)


def test_temporal_conv_mixes_frames_after_perturbation(rng):
    layer = TemporalConvLayer(features=8, norm_groups=4)
    x = jnp.asarray(rng.normal(size=(3, 4, 4, 8)), jnp.float32)  # B=1,F=3
    params = jax.jit(layer.init, static_argnums=2)(
        jax.random.PRNGKey(0), x, 3)
    # Nudge the zero conv so the temporal path is active.
    params = jax.tree_util.tree_map(
        lambda a: a + 0.05 if a.ndim == 5 else a, params)
    apply = jax.jit(layer.apply, static_argnums=2)
    y1 = np.asarray(apply(params, x, 3))
    x2 = x.at[2].add(10.0)  # change the last frame only
    y2 = np.asarray(apply(params, x2, 3))
    # middle frame output must change: temporal kernel spans adjacent frames
    assert not np.allclose(y1[1], y2[1])


def test_temporal_attention_identity_at_init(rng):
    layer = TemporalAttention(features=8, heads=2, norm_groups=4)
    x = jnp.asarray(rng.normal(size=(2 * 3, 4, 4, 8)), jnp.float32)
    params = jax.jit(layer.init, static_argnums=2)(
        jax.random.PRNGKey(0), x, 3)
    out = jax.jit(layer.apply, static_argnums=2)(params, x, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-6)


def test_unet3d_forward_shape(rng):
    model = UNet3D(**TINY)
    x = jnp.asarray(rng.normal(size=(2, 3, 8, 8, 3)), jnp.float32)
    t = jnp.asarray([0.1, 0.9], jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(2, 5, 16)), jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, ctx)
    out = jax.jit(model.apply)(params, x, t, ctx)
    assert out.shape == x.shape
    np.testing.assert_array_equal(np.asarray(out), 0.0)  # zero-init head


@pytest.fixture(scope="module")
def no_text():
    """The text-free TINY model, its one-clip input and its params,
    built once for the three tests below."""
    model = UNet3D(**TINY)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 2, 8, 8, 3)),
                    jnp.float32)
    t = jnp.asarray([0.5], jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, None)
    return model, params, x, t


def test_unet3d_no_text(no_text):
    model, params, x, t = no_text
    assert jax.jit(model.apply)(params, x, t, None).shape == x.shape


def test_unet3d_controlnet_residual_hooks(no_text):
    model, params, x, t = no_text
    apply = jax.jit(model.apply)

    # Trace once to learn the skip structure by feeding wrong count -> error
    with pytest.raises(ValueError):
        apply(params, x, t, None,
              down_block_additional_residuals=(jnp.zeros((1,)),))

    # Correct count: num_levels*num_res_blocks + (num_levels-1) downsamples + conv_in
    n_skips = 2 * 1 + 1 + 1
    zeros = tuple(jnp.zeros((1,)) for _ in range(n_skips))
    # zero residuals = unchanged output (broadcasting zeros is fine)
    out_plain = apply(params, x, t, None)
    out_hooked = apply(params, x, t, None,
                       down_block_additional_residuals=zeros,
                       mid_block_additional_residual=jnp.zeros((1,)))
    np.testing.assert_allclose(np.asarray(out_plain), np.asarray(out_hooked),
                               atol=1e-6)


def test_unet3d_grad(no_text):
    model, params, x, t = no_text

    def loss(p):
        return jnp.mean(model.apply(p, x, t, None) ** 2)

    g = jax.jit(jax.grad(loss))(params)
    assert all(np.all(np.isfinite(np.asarray(l)))
               for l in jax.tree_util.tree_leaves(g))
