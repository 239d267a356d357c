"""Rematerialization knobs: remat=True must be numerically transparent
(same params, same outputs, same grads) while checkpointing block
activations — the standard TPU HBM lever."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _check_equivalent(make_model, args, rng):
    base = make_model(remat=False)
    rem = make_model(remat=True)
    params = jax.jit(base.init)(jax.random.PRNGKey(0), *args)
    # identical parameter structure: remat is transparent to checkpoints
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(
                jax.eval_shape(rem.init, jax.random.PRNGKey(0), *args)))

    def out_and_grad(model):
        def fn(p):
            out = model.apply(p, *args)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        return jax.jit(jax.grad(fn, has_aux=True))

    # Each side is ONE compiled program (forward and backward): the
    # comparison is between two XLA programs that may fuse differently,
    # no longer between two runs of the same per-primitive kernels.
    g_a, out_a = out_and_grad(base)(params)
    g_b, out_b = out_and_grad(rem)(params)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               atol=1e-6, rtol=1e-6)
    for (pa, la), (pb, lb) in zip(
            jax.tree_util.tree_leaves_with_path(g_a),
            jax.tree_util.tree_leaves_with_path(g_b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=1e-5, rtol=1e-5, err_msg=str(pa))


def test_unet_remat_equivalent(rng):
    from flaxdiff_tpu.models.unet import Unet

    def make(remat):
        return Unet(output_channels=3, emb_features=16,
                    feature_depths=(8, 16),
                    attention_configs=(None, {"heads": 2, "dim_head": 8}),
                    num_res_blocks=1, norm_groups=4, remat=remat)

    x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
    t = jnp.zeros((2,))
    ctx = jnp.asarray(rng.normal(size=(2, 4, 16)), jnp.float32)
    _check_equivalent(make, (x, t, ctx), rng)


def test_dit_remat_equivalent(rng):
    from flaxdiff_tpu.models.dit import SimpleDiT

    def make(remat):
        return SimpleDiT(patch_size=2, emb_features=32, num_layers=2,
                         num_heads=2, output_channels=3, remat=remat)

    x = jnp.asarray(rng.normal(size=(2, 8, 8, 3)), jnp.float32)
    t = jnp.zeros((2,))
    ctx = jnp.asarray(rng.normal(size=(2, 4, 32)), jnp.float32)
    _check_equivalent(make, (x, t, ctx), rng)


def test_unet3d_remat_equivalent(rng):
    from flaxdiff_tpu.models.unet3d import UNet3D

    def make(remat):
        return UNet3D(output_channels=3, emb_features=16,
                      feature_depths=(8,), attention_levels=(True,),
                      heads=2, num_res_blocks=1, norm_groups=4,
                      remat=remat)

    x = jnp.asarray(rng.normal(size=(2, 4, 8, 8, 3)), jnp.float32)
    t = jnp.zeros((2,))
    ctx = jnp.asarray(rng.normal(size=(2, 4, 16)), jnp.float32)
    _check_equivalent(make, (x, t, ctx), rng)
