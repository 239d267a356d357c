"""Tests for the autoencoder layer: ABC video flattening, KL VAE."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.models.autoencoder import (
    IdentityAutoEncoder,
    KLAutoEncoder,
    gaussian_sample,
    kl_divergence,
)


@pytest.fixture(scope="module")
def vae():
    return KLAutoEncoder.create(
        jax.random.PRNGKey(0), input_channels=3, image_size=16,
        latent_channels=2, block_channels=(8, 16), layers_per_block=1,
        norm_groups=4)


def test_identity_ae_roundtrip(rng):
    ae = IdentityAutoEncoder()
    x = jnp.asarray(rng.normal(size=(2, 8, 8, 3)), jnp.float32)
    np.testing.assert_array_equal(np.asarray(ae(x)), np.asarray(x))
    assert ae.downscale_factor == 1


def test_kl_vae_shapes(vae, rng):
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
    z = vae.encode(x)
    assert vae.downscale_factor == 2
    assert z.shape == (2, 8, 8, 2)
    y = vae.decode(z)
    assert y.shape == x.shape


def test_kl_vae_video_flattening(vae, rng):
    x = jnp.asarray(rng.normal(size=(2, 3, 16, 16, 3)), jnp.float32)  # video
    z = vae.encode(x)
    assert z.shape == (2, 3, 8, 8, 2)
    y = vae.decode(z)
    assert y.shape == x.shape
    # Video path must equal per-frame processing.
    z_frame = vae.encode(x[:, 0])
    np.testing.assert_allclose(np.asarray(z[:, 0]), np.asarray(z_frame),
                               rtol=1e-5, atol=1e-5)


def test_kl_vae_stochastic_vs_mean(vae, rng):
    x = jnp.asarray(rng.normal(size=(1, 16, 16, 3)), jnp.float32)
    z_mean = vae.encode(x)
    z_a = vae.encode(x, key=jax.random.PRNGKey(1))
    z_b = vae.encode(x, key=jax.random.PRNGKey(2))
    assert not np.allclose(np.asarray(z_a), np.asarray(z_b))
    # Mean encode is deterministic.
    np.testing.assert_array_equal(np.asarray(z_mean),
                                  np.asarray(vae.encode(x)))


def test_gaussian_sample_and_kl():
    moments = jnp.concatenate([jnp.zeros((2, 4, 4, 2)),
                               jnp.zeros((2, 4, 4, 2))], axis=-1)
    # zero mean, zero logvar -> KL = 0
    np.testing.assert_allclose(np.asarray(kl_divergence(moments)), 0.0)
    s = gaussian_sample(moments, None)
    np.testing.assert_array_equal(np.asarray(s), 0.0)
    s2 = gaussian_sample(moments, jax.random.PRNGKey(0))
    assert np.std(np.asarray(s2)) > 0.5  # unit-variance samples


def test_kl_vae_trains_one_step(vae, rng):
    """One gradient step on recon+KL decreases loss on the same batch."""
    import optax
    x = jnp.asarray(rng.normal(size=(4, 16, 16, 3)), jnp.float32)

    def loss_fn(params):
        moments = vae.encoder.apply({"params": params["encoder"]}, x)
        z = gaussian_sample(moments, jax.random.PRNGKey(0))
        y = vae.decoder.apply({"params": params["decoder"]}, z)
        return jnp.mean((y - x) ** 2) + 1e-4 * jnp.mean(kl_divergence(moments))

    tx = optax.adam(1e-3)
    params = vae.params
    opt_state = tx.init(params)
    loss_and_grad = jax.jit(jax.value_and_grad(loss_fn))
    l0, g = loss_and_grad(params)
    for _ in range(5):
        updates, opt_state = tx.update(g, opt_state)
        params = optax.apply_updates(params, updates)
        l1, g = loss_and_grad(params)
    assert float(l1) < float(l0)


def test_serialize(vae):
    cfg = vae.serialize()
    assert cfg["latent_channels"] == 2 and cfg["block_channels"] == [8, 16]
