"""Tests for S5 SSM layers and the hybrid SSM/attention DiT."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.models.ssm import (
    BidirectionalS5Layer,
    HybridSSMAttentionDiT,
    S5Layer,
    SpatialFusionConv,
    SSMDiTBlock,
    build_block_pattern,
)


def test_s5_forward_shape_and_finite(rng):
    layer = S5Layer(features=16, state_dim=8)
    u = jnp.asarray(rng.normal(size=(2, 32, 16)), jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), u)
    y = jax.jit(layer.apply)(params, u)
    assert y.shape == u.shape
    assert np.all(np.isfinite(np.asarray(y)))


def test_s5_matches_sequential_recurrence(rng):
    """Parallel associative scan must equal the naive sequential recurrence."""
    layer = S5Layer(features=4, state_dim=6)
    u = jnp.asarray(rng.normal(size=(1, 10, 4)), jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), u)
    y = np.asarray(jax.jit(layer.apply)(params, u))

    p = params["params"]
    a = -np.exp(np.asarray(p["log_A_real"])) + 1j * np.asarray(p["A_imag"])
    dt = np.exp(np.asarray(p["log_dt"]))
    a_bar = np.exp(a * dt)
    b_bar = ((a_bar - 1.0) / (a + 1e-8))[:, None] * (
        np.asarray(p["B_re"]) + 1j * np.asarray(p["B_im"]))
    c = np.asarray(p["C_re"]) + 1j * np.asarray(p["C_im"])
    d = np.asarray(p["D"])

    un = np.asarray(u)[0]
    state = np.zeros(6, dtype=np.complex128)
    ys = []
    for k in range(un.shape[0]):
        state = a_bar * state + b_bar @ un[k]
        ys.append((c @ state).real + d * un[k])
    np.testing.assert_allclose(y[0], np.stack(ys), rtol=2e-4, atol=2e-5)


def test_s5_causality(rng):
    """Output at step k must not depend on inputs after k."""
    layer = S5Layer(features=4, state_dim=4)
    u1 = jnp.asarray(rng.normal(size=(1, 12, 4)), jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), u1)
    u2 = u1.at[:, 8:].set(99.0)  # perturb the future
    y1 = np.asarray(jax.jit(layer.apply)(params, u1))
    y2 = np.asarray(jax.jit(layer.apply)(params, u2))
    np.testing.assert_allclose(y1[:, :8], y2[:, :8], rtol=1e-5)
    assert not np.allclose(y1[:, 8:], y2[:, 8:])


def test_bidirectional_s5_sees_both_directions(rng):
    layer = BidirectionalS5Layer(features=4, state_dim=4)
    u1 = jnp.asarray(rng.normal(size=(1, 12, 4)), jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), u1)
    # Perturbing the future changes early outputs (backward scan).
    u2 = u1.at[:, 10:].set(5.0)
    y1 = np.asarray(jax.jit(layer.apply)(params, u1))
    y2 = np.asarray(jax.jit(layer.apply)(params, u2))
    assert not np.allclose(y1[:, :5], y2[:, :5])


def test_spatial_fusion_zero_init_is_identity(rng):
    fusion = SpatialFusionConv(features=8)
    y = jnp.asarray(rng.normal(size=(2, 4, 4, 8)), jnp.float32)
    params = jax.jit(fusion.init)(jax.random.PRNGKey(0), y)
    out = jax.jit(fusion.apply)(params, y)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(y))


@pytest.mark.parametrize("scan", ["raster", "hilbert", "zigzag"])
def test_ssm_dit_block_with_fusion(scan, rng):
    block = SSMDiTBlock(features=16, state_dim=8, use_2d_fusion=True,
                        scan_order=scan)
    x = jnp.asarray(rng.normal(size=(2, 16, 16)), jnp.float32)  # 4x4 grid
    cond = jnp.asarray(rng.normal(size=(2, 16)), jnp.float32)
    params = jax.jit(block.init)(jax.random.PRNGKey(0), x, cond)
    out = jax.jit(block.apply)(params, x, cond)
    assert out.shape == x.shape


def test_ssm_dit_block_fusion_non_square_grid(rng):
    """grid_hw must drive the fusion reshape; 2x8=16 tokens is a perfect
    square and previously mis-fused as 4x4."""
    block = SSMDiTBlock(features=8, state_dim=4, use_2d_fusion=True,
                        scan_order="hilbert", grid_hw=(2, 8))
    x = jnp.asarray(rng.normal(size=(1, 16, 8)), jnp.float32)
    cond = jnp.asarray(rng.normal(size=(1, 8)), jnp.float32)
    params = jax.jit(block.init)(jax.random.PRNGKey(0), x, cond)
    assert jax.jit(block.apply)(params, x, cond).shape == x.shape
    with pytest.raises(ValueError):
        bad = SSMDiTBlock(features=8, state_dim=4, use_2d_fusion=True,
                          grid_hw=(3, 3))
        jax.eval_shape(bad.init, jax.random.PRNGKey(0), x, cond)


def test_hybrid_non_square_image(rng):
    model = HybridSSMAttentionDiT(
        output_channels=1, patch_size=4, emb_features=32, num_layers=2,
        num_heads=2, ssm_state_dim=4, use_hilbert=True, use_2d_fusion=True)
    x = jnp.asarray(rng.normal(size=(1, 8, 32, 1)), jnp.float32)  # 2x8 grid
    t = jnp.asarray([0.5], jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, None)
    assert jax.jit(model.apply)(params, x, t, None).shape == x.shape


def test_build_block_pattern():
    assert build_block_pattern(4, "3:1") == ["ssm", "ssm", "ssm", "attn"]
    assert build_block_pattern(6, "1:1") == ["ssm", "attn"] * 3
    assert build_block_pattern(3, "all-ssm") == ["ssm"] * 3
    assert build_block_pattern(2, "all-attn") == ["attn"] * 2
    assert build_block_pattern(5, "3:1") == ["ssm", "ssm", "ssm", "attn", "ssm"]
    assert build_block_pattern(4, pattern=["attn", "ssm"]) == \
        ["attn", "ssm", "attn", "ssm"]
    with pytest.raises(ValueError):
        build_block_pattern(4, pattern=["conv"])


@pytest.mark.parametrize("scan,ratio", [
    ("raster", "1:1"), ("hilbert", "3:1"), ("zigzag", "all-ssm")])
def test_hybrid_ssm_dit_forward(scan, ratio, rng):
    model = HybridSSMAttentionDiT(
        output_channels=3, patch_size=4, emb_features=64, num_layers=2,
        num_heads=4, ssm_state_dim=8, ssm_attention_ratio=ratio,
        use_hilbert=scan == "hilbert", use_zigzag=scan == "zigzag",
        use_2d_fusion=True)
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
    t = jnp.asarray([0.1, 0.8], jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(2, 7, 32)), jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, ctx)
    out = jax.jit(model.apply)(params, x, t, ctx)
    assert out.shape == x.shape
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_hybrid_ssm_dit_grad(rng):
    model = HybridSSMAttentionDiT(
        output_channels=1, patch_size=2, emb_features=32, num_layers=2,
        num_heads=2, ssm_state_dim=4, ssm_attention_ratio="1:1")
    x = jnp.asarray(rng.normal(size=(1, 8, 8, 1)), jnp.float32)
    t = jnp.asarray([0.5], jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, None)

    @jax.jit
    def loss(p):
        return jnp.mean(model.apply(p, x, t, None) ** 2)

    g = jax.grad(loss)(params)
    assert all(np.all(np.isfinite(np.asarray(l)))
               for l in jax.tree_util.tree_leaves(g))
