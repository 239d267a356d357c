"""Tests for MM-DiT: MMAdaLNZero, blocks, SimpleMMDiT, hierarchical variant."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.models.mmdit import (
    HierarchicalMMDiT,
    MMAdaLNZero,
    PatchExpanding,
    PatchMerging,
    SimpleMMDiT,
)


def test_mm_adaln_zero_init_is_identity_modulation(rng):
    """Zero-init projections -> scales/shifts/gates all zero at init."""
    mod = MMAdaLNZero(features=16)
    x = jnp.asarray(rng.normal(size=(2, 5, 16)), jnp.float32)
    t = jnp.asarray(rng.normal(size=(2, 16)), jnp.float32)
    txt = jnp.asarray(rng.normal(size=(2, 7, 16)), jnp.float32)
    params = mod.init(jax.random.PRNGKey(0), x, t, txt)
    x_attn, g_attn, x_mlp, g_mlp = mod.apply(params, x, t, txt)
    np.testing.assert_array_equal(np.asarray(g_attn), 0.0)
    np.testing.assert_array_equal(np.asarray(g_mlp), 0.0)
    # modulation with zero scale/shift = plain layernorm output
    np.testing.assert_allclose(np.asarray(x_attn), np.asarray(x_mlp))


def test_patch_merge_expand_roundtrip_shapes(rng):
    x = jnp.asarray(rng.normal(size=(2, 16, 8)), jnp.float32)  # 4x4 grid
    merge = PatchMerging(out_features=12)
    p = merge.init(jax.random.PRNGKey(0), x, 4, 4)
    merged, h2, w2 = merge.apply(p, x, 4, 4)
    assert merged.shape == (2, 4, 12) and (h2, w2) == (2, 2)
    expand = PatchExpanding(out_features=8)
    pe = expand.init(jax.random.PRNGKey(1), merged, h2, w2)
    expanded, h3, w3 = expand.apply(pe, merged, h2, w2)
    assert expanded.shape == (2, 16, 8) and (h3, w3) == (4, 4)


def test_patch_merging_groups_true_2d_neighbors():
    """Each merged token must contain exactly the 2x2 spatial block."""
    hp = wp = 4
    # token value = row-major index, feature dim 1
    x = jnp.arange(hp * wp, dtype=jnp.float32).reshape(1, hp * wp, 1)
    merge = PatchMerging(out_features=4, merge_size=2)
    p = merge.init(jax.random.PRNGKey(0), x, hp, wp)
    # Inspect the pre-norm grouping by reproducing the reshape with identity C
    xg = x.reshape(1, 2, 2, 2, 2, 1).transpose(0, 1, 3, 2, 4, 5).reshape(1, 4, 4)
    # First merged token should hold row-major indices {0,1,4,5}
    assert sorted(np.asarray(xg)[0, 0].tolist()) == [0.0, 1.0, 4.0, 5.0]


@pytest.mark.parametrize("hilbert", [False, True])
def test_simple_mmdit_forward(hilbert, rng):
    model = SimpleMMDiT(output_channels=3, patch_size=4, emb_features=64,
                        num_layers=2, num_heads=4, use_hilbert=hilbert)
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
    t = jnp.asarray([0.1, 0.9], jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(2, 7, 32)), jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, ctx)
    out = jax.jit(model.apply)(params, x, t, ctx)
    assert out.shape == x.shape
    np.testing.assert_array_equal(np.asarray(out), 0.0)  # zero-init head


def test_simple_mmdit_requires_text(rng):
    model = SimpleMMDiT(patch_size=4, emb_features=64, num_layers=1, num_heads=4)
    x = jnp.zeros((1, 8, 8, 3))
    with pytest.raises(ValueError):
        model.init(jax.random.PRNGKey(0), x, jnp.zeros((1,)), None)


@pytest.mark.parametrize("hilbert", [False, True])
def test_hierarchical_mmdit_forward(hilbert, rng):
    model = HierarchicalMMDiT(
        output_channels=3, base_patch_size=2,
        emb_features=(32, 48, 64), num_layers=(1, 1, 1),
        num_heads=(2, 2, 4), use_hilbert=hilbert)
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
    t = jnp.asarray([0.2, 0.7], jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(2, 5, 24)), jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, ctx)
    out = jax.jit(model.apply)(params, x, t, ctx)
    assert out.shape == x.shape
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_hierarchical_mmdit_rejects_indivisible():
    model = HierarchicalMMDiT(base_patch_size=2, emb_features=(16, 32),
                              num_layers=(1, 1), num_heads=(2, 2))
    x = jnp.zeros((1, 6, 6, 3))
    with pytest.raises(ValueError):
        model.init(jax.random.PRNGKey(0), x, jnp.zeros((1,)),
                   jnp.zeros((1, 3, 8)))


def test_hierarchical_mmdit_grad_flow(rng):
    model = HierarchicalMMDiT(
        output_channels=1, base_patch_size=2, emb_features=(16, 24),
        num_layers=(1, 1), num_heads=(2, 2))
    x = jnp.asarray(rng.normal(size=(1, 8, 8, 1)), jnp.float32)
    t = jnp.asarray([0.5], jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(1, 3, 8)), jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, ctx)

    def loss(p):
        return jnp.mean(model.apply(p, x, t, ctx) ** 2)

    g = jax.jit(jax.grad(loss))(params)
    assert all(np.all(np.isfinite(np.asarray(l)))
               for l in jax.tree_util.tree_leaves(g))
