"""UNet + layer forward tests (shapes, dtypes, grad flow)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.models import Unet
from flaxdiff_tpu.models.attention import AttentionLayer, TransformerBlock
from flaxdiff_tpu.models.common import (
    FourierEmbedding,
    PixelShuffle,
    ResidualBlock,
    TimeEmbedding,
)


def test_time_embedding_shapes():
    emb = TimeEmbedding(features=64)
    out = emb.apply({}, jnp.arange(4.0))
    assert out.shape == (4, 64)
    f = FourierEmbedding(features=64)
    params = f.init(jax.random.PRNGKey(0), jnp.arange(4.0))
    out = f.apply(params, jnp.arange(4.0))
    assert out.shape == (4, 64)


def test_pixel_shuffle():
    x = jnp.arange(2 * 2 * 2 * 8, dtype=jnp.float32).reshape(2, 2, 2, 8)
    out = PixelShuffle(scale=2)(x)
    assert out.shape == (2, 4, 4, 2)


def test_residual_block_shapes():
    block = ResidualBlock(features=32, norm_groups=8)
    x = jnp.ones((2, 8, 8, 16))
    temb = jnp.ones((2, 64))
    params = jax.jit(block.init)(jax.random.PRNGKey(0), x, temb)
    out = jax.jit(block.apply)(params, x, temb)
    assert out.shape == (2, 8, 8, 32)


def test_attention_self_and_cross():
    attn = AttentionLayer(heads=2, dim_head=8)
    x = jnp.ones((2, 16, 32))
    ctx = jnp.ones((2, 7, 32))
    params = jax.jit(attn.init)(jax.random.PRNGKey(0), x, ctx)
    out = jax.jit(attn.apply)(params, x, ctx)
    assert out.shape == (2, 16, 32)
    # spatial input auto-flattens
    xs = jnp.ones((2, 4, 4, 32))
    params = jax.jit(attn.init)(jax.random.PRNGKey(0), xs)
    assert jax.jit(attn.apply)(params, xs).shape == (2, 4, 4, 32)


def test_transformer_block_projection_residual():
    tb = TransformerBlock(heads=2, dim_head=16, use_projection=True)
    x = jnp.ones((2, 4, 4, 32))
    ctx = jnp.ones((2, 7, 32))
    params = jax.jit(tb.init)(jax.random.PRNGKey(0), x, ctx)
    out = jax.jit(tb.apply)(params, x, ctx)
    assert out.shape == x.shape
    # zero-init proj_out => output == residual at init
    np.testing.assert_allclose(out, x, atol=1e-5)


@pytest.mark.parametrize("attn", [False, True])
def test_unet_forward(attn):
    configs = None
    if attn:
        configs = [None, None, {"heads": 2, "dim_head": 16, "use_projection": True}]
    model = Unet(output_channels=3, emb_features=64,
                 feature_depths=(16, 24, 32), attention_configs=configs,
                 num_res_blocks=1, norm_groups=8)
    x = jnp.ones((2, 16, 16, 3))
    temb = jnp.asarray([0.1, 0.7])
    ctx = jnp.ones((2, 7, 32)) if attn else None
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, temb, ctx)
    out = jax.jit(model.apply)(params, x, temb, ctx)
    assert out.shape == (2, 16, 16, 3)
    assert out.dtype == jnp.float32
    # zero-init output conv => exactly zero output at init
    np.testing.assert_allclose(out, 0.0, atol=1e-6)


def test_unet_grad_flows():
    model = Unet(output_channels=1, emb_features=32, feature_depths=(8, 12),
                 num_res_blocks=1, norm_groups=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8, 1))
    temb = jnp.asarray([0.5])
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, temb)

    target = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def loss(p):
        return jnp.mean((model.apply(p, x, temb) - target) ** 2)

    grad = jax.jit(jax.grad(loss))  # one program for all four gradients

    # At exact init the zero-init output conv blocks upstream gradients (the
    # standard zero-init property: only the final conv trains on step 0).
    g0 = grad(params)
    norms0 = [float(jnp.abs(v).sum()) for v in jax.tree_util.tree_leaves(g0)]
    assert np.isfinite(norms0).all()
    assert sum(n > 0 for n in norms0) >= 2  # conv_out kernel + bias

    # After a couple of SGD steps every zero-init layer (output conv, then
    # each resblock's conv2) is nonzero and gradient flows everywhere.
    p = params
    for _ in range(2):
        g = grad(p)
        p = jax.tree_util.tree_map(lambda w, gw: w - 0.1 * gw, p, g)
    g1 = grad(p)
    norms1 = [float(jnp.abs(v).sum()) for v in jax.tree_util.tree_leaves(g1)]
    assert np.isfinite(norms1).all()
    assert sum(n > 0 for n in norms1) > len(norms1) * 2 // 3


def test_unet_bf16_compute():
    model = Unet(output_channels=3, emb_features=32, feature_depths=(8, 12),
                 num_res_blocks=1, norm_groups=4, dtype=jnp.bfloat16)
    x = jnp.ones((1, 8, 8, 3))
    temb = jnp.asarray([0.5])
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, temb)
    out = jax.jit(model.apply)(params, x, temb)
    assert out.shape == (1, 8, 8, 3)
    assert bool(jnp.all(jnp.isfinite(out)))
