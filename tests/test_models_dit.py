"""Tests for sfc index math, vit_common, SimpleDiT, UViT, SimpleUDiT."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.models import sfc
from flaxdiff_tpu.models.dit import SimpleDiT
from flaxdiff_tpu.models.uvit import SimpleUDiT, UViT
from flaxdiff_tpu.models.vit_common import apply_rope, rope_frequencies


# ---------------------------------------------------------------------------
# Space-filling curves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,w", [(4, 4), (8, 8), (4, 8), (6, 6), (5, 7), (1, 9)])
def test_hilbert_indices_are_permutation(h, w):
    idx = sfc.hilbert_indices(h, w)
    assert sorted(idx.tolist()) == list(range(h * w))


def test_hilbert_locality_adjacent_steps_are_grid_neighbors():
    # On a power-of-2 square the Hilbert curve moves exactly one cell per step.
    h = w = 8
    idx = sfc.hilbert_indices(h, w)
    ys, xs = idx // w, idx % w
    dist = np.abs(np.diff(ys)) + np.abs(np.diff(xs))
    assert np.all(dist == 1)


@pytest.mark.parametrize("h,w", [(4, 4), (3, 5), (2, 2)])
def test_zigzag_indices(h, w):
    idx = sfc.zigzag_indices(h, w)
    assert sorted(idx.tolist()) == list(range(h * w))
    # Row 0 is left-to-right, row 1 (if any) right-to-left.
    assert idx[0] == 0 and idx[w - 1] == w - 1
    if h > 1:
        assert idx[w] == 2 * w - 1


def test_inverse_permutation():
    idx = sfc.hilbert_indices(4, 6)
    inv = sfc.inverse_permutation(idx)
    assert np.array_equal(inv[idx], np.arange(idx.shape[0]))


@pytest.mark.parametrize("mode", ["hilbert", "zigzag"])
def test_sfc_patchify_roundtrip(mode, rng):
    x = jnp.asarray(rng.normal(size=(2, 16, 24, 3)), jnp.float32)
    fn_p = sfc.hilbert_patchify if mode == "hilbert" else sfc.zigzag_patchify
    fn_u = sfc.hilbert_unpatchify if mode == "hilbert" else sfc.zigzag_unpatchify
    tokens, inv = fn_p(x, 4)
    assert tokens.shape == (2, 24, 48)
    back = fn_u(tokens, inv, 4, 16, 24, 3)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=0)


def test_patchify_roundtrip_plain(rng):
    x = jnp.asarray(rng.normal(size=(1, 8, 8, 2)), jnp.float32)
    tokens = sfc.patchify(x, 2)
    back = sfc.unpatchify(tokens, 2, 8, 8, 2)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_sincos_pos_embed_shape_and_distinctness():
    pe = sfc.build_2d_sincos_pos_embed(64, 4, 6)
    assert pe.shape == (24, 64)
    # All positions get distinct embeddings.
    assert len({tuple(np.round(row, 6)) for row in pe}) == 24


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def test_rope_preserves_norm_and_relative_phase(rng):
    dim, seq = 16, 12
    cos, sin = rope_frequencies(dim, seq)
    x = jnp.asarray(rng.normal(size=(1, seq, 2, dim)), jnp.float32)
    y = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # Relative property: <rope(q)_i, rope(k)_j> depends only on i - j.
    q = jnp.asarray(rng.normal(size=(1, seq, 1, dim)), jnp.float32)
    qc = jnp.tile(q[:, :1], (1, seq, 1, 1))  # constant token
    rq = np.asarray(apply_rope(qc, cos, sin))[0, :, 0]
    dots_gap1 = [float(rq[i] @ rq[i + 1]) for i in range(seq - 1)]
    np.testing.assert_allclose(dots_gap1, dots_gap1[0] * np.ones(seq - 1),
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# Model forwards (tiny configs)
# ---------------------------------------------------------------------------

TINY = dict(output_channels=3, patch_size=4, emb_features=64,
            num_layers=2, num_heads=4)


@pytest.mark.parametrize("scan", ["raster", "hilbert", "zigzag"])
def test_simple_dit_forward(scan, rng):
    model = SimpleDiT(use_hilbert=scan == "hilbert",
                      use_zigzag=scan == "zigzag", **TINY)
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
    t = jnp.asarray([0.1, 0.7], jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(2, 7, 32)), jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, ctx)
    out = jax.jit(model.apply)(params, x, t, ctx)
    assert out.shape == x.shape
    # Zero-init final projection -> exact zeros at init.
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_simple_dit_learn_sigma(rng):
    model = SimpleDiT(learn_sigma=True, **TINY)
    x = jnp.asarray(rng.normal(size=(1, 8, 8, 3)), jnp.float32)
    t = jnp.asarray([0.5], jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, None)
    assert jax.jit(model.apply)(params, x, t, None).shape == x.shape


@pytest.mark.parametrize("hilbert", [False, True])
def test_uvit_forward(hilbert, rng):
    model = UViT(use_hilbert=hilbert, add_residualblock_output=True, **TINY)
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
    t = jnp.asarray([0.1, 0.9], jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(2, 5, 32)), jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, ctx)
    assert jax.jit(model.apply)(params, x, t, ctx).shape == x.shape


def test_uvit_no_text(rng):
    model = UViT(**TINY)
    x = jnp.asarray(rng.normal(size=(1, 8, 8, 3)), jnp.float32)
    t = jnp.asarray([0.3], jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, None)
    assert jax.jit(model.apply)(params, x, t, None).shape == x.shape


@pytest.mark.parametrize("scan", ["raster", "hilbert"])
def test_simple_udit_forward(scan, rng):
    model = SimpleUDiT(use_hilbert=scan == "hilbert", **TINY)
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
    t = jnp.asarray([0.2, 0.8], jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(2, 7, 32)), jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, ctx)
    out = jax.jit(model.apply)(params, x, t, ctx)
    assert out.shape == x.shape
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_dit_jit_and_grad(rng):
    model = SimpleDiT(**TINY)
    x = jnp.asarray(rng.normal(size=(1, 8, 8, 3)), jnp.float32)
    t = jnp.asarray([0.5], jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x, t, None)

    @jax.jit
    def loss(p):
        return jnp.mean(model.apply(p, x, t, None) ** 2)

    g = jax.grad(loss)(params)
    flat = jax.tree_util.tree_leaves(g)
    assert all(np.all(np.isfinite(np.asarray(l))) for l in flat)
