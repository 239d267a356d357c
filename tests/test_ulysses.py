"""Ulysses all-to-all sequence parallelism must exactly match full
attention on the CPU mesh, gradients included."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from flaxdiff_tpu.ops.attention import dot_product_attention
from flaxdiff_tpu.parallel import create_mesh, ulysses_self_attention
from flaxdiff_tpu.parallel.context import use_mesh


@pytest.fixture(scope="module")
def seq_mesh():
    return create_mesh(axes={"data": 2, "seq": 4})


def _reference_attention(q, k, v):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.parametrize("seq_len", [16, 64])
def test_ulysses_matches_full_attention(seq_mesh, seq_len, rng):
    B, H, D = 4, 4, 8   # heads divisible by seq axis (4)
    q = jnp.asarray(rng.normal(size=(B, seq_len, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, seq_len, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, seq_len, H, D)), jnp.float32)
    out = ulysses_self_attention(q, k, v, seq_mesh)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference_attention(q, k, v)),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_under_jit_with_sharded_inputs(seq_mesh, rng):
    B, S, H, D = 2, 32, 4, 8
    sharding = NamedSharding(seq_mesh, P("data", "seq", None, None))
    arrs = [jax.device_put(
        jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32), sharding)
        for _ in range(3)]

    @jax.jit
    def f(q, k, v):
        return ulysses_self_attention(q, k, v, seq_mesh)

    out = f(*arrs)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference_attention(*arrs)),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_gradients_match(seq_mesh, rng):
    B, S, H, D = 2, 16, 4, 8
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

    g_u = jax.jit(jax.grad(lambda q: jnp.sum(
        ulysses_self_attention(q, k, v, seq_mesh) ** 2)))(q)
    g_r = jax.jit(jax.grad(
        lambda q: jnp.sum(_reference_attention(q, k, v) ** 2)))(q)
    np.testing.assert_allclose(np.asarray(g_u), np.asarray(g_r),
                               rtol=5e-4, atol=5e-4)


def test_ulysses_rejects_indivisible(seq_mesh, rng):
    q = jnp.zeros((2, 16, 3, 8))   # 3 heads don't divide seq axis 4
    with pytest.raises(ValueError, match="heads"):
        ulysses_self_attention(q, q, q, seq_mesh)
    q = jnp.zeros((2, 10, 4, 8))   # 10 tokens don't divide seq axis 4
    with pytest.raises(ValueError, match="sequence"):
        ulysses_self_attention(q, q, q, seq_mesh)


class TestDispatch:
    def test_backend_ulysses_routes_and_matches_xla(self, seq_mesh, rng):
        B, S, H, D = 2, 32, 4, 8
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        expected = dot_product_attention(q, k, v, backend="xla")
        with use_mesh(seq_mesh):
            out = dot_product_attention(q, k, v, backend="ulysses")
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    def test_backend_ulysses_degrades_without_mesh(self, rng):
        q = jnp.asarray(rng.normal(size=(2, 16, 4, 8)), jnp.float32)
        out = dot_product_attention(q, q, q, backend="ulysses")
        ref = dot_product_attention(q, q, q, backend="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_backend_ulysses_degrades_on_cross_attention(self, seq_mesh, rng):
        q = jnp.asarray(rng.normal(size=(2, 16, 4, 8)), jnp.float32)
        kv = jnp.asarray(rng.normal(size=(2, 7, 4, 8)), jnp.float32)
        with use_mesh(seq_mesh):
            out = dot_product_attention(q, kv, kv, backend="ulysses")
        ref = dot_product_attention(q, kv, kv, backend="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_model_level_ulysses_matches_xla(self, seq_mesh, rng):
        """A DiT with backend='ulysses' equals its XLA twin numerically."""
        from flaxdiff_tpu.models.dit import SimpleDiT

        model_u = SimpleDiT(output_channels=3, patch_size=4,
                            emb_features=32, num_layers=2, num_heads=4,
                            backend="ulysses")
        model_x = SimpleDiT(output_channels=3, patch_size=4,
                            emb_features=32, num_layers=2, num_heads=4,
                            backend="xla")
        x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
        t = jnp.full((2,), 500.0)
        params = jax.jit(model_x.init)(
            jax.random.PRNGKey(0), x, t, None)["params"]
        with use_mesh(seq_mesh):
            out_u = jax.jit(model_u.apply)({"params": params}, x, t, None)
        out_x = jax.jit(model_x.apply)({"params": params}, x, t, None)
        np.testing.assert_allclose(np.asarray(out_u), np.asarray(out_x),
                                   rtol=1e-4, atol=1e-4)
