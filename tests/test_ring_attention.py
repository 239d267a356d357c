"""Ring attention must exactly match full attention on a CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from flaxdiff_tpu.ops.attention import dot_product_attention
from flaxdiff_tpu.parallel import create_mesh
from flaxdiff_tpu.parallel.ring_attention import (
    ring_self_attention,
    sequence_sharding,
)


@pytest.fixture(scope="module")
def seq_mesh():
    return create_mesh(axes={"data": 2, "seq": 4})


def _reference_attention(q, k, v):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.parametrize("seq_len", [16, 64])
def test_ring_matches_full_attention(seq_mesh, seq_len, rng):
    B, H, D = 4, 2, 8
    q = jnp.asarray(rng.normal(size=(B, seq_len, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, seq_len, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, seq_len, H, D)), jnp.float32)
    expected = _reference_attention(q, k, v)
    out = ring_self_attention(q, k, v, seq_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_ring_matches_ops_layer(seq_mesh, rng):
    B, S, H, D = 2, 32, 4, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    expected = dot_product_attention(q, k, v, backend="xla")
    out = ring_self_attention(q, k, v, seq_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_ring_under_jit_with_sharded_inputs(seq_mesh, rng):
    """jit + explicitly device-put sequence-sharded inputs."""
    B, S, H, D = 2, 64, 2, 8
    sharding = NamedSharding(seq_mesh, P("data", "seq", None, None))
    q = jax.device_put(
        jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32), sharding)
    k = jax.device_put(
        jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32), sharding)
    v = jax.device_put(
        jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32), sharding)

    @jax.jit
    def f(q, k, v):
        return ring_self_attention(q, k, v, seq_mesh)

    out = f(q, k, v)
    expected = _reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)
    # output keeps the sequence sharding
    assert out.sharding.spec == P("data", "seq", None, None)


def test_ring_extreme_logits_stable(seq_mesh, rng):
    """Online softmax must stay finite with large score magnitudes."""
    B, S, H, D = 2, 16, 2, 8
    q = jnp.asarray(rng.normal(size=(B, S, H, D)) * 30, jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)) * 30, jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    out = np.asarray(ring_self_attention(q, k, v, seq_mesh))
    assert np.all(np.isfinite(out))
    expected = np.asarray(_reference_attention(q, k, v))
    np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-4)


def test_ring_gradients_match(seq_mesh, rng):
    B, S, H, D = 2, 16, 2, 8
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

    g_ring = jax.jit(jax.grad(
        lambda q: jnp.sum(ring_self_attention(q, k, v, seq_mesh) ** 2)))(q)
    g_full = jax.jit(jax.grad(
        lambda q: jnp.sum(_reference_attention(q, k, v) ** 2)))(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full),
                               rtol=1e-4, atol=1e-4)


def test_ring_gradients_match_midsize(rng):
    """Grads through the blockwise custom_vjp backward (chunk smaller than
    the shard, so the per-hop chunk scan really accumulates) vs XLA."""
    from flaxdiff_tpu.parallel import ring_attention as ra
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("seq",))
    B, S, H, D = 1, 512, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

    def ring128(q, k, v):
        spec = ra.seq_shard_spec(mesh)
        from jax import shard_map
        body = lambda a, b, c: ra.ring_attention_sharded(
            a, b, c, "seq", None, 128)
        return shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)

    g_ring = jax.jit(jax.grad(lambda q: jnp.sum(ring128(q, k, v) ** 2)))(q)
    g_full = jax.jit(jax.grad(
        lambda q: jnp.sum(_reference_attention(q, k, v) ** 2)))(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full),
                               rtol=1e-4, atol=1e-4)
    gk_ring = jax.jit(jax.grad(lambda k: jnp.sum(ring128(q, k, v) ** 2)))(k)
    gk_full = jax.jit(jax.grad(
        lambda k: jnp.sum(_reference_attention(q, k, v) ** 2)))(k)
    np.testing.assert_allclose(np.asarray(gk_ring), np.asarray(gk_full),
                               rtol=1e-4, atol=1e-4)


def test_ring_16k_tokens_per_shard(rng):
    """VERDICT r2 #3 acceptance: a >=16k-token-per-shard case RUNS with
    O(Sq*chunk) live memory (no [16k, 16k] score materialization), and
    matches an independent direct-softmax oracle."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("seq",))
    # Head dim 8: the claim is about the [16k, 16k] score block, whose
    # size the head dim does not enter.
    B, S, H, D = 1, 32768, 1, 8            # 16384 tokens per shard
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    out = np.asarray(ring_self_attention(q, k, v, mesh))
    assert out.shape == (B, S, H, D)
    assert np.all(np.isfinite(out))
    # Independent oracle: plain DIRECT softmax (no online accumulation,
    # no chunk masking, none of the ring module's code) per q slice over
    # the FULL kv — [2048, 32k] scores at a time, never [32k, 32k]. Four
    # of the sixteen slices: the first, the last, and the two on either
    # side of the shard boundary at 16,384.
    scale = D ** -0.5

    @jax.jit
    def direct(qs):
        s = jnp.einsum("bqhd,bkhd->bhqk", qs, k) * scale
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    for start in (0, S // 2 - 2048, S // 2, S - 2048):
        want = direct(q[:, start:start + 2048])
        np.testing.assert_allclose(out[:, start:start + 2048],
                                   np.asarray(want), rtol=2e-4, atol=2e-4)


def test_ring_flash_hops_interpret_mode(rng):
    """The Pallas flash hop path (fwd + bwd lse plumbing) in interpret
    mode on CPU: without this, _hop_fwd_flash/_hop_bwd_flash would ship
    to real TPU unverified."""
    from flaxdiff_tpu.parallel import ring_attention as ra
    from jax import shard_map
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("seq",))
    B, S, H, D = 2, 64, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

    def ring_flash(q, k, v):
        spec = ra.seq_shard_spec(mesh)
        body = lambda a, b, c: ra.ring_attention_sharded(
            a, b, c, "seq", None, ra._DEFAULT_CHUNK, True, True)
        return shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)

    out = ring_flash(q, k, v)
    want = _reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    g_ring = jax.jit(jax.grad(
        lambda k: jnp.sum(ring_flash(q, k, v) ** 2)))(k)
    g_full = jax.jit(jax.grad(
        lambda k: jnp.sum(_reference_attention(q, k, v) ** 2)))(k)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full),
                               rtol=1e-4, atol=1e-4)
    gv_ring = jax.jit(jax.grad(
        lambda v: jnp.sum(ring_flash(q, k, v) ** 2)))(v)
    gv_full = jax.jit(jax.grad(
        lambda v: jnp.sum(_reference_attention(q, k, v) ** 2)))(v)
    np.testing.assert_allclose(np.asarray(gv_ring), np.asarray(gv_full),
                               rtol=1e-4, atol=1e-4)


def test_sequence_sharding_spec(seq_mesh):
    s = sequence_sharding(seq_mesh)
    assert s.spec == P("data", "seq")


# -- model-level wiring (round-2: VERDICT r1 #5) ------------------------------

def test_backend_ring_dispatch_matches_xla(seq_mesh, rng):
    """dot_product_attention(backend='ring') under the active mesh equals
    the XLA path; falls back cleanly when no mesh is declared."""
    from flaxdiff_tpu.parallel import use_mesh
    q = jnp.asarray(rng.normal(size=(2, 32, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 32, 4, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 32, 4, 16)), jnp.float32)
    want = dot_product_attention(q, k, v, backend="xla")
    with use_mesh(seq_mesh):
        got = dot_product_attention(q, k, v, backend="ring")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # no mesh declared -> silently identical via the auto fallback
    got_nomesh = dot_product_attention(q, k, v, backend="ring")
    np.testing.assert_allclose(np.asarray(got_nomesh), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # cross-attention (kv_len != q_len) -> fallback, still correct
    kc = jnp.asarray(rng.normal(size=(2, 7, 4, 16)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(2, 7, 4, 16)), jnp.float32)
    want_c = dot_product_attention(q, kc, vc, backend="xla")
    with use_mesh(seq_mesh):
        got_c = dot_product_attention(q, kc, vc, backend="ring")
    np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c),
                               atol=2e-5, rtol=2e-5)


def test_dit_forward_with_ring_backend(seq_mesh, rng):
    """SimpleDiT spatial attention through the ring backend equals xla."""
    from flaxdiff_tpu.models.dit import SimpleDiT
    from flaxdiff_tpu.parallel import use_mesh

    def build(backend):
        return SimpleDiT(patch_size=2, emb_features=32, num_layers=1,
                         num_heads=2, output_channels=3, backend=backend)

    x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
    t = jnp.zeros((2,))
    ctx = jnp.asarray(rng.normal(size=(2, 4, 32)), jnp.float32)
    params = jax.jit(build("xla").init)(jax.random.PRNGKey(0), x, t, ctx)
    want = jax.jit(build("xla").apply)(params, x, t, ctx)
    with use_mesh(seq_mesh):
        got = jax.jit(build("ring").apply)(params, x, t, ctx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_unet3d_trains_one_step_with_ring_temporal_attention(rng):
    """VERDICT r1 #5 done-criterion: multi-device CPU test trains one
    UNet3D step with seq>1 — attention rides the ring over the 'seq'
    mesh axis wherever token counts tile it (temporal and, at divisible
    resolutions, spatial); conv/norm ops stay data-parallel."""
    import optax
    from flaxdiff_tpu.models.unet3d import UNet3D
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    mesh = create_mesh(axes={"data": 2, "seq": 4})
    n_frames, size = 8, 8
    model = UNet3D(output_channels=3, emb_features=16,
                   feature_depths=(8,), attention_levels=(True,),
                   heads=2, num_res_blocks=1, norm_groups=4,
                   backend="ring")

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, n_frames, size, size, 3)),
                          jnp.zeros((1,)), None)["params"]

    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
        schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(),
        mesh=mesh,
        config=TrainerConfig(log_every=1, uncond_prob=0.0,
                             normalize=False))
    nprng = np.random.default_rng(0)
    batch = {"sample": nprng.normal(
        size=(4, n_frames, size, size, 3)).astype(np.float32)}
    l1 = float(trainer.train_step(trainer.put_batch(batch)))
    l2 = float(trainer.train_step(trainer.put_batch(batch)))
    assert np.isfinite(l1) and np.isfinite(l2)
