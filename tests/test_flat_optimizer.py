"""flat_optimizer: fused per-dtype updates must equal leaf-wise ones."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from flaxdiff_tpu.trainer.optim import flat_optimizer


def _tree(key, dtype2=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "dense": {"kernel": jax.random.normal(k1, (32, 48)),
                  "bias": jnp.zeros((48,))},
        "norm": {"scale": jax.random.normal(k2, (7,)).astype(dtype2)},
        "conv": {"kernel": jax.random.normal(k3, (3, 3, 8, 16))},
    }


@pytest.mark.parametrize("make_tx", [
    lambda: optax.adam(1e-3),
    lambda: optax.adamw(1e-3, weight_decay=0.01),
    lambda: optax.chain(optax.clip_by_global_norm(1.0),
                        optax.sgd(1e-2, momentum=0.9)),
])
def test_flat_updates_match_leafwise(make_tx):
    params = _tree(jax.random.PRNGKey(0))
    tx, flat_tx = make_tx(), flat_optimizer(make_tx())
    state, flat_state = tx.init(params), flat_tx.init(params)
    p_ref, p_flat = params, params
    for step in range(3):
        grads = _tree(jax.random.PRNGKey(10 + step))
        u_ref, state = tx.update(grads, state, p_ref)
        p_ref = optax.apply_updates(p_ref, u_ref)
        u_flat, flat_state = flat_tx.update(grads, flat_state, p_flat)
        p_flat = optax.apply_updates(p_flat, u_flat)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(p_ref),
            jax.tree_util.tree_leaves_with_path(p_flat)):
        np.testing.assert_allclose(
            a, b, rtol=1e-6, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))


def test_mixed_dtypes_grouped_separately():
    params = _tree(jax.random.PRNGKey(1), dtype2=jnp.bfloat16)
    tx = flat_optimizer(optax.sgd(1e-1))
    state = tx.init(params)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    updates, _ = tx.update(grads, state, params)
    for path, leaf in jax.tree_util.tree_leaves_with_path(updates):
        want = jax.tree_util.tree_leaves_with_path(params)
        np.testing.assert_allclose(np.asarray(leaf, np.float32),
                                   -0.1 * np.ones(leaf.shape),
                                   rtol=1e-2)
        assert leaf.dtype == dict(
            (jax.tree_util.keystr(p), v.dtype)
            for p, v in want)[jax.tree_util.keystr(path)]


def test_global_norm_clip_preserved_by_concat():
    """clip_by_global_norm must behave identically — the global norm of
    the zero-padded concatenation equals the tree's global norm."""
    params = _tree(jax.random.PRNGKey(2))
    grads = jax.tree_util.tree_map(
        lambda leaf: 10.0 * jnp.ones_like(leaf), params)
    ref = optax.clip_by_global_norm(1.0)
    flat = flat_optimizer(optax.clip_by_global_norm(1.0))
    u_ref, _ = ref.update(grads, ref.init(params), params)
    u_flat, _ = flat.update(grads, flat.init(params), params)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(u_ref),
            jax.tree_util.tree_leaves_with_path(u_flat)):
        np.testing.assert_allclose(a, b, rtol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_trains_end_to_end_in_diffusion_trainer():
    """Drop-in as the trainer's tx: jitted FSDP train steps run and the
    loss stays finite with the flat opt state sharded like any other."""
    from flaxdiff_tpu.models.unet import Unet
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    model = Unet(output_channels=3, emb_features=16,
                 feature_depths=(8,), attention_configs=(None,),
                 num_res_blocks=1, norm_groups=4)

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, 16, 16, 3)),
                          jnp.zeros((1,)))["params"]

    mesh = create_mesh(axes={"data": 2, "fsdp": 4})
    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn,
        tx=flat_optimizer(optax.adamw(1e-3)),
        schedule=CosineNoiseSchedule(timesteps=1000),
        transform=EpsilonPredictionTransform(), mesh=mesh,
        config=TrainerConfig(uncond_prob=0.0, normalize=False))
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(3):
        batch = {"sample": rng.normal(
            size=(8, 16, 16, 3)).astype(np.float32)}
        losses.append(float(jax.device_get(
            trainer.train_step(trainer.put_batch(batch)))))
    assert all(np.isfinite(losses)), losses
