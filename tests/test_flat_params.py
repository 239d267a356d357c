"""Flat-parameter training mode (TrainerConfig.flat_params).

Params/EMA/optimizer state live as one padded vector per dtype; the
model unflattens inside the loss so AD returns flat gradients, and
every optimizer/EMA/apply update is a fused per-dtype kernel
(trainer/optim.py module docstring; the r3 on-chip trace attributed
~12% of the train step to leaf-wise update launches). The math must be
IDENTICAL to the structured path — adam/adamw/global-norm clip are
elementwise or concatenation-invariant.
"""
import jax
import json
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from flaxdiff_tpu.models.unet import Unet
from flaxdiff_tpu.parallel import create_mesh
from flaxdiff_tpu.predictors import EpsilonPredictionTransform
from flaxdiff_tpu.schedulers import CosineNoiseSchedule
from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig


def _make_trainer(flat: bool, mesh_axes=None, seed=3):
    size = 8
    model = Unet(output_channels=1, emb_features=16,
                 feature_depths=(8,), attention_configs=(None,),
                 num_res_blocks=1, norm_groups=4)

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, size, size, 1)),
                          jnp.zeros((1,)), None)["params"]

    return DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn,
        tx=optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3)),
        schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(),
        mesh=create_mesh(axes=mesh_axes or {"data": -1}),
        config=TrainerConfig(log_every=1, uncond_prob=0.0, seed=seed,
                             flat_params=flat),
    ), size


def _batches(size, n=4, batch=8):
    rng = np.random.default_rng(0)
    return [{"sample": rng.integers(0, 255, (batch, size, size, 1))
             .astype(np.uint8)} for _ in range(n)]


@pytest.fixture(scope="module")
def stepped_pair():
    """The structured and the flat trainer of one seed, stepped side by
    side over the same four batches: (t_ref, t_flat, size, losses).
    The sampler test reads the flat one after it; nobody steps either
    again."""
    t_ref, size = _make_trainer(flat=False)
    t_flat, _ = _make_trainer(flat=True)
    losses = [(float(t_ref.train_step(t_ref.put_batch(b))),
               float(t_flat.train_step(t_flat.put_batch(b))))
              for b in _batches(size)]
    return t_ref, t_flat, size, losses


def test_flat_params_matches_structured_path(stepped_pair):
    """Same seeds, same batches: the flat-state trainer must follow the
    structured trainer's loss trajectory, params, and EMA. Tolerance is
    loose-float, not bitwise: clip_by_global_norm sums squares in a
    different order over one concatenated vector than over per-leaf
    partials, so the clip scale differs in the last ulp."""
    t_ref, t_flat, _, losses = stepped_pair
    assert len(losses) == 4
    for l_ref, l_flat in losses:
        assert np.isclose(l_ref, l_flat, rtol=1e-6), (l_ref, l_flat)

    p_ref = jax.device_get(t_ref.get_params(use_ema=False))
    p_flat = jax.device_get(t_flat.get_params(use_ema=False))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        p_ref, p_flat)
    e_ref = jax.device_get(t_ref.get_params(use_ema=True))
    e_flat = jax.device_get(t_flat.get_params(use_ema=True))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        e_ref, e_flat)


def test_flat_params_state_is_flat_and_fsdp_sharded():
    """The state really is per-dtype vectors (that is the entire point:
    a handful of big leaves instead of hundreds), padded to 1024 so any
    fsdp axis divides it; under a (data, fsdp) mesh the vectors shard.
    The model must clear infer_fsdp_spec's 64k min_size (tiny tensors
    are deliberately replicated), so this test uses a ~119k-param
    config."""
    size = 8
    model = Unet(output_channels=1, emb_features=32,
                 feature_depths=(16, 32), attention_configs=(None, None),
                 num_res_blocks=1, norm_groups=4)

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, size, size, 1)),
                          jnp.zeros((1,)), None)["params"]

    t_flat = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adamw(1e-3),
        schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(),
        mesh=create_mesh(axes={"data": 2, "fsdp": 4}),
        config=TrainerConfig(log_every=1, uncond_prob=0.0,
                             flat_params=True))
    leaves = jax.tree_util.tree_leaves(t_flat.state.params)
    assert all(leaf.ndim == 1 for leaf in leaves)
    assert all(leaf.size % 1024 == 0 for leaf in leaves)
    # far fewer state leaves than the structured tree has params
    assert len(leaves) <= 4
    specs = jax.tree_util.tree_leaves(t_flat.state_specs.params)
    assert any("fsdp" in str(s) for s in specs)
    loss = float(t_flat.train_step(t_flat.put_batch(_batches(size, n=1)[0])))
    assert np.isfinite(loss)


def test_flat_params_trains_under_fsdp_mesh():
    t_flat, size = _make_trainer(flat=True, mesh_axes={"data": 2, "fsdp": 4})
    losses = [float(t_flat.train_step(t_flat.put_batch(b)))
              for b in _batches(size, n=3)]
    assert all(np.isfinite(losses))


def test_flat_params_sampler_roundtrip(stepped_pair):
    """get_params returns the structured tree the samplers expect."""
    from flaxdiff_tpu.samplers import DDIMSampler, DiffusionSampler
    from flaxdiff_tpu.utils import RngSeq

    _, t_flat, size, _ = stepped_pair
    engine = DiffusionSampler(
        model_fn=t_flat._apply_fn,
        schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(),
        sampler=DDIMSampler())
    out = engine.generate_samples(
        t_flat.get_params(use_ema=False), num_samples=2, resolution=size,
        diffusion_steps=4, rngstate=RngSeq.create(0), channels=1)
    assert out.shape == (2, size, size, 1)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_flat_params_with_grad_accum():
    """optax.MultiSteps over the flat vectors (CLI --grad_accum path):
    accumulation is per-leaf elementwise, so it composes with flat
    state; k micro-steps per optimizer update must still train."""
    size = 8
    model = Unet(output_channels=1, emb_features=16,
                 feature_depths=(8,), attention_configs=(None,),
                 num_res_blocks=1, norm_groups=4)

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, size, size, 1)),
                          jnp.zeros((1,)), None)["params"]

    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn,
        tx=optax.MultiSteps(optax.adamw(1e-3), every_k_schedule=2),
        schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(),
        mesh=create_mesh(axes={"data": -1}),
        config=TrainerConfig(log_every=1, uncond_prob=0.0,
                             flat_params=True))
    losses = [float(trainer.train_step(trainer.put_batch(b)))
              for b in _batches(size, n=4)]
    assert all(np.isfinite(losses))


def test_template_serialization_roundtrip():
    """param_template -> serialize -> deserialize -> unflatten must
    reproduce the original tree exactly (this is the path a flat-params
    checkpoint takes through inference restore), including nested
    modules, mixed dtypes, and pad_to padding."""
    from flaxdiff_tpu.trainer.optim import (deserialize_template,
                                            flatten_params,
                                            param_template,
                                            serialize_template,
                                            unflatten_params)

    rng = np.random.default_rng(0)
    tree = {
        "block_a": {"conv": {"kernel": rng.normal(size=(3, 3, 4, 8))
                             .astype(np.float32),
                             "bias": rng.normal(size=(8,))
                             .astype(np.float32)},
                    "scale": rng.normal(size=(13,)).astype(np.float16)},
        "head": {"kernel": rng.normal(size=(8, 2)).astype(np.float32)},
    }
    flats = flatten_params(tree, 1024)
    entries = json.loads(json.dumps(
        serialize_template(param_template(tree))))
    rebuilt = unflatten_params(deserialize_template(entries), flats)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), tree, rebuilt)
    # dtype preserved through the JSON hop
    assert rebuilt["block_a"]["scale"].dtype == jnp.float16
