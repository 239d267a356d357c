"""Tests: sharded checkpoint save/restore/resume, validation, logging."""
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from flaxdiff_tpu.metrics import EvaluationMetric, MetricTracker
from flaxdiff_tpu.predictors import EpsilonPredictionTransform
from flaxdiff_tpu.schedulers import CosineNoiseSchedule
from flaxdiff_tpu.trainer import (
    Checkpointer,
    DiffusionTrainer,
    JsonlLogger,
    TrainerConfig,
    ValidationConfig,
    Validator,
)
from flaxdiff_tpu.models.unet import Unet


def _make_trainer(mesh, tmp_path=None):
    # one resolution level: what is saved and restored is a state tree,
    # whatever its depth
    model = Unet(output_channels=1, emb_features=16, feature_depths=(8,),
                 num_res_blocks=1, norm_groups=4, attention_configs=(None,))
    x0 = jnp.zeros((2, 8, 8, 1))
    t0 = jnp.zeros((2,))

    def apply_fn(params, x, t, cond):
        return model.apply(params, x, t, None)

    def init_fn(key):
        return model.init(key, x0, t0, None)

    ckpt = Checkpointer(str(tmp_path), max_to_keep=2) if tmp_path else None
    return DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
        schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(),
        mesh=mesh, config=TrainerConfig(normalize=False, log_every=2),
        checkpointer=ckpt)


def _batches(n, rng):
    for _ in range(n):
        yield {"sample": rng.normal(size=(8, 8, 8, 1)).astype(np.float32)}


@pytest.fixture(scope="module")
def trained(mesh, tmp_path_factory):
    """ONE training (4 steps, a save at 2 and at 4) that the restore tests
    read: (trainer, its history, its checkpoint directory). Nobody trains
    or restores this trainer again; a test that writes takes a copy of
    the directory (`_copy`)."""
    ckpt_dir = tmp_path_factory.mktemp("trained") / "ckpt"
    trainer = _make_trainer(mesh, ckpt_dir)
    hist = trainer.fit(_batches(4, np.random.default_rng(0)), total_steps=4,
                       save_every=2)
    trainer.checkpointer.wait_until_finished()
    yield trainer, hist, ckpt_dir
    trainer.checkpointer.close()


def _copy(ckpt_dir, tmp_path):
    return shutil.copytree(ckpt_dir, tmp_path / "ckpt")


@pytest.fixture(scope="module")
def restored(trained, mesh, tmp_path_factory):
    """A fresh trainer that restored a copy of `trained`'s checkpoint,
    for the tests that only look at what a restore leaves behind."""
    trainer2 = _make_trainer(
        mesh, _copy(trained[2], tmp_path_factory.mktemp("restored")))
    step = trainer2.restore_checkpoint()
    yield trainer2, step
    trainer2.checkpointer.close()


def test_checkpoint_roundtrip(mesh, trained, restored):
    trainer, _, _ = trained
    saved_step = trainer.checkpointer.latest_step()
    assert saved_step == 4

    # Fresh trainer restores the exact sharded state.
    trainer2, restored_step = restored
    assert restored_step == 4
    p1 = jax.device_get(trainer.state.params)
    p2 = jax.device_get(trainer2.state.params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)), p1, p2)
    # Restored state keeps its FSDP shardings.
    leaf = jax.tree_util.tree_leaves(trainer2.state.params)[0]
    assert leaf.sharding.mesh.axis_names == mesh.axis_names


def test_checkpoint_resume_continues_training(mesh, trained, tmp_path, rng):
    trainer2 = _make_trainer(mesh, _copy(trained[2], tmp_path))
    assert trainer2.restore_checkpoint() == 4
    trainer2.fit(_batches(2, rng), total_steps=2)
    assert int(jax.device_get(trainer2.state.step)) == 6
    trainer2.checkpointer.wait_until_finished()
    assert trainer2.checkpointer.latest_step() == 6
    trainer2.checkpointer.close()


def test_fit_with_save_every_equal_total_steps(trained):
    """Final forced save must not crash when save_every already wrote the
    last step (orbax refuses duplicate steps): `trained` is that fit."""
    trainer, hist, _ = trained
    assert "final_loss" in hist
    assert trainer.checkpointer.latest_step() == 4


def test_restore_arms_best_state(restored):
    trainer2, _ = restored
    assert trainer2.best_state is not None  # NaN rollback armed after resume


def test_cross_mesh_restore(mesh, tmp_path, rng):
    """A checkpoint written from a (data=2, fsdp=4) mesh restores (a)
    topology-free to host numpy with NO orbax sharding warning, and (b)
    onto a DIFFERENT mesh shape via an abstract tree carrying the new
    shardings (VERDICT r2 weak #6)."""
    import warnings

    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.trainer.checkpoints import abstract_state_like

    # the writer is its own: bare `train_step`s and a forced save, with
    # no `fit` around them
    trainer = _make_trainer(mesh, tmp_path)
    it = _batches(3, rng)
    for _ in range(2):
        trainer.train_step(trainer.put_batch(next(it)))
    assert trainer.save_checkpoint(force=True)
    trainer.checkpointer.wait_until_finished()
    want = jax.device_get(trainer.state.params)

    # (a) host restore: numpy leaves, no different-topology warning
    ck = Checkpointer(str(tmp_path))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        state, _ = ck.restore_to_host()
    topo = [w for w in caught if "topolog" in str(w.message).lower()
            or "sharding info not provided" in str(w.message).lower()]
    assert not topo, [str(w.message) for w in topo]
    got = state["params"]
    # host numpy for real: a jax.Array here would carry the writer's
    # devices into every program the inference pipeline compiles
    assert all(type(leaf) is np.ndarray
               for leaf in jax.tree_util.tree_leaves(state))
    jax.tree_util.tree_map(np.testing.assert_allclose, want, got)
    ck.close()

    # (b) resharded restore onto a different mesh (1-D all-data)
    other = _make_trainer(create_mesh(axes={"data": -1}), None)
    ck = Checkpointer(str(tmp_path))
    abstract = abstract_state_like(other.state)
    restored, _ = ck.restore(abstract)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                np.asarray(b)),
        want, jax.device_get(restored.params))
    # leaves landed with the NEW mesh's shardings
    leaf = jax.tree_util.tree_leaves(restored.params)[0]
    assert leaf.sharding.mesh.shape == {"data": 8}
    ck.close()


def test_restore_without_checkpoint_raises(mesh, tmp_path):
    trainer = _make_trainer(mesh, tmp_path / "empty")
    with pytest.raises(FileNotFoundError):
        trainer.restore_checkpoint()
    trainer.checkpointer.close()


def test_metric_tracker_directions():
    tr = MetricTracker()
    assert tr.update("fid", 30.0, higher_is_better=False)
    assert not tr.update("fid", 40.0, higher_is_better=False)
    assert tr.update("fid", 20.0, higher_is_better=False)
    assert tr.update("clip", 0.2, higher_is_better=True)
    assert tr.update("clip", 0.3, higher_is_better=True)
    assert tr.best == {"fid": 20.0, "clip": 0.3}


def test_validator_runs_metrics(mesh, rng):
    trainer = _make_trainer(mesh)

    def model_fn(params, x, t, cond):
        return trainer._apply_fn(params, x, t, cond)

    mean_abs = EvaluationMetric(
        function=lambda samples, batch: float(np.abs(samples).mean()),
        name="mean_abs", higher_is_better=False)
    validator = Validator(
        model_fn=model_fn, schedule=trainer.schedule,
        transform=trainer.transform,
        config=ValidationConfig(num_samples=4, diffusion_steps=5,
                                resolution=8, channels=1, guidance_scale=0.0),
        metrics=[mean_abs])
    out = validator.run(trainer.get_params())
    assert out["samples"].shape == (4, 8, 8, 1)
    assert "mean_abs" in out["metrics"]
    assert out["improved"]["mean_abs"] is True
    # Second run with same params: not an improvement (equal value).
    out2 = validator.run(trainer.get_params())
    assert out2["improved"]["mean_abs"] is False


def test_jsonl_logger(tmp_path):
    path = str(tmp_path / "log.jsonl")
    lg = JsonlLogger(path)
    lg.log({"loss": 0.5, "curve": [1, 2]}, step=10)
    lg.log({"loss": 0.25}, step=20)
    lg.finish()
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["step"] == 10 and lines[0]["loss"] == 0.5
    # small numeric sequences serialize (telemetry PR bugfix; the old
    # logger silently dropped every list/dict/array value)
    assert lines[0]["curve"] == [1, 2]
    assert lines[1]["loss"] == 0.25
