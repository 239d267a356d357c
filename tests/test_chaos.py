"""Chaos suite: tiny CPU train runs under deterministic fault plans.

Every scenario here replays exactly (seeded FaultPlan + seeded data),
exercising the SAME production code paths a pod failure hits: corrupt
checkpoints fall back, transient save I/O retries, SIGTERM mid-async-save
still flushes, a wedged loader trips the watchdog, and injected NaNs
roll back to the best state — all visible in the resilience-event log.
"""
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from flaxdiff_tpu import resilience as R
from flaxdiff_tpu.predictors import EpsilonPredictionTransform
from flaxdiff_tpu.schedulers import CosineNoiseSchedule
from flaxdiff_tpu.trainer import Checkpointer, DiffusionTrainer, TrainerConfig

pytestmark = pytest.mark.chaos


def _make_trainer(mesh, tmp_path=None, event_log=None, **cfg_kw):
    import flax.linen as nn

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, t, cond=None):
            h = nn.Conv(8, (3, 3))(x)
            return nn.Conv(x.shape[-1], (3, 3))(jnp.tanh(h))

    model = Tiny()

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, 8, 8, 1)),
                          jnp.zeros((1,)))["params"]

    ckpt = None
    if tmp_path is not None:
        ckpt = Checkpointer(str(tmp_path), event_log=event_log)
    return DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
        schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(),
        mesh=mesh,
        config=TrainerConfig(normalize=False, log_every=2, **cfg_kw),
        checkpointer=ckpt)


def _data(rng, batch=8):
    while True:
        yield {"sample": rng.normal(size=(batch, 8, 8, 1))
               .astype(np.float32)}


def test_corrupt_latest_plus_transient_save_fault_recovers(
        mesh, tmp_path, rng):
    """The acceptance scenario: latest checkpoint corrupted AND a
    transient save I/O fault injected — fit restores from the previous
    good step, finishes with finite loss, and the event log records both
    the fallback restore and the retried save."""
    ckdir = tmp_path / "ckpt"
    trainer = _make_trainer(mesh, ckdir)
    trainer.fit(_data(rng), total_steps=4, save_every=2)   # saves 2, 4
    trainer.checkpointer.wait_until_finished()
    assert trainer.checkpointer.latest_step() == 4
    trainer.checkpointer.close()

    R.corrupt_step_dir(str(ckdir), 4)
    ev = R.EventLog("chaos")
    # one transient I/O failure on the next fresh save attempt
    plan = R.FaultPlan([R.FaultSpec("ckpt.save", at=(1,), times=1)], seed=0)
    with R.use_event_log(ev), plan.installed():
        trainer2 = _make_trainer(mesh, ckdir, event_log=ev)
        restored = trainer2.restore_checkpoint()
        assert restored == 2                    # fell back past corrupt 4
        assert ev.count("fallback_restore", "ckpt.restore") >= 1

        hist = trainer2.fit(_data(rng), total_steps=3, save_every=2)
        trainer2.checkpointer.wait_until_finished()

    assert np.isfinite(hist["final_loss"])
    assert len(hist["steps"]) > 0
    # step 4 is re-reached post-restore but already on disk: surfaced as
    # a skip, not counted as a fresh save
    assert ev.count("save_skipped", "ckpt.save") >= 1
    assert hist["saves"]["skipped_exists"] >= 1
    # the final save (step 5) hit the injected fault and was retried
    assert ev.count("retry", "ckpt.save") >= 1
    assert hist["saves"]["started"] >= 1
    assert trainer2.checkpointer.latest_step() == 5
    # the run's resilience summary surfaces the whole story
    assert hist["resilience"]["resilience/fallback_restore.ckpt.restore"] >= 1
    assert hist["resilience"]["resilience/retry.ckpt.save"] >= 1
    trainer2.checkpointer.close()


def test_sigterm_mid_async_save_still_flushes(mesh, tmp_path, rng):
    """host.sigterm fault right after a save_every save is dispatched:
    the preemption path must still flush the in-flight async save."""
    ev = R.EventLog("chaos")
    plan = R.FaultPlan(
        [R.FaultSpec("host.sigterm", at=(3,), error="flag", times=1)])
    with R.use_event_log(ev), plan.installed():
        trainer = _make_trainer(mesh, tmp_path / "ck", event_log=ev)
        hist = trainer.fit(_data(rng), total_steps=50, save_every=2)
    assert hist["preempted"] is True
    assert not hist["steps"] or hist["steps"][-1] < 50
    assert ev.count("fault_injected", "host.sigterm") == 1
    assert ev.count("preempt", "train.step") == 1
    trainer.checkpointer.wait_until_finished()
    saved = trainer.checkpointer.latest_step()
    assert saved is not None and saved >= 2
    # handler restored: later SIGTERMs are not swallowed
    assert signal.getsignal(signal.SIGTERM) not in (None,)
    trainer.checkpointer.close()


def test_step_nan_fault_triggers_rollback_event(mesh, rng):
    ev = R.EventLog("chaos")
    plan = R.FaultPlan(
        [R.FaultSpec("step.nan", at=(3,), error="flag", times=1)])
    with R.use_event_log(ev), plan.installed():
        trainer = _make_trainer(mesh)
        hist = trainer.fit(_data(rng), total_steps=8)
    assert ev.count("fault_injected", "step.nan") == 1
    assert ev.count("rollback", "train.step") == 1
    # training continued past the poisoned readback to a finite loss
    assert np.isfinite(hist["final_loss"])
    assert hist["resilience"]["resilience/rollback.train.step"] == 1


def test_wedged_loader_trips_watchdog(mesh, tmp_path, rng):
    """A data iterator that wedges mid-run: the watchdog fires, records
    the stall, and fit returns cleanly through the preemption path
    instead of hanging."""
    def stalling_data():
        src = _data(rng)
        for i, batch in enumerate(src):
            # past the upload worker's prefetch depth: the loop has
            # consumed batches (so step 1's compile, during which the
            # watchdog is paused, is over) before the wedge is reached;
            # at i == 2 a slow compile on a loaded machine hid it
            if i == 8:
                time.sleep(3.0)         # wedge >> watchdog timeout
            yield batch

    ev = R.EventLog("chaos")
    with R.use_event_log(ev):
        trainer = _make_trainer(mesh, tmp_path / "ck", event_log=ev,
                                watchdog_timeout=0.8)
        t0 = time.monotonic()
        hist = trainer.fit(stalling_data(), total_steps=200, save_every=50)
        elapsed = time.monotonic() - t0
    assert hist["watchdog_fired"] is True
    assert hist["preempted"] is True
    assert ev.count("watchdog_stall", "train.step") >= 1
    assert elapsed < 60                     # returned, did not hang
    trainer.checkpointer.wait_until_finished()
    assert trainer.checkpointer.latest_step() is not None
    trainer.checkpointer.close()


def test_watchdog_quiet_on_healthy_run(mesh, rng):
    ev = R.EventLog("chaos")
    with R.use_event_log(ev):
        trainer = _make_trainer(mesh, watchdog_timeout=30.0)
        hist = trainer.fit(_data(rng), total_steps=4)
    assert hist["watchdog_fired"] is False
    assert hist["preempted"] is False
    assert ev.count("watchdog_stall") == 0
    assert np.isfinite(hist["final_loss"])


def test_step_nan_rollback_under_pipelined_loop(mesh, rng):
    """ISSUE 5 satellite: the step.nan chaos scenario replayed under
    the sync-free loop (pipeline_depth=2, sampled telemetry off-hub) —
    the poisoned readback still lands in the loss window, still takes
    the detector path, and still rolls back exactly once."""
    ev = R.EventLog("chaos")
    plan = R.FaultPlan(
        [R.FaultSpec("step.nan", at=(3,), error="flag", times=1)])
    with R.use_event_log(ev), plan.installed():
        trainer = _make_trainer(mesh, pipeline_depth=2,
                                telemetry_sample_every=4)
        hist = trainer.fit(_data(rng), total_steps=8)
    assert ev.count("fault_injected", "step.nan") == 1
    assert ev.count("rollback", "train.step") == 1
    assert np.isfinite(hist["final_loss"])


def test_sigterm_checkpoints_last_settled_step_under_pipelining(
        mesh, tmp_path, rng):
    """Preemption under bounded in-flight dispatch: with up to 2 steps
    in flight at SIGTERM time, the exit save must persist the last
    SETTLED state — the checkpoint step equals the state's own step
    counter (every dispatched step settles before orbax serializes),
    never a torn in-between."""
    ev = R.EventLog("chaos")
    plan = R.FaultPlan(
        [R.FaultSpec("host.sigterm", at=(4,), error="flag", times=1)])
    with R.use_event_log(ev), plan.installed():
        trainer = _make_trainer(mesh, tmp_path / "ck", event_log=ev,
                                pipeline_depth=2)
        hist = trainer.fit(_data(rng), total_steps=50, save_every=10)
    assert hist["preempted"] is True
    trainer.checkpointer.wait_until_finished()
    saved = trainer.checkpointer.latest_step()
    state_step = int(jax.device_get(trainer.state.step))
    assert saved == state_step >= 4
    # the saved state is fully settled and finite
    restored = _make_trainer(mesh, tmp_path / "ck")
    assert restored.restore_checkpoint() == saved
    for leaf in jax.tree_util.tree_leaves(
            jax.device_get(restored.state.params)):
        assert np.all(np.isfinite(leaf))
    trainer.checkpointer.close()
    restored.checkpointer.close()


def test_chaos_run_from_env_plan(mesh, monkeypatch, rng):
    """The env-driven arming path: FLAXDIFF_FAULT_PLAN JSON installs a
    plan without code changes (how a real chaos job arms itself)."""
    plan = R.FaultPlan(
        [R.FaultSpec("step.nan", at=(2,), error="flag", times=1)])
    monkeypatch.setenv(R.faults.ENV_VAR, plan.to_json())
    # force a fresh env read, then restore whatever was active
    prev = R.install_plan(None)
    R.faults._env_loaded = False
    ev = R.EventLog("chaos")
    try:
        with R.use_event_log(ev):
            trainer = _make_trainer(mesh)
            trainer.fit(_data(rng), total_steps=4)
        assert ev.count("fault_injected", "step.nan") == 1
    finally:
        R.install_plan(prev)
