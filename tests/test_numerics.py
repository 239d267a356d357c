"""Training-health monitor (telemetry/numerics.py + memory.py): in-graph
aux vs a NumPy reference, cadence gating under jit and shard_map, the
in-graph skip_step gate, the numerics.nan chaos scenario (anomaly ->
provenance names the module -> rollback), the unified abnormal-loss
path, and HBM gauge smoke tests."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from flaxdiff_tpu import resilience as R
from flaxdiff_tpu import telemetry as T
from flaxdiff_tpu.predictors import EpsilonPredictionTransform
from flaxdiff_tpu.schedulers import CosineNoiseSchedule
from flaxdiff_tpu.trainer import (Checkpointer, DiffusionTrainer,
                                  TrainerConfig, TrainStepConfig,
                                  make_train_step)
from flaxdiff_tpu.trainer.train_state import TrainState


# -- in-graph aux vs NumPy reference ------------------------------------------

def _np_norm(tree):
    return math.sqrt(sum(float(np.sum(np.square(np.asarray(x, np.float32))))
                         for x in jax.tree_util.tree_leaves(tree)))


def test_numerics_aux_matches_numpy_reference():
    rng = np.random.default_rng(7)
    grads = {"enc": {"w": rng.normal(size=(4, 3)).astype(np.float32)},
             "dec": {"w": rng.normal(size=(5,)).astype(np.float32),
                     "b": rng.normal(size=(2, 2)).astype(np.float32)}}
    before = jax.tree_util.tree_map(
        lambda g: rng.normal(size=g.shape).astype(np.float32), grads)
    after = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, before, grads)

    aux = jax.device_get(jax.jit(T.numerics_aux)(
        jnp.float32(0.5), grads, before, after))

    assert aux["loss"] == pytest.approx(0.5)
    assert float(aux["grad_norm"]) == pytest.approx(_np_norm(grads),
                                                    rel=1e-5)
    assert float(aux["param_norm"]) == pytest.approx(_np_norm(after),
                                                     rel=1e-5)
    up = _np_norm(jax.tree_util.tree_map(lambda a, b: a - b, after, before))
    assert float(aux["update_norm"]) == pytest.approx(up, rel=1e-5)
    assert float(aux["update_ratio"]) == pytest.approx(
        up / _np_norm(before), rel=1e-5)
    assert float(aux["grad_nonfinite"]) == 0
    for mod in ("enc", "dec"):
        assert float(aux["module"][mod]["grad_norm"]) == pytest.approx(
            _np_norm(grads[mod]), rel=1e-5)
        assert float(aux["module"][mod]["update_ratio"]) == pytest.approx(
            0.1 * _np_norm(grads[mod]) / _np_norm(before[mod]), rel=1e-4)


def test_numerics_aux_counts_nonfinite_per_module():
    grads = {"ok": {"w": np.ones((3,), np.float32)},
             "bad": {"w": np.array([1.0, np.nan, np.inf], np.float32)}}
    params = jax.tree_util.tree_map(np.zeros_like, grads)
    aux = jax.device_get(jax.jit(T.numerics_aux)(
        jnp.float32(1.0), grads, params, params))
    assert float(aux["grad_nonfinite"]) == 2
    assert float(aux["module"]["bad"]["grad_nonfinite"]) == 2
    assert float(aux["module"]["ok"]["grad_nonfinite"]) == 0
    flat = T.flatten_aux(aux)
    assert flat["numerics/module/bad/grad_nonfinite"] == 2.0
    assert flat["numerics/grad_nonfinite"] == 2.0


def test_module_breakdown_descends_init_envelope():
    """The CLI hands model.init output through verbatim — a single-key
    `{"params": {...}}` envelope must not collapse the breakdown to one
    `params` row; leaf-holding single-module trees must NOT descend
    (kernel/bias are not modules)."""
    wrapped = {"params": {"down_0": {"w": np.ones((2,), np.float32)},
                          "up_0": {"w": np.ones((3,), np.float32)}}}
    assert sorted(T.top_level_modules(wrapped)) == ["down_0", "up_0"]
    inner, path = T.unwrap_module_tree(wrapped)
    assert path == ["params"] and sorted(inner) == ["down_0", "up_0"]
    single = {"Conv_0": {"kernel": np.ones((2,), np.float32)}}
    assert sorted(T.top_level_modules(single)) == ["Conv_0"]
    assert T.top_level_modules(np.ones((4,), np.float32)) == {}
    aux = jax.device_get(jax.jit(T.numerics_aux)(
        jnp.float32(1.0), wrapped, wrapped, wrapped))
    assert sorted(aux["module"]) == ["down_0", "up_0"]


# -- the anomaly detector ------------------------------------------------------

def _detector(**kw):
    hub = T.Telemetry(enabled=False)
    ev = R.EventLog("numerics")
    return T.AnomalyDetector(T.AnomalyConfig(**kw),
                             telemetry=hub, event_log=ev), hub, ev


class TestAnomalyDetector:
    def test_zscore_spike_fires_after_warmup_only(self):
        det, hub, ev = _detector(min_steps=5, zscore=4.0, window=10)
        rng = np.random.default_rng(0)
        for s in range(20):
            loss = 1.0 + 0.01 * float(rng.normal())
            assert det.observe(s, loss=loss, grad_norm=5.0) == []
        spikes = det.observe(20, loss=10.0, grad_norm=5.0)
        assert [a.kind for a in spikes] == ["loss_spike"]
        assert spikes[0].zscore > 4.0
        assert ev.count("anomaly", "numerics.loss_spike") == 1
        assert hub.counter("numerics/anomalies").value == 1
        # the spike never entered the EMA: normal values stay normal
        assert det.observe(21, loss=1.0, grad_norm=5.0) == []

    def test_grad_spike_is_independent_of_loss(self):
        det, _, _ = _detector(min_steps=3, zscore=4.0)
        rng = np.random.default_rng(1)
        for s in range(10):
            det.observe(s, loss=1.0 + 0.01 * float(rng.normal()),
                        grad_norm=2.0 + 0.01 * float(rng.normal()))
        out = det.observe(10, loss=1.0, grad_norm=50.0)
        assert [a.kind for a in out] == ["grad_spike"]

    def test_hard_triggers_bypass_warmup(self):
        det, hub, ev = _detector(min_steps=100)
        out = det.observe(1, loss=float("nan"), grad_norm=1.0)
        assert [a.kind for a in out] == ["nonfinite_loss"]
        out = det.observe(2, loss=1.0, grad_norm=1.0, grad_nonfinite=7)
        assert [a.kind for a in out] == ["nonfinite_grad"]
        assert hub.counter("numerics/nonfinite_steps").value == 2
        assert ev.count("anomaly") == 2

    def test_abnormal_loss_is_the_unified_hard_check(self):
        det, _, ev = _detector(abnormal_loss_floor=1e-8)
        assert det.abnormal_loss(0.37) is None
        assert det.abnormal_loss(float("inf")).kind == "nonfinite_loss"
        assert det.abnormal_loss(0.0).kind == "abnormal_loss"
        assert ev.count("anomaly", "numerics.abnormal_loss") == 1

    def test_unknown_action_rejected(self):
        with pytest.raises(ValueError, match="anomaly action"):
            T.AnomalyConfig(action="explode")


# -- the monitored train step (unit, no trainer) ------------------------------

def _tiny_model():
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

    return apply_fn, init_fn


def _unit_state(apply_fn, init_fn, seed=0):
    tx = optax.adam(1e-3)
    key = jax.random.PRNGKey(seed)
    init_key, train_key = jax.random.split(key)
    return TrainState.create(apply_fn=apply_fn, params=init_fn(init_key),
                             tx=tx, rng=train_key)


def test_skip_step_gates_nonfinite_update_in_graph(rng):
    """A batch that produces non-finite grads must leave params,
    opt-state and EMA bit-identical (the jnp.where gate), while a
    healthy batch moves them — and the aux reports the skip."""
    apply_fn, init_fn = _tiny_model()
    step = make_train_step(
        apply_fn, CosineNoiseSchedule(timesteps=100),
        EpsilonPredictionTransform(),
        TrainStepConfig(normalize=False),
        numerics=T.NumericsConfig(skip_nonfinite=True))
    jitted = jax.jit(step)
    state0 = _unit_state(apply_fn, init_fn)
    good = {"sample": rng.normal(size=(4, 8, 8, 1)).astype(np.float32)}
    bad = {"sample": np.full((4, 8, 8, 1), np.nan, np.float32)}

    state1, loss1, aux1 = jitted(state0, good)
    assert np.isfinite(float(loss1))
    assert float(aux1["skipped"]) == 0.0
    assert float(aux1["update_norm"]) > 0.0

    state2, loss2, aux2 = jitted(state1, bad)
    assert not np.isfinite(float(loss2))
    assert float(aux2["skipped"]) == 1.0
    assert float(aux2["grad_nonfinite"]) > 0
    for a, b in zip(jax.tree_util.tree_leaves(state2.params),
                    jax.tree_util.tree_leaves(state1.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(state2.ema_params),
                    jax.tree_util.tree_leaves(state1.ema_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the step counter still advanced: the next step folds a fresh rng
    assert int(state2.step) == int(state1.step) + 1

    # training continues cleanly past the gated step
    state3, loss3, aux3 = jitted(state2, good)
    assert np.isfinite(float(loss3)) and float(aux3["skipped"]) == 0.0


def test_monitored_step_under_shard_map(mesh, rng):
    """The numerics aux composes with a model whose forward runs inside
    shard_map over the mesh — per-module norms come out finite and the
    gradient flows to the replicated weights."""
    from jax import shard_map

    def smap(body, in_specs, out_specs):
        return shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    bspec = P(("data", "fsdp"))

    def apply_fn(params, x, t, cond):
        def body(scale, bias, xs):
            return jnp.tanh(xs * scale) + bias

        return smap(body, in_specs=(P(), P(), bspec),
                    out_specs=bspec)(params["scale"]["w"],
                                     params["bias"]["b"], x)

    def init_fn(key):
        return {"scale": {"w": jnp.ones(())},
                "bias": {"b": jnp.zeros(())}}

    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-2),
        schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(), mesh=mesh,
        config=TrainerConfig(normalize=False, log_every=2,
                             numerics_cadence=1))
    data = ({"sample": rng.normal(size=(8, 8, 8, 1)).astype(np.float32)}
            for _ in range(4))
    hub = T.Telemetry(enabled=False)
    with T.use_telemetry(hub):
        hist = trainer.fit(data, total_steps=3)
    assert np.isfinite(hist["final_loss"])
    assert hist["anomalies"] == 0
    # cadence-1 gauges landed on the hub for every step
    gn = hub.gauge("numerics/grad_norm").value
    assert np.isfinite(gn) and gn > 0
    assert hub.gauge("numerics/param_norm").value > 0


# -- fit-level integration -----------------------------------------------------

def _make_trainer(mesh, tmp_path=None, telemetry=None, **cfg_kw):
    apply_fn, init_fn = _tiny_model()
    ckpt = Checkpointer(str(tmp_path)) if tmp_path is not None else None
    return DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
        schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(), mesh=mesh,
        config=TrainerConfig(normalize=False, log_every=2, **cfg_kw),
        checkpointer=ckpt, telemetry=telemetry)


def _data(rng, batch=8):
    while True:
        yield {"sample": rng.normal(size=(batch, 8, 8, 1))
               .astype(np.float32)}


def test_trainer_rejects_unknown_anomaly_action(mesh):
    with pytest.raises(ValueError, match="anomaly_action"):
        _make_trainer(mesh, anomaly_action="explode")


def test_cadence_gating_exports_rows_only_on_cadence(mesh, tmp_path, rng):
    """numerics rows land exactly every N steps; off-cadence steps run
    the unmonitored program (no row, no aux)."""
    tel = T.Telemetry.create(str(tmp_path / "tel"))
    with T.use_telemetry(tel):
        trainer = _make_trainer(mesh, telemetry=tel, numerics_cadence=2)
        hist = trainer.fit(_data(rng), total_steps=6)
    tel.close()
    assert np.isfinite(hist["final_loss"])
    recs = [json.loads(x)
            for x in open(tmp_path / "tel" / "telemetry.jsonl")]
    rows = [r for r in recs if r.get("type") == "numerics"]
    assert [r["step"] for r in rows] == [2, 4, 6]
    for r in rows:
        assert r["numerics/grad_norm"] > 0
        assert r["numerics/update_ratio"] > 0
        assert r["numerics/grad_nonfinite"] == 0
        assert "numerics/module/Conv_0/grad_norm" in r
        assert "numerics/module/Conv_1/update_ratio" in r
    # the numerics phase exists only on cadence steps
    phase_rows = [r for r in recs if r.get("type") == "step_phases"]
    with_aux = [r for r in phase_rows if "numerics" in r]
    assert sorted(int(r["step"]) for r in with_aux) == [2, 4, 6]
    # registry carries the summary gauges (not the per-module series)
    snap = tel.registry.snapshot()
    assert snap["numerics/grad_norm"] > 0
    assert not any(k.startswith("numerics/module/") for k in snap)


def test_numerics_nan_chaos_provenance_and_rollback(mesh, tmp_path, rng):
    """ISSUE 4 acceptance: a planted non-finite gradient (numerics.nan
    corrupts Conv_0's params) fires the anomaly, the provenance pass
    names Conv_0 — not its backprop victims — and the rollback action
    restores the best state; diagnose_run renders it all."""
    tel = T.Telemetry.create(str(tmp_path / "tel"))
    plan = R.FaultPlan(
        [R.FaultSpec("numerics.nan", at=(3,), error="flag", times=1)])
    ev = R.EventLog("chaos")
    with T.use_telemetry(tel), R.use_event_log(ev), plan.installed():
        trainer = _make_trainer(mesh, telemetry=tel, numerics_cadence=1,
                                anomaly_action="rollback")
        hist = trainer.fit(_data(rng), total_steps=8)
    tel.close()

    assert ev.count("fault_injected", "numerics.nan") == 1
    assert ev.count("anomaly", "numerics.nonfinite_grad") >= 1
    assert ev.count("rollback", "train.step") >= 1
    prov = ev.events("nan_provenance")
    assert len(prov) == 1 and "Conv_0" in prov[0].detail \
        and "Conv_1" not in prov[0].detail
    # recovered: training continued to a finite loss
    assert np.isfinite(hist["final_loss"])
    assert hist["anomalies"] >= 1

    recs = [json.loads(x)
            for x in open(tmp_path / "tel" / "telemetry.jsonl")]
    assert any(r.get("type") == "numerics_anomaly"
               and r.get("action") == "rollback" for r in recs)
    prov_rows = [r for r in recs if r.get("type") == "nan_provenance"]
    assert prov_rows and prov_rows[0]["modules"] == ["Conv_0"]

    import contextlib
    import io
    from scripts.diagnose_run import main as diagnose
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert diagnose([str(tmp_path / "tel")]) == 0
    out = buf.getvalue()
    assert "Training health" in out
    assert "nonfinite_grad" in out
    assert "nan provenance" in out and "Conv_0" in out


def test_skip_step_action_absorbs_poisoned_batch(mesh, tmp_path, rng):
    """skip_step's end-to-end story: ONE poisoned batch mid-run fires
    the anomaly, the in-graph gate withholds the update (state never
    moves — zero update norm on the poisoned row), and training
    continues finite on the next batch with no rollback needed."""
    def data():
        src = _data(rng)
        for i, batch in enumerate(src):
            if i == 2:          # consumed by step 3 — NOT a log-cadence
                #                 step, so only the in-graph gate acts
                batch = {"sample": np.full((8, 8, 8, 1), np.nan,
                                           np.float32)}
            yield batch

    tel = T.Telemetry.create(str(tmp_path / "tel"))
    ev = R.EventLog("chaos")
    with T.use_telemetry(tel), R.use_event_log(ev):
        trainer = _make_trainer(mesh, telemetry=tel, numerics_cadence=1,
                                anomaly_action="skip_step")
        hist = trainer.fit(data(), total_steps=7)
    tel.close()
    assert ev.count("anomaly", "numerics.nonfinite_grad") == 1
    assert ev.count("skip_step", "numerics.skip") == 1
    assert ev.count("rollback", "train.step") == 0      # never needed
    assert tel.counter("numerics/skipped_steps").value == 1
    assert np.isfinite(hist["final_loss"])
    # the gate held the params still: the poisoned-step row reports
    # zero update norm alongside the non-finite grads
    recs = [json.loads(x)
            for x in open(tmp_path / "tel" / "telemetry.jsonl")]
    poisoned = [r for r in recs if r.get("type") == "numerics"
                and r.get("numerics/skipped", 0) > 0]
    assert len(poisoned) == 1
    assert poisoned[0]["numerics/update_norm"] == 0.0
    assert poisoned[0]["numerics/grad_nonfinite"] > 0
    # every healthy row really did move the state
    healthy = [r for r in recs if r.get("type") == "numerics"
               and r.get("numerics/skipped", 1) == 0]
    assert healthy and all(r["numerics/update_norm"] > 0 for r in healthy)


def test_step_nan_fault_takes_the_detector_path(mesh, rng):
    """Satellite: the trainer's two historical `isfinite or <= floor`
    sites now run through AnomalyDetector.abnormal_loss — a
    fault-injected NaN shows up as a numerics anomaly AND the legacy
    rollback event."""
    hub = T.Telemetry(enabled=False)
    plan = R.FaultPlan(
        [R.FaultSpec("step.nan", at=(3,), error="flag", times=1)])
    ev = R.EventLog("chaos")
    with T.use_telemetry(hub), R.use_event_log(ev), plan.installed():
        trainer = _make_trainer(mesh)
        hist = trainer.fit(_data(rng), total_steps=8)
    assert ev.count("rollback", "train.step") == 1
    assert ev.count("anomaly", "numerics.nonfinite_loss") == 1
    assert hub.counter("numerics/anomalies").value >= 1
    assert np.isfinite(hist["final_loss"])


def test_rollback_without_best_state_restores_checkpoint(
        mesh, tmp_path, rng):
    """The rollback action's checkpointer wiring: no best state yet
    (keep_best_state off) but a saved step on disk — _recover walks
    back to it instead of continuing on NaN params."""
    ev = R.EventLog("chaos")
    plan = R.FaultPlan(
        [R.FaultSpec("numerics.nan", at=(4,), error="flag", times=1)])
    with R.use_event_log(ev), plan.installed():
        trainer = _make_trainer(mesh, tmp_path / "ck",
                                numerics_cadence=1,
                                anomaly_action="rollback",
                                keep_best_state=False)
        hist = trainer.fit(_data(rng), total_steps=8, save_every=2)
        trainer.checkpointer.wait_until_finished()
    trainer.checkpointer.close()
    rollbacks = ev.events("rollback")
    assert rollbacks and any("checkpoint" in e.detail for e in rollbacks)
    assert np.isfinite(hist["final_loss"])


# -- HBM gauges ----------------------------------------------------------------

class TestMemoryMonitor:
    class _Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            if isinstance(self._stats, Exception):
                raise self._stats
            return self._stats

    def test_reduces_over_devices(self):
        mon = T.MemoryMonitor(devices=[
            self._Dev({"bytes_in_use": 100, "peak_bytes_in_use": 150,
                       "bytes_limit": 1000}),
            self._Dev({"bytes_in_use": 700, "peak_bytes_in_use": 800,
                       "bytes_limit": 1000})])
        s = mon.sample()
        assert s["memory/bytes_in_use"] == 700      # fullest chip
        assert s["memory/peak_bytes_in_use"] == 800
        assert s["memory/bytes_limit"] == 1000
        assert s["memory/utilization"] == pytest.approx(0.7)
        assert s["memory/devices"] == 2.0

    def test_watermark_spans_samples_and_resets_on_record(self):
        stats = {"bytes_in_use": 500, "bytes_limit": 1000}
        dev = self._Dev(stats)
        mon = T.MemoryMonitor(devices=[dev])
        mon.sample()
        stats["bytes_in_use"] = 200
        reg = T.MetricsRegistry()
        out = mon.record(reg)
        assert out["memory/step_watermark_bytes"] == 500    # the max seen
        assert reg.snapshot()["memory/bytes_in_use"] == 200.0
        stats["bytes_in_use"] = 300
        assert mon.sample()["memory/step_watermark_bytes"] == 300

    def test_backends_without_stats_fall_back_to_host_rss(self):
        """Off-TPU the monitor no longer goes dark: it reports process
        RSS from /proc/self/statm — and the host keys are DISJOINT
        from the HBM keys, so an HBM probe reads None, never a host
        number masquerading as device memory."""
        for dev in (self._Dev(None), self._Dev(RuntimeError("no stats"))):
            mon = T.MemoryMonitor(devices=[dev])
            s = mon.sample()
            assert not mon.disabled
            assert s["memory/host_rss_bytes"] > 0
            assert s["memory/host_vms_bytes"] >= s["memory/host_rss_bytes"]
            assert s["memory/host_rss_peak_bytes"] >= \
                s["memory/host_rss_bytes"]
            assert "memory/bytes_in_use" not in s
            assert s.get("memory/peak_bytes_in_use") is None
            reg = T.MetricsRegistry()
            mon.record(reg)
            assert reg.snapshot()["memory/host_rss_bytes"] > 0

    def test_no_stats_and_no_procfs_disables_quietly(self, tmp_path):
        """Non-Linux shape: no allocator stats AND no statm file —
        the old disabled latch stands."""
        mon = T.MemoryMonitor(devices=[self._Dev(None)],
                              statm_path=str(tmp_path / "missing"))
        assert mon.sample() == {}
        assert mon.disabled
        assert mon.record(T.MetricsRegistry()) == {}

    def test_real_backend_smoke(self):
        """Whatever this backend reports (CPU: host RSS), sampling and
        recording must not raise."""
        mon = T.MemoryMonitor()
        reg = T.MetricsRegistry()
        out = mon.record(reg)
        assert isinstance(out, dict)
        if "memory/bytes_in_use" in out:
            assert out["memory/bytes_in_use"] >= 0
        elif out:
            assert out["memory/host_rss_bytes"] > 0


# -- per-module update-ratio z-scoring (ISSUE 9 satellite) ---------------------

class TestModuleUpdateRatioZscore:
    def test_single_module_spike_is_named_and_soft(self):
        """One module's effective-LR running away fires an
        `update_ratio_spike` naming THAT module; steady modules stay
        silent; the spike is soft (never justifies rollback) and never
        updates the module's EMA."""
        det, hub, ev = _detector(min_steps=3, zscore=4.0, window=10)

        def flat(ratio_b):
            return {"numerics/loss": 1.0, "numerics/grad_norm": 1.0,
                    "numerics/grad_nonfinite": 0.0,
                    "numerics/module/enc/update_ratio": 1e-3,
                    "numerics/module/dec/update_ratio": ratio_b}

        for s in range(12):
            assert det.observe_aux(s, flat(2e-3)) == []
        out = det.observe_aux(12, flat(0.5))
        assert [a.kind for a in out] == ["update_ratio_spike"]
        assert out[0].metric == "module/dec/update_ratio"
        assert not out[0].hard
        assert ev.count("anomaly", "numerics.update_ratio_spike") == 1
        assert hub.counter("numerics/anomalies").value == 1
        # the spike stayed out of dec's EMA: normal values stay normal
        assert det.observe_aux(13, flat(2e-3)) == []

    def test_hard_anomaly_skips_module_pass(self):
        """A gated/poisoned step's ratios are artifacts — they must not
        teach the module EMAs (nor fire spikes of their own)."""
        det, _, _ = _detector(min_steps=1, zscore=4.0)
        bad = {"numerics/loss": float("nan"),
               "numerics/grad_norm": 1.0,
               "numerics/grad_nonfinite": 3.0,
               "numerics/module/enc/update_ratio": 99.0}
        out = det.observe_aux(1, bad)
        assert all(a.hard for a in out)
        assert det._mod_ratio == {}     # module EMAs never touched

    def test_module_ratio_extraction(self):
        flat = {"numerics/module/enc/update_ratio": 0.25,
                "numerics/module/enc/grad_norm": 7.0,
                "numerics/update_ratio": 0.5,
                "numerics/loss": 1.0}
        assert T.AnomalyDetector.module_update_ratios(flat) == {
            "enc": 0.25}


# -- per-leaf nonfinite-gate visibility counter (ISSUE 9 satellite) ------------

def test_gate_counter_counts_masked_elements_in_graph(rng):
    """With TrainState.gate_events carried, the elementwise gate
    accumulates how many params/opt/EMA elements it masked — zero on a
    healthy step, every element of the poisoned update on a NaN batch —
    while the gating semantics stay bit-identical (state unchanged)."""
    apply_fn, init_fn = _tiny_model()
    step = make_train_step(
        apply_fn, CosineNoiseSchedule(timesteps=100),
        EpsilonPredictionTransform(), TrainStepConfig(normalize=False),
        gate_nonfinite=True)
    jitted = jax.jit(step)
    tx = optax.adam(1e-3)
    init_key, train_key = jax.random.split(jax.random.PRNGKey(0))
    state0 = TrainState.create(apply_fn=apply_fn,
                               params=init_fn(init_key), tx=tx,
                               rng=train_key, gate_counter=True)
    assert state0.gate_events.shape == (3,)
    good = {"sample": rng.normal(size=(4, 8, 8, 1)).astype(np.float32)}
    bad = {"sample": np.full((4, 8, 8, 1), np.nan, np.float32)}

    state1, _ = jitted(state0, good)
    counts1 = np.asarray(state1.gate_events)
    assert counts1.sum() == 0

    n_params = sum(int(np.asarray(l).size) for l in
                   jax.tree_util.tree_leaves(state1.params))
    state2, loss2 = jitted(state1, bad)
    counts2 = np.asarray(state2.gate_events)
    assert not np.isfinite(float(loss2))
    # a NaN loss poisons every update element: params and EMA each count
    # their full size, adam's m/v double it
    assert counts2[0] == n_params and counts2[2] == n_params
    assert counts2[1] == 2 * n_params
    for a, b in zip(jax.tree_util.tree_leaves(state2.params),
                    jax.tree_util.tree_leaves(state1.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # cumulative: a second poisoned step doubles the account
    state3, _ = jitted(state2, bad)
    assert np.asarray(state3.gate_events).sum() == 2 * counts2.sum()


def test_gate_counter_counts_in_monitored_twin(rng):
    """The monitored (cadence) program gates with the global verdict —
    it must keep the SAME visibility account or cadence steps would be
    a hole in the series."""
    apply_fn, init_fn = _tiny_model()
    step = make_train_step(
        apply_fn, CosineNoiseSchedule(timesteps=100),
        EpsilonPredictionTransform(), TrainStepConfig(normalize=False),
        numerics=T.NumericsConfig(skip_nonfinite=True),
        gate_nonfinite=True)
    jitted = jax.jit(step)
    tx = optax.adam(1e-3)
    init_key, train_key = jax.random.split(jax.random.PRNGKey(0))
    state0 = TrainState.create(apply_fn=apply_fn,
                               params=init_fn(init_key), tx=tx,
                               rng=train_key, gate_counter=True)
    good = {"sample": rng.normal(size=(4, 8, 8, 1)).astype(np.float32)}
    bad = {"sample": np.full((4, 8, 8, 1), np.nan, np.float32)}

    state1, _, aux1 = jitted(state0, good)
    assert np.asarray(state1.gate_events).sum() == 0
    assert float(aux1["skipped"]) == 0.0

    state2, _, aux2 = jitted(state1, bad)
    assert float(aux2["skipped"]) == 1.0
    assert np.asarray(state2.gate_events).sum() > 0


def test_gate_counter_requires_gate_nonfinite(mesh):
    import flax.linen as nn

    with pytest.raises(ValueError, match="gate_counter"):
        DiffusionTrainer(
            apply_fn=lambda p, x, t, c: x,
            init_fn=lambda k: {"w": jnp.zeros((2,))},
            tx=optax.adam(1e-3),
            schedule=CosineNoiseSchedule(timesteps=100),
            transform=EpsilonPredictionTransform(), mesh=mesh,
            config=TrainerConfig(gate_counter=True,
                                 gate_nonfinite=False))
