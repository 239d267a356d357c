"""Telemetry subsystem (flaxdiff_tpu/telemetry/): metrics registry +
exporters, step-phase timing, goodput ledger, cross-host aggregation,
trace spans — plus the end-to-end acceptance run: a CPU `fit` under
fault injection whose JSONL stream carries per-step phases and pod
aggregates, whose goodput account sums to wall-clock, and whose badput
is attributed across a simulated restart."""
import json
import os
import threading
import time

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from flaxdiff_tpu import resilience as R
from flaxdiff_tpu import telemetry as T
from flaxdiff_tpu.predictors import EpsilonPredictionTransform
from flaxdiff_tpu.schedulers import CosineNoiseSchedule
from flaxdiff_tpu.trainer import Checkpointer, DiffusionTrainer, TrainerConfig


# -- metrics registry ---------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_gauge_roundtrip(self):
        reg = T.MetricsRegistry()
        reg.counter("c").inc()
        reg.counter("c").inc(2.5)
        reg.gauge("g").set(7)
        snap = reg.snapshot()
        assert snap["c"] == 3.5 and snap["g"] == 7.0

    def test_type_confusion_raises(self):
        reg = T.MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("x")

    def test_histogram_stats_and_percentiles(self):
        reg = T.MetricsRegistry()
        h = reg.histogram("lat")
        for v in [0.01] * 90 + [1.0] * 10:
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 100
        assert snap["min"] == 0.01 and snap["max"] == 1.0
        assert snap["p50"] <= 0.05          # bulk sits in the small bucket
        assert snap["p99"] >= 0.5           # tail sees the slow bucket
        flat = reg.snapshot()
        assert flat["lat/count"] == 100.0

    def test_series_cap_degrades_with_counter(self):
        reg = T.MetricsRegistry(max_series=2)
        reg.counter("a")
        reg.counter("b")
        c = reg.counter("c")                 # past the cap: shared no-op
        c.inc(100)
        snap = reg.snapshot()
        assert "c" not in snap
        assert snap["telemetry/dropped_series"] == 1.0
        # bounded memory: a cardinality bug cannot grow the registry
        for i in range(50):
            reg.histogram(f"h{i}").observe(1.0)
        assert len(reg.snapshot()) <= 4      # a, b, dropped counter (+step)


def test_jsonl_exporter_stream(tmp_path):
    ex = T.JsonlExporter(str(tmp_path / "t.jsonl"))
    ex.export({"a": 1.0}, step=3)
    ex.write({"type": "step_phases", "step": 1, "wall": 0.5})
    ex.close()
    recs = [json.loads(x) for x in open(tmp_path / "t.jsonl")]
    assert recs[0]["type"] == "metrics" and recs[0]["step"] == 3
    assert recs[1]["type"] == "step_phases" and "_time" in recs[1]


def test_raw_records_carry_epoch_tag(tmp_path):
    """Every raw JSONL row is stamped with the hub's epoch (the PR-3
    carried-over follow-up): rows written by a stale same-incarnation
    driver after a coordinated restart voted a new epoch stay
    distinguishable row by row, not just file by file."""
    path = tmp_path / "t.jsonl"
    hub = T.Telemetry(exporters=[T.JsonlExporter(str(path))])
    assert hub.epoch == hub.goodput.incarnation   # default epoch source
    hub.record_step({"step": 1, "wall": 0.5})
    hub.set_epoch(7)                              # pod-agreed epoch wins
    hub.record_step({"step": 2, "wall": 0.5})
    hub.write_record({"type": "custom", "epoch": 99})  # caller's wins
    hub.close()
    recs = [json.loads(x) for x in open(path)]
    assert recs[0]["epoch"] == hub.goodput.incarnation
    assert recs[1]["epoch"] == 7
    assert recs[2]["epoch"] == 99


def test_prometheus_textfile_atomic_format(tmp_path):
    path = tmp_path / "metrics.prom"
    ex = T.PrometheusTextfileExporter(str(path))
    ex.export({"phase/wall/p99": 0.25, "weird name!": 2.0,
               "skip_nan": float("nan")}, step=7)
    text = path.read_text()
    assert "flaxdiff_step 7" in text
    assert "flaxdiff_phase_wall_p99 0.25" in text
    assert "flaxdiff_weird_name_ 2.0" in text
    assert "nan" not in text.lower()
    assert not os.path.exists(str(path) + ".tmp")   # atomic rename


def test_logger_exporter_fans_into_trainer_logger(tmp_path):
    from flaxdiff_tpu.trainer.logging import JsonlLogger
    lg = JsonlLogger(str(tmp_path / "train.jsonl"))
    ex = T.LoggerExporter(lg)
    ex.export({"m": 1.5}, step=2)
    lg.finish()
    rec = json.loads(open(tmp_path / "train.jsonl").read())
    assert rec["m"] == 1.5 and rec["step"] == 2


# -- step-phase timer ---------------------------------------------------------

class TestStepPhaseTimer:
    def test_phases_sum_to_wall_clock(self):
        """The load-bearing invariant: tracked phases + the `other`
        residual equal the step's wall-clock (within clock tolerance)."""
        reg = T.MetricsRegistry()
        timer = T.StepPhaseTimer(registry=reg)
        timer.begin_step(1)
        with timer.phase("data_wait"):
            time.sleep(0.02)
        with timer.phase("host"):
            time.sleep(0.01)
        with timer.phase("device"):
            time.sleep(0.03)
        time.sleep(0.01)                     # untracked -> "other"
        out = timer.end_step()
        parts = sum(v for k, v in out.items()
                    if k not in ("wall", "step"))
        assert abs(parts - out["wall"]) < 1e-6 * max(out["wall"], 1.0)
        assert out["data_wait"] >= 0.02 and out["device"] >= 0.03
        assert out["other"] >= 0.009
        assert out["step"] == 1.0
        assert reg.histogram("phase/device").count == 1

    def test_end_without_begin_raises(self):
        timer = T.StepPhaseTimer()
        timer.begin_step(1)
        timer.end_step()
        with pytest.raises(RuntimeError, match="begin_step"):
            timer.end_step()

    def test_device_phase_feeds_mfu_meter(self):
        from flaxdiff_tpu.profiling import MFUMeter
        meter = MFUMeter(flops_per_step=1e9, peak_flops=1e12)
        timer = T.StepPhaseTimer(mfu_meter=meter)
        timer.begin_step(1)
        with timer.phase("device"):
            time.sleep(0.01)
        timer.end_step()
        assert meter.steps == 1
        assert meter.mean_step_time() >= 0.01


# -- goodput ledger -----------------------------------------------------------

class TestGoodputLedger:
    def test_totals_and_fraction(self):
        g = T.GoodputLedger()
        g.record_productive(8.0)
        g.record_badput("compile", 1.0)
        g.record_badput("data_stall", 1.0)
        t = g.totals()
        assert t["total_s"] == 10.0
        assert t["goodput_fraction"] == pytest.approx(0.8)

    def test_measure_badput_context(self):
        g = T.GoodputLedger()
        with g.measure_badput("restart"):
            time.sleep(0.02)
        assert g.totals()["badput_s"]["restart"] >= 0.02

    def test_persists_cumulatively_across_incarnations(self, tmp_path):
        path = str(tmp_path / "goodput.json")
        g1 = T.GoodputLedger(path)
        assert g1.incarnation == 1
        g1.record_productive(5.0)
        g1.record_badput("compile", 2.0)
        g1.persist()
        g2 = T.GoodputLedger(path)
        assert g2.incarnation == 2
        g2.record_productive(3.0)
        g2.record_badput("restart", 1.0)
        g2.persist()
        on_disk = json.load(open(path))
        assert on_disk["incarnations"] == 2
        assert on_disk["productive_s"] == pytest.approx(8.0)
        assert on_disk["badput_s"]["compile"] == pytest.approx(2.0)
        assert on_disk["badput_s"]["restart"] == pytest.approx(1.0)

    def test_torn_file_starts_fresh(self, tmp_path):
        path = tmp_path / "goodput.json"
        path.write_text('{"productive_s": 5.0, "inc')
        g = T.GoodputLedger(str(path))
        assert g.incarnation == 1
        assert g.totals()["productive_s"] == 0.0

    def test_partially_corrupt_file_starts_fully_fresh(self, tmp_path):
        """Valid JSON with a corrupt badput_s must not keep the prior
        productive seconds while zeroing badput — that would inflate
        goodput_fraction cumulatively. Fresh means ALL fields fresh."""
        path = tmp_path / "goodput.json"
        path.write_text(json.dumps({"productive_s": 500.0,
                                    "badput_s": {"compile": "garbage"},
                                    "incarnations": 7}))
        g = T.GoodputLedger(str(path))
        assert g.incarnation == 1
        t = g.totals()
        assert t["productive_s"] == 0.0 and t["badput_s"] == {}

    def test_nonzero_rank_never_writes(self, tmp_path):
        path = str(tmp_path / "goodput.json")
        g = T.GoodputLedger(path, process_index=3)
        g.record_productive(1.0)
        g.persist()
        assert not os.path.exists(path)


# -- cross-host aggregation ---------------------------------------------------

def test_aggregator_world_of_four_stats():
    transports = R.InMemoryTransport.make_world(4)
    aggs = [T.CrossHostAggregator(t, timeout=5.0) for t in transports]
    results = [None] * 4

    def run(rank):
        results[rank] = aggs[rank].aggregate(
            {"step_time": 0.1 * (rank + 1), "only_on_0": 7.0}
            if rank == 0 else {"step_time": 0.1 * (rank + 1)})

    threads = [threading.Thread(target=run, args=(r,)) for r in range(1, 4)]
    for t in threads:
        t.start()
    run(0)
    for t in threads:
        t.join()
    # every host computed the identical reduction
    assert all(r == results[0] for r in results[1:])
    st = results[0]["step_time"]
    assert st["min"] == pytest.approx(0.1)
    assert st["max"] == pytest.approx(0.4)
    assert st["mean"] == pytest.approx(0.25)
    assert st["hosts"] == 4.0
    assert st["spread"] == pytest.approx((0.4 - 0.1) / 0.25)
    assert st["min"] <= st["p50"] <= st["p99"] <= st["max"]
    # metrics missing on some hosts reduce over reporters only
    assert results[0]["only_on_0"]["hosts"] == 1.0
    flat = T.CrossHostAggregator.flatten(results[0])
    assert flat["pod/step_time/max"] == pytest.approx(0.4)


def test_hub_aggregate_timeout_degrades_not_dies():
    """A dead peer turns aggregation off (telemetry_lost event); it
    must never kill training."""
    t0, _t1 = R.InMemoryTransport.make_world(2)   # peer never calls
    hub = T.Telemetry(aggregator=T.CrossHostAggregator(t0, timeout=0.2))
    ev = R.EventLog("t")
    with R.use_event_log(ev):
        assert hub.aggregate({"x": 1.0}) is None
    assert hub.aggregator.disabled
    assert ev.count("telemetry_lost", "telemetry.aggregate") == 1
    assert hub.aggregate({"x": 1.0}) is None      # stays off, stays quiet


def test_hub_aggregate_swallows_non_timeout_failures():
    """'Metrics must never kill a run' covers EVERY failure mode, not
    just BarrierTimeout: a malformed peer payload or transport bug
    records telemetry_lost and degrades instead of raising into fit."""
    class BrokenTransport(R.InMemoryTransport):
        def allgather_json(self, name, obj, timeout):
            raise TypeError("malformed peer payload")

    t0 = BrokenTransport.make_world(1)[0]
    hub = T.Telemetry(aggregator=T.CrossHostAggregator(t0, timeout=0.2))
    ev = R.EventLog("t")
    with R.use_event_log(ev):
        assert hub.aggregate({"x": 1.0}) is None      # no raise
        assert hub.aggregator.disabled
        assert hub.aggregate({"x": 1.0}) is None      # stays quiet
    events = ev.events(kind="telemetry_lost")
    assert len(events) == 1 and "TypeError" in events[0].detail


def test_disable_tombstone_propagates_without_stall():
    """A disabled host publishes a non-blocking tombstone each round;
    the surviving peer's NEXT gather sees it and disables too instead
    of blocking for the full timeout at every log cadence."""
    t0, t1 = R.InMemoryTransport.make_world(2)
    hub0 = T.Telemetry(aggregator=T.CrossHostAggregator(t0, timeout=5.0))
    hub1 = T.Telemetry(aggregator=T.CrossHostAggregator(t1, timeout=5.0))
    hub0.aggregator.disabled = True           # host 0 failed earlier
    ev = R.EventLog("t")
    with R.use_event_log(ev):
        res0 = [None]
        th = threading.Thread(
            target=lambda: res0.__setitem__(0, hub0.aggregate({"x": 1.0})))
        th.start()
        t_start = time.perf_counter()
        assert hub1.aggregate({"x": 2.0}) is None
        elapsed = time.perf_counter() - t_start
        th.join()
    assert res0[0] is None
    assert hub1.aggregator.disabled           # propagated in one round
    assert elapsed < 2.0                      # no 5s timeout stall
    assert ev.count("telemetry_lost", "telemetry.aggregate") == 1
    # both sides now fully degraded and non-blocking
    assert hub0.aggregate({"x": 1.0}) is None
    assert hub1.aggregate({"x": 2.0}) is None


# -- tracing ------------------------------------------------------------------

class TestTraceRecorder:
    def test_spans_write_valid_chrome_trace(self, tmp_path):
        rec = T.TraceRecorder(str(tmp_path / "trace.json"), pid=2)
        with rec.span("fit", cat="train", args={"steps": 3}):
            with rec.span("step"):
                pass
        rec.instant("preempt")
        path = rec.save()
        doc = json.load(open(path))
        events = doc["traceEvents"]
        spans = {e["name"]: e for e in events if e["ph"] == "X"}
        assert set(spans) == {"fit", "step"}
        assert spans["step"]["dur"] <= spans["fit"]["dur"]
        assert all(e["pid"] == 2 for e in events if e["ph"] == "X")
        assert any(e["ph"] == "i" and e["name"] == "preempt"
                   for e in events)

    def test_error_span_closes_and_marks(self, tmp_path):
        rec = T.TraceRecorder(str(tmp_path / "trace.json"))
        with pytest.raises(ValueError):
            with rec.span("bad"):
                raise ValueError("boom")
        doc = json.load(open(rec.save()))
        bad = [e for e in doc["traceEvents"] if e.get("name") == "bad"][0]
        assert bad["args"]["error"] is True

    def test_bounded_events_count_drops(self, tmp_path):
        rec = T.TraceRecorder(str(tmp_path / "t.json"), max_events=3)
        for _ in range(10):
            with rec.span("s"):
                pass
        doc = json.load(open(rec.save()))
        assert len(doc["traceEvents"]) == 3
        assert doc["flaxdiff_dropped_events"] == 8


# -- fit end-to-end (the acceptance scenario) ---------------------------------

def _make_trainer(mesh, tmp_path=None, telemetry=None, **cfg_kw):
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


def test_fit_telemetry_acceptance(mesh, tmp_path, rng):
    """ISSUE 3 acceptance: CPU fit with fault injection -> the JSONL
    stream holds per-step phase timings and pod aggregates (via
    InMemoryTransport); productive+badput holds every step row and
    closes against fit's wall-clock; diagnose_run renders; the trace
    file is valid Chrome JSON."""
    tel = T.Telemetry.create(str(tmp_path / "tel"),
                             transport=R.InMemoryTransport.make_world(1)[0])
    plan = R.FaultPlan(
        [R.FaultSpec("step.nan", at=(3,), error="flag", times=1)])
    with T.use_telemetry(tel), plan.installed():
        trainer = _make_trainer(mesh, tmp_path / "ck", telemetry=tel)
        t0 = time.perf_counter()
        hist = trainer.fit(_data(rng), total_steps=6, save_every=2)
        wall = time.perf_counter() - t0
        trainer.checkpointer.wait_until_finished()
    tel.close()
    trainer.checkpointer.close()

    # per-step phase rows, one per executed step, phases summing to wall
    recs = [json.loads(x) for x in open(tmp_path / "tel" / "telemetry.jsonl")]
    steps = [r for r in recs if r.get("type") == "step_phases"]
    assert len(steps) == 6
    for r in steps:
        assert {"host", "other", "wall", "step"} <= set(r)
        # "epoch" is the row's incarnation tag (PR 8), not a phase
        parts = sum(v for k, v in r.items()
                    if k not in ("type", "step", "wall", "_time",
                                 "epoch"))
        assert parts == pytest.approx(r["wall"], rel=1e-3, abs=1e-5)
    assert any("device" in r for r in steps)       # block_until_ready ran
    assert any(r.get("checkpoint", 0) > 0 for r in steps)

    # pod aggregates over the in-memory transport
    pods = [r for r in recs if r.get("type") == "pod_metrics"]
    assert pods and pods[-1]["world"] == 1
    assert "pod/step_time/mean" in pods[-1]
    assert "pod/step_time/p99" in pods[-1]

    # metrics snapshots carry the fault's rollback counter
    metrics = [r for r in recs if r.get("type") == "metrics"]
    assert metrics and metrics[-1]["goodput/fraction"] > 0

    # The goodput account closes. Against its own clocks, under any load:
    # every second of a step row is in it (what it holds beyond the rows
    # is measured outside them: the final save, the rollback's restore).
    g = json.load(open(tmp_path / "tel" / "goodput.json"))
    attributed = g["productive_s"] + sum(g["badput_s"].values())
    in_rows = sum(r["wall"] for r in steps)
    assert in_rows <= attributed * (1 + 1e-3), (in_rows, attributed)
    # Against the test's clock around `fit`: never more than it, and
    # short of it by fit's prologue and epilogue only (thread start, the
    # upload worker's join, the telemetry flush: host work outside every
    # step). That remainder was held to 5% of a 3.4 s wall, which a
    # 0.34 s stall beside six busy workers broke (9.97%, the largest of
    # 19 loaded runs in PR 42; the driver's failed run at PR 41). It is
    # held to 15% now; the rows above hold the account itself.
    assert attributed <= wall * (1 + 1e-3), (attributed, wall)
    assert (wall - attributed) / wall < 0.15, (attributed, wall)
    assert g["badput_s"]["compile"] > 0
    assert g["badput_s"]["checkpoint_commit"] > 0
    assert hist["goodput"]["productive_s"] > 0

    # trace file: valid Chrome trace-event JSON with checkpoint spans
    doc = json.load(open(tmp_path / "tel" / "trace.json"))
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert "ckpt.save" in names and "ckpt.final_save" in names

    # diagnose_run renders the report from the same stream
    import contextlib
    import io
    from scripts.diagnose_run import main as diagnose
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert diagnose([str(tmp_path / "tel")]) == 0
    out = buf.getvalue()
    assert "Goodput" in out and "goodput fraction" in out
    assert "Step phases" in out and "checkpoint" in out
    assert "Pod skew" in out
    assert "valid JSON" in out


def test_goodput_attributed_across_simulated_restart(mesh, tmp_path, rng):
    """Badput attribution across job incarnations: run 1 trains and
    dies; run 2 (a fresh hub on the same directory) restores at start.
    The cumulative account gains `restart` badput and keeps run 1's
    productive time."""
    tel_dir = tmp_path / "tel"
    tel1 = T.Telemetry.create(str(tel_dir))
    with T.use_telemetry(tel1):
        t1 = _make_trainer(mesh, tmp_path / "ck", telemetry=tel1)
        t1.fit(_data(rng), total_steps=4, save_every=2)
        t1.checkpointer.wait_until_finished()
    tel1.close()
    t1.checkpointer.close()
    run1 = json.load(open(tel_dir / "goodput.json"))
    assert run1["incarnations"] == 1
    assert "restart" not in run1["badput_s"]

    tel2 = T.Telemetry.create(str(tel_dir))      # the relaunched job
    assert tel2.goodput.incarnation == 2
    with T.use_telemetry(tel2):
        t2 = _make_trainer(mesh, tmp_path / "ck", telemetry=tel2,
                           restore_at_start=True)
        hist = t2.fit(_data(rng), total_steps=3, save_every=2)
        t2.checkpointer.wait_until_finished()
    tel2.close()
    t2.checkpointer.close()

    cumulative = json.load(open(tel_dir / "goodput.json"))
    assert cumulative["incarnations"] == 2
    assert cumulative["badput_s"]["restart"] > 0          # the resume cost
    assert cumulative["productive_s"] > run1["productive_s"]
    assert hist["goodput"]["badput_s"]["restart"] > 0     # per-fit delta too


def test_fit_without_telemetry_keeps_async_dispatch(mesh, rng):
    """The disabled default hub must not add the per-step device sync:
    no step_phases rows anywhere, no device phase timed, and the
    in-memory goodput account still closes (it is free)."""
    hub = T.Telemetry(enabled=False)
    with T.use_telemetry(hub):
        trainer = _make_trainer(mesh)
        hist = trainer.fit(_data(rng), total_steps=4)
    assert np.isfinite(hist["final_loss"])
    assert hist["goodput"]["productive_s"] > 0
    # device phase never timed without block_until_ready
    assert hub.registry.histogram("phase/device").count == 0
    assert hub.registry.histogram("phase/host").count == 4


def test_jsonl_logger_serializes_small_sequences_and_counts_drops(tmp_path):
    """Satellite bugfix: list/dict/small-array values serialize instead
    of vanishing; the unserializable remainder is counted on the
    telemetry hub."""
    from flaxdiff_tpu.trainer.logging import JsonlLogger
    hub = T.Telemetry(enabled=False)
    with T.use_telemetry(hub):
        lg = JsonlLogger(str(tmp_path / "log.jsonl"))
        lg.log({"loss_curve": [0.5, 0.25, 0.125],
                "shape": (8, 8),
                "small_arr": np.arange(3, dtype=np.float32),
                "nested": {"a": np.float32(1.5), "b": 2},
                "huge": np.zeros(10_000),
                "opaque": object()}, step=1)
        lg.finish()
    rec = json.loads(open(tmp_path / "log.jsonl").read())
    assert rec["loss_curve"] == [0.5, 0.25, 0.125]
    assert rec["shape"] == [8, 8]
    assert rec["small_arr"] == [0.0, 1.0, 2.0]
    assert rec["nested"] == {"a": 1.5, "b": 2}
    assert "huge" not in rec and "opaque" not in rec
    assert hub.counter("telemetry/dropped_keys").value == 2


def test_jsonl_logger_counts_nested_dict_drops(tmp_path):
    """'Never silently dropped' must hold one level down too: entries
    lost inside a surviving sub-dict count toward dropped_keys."""
    from flaxdiff_tpu.trainer.logging import JsonlLogger
    hub = T.Telemetry(enabled=False)
    with T.use_telemetry(hub):
        lg = JsonlLogger(str(tmp_path / "log.jsonl"))
        lg.log({"nested": {"keep": 1.0, "lost": object(),
                           "huge": np.zeros(10_000)},
                "all_lost": {"a": object(), "b": object()}}, step=1)
        lg.finish()
    rec = json.loads(open(tmp_path / "log.jsonl").read())
    assert rec["nested"] == {"keep": 1.0}
    assert "all_lost" not in rec
    # 2 inside the surviving sub-dict + 2 inside the vanished one
    assert hub.counter("telemetry/dropped_keys").value == 4


def test_profiler_trace_failure_records_event(monkeypatch, tmp_path):
    """Satellite bugfix: a start_trace failure is a `trace_failed`
    resilience event, not a silent pass."""
    import jax
    from flaxdiff_tpu.profiling import trace

    def boom(*a, **k):
        raise RuntimeError("already tracing")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    ev = R.EventLog("t")
    with R.use_event_log(ev):
        with trace(str(tmp_path)):
            pass
    assert ev.count("trace_failed", "profiler.start_trace") == 1
    detail = ev.events("trace_failed")[0].detail
    assert "already tracing" in detail
