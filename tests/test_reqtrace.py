"""Request-level tracing + program evidence registry (ISSUE 13).

Covers the acceptance bars:
- every program in a warm `SamplerProgramEngine` cache has a
  `programs.jsonl` record (cache key, compile ms, FLOPs estimate);
- a traced end-to-end serving replay produces a Chrome trace whose
  per-request span sums reconcile with the `serving/*_ms` histograms
  within timer resolution;
- the counting mock proves a traced run performs the IDENTICAL
  seam-counted host syncs as an untraced run, and warm replays with
  tracing enabled still report zero re-traces;
- `TraceRecorder` bounded-event drops surface as
  `telemetry/trace_dropped_events`;
- `scripts/diagnose_run.py` renders Request-traces and Programs
  sections in text and --json.
"""
import json
import os
import threading

import numpy as np
import pytest

from flaxdiff_tpu.serving import (SampleRequest, SchedulerConfig,
                                  ServingScheduler)
from flaxdiff_tpu.serving import scheduler as sched_mod
from flaxdiff_tpu.telemetry import (ProgramRegistry, Telemetry,
                                    read_registry, stable_json)
from flaxdiff_tpu.telemetry.reqtrace import RequestTracer
from flaxdiff_tpu.telemetry.tracing import TraceRecorder


@pytest.fixture(scope="module")
def tiny_pipe():
    import jax
    import jax.numpy as jnp

    from flaxdiff_tpu.inference import (DiffusionInferencePipeline,
                                        build_model)
    config = {
        "model": {"name": "simple_dit", "emb_features": 32,
                  "num_heads": 4, "num_layers": 2, "patch_size": 4,
                  "output_channels": 1},
        "schedule": {"name": "cosine", "timesteps": 100},
        "predictor": "epsilon",
    }
    # 2 layers: splittable trunk so cache-plan requests also run
    model = build_model("simple_dit", emb_features=32, num_heads=4,
                        num_layers=2, patch_size=4, output_channels=1)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)),
                        jnp.zeros((1,)), None)
    return DiffusionInferencePipeline.from_config(config, params=params)


def _requests():
    return [SampleRequest(resolution=8, channels=1, diffusion_steps=n,
                          sampler=s, seed=seed, use_ema=False)
            for n, s, seed in ((3, "ddim", 1), (5, "ddim", 2),
                               (4, "euler_ancestral", 3))]


def _run(sched, reqs):
    futs = [sched.submit(r) for r in reqs]
    sched.start()
    return [f.result(timeout=300) for f in futs]


# ---------------------------------------------------------------------------
# Acceptance: registry coverage + reconciliation on a traced replay
# ---------------------------------------------------------------------------

def test_traced_replay_registry_and_reconciliation(tiny_pipe, tmp_path):
    tel = Telemetry.create(str(tmp_path))
    sched = ServingScheduler(
        pipeline=tiny_pipe, telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=2, batch_buckets=(2,)))
    outs = _run(sched, _requests())
    sched.close()
    tel.close()

    # -- every warm-cache program has a registry record ---------------------
    rows = read_registry(str(tmp_path / "programs.jsonl"))
    assert len(rows) == sched.engine.program_cache_size
    registered = {(r["kind"], r["key"]) for r in rows}
    for key in sched.engine._programs:
        kind = key[0]
        assert (kind, str(key)) in registered, key
    for r in rows:
        assert r["compile_ms"] and r["compile_ms"] > 0
        # a request's init and noise programs hold no model evaluation
        assert r["kind"] in ("init", "noise", "handoff") \
            or r["flops_jaxpr"] > 0
        assert r["fingerprint"]["platform"]
    # every program kind this workload compiles is present, and only the
    # round program holds the network: the hand-off of finished rows
    # (stack, clip) is no evaluation
    assert {r["kind"] for r in rows} == {"init", "noise", "chunk", "handoff"}
    flops = {r["kind"]: r["flops_jaxpr"] for r in rows}
    assert flops["handoff"] < 0.01 * flops["chunk"]

    # -- per-request rows reconcile with the histograms ---------------------
    recs = [json.loads(line) for line in
            open(tmp_path / "telemetry.jsonl", encoding="utf-8")]
    traces = [r for r in recs if r.get("type") == "request_trace"]
    assert len(traces) == len(outs)
    for t in traces:
        # the identity is exact by construction: all four values derive
        # from the same three timestamps
        assert t["queue_ms"] + t["compile_ms"] + t["device_ms"] \
            == pytest.approx(t["latency_ms"], abs=0.51)
        assert t["rounds"] >= 1 and len(t["round_detail"]) == t["rounds"]
        for d in t["round_detail"]:
            assert d["kind"] == "chunk" and "key" in d and "bucket" in d
    for span, hist in (("latency_ms", "serving/latency_ms"),
                       ("queue_ms", "serving/queue_ms"),
                       ("compile_ms", "serving/compile_ms"),
                       ("device_ms", "serving/device_ms")):
        h = tel.registry.histogram(hist)
        assert h.count == len(traces)
        assert sum(t[span] for t in traces) == pytest.approx(
            h.total, abs=0.51 * len(traces))

    # -- the Chrome trace has the request + round span families -------------
    doc = json.load(open(tmp_path / "trace.json", encoding="utf-8"))
    names = {e.get("name") for e in doc["traceEvents"]}
    assert {"req.submit", "req.queue", "req.serve", "serve.round",
            "serve.handoff"} <= names
    assert "serve.finalize" not in names


# ---------------------------------------------------------------------------
# Counting mock: tracing adds ZERO host syncs; warm stays retrace-free
# ---------------------------------------------------------------------------

def test_tracing_adds_no_host_syncs_and_warm_zero_retrace(
        tiny_pipe, tmp_path, monkeypatch):
    counts = {"blocks": 0, "gets": 0}
    real_block = sched_mod._block_until_ready
    real_get = sched_mod._device_get

    def count_block(x):
        # a result's syncs (completion thread). `serve.pace`, on the
        # dispatch thread, takes the same seam as often as the device
        # is behind, which is timing and not tracing
        if threading.current_thread().name == "serving-complete":
            counts["blocks"] += 1
        return real_block(x)

    def count_get(x):
        counts["gets"] += 1
        return real_get(x)

    monkeypatch.setattr(sched_mod, "_block_until_ready", count_block)
    monkeypatch.setattr(sched_mod, "_device_get", count_get)

    def replay(tel):
        sched = ServingScheduler(
            pipeline=tiny_pipe, telemetry=tel, autostart=False,
            config=SchedulerConfig(round_steps=2, batch_buckets=(2,)))
        outs = _run(sched, _requests())
        misses_cold = tel.registry.counter(
            "serving/program_cache_misses").value
        before = dict(counts)
        outs_warm = _run(sched, _requests())
        sched.close()
        return (outs, outs_warm,
                tel.registry.counter(
                    "serving/program_cache_misses").value - misses_cold,
                {k: counts[k] - before[k] for k in counts})

    counts.update(blocks=0, gets=0)
    untraced = replay(Telemetry(enabled=False))
    syncs_untraced = dict(counts)
    counts.update(blocks=0, gets=0)
    traced = replay(Telemetry.create(str(tmp_path)))
    syncs_traced = dict(counts)

    # identical seam-counted host syncs, traced vs untraced
    assert syncs_traced == syncs_untraced
    # warm replays with tracing enabled still re-trace nothing
    assert traced[2] == 0 and untraced[2] == 0
    # and tracing never changed the samples
    for a, b in zip(untraced[0], traced[0]):
        np.testing.assert_array_equal(a.samples, b.samples)
    for a, b in zip(traced[0], traced[1]):
        np.testing.assert_array_equal(a.samples, b.samples)


# ---------------------------------------------------------------------------
# Shed + drop-counter + unit pieces (no jax needed)
# ---------------------------------------------------------------------------

def test_shed_requests_close_their_trace(tmp_path):
    tel = Telemetry.create(str(tmp_path))
    sched = ServingScheduler(
        engine=_FakeEngine(), telemetry=tel, autostart=False,
        config=SchedulerConfig(max_queue=1))
    keep = sched.submit(SampleRequest(resolution=8, diffusion_steps=2))
    doomed = sched.submit(SampleRequest(resolution=8, diffusion_steps=2))
    with pytest.raises(Exception):
        doomed.result(timeout=1)
    sched.start()
    keep.result(timeout=10)
    sched.close()
    tel.close()
    recs = [json.loads(line) for line in
            open(tmp_path / "telemetry.jsonl", encoding="utf-8")]
    shed = [r for r in recs if r.get("type") == "request_trace"
            and r.get("outcome", "").startswith("shed:")]
    assert len(shed) == 1 and shed[0]["outcome"] == "shed:queue_full"


class _FakeEngine:
    """Minimal jax-free engine (mirrors tests/test_serving.py)."""

    def __init__(self):
        from flaxdiff_tpu.serving import RequestState
        self._rs = RequestState
        self.telemetry = Telemetry(enabled=False)

    def group_key(self, req):
        return (req.resolution, req.sampler, req.num_samples)

    def prepare(self, req, future, submit_t, admit_t):
        return self._rs(req=req, future=future, submit_t=submit_t,
                        admit_t=admit_t, group=self.group_key(req),
                        x=None, rng=None, state=None, pairs=None,
                        cond=None, uncond=None)

    def advance(self, rows, bucket, round_steps):
        finished = []
        for r in rows:
            r.done += min(r.remaining, round_steps)
            r.rounds += 1
            if r.remaining <= 0:
                finished.append(r)
        return finished, 0.0

    def finalize(self, rows, bucket):
        return np.stack([np.zeros((r.req.num_samples, 2, 2, 1))
                         for r in rows]), 0.0


def test_trace_recorder_drop_counter(tmp_path):
    from flaxdiff_tpu.telemetry import MetricsRegistry
    reg = MetricsRegistry()
    rec = TraceRecorder(str(tmp_path / "t.json"), max_events=3,
                        on_drop=lambda n: reg.counter(
                            "telemetry/trace_dropped_events").inc(n))
    for i in range(6):
        rec.instant(f"e{i}")
    assert rec.dropped == 4     # 1 metadata + 2 stored, 4 past bound
    assert reg.counter("telemetry/trace_dropped_events").value == 4
    rec.save()
    doc = json.load(open(tmp_path / "t.json", encoding="utf-8"))
    assert doc["flaxdiff_dropped_events"] == 4


def test_program_registry_dedupe_and_stability(tmp_path):
    path = str(tmp_path / "programs.jsonl")
    reg = ProgramRegistry(path)
    row = reg.record("chunk", ("chunk", 2, 4), compile_ms=12.3456,
                     flops_jaxpr=1e6)
    assert row is not None
    assert reg.record("chunk", ("chunk", 2, 4), compile_ms=99.0) is None
    reg2 = ProgramRegistry(str(tmp_path / "p2.jsonl"))
    row2 = reg2.record("chunk", ("chunk", 2, 4), compile_ms=12.3456,
                       flops_jaxpr=1e6)
    # byte-stable contract: same inputs -> identical serialized row
    assert stable_json(row) == stable_json(row2)
    assert len(read_registry(path)) == 1


def test_tracer_noop_on_disabled_hub():
    tracer = RequestTracer(Telemetry(enabled=False))
    assert not tracer.enabled
    assert tracer.begin(SampleRequest(resolution=8), 0.0) is None
    tracer.shed(None, "queue_full", 0.0)     # all no-ops, no raise
    tracer.round([], None, 0.0, 1.0, 1)
    tracer.complete(object(), 0, 0, 0, 0, 0.0)


# ---------------------------------------------------------------------------
# Trainer + solo compile-site registration
# ---------------------------------------------------------------------------

def test_trainer_registers_step_programs(tmp_path, mesh):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, t, cond=None):
            h = nn.Conv(8, (3, 3))(x)
            return nn.Conv(x.shape[-1], (3, 3))(jnp.tanh(h))

    model = Tiny()
    tel = Telemetry.create(str(tmp_path))
    tr = DiffusionTrainer(
        apply_fn=lambda p, x, t, c: model.apply({"params": p}, x, t),
        init_fn=lambda k: model.init(k, jnp.zeros((1, 8, 8, 1)),
                                     jnp.zeros((1,)))["params"],
        tx=optax.adam(1e-3), schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(), mesh=mesh,
        config=TrainerConfig(normalize=False, log_every=2,
                             numerics_cadence=3),
        telemetry=tel)
    rng = np.random.default_rng(0)

    def data():
        while True:
            # batch divisible by the conftest mesh's 8 fake devices
            yield {"sample": rng.normal(size=(8, 8, 8, 1))
                   .astype(np.float32)}

    tr.fit(data(), 6)
    tel.close()
    kinds = {r["kind"]: r
             for r in read_registry(str(tmp_path / "programs.jsonl"))}
    # the plain step (with its measured first-step compile) AND the
    # monitored twin are both on the books, with jaxpr FLOPs
    assert kinds["train_step"]["compile_ms"] > 0
    assert kinds["train_step"]["flops_jaxpr"] > 0
    assert kinds["train_step_monitored"]["flops_jaxpr"] > 0


def test_solo_generate_registers_program_and_stays_bit_identical(
        tiny_pipe, tmp_path):
    from flaxdiff_tpu.inference import DiffusionInferencePipeline
    from flaxdiff_tpu.telemetry import use_telemetry

    baseline = np.asarray(tiny_pipe.generate_samples(
        num_samples=1, resolution=8, channels=1, diffusion_steps=3,
        sampler="ddim", seed=5, use_ema=False))
    # a FRESH pipeline: the registering wrapper is installed at program
    # BUILD time, so the registry must be active before the first call
    pipe = DiffusionInferencePipeline.from_config(
        {"model": {"name": "simple_dit", "emb_features": 32,
                   "num_heads": 4, "num_layers": 2, "patch_size": 4,
                   "output_channels": 1},
         "schedule": {"name": "cosine", "timesteps": 100},
         "predictor": "epsilon"}, params=tiny_pipe.params)
    tel = Telemetry.create(str(tmp_path))
    with use_telemetry(tel):
        out = np.asarray(pipe.generate_samples(
            num_samples=1, resolution=8, channels=1, diffusion_steps=3,
            sampler="ddim", seed=5, use_ema=False))
    tel.close()
    # the registering wrapper is transparent: same bits as the raw path
    np.testing.assert_array_equal(out, baseline)
    solo = [r for r in read_registry(str(tmp_path / "programs.jsonl"))
            if r["kind"] == "solo"]
    assert len(solo) == 1
    assert solo[0]["compile_ms"] > 0 and "DDIMSampler" in solo[0]["key"]


# ---------------------------------------------------------------------------
# diagnose_run sections
# ---------------------------------------------------------------------------

def test_diagnose_run_reqtrace_and_programs_sections(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    from scripts.diagnose_run import main

    tel = Telemetry.create(str(tmp_path))
    tel.write_record({"type": "request_trace", "trace_id": "req-1-0",
                      "outcome": "ok", "queue_ms": 1.0,
                      "compile_ms": 10.0, "device_ms": 5.0,
                      "latency_ms": 16.0, "rounds": 2,
                      "sampler": "ddim", "nfe": 4, "resolution": 8,
                      "round_detail": [
                          {"round": 1, "kind": "chunk", "bucket": 2,
                           "rows": 1, "ms": 3.0, "miss": True,
                           "key": "('chunk', 2, 2)"},
                          {"round": 2, "kind": "chunk", "bucket": 2,
                           "rows": 1, "ms": 2.0}]})
    tel.write_record({"type": "request_trace", "trace_id": "req-1-1",
                      "outcome": "shed:deadline", "queue_ms": 50.0,
                      "sampler": "ddim", "nfe": 4, "resolution": 8})
    tel.programs.record("chunk", ("chunk", 2, 2), compile_ms=123.4,
                        flops_jaxpr=2.5e9, flops_cost=3.0e9,
                        collectives=8,
                        comm_bytes_by_axis={"seq": 4096})
    tel.close()

    assert main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "== Request traces (1 completed, 1 shed) ==" in out
    assert "slowest: req-1-0" in out
    assert "round    1 chunk" in out and "MISS" in out
    assert "== Programs (1 registered" in out
    assert "2.500" in out and "123.4" in out
    # static comm model columns (ISSUE 14): dispatch count + KiB/axis
    assert "comm KiB/axis" in out
    assert "seq=4.0" in out

    assert main([str(tmp_path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["request_traces"]["completed"] == 1
    assert doc["request_traces"]["shed"] == 1
    assert doc["request_traces"]["spans"]["latency_ms"]["p50"] == 16.0
    assert doc["request_traces"]["slowest"]["trace_id"] == "req-1-0"
    assert doc["programs"][0]["kind"] == "chunk"
    assert doc["programs"][0]["collectives"] == 8
    assert doc["programs"][0]["comm_bytes_by_axis"] == {"seq": 4096}
