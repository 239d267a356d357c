"""Serving subsystem tests (flaxdiff_tpu/serving/, docs/SERVING.md).

Scheduler mechanics run against a jax-free FakeEngine (fast,
deterministic); the host-sync contract is enforced with counting mocks
on the module-level seams (the PR-5 convention); the acceptance bars —
batched == solo bit-identity under padding/masking/chunking, and a
warm program cache that never re-traces — run against a real tiny
pipeline.
"""
import contextlib
import threading
import time

import numpy as np
import pytest

from flaxdiff_tpu.serving import (DeadlineExceeded, PoissonWorkloadSpec,
                                  RequestState, SampleRequest,
                                  SchedulerClosed, SchedulerConfig,
                                  ServingScheduler, build_workload,
                                  bucket_up, nfe_bucket, replay)
from flaxdiff_tpu.serving import scheduler as sched_mod
from flaxdiff_tpu.telemetry import Telemetry


# ---------------------------------------------------------------------------
# Pure helpers
# ---------------------------------------------------------------------------

def test_bucket_helpers():
    assert bucket_up(1, (1, 2, 4)) == 1
    assert bucket_up(3, (1, 2, 4)) == 4
    assert bucket_up(9, (1, 2, 4)) == 4      # capped at max bucket
    assert nfe_bucket(1) == 1
    assert nfe_bucket(5) == 8
    assert nfe_bucket(64) == 64


def test_request_validation():
    with pytest.raises(ValueError, match="diffusion_steps"):
        SampleRequest(diffusion_steps=0)
    r = SampleRequest(prompts=["a", "b", "c"])
    assert r.num_samples == 3                # prompts drive the block


def test_poisson_workload_deterministic():
    spec = PoissonWorkloadSpec(
        n_requests=16, rate_hz=8.0, seed=99,
        mix=[{"resolution": 8, "diffusion_steps": 4},
             {"resolution": 8, "diffusion_steps": 8}])
    w1, w2 = build_workload(spec), build_workload(spec)
    assert [t for t, _ in w1] == [t for t, _ in w2]
    assert [r.seed for _, r in w1] == [r.seed for _, r in w2]
    assert [r.diffusion_steps for _, r in w1] \
        == [r.diffusion_steps for _, r in w2]
    # arrivals strictly increase; both NFEs drawn
    ts = [t for t, _ in w1]
    assert all(b > a for a, b in zip(ts, ts[1:]))
    assert {r.diffusion_steps for _, r in w1} == {4, 8}
    # a different seed is a different workload
    assert [t for t, _ in build_workload(
        PoissonWorkloadSpec(n_requests=16, rate_hz=8.0, seed=100,
                            mix=spec.mix))] != ts


# ---------------------------------------------------------------------------
# FakeEngine: the scheduler's engine contract without jax
# ---------------------------------------------------------------------------

class FakeEngine:
    """Deterministic jax-free engine: result rows are f(seed); advance
    moves each row min(remaining, round_steps) of its `nfe + 1` turns
    (its steps, then its terminal denoise); per-call counters let tests
    assert what compute was (not) spent."""

    def __init__(self, step_delay_s: float = 0.0):
        self.prepared = []
        self.advance_calls = []
        self.finalize_calls = []
        self.step_delay_s = step_delay_s
        self.telemetry = Telemetry(enabled=False)

    def group_key(self, req):
        return (req.resolution, req.sampler, req.num_samples)

    def prepare(self, req, future, submit_t, admit_t):
        st = RequestState(req=req, future=future, submit_t=submit_t,
                          admit_t=admit_t, group=self.group_key(req),
                          x=None, rng=None, state=None, pairs=None,
                          cond=None, uncond=None)
        self.prepared.append(req)
        return st

    def advance(self, rows, bucket, round_steps):
        self.advance_calls.append((len(rows), bucket, round_steps))
        if self.step_delay_s:
            time.sleep(self.step_delay_s)
        finished = []
        for r in rows:
            r.done += min(r.remaining, round_steps)
            r.rounds += 1
            if r.remaining <= 0:
                finished.append(r)
        return finished, 0.0

    def finalize(self, rows, bucket):
        self.finalize_calls.append((len(rows), bucket))
        out = np.stack([np.full((r.req.num_samples, 2, 2, 1),
                                float(r.req.seed)) for r in rows])
        return out, 0.0


def _fake_scheduler(tel=None, **cfg_kwargs):
    eng = FakeEngine()
    tel = tel or Telemetry(enabled=False)
    cfg = SchedulerConfig(**{"round_steps": 4,
                             "batch_buckets": (1, 2, 4), **cfg_kwargs})
    return eng, ServingScheduler(engine=eng, config=cfg, telemetry=tel,
                                 autostart=False)


@pytest.mark.parametrize("apart", [False, True])
def test_rows_served_apart_ride_rounds_of_the_smallest_bucket(apart):
    """An engine whose model a round evaluates one row at a time
    (`rows_apart`) gets rounds of the smallest bucket: first come, first
    served, a request's turns back to back in ONE round (its length is
    inside `round_steps`), its result out when its own last turn ends.
    Any other engine's rounds are as wide as the queue fills them."""
    eng, sched = _fake_scheduler(round_steps=8)
    eng.rows_apart = apart
    warmed = []
    eng.prewarm = lambda reqs, steps, buckets: warmed.append(buckets) or {}
    assert sched.batch_buckets == ((1,) if apart else (1, 2, 4))
    reqs = [SampleRequest(resolution=8, diffusion_steps=nfe, seed=7 + i)
            for i, nfe in enumerate((4, 2, 3, 2, 2, 3))]
    sched.prewarm(reqs[:1])
    assert warmed == [sched.batch_buckets]
    order, finalize = [], eng.finalize
    eng.finalize = lambda rows, bucket: (
        order.extend(r.req.seed for r in rows), finalize(rows, bucket))[1]
    futs = [sched.submit(r) for r in reqs]
    sched.start()
    outs = [f.result(timeout=10) for f in futs]
    sched.close()
    assert all(np.all(o.samples == float(r.seed))
               for r, o in zip(reqs, outs))
    if apart:
        assert eng.advance_calls == [(1, 1, 8)] * len(reqs)
        assert eng.finalize_calls == [(1, 1)] * len(reqs)
        assert [o.rounds for o in outs] == [1] * len(reqs)
        assert order == [r.seed for r in reqs]
    else:
        assert eng.advance_calls == [(4, 4, 8), (2, 2, 8)]


def test_scheduler_completes_all_and_routes_results():
    tel = Telemetry(enabled=False)
    eng, sched = _fake_scheduler(tel)
    reqs = [SampleRequest(resolution=8, diffusion_steps=3 + (i % 3),
                          sampler=("ddim", "euler")[i % 2], seed=100 + i)
            for i in range(10)]
    futs = [sched.submit(r) for r in reqs]
    sched.start()
    outs = [f.result(timeout=10) for f in futs]
    sched.close()
    for r, o in zip(reqs, outs):
        # each request got ITS OWN rows back, whatever it batched with
        assert np.all(o.samples == float(r.seed))
        assert o.samples.shape == (1, 2, 2, 1)
        assert o.rounds >= 1 and o.latency_ms >= o.queue_ms
    snap = tel.registry.snapshot()
    assert snap["serving/requests_in"] == 10
    assert snap["serving/requests_ok"] == 10
    assert snap.get("serving/shed", 0) == 0
    # two groups of 5 bucketed to 4+1 rows -> some padding happened
    assert snap["serving/rows_real"] >= 10


def test_heterogeneous_nfe_exits_early():
    """A short request grouped with a long one completes in fewer
    rounds — continuous admission, not wait-for-longest. (2 and 8
    turns: a request's steps and its terminal denoise.)"""
    eng, sched = _fake_scheduler(round_steps=2)
    short = sched.submit(SampleRequest(resolution=8, diffusion_steps=1,
                                       sampler="ddim", seed=1))
    long = sched.submit(SampleRequest(resolution=8, diffusion_steps=7,
                                      sampler="ddim", seed=2))
    sched.start()
    r_short = short.result(timeout=10)
    r_long = long.result(timeout=10)
    sched.close()
    assert r_short.rounds == 1 and r_long.rounds == 4
    # both rode the same first round (one group)
    assert eng.advance_calls[0][0] == 2


def test_deadline_shed_before_compute():
    eng, sched = _fake_scheduler()
    tel = sched.telemetry
    doomed = sched.submit(SampleRequest(resolution=8, diffusion_steps=4,
                                        deadline_s=0.0))
    time.sleep(0.01)                          # deadline passes in-queue
    ok = sched.submit(SampleRequest(resolution=8, diffusion_steps=4,
                                    seed=5))
    sched.start()
    assert np.all(ok.result(timeout=10).samples == 5.0)
    with pytest.raises(DeadlineExceeded):
        doomed.result(timeout=10)
    sched.close()
    # the shed request never reached prepare/advance
    assert all(r.deadline_s is None for r in eng.prepared)
    assert tel.registry.counter("serving/shed").value == 1


def test_queue_full_sheds_at_the_door():
    eng, sched = _fake_scheduler(max_queue=1)
    keep = sched.submit(SampleRequest(resolution=8, diffusion_steps=2))
    reject = sched.submit(SampleRequest(resolution=8, diffusion_steps=2))
    with pytest.raises(DeadlineExceeded, match="queue full"):
        reject.result(timeout=1)
    sched.start()
    keep.result(timeout=10)
    sched.close()
    assert sched.telemetry.registry.counter("serving/shed").value == 1


def test_midflight_deadline_shed_at_round_boundary():
    """A request whose deadline passes BETWEEN rounds is shed at the
    next round boundary (not only at dispatch admission), with
    `serving/shed_midflight` counting it and the future resolving
    `DeadlineExceeded` — no more compute is spent on it."""
    eng = FakeEngine(step_delay_s=0.03)
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(
        engine=eng, telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=1, batch_buckets=(1, 2)))
    # 8 rounds x 30 ms but a 50 ms budget: admitted (deadline alive at
    # dispatch), then expires mid-flight
    doomed = sched.submit(SampleRequest(resolution=8, diffusion_steps=8,
                                        sampler="ddim", deadline_s=0.05))
    ok = sched.submit(SampleRequest(resolution=8, diffusion_steps=8,
                                    sampler="ddim", seed=9))
    sched.start()
    assert np.all(ok.result(timeout=20).samples == 9.0)
    with pytest.raises(DeadlineExceeded, match="mid-flight"):
        doomed.result(timeout=20)
    sched.close()
    snap = tel.registry.snapshot()
    assert snap["serving/shed_midflight"] == 1
    assert snap["serving/shed"] == 1
    # it WAS admitted (this is the mid-flight case, not queue shedding)
    assert any(r.deadline_s is not None for r in eng.prepared)


def test_dispatch_thread_death_fails_all_futures(monkeypatch):
    """Regression for the stranded-future bug class: if the dispatch
    thread dies, every queued/in-flight future must resolve with a
    typed ServingFault, and later submits are refused — nobody waits
    forever."""
    from flaxdiff_tpu.serving import ServingFault
    eng, sched = _fake_scheduler()
    futs = [sched.submit(SampleRequest(resolution=8, diffusion_steps=4,
                                       seed=i)) for i in range(3)]
    monkeypatch.setattr(
        sched, "_pick_group_locked",
        lambda: (_ for _ in ()).throw(RuntimeError("scheduler bug")))
    sched.start()
    for f in futs:
        with pytest.raises(ServingFault) as ei:
            f.result(timeout=10)
        assert ei.value.kind == "scheduler_died"
    with pytest.raises(SchedulerClosed):
        sched.submit(SampleRequest(resolution=8)).result(timeout=5)
    sched.close(drain=False)


def test_submit_after_close_and_drain():
    eng, sched = _fake_scheduler()
    futs = [sched.submit(SampleRequest(resolution=8, diffusion_steps=4,
                                       seed=i)) for i in range(3)]
    sched.start()
    sched.close(drain=True)                  # drain finishes queued work
    for f in futs:
        assert f.result(timeout=1) is not None
    with pytest.raises(SchedulerClosed):
        sched.submit(SampleRequest(resolution=8)).result(timeout=1)


def test_close_without_drain_cancels():
    eng, sched = _fake_scheduler()
    futs = [sched.submit(SampleRequest(resolution=8, diffusion_steps=4))
            for _ in range(4)]
    sched.close(drain=False)                 # never started: all cancel
    sched.start()
    for f in futs:
        with pytest.raises(SchedulerClosed):
            f.result(timeout=1)


def test_completion_sync_seams_counted(monkeypatch):
    """The PR-5 counting-mock contract: ALL host syncs go through the
    module seams, and one completed batch costs exactly one
    block_until_ready + one device_get — the dispatch loop itself
    never syncs."""
    blocks, gets = [], []
    real_block = sched_mod._block_until_ready
    real_get = sched_mod._device_get
    monkeypatch.setattr(sched_mod, "_block_until_ready",
                        lambda x: (blocks.append(1), real_block(x))[1])
    monkeypatch.setattr(sched_mod, "_device_get",
                        lambda x: (gets.append(1), real_get(x))[1])
    eng, sched = _fake_scheduler(round_steps=16)
    futs = [sched.submit(SampleRequest(resolution=8, diffusion_steps=4,
                                       sampler="ddim", seed=i))
            for i in range(3)]               # one group, one round
    sched.start()
    for f in futs:
        f.result(timeout=10)
    sched.close()
    assert len(blocks) == 1 and len(gets) == 1


def test_backpressure_bounds_inflight(monkeypatch):
    """With a stalled completion thread the dispatch loop must WAIT
    (counted), not queue unbounded completed batches."""
    real_block = sched_mod._block_until_ready

    def slow_block(x):
        time.sleep(0.05)
        return real_block(x)

    monkeypatch.setattr(sched_mod, "_block_until_ready", slow_block)
    tel = Telemetry(enabled=False)
    eng = FakeEngine()
    sched = ServingScheduler(
        engine=eng, telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=8, batch_buckets=(1,),
                               max_inflight=1))
    futs = [sched.submit(SampleRequest(resolution=8, diffusion_steps=4,
                                       seed=i)) for i in range(6)]
    sched.start()
    for f in futs:
        f.result(timeout=20)
    sched.close()
    assert tel.registry.counter("serving/backpressure_waits").value > 0
    snap = tel.registry.snapshot()
    assert snap["serving/requests_ok"] == 6


def test_replay_with_fake_engine():
    eng, sched = _fake_scheduler()
    sched.start()
    spec = PoissonWorkloadSpec(
        n_requests=12, rate_hz=200.0, seed=3,
        mix=[{"resolution": 8, "diffusion_steps": 4},
             {"resolution": 8, "diffusion_steps": 8}])
    summary = replay(sched, build_workload(spec), timeout_s=20)
    sched.close()
    assert summary["completed"] == 12 and summary["shed"] == 0
    assert summary["latency_ms"]["p50"] is not None
    assert summary["latency_ms"]["p99"] >= summary["latency_ms"]["p50"]
    assert summary["throughput_rps"] > 0


def test_thread_safe_submit():
    eng, sched = _fake_scheduler(max_queue=512)
    sched.start()
    futs, lock = [], threading.Lock()

    def blast(base):
        mine = [sched.submit(SampleRequest(resolution=8,
                                           diffusion_steps=4,
                                           seed=base + i))
                for i in range(20)]
        with lock:
            futs.extend(mine)

    threads = [threading.Thread(target=blast, args=(1000 * t,))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    results = [f.result(timeout=20) for f in futs]
    sched.close()
    assert len(results) == 80
    assert {float(r.samples.flat[0]) for r in results} \
        == {float(r.request.seed) for r in results}


# ---------------------------------------------------------------------------
# Run-ahead: one round launched behind the one the device is running
# ---------------------------------------------------------------------------

class Carry:
    """A round's output as the scheduler's seams see it: ready on
    command, like a device array behind a running program."""

    def __init__(self, fail: bool = False):
        self.done = threading.Event()
        self.fail = fail

    def is_ready(self):
        return self.done.is_set()

    def block_until_ready(self):
        assert self.done.wait(20), "the test never released this round"
        if self.fail:
            raise RuntimeError("the round failed on the device")
        return self


class GatedEngine(FakeEngine):
    """FakeEngine whose rounds return at once (a launch) and finish
    when the test says so: `carries[i]` is round i's output."""

    def __init__(self, failing=()):
        super().__init__()
        self.carries = []
        self.failing = set(failing)

    def advance(self, rows, bucket, round_steps):
        carry = Carry(fail=len(self.carries) in self.failing)
        self.carries.append(carry)
        for r in rows:
            r.x = carry
        return super().advance(rows, bucket, round_steps)


def _wait_for(cond, timeout=10.0):
    t_end = time.perf_counter() + timeout
    while not cond():
        assert time.perf_counter() < t_end, "timed out"
        time.sleep(0.002)


def _gated_scheduler(tel=None, failing=(), buckets=(1,)):
    eng = GatedEngine(failing)
    sched = ServingScheduler(
        engine=eng, telemetry=tel or Telemetry(enabled=False),
        autostart=False,
        config=SchedulerConfig(round_steps=1, batch_buckets=buckets))
    return eng, sched


def test_dispatch_thread_is_never_more_than_one_round_ahead():
    """With every round unfinished until released, the thread launches
    the running round and ONE behind it, then waits in `serve.pace`;
    each release lets exactly one more round out. The wait holds no
    lock."""
    tel = Telemetry(enabled=False)
    eng, sched = _gated_scheduler(tel)
    fut = sched.submit(SampleRequest(resolution=8, diffusion_steps=5,
                                     sampler="ddim", seed=4))     # 6 turns
    sched.start()
    for released in range(5):
        _wait_for(lambda: len(eng.carries) == released + 2)
        time.sleep(0.05)                  # it would have raced by now
        assert len(eng.carries) == released + 2
        assert sched.queue_depth() == 0   # takes the scheduler's lock
        eng.carries[released].done.set()
    eng.carries[5].done.set()
    assert np.all(fut.result(timeout=10).samples == 4.0)
    sched.close()
    snap = tel.registry.snapshot()
    # every round but the first left while the round before still ran
    assert snap["serving/rounds"] == 6
    assert snap["serving/rounds_overlapped"] == 5


def test_rounds_overlapped_is_zero_when_the_device_keeps_up():
    """A round whose output is ready before the next turn (the plain
    FakeEngine: the host is the slower side) overlaps nothing and never
    waits in `serve.pace`."""
    tel = Telemetry(enabled=False)
    eng, sched = _fake_scheduler(tel, round_steps=1)
    fut = sched.submit(SampleRequest(resolution=8, diffusion_steps=5,
                                     sampler="ddim", seed=2))     # 6 turns
    sched.start()
    fut.result(timeout=10)
    sched.close()
    snap = tel.registry.snapshot()
    assert snap["serving/rounds"] == 6
    assert snap.get("serving/rounds_overlapped", 0) == 0


def test_pace_is_one_block_on_the_older_round(monkeypatch):
    """`serve.pace` goes through the `_block_until_ready` seam, once a
    turn at most, on the OLDER of the two unfinished rounds."""
    blocked = []
    real_block = sched_mod._block_until_ready

    def recording(x):
        if threading.current_thread().name == "serving-dispatch":
            blocked.append(x)
        return real_block(x)

    monkeypatch.setattr(sched_mod, "_block_until_ready", recording)
    eng, sched = _gated_scheduler()
    fut = sched.submit(SampleRequest(resolution=8, diffusion_steps=3,
                                     sampler="ddim", seed=1))     # 4 turns
    sched.start()
    for i in range(3):
        _wait_for(lambda: len(blocked) == i + 1)
        assert len(eng.carries) == min(i + 2, 4)
        eng.carries[i].done.set()
    fut.result(timeout=10)
    sched.close()
    # with rounds i+1 and i+2 unfinished, the turn waited for round i+1
    assert blocked == eng.carries[:3]


def test_midflight_deadline_shed_at_round_boundary_under_run_ahead():
    """A deadline that passes while the thread is a round ahead is
    still acted on at the next round boundary: `serve.pace` stands
    before admission, so the turn after the wait sheds the request
    before launching anything more for it."""
    tel = Telemetry(enabled=False)
    eng, sched = _gated_scheduler(tel, buckets=(1, 2))
    doomed = sched.submit(SampleRequest(resolution=8, diffusion_steps=7,
                                        sampler="ddim", deadline_s=0.05))
    ok = sched.submit(SampleRequest(resolution=8, diffusion_steps=3,
                                    sampler="ddim", seed=9))      # 4 turns
    sched.start()
    _wait_for(lambda: len(eng.carries) == 2)
    time.sleep(0.08)                      # the deadline passes in pace
    for c in eng.carries:
        c.done.set()
    _wait_for(lambda: len(eng.advance_calls) >= 3)
    with pytest.raises(DeadlineExceeded, match="after 2 round"):
        doomed.result(timeout=10)
    while not ok.done():
        for c in list(eng.carries):
            c.done.set()
        time.sleep(0.002)
    assert np.all(ok.result(timeout=10).samples == 9.0)
    for c in eng.carries:
        c.done.set()
    sched.close()
    # rounds 1 and 2 carried both rows; from round 3 on the survivor
    assert [n for n, _, _ in eng.advance_calls] == [2, 2, 1, 1]
    assert tel.registry.snapshot()["serving/shed_midflight"] == 1


def test_a_round_that_failed_on_the_device_does_not_kill_the_loop():
    """The pace wait is not a fault barrier of its own: a round whose
    output raises when waited for is recorded and the loop goes on (the
    round and fetch barriers own the fault)."""
    from flaxdiff_tpu import resilience as R
    ev = R.EventLog("pace")
    eng, sched = _gated_scheduler(failing={0})
    with R.use_event_log(ev):
        fut = sched.submit(SampleRequest(resolution=8, diffusion_steps=4,
                                         sampler="ddim", seed=6))
        sched.start()
        while not fut.done():
            for c in list(eng.carries):
                c.done.set()
            time.sleep(0.002)
        assert np.all(fut.result(timeout=10).samples == 6.0)
        for c in eng.carries:
            c.done.set()
        sched.close()
    assert ev.count(site="serving.pace") == 1


# ---------------------------------------------------------------------------
# Real-engine acceptance: bit-identity + warm cache
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_pipe():
    import jax
    import jax.numpy as jnp

    from flaxdiff_tpu.inference import (DiffusionInferencePipeline,
                                        build_model)
    config = {
        "model": {"name": "simple_dit", "emb_features": 32,
                  "num_heads": 4, "num_layers": 1, "patch_size": 4,
                  "output_channels": 1},
        "schedule": {"name": "cosine", "timesteps": 100},
        "predictor": "epsilon",
    }
    model = build_model("simple_dit", emb_features=32, num_heads=4,
                        num_layers=1, patch_size=4, output_channels=1)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)), jnp.zeros((1,)),
        None)
    return DiffusionInferencePipeline.from_config(config, params=params)


def test_batched_bit_identity_with_padding_and_chunking(tiny_pipe):
    """THE acceptance bar: requests batched, padded (buckets force a
    padding row), NFE-masked, and chunked across rounds produce
    bit-identical samples to solo generate_samples with the same
    seed — including a stochastic sampler's per-step noise."""
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(
        pipeline=tiny_pipe, telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=2, batch_buckets=(4,)))
    reqs = [
        SampleRequest(resolution=8, channels=1, diffusion_steps=3,
                      sampler="euler_ancestral", seed=7, use_ema=False),
        SampleRequest(resolution=8, channels=1, diffusion_steps=5,
                      sampler="euler_ancestral", seed=11, use_ema=False),
        SampleRequest(resolution=8, channels=1, diffusion_steps=4,
                      sampler="ddim", seed=3, use_ema=False),
    ]
    futs = [sched.submit(r) for r in reqs]
    sched.start()
    outs = [f.result(timeout=300) for f in futs]
    sched.close()

    for r, o in zip(reqs, outs):
        solo = tiny_pipe.generate_samples(
            num_samples=1, resolution=8, channels=1,
            diffusion_steps=r.diffusion_steps, sampler=r.sampler,
            seed=r.seed, use_ema=False)
        np.testing.assert_array_equal(o.samples, solo)
    snap = tel.registry.snapshot()
    # buckets=(4,) with groups of 2 and 1 -> padding rows existed, and
    # the padded outputs were still bit-exact above
    assert snap["serving/rows_padded"] > 0
    assert snap["serving/requests_ok"] == 3


def test_samples_do_not_depend_on_round_mates(tiny_pipe):
    """Rows never interact: one request's samples are the same bits
    whatever shares its rounds (other seeds, other NFEs, other slots of
    the bucket), so stack and unstack inside the round program hand
    every row its own carry. On a v-predicting model, whose samples do
    not saturate at the clip (every step shows in them)."""
    from flaxdiff_tpu.inference import DiffusionInferencePipeline
    pipe = DiffusionInferencePipeline.from_config(
        dict(tiny_pipe.config, predictor="v"), params=tiny_pipe.params)

    def serve(mates):
        sched = ServingScheduler(
            pipeline=pipe, telemetry=Telemetry(enabled=False),
            autostart=False,
            config=SchedulerConfig(round_steps=2, batch_buckets=(4,)))
        futs = [sched.submit(_tiny_request(n, seed, "euler_ancestral"))
                for n, seed in mates]
        sched.start()
        outs = [f.result(timeout=300).samples for f in futs]
        sched.close()
        return outs

    first = serve([(5, 7), (3, 11), (4, 3)])
    second = serve([(2, 1), (7, 2), (5, 7)])        # another slot, too
    np.testing.assert_array_equal(first[0], second[2])
    assert (np.abs(first[0]) < 1.0).mean() > 0.5


def test_multistep_state_carry_bit_identity(tiny_pipe):
    """Multistep DPM is the hardest carry: its scan state (denoised
    history + lambda trail, keyed on the global step index) must
    survive chunk boundaries, masking, and batch stacking bit-exactly."""
    sched = ServingScheduler(
        pipeline=tiny_pipe, telemetry=Telemetry(enabled=False),
        autostart=False,
        config=SchedulerConfig(round_steps=2, batch_buckets=(1, 2)))
    reqs = [SampleRequest(resolution=8, channels=1, diffusion_steps=5,
                          sampler="multistep_dpm", seed=13,
                          use_ema=False),
            SampleRequest(resolution=8, channels=1, diffusion_steps=3,
                          sampler="multistep_dpm", seed=17,
                          use_ema=False)]
    futs = [sched.submit(r) for r in reqs]
    sched.start()
    outs = [f.result(timeout=300) for f in futs]
    sched.close()
    for r, o in zip(reqs, outs):
        solo = tiny_pipe.generate_samples(
            num_samples=1, resolution=8, channels=1,
            diffusion_steps=r.diffusion_steps, sampler=r.sampler,
            seed=r.seed, use_ema=False)
        np.testing.assert_array_equal(o.samples, solo)


# ---------------------------------------------------------------------------
# A round ends where its first row ends (ISSUE 31): its length is an
# operand of the one compiled chunk program
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def deep_pipe():
    """Three perturbed blocks (the cache plans split the depth, and an
    AdaLN-Zero block is an identity at init), v-prediction (samples do
    not saturate at the clip: every step shows in them)."""
    import jax
    import jax.numpy as jnp

    from flaxdiff_tpu.inference import (DiffusionInferencePipeline,
                                        build_model)
    kw = {"emb_features": 32, "num_heads": 4, "num_layers": 3,
          "patch_size": 4, "output_channels": 1}
    params = jax.jit(build_model("simple_dit", **kw).init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)), jnp.zeros((1,)),
        None)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    params = jax.tree_util.tree_unflatten(
        treedef, [l + 0.05 * jax.random.normal(k, l.shape, l.dtype)
                  for l, k in zip(leaves, keys)])
    return DiffusionInferencePipeline.from_config(
        {"model": dict(kw, name="simple_dit"),
         "schedule": {"name": "cosine", "timesteps": 100},
         "predictor": "v"}, params=params)


def _plan_of(kind):
    from flaxdiff_tpu.ops.diffcache import CachePlan
    from flaxdiff_tpu.ops.spatialcache import ComposedPlan, SpatialPlan
    cache = CachePlan(refresh_every=3, refresh_head=1, refresh_tail=1)
    return {"chunk": None, "chunk_cached": cache,
            "chunk_spatial": ComposedPlan(
                cache=cache, spatial=SpatialPlan(keep_fraction=0.5))}[kind]


def _advance_cut(engine, row, steps, round_steps=8):
    """One solo round of exactly `steps` turns in the program compiled
    for `round_steps`: the rule that ends a round where its first row
    ends is made to say `steps` for the length of the call (what a
    round-mate `steps` turns from its end does to a round)."""
    from unittest import mock

    from flaxdiff_tpu.serving import engine as engine_mod
    with mock.patch.object(engine_mod, "round_length",
                           lambda rows, rs: (rs, steps)):
        engine.advance([row], 1, round_steps)
    return engine.last_round_info


@pytest.fixture(scope="module")
def cut_engines():
    """One engine a (pipeline, kind), kept over the cut cases so that
    they share its one compiled chunk program."""
    return {}


def _samples_by_cuts(engines, pipe, kind, cuts):
    """A 20-step request's samples with its 21 turns (the last is the
    terminal denoise) cut into rounds as `cuts`, every round through
    the ONE size-8 program of its kind."""
    from flaxdiff_tpu.serving import ServingFuture
    from flaxdiff_tpu.serving.engine import SamplerProgramEngine
    engine = engines.get((id(pipe), kind))
    if engine is None:
        engine = engines[id(pipe), kind] = SamplerProgramEngine(
            pipe, telemetry=Telemetry(enabled=False))
    row = engine.prepare(SampleRequest(
        resolution=8, channels=1, diffusion_steps=20,
        sampler="euler_ancestral", seed=5, use_ema=False,
        cache_plan=_plan_of(kind)), ServingFuture(), 0.0, 0.0)
    for c in cuts:
        info = _advance_cut(engine, row, c)
        assert info["kind"] == kind and info["steps"] == c
    assert row.remaining == 0 and row.rounds == len(cuts)
    out, _ = engine.finalize([row], 1)
    # every cut of every case went through one chunk program, the only
    # program of the engine that holds the network
    assert sum(k[0] == kind for k in engine._programs) == 1
    assert {k[0] for k in engine._programs} \
        == {"init", "noise", kind, "handoff"}
    return np.asarray(out[0])


@pytest.mark.parametrize(
    "cuts", [(5, 5, 5, 5, 1), (1,) * 21, (3, 8, 2, 8), (7, 7, 7)],
    ids=["5x4+terminal-alone", "1x21", "3+8+2+8", "7x3"])
@pytest.mark.parametrize("kind", ["chunk", "chunk_cached", "chunk_spatial"])
def test_samples_do_not_depend_on_where_rounds_are_cut(
        deep_pipe, cut_engines, kind, cuts):
    """A request's samples are equal to the last bit whether its 21
    turns run as 8+8+5 (what it gets alone), 5+5+5+5 and the terminal
    denoise in a round of its own (the cut between the last step and
    the terminal turn), 21 x 1 or an uneven cut: its steps, its RNG
    lineage (one split a turn that runs, none for a turn that does not),
    its cache schedule and its terminal value are its own."""
    assert sum(cuts) == 21
    base = _samples_by_cuts(cut_engines, deep_pipe, kind, (8, 8, 5))
    np.testing.assert_array_equal(
        _samples_by_cuts(cut_engines, deep_pipe, kind, cuts), base)
    assert (np.abs(base) < 1.0).mean() > 0.5        # not saturated


@pytest.mark.parametrize("cuts", [(8, 8, 5), (5, 5, 5, 5, 1), (1,) * 21],
                         ids=["8+8+5", "5x4+terminal-alone", "1x21"])
def test_cut_rounds_equal_the_solo_scan(tiny_pipe, cut_engines, cuts):
    """The anchor of the cut cases, on the model whose batched-equals-
    solo bar holds to the bit (ROADMAP D9): however the chunk program's
    rounds are cut, the samples are those of `generate_samples` and its
    single scan."""
    solo = tiny_pipe.generate_samples(
        num_samples=1, resolution=8, channels=1, diffusion_steps=20,
        sampler="euler_ancestral", seed=5, use_ema=False)
    np.testing.assert_array_equal(
        _samples_by_cuts(cut_engines, tiny_pipe, "chunk", cuts), solo)


def _rounds_by_rule(nfes, cap, round_steps):
    """What `round_length` and FIFO admission give `nfes` submitted
    together, each a trajectory of `nfe + 1` turns: ({request: its
    rounds}, [every round's length])."""
    queue, active = [(i, n + 1) for i, n in enumerate(nfes)], []
    rounds, lengths = {i: 0 for i in range(len(nfes))}, []
    while queue or active:
        while queue and len(active) < cap:
            active.append(list(queue.pop(0)))
        steps = min([round_steps] + [left for _, left in active])
        lengths.append(steps)
        for a in active:
            a[1] -= steps
            rounds[a[0]] += 1
        active = [a for a in active if a[1] > 0]
    return rounds, lengths


@pytest.mark.parametrize("nfes,buckets,round_steps", [
    ((3, 5, 7, 6, 9, 4), (2,), 4),
    ((20, 30, 50, 20, 20, 30, 20, 20, 30, 20), (1, 2, 4), 8),
    ((5, 5, 5, 1, 13), (1, 2), 8),
], ids=["cap2-rs4", "deal-6-3-1", "ones-and-long"])
def test_no_row_step_is_dead_and_rounds_follow_the_rule(
        tiny_pipe, nfes, buckets, round_steps):
    """Mixed step counts over fewer rows than requests (the queue is
    never empty until the tail): every row is live on every turn its
    rounds run (`serving/row_steps_run == serving/row_steps_live`), a
    request's `rounds` and the count of rounds are what the rule gives,
    and every request ran its own `nfe + 1` turns, no more: its steps
    and ONE terminal denoise, which rode a round
    (`serving/terminal_turns`)."""
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(
        pipeline=tiny_pipe, telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=round_steps,
                               batch_buckets=buckets))
    futs = [sched.submit(_tiny_request(n, 50 + i))
            for i, n in enumerate(nfes)]
    sched.start()
    outs = [f.result(timeout=300) for f in futs]
    sched.close()
    want, lengths = _rounds_by_rule(nfes, max(buckets), round_steps)
    assert [o.rounds for o in outs] == [want[i] for i in range(len(nfes))]
    snap = tel.registry.snapshot()
    assert snap["serving/rounds"] == len(lengths)
    assert snap["serving/row_steps_run"] == snap["serving/row_steps_live"] \
        == sum(nfes) + len(nfes)
    assert snap["serving/terminal_turns"] == len(nfes)
    assert max(lengths) <= round_steps and min(lengths) >= 1
    assert len(set(lengths)) > 1            # the rule did cut rounds short


@pytest.fixture(scope="module")
def warm_round8(tiny_pipe):
    """An engine whose size-8 bucket-1 chunk program has run once, and
    the telemetry that counted it."""
    from flaxdiff_tpu.serving import ServingFuture
    from flaxdiff_tpu.serving.engine import SamplerProgramEngine
    tel = Telemetry(enabled=False)
    engine = SamplerProgramEngine(tiny_pipe, telemetry=tel)
    row = engine.prepare(_tiny_request(40, 1), ServingFuture(), 0.0, 0.0)
    engine.advance([row], 1, 8)
    sched_mod._block_until_ready(row.x)
    return engine, tel, row


@pytest.mark.parametrize("steps", range(1, 9))
def test_round_lengths_share_one_program_and_one_compilation(
        warm_round8, steps):
    """Round lengths 1..8 at one bucket are one entry of the program
    cache and one compilation: the length is an operand, never a trace
    constant."""
    engine, tel, row = warm_round8
    misses = tel.registry.counter("serving/program_cache_misses")
    m0, size0, done0 = misses.value, engine.program_cache_size, row.done
    if row.remaining < steps:
        row.done = done0 = 0            # its trajectory, again
    with _count_compiles() as compiled:
        info = _advance_cut(engine, row, steps)
        sched_mod._block_until_ready(row.x)
    assert info["steps"] == steps and info["n_act"] == [steps]
    assert not info["miss"] and row.done == done0 + steps
    assert compiled == [] and misses.value == m0
    assert engine.program_cache_size == size0
    assert sum(k[0] == "chunk" for k in engine._programs) == 1


@pytest.mark.parametrize("nfes", [(3, 5, 4), (6,), (9, 2, 16, 11)],
                         ids=["3-5-4", "6", "9-2-16-11"])
def test_run_to_completion_is_one_round_of_the_exact_longest_length(
        tiny_pipe, nfes):
    """`round_steps=0`: every row finishes in ONE round, which runs the
    longest row's turns exactly (in the program of their power-of-two
    bucket); shorter rows take their terminal turn where their own
    trajectory ends and keep their carry past it, and the samples are
    the solo scan's to the last bit."""
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(
        pipeline=tiny_pipe, telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=0, batch_buckets=(4,)))
    futs = [sched.submit(_tiny_request(n, 70 + i, "euler_ancestral"))
            for i, n in enumerate(nfes)]
    sched.start()
    outs = [f.result(timeout=300) for f in futs]
    sched.close()
    assert [o.rounds for o in outs] == [1] * len(nfes)
    snap = tel.registry.snapshot()
    assert snap["serving/rounds"] == 1
    assert snap["serving/row_steps_run"] == len(nfes) * (max(nfes) + 1)
    assert snap["serving/row_steps_live"] == sum(nfes) + len(nfes)
    assert snap["serving/terminal_turns"] == len(nfes)
    info = sched.engine.last_round_info
    assert info["steps"] == max(nfes) + 1
    assert info["n_act"] == [n + 1 for n in nfes]
    chunk_keys = [k for k in sched.engine._programs if k[0] == "chunk"]
    assert [k[2] for k in chunk_keys] == [nfe_bucket(max(nfes) + 1)]
    for i, (n, o) in enumerate(zip(nfes, outs)):
        solo = tiny_pipe.generate_samples(
            num_samples=1, resolution=8, channels=1, diffusion_steps=n,
            sampler="euler_ancestral", seed=70 + i, use_ema=False)
        np.testing.assert_array_equal(o.samples, solo)


@contextlib.contextmanager
def _count_compiles():
    """The seconds of every backend compile inside the block (what the
    benchmark's `correct` holds at zero inside its window)."""
    import jax.monitoring
    seen, on = [], [True]

    def listen(event, secs, **_):
        if on[0] and event == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        on[0] = False       # jax has no public way to take it off again


def _tiny_request(nfe, seed, sampler="ddim"):
    return SampleRequest(resolution=8, channels=1, diffusion_steps=nfe,
                         sampler=sampler, seed=seed, use_ema=False)


def _drive(engine, rows, buckets, round_steps):
    """The scheduler's own engine calls for `rows`, to the end."""
    live = rows
    while live:
        finished, _ = engine.advance(live, bucket_up(len(live), buckets),
                                     round_steps)
        live = [r for r in live if r.remaining > 0]
        if finished:
            out, _ = engine.finalize(finished,
                                     bucket_up(len(finished), buckets))
            sched_mod._block_until_ready(out)


def _warm_walk(engine, buckets, round_steps, nfes):
    """Every shape traffic can meet, as the benchmark's `warm_engine`
    walks them: a round and a hand-off for every bucket and every count
    of rows that finish together, and one request of every NFE."""
    from flaxdiff_tpu.serving import ServingFuture

    def rows_of(ns):
        return [engine.prepare(_tiny_request(n, 10 ** 6 + i),
                               ServingFuture(), 0.0, 0.0)
                for i, n in enumerate(ns)]

    for n in range(1, max(buckets) + 1):
        _drive(engine, rows_of([round_steps] * n), buckets, round_steps)
    _drive(engine, rows_of(nfes), buckets, round_steps)


def test_warm_cache_never_retraces(tiny_pipe):
    """Repeat traffic of identical request shapes must be served
    entirely from the compiled-program cache: zero misses on the
    second pass."""
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(
        pipeline=tiny_pipe, telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=2, batch_buckets=(1, 2)))

    def pass_once():
        futs = [sched.submit(SampleRequest(
            resolution=8, channels=1, diffusion_steps=n, sampler="ddim",
            seed=s, use_ema=False))
            for n, s in ((3, 1), (3, 2), (5, 9))]
        sched.start()
        return [f.result(timeout=300) for f in futs]

    first = pass_once()
    misses_cold = tel.registry.counter(
        "serving/program_cache_misses").value
    assert misses_cold > 0
    with _count_compiles() as compiled:
        second = pass_once()
    sched.close()
    # neither the engine's own programs nor anything eager beside them
    assert compiled == []
    assert tel.registry.counter(
        "serving/program_cache_misses").value == misses_cold
    assert tel.registry.counter("serving/program_cache_hits").value > 0
    # same request, same seed -> same samples on both passes
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a.samples, b.samples)


def test_warm_walk_then_traffic_compiles_nothing(tiny_pipe):
    """After a `warm_engine`-style walk, traffic that arrives while
    rounds run (any mix of fresh and continued rows, any bucket)
    compiles nothing, and a turn stays a handful of launches."""
    tel = Telemetry(enabled=False)
    cfg = SchedulerConfig(round_steps=2, batch_buckets=(1, 2, 4))
    sched = ServingScheduler(pipeline=tiny_pipe, telemetry=tel,
                             autostart=False, config=cfg)
    _warm_walk(sched.engine, cfg.batch_buckets, cfg.round_steps, (3, 4, 5))
    count = tel.registry.counter
    misses = count("serving/program_cache_misses").value
    launches, rounds = (count("serving/launches").value,
                        count("serving/rounds").value)
    sched.start()
    with _count_compiles() as compiled:
        futs = []
        for i in range(12):
            futs.append(sched.submit(_tiny_request((3, 4, 5)[i % 3], i)))
            time.sleep(0.003 * (i % 4))
        outs = [f.result(timeout=300) for f in futs]
    sched.close()
    assert len(outs) == 12 and compiled == []
    assert count("serving/program_cache_misses").value == misses
    per_round = ((count("serving/launches").value - launches)
                 / (count("serving/rounds").value - rounds))
    assert 2.0 <= per_round <= 16.0


@pytest.fixture
def dispatches(monkeypatch):
    """Names of every compiled program dispatched from Python while the
    test runs, an eager operation's one-operation program included: the
    C++ fast path is switched off, so each call comes through
    `_run_python_pjit`. (A call nested in a trace comes through it too:
    count warm calls only.)"""
    import jax
    from jax._src import pjit
    names = []
    real = pjit._run_python_pjit

    def counting(p, args_flat, fun, *args, **kwargs):
        names.append(getattr(fun, "__name__", str(fun)))
        return real(p, args_flat, fun, *args, **kwargs)

    monkeypatch.setattr(pjit, "_run_python_pjit", counting)
    monkeypatch.setattr(pjit, "_get_fastpath_data", lambda *a, **k: None)
    jax.clear_caches()          # fast-path entries of earlier tests
    yield names
    jax.clear_caches()          # and ours, which have no fast path


def test_a_warm_turn_is_a_handful_of_counted_launches(tiny_pipe,
                                                      dispatches):
    """One warm loop turn with 8 rows, 2 of them just admitted and 2
    finishing: every program dispatched is one of the engine's own,
    through its counting helper — an init and a noise program for each
    admitted request, 1 round (the two rows' terminal denoises are turns
    of it), 1 hand-off — and no eager one-operation program beside
    them."""
    from flaxdiff_tpu.serving import ServingFuture
    from flaxdiff_tpu.serving.engine import SamplerProgramEngine
    tel = Telemetry(enabled=False)
    engine = SamplerProgramEngine(tiny_pipe, telemetry=tel)
    buckets, rs = (1, 2, 4, 8), 2

    def admit(nfe, seed):
        return engine.prepare(_tiny_request(nfe, seed), ServingFuture(),
                              0.0, 0.0)

    _drive(engine, [admit(2, s) for s in range(2)], buckets, rs)  # b2
    rows = [admit(3, 10 + i) for i in range(2)] \
        + [admit(8, 20 + i) for i in range(4)]
    rows += [admit(6, 30), admit(6, 31)]
    finished, _ = engine.advance(rows, 8, rs)       # warms bucket 8
    assert not finished
    rows = rows[:6]                                 # two slots free

    launches = tel.registry.counter("serving/launches")
    l0, n0 = launches.value, len(dispatches)
    rows += [admit(6, 40), admit(6, 41)]
    finished, compile_s = engine.advance(rows, 8, rs)
    out, _ = engine.finalize(finished, bucket_up(len(finished), buckets))
    turn = dispatches[n0:]
    assert len(finished) == 2 and compile_s == 0.0
    assert sorted(turn) == ["sampler_chunk", "sampler_handoff",
                            "sampler_init", "sampler_init",
                            "sampler_noise", "sampler_noise"]
    assert launches.value - l0 == len(turn) <= 16
    assert out.shape[0] == 2


def test_second_prepare_of_a_seen_nfe_computes_and_reads_nothing(
        tiny_pipe, monkeypatch):
    """What depends only on (sampler, NFE, schedule) is made once and
    kept as host values: a later request of that NFE calls no
    `get_timestep_spacing` and reads no device value back (the first
    read goes through the `_device_get` seam)."""
    from jax._src.array import ArrayImpl

    from flaxdiff_tpu.samplers import common
    from flaxdiff_tpu.serving import ServingFuture
    from flaxdiff_tpu.serving.engine import SamplerProgramEngine
    spacings, gets, reads = [], [], []
    real_spacing, real_get = common.get_timestep_spacing, \
        sched_mod._device_get
    real_value = ArrayImpl._value
    monkeypatch.setattr(
        common, "get_timestep_spacing",
        lambda *a, **k: (spacings.append(1), real_spacing(*a, **k))[1])
    monkeypatch.setattr(
        sched_mod, "_device_get",
        lambda x: (gets.append(1), real_get(x))[1])
    engine = SamplerProgramEngine(tiny_pipe,
                                  telemetry=Telemetry(enabled=False))
    first = engine.prepare(_tiny_request(5, 1), ServingFuture(), 0.0, 0.0)
    assert len(spacings) == 1 and len(gets) == 1
    # 5 steps and the terminal turn, whose pair is (t_term, t_term)
    assert isinstance(first.pairs, np.ndarray) \
        and first.pairs.shape == (6, 2)
    assert first.pairs[-1, 0] == first.pairs[-1, 1] == first.pairs[-2, 1]

    monkeypatch.setattr(ArrayImpl, "_value", property(
        lambda self: (reads.append(1), real_value.fget(self))[1]))
    again = engine.prepare(_tiny_request(5, 2), ServingFuture(), 0.0, 0.0)
    monkeypatch.setattr(ArrayImpl, "_value", real_value)
    assert len(spacings) == 1 and len(gets) == 1 and reads == []
    assert again.pairs is first.pairs
    # and the carry is the solo path's, to the bit: its keys, its noise
    from flaxdiff_tpu.utils import RngSeq
    rng, noise_key = RngSeq.create(2).next_key()
    _, loop_key = rng.next_key()
    solo = tiny_pipe.get_sampler("ddim", 0.0)
    np.testing.assert_array_equal(
        again.x, solo.make_noise_program((1, 8, 8, 1))(noise_key))
    np.testing.assert_array_equal(again.rng, loop_key)


def test_prompted_cfg_bit_identity():
    """Conditioned + CFG requests through the scheduler match solo
    prompted generation bitwise (cond/uncond row stacking is
    output-invariant)."""
    import jax
    import jax.numpy as jnp

    from flaxdiff_tpu.inference import (DiffusionInferencePipeline,
                                        build_model)
    from flaxdiff_tpu.inputs import (ConditionalInputConfig,
                                     DiffusionInputConfig)
    from flaxdiff_tpu.inputs.encoders import HashTextEncoder

    enc = HashTextEncoder.create(features=16, max_length=8)
    model = build_model("simple_dit", emb_features=32, num_heads=4,
                        num_layers=1, patch_size=4, output_channels=1)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)), jnp.zeros((1,)),
        jnp.asarray(enc([""])))
    pipe = DiffusionInferencePipeline.from_config(
        {"model": {"name": "simple_dit", "emb_features": 32,
                   "num_heads": 4, "num_layers": 1, "patch_size": 4,
                   "output_channels": 1},
         "schedule": {"name": "cosine", "timesteps": 100},
         "predictor": "epsilon"}, params=params)
    pipe.input_config = DiffusionInputConfig(
        sample_data_key="sample", sample_data_shape=(8, 8, 1),
        conditions=[ConditionalInputConfig(encoder=enc)])

    sched = ServingScheduler(
        pipeline=pipe, telemetry=Telemetry(enabled=False),
        autostart=False,
        config=SchedulerConfig(round_steps=2, batch_buckets=(1, 2)))
    futs = [sched.submit(SampleRequest(
        resolution=8, channels=1, diffusion_steps=3, sampler="ddim",
        guidance_scale=2.0, prompts=[p], seed=s, use_ema=False))
        for p, s in (("a red flower", 21), ("blue sky", 22))]
    sched.start()
    outs = [f.result(timeout=300) for f in futs]
    sched.close()
    for (p, s), o in zip((("a red flower", 21), ("blue sky", 22)), outs):
        solo = pipe.generate_samples(
            prompts=[p], resolution=8, channels=1, diffusion_steps=3,
            sampler="ddim", guidance_scale=2.0, seed=s, use_ema=False)
        np.testing.assert_array_equal(o.samples, solo)
