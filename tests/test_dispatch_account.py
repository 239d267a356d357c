"""The serving loop's own time account (ISSUE 41; serving/scheduler.py
`_DispatchAccount`, `_resolve`; serving/request.py `SampleResult`).

What holds, on the CPU: every turn of the dispatch loop is booked whole
to `serving/dispatch_loop_ms` and the four counters beside it add up to
it, turn by turn, whatever the turn did (a round, a fault's `continue`,
an idle wait, a wait for the device or for the completion thread); a
result's `queue_ms + compile_ms + service_ms + tail_ms` is its
`latency_ms` to the last bit of the sum the code forms, cold, warm and
after a requeue; the histograms observe what the results carry; the
account reads no device; and the benchmark's two files name counters
this code writes.
"""
import json
import os
import threading
import time

import pytest

from flaxdiff_tpu import resilience as R
from flaxdiff_tpu.serving import (SampleRequest, SchedulerConfig,
                                  ServingScheduler)
from flaxdiff_tpu.serving import scheduler as sched_mod
from flaxdiff_tpu.telemetry import Telemetry
from tests.test_serving import (FakeEngine, _gated_scheduler, _wait_for)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WAITS = ("pace", "wait", "backpressure")
ACCOUNT = ("loop",) + WAITS + ("work",)
GENERATE_CELLS = ("dit-xl-2.generate", "command-a-plus.generate-few",
                  "brumby-14b.generate-fewer", "glm-5.2.generate-fewer-1024")
NEW_METRICS = {
    "serve.host_ms_per_round": ("serving/dispatch_work_ms",
                                "serving/rounds"),
    "serve.dispatch_busy_share": ("serving/dispatch_work_ms",
                                  "serving/dispatch_loop_ms"),
}


def _read(tel):
    return {k: tel.counter(f"serving/dispatch_{k}_ms").value
            for k in ACCOUNT}


def _request(nfe, seed, **kw):
    return SampleRequest(resolution=8, channels=1, diffusion_steps=nfe,
                         sampler="ddim", seed=seed, use_ema=False, **kw)


@pytest.fixture(scope="module")
def tiny_pipe():
    from tests.test_terminal_turn import _pipe
    return _pipe(1)


# -- the account alone, on a scripted clock ----------------------------------

class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(sched_mod, "_now", c)
    return c


@pytest.mark.parametrize("wait", WAITS)
def test_a_turn_is_booked_whole_and_split(clock, wait):
    """A turn of 10 ms that waited 7: 10 to the loop, 7 to that wait,
    3 to the work, nothing to the other two; the first `turn()` books
    nothing (there is no turn before it)."""
    tel = Telemetry(enabled=False)
    acct = sched_mod._DispatchAccount(tel)
    acct.turn()
    assert _read(tel) == dict.fromkeys(ACCOUNT, 0.0)
    clock.t += 0.001
    with acct.waiting(wait):
        clock.t += 0.007
    clock.t += 0.002
    # the five move together, at a turn's top: a reader in mid-turn sees
    # whole turns in every one of them
    assert _read(tel) == dict.fromkeys(ACCOUNT, 0.0)
    acct.turn()
    got = _read(tel)
    want = dict.fromkeys(ACCOUNT, 0.0)
    want.update(loop=10.0, work=3.0, **{wait: 7.0})
    assert got == pytest.approx(want, abs=1e-9)
    # the next turn starts from nothing waited
    clock.t += 0.004
    acct.turn()
    assert _read(tel) == pytest.approx(
        dict(want, loop=14.0, work=7.0), abs=1e-9)


def test_a_wait_that_raises_is_booked_and_work_never_falls(clock):
    """The wait's time is booked on the way out of a fault too; a turn
    that was all wait adds no work, and rounding never takes any away
    (a counter is monotone)."""
    tel = Telemetry(enabled=False)
    acct = sched_mod._DispatchAccount(tel)
    acct.turn()
    with pytest.raises(RuntimeError):
        with acct.waiting("pace"):
            clock.t += 0.005
            raise RuntimeError("the round failed on the device")
    acct.turn()
    assert _read(tel) == pytest.approx(
        dict(loop=5.0, pace=5.0, wait=0.0, backpressure=0.0, work=0.0),
        abs=1e-9)
    # three waits of a turn whose float sum passes the turn's wall
    for _ in range(3):
        with acct.waiting("wait"):
            clock.t += 0.1 / 3
    before = _read(tel)["work"]
    acct.turn()
    assert _read(tel)["work"] >= before


def test_the_account_reads_no_device(monkeypatch, clock):
    tel = Telemetry(enabled=False)

    def refuse(*a):
        raise AssertionError("the account touched a sync seam")

    for seam in ("_block_until_ready", "_device_get", "_is_ready"):
        monkeypatch.setattr(sched_mod, seam, refuse)
    acct = sched_mod._DispatchAccount(tel)
    acct.turn()
    for w in WAITS:
        with acct.waiting(w):
            clock.t += 0.001
    acct.turn()
    assert _read(tel)["loop"] == pytest.approx(3.0, abs=1e-9)


# -- the account on a scheduler: every turn, whatever it did ------------------

def _turns_of(sched, tel):
    """Record the five counters after every turn's booking (in the
    dispatch thread, where they are written)."""
    turns = [_read(tel)]
    book = sched._account.turn

    def recording():
        book()
        turns.append(_read(tel))

    sched._account.turn = recording
    return turns


def _check_turns(turns):
    """Turn by turn: the four parts add up to the loop's wall within a
    microsecond, and no counter ever falls."""
    assert len(turns) > 2
    for a, b in zip(turns, turns[1:]):
        d = {k: b[k] - a[k] for k in ACCOUNT}
        assert all(v >= 0.0 for v in d.values()), d
        parts = d["pace"] + d["wait"] + d["backpressure"] + d["work"]
        assert parts == pytest.approx(d["loop"], abs=1e-3), d
    last = turns[-1]
    assert last["loop"] > 0
    assert last["pace"] + last["wait"] + last["backpressure"] \
        + last["work"] == pytest.approx(last["loop"],
                                        abs=1e-3 * len(turns))
    return last


def test_real_rounds_are_accounted_turn_by_turn(tiny_pipe):
    """Rows of NFE 2, 3 and 5 through real rounds of at most 2 turns:
    every loop turn is booked, the whole adds up, and the loop's wall is
    the thread's life to within its start and its join."""
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(
        pipeline=tiny_pipe, telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=2, batch_buckets=(4,)))
    turns = _turns_of(sched, tel)
    futs = [sched.submit(_request(n, 30 + n)) for n in (2, 3, 5)]
    t0 = time.perf_counter()
    sched.start()
    for f in futs:
        f.result(timeout=600)
    sched.close()
    elapsed_ms = (time.perf_counter() - t0) * 1e3
    last = _check_turns(turns)
    assert last == _read(tel)       # nothing booked outside a turn
    rounds = tel.counter("serving/rounds").value
    assert rounds == 4 and len(turns) - 1 >= rounds
    assert 0 < last["work"] <= last["loop"] <= elapsed_ms
    assert last["loop"] > 0.5 * elapsed_ms


def test_a_faults_continue_and_the_parked_wait_are_turns_too():
    """A one-shot round fault: the turn that faulted `continue`s after
    conviction and requeue (its wall is work), the turns that find only
    backoff-parked entries wait 20 ms at a time under `serve.wait`."""
    tel = Telemetry(enabled=False)
    eng = FakeEngine()
    sched = ServingScheduler(
        engine=eng, telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=16, batch_buckets=(4,)))
    turns = _turns_of(sched, tel)
    plan = R.FaultPlan([R.FaultSpec("serving.round", at=(1,), times=1)],
                       seed=0)
    with plan.installed():
        futs = [sched.submit(SampleRequest(resolution=8, diffusion_steps=4,
                                           sampler="ddim", seed=100 + i))
                for i in range(4)]
        sched.start()
        outs = [f.result(timeout=20) for f in futs]
        sched.close()
    assert tel.counter("serving/round_faults").value == 1
    assert [o.attempts for o in outs] == [1] * 4
    last = _check_turns(turns)
    # the backoff (50 ms) was sat out in parked waits
    assert last["wait"] >= 30.0
    assert last["pace"] == 0.0 and last["backpressure"] == 0.0


def test_an_idle_wait_is_wait_and_not_work():
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(
        engine=FakeEngine(), telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=16, batch_buckets=(4,)))
    turns = _turns_of(sched, tel)
    sched.start()
    time.sleep(0.12)                      # nothing to serve
    fut = sched.submit(SampleRequest(resolution=8, diffusion_steps=4,
                                     sampler="ddim", seed=3))
    fut.result(timeout=10)
    sched.close()
    last = _check_turns(turns)
    assert last["wait"] >= 100.0
    assert last["work"] < last["wait"]


def test_the_wait_for_the_device_is_pace():
    """Every round unfinished until released 30 ms later: the thread
    sits in `serve.pace`, and that time is not its work."""
    tel = Telemetry(enabled=False)
    eng, sched = _gated_scheduler(tel)
    turns = _turns_of(sched, tel)
    fut = sched.submit(SampleRequest(resolution=8, diffusion_steps=3,
                                     sampler="ddim", seed=1))     # 4 turns
    sched.start()
    for i in range(4):
        _wait_for(lambda: len(eng.carries) == min(i + 2, 4))
        time.sleep(0.03)
        eng.carries[i].done.set()
    fut.result(timeout=10)
    sched.close()
    last = _check_turns(turns)
    assert last["pace"] >= 40.0           # two waits of 30 ms at least
    assert last["backpressure"] == 0.0


def test_the_wait_for_the_completion_thread_is_backpressure(monkeypatch):
    real_block = sched_mod._block_until_ready

    def slow_block(x):
        time.sleep(0.03)
        return real_block(x)

    monkeypatch.setattr(sched_mod, "_block_until_ready", slow_block)
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(
        engine=FakeEngine(), telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=8, batch_buckets=(1,),
                               max_inflight=1))
    turns = _turns_of(sched, tel)
    futs = [sched.submit(SampleRequest(resolution=8, diffusion_steps=4,
                                       seed=i)) for i in range(6)]
    sched.start()
    outs = [f.result(timeout=20) for f in futs]
    sched.close()
    last = _check_turns(turns)
    assert tel.counter("serving/backpressure_waits").value > 0
    assert last["backpressure"] >= 25.0
    assert last["pace"] == 0.0
    # the completion thread's 30 ms are the batch's tail, not its service
    assert all(o.tail_ms >= 25.0 for o in outs)


# -- a request's result says where its latency went --------------------------

def _identity(o):
    """To the last bit of the sums the code forms."""
    assert o.device_ms == o.service_ms + o.tail_ms
    assert o.latency_ms == o.queue_ms + o.compile_ms + o.device_ms
    assert o.queue_ms + o.compile_ms + o.service_ms + o.tail_ms \
        == pytest.approx(o.latency_ms, rel=1e-12)
    assert min(o.queue_ms, o.compile_ms, o.service_ms, o.tail_ms) >= 0.0
    assert o.timings() == {
        "queue_ms": o.queue_ms, "compile_ms": o.compile_ms,
        "device_ms": o.device_ms, "latency_ms": o.latency_ms,
        "service_ms": o.service_ms, "tail_ms": o.tail_ms}


@pytest.fixture(scope="module")
def cold_then_warm(tiny_pipe):
    """One scheduler over a fresh engine: a first wave that compiles
    every program it rides, then the same traffic again."""
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(
        pipeline=tiny_pipe, telemetry=tel,
        config=SchedulerConfig(round_steps=2, batch_buckets=(4,)))
    waves = {}
    for wave in ("cold", "warm"):
        t0 = time.perf_counter()
        futs = [sched.submit(_request(n, 40 + n)) for n in (2, 3, 5)]
        outs = [f.result(timeout=600) for f in futs]
        waves[wave] = (outs, (time.perf_counter() - t0) * 1e3)
    sched.close()
    return tel, waves


@pytest.mark.parametrize("wave", ["cold", "warm"])
def test_the_parts_of_a_latency_add_up_to_it(cold_then_warm, wave):
    _, waves = cold_then_warm
    outs, elapsed_ms = waves[wave]
    for o in outs:
        _identity(o)
        assert 0.0 < o.latency_ms <= elapsed_ms
        if wave == "cold":
            assert o.compile_ms > 0.0
        else:
            assert o.compile_ms == 0.0 and o.service_ms > 0.0
    # NFE 2 and 3 end in different rounds of at most 2 turns: each
    # batch has its own hand-off, and the longer ride is the longer
    # service
    by_nfe = {o.request.diffusion_steps: o for o in outs}
    if wave == "warm":
        assert by_nfe[5].service_ms > by_nfe[2].service_ms


@pytest.mark.parametrize("field", ["latency_ms", "queue_ms", "compile_ms",
                                   "device_ms", "service_ms", "tail_ms"])
def test_the_histograms_observe_what_the_results_carry(cold_then_warm,
                                                       field):
    tel, waves = cold_then_warm
    outs = waves["cold"][0] + waves["warm"][0]
    h = tel.registry.histogram(f"serving/{field}")
    assert h.count == len(outs) == 6
    assert h.total == pytest.approx(sum(getattr(o, field) for o in outs),
                                    rel=1e-9)


def test_the_identity_holds_after_a_requeue():
    """A round fault requeues the batch: the attempt that delivers is
    the one the result describes, and the failed attempt with its
    backoff is queue time (the request was waiting to be served)."""
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(
        engine=FakeEngine(), telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=16, batch_buckets=(4,)))
    plan = R.FaultPlan([R.FaultSpec("serving.round", at=(1,), times=1)],
                       seed=0)
    with plan.installed():
        futs = [sched.submit(SampleRequest(resolution=8, diffusion_steps=4,
                                           sampler="ddim", seed=100 + i))
                for i in range(4)]
        sched.start()
        outs = [f.result(timeout=20) for f in futs]
        sched.close()
    for o in outs:
        assert o.attempts == 1
        _identity(o)
        assert o.queue_ms >= 45.0         # the 50 ms backoff
        assert o.service_ms + o.tail_ms < o.queue_ms
    # one batch, one hand-off, one fetch: one tail
    assert len({o.tail_ms for o in outs}) == 1


def test_service_ends_at_the_hand_off_and_tail_at_the_host(monkeypatch):
    """The cut between the two is the instant the dispatch thread hands
    the batch over: a slow round lengthens the service, a slow fetch the
    tail, and neither the other."""
    real_get = sched_mod._device_get

    def slow_get(x):
        time.sleep(0.06)
        return real_get(x)

    monkeypatch.setattr(sched_mod, "_device_get", slow_get)
    sched = ServingScheduler(
        engine=FakeEngine(step_delay_s=0.02),
        telemetry=Telemetry(enabled=False), autostart=False,
        config=SchedulerConfig(round_steps=2, batch_buckets=(1,)))
    fut = sched.submit(SampleRequest(resolution=8, diffusion_steps=5,
                                     sampler="ddim", seed=2))  # 3 rounds
    sched.start()
    o = fut.result(timeout=10)
    sched.close()
    _identity(o)
    assert o.rounds == 3
    assert 55.0 <= o.service_ms < 115.0   # three rounds of 20 ms
    assert 55.0 <= o.tail_ms < 115.0      # one fetch of 60 ms


# -- the sync seams: as often as before ---------------------------------------

def test_the_seams_are_called_as_often_as_before(monkeypatch, tiny_pipe):
    """On real rounds: the completion thread waits once and reads once
    for each batch handed to it; the dispatch thread's only waits on
    the device are `serve.pace`'s, one a turn at most, and its only
    reads the tables of turns a fresh engine makes at admission (the
    account calls no seam: `test_the_account_reads_no_device`)."""
    calls = {"block": [], "get": []}
    real_block, real_get = (sched_mod._block_until_ready,
                            sched_mod._device_get)

    def block(x):
        calls["block"].append(threading.current_thread().name)
        return real_block(x)

    def get(x):
        calls["get"].append(threading.current_thread().name)
        return real_get(x)

    monkeypatch.setattr(sched_mod, "_block_until_ready", block)
    monkeypatch.setattr(sched_mod, "_device_get", get)
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(
        pipeline=tiny_pipe, telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=2, batch_buckets=(4,)))
    handed = []
    finalize = sched.engine.finalize
    sched.engine.finalize = lambda rows, b: (handed.append(len(rows)),
                                             finalize(rows, b))[1]
    futs = [sched.submit(_request(n, 50 + n)) for n in (2, 3, 5)]
    sched.start()
    for f in futs:
        f.result(timeout=600)
    sched.close()
    assert handed == [1, 1, 1]
    assert calls["get"].count("serving-complete") == 3
    # a fresh engine reads each NFE's table of turns once, at admission
    assert calls["get"].count("serving-dispatch") == 3
    assert calls["block"].count("serving-complete") == 3
    paced = calls["block"].count("serving-dispatch")
    assert paced <= tel.counter("serving/rounds").value - 1
    assert set(calls["block"]) <= {"serving-complete", "serving-dispatch"}


# -- the benchmark's files name what this code writes -------------------------

def _harness():
    """`benchmark/harness` as the top-level package the benchmark's own
    code imports it as."""
    from tests.test_brumby import _benchmark_package
    _benchmark_package("harness")
    from harness import layer_metrics, spec as bench_spec
    return bench_spec, layer_metrics


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_new_metric_files_read_counters_the_scheduler_writes(
        name, cold_then_warm):
    bench_spec, layer_metrics = _harness()
    f = bench_spec.load_layer_metric(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json"))
    assert f["name"] == name and f["source"] == "program_counter"
    assert f["layer"] == "serving (serving/scheduler.py, engine.py)"
    assert f["kinds"] == ["closed_loop", "open_loop"]
    assert f["read"] == {"from": "counter_ratio",
                         "numerator": NEW_METRICS[name][0],
                         "denominator": NEW_METRICS[name][1]}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        (entry,) = [m for m in json.load(fh)["per_layer"]
                    if m["name"] == name]
    assert tuple(entry["workloads"]) == GENERATE_CELLS
    assert entry["moves"] == f["moves"] == "gen_img_per_s"
    # the counters exist once a scheduler has served (no flag, no
    # recorder: the disabled hub counts), and the reader the file names
    # makes a number of them
    tel, _ = cold_then_warm
    snap = tel.registry.snapshot()
    assert all(snap[c] > 0 for c in NEW_METRICS[name])
    window = layer_metrics.Window(
        trace=None, interval=None, wall_s=1.0, steps=0, images=0, chips=1,
        results=[], counters=snap, memory={}, peaks={}, cfg={})
    value = layer_metrics.READERS["counter_ratio"](f["read"], window)
    assert value == snap[NEW_METRICS[name][0]] / snap[NEW_METRICS[name][1]]
    if name == "serve.dispatch_busy_share":
        assert 0.0 < value <= 1.0
    # on a program without the account (this PR's parent) the harness
    # makes the counters at 0: the share is left out, never an error
    bare = dict.fromkeys(NEW_METRICS[name], 0.0)
    bare["serving/rounds"] = 7.0
    window.counters = bare
    assert layer_metrics.READERS["counter_ratio"](f["read"], window) \
        in (None, 0.0)


@pytest.mark.parametrize("cell", GENERATE_CELLS)
def test_each_generate_cell_loads_with_the_new_metrics(cell):
    bench_spec, layer_metrics = _harness()
    loaded = bench_spec.load_benchmark(ROOT).cell(cell)
    names = [m["name"] for m in loaded.per_layer]
    assert set(NEW_METRICS) <= set(names)
    named = layer_metrics.counters_named(loaded.per_layer)
    assert {"serving/dispatch_work_ms", "serving/dispatch_loop_ms",
            "serving/rounds"} <= set(named)
