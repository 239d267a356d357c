"""The reduction from a trace to metrics, on a small trace recorded on a
v5e chip (PR 23's probe; `data/v5e_probe_trace.json`).

Read off that trace by hand: the host span `bench.window` runs from
44,787,069 ns for 35,174,209 ns. Three executions of `jit_f` are on the
device; the first starts at 43,821,573 ns, before the window on the
host's clock (the two clocks differ by about a millisecond), so two
lie inside: 82 operations, 342,090 ns busy, of which 290,350 ns in
Mosaic custom calls (GroupNorm+SiLU, flash attention, AdaLN). The
device is idle between executions while the host sleeps.
"""
import json
import os

import pytest

from harness import layer_metrics, spec, trace

from .conftest import BENCH

W0, WDUR = 44787069.0, 35174209.0
BUSY_NS, MOSAIC_NS = 342090.0, 290350.0


@pytest.fixture(scope="module")
def probe():
    with open(os.path.join(BENCH, "tests", "data",
                           "v5e_probe_trace.json")) as f:
        return trace.from_events(json.load(f)["rows"])


def _window(tr, **kw):
    base = dict(trace=tr, interval=tr.window(), wall_s=WDUR / 1e9, steps=2,
                images=0, chips=1, results=[], counters={}, memory={},
                peaks={}, cfg={})
    base.update(kw)
    return layer_metrics.Window(**base)


def _metric(name):
    return spec.load_layer_metric(os.path.join(
        BENCH, "layer_metrics", name + ".json"))["read"]


def test_planes_lines_and_window(probe):
    assert [d.name for d in probe.devices] == ["/device:TPU:0"]
    assert len(probe.devices[0].ops) == 123
    assert probe.window() == (W0, W0 + WDUR)


def test_idle_share_and_busy_seconds(probe):
    w = _window(probe)
    assert trace.busy_seconds(probe, w.interval) == pytest.approx(
        BUSY_NS / 1e9, rel=1e-9)
    idle = layer_metrics.READERS["device_busy"](
        _metric("device.idle_pct.train"), w)
    assert idle == pytest.approx(100 * (1 - BUSY_NS / WDUR), rel=1e-9)
    assert idle == pytest.approx(99.0274, abs=1e-3)
    per_step = layer_metrics.READERS["device_busy"](
        _metric("train.step_device_ms"), w)
    assert per_step == pytest.approx(BUSY_NS / 2 / 1e6, rel=1e-9)


def test_mosaic_share(probe):
    share = layer_metrics.READERS["device_events"](
        _metric("kernel.mosaic_share_pct.train"), _window(probe))
    assert share == pytest.approx(100 * MOSAIC_NS / BUSY_NS, rel=1e-9)
    assert share == pytest.approx(84.8753, abs=1e-3)


def test_breakdown_names_kernels_and_the_hosts_span(probe):
    b = trace.breakdown(probe, probe.window())
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 5
    assert b["device_ops"][0][0].endswith("[mosaic]")    # flash attention
    # the longest gaps are the host's sleeps, outside any bench.step span
    assert b["idle_gaps"][0][0] == "bench.window"
    assert b["idle_gaps"][0][1] == pytest.approx(0.0127, abs=5e-4)


def test_no_device_plane_reads_nothing():
    tr = trace.from_events([{"plane": "/host:CPU", "line": "python",
                             "name": "bench.window", "start_ns": 0,
                             "dur_ns": 10}])
    w = _window(tr)
    for name in ("device.idle_pct.train", "train.step_device_ms"):
        assert layer_metrics.READERS["device_busy"](_metric(name), w) is None


def _rows(dev, line, evs):
    return [{"plane": dev, "line": line, "name": n, "start_ns": s,
             "dur_ns": d} for n, s, d in evs]


def test_collective_share_and_exposed_time_on_a_hand_made_trace():
    """Interval arithmetic of the collective readers on a trace small
    enough to work out by hand (synthetic: the recorded collective rows
    are in `test_recorded_fsdp_trace` where the four-chip run left any).
    Window 0..1000 ns. Compute runs 0..400 and 500..900. An async
    all-gather spans 300..600 (its start op 300..310, its done op
    590..600 on the ops line), and a synchronous all-reduce 900..950.
    Collective time = [300,600] + [900,950] = 350. Busy = ops union =
    [0,400] + [500,950] = 850. Exposed = collective minus compute =
    [300,310] + [400,500] + [590,600] + [900,950] = 170 (the start and
    done operations are not compute)."""
    dev = "/device:TPU:0"
    rows = _rows(dev, "XLA Ops", [
        ("%fusion.1 = f32[8] fusion()", 0, 300),
        ("%all-gather-start.1 = (f32[8]) all-gather-start()", 300, 10),
        ("%fusion.2 = f32[8] fusion()", 310, 90),
        ("%fusion.3 = f32[8] fusion()", 500, 90),
        ("%all-gather-done.1 = f32[8] all-gather-done()", 590, 10),
        ("%fusion.4 = f32[8] fusion()", 600, 300),
        ("%all-reduce.1 = f32[8] all-reduce()", 900, 50)])
    rows += _rows(dev, "Async XLA Ops", [
        ("%all-gather-start.1 = (f32[8]) all-gather-start()", 300, 300)])
    rows += _rows("/host:CPU", "python", [("bench.window", 0, 1000)])
    w = _window(trace.from_events(rows), steps=1)
    pct = layer_metrics.READERS["device_events"](
        _metric("fsdp.collective_pct"), w)
    exposed = layer_metrics.READERS["device_events"](
        _metric("fsdp.exposed_collective_ms"), w)
    assert pct == pytest.approx(100 * 350 / 850)
    assert exposed == pytest.approx(170 / 1e6)


def test_interval_arithmetic():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.measure([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert trace.clip([(0, 5), (8, 12)], (4, 10)) == [(4, 5), (8, 10)]
