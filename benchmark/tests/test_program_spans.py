"""The program's spans beside the device's idle time
(`harness/program_spans.py`): on a hand-made trace with known answers,
on a trace of the generate cell recorded on a v5e chip (PR 24;
`data/v5e_generate_spans.json`, made by `spans.py --record`), and on
the CPU rehearsals of both cells.

The hand-made trace, in milliseconds. The device is busy over [0, 100],
[300.4, 500] and [800.1, 1000]: 500.5 ms idle in two gaps. The
dispatching thread (`python#1`) holds

  admit [100,150]
  round [150,320]   stack [160,290]  launch [299,300]  unstack [300,318]
  finalize [320,360]  stack [325,335]  launch [335,345]
  admit [520,560]
  round [560,830]   stack [570,700]  launch [795,799]  unstack [799,825]

and the completing thread (`python#2`) fetch [400,450] and [850,900].
Put down to the innermost span, the idle time is: stack 260, round's
own 124, admit 90, launch 5, unstack 1.5, under no span 20.
"""
import json
import os
import subprocess
import sys

import pytest

from harness import program_spans as ps
from harness import trace as tr

from .conftest import BENCH, ROOT

MS = 1e6
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _op(a, b, name="%fusion.1 = fusion()", line=tr.OPS_LINE):
    return {"plane": DEV, "line": line, "name": name,
            "start_ns": a * MS, "dur_ns": (b - a) * MS}


def _span(name, a, b, thread="python#1", **stats):
    return {"plane": HOST, "line": "python", "name": "fdt." + name,
            "start_ns": a * MS, "dur_ns": (b - a) * MS,
            "thread": thread, "stats": stats}


def _made():
    rows = [{"plane": HOST, "line": "python", "name": "bench.window",
             "start_ns": 0.0, "dur_ns": 1000 * MS},
            _op(0, 100), _op(300.4, 500), _op(800.1, 1000)]
    rows += [_op(a, a + 1, f"jit_{n}(7)", ps.MODULES_LINE) for a, n in
             ((0, "sampler_chunk"), (300.4, "sampler_chunk"),
              (345.5, "sampler_terminal"), (800.1, "sampler_chunk"))]
    rows += [
        _span("serve.admit", 100, 150),
        _span("serve.round", 150, 320, round=1, bucket=8, rows=8, steps=8),
        _span("serve.stack", 160, 290),
        _span("serve.launch", 299, 300, kind="chunk"),
        _span("serve.unstack", 300, 318),
        _span("serve.finalize", 320, 360, rows=2, bucket=2),
        _span("serve.stack", 325, 335),
        _span("serve.launch", 335, 345, kind="terminal"),
        _span("serve.admit", 520, 560),
        _span("serve.round", 560, 830, round=2, bucket=8, rows=8, steps=8),
        _span("serve.stack", 570, 700),
        _span("serve.launch", 795, 799, kind="chunk"),
        _span("serve.unstack", 799, 825),
        _span("serve.fetch", 400, 450, thread="python#2", rows=2),
        _span("serve.fetch", 850, 900, thread="python#2", rows=3),
    ]
    return rows


@pytest.fixture(scope="module")
def made():
    rows = _made()
    trace, spans, modules = ps.split(rows)
    return trace, spans, modules, trace.window()


def test_rows_split_into_trace_spans_and_programs(made):
    trace, spans, modules, window = made
    assert window == (0.0, 1000 * MS)
    assert [s[0] for s in trace.spans] == ["bench.window"]
    assert len(trace.devices[0].ops) == 3          # not the programs
    assert len(spans) == 15 and len(modules) == 4
    assert spans[0].name == "serve.admit"          # prefix taken off
    assert spans[1].stats == {"round": 1, "bucket": 8, "rows": 8,
                              "steps": 8}
    assert ps.dispatch_thread(spans) == "python#1"


def test_self_time_is_duration_less_the_children(made):
    _, spans, _, _ = made
    own = {(s.name, s.start / MS): tr.measure(iv) / MS
           for s, iv in zip(spans, ps.self_intervals(spans))}
    assert own[("serve.round", 150)] == pytest.approx(21)
    assert own[("serve.round", 560)] == pytest.approx(110)
    assert own[("serve.finalize", 320)] == pytest.approx(20)
    assert own[("serve.stack", 160)] == pytest.approx(130)     # a leaf
    assert own[("serve.fetch", 400)] == pytest.approx(50)      # its thread


# Per round means per WHOLE turn: from the first round's start (150) to
# the second's (560) is the one whole turn this trace holds.
@pytest.mark.parametrize("metric, want", [
    ("serve.round_host_ms", 250.0),     # round 170 + finalize 40 + admit 40
    ("serve.stack_ms", 140.0),          # the round's 130 + the finalize's 10
    ("serve.unstack_ms", 18.0),
    ("serve.launch_ms", 11.0),
    ("serve.fetch_ms", 50.0),           # [400, 850): the first fetch
    ("device.idle_in_stack_pct.gen", 100 * 261.5 / 500.5),
    ("device.idle_unattributed_pct.gen", 100 * 20 / 500.5),
])
def test_the_metrics_on_the_hand_made_trace(made, metric, want):
    trace, spans, _, window = made
    got = ps.reduce(ps.METRICS[metric], spans, trace, window, steps=16)
    assert got == pytest.approx(want, rel=1e-9)


def test_self_ms_per_whole_turns_and_per_step(made):
    trace, spans, _, _ = made
    whole = (0.0, 1000 * MS)
    assert ps.ms_per(spans, whole, ["serve.round"], "serve.round",
                     self_time=True) == pytest.approx(21)
    # per step the whole window counts, spans cut at its ends: from
    # 200 ms the first stack is [200, 290]
    late = (200 * MS, 1000 * MS)
    assert ps.ms_per(spans, late, ["serve.stack"], "step", steps=2) \
        == pytest.approx((90 + 10 + 130) / 2)
    # a window with one round's start holds no whole turn; nothing
    # matches; nothing to divide by: nothing to report
    assert ps.ms_per(spans, late, ["serve.stack"], "serve.round") is None
    assert ps.ms_per(spans, whole, ["serve.wait"], "serve.round") is None
    assert ps.ms_per(spans, whole, ["serve.stack"], "step", steps=0) is None


def test_idle_time_is_put_down_to_the_innermost_span(made):
    trace, spans, _, window = made
    idle = ps.idle_of(trace, window)
    assert tr.measure(idle) / MS == pytest.approx(500.5)
    by = ps.idle_by_span_pct(spans, idle)
    want = {"serve.stack": 260, "serve.round": 124, "serve.admit": 90,
            "unattributed": 20, "serve.launch": 5, "serve.unstack": 1.5}
    assert list(by) == list(want)                  # largest first
    for k, v in want.items():
        assert by[k] == pytest.approx(100 * v / 500.5)
    assert sum(by.values()) == pytest.approx(100)
    assert ps.named_gaps(spans, idle, top=2) == [
        ["fdt.serve.stack", pytest.approx(0.3001)],
        ["fdt.serve.stack", pytest.approx(0.2004)]]


def test_clock_offset_is_the_least_launch_to_device_start(made):
    _, spans, modules, window = made
    # 299 -> 300.4, 335 -> 345.5, 795 -> 800.1
    assert ps.clock_offset_ms(spans, modules, window) \
        == pytest.approx(1.4)
    assert ps.clock_offset_ms(spans, [], window) is None


def test_a_capture_without_the_programs_spans_reads_nothing():
    """The parent commit's capture: device events and `bench.*` only.
    Every reader returns None and the report holds no metric."""
    rows = [r for r in _made() if not r["name"].startswith("fdt.")]
    trace, spans, modules = ps.split(rows)
    assert spans == []
    for rd in ps.METRICS.values():
        assert ps.reduce(rd, spans, trace, trace.window(), 16) is None
    assert ps.report(trace, spans, modules)["metrics"] == {}


def test_fit_spans_are_read_per_step():
    rows = [{"plane": HOST, "line": "python", "name": "bench.window",
             "start_ns": 0.0, "dur_ns": 300 * MS}, _op(0, 299)]
    for i in range(3):
        a = 100 * i
        rows += [_span("fit.step", a + 1, a + 99, step_num=i + 1),
                 _span("fit.host", a + 2, a + 5),
                 _span("fit.data_wait", a + 6, a + 6.5)]
    trace, spans, modules = ps.split(rows)
    assert ps.dispatch_thread(spans) == "python#1"
    rep = ps.report(trace, spans, modules, steps=3)
    assert rep["metrics"] == {"fit.dispatch_ms": pytest.approx(3.0),
                              "fit.data_wait_ms": pytest.approx(0.5)}
    assert rep["per"] == "step" and rep["spans_per_turn"] == 3


# -- the recorded chip trace --------------------------------------------------

RECORDED = os.path.join(BENCH, "tests", "data", "v5e_generate_spans.json")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        rows = json.load(f)["rows"]
    trace, spans, modules = ps.split(rows)
    return trace, spans, modules, ps.report(trace, spans, modules)


def test_recorded_trace_is_small_and_holds_the_serving_spans(recorded):
    _, spans, modules, rep = recorded
    assert os.path.getsize(RECORDED) < 400_000
    names = {s.name for s in spans}
    assert {"serve.admit", "serve.round", "serve.stack", "serve.launch",
            "serve.unstack", "serve.finalize", "serve.fetch",
            "serve.resolve"} <= names
    assert any("sampler_chunk" in m[0] for m in modules)
    assert any("sampler_terminal" in m[0] for m in modules)
    # `trace_rounds` is 6; the sixth round was still open when the
    # capture stopped, and an open span is not recorded
    assert rep["turns"] == 5


def test_recorded_trace_reads_every_serving_metric(recorded):
    trace, spans, _, rep = recorded
    want = [m for m in ps.METRICS if not m.startswith("fit.")]
    assert sorted(rep["metrics"]) == sorted(want)
    m = rep["metrics"]
    # the parts of a round lie inside it
    assert m["serve.stack_ms"] + m["serve.unstack_ms"] \
        + m["serve.launch_ms"] < m["serve.round_host_ms"]
    window = trace.window()
    assert m["serve.round_host_ms"] * rep["turns"] \
        < 1.02 * (window[1] - window[0]) / 1e6
    assert 0 <= m["device.idle_unattributed_pct.gen"] < 100
    assert 0 < m["device.idle_in_stack_pct.gen"] < 100
    assert sum(rep["idle_by_span_pct"].values()) == pytest.approx(100)
    assert rep["idle_by_span_pct"]["unattributed"] == pytest.approx(
        m["device.idle_unattributed_pct.gen"])
    assert all(g[0].startswith("fdt.serve.") for g in rep["idle_gaps"])
    assert -5 < rep["clock_offset_ms"] < 20


# -- the CPU rehearsals -------------------------------------------------------

@pytest.mark.parametrize("cell, want", [
    ("dit-xl-2.generate", ["serve.round_host_ms", "serve.stack_ms",
                           "serve.unstack_ms", "serve.launch_ms",
                           "serve.fetch_ms"]),
    ("unet128.train", ["fit.dispatch_ms", "fit.data_wait_ms"]),
])
def test_a_traced_rehearsal_reads_the_span_metrics(cell, want, tmp_path):
    """Every `program_span` metric but the two idle shares (a CPU
    capture has no device plane) comes out of the traced rehearsal's own
    capture, through `spans.py` as a builder runs it."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(2 ** 31 + 24), "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    s = subprocess.run(
        [sys.executable, os.path.join(BENCH, "spans.py"),
         os.path.join(BENCH, "out", cell, "trace"), "--steps", "6"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert s.returncode == 0, s.stderr[-3000:]
    rep = json.loads(s.stdout.strip().splitlines()[-1])
    assert sorted(rep["metrics"]) == sorted(want)
    assert all(v > 0 for v in rep["metrics"].values())
    assert rep["idle_by_span_pct"] == {} and rep["idle_s"] == 0
