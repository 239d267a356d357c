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


# -- rounds on the device's clock, rooflines, the served step's share --------

def _generate_window(record="v5e_generate_spans.json", **kw):
    """PR 24's recorded generate capture (`data/v5e_generate_spans.json`:
    rounds of 8 steps and 8 rows, not yet overlapped) as a serving
    window. Read off it by hand: five `serve.round` spans (rounds 9 to
    13) and six `jit_sampler_chunk` programs, the first launched before
    the capture; the five pair in order. Whole periods: from the start
    of round 9's program (234,529,837 ns) to the start of round 13's
    (1,959,206,504 ns), 1,724,676,667 ns holding rounds 9 to 12, whose
    chunk programs ran 924,802,083 ns and whose four terminal programs
    34,706,443 ns; 1, 3, 2 and 2 rows were finalised after them."""
    from harness import device, models
    with open(os.path.join(BENCH, "tests", "data", record)) as f:
        rows = json.load(f)["rows"]
    tr = trace.from_events(rows)
    cfg = models.effective_config(spec.load_config(os.path.join(
        BENCH, "configs", "dit-xl-2-256.json")), False)
    base = dict(trace=tr, interval=tr.window(), wall_s=2.25, steps=0,
                images=0, chips=1, results=[], counters={}, memory={},
                peaks=device.peaks_for("TPU v5 lite"), cfg=cfg,
                evals_per_row_step=2)
    base.update(kw)
    return layer_metrics.Window(**base)


def test_step_device_ms_counts_the_steps_the_rounds_ran():
    got = layer_metrics.READERS["device_rounds"](
        _metric("sampler.step_device_ms"), _generate_window())
    assert got == pytest.approx((924802083 + 34706443) / 1e6 / 32, rel=1e-9)
    assert got == pytest.approx(29.98464, abs=1e-4)


def test_served_ops_is_the_whole_steps_share_of_the_peak():
    from harness import flops
    w = _generate_window()
    got = layer_metrics.READERS["served_ops"](_metric("serve.mfu_pct"), w)
    evals = 2 * (4 * 8 * 8 + (1 + 3 + 2 + 2))          # guided: twice
    want = 100 * evals * flops.forward_flops(w.cfg) / 1.724676667 / 197e12
    assert got == pytest.approx(want, rel=1e-9)
    assert got == pytest.approx(36.89, abs=0.05)    # the device idled 45%
    # rows that ran dead steps count only their live ones
    half = _generate_window(counters={"serving/row_steps_live": 128.0,
                                      "serving/row_steps_run": 256.0})
    assert layer_metrics.READERS["served_ops"](
        _metric("serve.mfu_pct"), half) == pytest.approx(
        want * (128 + 8) / (256 + 8), rel=1e-9)


def test_the_three_readers_on_a_capture_of_overlapped_rounds():
    """PR 34's capture of `dit-xl-2.generate` (seed 2147484002, one v5e
    chip; `data/v5e_generate_rounds.json`: what `spans.py --record`
    keeps, and the window's `fdt_flash_fwd` events beside it, their
    names cut to 48 characters): every round launched while the one
    before it runs. Read off it by hand: `serve.round` spans of rounds 12 to 16
    with 2, 2, 8, 8, 2 steps; six `jit_sampler_chunk` programs, the
    first (223.2 ms, cut by the capture's start) launched before it and
    the last (6.6 ms) cut by its end. The spans pair with the programs
    of 62.35, 62.34, 230.57, 230.57 ms and the cut one: round 12's span
    opens at 49.3 ms, 4 ms after round 11's program started, and its
    own program starts at 268.4. Whole periods: 268,401,753 to
    881,435,201 ns = 613.03 ms for rounds 12 to 15: 20 steps, 3 + 1 + 2
    + 0 rows finalised, 612,901,783 ns in `jit_sampler_*` programs,
    644 flash kernel events (23 evaluations of the batch x 28 blocks)
    of 74,822,278 ns."""
    from harness import flops
    w = _generate_window("v5e_generate_rounds.json", counters={
        "serving/row_steps_live": 232.0, "serving/row_steps_run": 232.0})
    step = layer_metrics.READERS["device_rounds"](
        _metric("sampler.step_device_ms"), w)
    assert step == pytest.approx(612901783 / 1e6 / 20, rel=1e-9)
    assert step == pytest.approx(30.645, abs=1e-3)
    evals = 2 * (8 * 20 + 6)
    mfu = layer_metrics.READERS["served_ops"](_metric("serve.mfu_pct"), w)
    assert mfu == pytest.approx(
        100 * evals * flops.forward_flops(w.cfg) / 0.613033448 / 197e12,
        rel=1e-9)
    assert mfu == pytest.approx(65.27, abs=0.01)
    roof = layer_metrics.READERS["kernel_roofline"](
        _metric("kernel.flash_fwd_roofline_pct.gen"), w)
    per_eval = 28 * 4 * 256 * 1152 * 2                  # bytes, bfloat16
    assert roof["bound"] == "bytes"
    assert roof["value"] == pytest.approx(
        100 * evals * per_eval / 819e9 / 74822278e-9, rel=1e-9)
    assert roof["value"] == pytest.approx(35.79, abs=0.01)


def test_rounds_pair_with_their_programs_in_order():
    from harness import program_spans as ps
    w = _generate_window()
    rounds = ps.device_rounds(ps.from_rows(w.trace.rows),
                              ps.modules_of(w.trace.rows),
                              "^jit_sampler_chunk", 1)
    assert [r.start for r in rounds] == [234529837.0, 598697921.0,
                                         1109191156.0, 1536660734.0,
                                         1959206504.0]
    assert [(r.steps, r.rows, r.finished) for r in rounds] == [
        (8, 8, 1), (8, 8, 3), (8, 8, 2), (8, 8, 2), (8, 8, 2)]
    # no device plane, nothing to read
    bare = _generate_window(trace=trace.from_events(
        [r for r in w.trace.rows if r["plane"] == "/host:CPU"]))
    for name in ("sampler.step_device_ms", "serve.mfu_pct",
                 "kernel.flash_fwd_roofline_pct.gen"):
        read = _metric(name)
        assert layer_metrics.READERS[read["from"]](read, bare) is None


PROBE_FLASH = {
    "from": "kernel_roofline", "match": r"^%f\.7 ", "kernel": "fdt_flash_fwd",
    "of": {"flops": "bf16_flops_per_s", "bytes": "hbm_bytes_per_s"}}


def _probe_flash_window(probe, images):
    """The probe's flash-attention call is `%f.7`: 32 heads of 128 over
    1,024 tokens, twice inside the window for 115,103 ns each. As one
    DiT block's attention over one row: 4 x 1024^2 x 4096 = 17.18 GFLOP
    (87.2 us at the peak) and 4 x 1024 x 4096 x 2 bytes = 33.6 MB (41.0
    us): bound by operations, 87.2 / 115.1 = 75.8%."""
    from harness import device
    cfg = {"family": "dit",
           "model": {"emb_features": 4096, "num_layers": 1, "patch_size": 2,
                     "dtype": "bfloat16"},
           "input": {"resolution": 64}}
    return _window(probe, images=images, cfg=cfg,
                   peaks=device.peaks_for("TPU v5 lite"))


def test_kernel_roofline_on_the_probes_flash_call(probe):
    got = layer_metrics.READERS["kernel_roofline"](
        PROBE_FLASH, _probe_flash_window(probe, 2))
    assert got["bound"] == "operations"
    assert got["value"] == pytest.approx(
        100 * 2 * 4 * 1024 ** 2 * 4096 / 197e12 / 230206e-9, rel=1e-9)
    assert got["value"] == pytest.approx(75.77, abs=0.01)
    notes = {}
    m = {"name": "k", "unit": "%", "file": {"read": PROBE_FLASH}}
    line = layer_metrics.read_all([m], _probe_flash_window(probe, 2), notes)
    assert line["k"] == {"value": got["value"], "unit": "%"}
    assert notes == {"k": {"bound": "operations"}}


def test_a_share_over_100_is_an_error_not_clipped(probe):
    with pytest.raises(ValueError, match="counted too high"):
        layer_metrics.READERS["kernel_roofline"](
            PROBE_FLASH, _probe_flash_window(probe, 4))


def test_flash_costs_match_the_reference_attentions_own_count():
    """`kernel_costs` against a count of the plain attention's jaxpr, at
    the head size the model has (72), not the 128 a kernel pads it to."""
    import jax
    import jax.numpy as jnp
    from harness import flops, models
    from reference import nn
    from .test_flops_and_references import _jaxpr_flops
    cfg = models.effective_config(spec.load_config(os.path.join(
        BENCH, "configs", "dit-xl-2-256.json")), False)
    m = cfg["model"]
    heads, d = m["num_heads"], m["emb_features"] // m["num_heads"]
    assert d == 72
    q = jnp.zeros((1, 256, heads, d))
    counted = _jaxpr_flops(jax.make_jaxpr(nn.attention)(q, q, q).jaxpr)
    cost = flops.kernel_costs(cfg)["fdt_flash_fwd"]
    assert cost["flops"] == pytest.approx(m["num_layers"] * counted,
                                          rel=1e-12)
    assert cost["bytes"] == m["num_layers"] * 4 * q.size * 2    # bfloat16


SERVING_COUNTERS = {
    "serving/rows_real", "serving/rounds", "serving/row_steps_live",
    "serving/row_steps_run", "serving/terminal_turns",
    "serving/dispatch_work_ms", "serving/dispatch_loop_ms"}
MOE_COUNTERS = {"moe/picks_routed", "moe/picks_held", "moe/picks_hottest",
                "moe/picks_fitted"}


@pytest.mark.parametrize("cell,counters", [
    ("unet128.train", {"fit/log_step_stall_ms"}),
    ("dit-xl-2.generate", SERVING_COUNTERS),
    ("brumby-14b.generate-fewer", SERVING_COUNTERS),
    ("command-a-plus.generate-few", SERVING_COUNTERS | MOE_COUNTERS),
    ("glm-5.2.generate-fewer-1024", SERVING_COUNTERS | MOE_COUNTERS
     | {"dsa/keys_selected", "dsa/keys_visible"})])
def test_the_counters_a_cell_reads_are_the_ones_its_files_name(cell,
                                                               counters):
    bench = spec.load_benchmark(os.path.dirname(BENCH))
    named = layer_metrics.counters_named(bench.cell(cell).per_layer)
    assert len(set(named)) == len(named)
    assert set(named) == counters


def test_dump_rows_keeps_host_spans_and_the_tail(tmp_path, probe):
    import gzip
    out = tmp_path / "rows.json.gz"
    trace.dump_rows(probe, str(out), limit=20)
    with gzip.open(out, "rt") as f:
        rows = json.load(f)
    lines = ("XLA Ops", "Async XLA Ops")
    ops = [r for r in probe.rows if r["line"] in lines]
    kept = [r for r in rows if r["line"] in lines]
    assert len(ops) > 123 and len(kept) == 20
    assert [r["start_ns"] for r in kept] == [
        r["start_ns"] for r in ops[:10] + ops[-10:]]
    assert [r for r in rows if r["name"] == "bench.window"]


def _served(steps, turn_ms, capture_from_ms, clock_ms=0.0, ahead=1):
    """A synthetic capture of a dispatch thread `ahead` rounds ahead of
    its device: round k's span opens once the program of round k - 1 -
    `ahead` has ended (and the turn before is over), launches a program of 7.0 + 28.1 ms a
    step, and a 5 ms terminal follows every program. The capture keeps
    what starts after `capture_from_ms`; the device's clock reads
    `clock_ms` ahead of the host's. Returns (rows, {program start:
    steps})."""
    ms = 1e6
    host_free, dev_free, ends, rows, truth = 0.0, 0.0, [], [], {}
    for k, n in enumerate(steps):
        opens = max(host_free,
                    ends[k - 1 - ahead] if k > ahead else 0.0) + 3 * ms
        start = max(opens + 0.1 * ms, dev_free)
        dur = (7.0 + 28.1 * n) * ms
        ends.append(start + dur)
        dev_free = start + dur + 5 * ms
        host_free = opens + turn_ms * ms
        truth[start + clock_ms * ms] = n
        for name, line, plane, s, d, stats in (
                ("fdt.serve.round", "python3", "/host:CPU", opens, 4 * ms,
                 {"round": k, "bucket": 8, "rows": 8, "steps": n}),
                ("fdt.serve.finalize", "python3", "/host:CPU",
                 opens + 4.5 * ms, ms, {"rows": 1, "bucket": 1}),
                ("jit_sampler_chunk(1)", "XLA Modules", "/device:TPU:0",
                 start + clock_ms * ms, dur, None),
                ("jit_sampler_terminal(2)", "XLA Modules", "/device:TPU:0",
                 start + dur + clock_ms * ms, 5 * ms, None)):
            if s >= capture_from_ms * ms:
                row = {"plane": plane, "line": line, "name": name,
                       "start_ns": s, "dur_ns": d}
                if stats is not None:
                    row.update(thread="python3#1", stats=stats)
                rows.append(row)
    return rows, truth


@pytest.mark.parametrize("turn_ms", [8.0, 300.0],
                         ids=["device-bound", "host-bound"])
@pytest.mark.parametrize("clock_ms", [-4.0, 0.0, 2.0])
def test_rounds_pair_with_their_programs_wherever_the_capture_cuts(
        turn_ms, clock_ms):
    """With the device saturated, round k's span opens just as round
    k-1's program starts, so start times cannot pair them; the
    programs' own durations can. Every cut of the capture, both regimes,
    and a clock offset either way give each program its own steps."""
    from harness import program_spans as ps
    steps = [8, 8, 2, 2, 8, 5, 8, 3, 8, 8, 2, 8]
    paired = 0
    for cut in range(0, 900, 7):
        rows, truth = _served(steps, turn_ms, float(cut), clock_ms)
        rounds = ps.device_rounds(ps.from_rows(rows), ps.modules_of(rows),
                                  "^jit_sampler_chunk", 1)
        for r in rounds:
            assert truth[r.start] == r.steps, (cut, r)
            assert r.finished == 1
        paired += len(rounds)
    assert paired > 500


def _pair(rows, rounds_ahead=1):
    from harness import program_spans as ps
    return ps.device_rounds(ps.from_rows(rows), ps.modules_of(rows),
                            "^jit_sampler_chunk", rounds_ahead)


def test_a_pairing_that_cannot_be_told_is_an_error_not_a_guess():
    """Rounds of 3, 5 and 8 steps behind a saturated device, the
    capture's first program launched before it: the spans fit their own
    programs, and fit the programs one earlier just as well (more steps,
    longer, every time). The two give the 3-step program 3 and 5 steps:
    30% of the 18 steps either way."""
    from harness import program_spans as ps
    rows, truth = _served([8, 8, 3, 5, 8], 8.0, 300.0)
    assert sorted(truth.values())[:2] == [3, 5]
    with pytest.raises(ps.PairingError, match="both pair"):
        _pair(rows)
    # the metric's reader does not swallow it
    w = _generate_window(trace=trace.from_events(rows), interval=(0.0, 2e9))
    with pytest.raises(ps.PairingError):
        layer_metrics.READERS["device_rounds"](
            _metric("sampler.step_device_ms"), w)
    # one more round of 8 behind them and only one pairing is left
    rows, truth = _served([8, 8, 3, 5, 8, 8], 8.0, 300.0)
    assert [truth[r.start] for r in _pair(rows)] == [
        r.steps for r in _pair(rows)]


def test_a_deeper_run_ahead_than_the_file_states_is_an_error():
    """The metric's file states how far the dispatch thread runs ahead
    (`rounds_ahead`); the harness takes no constant of the program's. A
    thread two rounds ahead, read as one ahead: a round's program still
    runs when the span two rounds later opens, and the pairing one
    earlier, which the times allow, has rounds of the same steps against
    programs that differ: an error. Read as two ahead it pairs."""
    from harness import program_spans as ps
    steps = [8, 8, 2, 2, 8, 5, 8, 3, 8, 8, 2, 8]
    rows, truth = _served(steps, 8.0, 300.0, ahead=2)
    with pytest.raises(ps.PairingError, match="no pairing"):
        _pair(rows, rounds_ahead=1)
    rounds = _pair(rows, rounds_ahead=2)
    assert len(rounds) >= 8
    assert [truth[r.start] for r in rounds] == [r.steps for r in rounds]


def test_a_capture_with_nothing_to_pair_reads_nothing():
    from harness import program_spans as ps
    rows, _ = _served([8, 8, 8, 8], 8.0, 0.0)
    assert _pair([r for r in rows if r["plane"] == "/host:CPU"]) == []
    assert _pair([r for r in rows if r["plane"] != "/host:CPU"]) == []
    # programs that all ran before the spans opened: there, and unpairable
    late = [dict(r, start_ns=r["start_ns"] + 5e9)
            if r["plane"] == "/host:CPU" else r for r in rows]
    with pytest.raises(ps.PairingError, match="no pairing"):
        _pair(late)


def test_a_reader_of_rounds_has_to_state_the_run_ahead(tmp_path):
    m = spec.load_layer_metric(os.path.join(
        BENCH, "layer_metrics", "sampler.step_device_ms.json"))
    m["read"] = {k: v for k, v in m["read"].items() if k != "rounds_ahead"}
    path = tmp_path / "m.json"
    path.write_text(json.dumps(m))
    with pytest.raises(spec.SpecError, match="rounds_ahead"):
        spec.load_layer_metric(str(path))
