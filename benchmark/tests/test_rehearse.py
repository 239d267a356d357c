"""Each cell end to end with `--rehearse` on the CPU at tiny sizes (the
four-chip cell on four virtual devices); the result line's keys, metric
names and units against BENCHMARK.json; no result line without a TPU;
and `correct` coming out false when the timed path is broken underneath
or when the reference in a lower precision stands in the program's
place."""
import json
import os
import subprocess
import sys

import pytest

from harness import spec

from .conftest import BENCH, ROOT

RUN = os.path.join(BENCH, "run.py")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _run(args, cwd=ROOT, timeout=1500):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, RUN] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _last_json(stdout: str):
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", _cells())
@pytest.mark.parametrize("traced", [0, 1])
def test_cell_rehearses_end_to_end(cell, traced):
    r = _run(["--workload", cell, "--seed", str(2 ** 31 + 11 + traced),
              "--seconds", "2", "--trace", str(traced), "--rehearse"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = _last_json(r.stdout)
    assert LINE_KEYS <= set(line) <= LINE_KEYS | {"breakdown", "notes",
                                                  "compared"}
    # each number compared beside its limit: the line's last key, and the
    # last lines on standard error
    assert list(line)[-1] == "compared" and line["compared"]
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert r.stderr.strip().splitlines()[-1].startswith("compared ")
    assert line["correct"] is True, r.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"       # labelled, not a chip
    c = spec.load_benchmark(ROOT).cell(cell)
    assert line["device"]["count"] >= c.chips
    declared = {m["name"]: m["unit"] for m in
                (c.per_layer if traced else c.end_to_end)}
    got = {k: v["unit"] for k, v in line["metrics"].items()}
    assert got.items() <= declared.items()
    if traced:
        # the CPU's trace has no device plane: the device readers find
        # nothing and are left out; the host-side ones are all there
        host = {m["name"] for m in c.per_layer
                if m["source"] != "device_trace"
                and m["file"]["read"]["from"] != "memory_stats"}
        assert host <= set(got)
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        assert set(got) == set(declared)
        assert all(v["value"] > 0 for v in line["metrics"].values())
    assert "compilations inside the window: 0" in r.stdout


def test_no_tpu_means_no_result_line():
    r = _run(["--workload", _cells()[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"])
    assert r.returncode != 0
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]


def test_benchmark_alone_in_a_directory_fails_without_a_result(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", _cells()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]


def _main_in_process(argv, capsys):
    import importlib
    run = importlib.import_module("run")
    rc = run.main(argv)
    out = capsys.readouterr().out
    return rc, _last_json(out), out


TRAIN = [c for c in _cells() if c.startswith("unet")][:1]
SERVE = [c for c in _cells() if "generate" in c][:1]


@pytest.mark.skipif(not TRAIN, reason="no training cell")
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch, capsys):
    from harness import train_steady
    build = train_steady.build_trainer

    def broken(*a, **k):
        trainer, init_fn, shapes = build(*a, **k)
        import jax
        plain = jax.jit(trainer._step_fn)      # no donation: state lives
        trainer._step = lambda state, batch: (state,
                                              plain(state, batch)[1])
        return trainer, init_fn, shapes

    monkeypatch.setattr(train_steady, "build_trainer", broken)
    rc, line, out = _main_in_process(
        ["--workload", TRAIN[0], "--seed", "3", "--seconds", "1",
         "--trace", "0", "--rehearse"], capsys)
    assert rc == 0 and line["correct"] is False, out[-2000:]
    assert "FAIL" in out


@pytest.mark.skipif(not TRAIN, reason="no training cell")
def test_half_of_every_batch_left_out_is_not_correct(monkeypatch, capsys):
    """A part of the batch left out. At seeded weights every row's loss
    is nearly the same, so the loss hardly shows it (3% here, under its
    limit); the first gradient does."""
    from harness import train_steady
    feed = train_steady._feed

    def halved(batches, start):
        import jax
        for b in feed(batches, start):
            yield jax.tree_util.tree_map(lambda a: a[:len(a) // 2], b)

    monkeypatch.setattr(train_steady, "_feed", halved)
    rc, line, out = _main_in_process(
        ["--workload", TRAIN[0], "--seed", "3", "--seconds", "1",
         "--trace", "0", "--rehearse"], capsys)
    assert rc == 0 and line["correct"] is False, out[-2000:]
    assert [ln for ln in out.splitlines()
            if "first-gradient norm" in ln and ln.endswith("FAIL")], out


@pytest.mark.skipif(not TRAIN, reason="no training cell")
def test_a_gradient_at_twice_its_scale_is_not_correct(monkeypatch, capsys):
    """The fault the first gradient's limit is there to catch: Adam's
    update hardly moves with the gradient's scale, so neither the loss
    nor the parameters' change shows it."""
    import optax
    adamw = optax.adamw
    monkeypatch.setattr(optax, "adamw", lambda *a, **k: optax.chain(
        optax.scale(2.0), adamw(*a, **k)))
    rc, line, out = _main_in_process(
        ["--workload", TRAIN[0], "--seed", "3", "--seconds", "1",
         "--trace", "0", "--rehearse"], capsys)
    assert rc == 0 and line["correct"] is False, out[-2000:]
    failed = [ln for ln in out.splitlines() if ln.endswith("FAIL")]
    assert failed and all("first-gradient" in ln or "first gradient" in ln
                          for ln in failed), out[-2000:]


@pytest.mark.skipif(not SERVE, reason="no serving cell")
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        monkeypatch, capsys):
    from flaxdiff_tpu.serving.engine import SamplerProgramEngine
    real = SamplerProgramEngine.finalize

    def altered(self, rows, bucket):
        out, secs = real(self, rows, bucket)
        return -out, secs

    monkeypatch.setattr(SamplerProgramEngine, "finalize", altered)
    rc, line, out = _main_in_process(
        ["--workload", SERVE[0], "--seed", "3", "--seconds", "1",
         "--trace", "0", "--rehearse"], capsys)
    assert rc == 0 and line["correct"] is False, out[-2000:]


@pytest.mark.skipif(not SERVE, reason="no serving cell")
def test_a_trajectory_whose_last_round_is_dropped_is_not_correct(
        monkeypatch, capsys):
    """Every step of every round moves what is compared: a scheduler
    that never runs a request's last round (2 to 6 of its 20 to 50
    steps) is caught."""
    from flaxdiff_tpu.serving.engine import SamplerProgramEngine
    real = SamplerProgramEngine.advance

    def short(self, rows, bucket, round_steps):
        for r in rows:
            if r.done and 0 < r.remaining <= round_steps:
                r.done = r.nfe          # its last round: no step is run
        return real(self, rows, bucket, round_steps)

    monkeypatch.setattr(SamplerProgramEngine, "advance", short)
    rc, line, out = _main_in_process(
        ["--workload", SERVE[0], "--seed", "3", "--seconds", "1",
         "--trace", "0", "--rehearse"], capsys)
    assert rc == 0 and line["correct"] is False, out[-2000:]
    assert "largest gap = 0 " in out        # still repeatable, only wrong


@pytest.mark.skipif(not SERVE, reason="no serving cell")
def test_readings_over_seeds_in_one_process():
    """The builder's tool that limits are set from: sound readings under
    the limits, the control's over them."""
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "readings.py"), "--workload",
         SERVE[0], "--seeds", "5,6", "--control-seeds", "7", "--seconds",
         "1", "--rehearse"], cwd=ROOT, capture_output=True, text=True,
        timeout=900, env={k: v for k, v in os.environ.items()
                          if k != "XLA_FLAGS"})
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    got = _last_json(r.stdout)
    assert [s["correct"] for s in got["sound"]] == [True, True]
    assert [c["correct"] for c in got["control"]] == [False]


@pytest.mark.parametrize("cell", TRAIN + SERVE)
def test_the_control_in_a_lower_precision_is_not_correct(cell, capsys):
    """The reference with its products in fp8 (the step below the
    configuration's bfloat16), put in the program's place, at the test's
    size."""
    rc, line, out = _main_in_process(
        ["--workload", cell, "--seed", "4", "--seconds", "1", "--trace",
         "0", "--rehearse", "--control", "fp8"], capsys)
    assert rc == 0 and line["correct"] is False, out[-2000:]


def test_an_open_loop_cell_is_data_alone(tmp_path):
    """No first cell is open-loop, but a later PR can bring one as data:
    a mix file and a `workloads` entry, dropped into a copy, run end to
    end by the same harness (Poisson arrivals, each request timed from
    when it was due, the generator's own lateness printed)."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "flaxdiff_tpu"), tmp_path / "flaxdiff_tpu")
    mix = {"kind": "open_loop", "why": "Poisson arrivals below the knee",
           "rate_hz": 15.0, "shape": "poisson",
           "nfe_deal": {"20": 6, "30": 3, "50": 1}, "guidance_scale": 3.0,
           "images_per_request": 1, "sampler": "ddim", "trace_rounds": 3,
           "check_requests": 3, "warm_blocks": 1}
    (tmp_path / "benchmark" / "traffic" / "serve-p08.json").write_text(
        json.dumps(mix))
    raw = json.loads((tmp_path / "BENCHMARK.json").read_text())
    raw["workloads"].append({"name": "dit-xl-2.serve-p08",
                             "config": "dit-xl-2-256",
                             "traffic": "serve-p08", "chips": 1,
                             "why": "open loop at four fifths of the knee"})
    for m in raw["end_to_end"] + raw["per_layer"]:
        if "workloads" in m and "dit-xl-2.generate" in m["workloads"]:
            m["workloads"].append("dit-xl-2.serve-p08")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(raw))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "dit-xl-2.serve-p08", "--seed", "9", "--seconds", "3",
         "--trace", "0", "--rehearse"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = _last_json(r.stdout)
    assert line["correct"] is True, r.stdout[-3000:]
    assert set(line["metrics"]) == {"gen_img_per_s", "request_ms_p50",
                                    "setup_s"}
    assert "generator lateness ms" in r.stdout
    assert 20 <= line["attempted"] <= 80      # ~15 Hz for 3 s, not a flood


FSDP_CELL = {"name": "dit-xl-2.train-fsdp4", "config": "dit-xl-2-256",
             "traffic": "train-steady", "chips": 4,
             "why": "global batch 256 (64 a chip), state sharded 4 ways"}


@pytest.mark.parametrize("traced", [0, 1])
def test_the_four_chip_fsdp_cell_rehearses_on_four_virtual_devices(
        tmp_path, traced):
    """The cell PR 23 could not prove on the chip, kept runnable: added
    to a copy as data (a `workloads` entry and its two `fsdp.*` metrics)
    and run end to end on four virtual CPU devices."""
    import shutil
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "flaxdiff_tpu"), tmp_path / "flaxdiff_tpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = json.load(f)
    if FSDP_CELL["name"] not in [w["name"] for w in raw["workloads"]]:
        raw["workloads"].append(FSDP_CELL)
        for m in raw["end_to_end"] + raw["per_layer"]:
            if "workloads" in m and "unet128.train" in m["workloads"]:
                m["workloads"].append(FSDP_CELL["name"])
        for name in ("fsdp.collective_pct", "fsdp.exposed_collective_ms"):
            f = spec.load_layer_metric(os.path.join(
                BENCH, "layer_metrics", name + ".json"))
            raw["per_layer"].append(
                {k: f[k] for k in ("name", "unit", "better", "source",
                                   "layer", "moves")}
                | {"workloads": [FSDP_CELL["name"]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(raw))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", FSDP_CELL["name"], "--seed", "13", "--seconds", "2",
         "--trace", str(traced), "--rehearse"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=1500)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = _last_json(r.stdout)
    assert line["correct"] is True, r.stdout[-3000:]
    assert line["device"]["count"] == 4
    if traced:
        assert {"fit.step_wall_ms", "train.mfu_pct"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"train_img_per_s_chip", "setup_s"}


@pytest.mark.skipif(not SERVE, reason="no serving cell")
def test_a_new_counter_is_read_by_a_metric_file_alone(tmp_path):
    """The counters a cell reads are the ones its files name: the step
    occupancy (live row-steps over row-steps run; 1.0 since rounds end
    where their first row ends) added to a copy as a file and an entry,
    with no edit to the harness."""
    import shutil
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "flaxdiff_tpu"), tmp_path / "flaxdiff_tpu")
    metric = {"name": "serve.step_occupancy", "unit": "share",
              "better": "higher", "moves": "gen_img_per_s",
              "layer": "serving (serving/scheduler.py, engine.py)",
              "source": "program_counter",
              "kinds": ["closed_loop", "open_loop"],
              "read": {"from": "counter_ratio",
                       "numerator": "serving/row_steps_live",
                       "denominator": "serving/row_steps_run"}}
    (tmp_path / "benchmark" / "layer_metrics" / "serve.step_occupancy.json"
     ).write_text(json.dumps(metric))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = json.load(f)
    raw["per_layer"].append(
        {k: metric[k] for k in ("name", "unit", "better", "moves", "layer",
                                "source")} | {"workloads": [SERVE[0]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(raw))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", SERVE[0], "--seed", "17", "--seconds", "2",
         "--trace", "1", "--rehearse"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = _last_json(r.stdout)
    assert line["correct"] is True
    assert line["metrics"]["serve.step_occupancy"] == {"value": 1.0,
                                                       "unit": "share"}
