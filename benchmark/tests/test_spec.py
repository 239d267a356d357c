"""BENCHMARK.json and the data files: lint of names and units, refusal
of unknown keys, and the data-driven requirement itself — a new
configuration, mix and per-layer metric dropped into a temporary copy
are picked up with no code change."""
import json
import os
import shutil

import pytest

from harness import spec

from .conftest import BENCH, ROOT


def _raw():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_passes_the_contracts_lint():
    raw = _raw()
    spec.lint(raw)
    assert set(raw) == spec.BENCH_KEYS
    assert raw["paths"] == ["benchmark"]
    assert 1 <= raw["run_seconds"] <= 51
    four = [w for w in raw["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(raw["workloads"]) // 4)
    for w in raw["workloads"]:
        assert len(w["why"]) <= 200
    assert len(json.dumps(raw)) < 64 * 1024


def test_every_cell_resolves_and_reports_what_the_contract_asks():
    bench = spec.load_benchmark(ROOT)
    for name in bench.cell_names():
        cell = bench.cell(name)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer, name
        for m in cell.per_layer:
            assert m["moves"] in e2e, (name, m["name"])
            assert cell.traffic["kind"] in m["file"]["kinds"]


@pytest.mark.parametrize("name", ["a b", "a,b", "a/b", "", "x" * 65, "-a",
                                  "μs"])
def test_bad_names_are_refused(name):
    assert not spec.NAME_RE.match(name)


@pytest.mark.parametrize("unit", ["tokens per second", "", "x" * 17,
                                  "μs", "a,b"])
def test_bad_units_are_refused(unit):
    assert not spec.UNIT_RE.match(unit)


@pytest.mark.parametrize("unit", ["img/s/chip", "%", "ms", "tokens/s", "us"])
def test_good_units_pass(unit):
    assert spec.UNIT_RE.match(unit)


def test_unknown_keys_are_refused_not_ignored(tmp_path):
    t = {"kind": "closed_loop", "why": "x", "clients": 2, "typo_key": 1}
    p = tmp_path / "t.json"
    p.write_text(json.dumps(t))
    with pytest.raises(spec.SpecError, match="typo_key"):
        spec.load_traffic(str(p))
    raw = _raw()
    raw["end_to_end"][0]["why"] = "not allowed on a metric"
    with pytest.raises(spec.SpecError, match="why"):
        spec.lint(raw)


def _copy(tmp_path):
    root = tmp_path / "copy"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    return root


def test_new_config_mix_and_metric_are_files_not_code(tmp_path):
    root = _copy(tmp_path)
    bdir = root / "benchmark"
    cfg = json.loads((bdir / "configs" / "dit-xl-2-256.json").read_text())
    cfg["name"] = "dit-b-2-256"
    cfg["model"].update(emb_features=768, num_layers=12, num_heads=12)
    (bdir / "configs" / "dit-b-2-256.json").write_text(json.dumps(cfg))
    mix = json.loads((bdir / "traffic" / "generate-closed16.json"
                      ).read_text())
    mix.update(kind="open_loop", rate_hz=4.0, shape="burst", burst_len=8,
               burst_idle_s=1.0, peak_factor=2.0)
    del mix["clients"]
    (bdir / "traffic" / "serve-burst.json").write_text(json.dumps(mix))
    metric = {"name": "serve.latency_ms_p99", "unit": "ms",
              "better": "lower", "moves": "request_ms_p50",
              "layer": "serving (serving/scheduler.py, engine.py)",
              "source": "program_span", "kinds": ["open_loop"],
              "read": {"from": "result_field", "field": "latency_ms",
                       "percentile": 99}}
    (bdir / "layer_metrics" / "serve.latency_ms_p99.json").write_text(
        json.dumps(metric))
    raw = json.loads((root / "BENCHMARK.json").read_text())
    raw["configs"].append({"name": "dit-b-2-256", "source": "DiT-B/2",
                           "file": "benchmark/configs/dit-b-2-256.json",
                           "reduced": [], "why": "a smaller DiT"})
    raw["workloads"].append({"name": "dit-b-2.serve-burst",
                             "config": "dit-b-2-256",
                             "traffic": "serve-burst", "chips": 1,
                             "why": "bursts of 8"})
    for m in raw["end_to_end"]:
        if m["name"] in ("request_ms_p50", "gen_img_per_s"):
            m["workloads"].append("dit-b-2.serve-burst")
    raw["per_layer"].append({k: metric[k] for k in
                             ("name", "unit", "better", "moves", "layer",
                              "source")}
                            | {"workloads": ["dit-b-2.serve-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(raw))

    bench = spec.load_benchmark(str(root))
    cell = bench.cell("dit-b-2.serve-burst")
    assert cell.config["model"]["emb_features"] == 768
    assert cell.traffic["kind"] == "open_loop"
    assert "serve.latency_ms_p99" in [m["name"] for m in cell.per_layer]
    assert {m["name"] for m in cell.end_to_end} == {
        "gen_img_per_s", "request_ms_p50", "setup_s"}
    # and the general reader reads the new metric with no new code
    from harness import layer_metrics

    class R:
        latency_ms = 10.0
    w = layer_metrics.Window(trace=None, interval=None, wall_s=1.0, steps=1,
                             images=1, chips=1, results=[R(), R()],
                             counters={}, memory={}, peaks={}, cfg={})
    got = layer_metrics.read_all(cell.per_layer, w)
    assert got["serve.latency_ms_p99"] == {"value": 10.0, "unit": "ms"}


def test_peaks_table_is_exact_key_and_unknown_is_an_error():
    from harness import device
    row = device.peaks_for("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        device.peaks_for("cpu")            # placeholder, rehearsal only
    assert device.peaks_for("cpu", rehearse=True)["rehearsal_only"]


def test_a_configuration_without_limits_stops_the_run():
    """Limits are the configuration's own: one that was never read on
    the chip borrows nobody's."""
    from harness import check
    cfg = spec.load_config(os.path.join(BENCH, "configs",
                                        "dit-xl-2-256.json"))
    assert check.load_limits(cfg, "serve")["repeat_max_abs"] == 0
    with pytest.raises(KeyError, match="limits.train"):
        check.load_limits(cfg, "train")     # its training cell: unread
