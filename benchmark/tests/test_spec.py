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


@pytest.mark.parametrize("name", [w["name"] for w in _raw()["workloads"]])
def test_every_cell_resolves_and_reports_what_the_contract_asks(name):
    """Data only: what guards the files a `model_config` PR adds."""
    cell = spec.load_benchmark(ROOT).cell(name)
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


# -- a configuration drawn from the catalog ---------------------------------

TOY_DIR = os.path.join(BENCH, "tests", "data", "configs")


def _toy(tmp_path):
    """A copy of the toy catalog configuration (file, row, and its entry
    in BENCHMARK.json `configs`), to break."""
    shutil.copytree(TOY_DIR, tmp_path / "configs")
    path = tmp_path / "configs" / "toy-catalog.json"
    row_path = tmp_path / "configs" / "sources" / "toy-catalog.json"
    cfg, row = json.loads(path.read_text()), json.loads(row_path.read_text())
    entry = {"name": cfg["name"], "source": row["source_url"],
             "file": "configs/toy-catalog.json",
             "reduced": list(cfg["reduced"]), "why": "a toy"}
    return path, row_path, cfg, row, entry


@pytest.fixture
def toy_dit(monkeypatch):
    """A model that reads the toy row's keys under the source's names,
    as the model a `model_config` PR brings reads its row's: SimpleDiT
    with fields for the per-layer list, the head size, the experts held
    and of a token, and the vocabulary (it does nothing with them)."""
    import dataclasses

    from flaxdiff_tpu.inference import registry
    from flaxdiff_tpu.models.dit import SimpleDiT

    @dataclasses.dataclass
    class ToyDiT(SimpleDiT):
        layer_types: tuple = ()
        head_dim: int = 0
        num_experts: int = 0
        num_experts_per_tok: int = 0
        vocab_size: int = 0
        __hash__ = SimpleDiT.__hash__

    monkeypatch.setitem(registry.MODEL_REGISTRY, "toy_dit", ToyDiT)


def test_a_catalog_configuration_holds_its_sources_keys_at_the_top(
        tmp_path, toy_dit, capsys):
    from harness import models
    path, _, _, row, entry = _toy(tmp_path)
    cfg = spec.load_config(str(path), entry)
    assert set(row["config"]) <= set(cfg)
    assert set(spec.source_keys(cfg)) == set(row["config"])
    assert cfg["rms_norm_eps"] is None and cfg["use_qk_norm"] is False
    assert cfg["rope_parameters"] == row["config"]["rope_parameters"]
    # the model is built from the source's keys as they stand after
    # `reduced`, with the program's own `model` group over them
    eff = models.effective_config(cfg, False)
    assert eff["model"]["num_layers"] == 4 and eff["model"]["patch_size"] == 2
    assert eff["model"]["layer_types"] == ["local", "local", "local", "full"]
    model, _, _, shapes = models.build(eff)
    assert (model.emb_features, model.head_dim, model.num_experts) == (
        32, 8, 8)
    assert model.layer_types == ("local", "local", "local", "full")
    assert sorted(k for k in shapes if k.startswith("block_")) == [
        "block_0", "block_1", "block_2", "block_3"]
    # what the model has no field for is said, every run
    said = capsys.readouterr().out
    assert "does not read: ['model_type', 'rms_norm_eps', " \
        "'rope_parameters', 'use_qk_norm']" in said
    # a rehearsal may shrink a width, at the top level, that a run may not
    assert models.effective_config(cfg, True)["model"]["emb_features"] == 16
    assert models.effective_config(cfg, True)["model"]["patch_size"] == 4


@pytest.mark.parametrize("registry_name,change,names", [
    ("simple_dit", {}, "head_dim.*layer_types.*num_experts"),
    ("toy_dit", {"model": {"patch": 2}}, "patch"),
], ids=["a-width-and-a-reduced-key", "a-key-of-the-model-group"])
def test_a_key_the_model_would_drop_is_refused(tmp_path, toy_dit,
                                               registry_name, change, names):
    """`build_model` drops a key its class has no field for. A width or
    a reduced key dropped would pass the lint as equal to the source
    while the model runs at its own default."""
    from harness import models
    path, _, _, _, entry = _toy(tmp_path)
    eff = models.effective_config(spec.load_config(str(path), entry), False)
    eff["registry_name"] = registry_name
    for group, values in change.items():
        eff[group].update(values)
    with pytest.raises(spec.SpecError, match=names):
        models.build(eff)


def _missing(cfg, row, entry):
    del cfg["use_qk_norm"]


def _number_as_null(cfg, row, entry):
    cfg["mlp_ratio"] = None


def _width_changed(cfg, row, entry):
    cfg["emb_features"] = 16


def _width_listed(cfg, row, entry):
    cfg["emb_features"] = 16
    cfg["reduced"].append("emb_features")
    entry["reduced"].append("emb_features")
    cfg["published"]["emb_features"] = 32


def _reduced_differs(cfg, row, entry):
    entry["reduced"] = ["num_layers"]


def _collision(cfg, row, entry):
    row["config"]["family"] = "toy"


def _grown(cfg, row, entry):
    cfg["num_layers"] = 8


def _head_size_listed(cfg, row, entry):
    cfg["head_dim"] = 4
    cfg["reduced"].append("head_dim")
    entry["reduced"].append("head_dim")


def _published_missing(cfg, row, entry):
    del cfg["published"]["num_layers"]


def _stray_key(cfg, row, entry):
    cfg["hidden_size"] = 32         # the row's own size, not in `config`


def _half_a_period(cfg, row, entry):
    cfg["layer_types"], cfg["num_layers"] = ["local", "full"], 2


def _list_and_count_disagree(cfg, row, entry):
    cfg["num_layers"] = 5


def _few_experts(cfg, row, entry):
    cfg["num_experts"] = 4


def _an_experts_share_of_a_token(cfg, row, entry):
    cfg["num_experts_per_tok"] = 1
    cfg["reduced"].append("num_experts_per_tok")
    entry["reduced"].append("num_experts_per_tok")


def _thin_vocabulary(cfg, row, entry):
    cfg["vocab_size"] = 7


@pytest.mark.parametrize("breaks,names", [
    (_missing, "use_qk_norm"), (_number_as_null, "mlp_ratio"),
    (_width_changed, "emb_features"), (_width_listed, "emb_features"),
    (_reduced_differs, "reduced"), (_collision, "family"),
    (_grown, "num_layers"), (_head_size_listed, "head_dim"),
    (_published_missing, "published"), (_stray_key, "hidden_size"),
    (_half_a_period, "layer_types.*whole period"),
    (_list_and_count_disagree, "layer_types.*num_layers"),
    (_few_experts, "num_experts.*8 routed"),
    (_an_experts_share_of_a_token, "num_experts_per_tok.*never a width"),
    (_thin_vocabulary, "vocab_size.*eighth")],
    ids=lambda v: getattr(v, "__name__", None))
def test_a_file_that_differs_from_its_source_is_refused(tmp_path, breaks,
                                                        names):
    """Where the driver would say `config_differs`, `load_config` fails
    first, naming the key."""
    path, row_path, cfg, row, entry = _toy(tmp_path)
    breaks(cfg, row, entry)
    path.write_text(json.dumps(cfg))
    row_path.write_text(json.dumps(row))
    with pytest.raises(spec.SpecError, match=names):
        spec.load_config(str(path), entry)


# -- `reduced`: a list is told by its shape, a token's experts by name ------

LAYOUTS_ROW = os.path.join(TOY_DIR, "sources", "toy-layouts.json")


def _layouts(tmp_path, depth, lists=("rope_layout", "sliding_window_layout"),
             **cut):
    """A configuration file built from the toy row whose per-layer lists
    are called layouts (SmallThinker's names): `num_hidden_layers` and
    the named lists cut to their first `depth` entries, `cut` the other
    keys changed; everything that differs is listed in `reduced`."""
    (tmp_path / "configs" / "sources").mkdir(parents=True)
    shutil.copy(LAYOUTS_ROW, tmp_path / "configs" / "sources")
    with open(LAYOUTS_ROW) as f:
        row = json.load(f)
    with open(os.path.join(TOY_DIR, "toy-catalog.json")) as f:
        toy = json.load(f)
    src = row["config"]
    cfg = {k: toy[k] for k in ("family", "registry_name", "model", "input",
                               "conditioning", "schedule", "predictor",
                               "assumed", "deployment")}
    cfg.update(src, name=row["name"], source=row["source_url"],
               num_hidden_layers=depth)
    cfg.update({k: src[k][:depth] for k in lists}, **cut)
    cfg["reduced"] = [k for k in src if not spec._same(cfg[k], src[k])]
    cfg["published"] = {k: src[k] for k in cfg["reduced"]}
    path = tmp_path / "configs" / "toy-layouts.json"
    path.write_text(json.dumps(cfg))
    entry = {"name": cfg["name"], "source": cfg["source"],
             "file": "configs/toy-layouts.json",
             "reduced": list(cfg["reduced"]), "why": "a toy"}
    return str(path), entry


@pytest.mark.parametrize("how,refused", [
    (dict(depth=8), None),
    (dict(depth=8, moe_num_primary_experts=8), None),
    (dict(depth=8, lists=("rope_layout",)),
     "'sliding_window_layout' lists 16 layers and 'num_hidden_layers' is 8"),
    (dict(depth=4, lists=()), "'rope_layout' lists 16 layers"),
    (dict(depth=3), "num_hidden_layers.*at least four layers"),
    (dict(depth=8, rope_layout=[0, 1, 1]), "rope_layout.*whole period"),
    (dict(depth=8, sliding_window_layout=[0, 1, 1, 1, 1, 1, 1, 1]),
     "sliding_window_layout.*may only be smaller"),
    (dict(depth=8, sliding_window_size=8),
     "sliding_window_size.*never a width"),
    (dict(depth=8, moe_num_active_primary_experts=1),
     "moe_num_active_primary_experts.*the experts a token picks are a "
     "width"),
    (dict(depth=8, moe_ffn_hidden_size=16),
     "moe_ffn_hidden_size.*never a width"),
    (dict(depth=8, rope_short_factor=[1.0, 1.5]),
     "rope_short_factor.*the list that goes with depth.*never a width"),
], ids=["both-lists-two-periods", "and-the-experts-held", "one-list-cut",
        "the-count-alone", "under-four-layers", "less-than-a-period",
        "not-a-run-of-the-published-entries", "the-window", "a-tokens-experts",
        "an-experts-width", "a-list-of-another-length"])
def test_reduced_knows_a_per_layer_list_by_its_shape(tmp_path, how, refused):
    """A list with one entry a layer may be cut with the depth whatever
    it is called (at the parent `sliding_window_layout` was refused as
    a width, for the `window` in its name); a scalar is a count by its
    name, and never where the name says "a token's"."""
    path, entry = _layouts(tmp_path, **how)
    if refused is None:
        cfg = spec.load_config(path, entry)
        assert cfg["num_hidden_layers"] == len(cfg["rope_layout"]) == len(
            cfg["sliding_window_layout"]) == 8
        assert {"num_hidden_layers", "rope_layout",
                "sliding_window_layout"} <= set(cfg["reduced"])
    else:
        with pytest.raises(spec.SpecError, match=refused):
            spec.load_config(path, entry)


@pytest.mark.parametrize("name,token", [
    ("moe_num_active_primary_experts", True), ("num_used_experts", True),
    ("top_k_experts", True), ("topk_experts", True),
    ("n_experts_per_token_heads", True), ("num_active_heads", True),
    ("moe_num_primary_experts", False), ("n_routed_experts", False),
    ("num_key_value_heads", False), ("num_hidden_layers", False),
    ("deactivated_experts", False), ("unused_experts", False)])
def test_a_count_whose_name_says_a_token_is_a_width(name, token):
    assert spec.COUNT_RE.search(name)
    assert bool(spec.TOKEN_RE.search(name)) == token


def test_the_configurations_without_a_source_copy_load_as_before():
    plain = [c for c in _raw()["configs"] if not os.path.exists(os.path.join(
        BENCH, "configs", "sources", c["name"] + ".json"))]
    assert {c["name"] for c in plain} >= {"unet-flaxdiff-128",
                                          "dit-xl-2-256"}
    for c in plain:
        cfg = spec.load_config(os.path.join(ROOT, c["file"]), c)
        assert not spec.source_keys(cfg)
        assert cfg["reduced"] == c["reduced"]


@pytest.mark.parametrize("kinds,want", [
    (["local", "local", "local", "full"] * 8, (0, 4)),
    (["dense"] + ["moe"] * 9, (1, 1)),
    (["dense", "dense"] + ["a", "b"] * 4 + ["a"], (2, 2)),
    (["a", "b", "c"], (0, 3))])
def test_the_period_of_a_published_per_layer_list(kinds, want):
    assert spec._pattern(kinds) == want


@pytest.mark.parametrize("got,low", [
    (["dense", "moe", "moe", "moe"], True),        # three after the dense one
    (["dense", "moe", "moe", "moe", "moe"], False),
    (["moe"] * 4, False), (["moe"] * 3, True)])
def test_leading_layers_count_once_towards_the_floor(got, low):
    said = spec._under_floor("layer_types", got, ["dense"] + ["moe"] * 9)
    assert bool(said) == low and ("whole period" in said) == low
