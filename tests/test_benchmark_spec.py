"""The benchmark's data files, held to the contract by the driver's own
run of tier-1 (data only: no jax, no model): `BENCHMARK.json` passes the
harness's lint, and every cell resolves its configuration (held to its
catalog row where it has one), its traffic mix and its per-layer files,
and reports what the contract asks of a cell. The cell test is
`benchmark/tests/test_spec.py`'s, parametrised over the cells, so that a
configuration a later PR adds is guarded the day it lands."""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _harness():
    """`benchmark/harness` as the top-level package the benchmark's own
    code imports it as, WITHOUT `benchmark/` on `sys.path` (see
    tests/test_cohere2_moe.py)."""
    if "harness" not in sys.modules:
        where = os.path.join(ROOT, "benchmark", "harness")
        spec = importlib.util.spec_from_file_location(
            "harness", os.path.join(where, "__init__.py"),
            submodule_search_locations=[where])
        sys.modules["harness"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules["harness"])
    from harness import spec
    return spec


def _raw():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_passes_the_contracts_lint():
    spec, raw = _harness(), _raw()
    spec.lint(raw)
    assert set(raw) == spec.BENCH_KEYS
    assert raw["paths"] == ["benchmark"]
    four = [w for w in raw["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(raw["workloads"]) // 4)
    assert len(raw["configs"]) <= 24 and len(raw["workloads"]) <= 24
    assert len(raw["per_layer"]) <= 128
    assert len(json.dumps(raw)) < 64 * 1024
    # every configuration is some cell's, under a file of its own
    assert {c["name"] for c in raw["configs"]} \
        == {w["config"] for w in raw["workloads"]}
    files = [c["file"] for c in raw["configs"]]
    assert len(set(files)) == len(files)
    # a metric's cells exist, and report the end-to-end metric it moves
    cells = {w["name"] for w in raw["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in raw["end_to_end"]}
    for m in raw["end_to_end"] + raw["per_layer"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]
    for m in raw["per_layer"]:
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]], m["name"]


@pytest.mark.parametrize("name", [w["name"] for w in _raw()["workloads"]])
def test_every_cell_resolves_and_reports_what_the_contract_asks(name):
    """Data only: what guards the files a `model_config` PR adds."""
    cell = _harness().load_benchmark(ROOT).cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, name
    for m in cell.per_layer:
        assert m["moves"] in e2e, (name, m["name"])
        assert cell.traffic["kind"] in m["file"]["kinds"]
    # a configuration drawn from the catalog carries its row, and is
    # held to it by `load_config` on the way here
    source = os.path.join(ROOT, "benchmark", "configs", "sources",
                          cell.config_name + ".json")
    if os.path.exists(source):
        with open(source) as f:
            row = json.load(f)
        assert cell.config["source"] == row["source_url"]
        changed = {k for k, v in row["config"].items()
                   if cell.config[k] != v}
        assert changed == set(cell.config["reduced"])
