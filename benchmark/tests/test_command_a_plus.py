"""The `command-a-plus-dn-256` configuration: held to its catalog row,
rehearsed on the CPU, and its real-size serving round program compiled
for one described v5e chip (no chip attached; a compile, not a run).

  python -m pytest benchmark/tests/test_command_a_plus.py -q -s
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from . import round_program
from .conftest import BENCH, ROOT

NAME = "command-a-plus-dn-256"
CELL = "command-a-plus.generate-few"
HBM = round_program.HBM


def _entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = json.load(f)
    return raw, {c["name"]: c for c in raw["configs"]}[NAME]


def test_the_configuration_loads_and_is_held_to_its_source():
    from harness import models, spec
    raw, entry = _entry()
    cfg = spec.load_config(os.path.join(ROOT, entry["file"]), entry=entry)
    with open(os.path.join(BENCH, "configs", "sources", NAME + ".json")) as f:
        row = json.load(f)
    assert cfg["source"] == entry["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_experts"}
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    assert cfg["layer_types"] == row["config"]["layer_types"][:4]
    assert (cfg["num_hidden_layers"], cfg["num_experts"]) == (4, 16)
    # every width reaches the model under its own name; what goes unread
    # is no width and no reduced key
    eff = models.effective_config(cfg, False)
    from flaxdiff_tpu.inference.registry import MODEL_REGISTRY
    unread = models.unread_keys(
        eff, MODEL_REGISTRY[cfg["registry_name"]].__dataclass_fields__)
    assert not [k for k in unread if spec.WIDTH_RE.search(k)
                or k in cfg["reduced"]]
    cell = spec.load_benchmark(ROOT).cell(CELL)
    assert cell.chips == 1 and cell.traffic["nfe_deal"] == {
        "4": 6, "6": 3, "8": 1}
    names = {m["name"] for m in cell.per_layer}
    assert {"kernel.moe_gmm_share_pct.gen", "kernel.moe_gmm_roofline_pct.gen",
            "moe.held_pick_share", "moe.hottest_expert_share",
            "serve.mfu_pct", "kernel.flash_fwd_roofline_pct.gen"} <= names
    assert "kernel.adaln_share_pct.gen" not in names


def test_required_operations_and_kernel_costs_at_the_published_widths():
    from harness import flops, models, spec
    cfg = models.effective_config(
        spec.load_benchmark(ROOT).cell(CELL).config, False)
    gflop = flops.forward_flops(cfg) / 1e9
    assert gflop == pytest.approx(cfg["required_gflop_per_image_fwd"],
                                  rel=0.005)
    # 4 layers x 334 tokens x 2 x 394.8 M active parameters, and the
    # causal half of the scores
    assert gflop == pytest.approx(4 * 334 * 2 * 0.3948 + 14.7, rel=0.01)
    costs = flops.kernel_costs(cfg)
    # a held pick is 2 x 3 x 4096 x 4096 operations; 334 land here a layer
    assert costs["fdt_moe_gmm"]["flops"] == pytest.approx(
        4 * 334 * 6 * 4096 * 4096)
    # an expert's weights once a call of 16 evaluations
    assert costs["fdt_moe_gmm"]["bytes"] > 4 * 16 * 3 * 4096 * 4096 * 2 / 16
    assert costs["fdt_flash_fwd"]["flops"] == pytest.approx(
        4 * 4 * (334 * 335 / 2) * 128 * 128)


def test_the_cell_rehearses_on_the_cpu_with_correct_true():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147486001", "--seconds", "3", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1500,
        cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    # 4 of 16 experts are held in the rehearsal: a quarter of the picks
    assert 0.1 < m["moe.held_pick_share"]["value"] < 0.45
    assert 0.25 <= m["moe.hottest_expert_share"]["value"] < 0.7
    assert "source key(s) the model 'cohere2_moe_dn' does not read" \
        in out.stdout
    assert "vocab_size" in out.stdout


@pytest.fixture(scope="module")
def topo():
    return round_program.describe_v5e()


@pytest.mark.slow
def test_the_round_program_fits_a_v5e_chip(topo):
    compiled, mem = round_program.compile_round_program(topo, CELL)
    print(NAME, mem)
    assert "picks" in mem["tally"]      # the routers' picks ride the row
    assert mem["parameters"] == pytest.approx(4.60e9, rel=0.01)
    assert mem["argument"] > 9.1e9           # the bfloat16 tree: 57% of HBM
    assert mem["total"] < HBM
    text = compiled.as_text()
    for kernel in ("fdt_flash_fwd", "fdt_moe_gmm_gate_up",
                   "fdt_moe_gmm_down"):
        assert kernel in text, kernel


if __name__ == "__main__":
    round_program.main(CELL, *sys.argv[1:3])
