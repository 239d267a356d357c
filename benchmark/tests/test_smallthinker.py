"""The `smallthinker-21b-dn-1536` configuration: held to its catalog row,
its reference's stages and costs, rehearsed on the CPU, and its real-size
serving round program compiled for one described v5e chip (no chip
attached; a compile, not a run).

  python -m pytest benchmark/tests/test_smallthinker.py -q -s
  python -m benchmark.tests.test_smallthinker 1      # the compile alone
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from . import round_program
from .conftest import BENCH, ROOT

NAME = "smallthinker-21b-dn-1536"
CELL = "smallthinker-21b.generate-fewer-1536"
HBM = round_program.HBM
TOKENS = 1 + 77 + 96 * 96
WINDOWED = 4096 * 4097 // 2 + (TOKENS - 4096) * 4096      # pairs a layer
CAUSAL = TOKENS * (TOKENS + 1) // 2


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
        "num_hidden_layers", "rope_layout", "sliding_window_layout"}
    # two whole periods from the first layer: full, 3 windowed, twice
    assert cfg["num_hidden_layers"] == 8
    assert cfg["rope_layout"] == row["config"]["rope_layout"][:8] \
        == cfg["sliding_window_layout"] == [0, 1, 1, 1, 0, 1, 1, 1]
    # no width is cut and every expert is held
    assert cfg["moe_num_primary_experts"] == cfg["model"][
        "router_experts"] == row["config"]["moe_num_primary_experts"] == 64
    assert cfg["moe_num_active_primary_experts"] == 6
    assert cfg["model"]["first_expert"] == 0
    assert cfg["serve"] == {"hold_ema": False}
    for key in ("router_input", "router_precision", "rope_pairing",
                "dense_width", "limits"):
        assert key in cfg["assumed"], key
    # every key the model leaves unread is neither a width nor reduced
    # nor the program's own; the four are said in every run
    eff = models.effective_config(cfg, False)
    from flaxdiff_tpu.inference.registry import MODEL_REGISTRY
    fields = MODEL_REGISTRY[cfg["registry_name"]].__dataclass_fields__
    assert models.unread_keys(eff, fields) == [
        "max_position_embeddings", "model_name", "tie_word_embeddings",
        "vocab_size"]
    cell = spec.load_benchmark(ROOT).cell(CELL)
    assert cell.chips == 1 and cell.traffic["nfe_deal"] == {
        "2": 6, "3": 3, "4": 1}
    assert cell.traffic["guidance_scale"] == 3.0
    assert cell.traffic["check_requests"] == cell.traffic["trace_rounds"] == 4
    # few requests a window: judged in row-turns, kept off gen_img_per_s
    assert {m["name"] for m in cell.end_to_end} == {
        "gen_row_turns_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"swa.read_pair_share", "kernel.flash_window_share_pct.gen",
            "kernel.flash_window_roofline_pct.gen", "serve.mfu_pct.turns",
            "sampler.step_device_ms.turns", "serve.rows_per_round.turns",
            "kernel.flash_fwd_roofline_pct.turns",
            "kernel.moe_gmm_roofline_pct.turns",
            "moe.hottest_expert_share.turns"} <= names
    assert all(m["moves"] == "gen_row_turns_per_s" for m in cell.per_layer)
    # a split metric reads what the metric it was split from reads
    for m in cell.per_layer:
        if not m["name"].endswith(".turns"):
            continue
        stem = m["name"][:-len(".turns")]
        for old in (stem, stem + ".gen"):
            path = os.path.join(BENCH, "layer_metrics", old + ".json")
            if os.path.exists(path):
                with open(path) as f:
                    was = json.load(f)
                assert was["read"] == m["file"]["read"], m["name"]
                assert (was["layer"], was["unit"], was["better"]) == (
                    m["layer"], m["unit"], m["better"])
                break
        else:
            raise AssertionError(f"{m['name']}: split from no metric")


def test_reference_stages_fold_to_its_forward_and_share_one_layer():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import models, spec, weights
    from reference import smallthinker as ref
    cfg = models.effective_config(
        spec.load_benchmark(ROOT).cell(CELL).config, True)
    _, apply_fn, init_fn, _ = models.build(cfg)
    params = jax.jit(init_fn)(jax.random.PRNGKey(3))
    res, ch = cfg["input"]["resolution"], cfg["input"]["channels"]
    tok, feat = cfg["conditioning"]["tokens"], cfg["conditioning"]["features"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, res, res, ch))
    t = jnp.asarray([30.0, 800.0])
    text = weights.request_context(5, 0, tok, feat).repeat(2, axis=0)
    stages = ref.stages(cfg["model"], x.shape)
    assert [n for n, _, _ in stages] == ["embed"] + [
        f"layer_{i}" for i in range(8)] + ["head"]
    # the eight layers share ONE apply: the layout bits are data
    assert len({apply for n, _, apply in stages
                if n.startswith("layer")}) == 1
    assert set(params) == {n for _, needs, _ in stages for n in needs}
    with jax.default_matmul_precision("highest"):
        carry = {"x": x, "t": t, "text": text}
        for _, needs, apply in stages:
            carry = jax.jit(apply)(tuple(params[n] for n in needs), carry)
        want = ref.forward(params, cfg["model"], x, t, text)
        got = jax.jit(apply_fn)(params, x, t, {"text": text})
    np.testing.assert_allclose(carry, want, atol=1e-5, rtol=1e-5)
    # the window binds in the rehearsal too
    assert cfg["model"]["sliding_window_size"] < 1 + tok + (res // 2) ** 2
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_required_operations_and_kernel_costs_are_the_closed_forms():
    from harness import flops, models, spec
    cfg = models.effective_config(
        spec.load_benchmark(ROOT).cell(CELL).config, False)
    assert TOKENS == 9294 and CAUSAL == 43_193_865
    assert WINDOWED == 29_681_664
    assert WINDOWED / CAUSAL == pytest.approx(0.687, abs=5e-4)
    assert (6 * WINDOWED + 2 * CAUSAL) / (8 * CAUSAL) == pytest.approx(
        0.765, abs=5e-4)
    total = flops.forward_flops(cfg)
    assert total / 1e9 == pytest.approx(
        cfg["required_gflop_per_image_fwd"], rel=0.0005)
    # by hand: parameters a token reads in a layer, times two
    proj = 2560 * 128 * (28 + 4 + 4 + 28)
    expert = 3 * 2560 * 768
    per_token = 2 * 8 * (proj + 2560 * 64 + 6 * expert)
    assert per_token / 1e6 == pytest.approx(904.4, rel=0.001)
    scores = 4 * (6 * WINDOWED + 2 * CAUSAL) * 128 * 28
    assert total == pytest.approx(TOKENS * per_token + scores, rel=0.001)
    costs = flops.kernel_costs(cfg)
    assert costs["fdt_flash_fwd"]["flops"] == scores
    assert costs["fdt_flash_fwd_window"]["flops"] \
        == 4 * 6 * WINDOWED * 128 * 28
    a_layer = 2 * TOKENS * 128 * (28 + 4) * 2
    assert costs["fdt_flash_fwd"]["bytes"] == 8 * a_layer
    assert costs["fdt_flash_fwd_window"]["bytes"] == 6 * a_layer
    picks = TOKENS * 6
    assert costs["fdt_moe_gmm"]["flops"] == 8 * picks * 2 * expert
    assert costs["fdt_moe_gmm"]["bytes"] == 8 * 2 * (
        picks * 2 * (2560 + 768) + 64 * expert / 2)


@pytest.mark.parametrize("traced", [0, 1])
def test_the_cell_rehearses_on_the_cpu_with_correct_true(traced):
    # six seconds: `gen_row_turns_per_s` needs three completions, and a
    # window of three held two beside other busy workers
    from harness import spec
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2147486001 + traced), "--seconds", "6", "--trace",
         str(traced), "--rehearse"], capture_output=True, text=True,
        timeout=1500, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    cell = spec.load_benchmark(ROOT).cell(CELL)
    if not traced:
        assert set(line["metrics"]) == {"gen_row_turns_per_s", "setup_s"}
        return
    # every listed metric that needs no device plane prints a number
    host = {m["name"] for m in cell.per_layer
            if m["source"] != "device_trace"
            and m["file"]["read"]["from"] != "memory_stats"}
    assert host <= set(line["metrics"])
    # rows evaluated one at a time are served in rounds of ONE row
    assert line["metrics"]["serve.rows_per_round.turns"]["value"] == 1.0
    # 150 tokens under a window of 96 on six of eight layers
    assert line["metrics"]["swa.read_pair_share"]["value"] == pytest.approx(
        (6 * 9840 + 2 * 11325) / (8 * 11325), abs=1e-6)
    # every expert is held: the hottest of 8 takes an eighth or more
    assert 0.125 <= line["metrics"]["moe.hottest_expert_share.turns"][
        "value"] < 1


@pytest.fixture(scope="module")
def topo():
    return round_program.describe_v5e()


@pytest.mark.slow
def test_the_round_program_fits_a_v5e_chip(topo):
    compiled, mem = round_program.compile_round_program(topo, CELL, 1)
    print(NAME, mem)
    assert mem["tally"] == ["fitted", "picks"]
    assert mem["parameters"] == pytest.approx(3.198e9, rel=0.01)
    assert mem["argument"] > 6.3e9           # the bfloat16 tree: 40% of HBM
    assert mem["total"] < HBM
    text = compiled.as_text()
    for kernel in ("fdt_flash_fwd", "fdt_flash_fwd_window",
                   "fdt_moe_gmm_gate_up", "fdt_moe_gmm_down",
                   "fdt_moe_combine"):
        assert kernel in text, kernel


if __name__ == "__main__":
    round_program.main(CELL, *sys.argv[1:3])
