"""The `glm-5.2-dn-1024` configuration: held to its catalog row, its
reference's stages and costs, rehearsed on the CPU, and its real-size
serving round program compiled for one described v5e chip (no chip
attached; a compile, not a run).

  python -m pytest benchmark/tests/test_glm_moe_dsa.py -q -s
  python -m benchmark.tests.test_glm_moe_dsa 8 8      # the compile alone
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from . import round_program
from .conftest import BENCH, ROOT

NAME = "glm-5.2-dn-1024"
CELL = "glm-5.2.generate-fewer-1024"
HBM = round_program.HBM
TOKENS = 1 + 77 + 64 * 64


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
        "num_hidden_layers", "mlp_layer_types", "indexer_types",
        "n_routed_experts"}
    # the published entries 2 to 6: the last leading dense layer, one
    # whole IndexShare period, four sparse layers
    assert cfg["mlp_layer_types"] == row["config"]["mlp_layer_types"][2:7] \
        == ["dense"] + ["sparse"] * 4
    assert cfg["indexer_types"] == row["config"]["indexer_types"][2:7] \
        == ["full", "shared", "shared", "shared", "full"]
    assert cfg["model"]["first_layer"] == 2 and cfg["num_hidden_layers"] == 5
    assert cfg["n_routed_experts"] == 16 and cfg["index_topk"] == 2048
    assert cfg["model"]["router_experts"] == row["config"][
        "n_routed_experts"] == 256
    # every source key but the vocabulary's and the MTP module's is a
    # field of the model; the two that go unread are said in every run
    eff = models.effective_config(cfg, False)
    from flaxdiff_tpu.inference.registry import MODEL_REGISTRY
    fields = MODEL_REGISTRY[cfg["registry_name"]].__dataclass_fields__
    assert models.unread_keys(eff, fields) == [
        "num_nextn_predict_layers", "vocab_size"]
    # the rule behind the published list, at all 78 layers
    from flaxdiff_tpu.models.glm_moe_dsa import published_indexer_type
    assert [published_indexer_type(i, 3, 4) for i in range(78)] \
        == row["config"]["indexer_types"]
    cell = spec.load_benchmark(ROOT).cell(CELL)
    assert cell.chips == 1 and cell.traffic["nfe_deal"] == {
        "2": 6, "3": 3, "4": 1}
    assert cell.traffic["guidance_scale"] == 3.0
    assert cell.traffic["check_requests"] == cell.traffic["trace_rounds"] == 4
    # few requests a window: the throughput is also counted in row-turns
    assert {m["name"] for m in cell.end_to_end} == {
        "gen_img_per_s", "gen_row_turns_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"serve.mfu_pct", "sampler.step_device_ms",
            "dsa.selected_key_share", "moe.held_pick_share",
            "kernel.flash_fwd_roofline_pct.gen",
            "kernel.moe_gmm_roofline_pct.gen"} <= names


def test_reference_stages_fold_to_its_forward_and_share_their_layers():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import models, spec, weights
    from reference import glm_moe_dsa as ref
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
    assert [n for n, _, _ in stages] == [
        "embed", "layer_0", "layer_1", "layer_2", "layer_3", "layer_4",
        "head"]
    applies = [apply for n, _, apply in stages if n.startswith("layer")]
    # dense + full, sparse + shared three times over ONE apply, sparse + full
    assert applies[1] is applies[2] is applies[3]
    assert len(set(applies)) == 3
    assert set(params) == {n for _, needs, _ in stages for n in needs}
    with jax.default_matmul_precision("highest"):
        carry = {"x": x, "t": t, "text": text}
        for _, needs, apply in stages:
            carry = jax.jit(apply)(tuple(params[n] for n in needs), carry)
        want = ref.forward(params, cfg["model"], x, t, text)
        got = jax.jit(apply_fn)(params, x, t, {"text": text})
    np.testing.assert_allclose(carry, want, atol=1e-5, rtol=1e-5)
    # the selector binds in the rehearsal too (index_topk under its row)
    assert cfg["model"]["index_topk"] < 1 + tok + (res // 2) ** 2
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_required_operations_and_kernel_costs_are_the_closed_forms():
    from harness import flops, models, spec
    from reference import glm_moe_dsa as ref
    cfg = models.effective_config(
        spec.load_benchmark(ROOT).cell(CELL).config, False)
    assert ref.selected_pairs(TOKENS, 2048) == 6_452_224
    assert TOKENS * (TOKENS + 1) // 2 == 8_713_225
    assert 6_452_224 / 8_713_225 == pytest.approx(0.7405, abs=5e-5)
    total = flops.forward_flops(cfg)
    assert total / 1e9 == pytest.approx(
        cfg["required_gflop_per_image_fwd"], rel=0.0005)
    # by hand: parameters a token reads, times two
    attn, index = 165.02e6, 9.37e6
    dense_layer = attn + index + 3 * 6144 * 12288
    sparse_layer = attn + 6144 * 256 + 3 * 6144 * 2048 * (8 * 16 / 256 + 1)
    per_token = 2 * (dense_layer + 3 * sparse_layer + sparse_layer + index)
    assert per_token / 1e6 == pytest.approx(2606, rel=0.001)
    core = 4 * 6_452_224 * 256 * 64
    scores = 2 * 8_713_225 * 32 * 128
    assert total == pytest.approx(
        TOKENS * per_token + 5 * core + 2 * scores, rel=0.001)
    costs = flops.kernel_costs(cfg)
    assert costs["fdt_flash_fwd"]["flops"] == 5 * core
    assert costs["fdt_flash_fwd"]["bytes"] == 5 * (
        4 * TOKENS * 256 * 64 * 2 + 8_713_225)
    picks = TOKENS * 8 * 16 / 256
    assert costs["fdt_moe_gmm"]["flops"] == 4 * picks * 2 * 3 * 6144 * 2048
    assert costs["fdt_moe_gmm"]["bytes"] == 4 * 2 * (
        picks * 2 * (6144 + 2048) + 16 * 3 * 6144 * 2048 / 2)


def test_the_cell_rehearses_on_the_cpu_with_correct_true():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147486001", "--seconds", "3", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1500,
        cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    # rows evaluated one at a time are served in rounds of ONE row (PR 43)
    assert line["metrics"]["serve.rows_per_round"]["value"] == 1.0
    # 150 tokens, the 96 largest: (96 x 97 / 2 + 54 x 96) / (150 x 151 / 2)
    assert line["metrics"]["dsa.selected_key_share"]["value"] \
        == pytest.approx(9840 / 11325, abs=0.01)
    assert 0 < line["metrics"]["moe.held_pick_share"]["value"] < 1


@pytest.fixture(scope="module")
def topo():
    return round_program.describe_v5e()


@pytest.mark.slow
def test_the_round_program_fits_a_v5e_chip(topo):
    compiled, mem = round_program.compile_round_program(topo, CELL)
    print(NAME, mem)
    assert {"picks", "keys"} <= set(mem["tally"])
    assert mem["parameters"] == pytest.approx(3.688e9, rel=0.01)
    assert mem["argument"] > 7.4e9           # the bfloat16 tree: 46% of HBM
    assert mem["total"] < HBM
    text = compiled.as_text()
    for kernel in ("fdt_flash_fwd", "fdt_moe_gmm_gate_up",
                   "fdt_moe_gmm_down"):
        assert kernel in text, kernel
    # the spans of the new sub-blocks reach the HLO's op_name
    for scope in ("fdt_mla_proj", "fdt_dsa_index", "fdt_dsa_select",
                  "fdt_mla_core"):
        assert scope in text, scope


if __name__ == "__main__":
    round_program.main(CELL, *sys.argv[1:3])
