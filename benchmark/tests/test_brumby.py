"""The `brumby-14b-dn-384` configuration: held to its catalog row,
rehearsed on the CPU, and its real-size serving round program compiled
for one described v5e chip (no chip attached; a compile, not a run).

  python -m pytest benchmark/tests/test_brumby.py -q -s
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from . import round_program
from .conftest import BENCH, ROOT

NAME = "brumby-14b-dn-384"
CELL = "brumby-14b.generate-fewer"
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
        "num_hidden_layers"}
    assert cfg["published"] == {"num_hidden_layers": 40}
    assert cfg["num_hidden_layers"] == 4 and cfg["max_window_layers"] == 40
    # EVERY source key is a field of the model: nothing goes unread
    eff = models.effective_config(cfg, False)
    from flaxdiff_tpu.inference.registry import MODEL_REGISTRY
    fields = MODEL_REGISTRY[cfg["registry_name"]].__dataclass_fields__
    assert set(row["config"]) <= set(fields) and len(row["config"]) == 18
    assert models.unread_keys(eff, fields) == []
    cell = spec.load_benchmark(ROOT).cell(CELL)
    assert cell.chips == 1 and cell.traffic["nfe_deal"] == {
        "2": 6, "3": 3, "4": 1}
    assert cell.traffic["guidance_scale"] == 3.0
    names = {m["name"] for m in cell.per_layer}
    assert {"serve.mfu_pct", "sampler.step_device_ms"} <= names
    # the retention is XLA's composition (the A/B in the round program,
    # PERF.md PR 38): no Mosaic kernel on its path, so none is read in
    # the cell, not as a class either (0.0 / 0.0 in the ledger: PR 45)
    assert not {"kernel.mosaic_share_pct.gen", "kernel.flash_share_pct.gen",
                "kernel.adaln_share_pct.gen", "moe.held_pick_share"} & names
    assert not [n for n in names
                if n.startswith(("kernel.power_", "retention."))]


def test_reference_stages_fold_to_its_forward_and_share_one_layer():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import models, spec, weights
    from reference import brumby as ref
    cfg = models.effective_config(
        spec.load_benchmark(ROOT).cell(CELL).config, True)
    _, _, init_fn, _ = models.build(cfg)
    params = jax.jit(init_fn)(jax.random.PRNGKey(3))
    res, ch = cfg["input"]["resolution"], cfg["input"]["channels"]
    tok, feat = cfg["conditioning"]["tokens"], cfg["conditioning"]["features"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, res, res, ch))
    t = jnp.asarray([30.0, 800.0])
    text = weights.request_context(5, 0, tok, feat).repeat(2, axis=0)
    stages = ref.stages(cfg["model"], x.shape)
    assert [n for n, _, _ in stages] == [
        "embed", "layer_0", "layer_1", "layer_2", "layer_3", "head"]
    assert len({apply for n, _, apply in stages
                if n.startswith("layer")}) == 1
    assert set(params) == {n for _, needs, _ in stages for n in needs}
    with jax.default_matmul_precision("highest"):
        carry = {"x": x, "t": t, "text": text}
        for _, needs, apply in stages:
            carry = jax.jit(apply)(tuple(params[n] for n in needs), carry)
        want = ref.forward(params, cfg["model"], x, t, text)
    np.testing.assert_allclose(carry, want, atol=1e-5, rtol=1e-5)
    # the queries in blocks give what they give whole
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 37, h, 8))
               for i, h in ((1, 4), (2, 2), (3, 2)))
    log_g = jax.nn.log_sigmoid(
        jax.random.normal(jax.random.PRNGKey(4), (1, 37, 2)) + 2.0)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ref.retention(q, k, v, log_g, block=8),
                                   ref.retention(q, k, v, log_g),
                                   atol=1e-6, rtol=1e-5)


def test_required_operations_at_the_published_widths():
    from harness import flops, models, spec
    from reference import brumby as ref
    cfg = models.effective_config(
        spec.load_benchmark(ROOT).cell(CELL).config, False)
    gflop = flops.forward_flops(cfg) / 1e9
    assert gflop == pytest.approx(cfg["required_gflop_per_image_fwd"],
                                  rel=0.005)
    # by hand: a layer is 26.21 + 2 x 5.24 + 26.21 + 0.04 + 3 x 89.13 =
    # 330.3 M parameters, twice that a token; the retention's causal half
    # 40 heads x 654 x 655 / 2 pairs x 512; 0.8 GFLOP of embedding and head
    pairs = 654 * 655 / 2
    layer = 2 * 654 * 330.34e6 + 40 * pairs * 512
    assert gflop == pytest.approx((4 * layer + 0.8e9) / 1e9, rel=0.002)
    # the two forms cross near 10k tokens: the cell's rows are counted
    # pairwise, a 16k-token row at the state form
    m = cfg["model"]
    state = (40 + 8) * 2 * 8256 * 128
    assert ref.retention_flops(m, 654) == pytest.approx(40 * pairs * 512)
    assert ref.retention_flops(m, 16462) == pytest.approx(16462 * state)
    assert 9000 < state / 10240 < 11000


def test_the_cell_rehearses_on_the_cpu_with_correct_true():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147486001", "--seconds", "3", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1500,
        cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["serve.rows_per_round"]["value"] > 1
    assert "does not read" not in out.stdout


@pytest.fixture(scope="module")
def topo():
    return round_program.describe_v5e()


@pytest.mark.slow
def test_the_round_program_fits_a_v5e_chip(topo):
    compiled, mem = round_program.compile_round_program(topo, CELL)
    print(NAME, mem)
    assert mem["tally"] == []           # the model counts nothing itself
    assert mem["parameters"] == pytest.approx(1.353e9, rel=0.01)
    assert mem["argument"] > 2.7e9           # the bfloat16 tree
    assert 0.125 * 16e9 < mem["total"] < HBM
    # no Mosaic kernel on this path: the retention is XLA's composition
    assert "tpu_custom_call" not in compiled.as_text()


if __name__ == "__main__":
    round_program.main(CELL, *sys.argv[1:3])
