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

from .conftest import BENCH, ROOT

NAME = "command-a-plus-dn-256"
CELL = "command-a-plus.generate-few"
HBM = 15.75e9        # what the v5e compiler allows a program


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
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def compile_round_program(topo, bucket: int = 8, round_steps: int = 8):
    """The serving round program of the configuration at its real size
    (`bucket` guided rows, the model's Pallas kernels on), compiled for
    one described v5e chip. Returns (compiled, bytes dict)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from flaxdiff_tpu.inference import DiffusionInferencePipeline
    from flaxdiff_tpu.ops import attention as att, moe
    from flaxdiff_tpu.serving.engine import _round_program
    from harness import models, spec

    cfg = models.effective_config(
        spec.load_benchmark(ROOT).cell(CELL).config, False)
    # the program picks its kernels by asking jax for its first device;
    # here that is the CPU, so the test steers it to the TPU path
    att._flash_on_tpu = lambda: True
    moe._on_tpu = lambda: True
    _, _, _, shapes = models.build(cfg)
    pipe = DiffusionInferencePipeline.from_config(
        {"model": dict(cfg["model"], name=cfg["registry_name"]),
         "schedule": dict(cfg["schedule"]), "predictor": cfg["predictor"]},
        params=None)
    ds = pipe.get_sampler("ddim", 3.0)
    one = SingleDeviceSharding(topo.devices[0])

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    res, ch = cfg["input"]["resolution"], cfg["input"]["channels"]
    tok, feat = cfg["conditioning"]["tokens"], cfg["conditioning"]["features"]
    row = {"x": on((1, res, res, ch), jnp.float32),
           "keys": on((2,), jnp.uint32), "state": (),
           "cond": on((1, tok, feat), jnp.float32),
           "uncond": on((1, tok, feat), jnp.float32),
           "tally": on(ds.tally_shape, jnp.int32)}
    batch = {"pairs": on((bucket, round_steps, 2), jnp.float32),
             "n_act": on((bucket,), jnp.int32),
             "offsets": on((bucket,), jnp.int32),
             "steps": on((), jnp.int32)}
    params = {"params": jax.tree_util.tree_map(
        lambda s: on(s.shape, s.dtype), shapes)}
    compiled = _round_program(ds.make_chunk_program(round_steps)).lower(
        params, (row,) * bucket, batch).compile()
    ma = compiled.memory_analysis()
    return compiled, {
        "argument": ma.argument_size_in_bytes,
        "output": ma.output_size_in_bytes, "temp": ma.temp_size_in_bytes,
        "alias": ma.alias_size_in_bytes,
        "total": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
        "parameters": models.count_params(shapes)}


@pytest.mark.slow
def test_the_round_program_fits_a_v5e_chip(topo):
    compiled, mem = compile_round_program(topo)
    print(NAME, mem)
    assert mem["parameters"] == pytest.approx(4.60e9, rel=0.01)
    assert mem["argument"] > 9.1e9           # the bfloat16 tree: 57% of HBM
    assert mem["total"] < HBM
    text = compiled.as_text()
    for kernel in ("fdt_flash_fwd", "fdt_moe_gmm_gate_up",
                   "fdt_moe_gmm_down"):
        assert kernel in text, kernel


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import time

    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    sys.path[:0] = [ROOT, BENCH]
    t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    t0 = time.time()
    c, mem = compile_round_program(
        t, *(int(a) for a in sys.argv[1:3]))
    txt = c.as_text()
    print("RESULT", NAME, mem, "mosaic_calls", txt.count("tpu_custom_call"),
          f"{time.time() - t0:.0f}s")
