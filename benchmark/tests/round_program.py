"""A cell's serving round program at its real size, compiled for one
described v5e chip (no chip attached: a compile, not a run). What the
configurations' own test files share; the next configuration's file
calls it with its cell's name.

  python -m benchmark.tests.round_program <cell> [bucket [round_steps]]
"""
from __future__ import annotations

import os
import sys

from .conftest import BENCH, ROOT

HBM = 15.75e9        # what the v5e compiler allows a program


def _describe():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def describe_v5e():
    """The described topology, or a skip. Call it from a fixture of the
    test file, never while a module is imported: one process at a time
    may load the TPU's library."""
    import pytest
    try:
        return _describe()
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def compile_round_program(topo, cell: str, bucket: int = 8,
                          round_steps: int = 8):
    """`jit_sampler_chunk` of the cell's configuration, `bucket` guided
    rows, the model's Pallas kernels on. A row's operands are what
    `SamplerProgramEngine.advance` hands the program: its carry, the
    tally the model asks for (a named set, `ds.tally_shape`, or none)
    and the round's `pairs`, `n_act`, `offsets`, `steps` and `term`.
    Returns (compiled, bytes dict)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from flaxdiff_tpu.inference import DiffusionInferencePipeline
    from flaxdiff_tpu.ops import attention as att, moe
    from flaxdiff_tpu.serving.engine import _round_program
    from harness import models, spec

    cfg = models.effective_config(
        spec.load_benchmark(ROOT).cell(cell).config, False)
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
           "uncond": on((1, tok, feat), jnp.float32)}
    if ds.tally_shape is not None:
        row["tally"] = {name: on(shape, jnp.int32)
                        for name, shape in ds.tally_shape.items()}
    batch = {"pairs": on((bucket, round_steps, 2), jnp.float32),
             "n_act": on((bucket,), jnp.int32),
             "offsets": on((bucket,), jnp.int32),
             "steps": on((), jnp.int32),
             "term": on((bucket,), jnp.int32)}
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
        "parameters": models.count_params(shapes),
        "tally": sorted(ds.tally_shape or ())}


def main(cell: str, *sizes: str) -> None:
    import time
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path[:0] = [ROOT, BENCH]
    t0 = time.time()
    c, mem = compile_round_program(_describe(), cell,
                                   *(int(a) for a in sizes[:2]))
    print("RESULT", cell, mem, "mosaic_calls",
          c.as_text().count("tpu_custom_call"), f"{time.time() - t0:.0f}s")


if __name__ == "__main__":
    main(*sys.argv[1:])
