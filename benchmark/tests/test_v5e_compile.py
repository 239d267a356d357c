"""The guide's third rehearsal, kept as a test: each cell's train step
compiled at its real size for a described v5e 2x2 (no chip attached).
`memory_analysis()` per chip is what decides batch and remat off the
chip. Slow (minutes per case); run by hand:

  python -m pytest benchmark/tests/test_v5e_compile.py -q -s
  python benchmark/tests/test_v5e_compile.py <config> <chips> <batch_per_chip> [remat]
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def compile_train_step(topo, config_name: str, chips: int,
                       batch_per_chip: int, remat: bool = False):
    """Compile the program's own train step for `chips` described v5e
    devices; returns (compiled, per-device bytes dict)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flaxdiff_tpu.parallel import fsdp_sharding_tree, sharding_tree
    from flaxdiff_tpu.parallel.context import use_mesh
    from flaxdiff_tpu.parallel.mesh import batch_spec
    from flaxdiff_tpu.predictors import TRANSFORM_REGISTRY
    from flaxdiff_tpu.schedulers import get_schedule
    from flaxdiff_tpu.trainer.train_state import TrainState
    from flaxdiff_tpu.trainer.train_step import (TrainStepConfig,
                                                 make_train_step)
    from harness import models, spec

    cfg = models.effective_config(spec.load_config(
        os.path.join(BENCH, "configs", config_name + ".json")), False)
    if remat:
        cfg["model"]["remat"] = True
    # the program picks its kernels by asking jax for its first device;
    # here that is the CPU, so the test steers it to the TPU path
    from flaxdiff_tpu.ops import attention as att, fused_adaln as fa, \
        fused_norm as fnorm
    fa._on_tpu = lambda: True
    att._flash_on_tpu = lambda: True
    fnorm._use_pallas = lambda interpret, force_pallas: (True, False)
    tc = cfg["train"]
    _, apply_fn, init_fn, _ = models.build(cfg)
    tx = optax.adamw(tc["learning_rate"], b1=tc["b1"], b2=tc["b2"],
                     eps=tc["eps"], weight_decay=tc["weight_decay"])
    tok, feat = cfg["conditioning"]["tokens"], cfg["conditioning"]["features"]
    res, ch = cfg["input"]["resolution"], cfg["input"]["channels"]
    sched = dict(cfg["schedule"])
    step_fn = make_train_step(
        apply_fn, get_schedule(sched.pop("name"), **sched),
        TRANSFORM_REGISTRY[cfg["predictor"]](),
        TrainStepConfig(uncond_prob=tc["uncond_prob"],
                        ema_decay=tc["ema_decay"], normalize=tc["normalize"],
                        weighted_loss=tc["weighted_loss"]),
        null_cond={"text": np.zeros((1, tok, feat), np.float32)},
        gate_nonfinite=True)

    def create_state(key):
        k1, k2 = jax.random.split(key)
        return TrainState.create(apply_fn=apply_fn, params=init_fn(k1),
                                 tx=tx, rng=k2, ema_decay=tc["ema_decay"])

    devs = np.asarray(topo.devices[:chips]).reshape(1, chips)
    mesh = Mesh(devs, ("data", "fsdp"))
    shapes = jax.eval_shape(create_state, jax.random.PRNGKey(0))
    shard = sharding_tree(fsdp_sharding_tree(shapes, mesh), mesh)
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shard)
    bsh = NamedSharding(mesh, batch_spec(mesh))
    gb = batch_per_chip * chips
    batch = {"sample": jax.ShapeDtypeStruct((gb, res, res, ch), jnp.float32,
                                            sharding=bsh),
             "cond": {"text": jax.ShapeDtypeStruct((gb, tok, feat),
                                                   jnp.float32, sharding=bsh)}}
    with use_mesh(mesh):
        compiled = jax.jit(
            step_fn, donate_argnums=(0,),
            out_shardings=(shard, NamedSharding(mesh, P()))
        ).lower(state, batch).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    return compiled, {"argument": ma.argument_size_in_bytes,
                      "output": ma.output_size_in_bytes,
                      "temp": ma.temp_size_in_bytes,
                      "alias": ma.alias_size_in_bytes, "total": total}


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


CASES = [("unet-flaxdiff-128", 1), ("dit-xl-2-256", 4)]


@pytest.mark.slow
@pytest.mark.parametrize("config_name,chips", CASES)
def test_train_step_fits_a_v5e_chip(topo, config_name, chips):
    from harness import models, spec
    cfg = models.effective_config(spec.load_config(
        os.path.join(BENCH, "configs", config_name + ".json")), False)
    tc = cfg["train"]
    compiled, mem = compile_train_step(topo, config_name, chips,
                                       tc["batch_per_chip"],
                                       bool(tc.get("remat")))
    print(config_name, chips, mem)
    assert mem["total"] < 15.7e9
    assert "tpu_custom_call" in compiled.as_text()


def stand_in_shapes(layers: int = 4, experts: int = 16):
    """Shapes only, bfloat16: the serving cut ISSUE 34 sizes the harness
    for. Hidden 4096, 128 query and 8 key-value heads of 128, 4 shared
    and `experts` routed experts of width 4096 with three matrices each,
    a router over 128: 1,149.7 M parameters a layer, 4.60 B in four."""
    import jax
    import jax.numpy as jnp

    def k(*shape):
        return {"kernel": jax.ShapeDtypeStruct(shape, jnp.bfloat16)}

    def mlp(n):
        return {"gate": k(n, 4096, 4096), "up": k(n, 4096, 4096),
                "down": k(n, 4096, 4096)}

    return {f"layer_{i}": {
        "attn": {"q": k(4096, 16384), "k": k(4096, 1024),
                 "v": k(4096, 1024), "out": k(16384, 4096)},
        "router": k(4096, 128), "shared_experts": mlp(4),
        "experts": mlp(experts)} for i in range(layers)}


HBM = 15.75e9        # what the v5e compiler allows a program


def test_a_4_6_b_parameter_tree_is_made_a_stage_at_a_time(topo):
    """The stage-wise init of a bfloat16 tree that fills most of a chip,
    and one float32 stage of it for the reference, compiled for one v5e
    chip: set-up peaks at the finished tree plus one stage's program,
    the reference at one float32 stage plus its carries. A compile, not
    a run."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from harness import models, weights

    shapes = stand_in_shapes()
    assert models.count_params(shapes) == pytest.approx(4.6e9, rel=0.01)
    maker = weights.Maker(shapes)
    one = SingleDeviceSharding(topo.devices[0])
    total = {}
    for widen in (False, True):
        fn, folds = maker.program("layer_0", widen)
        assert fn is maker.program("layer_3", widen)[0]     # one program
        ma = fn.lower(
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one),
            jax.ShapeDtypeStruct(folds.shape, folds.dtype, sharding=one)
        ).compile().memory_analysis()
        total[widen] = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print("stage program", "float32" if widen else "bfloat16",
              {"output": ma.output_size_in_bytes,
               "temp": ma.temp_size_in_bytes, "total": total[widen]})
    stage = maker.nbytes(["layer_0"])
    assert stage == pytest.approx(2.3e9, rel=0.01)
    assert maker.nbytes(["layer_0"], widen=True) == 2 * stage
    # set-up: three stages made, the fourth's program running
    assert 3 * stage + total[False] < HBM
    # the reference: the program's tree freed; one stage in float32 and
    # the carries of 8 guided requests of 4,096 tokens (2 x 4096 x 4096
    # float32 each, a few times over for a block's intermediates)
    carries = 8 * 6 * 2 * 4096 * 4096 * 4
    assert total[True] + carries < HBM
    assert np.dtype(np.float32).itemsize * models.count_params(
        shapes) > HBM      # the whole tree in float32 would not fit


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    name, chips, bpc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    import time
    t0 = time.time()
    try:
        c, mem = compile_train_step(t, name, chips, bpc, len(sys.argv) > 4)
        txt = c.as_text()
        print("RESULT", name, chips, bpc, sys.argv[4:], mem,
              "mosaic_calls", txt.count("tpu_custom_call"),
              "all-gather", txt.count("all-gather("),
              "reduce-scatter", txt.count("reduce-scatter("),
              "all-reduce", txt.count("all-reduce("),
              f"{time.time() - t0:.0f}s")
    except Exception as e:  # noqa: BLE001
        print("RESULT", name, chips, bpc, sys.argv[4:], "FAILED",
              type(e).__name__, str(e)[:600], f"{time.time() - t0:.0f}s")
