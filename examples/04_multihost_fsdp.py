#!/usr/bin/env python
"""FSDP-sharded training over an N-D device mesh (reference analogue: the
"multi-host data-parallel training" notebook — upgraded from replicated
data-parallel to real FSDP).

Builds a (data, fsdp) mesh, shards parameters/optimizer/EMA over the
`fsdp` axis via per-tensor PartitionSpecs (automatic inference), shards
the batch over `data`, and lets XLA SPMD insert the all-gathers /
reduce-scatters. The same code runs on a TPU pod (mesh axes follow the
real topology, `jax.distributed.initialize()` for multi-host) and on this
script's default: an 8-device virtual CPU mesh for local verification.

Run:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        python examples/04_multihost_fsdp.py
(the script sets these itself when it detects a single local device)
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=16, help="global batch")
    ap.add_argument("--image_size", type=int, default=16)
    ap.add_argument("--data_axis", type=int, default=2)
    ap.add_argument("--fsdp_axis", type=int, default=4)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    if args.smoke:
        args.steps = 12

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from flaxdiff_tpu.models.unet import Unet
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    # Mesh: data x fsdp over the local devices. On a real pod, axis sizes
    # follow the slice topology and DCN becomes the outermost axis.
    mesh = create_mesh(axes={"data": args.data_axis, "fsdp": args.fsdp_axis})
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} over "
          f"{len(jax.devices())} devices")

    model = Unet(output_channels=3, emb_features=64,
                 feature_depths=(16, 32), attention_configs=None,
                 num_res_blocks=1)

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, args.image_size,
                                          args.image_size, 3)),
                          jnp.zeros((1,)))["params"]

    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(2e-3),
        schedule=CosineNoiseSchedule(timesteps=1000),
        transform=EpsilonPredictionTransform(),
        mesh=mesh,
        config=TrainerConfig(uncond_prob=0.0, normalize=False,
                             log_every=max(args.steps // 4, 1)))

    # Show where the parameters actually live: per-tensor PartitionSpecs
    # inferred by size (big kernels shard on fsdp, small stay replicated).
    sharded = replicated = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            trainer.state.params):
        if "fsdp" in str(leaf.sharding.spec):
            sharded += 1
        else:
            replicated += 1
    print(f"params: {sharded} tensors sharded on fsdp, "
          f"{replicated} replicated")

    # Data: each process contributes its slice;
    # make_array_from_process_local_data (inside put_batch) assembles the
    # global batch. Single-process here, so local batch == global batch.
    rng = np.random.default_rng(0)

    def data():
        while True:
            yield {"sample": rng.normal(
                size=(args.batch, args.image_size, args.image_size, 3)
            ).astype(np.float32) * 0.5}

    history = trainer.fit(data(), total_steps=args.steps)
    print(f"loss {history['loss'][0]:.4f} -> {history['final_loss']:.4f}")
    assert history["final_loss"] < history["loss"][0], "loss must decrease"
    return history


if __name__ == "__main__":
    main()
