#!/usr/bin/env python
"""Sequence-parallel training with ring attention (no reference analogue
— the reference is data-parallel only).

A DiT's token sequence is sharded over the mesh's `seq` axis; attention
runs as exact ring attention: each device holds its sequence shard, K/V
shards rotate around the ring via `ppermute` (ICI neighbor exchange on a
real pod) with online-softmax accumulation — O(L/n) memory per device,
bitwise-exact vs full attention. It is a *backend*, not a model rewrite:
the same `SimpleDiT` runs single-chip (`backend="auto"`) or
sequence-parallel (`backend="ring"` under a mesh with a `seq` axis).

Runs on an 8-virtual-device CPU mesh by default.
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
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--image_size", type=int, default=32)
    ap.add_argument("--patch_size", type=int, default=4)  # 64 tokens
    ap.add_argument("--seq_axis", type=int, default=4)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    if args.smoke:
        args.steps = 6

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from flaxdiff_tpu.models.dit import SimpleDiT
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.parallel.context import use_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    n = len(jax.devices())
    mesh = create_mesh(axes={"data": n // args.seq_axis,
                             "seq": args.seq_axis})
    tokens = (args.image_size // args.patch_size) ** 2
    print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}; "
          f"{tokens} tokens -> {tokens // args.seq_axis} per device")

    model = SimpleDiT(output_channels=3, patch_size=args.patch_size,
                      emb_features=64, num_layers=2, num_heads=2,
                      backend="ring")   # <- the only change vs single-chip

    def apply_fn(params, x, t, cond):
        text = cond["text"] if cond is not None else None
        return model.apply({"params": params}, x, t, text)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, args.image_size,
                                          args.image_size, 3)),
                          jnp.zeros((1,)),
                          jnp.zeros((1, 4, 64)))["params"]

    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
        schedule=CosineNoiseSchedule(timesteps=1000),
        transform=EpsilonPredictionTransform(),
        mesh=mesh,
        config=TrainerConfig(uncond_prob=0.0, normalize=False,
                             log_every=max(args.steps // 3, 1)),
        null_cond={"text": jnp.zeros((1, 4, 64))})

    rng = np.random.default_rng(0)

    def data():
        while True:
            yield {
                "sample": rng.normal(size=(args.batch, args.image_size,
                                           args.image_size, 3))
                .astype(np.float32) * 0.5,
                "cond": {"text": rng.normal(size=(args.batch, 4, 64))
                         .astype(np.float32)},
            }

    history = trainer.fit(data(), total_steps=args.steps)
    print(f"loss {history['loss'][0]:.4f} -> {history['final_loss']:.4f} "
          f"(ring attention, fwd+bwd, over the seq axis)")

    # cross-check: the ring program computes the same function as
    # single-device XLA attention
    x = jnp.asarray(rng.normal(size=(2, args.image_size, args.image_size,
                                     3)), jnp.float32)
    t = jnp.full((2,), 500.0)
    params = trainer.get_params(use_ema=False)
    with use_mesh(mesh):
        ring_out = model.apply({"params": params}, x, t, None)
    xla_out = SimpleDiT(output_channels=3, patch_size=args.patch_size,
                        emb_features=64, num_layers=2, num_heads=2,
                        backend="xla").apply({"params": params}, x, t, None)
    err = float(jnp.max(jnp.abs(ring_out - xla_out)))
    print(f"max |ring - xla| = {err:.2e}")
    assert err < 1e-4
    return history


if __name__ == "__main__":
    main()
