#!/usr/bin/env python
"""Unconditional diffusion from scratch: UNet + cosine schedule + DDPM.

The "hello world" of the framework (reference analogue: the "simple
diffusion" tutorial notebook). Trains a small UNet to denoise a toy
two-mode image distribution, then samples with DDPM and DDIM from the
same trained params — every sampler runs its whole trajectory inside one
compiled `lax.scan`.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--image_size", type=int, default=16)
    ap.add_argument("--sample_steps", type=int, default=50)
    ap.add_argument("--out", default=None, help="PNG path for the grid")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    depths = (16, 32)
    if args.smoke:
        args.steps, args.batch, args.sample_steps = 6, 8, 2
        depths = (16,)          # one level: less to compile

    import jax.numpy as jnp
    import numpy as np
    import optax

    from flaxdiff_tpu.data import get_dataset, get_dataset_grain
    from flaxdiff_tpu.models.unet import Unet
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.samplers import DDIMSampler, DDPMSampler, DiffusionSampler
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    # 1. data: a deterministic toy distribution (swap for any registry name)
    dataset = get_dataset("synthetic", image_size=args.image_size, n=256)
    loader = get_dataset_grain(dataset, batch_size=args.batch,
                               image_size=args.image_size)
    data = loader["train"]()

    # 2. model: a small UNet, no attention at this resolution
    model = Unet(output_channels=3, emb_features=64,
                 feature_depths=depths, attention_configs=None,
                 num_res_blocks=1)

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, args.image_size,
                                          args.image_size, 3)),
                          jnp.zeros((1,)))["params"]

    # 3. diffusion math: cosine VP schedule, epsilon prediction
    schedule = CosineNoiseSchedule(timesteps=1000)
    transform = EpsilonPredictionTransform()

    # 4. train
    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(2e-3),
        schedule=schedule, transform=transform,
        mesh=create_mesh(axes={"data": -1}),
        config=TrainerConfig(uncond_prob=0.0, log_every=max(args.steps // 5, 1)))
    history = trainer.fit(data, total_steps=args.steps)
    print(f"final loss {history['final_loss']:.4f}")

    # 5. sample with two different samplers from the same params
    params = trainer.get_params(use_ema=True)
    for name, sampler in (("ddpm", DDPMSampler()), ("ddim", DDIMSampler())):
        engine = DiffusionSampler(model_fn=apply_fn, schedule=schedule,
                                  transform=transform, sampler=sampler)
        samples = engine.generate_samples(
            params, num_samples=8, resolution=args.image_size,
            diffusion_steps=args.sample_steps)
        print(f"{name}: sampled {samples.shape}, "
              f"range [{float(samples.min()):.2f}, {float(samples.max()):.2f}]")

    if args.out:
        from flaxdiff_tpu.trainer.logging import save_image_grid
        save_image_grid(np.asarray(samples), args.out)
        print(f"wrote {args.out}")
    return history


if __name__ == "__main__":
    main()
