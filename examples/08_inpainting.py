#!/usr/bin/env python
"""Masked inpainting and img2img with a trained model (capabilities the
reference library lacks).

Trains the toy unconditional model from example 01, then:
- img2img (SDEdit): start the trajectory from a noised input at an
  intermediate step — low start_step stays close to the input, high
  start_step re-imagines it;
- inpainting: regenerate only the masked region while the rest of the
  image is pinned to the reference, re-noised per step so the generated
  region blends against a consistent neighborhood.

Both run inside the sampler's single compiled lax.scan.
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
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    depths = (16, 32)
    if args.smoke:
        args.steps, args.batch, args.sample_steps = 6, 8, 2
        depths = (16,)          # one level: less to compile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from flaxdiff_tpu.data import get_dataset, get_dataset_grain
    from flaxdiff_tpu.models.unet import Unet
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.samplers import DDIMSampler, DiffusionSampler
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig
    from flaxdiff_tpu.utils import RngSeq

    dataset = get_dataset("synthetic", image_size=args.image_size, n=256)
    data = get_dataset_grain(dataset, batch_size=args.batch,
                             image_size=args.image_size)["train"]()

    model = Unet(output_channels=3, emb_features=64,
                 feature_depths=depths, attention_configs=None,
                 num_res_blocks=1)

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, args.image_size,
                                          args.image_size, 3)),
                          jnp.zeros((1,)))["params"]

    schedule = CosineNoiseSchedule(timesteps=1000)
    transform = EpsilonPredictionTransform()
    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(2e-3),
        schedule=schedule, transform=transform,
        mesh=create_mesh(axes={"data": -1}),
        config=TrainerConfig(uncond_prob=0.0, log_every=max(args.steps // 4, 1)))
    history = trainer.fit(data, total_steps=args.steps)
    print(f"trained: final loss {history['final_loss']:.4f}")

    params = trainer.get_params(use_ema=False)
    engine = DiffusionSampler(model_fn=apply_fn, schedule=schedule,
                              transform=transform, sampler=DDIMSampler())

    # img2img: noise a reference to an intermediate step and denoise back
    reference = jnp.full((4, args.image_size, args.image_size, 3), -0.5)
    start = 0.4 * schedule.timesteps
    rngstate = RngSeq.create(7)
    rngstate, k = rngstate.next_key()
    noise = jax.random.normal(k, reference.shape)
    t_b = jnp.full((reference.shape[0],), start)
    noised = schedule.add_noise(reference, noise, t_b)
    edited = engine.generate_samples(
        params, num_samples=4, resolution=args.image_size,
        diffusion_steps=args.sample_steps, init_samples=noised,
        start_step=start, rngstate=rngstate)
    drift = float(jnp.abs(edited - reference).mean())
    print(f"img2img from step {start:.0f}: mean drift from input {drift:.3f}")

    # inpainting: regenerate the left half, keep the right half
    mask = np.zeros((4, args.image_size, args.image_size), np.float32)
    mask[:, :, : args.image_size // 2] = 1.0
    out = engine.generate_samples(
        params, num_samples=4, resolution=args.image_size,
        diffusion_steps=args.sample_steps, rngstate=RngSeq.create(0),
        inpaint_reference=reference, inpaint_mask=mask)
    kept_err = float(jnp.abs(
        out[:, :, args.image_size // 2:] -
        reference[:, :, args.image_size // 2:]).max())
    gen_mean = float(out[:, :, : args.image_size // 2].mean())
    print(f"inpaint: kept-region max err {kept_err:.2e}, "
          f"generated-region mean {gen_mean:.3f}")
    assert kept_err < 1e-4
    return history


if __name__ == "__main__":
    main()
