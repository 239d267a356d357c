#!/usr/bin/env python
"""Latent diffusion end-to-end on the first-party KL VAE.

The reference could only do latent diffusion through the downloaded
Stable-Diffusion VAE (its own autoencoder stub returned zeros and its VAE
trainer was broken). Here the whole loop is first-party: (1) train the
KL autoencoder, (2) measure the latent scaling factor (SD convention:
1/std of encoded latents), (3) train a diffusion prior in latent space —
the VAE encode runs inside the jitted train step — and (4) sample,
decoding latents back to pixels inside the sampler's post-process.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--vae_steps", type=int, default=300)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--image_size", type=int, default=16)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    depths = (32, 64)
    if args.smoke:
        args.vae_steps, args.steps, args.batch = 8, 6, 8
        depths = (32,)          # one level: less to compile

    import jax
    import jax.numpy as jnp
    import optax

    from flaxdiff_tpu.data import get_dataset, get_dataset_grain
    from flaxdiff_tpu.models.autoencoder import KLAutoEncoder
    from flaxdiff_tpu.models.unet import Unet
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.samplers import DDIMSampler, DiffusionSampler
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig
    from flaxdiff_tpu.trainer.autoencoder_trainer import (
        AutoEncoderTrainer, AutoEncoderTrainerConfig)

    mesh = create_mesh(axes={"data": -1})
    dataset = get_dataset("synthetic", image_size=args.image_size, n=256)

    def batches():
        return get_dataset_grain(dataset, batch_size=args.batch,
                                 image_size=args.image_size)["train"]()

    # 1. train the VAE (2x downscale, tiny widths for the demo)
    vae0 = KLAutoEncoder.create(
        jax.random.PRNGKey(0), input_channels=3, image_size=args.image_size,
        latent_channels=4, block_channels=(16, 32), layers_per_block=1,
        norm_groups=4)
    vt = AutoEncoderTrainer(
        vae0, optax.adam(2e-3), mesh,
        AutoEncoderTrainerConfig(kl_weight=1e-6,
                                 log_every=max(args.vae_steps // 3, 1)))
    vh = vt.fit(batches(), total_steps=args.vae_steps)
    quality = vt.evaluate(next(batches()))
    print(f"VAE: recon {vh['recon'][-1]:.4f}, psnr {quality['psnr']:.1f} dB")

    # 2. latent scale so the prior sees ~unit-variance latents
    scale = vt.measure_latent_scale(batches())
    vae = vt.trained_vae(scaling_factor=scale)
    print(f"latent scaling_factor {scale:.3f} "
          f"(downscale {vae.downscale_factor}x, {vae.latent_channels}ch)")

    # 3. diffusion prior over latents: the trainer's autoencoder hook
    # encodes batches INSIDE the jitted step
    lat_res = args.image_size // vae.downscale_factor
    model = Unet(output_channels=vae.latent_channels, emb_features=64,
                 feature_depths=depths, attention_configs=None,
                 num_res_blocks=1)

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, lat_res, lat_res,
                                          vae.latent_channels)),
                          jnp.zeros((1,)))["params"]

    schedule = CosineNoiseSchedule(timesteps=1000)
    transform = EpsilonPredictionTransform()
    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(2e-3),
        schedule=schedule, transform=transform, mesh=mesh,
        config=TrainerConfig(uncond_prob=0.0,
                             log_every=max(args.steps // 3, 1)),
        autoencoder=vae)
    history = trainer.fit(batches(), total_steps=args.steps)
    print(f"prior final loss {history['final_loss']:.4f}")

    # 4. sample in latent space; the engine decodes through the VAE
    engine = DiffusionSampler(model_fn=apply_fn, schedule=schedule,
                              transform=transform, sampler=DDIMSampler(),
                              autoencoder=vae)
    samples = engine.generate_samples(
        trainer.get_params(), num_samples=4, resolution=args.image_size,
        diffusion_steps=20)
    assert samples.shape == (4, args.image_size, args.image_size, 3)
    print(f"decoded samples {samples.shape}")
    return history


if __name__ == "__main__":
    main()
