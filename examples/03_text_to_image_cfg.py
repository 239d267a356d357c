#!/usr/bin/env python
"""Text-conditional diffusion with classifier-free guidance (reference
analogue: the "text to image" tutorial notebook).

Shows the conditioning stack end to end: a text encoder (offline hash
encoder by default — swap for `CLIPTextEncoder.from_modelname()` when
downloads are available), `ConditionalInputConfig` with its cached null
embedding, CFG dropout inside the train step (`jnp.where` splice against
the null embedding), and guided sampling where the scan doubles the
batch to evaluate conditional+unconditional in one model call.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--image_size", type=int, default=16)
    ap.add_argument("--guidance", type=float, default=3.0)
    ap.add_argument("--sample_steps", type=int, default=50)
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
    from flaxdiff_tpu.data.prefetch import prefetch_map
    from flaxdiff_tpu.inputs import (ConditionalInputConfig,
                                     DiffusionInputConfig, HashTextEncoder)
    from flaxdiff_tpu.models.unet import Unet
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.samplers import DiffusionSampler, EulerAncestralSampler
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    # conditioning: encoder + input config with a cached null embedding
    encoder = HashTextEncoder.create(features=32)
    cond_cfg = ConditionalInputConfig(encoder=encoder)
    input_config = DiffusionInputConfig(
        sample_data_key="sample",
        sample_data_shape=(args.image_size, args.image_size, 3),
        conditions=[cond_cfg])

    # data: synthetic set ships captions ("bright"/"dark"); encode on a
    # background thread so the device never waits for the encoder
    dataset = get_dataset("synthetic", image_size=args.image_size, n=256)
    raw = get_dataset_grain(dataset, batch_size=args.batch,
                            image_size=args.image_size)["train"]()

    def encode_text(batch):
        batch["cond"] = {"text": np.asarray(encoder(batch["text"]))}
        return batch

    data = prefetch_map(encode_text, raw, depth=2)

    # model: cross-attention on the deepest level reads the text tokens
    attn = {"heads": 2, "dim_head": 16, "backend": "auto"}
    model = Unet(output_channels=3, emb_features=64,
                 feature_depths=depths,
                 attention_configs=(None,) * (len(depths) - 1) + (attn,),
                 num_res_blocks=1)

    def apply_fn(params, x, t, cond):
        text = cond["text"] if cond is not None else None
        return model.apply({"params": params}, x, t, text)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, args.image_size,
                                          args.image_size, 3)),
                          jnp.zeros((1,)),
                          jnp.zeros((1, encoder.max_length,
                                     encoder.features)))["params"]

    schedule = CosineNoiseSchedule(timesteps=1000)
    transform = EpsilonPredictionTransform()
    null_text = input_config.get_unconditionals(1)[0]

    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(2e-3),
        schedule=schedule, transform=transform,
        mesh=create_mesh(axes={"data": -1}),
        config=TrainerConfig(uncond_prob=0.12,   # CFG dropout, ref default
                             log_every=max(args.steps // 5, 1)),
        null_cond={"text": jnp.asarray(null_text)})
    history = trainer.fit(data, total_steps=args.steps)
    print(f"final loss {history['final_loss']:.4f}")

    # guided sampling: prompt batch vs the cached null embedding
    engine = DiffusionSampler(model_fn=apply_fn, schedule=schedule,
                              transform=transform,
                              sampler=EulerAncestralSampler(),
                              guidance_scale=args.guidance)
    prompts = ["bright"] * 4 + ["dark"] * 4
    samples = engine.generate_samples(
        trainer.get_params(), num_samples=8, resolution=args.image_size,
        diffusion_steps=args.sample_steps,
        conditioning={"text": jnp.asarray(encoder(prompts))},
        unconditional={"text": jnp.asarray(
            input_config.get_unconditionals(8)[0])})
    bright = float(samples[:4].mean())
    dark = float(samples[4:].mean())
    print(f"guided samples {samples.shape}: mean(bright)={bright:.3f} "
          f"mean(dark)={dark:.3f}")
    return {"history": history, "bright": bright, "dark": dark}


if __name__ == "__main__":
    main()
