#!/usr/bin/env python
"""Audio-conditioned video diffusion: 3D UNet on synchronized AV clips.

The reference's video+audio path needed VoxCeleb2 + decord/ffmpeg; this
framework's AV pipeline (`data/sources/av.py`) reads random video clips
with cv2 and takes audio from ffmpeg OR a sidecar wav, so the whole
example is hermetic: it synthesizes tiny mp4+wav pairs, samples random
clips with retries, mel-tokenizes the audio one token per frame, and
trains a temporal-attention UNet3D on [B, F, H, W, C] batches — then
samples a short clip conditioned on audio.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def synthesize_av_files(root: str, n: int = 8, size: int = 32,
                        dur: float = 2.0, fps: int = 25):
    """cv2-encoded mp4s + sine-tone sidecar wavs (no ffmpeg needed)."""
    import cv2
    import numpy as np
    from scipy.io import wavfile
    os.makedirs(root, exist_ok=True)
    rng = np.random.default_rng(0)
    for i in range(n):
        path = os.path.join(root, f"{i}.mp4")
        w = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*"mp4v"), fps,
                            (size, size))
        for f in range(int(dur * fps)):
            frame = np.full((size, size, 3), (f * 9 + i * 23) % 255, np.uint8)
            frame[: size // 4] = rng.integers(0, 255, (size // 4, size, 3),
                                              dtype=np.uint8)
            w.write(frame)
        w.release()
        sr = 22050
        t = np.arange(int(dur * sr), dtype=np.float32) / sr
        tone = 220 * (i + 1)
        wav = (0.4 * np.sin(2 * np.pi * tone * t) * 32767).astype(np.int16)
        wavfile.write(path[:-4] + ".wav", sr, wav)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--image_size", type=int, default=32)
    ap.add_argument("--num_frames", type=int, default=4)
    ap.add_argument("--video_dir", default=None,
                    help="folder of mp4s (+optional sidecar wavs); "
                         "default: synthesized toy clips")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    if args.smoke:
        args.steps, args.image_size, args.num_frames = 4, 16, 2

    import jax.numpy as jnp
    import numpy as np
    import optax

    from flaxdiff_tpu.data import get_dataset, get_dataset_grain
    from flaxdiff_tpu.data.prefetch import prefetch_map
    from flaxdiff_tpu.inputs import MelAudioEncoder
    from flaxdiff_tpu.models.unet3d import UNet3D
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.samplers import DiffusionSampler, EulerAncestralSampler
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    root = args.video_dir
    tmp = None
    if root is None:
        tmp = tempfile.TemporaryDirectory()
        root = tmp.name
        synthesize_av_files(root, size=args.image_size)
        print(f"synthesized toy AV clips in {root}")

    # AV pipeline: random clip sampling (with retries), sidecar-wav audio,
    # per-frame waveform alignment
    dataset = get_dataset("av_folder", root=root,
                          image_size=args.image_size,
                          num_frames=args.num_frames)
    raw = get_dataset_grain(dataset, batch_size=args.batch,
                            image_size=args.image_size)["train"]()

    # audio -> one conditioning token per video frame
    audio_enc = MelAudioEncoder.create()

    def encode_audio(batch):
        fw = batch["audio"]["framewise_audio"]
        batch["cond"] = {"audio": np.asarray(audio_enc(fw))}
        return {"sample": batch["sample"], "cond": batch["cond"]}

    data = prefetch_map(encode_audio, raw, depth=2)

    model = UNet3D(output_channels=3, emb_features=32,
                   feature_depths=(16,), attention_levels=(True,),
                   num_res_blocks=1, heads=2, norm_groups=4)

    def apply_fn(params, x, t, cond):
        ctx = cond["audio"] if cond is not None else None
        return model.apply({"params": params}, x, t, ctx)

    def init_fn(key):
        return model.init(
            key,
            jnp.zeros((1, args.num_frames, args.image_size,
                       args.image_size, 3)),
            jnp.zeros((1,)),
            jnp.zeros((1, args.num_frames, audio_enc.features)))["params"]

    schedule = CosineNoiseSchedule(timesteps=1000)
    transform = EpsilonPredictionTransform()
    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
        schedule=schedule, transform=transform,
        mesh=create_mesh(axes={"data": -1}),
        config=TrainerConfig(uncond_prob=0.1,
                             log_every=max(args.steps // 3, 1)),
        null_cond={"audio": jnp.zeros((1, args.num_frames,
                                       audio_enc.features))})
    history = trainer.fit(data, total_steps=args.steps)
    print(f"final loss {history['final_loss']:.4f}")

    # sample a clip conditioned on one training clip's audio
    ref = next(data)
    engine = DiffusionSampler(model_fn=apply_fn, schedule=schedule,
                              transform=transform,
                              sampler=EulerAncestralSampler(),
                              guidance_scale=1.5)
    clip = engine.generate_samples(
        trainer.get_params(), num_samples=2, resolution=args.image_size,
        sequence_length=args.num_frames, diffusion_steps=5,
        conditioning={"audio": jnp.asarray(ref["cond"]["audio"][:2])},
        unconditional={"audio": jnp.zeros((2, args.num_frames,
                                           audio_enc.features))})
    assert clip.shape == (2, args.num_frames, args.image_size,
                          args.image_size, 3)
    print(f"sampled video {clip.shape}")
    if tmp is not None:
        tmp.cleanup()
    return history


if __name__ == "__main__":
    main()
