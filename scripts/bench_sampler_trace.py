#!/usr/bin/env python
"""Profile DDIM-50 inference at 256^2 on chip and break the latency down.

1153 ms (23 ms/NFE) was recorded (BENCH_r03_midround.json) but never
examined. This captures a device trace of the compiled sampler scan in
three configurations — unconditional, CFG (guidance>0: the 2x-batched
model call), and CFG+EMA-style second param tree — then attributes
device time by op family via scripts/analyze_trace.py, so the number
either improves or gets a documented floor.

Usage: python scripts/bench_sampler_trace.py --out r4_ddim_profile.jsonl
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TEXT_LEN = 77
TEXT_DIM = 768


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--image_size", type=int, default=256)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--depths", default="64,128,256,512")
    ap.add_argument("--emb", type=int, default=512)
    ap.add_argument("--trace", default="ddim_trace")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from flaxdiff_tpu.utils import configure_compilation_cache
    configure_compilation_cache()

    from flaxdiff_tpu.models.unet import Unet
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.profiling import trace
    from flaxdiff_tpu.samplers import DDIMSampler, DiffusionSampler
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.utils import RngSeq

    size = args.image_size
    depths = tuple(int(x) for x in args.depths.split(","))
    attn = {"heads": 8, "dim_head": 64, "backend": "auto"}
    model = Unet(output_channels=3, emb_features=args.emb,
                 feature_depths=depths,
                 attention_configs=tuple(
                     None if i < len(depths) - 2 else dict(attn)
                     for i in range(len(depths))),
                 num_res_blocks=2, dtype=jnp.bfloat16)

    def apply_fn(params, x, t, cond):
        text = (cond["text"] if isinstance(cond, dict) else
                jnp.zeros((x.shape[0], TEXT_LEN, TEXT_DIM), x.dtype))
        return model.apply({"params": params}, x, t, text)

    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, size, size, 3)), jnp.zeros((1,)),
                        jnp.zeros((1, TEXT_LEN, TEXT_DIM)))["params"]
    text = jax.random.normal(jax.random.PRNGKey(1),
                             (args.batch, TEXT_LEN, TEXT_DIM), jnp.float32)
    null = jnp.zeros((args.batch, TEXT_LEN, TEXT_DIM), jnp.float32)

    res = {"metric": "ddim_profile", "image_size": size,
           "steps": args.steps, "batch": args.batch,
           "platform": jax.devices()[0].platform, "configs": {}}

    def measure(name, guidance, cond, uncond):
        engine = DiffusionSampler(
            model_fn=apply_fn,
            schedule=CosineNoiseSchedule(timesteps=1000),
            transform=EpsilonPredictionTransform(),
            sampler=DDIMSampler(), guidance_scale=guidance)

        def once(seed):
            out = engine.generate_samples(
                params, num_samples=args.batch, resolution=size,
                diffusion_steps=args.steps, rngstate=RngSeq.create(seed),
                conditioning=cond, unconditional=uncond)
            float(jnp.sum(out).astype(jnp.float32))

        once(0)  # compile
        times = []
        for i in range(args.repeats):
            t0 = time.perf_counter()
            once(i + 1)
            times.append(time.perf_counter() - t0)
        med = sorted(times)[len(times) // 2]
        entry = {"latency_ms": round(med * 1e3, 2),
                 "ms_per_nfe": round(med * 1e3 / args.steps /
                                     (2 if guidance else 1), 2)}
        res["configs"][name] = entry
        log(f"{name}: {entry}")
        return engine

    engine = measure("uncond", 0.0, None, None)
    measure("cfg3", 3.0, {"text": text}, {"text": null})

    # trace the unconditional config (the BASELINE.md target shape)
    try:
        with trace(args.trace):
            out = engine.generate_samples(
                params, num_samples=args.batch, resolution=size,
                diffusion_steps=args.steps, rngstate=RngSeq.create(99))
            float(jnp.sum(out).astype(jnp.float32))
        res["trace_dir"] = args.trace
        from scripts.analyze_trace import main as analyze
        analyze([args.trace, "--top", "12"])
    # SystemExit included: analyze_trace exits on host-only captures (CPU)
    except (Exception, SystemExit) as e:
        res["trace_error"] = f"{type(e).__name__}: {e}"[:200]

    line = json.dumps(res)
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
