#!/usr/bin/env python
"""Flexible-resolution train sweep CLI over bench.py's builders.

`python bench.py --stage sweep256` runs the canonical north-star stage
(256^2, feature_depths 128-1024, fixed batch ladder). This CLI is the
free-form variant for hardware sessions: any size/depths/batch list,
same per-batch outcome recording and remat retry, same trainer
construction and timing — imported from bench.py, not duplicated. One
process: it holds the chip for its whole run.

Usage (on the chip):
  python scripts/bench_sweep256.py --image_size 256 \
      --depths 128,256,512,1024 --batches 1,2,4,8,16,32 \
      --out r4_sweep256.jsonl
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def attempt(image_size, depths, batch, remat, timed_steps, attn_backend):
    """One (batch, remat) cell; returns a dict with numbers or a cause."""
    from bench import build_trainer, make_batches, run
    from flaxdiff_tpu.profiling import device_peak_flops, mfu
    try:
        trainer = build_trainer(tpu_native=True, image_size=image_size,
                                depths=depths, remat=remat,
                                attn_backend=attn_backend)
        ips, step_s, flops = run(trainer,
                                 make_batches(batch, image_size, n=2),
                                 batch, sync_every_step=False,
                                 timed_steps=timed_steps)
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"[:300], "remat": remat}
    finally:
        # free param+opt state before the next cell shrinks the frontier
        try:
            del trainer
        except UnboundLocalError:
            pass
    peak = device_peak_flops()
    return {"imgs_per_sec_per_chip": round(ips, 3),
            "step_time_ms": round(step_s * 1e3, 2),
            "mfu_hw": (round(mfu(flops, step_s, peak), 4)
                       if flops and peak else None),
            "remat": remat}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--image_size", type=int, default=256)
    ap.add_argument("--depths", default="128,256,512,1024")
    ap.add_argument("--batches", default="1,2,4,8,16,32")
    ap.add_argument("--timed_steps", type=int, default=10)
    ap.add_argument("--attn_backend", default="auto")
    ap.add_argument("--trace", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax

    from flaxdiff_tpu.utils import configure_compilation_cache
    configure_compilation_cache()

    depths = tuple(int(x) for x in args.depths.split(","))
    batches = [int(x) for x in args.batches.split(",")]
    dev = jax.devices()[0]
    res = {"metric": f"sweep{args.image_size}", "platform": dev.platform,
           "device_kind": dev.device_kind,
           "device_count": jax.device_count(),
           "image_size": args.image_size, "depths": list(depths),
           "attn_backend": args.attn_backend, "per_batch": {}}

    failures = 0
    for batch in batches:
        cell = attempt(args.image_size, depths, batch, False,
                       args.timed_steps, args.attn_backend)
        res["per_batch"][str(batch)] = cell
        log(f"batch {batch}: {cell}")
        if "error" in cell:
            # remat answers "was that OOM?" empirically: it trades
            # FLOPs for activation memory, so a batch that only fits
            # rematerialized pins the cause on memory
            cell_r = attempt(args.image_size, depths, batch, True,
                             args.timed_steps, args.attn_backend)
            res["per_batch"][f"{batch}_remat"] = cell_r
            log(f"batch {batch} remat: {cell_r}")
            failures += 1
            if failures >= 2 and "error" in cell_r:
                break
    ok_num = {int(k): v for k, v in res["per_batch"].items()
              if "error" not in v and "_" not in k}
    ok_all = {k: v for k, v in res["per_batch"].items() if "error" not in v}
    if ok_all:
        best_key = max(ok_all, key=lambda k:
                       ok_all[k]["imgs_per_sec_per_chip"])
        res["best"] = dict(ok_all[best_key], batch=best_key)
    if args.trace and ok_num:
        try:
            from bench import build_trainer, make_batches
            from flaxdiff_tpu.profiling import trace
            best_b = max(ok_num,
                         key=lambda k: ok_num[k]["imgs_per_sec_per_chip"])
            trainer = build_trainer(tpu_native=True,
                                    image_size=args.image_size,
                                    depths=depths,
                                    attn_backend=args.attn_backend)
            put = [trainer.put_batch(b)
                   for b in make_batches(best_b, args.image_size, n=2)]
            for i in range(2):
                loss = trainer.train_step(put[i % 2])
            float(jax.device_get(loss))
            with trace(args.trace):
                for i in range(5):
                    loss = trainer.train_step(put[i % 2])
                float(jax.device_get(loss))
            res["trace_dir"] = args.trace
        except Exception as e:
            # a failed trace capture must not erase the measured
            # per-batch cells below
            res["trace_error"] = f"{type(e).__name__}: {e}"[:200]
    line = json.dumps(res)
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
