#!/usr/bin/env python3
"""The numbers `correct` compares, over many seeds in one process: what a
limit is set from (PERF.md, section 6). A builder's tool, like
`spread.py`; no run of the benchmark calls it.

  python benchmark/readings.py --workload <name> --seeds 1,2,3 \
      [--control-seeds 7,8,9] [--seconds 6]

For each of `--seeds` the sound program's readings: a training cell's
first steps through `fit`, followed by the reference; a serving cell's
short window at the cell's own load (set-up is paid once: the weights of
the next seed are put into the same pipeline), its finished requests
sampled as a run samples them. For each of `--control-seeds` the
control's: the reference with its products in `--control`, put in the
program's place. The last line is {"sound": [...], "control": [...]}.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import threading
import time

import run as entry


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="fp8", choices=("bf16", "fp8"))
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=entry.ROOT)
    args = ap.parse_args(argv)
    args.seeds = [int(s) for s in args.seeds.split(",") if s]
    args.control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    return args


def train(cell, cfg, args):
    import jax  # noqa: F401 - the harness imports it lazily

    from harness import train_steady as ts, weights
    tc, traffic = cfg["train"], cell.traffic
    shape = (tc["batch_per_chip"] * cell.chips, cfg["input"]["resolution"],
             cfg["input"]["channels"], cfg["conditioning"]["tokens"],
             cfg["conditioning"]["features"])
    null_ctx = weights.null_context(*shape[3:])
    n_check = int(traffic["check_steps"])
    sound, control = [], []
    for seed in args.seeds:
        batches = weights.train_batches(seed, tc["host_batches"], *shape)
        (trainer, shapes, init_key, train_key, losses, first_grad,
         grad_norms, delta) = ts._first_steps(cfg, traffic, cell.chips,
                                              seed, null_ctx, batches)
        devices = list(trainer.mesh.devices.flat)
        del trainer
        gc.collect()
        ref = ts._reference(cfg, batches, shapes, init_key, train_key,
                            null_ctx, n_check, devices, "")
        ok, got, _ = ts._compare(cfg, n_check, losses, first_grad,
                                 grad_norms, delta, ref)
        sound.append(dict(got, seed=seed, correct=ok))
        del ref, first_grad
    for seed in args.control_seeds:
        batches = weights.train_batches(seed, tc["host_batches"], *shape)
        args.seed = seed
        out = ts._control_only(cfg, traffic, batches, null_ctx,
                               jax.devices()[:cell.chips], args,
                               time.perf_counter())
        control.append(dict(out["readings"], seed=seed,
                            correct=out["correct"]))
    return sound, control


def serve(cell, cfg, args):
    import jax

    from flaxdiff_tpu.serving import SchedulerConfig, ServingScheduler
    from flaxdiff_tpu.telemetry import Telemetry
    from harness import check, loadgen, serving as sv, weights
    traffic = cell.traffic
    null_ctx = weights.null_context(cfg["conditioning"]["tokens"],
                                    cfg["conditioning"]["features"])
    limits = check.load_limits(cfg, "serve")
    sound, control = [], []
    if args.seeds:
        pipe, shapes, _ = sv.build_pipeline(cfg, args.seeds[0], null_ctx)
        sconf = SchedulerConfig()
        sched = ServingScheduler(pipeline=pipe,
                                 telemetry=Telemetry(enabled=False),
                                 autostart=False, config=sconf)
        sv.warm_engine(sched.engine, cfg, traffic, args.seeds[0],
                       sconf.batch_buckets, sconf.round_steps)
        sched.start()
        make = weights.Maker(shapes).make
    for seed in args.seeds:
        raw_key, ema_key = sv.serve_keys(seed)
        pipe.params = pipe.ema_params = None
        pipe.params = {"params": make(raw_key)}
        pipe.ema_params = {"params": make(ema_key)}
        nfes = loadgen.dealt_nfe(seed, traffic["nfe_deal"], 100000)
        fields_of = lambda i: sv.request_fields(cfg, traffic, seed, i,
                                                nfes[i])
        rec, stop = loadgen.Recorder(), threading.Event()
        threads = loadgen.closed_loop(
            lambda i: sched.submit(sv.make_request(cfg, fields_of(i), seed)),
            int(traffic["clients"]), stop, rec, fields_of)
        time.sleep(args.seconds)
        stop.set()
        for t in threads:
            t.join(600)
        pool = [d for d in rec.snapshot() if d.result is not None]
        served = sv.pick_served(pool, int(traffic["check_requests"]), seed)
        gaps = sv.reference_gaps(cfg, served, shapes, ema_key, null_ctx,
                                 seed, "", jax.devices()[:cell.chips])
        ok = check.verdict(sv.gap_rows(gaps, len(served), limits, "served"))
        sound.append(dict(gaps, seed=seed, finished=len(pool), correct=ok))
    if args.seeds:
        sched.close(drain=True)
        del sched, pipe
        gc.collect()
    for seed in args.control_seeds:
        args.seed = seed
        out = sv._control_only(cfg, traffic, null_ctx,
                               jax.devices()[:cell.chips], args,
                               time.perf_counter())
        control.append(dict(out["readings"], seed=seed,
                            correct=out["correct"]))
    return sound, control


def main(argv=None) -> int:
    args = parse(argv)
    ready = entry.prepare(args)
    if isinstance(ready, int):
        return ready
    cell, _, _ = ready
    from harness import models
    cfg = models.effective_config(cell.config, args.rehearse)
    kind = cell.traffic["kind"]
    sound, control = (train if kind == "train_steady" else serve)(
        cell, cfg, args)
    for row in sound:
        print("sound " + json.dumps(row), flush=True)
    for row in control:
        print("control " + json.dumps(row), flush=True)
    print(json.dumps({"sound": sound, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
