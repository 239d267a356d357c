"""Benchmark: flagship text-conditional UNet train-step throughput + MFU.

Measures imgs/sec/chip and model-FLOPs-utilization for the framework's
jitted+sharded train step on the flagship config (text-conditional UNet,
128x128, CLIP-dim cross attention), sweeping batch size to find the
chip's sweet spot, and compares against a reference-style configuration
run on the same hardware: f32 activations, plain XLA attention, unfused
GroupNorm+SiLU, and a blocking per-step loss readback — the execution
semantics of the reference's single-chip train loop
(reference flaxdiff/trainer/simple_trainer.py:526-542,
general_diffusion_trainer.py:248-349), re-created on this framework
(`ref`, `baseline_kind`).

Two MFU figures:
  mfu_hw    — numerator from XLA cost analysis of the program that runs
              (includes the flash path's head_dim 64->128 pad work);
  mfu_model — numerator from an analytic jaxpr walk of an xla-attention
              twin of the step at TRUE shapes (unpadded; matmul+conv only).

Process model: the parent NEVER imports jax — a chip belongs to one
process at a time, so the parent runs one stage child at a time and
each child owns the device for its lifetime. Every child prints the
`platform`, `device_kind` and device count jax gave it. The headline
`value` is taken only from a sweep child that reported platform `tpu`;
on anything else (an explicit JAX_PLATFORMS=cpu run, which the tests
use to exercise the harness) the run is labelled with that platform and
`value` stays null — nothing from a CPU is published under a device
metric's name. There is no fallback: a child that cannot initialise its
backend fails its stage.

The whole run fits a HARD --budget: stages are ordered by information
value, each gets a timeout no larger than the remaining budget, and
stages that no longer fit are recorded as skipped. A SIGTERM handler
emits the cumulative result as the final line before dying. After every
stage the parent prints a cumulative JSON line and appends it to
bench_partial.jsonl.

The sweep records EVERY attempted batch with a number or its full
failure cause, and retries failed batches with remat=True to pin memory
as the cause.

Prints ONE cumulative JSON line per completed stage; the LAST line is
the final result:
  {"metric": ..., "value": N|null, "unit": ..., "platform": ...,
   "device_kind": ..., "device_count": N, "vs_baseline": N,
   "mfu_hw": ..., "mfu_model": ..., "stages": {...}, ...}

Flags:
  --trace DIR    profiler-trace dir (default ./bench_trace, always captured)
  --quick        single batch size, fewer steps (CI smoke)
  --budget S     hard wall-clock for the whole run (default 1380)
  --stages a,b,c explicit stage list (default: info-value order)
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time
import traceback

IMAGE_SIZE = 128
TEXT_LEN = 77
TEXT_DIM = 768
WARMUP_STEPS = 3
TIMED_STEPS = 30
BATCH_SWEEP = (16, 32, 64, 128, 256)  # sweep stops at the first OOM
BASELINE_BATCH = 16  # the reference's documented flowers config batch
# the reference's largest documented run (README.md:262-276) at the
# BASELINE.json north-star resolution
NORTH_STAR_DEPTHS = (128, 256, 512, 1024)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Stage bodies (run in child processes; may import jax)
# ---------------------------------------------------------------------------

def _stage_init():
    """First call of every stage body (stage children may import the
    package; the parent never does): the persistent compile cache must
    be configured before the stage's first compile."""
    from flaxdiff_tpu.utils import configure_compilation_cache
    configure_compilation_cache()


def _device_fields() -> dict:
    """What jax gave THIS process — stamped on every stage result."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def build_trainer(tpu_native: bool, image_size: int = IMAGE_SIZE,
                  attn_backend: str | None = None,
                  flat_opt: bool = False,
                  flat_params: bool = False,
                  depths: tuple = (64, 128, 256, 512),
                  attn_levels: int = 2,
                  remat: bool = False):
    import jax.numpy as jnp
    import numpy as np
    import optax

    from flaxdiff_tpu.models.unet import Unet
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import (DiffusionTrainer, TrainerConfig,
                                      flat_optimizer)

    backend = attn_backend or ("auto" if tpu_native else "xla")
    attn = {
        "heads": 8,
        "dim_head": 64,
        "backend": backend,
        "force_fp32_for_softmax": True,
    }
    # bf16 rides the MXU on TPU; on a cpu run (the tests' harness
    # check) it is emulated and only slows the run down
    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    configs = tuple(
        None if i < len(depths) - attn_levels else dict(attn)
        for i in range(len(depths)))
    model = Unet(
        output_channels=3,
        emb_features=max(depths),
        feature_depths=tuple(depths),
        attention_configs=configs,
        num_res_blocks=2,
        dtype=jnp.bfloat16 if (tpu_native and on_tpu) else None,
        remat=remat,
    )
    shape = (1, image_size, image_size, 3)
    ctx = (1, TEXT_LEN, TEXT_DIM)

    def apply_fn(params, x, t, cond):
        text = cond["text"] if cond is not None else jnp.zeros(
            (x.shape[0], TEXT_LEN, TEXT_DIM), x.dtype)
        return model.apply({"params": params}, x, t, text)

    def init_fn(key):
        return model.init(key, jnp.zeros(shape), jnp.zeros((1,)),
                          jnp.zeros(ctx))["params"]

    mesh = create_mesh(axes={"data": -1})
    null_cond = {"text": np.zeros((1, TEXT_LEN, TEXT_DIM), np.float32)}
    return DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn,
        tx=(flat_optimizer(optax.adamw(1e-4)) if flat_opt
            else optax.adamw(1e-4)),
        schedule=CosineNoiseSchedule(timesteps=1000),
        transform=EpsilonPredictionTransform(),
        mesh=mesh,
        config=TrainerConfig(uncond_prob=0.12, normalize=False,
                             flat_params=flat_params,
                             # the reference-semantics baseline has no
                             # in-graph non-finite gate (its NaN check
                             # is the per-step host sync run() applies);
                             # ours ships the production default
                             gate_nonfinite=tpu_native),
        null_cond=null_cond,
    )


def make_batches(batch, image_size=IMAGE_SIZE, n=4, seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [{
        "sample": rng.normal(
            size=(batch, image_size, image_size, 3)).astype(np.float32),
        "cond": {"text": rng.normal(
            size=(batch, TEXT_LEN, TEXT_DIM)).astype(np.float32)},
    } for _ in range(n)]


def run(trainer, batches, batch, sync_every_step: bool, timed_steps: int):
    """Returns (imgs_per_sec_per_chip, mean_step_time, per_device_flops).

    The end-of-loop barrier is a scalar host readback of the final
    loss: the final step depends on the whole chain of optimizer-state
    updates, so one readback is a completion barrier for the full timed
    loop by data dependence, and the value it returns is the one the
    NaN check needs anyway. Checked against jax.block_until_ready on a
    v5e chip over 30-step windows (PR 21): 69.65 vs 69.65 ms/step on
    this flagship step, 5.925 vs 5.909 ms/step on an 8192^3 bf16 matmul
    chain — they agree, the readback costing one scalar D2H per
    window (~0.3%, conservative)."""
    import jax
    n_chips = jax.local_device_count()
    put = [trainer.put_batch(b) for b in batches]
    for i in range(WARMUP_STEPS):
        loss = trainer.train_step(put[i % len(put)])
    float(jax.device_get(loss))
    flops = trainer.step_flops(put[0])

    t0 = time.perf_counter()
    for i in range(timed_steps):
        loss = trainer.train_step(put[i % len(put)])
        if sync_every_step:
            # Reference semantics: loss scalar read back every step for the
            # NaN check (reference simple_trainer.py:542).
            float(jax.device_get(loss))
    float(jax.device_get(loss))
    dt = time.perf_counter() - t0
    step_time = dt / timed_steps
    return timed_steps * batch / dt / n_chips, step_time, flops


def _sweep_body(image_size: int, depths: tuple,
                sweep: tuple, timed: int,
                remat_axis: bool = False) -> dict:
    """Shared batch-sweep core for the 128^2 flagship and 256^2
    north-star stages: every attempted batch lands in per_batch with a
    number or its full failure cause; failed batches retry with
    remat=True (pins memory as the cause).

    Every successful cell also records the HBM high-water mark from
    `telemetry/memory.py` (allocator peak_bytes_in_use, fullest chip).
    The allocator peak is monotonic per process, so a cell whose peak
    did not move above the sweep's running maximum is flagged
    `hbm_peak_masked` — its true peak is hidden under an earlier,
    bigger cell's. With `remat_axis`, the winning batch's OTHER remat
    setting is measured as an addendum so the sweep JSON carries the
    remat on/off step-time + HBM trade at the headline batch (ROADMAP
    item-2 follow-up)."""
    import jax

    from flaxdiff_tpu.profiling import device_peak_flops, mfu
    from flaxdiff_tpu.telemetry.memory import MemoryMonitor

    cpu = jax.devices()[0].platform == "cpu"
    n_chips = jax.local_device_count()
    peak = device_peak_flops()
    log(f"devices: {jax.devices()} ({n_chips} chips, peak "
        f"{peak / 1e12 if peak else float('nan'):.0f} TFLOP/s bf16)")

    per_batch = {}
    best = None  # (ips, batch, step_time, flops_hw, remat)
    memory = MemoryMonitor()
    hbm_seen = [0.0]    # sweep-running allocator peak (masking flag)

    def attempt(batch, remat):
        nonlocal best
        key = f"{batch}_remat" if remat else str(batch)
        try:
            trainer = build_trainer(tpu_native=True, image_size=image_size,
                                    depths=depths, remat=remat)
            ips, step_time, flops = run(
                trainer, make_batches(batch, image_size), batch,
                sync_every_step=False, timed_steps=timed)
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
            per_batch[key] = {"error": err[:300], "remat": remat,
                              "traceback": traceback.format_exc()[-600:]}
            log(f"batch {key}: FAILED {err[:200]}")
            return False
        finally:
            try:
                del trainer   # free before the next cell
            except UnboundLocalError:
                pass
        m_hw = mfu(flops, step_time, peak) if flops and peak else None
        per_batch[key] = {
            "imgs_per_sec_per_chip": round(ips, 3),
            "step_time_ms": round(step_time * 1e3, 2),
            "mfu_hw": None if m_hw is None else round(m_hw, 4),
            "remat": remat}
        snap = memory.sample()
        if snap:
            hbm_peak = snap.get("memory/peak_bytes_in_use", 0.0)
            per_batch[key]["hbm_peak_gib"] = round(hbm_peak / 2 ** 30, 3)
            if hbm_peak <= hbm_seen[0]:
                # allocator peaks are process-monotonic: this cell's
                # own peak is hidden under an earlier cell's
                per_batch[key]["hbm_peak_masked"] = True
            hbm_seen[0] = max(hbm_seen[0], hbm_peak)
        log(f"batch {key}: {ips:.2f} imgs/s/chip, "
            f"step {step_time * 1e3:.1f} ms, mfu_hw "
            f"{m_hw if m_hw is None else round(m_hw, 3)}")
        if best is None or ips > best[0]:
            best = (ips, batch, step_time, flops, remat)
        return True

    def print_progress():
        # a complete result-so-far line on stdout: if the stage is
        # killed at its timeout, run_stage salvages this line instead
        # of losing the measured cells
        line = {"platform": jax.devices()[0].platform,
                "image_size": image_size, "per_batch": dict(per_batch)}
        if best is not None:
            ips_b, batch_b, st_b, fl_b, rm_b = best
            line.update(
                imgs_per_sec_per_chip=round(ips_b, 3),
                batch_per_chip=batch_b, remat=rm_b,
                step_time_ms=round(st_b * 1e3, 2),
                mfu_hw=(round(mfu(fl_b, st_b, peak), 4)
                        if fl_b and peak else None))
        print(json.dumps(line), flush=True)

    failures = 0
    for batch in sweep:
        ok_plain = attempt(batch, remat=False)
        print_progress()
        if ok_plain:
            failures = 0
            continue
        # the non-remat cell failed on the workload: the remat retry
        # answers "was that memory?" (remat trades FLOPs for activation
        # memory, the knob exists on every block family)
        ok_r = attempt(batch, remat=True)
        print_progress()
        failures = 0 if ok_r else failures + 1
        if failures >= 2:
            break
    remat_cells = None
    if remat_axis and best is not None:
        # the remat-policy axis: measure the headline batch's OTHER
        # remat setting so both cells exist side by side (step time +
        # HBM peak = the compute/memory trade, in one JSON)
        b_batch, b_remat = best[1], best[4]
        other_key = str(b_batch) if b_remat else f"{b_batch}_remat"
        if other_key not in per_batch:
            attempt(b_batch, remat=not b_remat)
        on_key, off_key = f"{b_batch}_remat", str(b_batch)
        remat_cells = {"batch": b_batch,
                       "off": per_batch.get(off_key),
                       "on": per_batch.get(on_key)}
    return {"per_batch": per_batch, "best": best,
            "cpu": cpu, "peak": peak, "remat_axis": remat_cells}


def stage_sweep(args) -> dict:
    """Batch sweep of the TPU-native trainer + trace + both MFU figures."""
    _stage_init()
    import jax

    from flaxdiff_tpu.profiling import device_peak_flops, mfu, trace

    cpu = jax.devices()[0].platform == "cpu"
    image_size = 64 if cpu else IMAGE_SIZE
    timed = 5 if cpu else (10 if args.quick else TIMED_STEPS)
    sweep = ((4,) if cpu else
             (BASELINE_BATCH,) if args.quick else BATCH_SWEEP)

    core = _sweep_body(image_size, (64, 128, 256, 512), sweep, timed,
                       remat_axis=True)
    if core["best"] is None:
        # no throughput number, but the per-batch causes ARE the result
        return {"platform": jax.devices()[0].platform,
                "image_size": image_size,
                "per_batch": core["per_batch"],
                "error": "every batch failed"}
    ips, batch, step_time, flops, best_remat = core["best"]
    peak = core["peak"]

    # Analytic model-FLOPs (best batch only): an xla-attention twin's
    # traced jaxpr exposes the attention matmuls at TRUE head_dim (a flash
    # trainer's pallas_call is opaque to tracing). Built AFTER the sweep —
    # a second resident param+opt state would shrink the sweep's OOM
    # frontier and skew the headline batch size.
    model_flops = None
    count = None
    try:
        count = build_trainer(tpu_native=True, image_size=image_size,
                              attn_backend="xla", remat=best_remat)
        model_flops = count.step_model_flops(
            count.put_batch(make_batches(batch, image_size, n=1)[0]))
        if model_flops:
            model_flops /= jax.device_count()  # whole-mesh trace -> per chip
    except Exception as e:
        log(f"model-FLOPs count failed ({type(e).__name__}: {e}); "
            "mfu_model will be null")
    finally:
        del count   # must not stay resident through the trace rebuild
    # rebuild the measured trainer for the trace capture below
    ours = build_trainer(tpu_native=True, image_size=image_size,
                         remat=best_remat)
    for b in make_batches(batch, image_size, n=2):
        loss = ours.train_step(ours.put_batch(b))   # re-warm the program
    float(jax.device_get(loss))

    trace_dir = args.trace
    try:
        log(f"capturing profiler trace -> {trace_dir}")
        batches = [ours.put_batch(b)
                   for b in make_batches(batch, image_size)]
        with trace(trace_dir):
            for i in range(5):
                loss = ours.train_step(batches[i % len(batches)])
            float(jax.device_get(loss))
        traced = os.path.isdir(trace_dir) and any(os.scandir(trace_dir))
    except Exception as e:
        log(f"trace capture failed: {type(e).__name__}: {e}")
        traced = False

    return {
        "platform": jax.devices()[0].platform,
        "image_size": image_size,
        "imgs_per_sec_per_chip": round(ips, 3),
        "batch_per_chip": batch,
        "remat": best_remat,
        "per_batch": core["per_batch"],
        "step_time_ms": round(step_time * 1e3, 2),
        "per_device_tflops_per_step":
            round(flops / 1e12, 3) if flops else None,
        "model_tflops_per_step":
            round(model_flops / 1e12, 3) if model_flops else None,
        "mfu_hw": (round(mfu(flops, step_time, peak), 4)
                   if flops and peak else None),
        "mfu_model": (round(mfu(model_flops, step_time, peak), 4)
                      if model_flops and peak else None),
        "remat_axis": core.get("remat_axis"),
        "trace_dir": trace_dir if traced else None,
    }


def stage_sweep256(args) -> dict:
    """North-star shape: 256^2 text-conditional UNet, feature_depths
    [128,256,512,1024] (the reference's largest documented run,
    reference README.md:262-276; BASELINE.json north star asks >=40%
    MFU on this at pod scale). First-ever on-chip 256^2 train numbers
    (VERDICT r3 weak #3)."""
    _stage_init()
    import jax

    cpu = jax.devices()[0].platform == "cpu"
    if cpu:
        image_size, depths, sweep, timed = 32, (8, 16), (4,), 3
    elif args.quick:
        image_size, depths, sweep, timed = 256, NORTH_STAR_DEPTHS, (4,), 5
    else:
        image_size, depths, sweep, timed = (
            256, NORTH_STAR_DEPTHS, (2, 4, 8, 16, 32), 10)
    core = _sweep_body(image_size, depths, sweep, timed)
    if core["best"] is None:
        return {"platform": jax.devices()[0].platform,
                "image_size": image_size, "depths": list(depths),
                "per_batch": core["per_batch"],
                "error": "every batch failed"}
    ips, batch, step_time, flops, best_remat = core["best"]
    from flaxdiff_tpu.profiling import mfu
    peak = core["peak"]
    return {
        "platform": jax.devices()[0].platform,
        "image_size": image_size,
        "depths": list(depths),
        "imgs_per_sec_per_chip": round(ips, 3),
        "batch_per_chip": batch,
        "remat": best_remat,
        "per_batch": core["per_batch"],
        "step_time_ms": round(step_time * 1e3, 2),
        "mfu_hw": (round(mfu(flops, step_time, peak), 4)
                   if flops and peak else None),
    }


def stage_ref(args) -> dict:
    """Reference-execution-semantics baseline on the same hardware.

    Headline cell is the reference's documented batch 16; a small batch
    sweep also records the baseline at ITS best batch so the vs_baseline
    ratio can be quoted at matched best-effort, not only at the
    reference's pinned config (VERDICT r3 weak #8)."""
    _stage_init()
    import jax
    cpu = jax.devices()[0].platform == "cpu"
    image_size = 64 if cpu else IMAGE_SIZE
    timed = 5 if cpu else (10 if args.quick else TIMED_STEPS)
    sweep = ((4,) if cpu else
             (BASELINE_BATCH,) if args.quick else (16, 32, 64))
    log("building reference-style trainer (f32, XLA attn, per-step sync)...")
    ref = build_trainer(tpu_native=False, image_size=image_size)
    per_batch = {}
    for batch in sweep:
        try:
            ips, step_time, _ = run(ref, make_batches(batch, image_size),
                                    batch, sync_every_step=True,
                                    timed_steps=timed)
            per_batch[str(batch)] = {
                "imgs_per_sec_per_chip": round(ips, 3),
                "step_time_ms": round(step_time * 1e3, 2)}
            log(f"reference-style batch {batch}: {ips:.2f} imgs/sec/chip")
        except Exception as e:
            per_batch[str(batch)] = {
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-600:]}
            log(f"reference-style batch {batch}: FAILED {e}"[:200])
            break
    ok = {b: c for b, c in per_batch.items()
          if "imgs_per_sec_per_chip" in c}
    if not ok:
        return {"platform": jax.devices()[0].platform,
                "per_batch": per_batch,
                "error": "every batch failed"}
    head = str(sweep[0])
    best_b = max(ok, key=lambda b: ok[b]["imgs_per_sec_per_chip"])
    res = {"platform": jax.devices()[0].platform, "per_batch": per_batch,
           "best_batch": int(best_b)}
    if len(ok) == len(sweep):
        # a failed cell means the baseline's true best batch may not
        # have been measured: publishing best_* would overstate
        # vs_baseline_best
        res["best_imgs_per_sec_per_chip"] = \
            ok[best_b]["imgs_per_sec_per_chip"]
    src = head if head in ok else best_b   # documented-config headline
    if src != head:
        # the baseline_kind string promises batch 16; flag loudly when
        # the published cell is a substitute
        res["headline_batch_fallback"] = \
            f"documented batch {head} failed; published batch {src}"
    res["imgs_per_sec_per_chip"] = ok[src]["imgs_per_sec_per_chip"]
    res["batch_per_chip"] = int(src)
    res["step_time_ms"] = ok[src]["step_time_ms"]
    return res


def stage_ddim(args) -> dict:
    """50-step DDIM latency at 256^2 (BASELINE.md inference target).

    The whole trajectory is ONE compiled lax.scan program (the reference
    dispatches per step from a Python loop)."""
    _stage_init()
    import jax
    import jax.numpy as jnp

    from flaxdiff_tpu.models.unet import Unet
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.samplers import DDIMSampler, DiffusionSampler
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.utils import RngSeq

    cpu = jax.devices()[0].platform == "cpu"
    if cpu or args.quick:
        image_size, steps, repeats, key = 64, 5, 2, "ddim5_latency_ms_64"
    else:
        image_size, steps, repeats, key = 256, 50, 5, "ddim50_latency_ms_256"
    batch = 1

    attn = {"heads": 8, "dim_head": 64, "backend": "auto"}
    model = Unet(output_channels=3, emb_features=512,
                 feature_depths=(64, 128, 256, 512),
                 attention_configs=(None, None, dict(attn), dict(attn)),
                 num_res_blocks=2, dtype=jnp.bfloat16)

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t,
                           jnp.zeros((x.shape[0], TEXT_LEN, TEXT_DIM),
                                     x.dtype))

    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, image_size, image_size, 3)),
                        jnp.zeros((1,)),
                        jnp.zeros((1, TEXT_LEN, TEXT_DIM)))["params"]
    engine = DiffusionSampler(model_fn=apply_fn,
                              schedule=CosineNoiseSchedule(timesteps=1000),
                              transform=EpsilonPredictionTransform(),
                              sampler=DDIMSampler())

    def run_once(seed, n):
        out = engine.generate_samples(
            params, num_samples=n, resolution=image_size,
            diffusion_steps=steps, rngstate=RngSeq.create(seed))
        # scalar readback as the completion barrier (see run())
        float(jnp.sum(out).astype(jnp.float32))

    run_once(0, batch)  # compile
    times = []
    for i in range(repeats):
        t0 = time.perf_counter()
        run_once(i + 1, batch)
        times.append(time.perf_counter() - t0)
    med = sorted(times)[len(times) // 2]
    log(f"{key}: {med * 1e3:.1f} ms")
    res = {"platform": jax.devices()[0].platform,
           "key": key, "latency_ms": round(med * 1e3, 2)}
    if not (cpu or args.quick):
        # The batch-1 headline is already measured: print it NOW so a
        # timeout during the batch-8 addendum below (a second compile of
        # a new shape) can be salvaged by run_stage instead of losing
        # the whole stage.
        print(json.dumps(res), flush=True)
        # throughput at batch 8: batch-1 inference ran ~11.5x above its
        # analytic compute floor (tiny per-step matmuls; ROADMAP queue 1
        # item 1); batching is the honest recovery lever, so record it
        bt = 8
        try:
            run_once(100, bt)   # compile the batched program
            bt_times = []
            for i in range(3):   # median like the batch-1 number —
                t0 = time.perf_counter()   # one stall must not become
                run_once(101 + i, bt)      # the recorded evidence
                bt_times.append(time.perf_counter() - t0)
            dt = sorted(bt_times)[1]
            res["batch8_latency_ms"] = round(dt * 1e3, 2)
            res["batch8_imgs_per_sec"] = round(bt / dt, 3)
            log(f"ddim batch8: {dt * 1e3:.1f} ms "
                f"({bt / dt:.2f} imgs/s)")
        except Exception as e:
            res["batch8_error"] = traceback.format_exc()[-400:]
    return res


def stage_attnpad(args) -> dict:
    """Cost of the flash path's head_dim 64->128 zero-pad, measured.

    Times flash attention fwd+bwd on the flagship's attention shape with
    (a) the default padded dispatch, (b) XLA attention at true d=64, and
    (c) if FLAXDIFF_FLASH_NATIVE_D works on this backend, the kernel at
    native d=64. Quantifies VERDICT r2 weak #2's padding concern."""
    _stage_init()
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        return {"platform": jax.devices()[0].platform,
                "skipped": "flash kernel needs TPU"}

    B, L, H, D = 8, 1024, 8, 64   # flagship 32x32-latent level shape
    q = jax.random.normal(jax.random.PRNGKey(0), (B, L, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, L, H, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, L, H, D), jnp.bfloat16)

    res = {"platform": "tpu", "shape": [B, L, H, D],
           # record the block env the cells run under (flashtune's
           # exported winner) so the native-vs-padded delta is
           # attributable to the head-dim choice alone
           "block_env": {"q": os.environ.get("FLAXDIFF_FLASH_BLOCK_Q"),
                         "k": os.environ.get("FLAXDIFF_FLASH_BLOCK_K")}}
    # this stage OWNS the native-d toggle: flashtune's exported winner
    # may carry NATIVE_D=1, which would make the "padded" run silently
    # measure the native kernel and zero out the very comparison this
    # stage exists to make
    os.environ.pop("FLAXDIFF_FLASH_NATIVE_D", None)
    # the per-shape autotuner cache could also flip native-d under this
    # stage's feet — same ownership rule as the env toggle above
    os.environ.pop("FLAXDIFF_FLASH_TUNE_CACHE", None)
    from flaxdiff_tpu.ops import autotune as _autotune
    _autotune.deactivate()
    res["flash_padded_ms"] = round(chained_grad_ms("flash", q, k, v), 3)
    res["xla_d64_ms"] = round(chained_grad_ms("xla", q, k, v), 3)
    try:
        os.environ["FLAXDIFF_FLASH_NATIVE_D"] = "1"
        res["flash_native_d64_ms"] = round(
            chained_grad_ms("flash", q, k, v), 3)
    except Exception as e:
        res["flash_native_d64_ms"] = None
        res["flash_native_error"] = traceback.format_exc()[-400:]
    finally:
        os.environ.pop("FLAXDIFF_FLASH_NATIVE_D", None)
    log(f"attnpad: {res}")
    return res


def chained_grad_ms(backend: str, q0, k, v, iters: int = 30) -> float:
    """Time one attention fwd+bwd via jit(grad) with the chained-dq /
    scalar-readback harness, now factored into
    flaxdiff_tpu/ops/autotune.py (the autotuner probes with the SAME
    harness, so bench numbers and tuner decisions cannot drift). This
    wrapper keeps the bench's backend-string interface for the
    flashtune/attnpad/longseq stages."""
    import jax

    from flaxdiff_tpu.ops.attention import dot_product_attention
    from flaxdiff_tpu.ops.autotune import chained_grad_ms as _chained

    def loss(q, k, v):
        return dot_product_attention(q, k, v, backend=backend).sum()
    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return _chained(lambda q, k, v: g(q, k, v)[0], q0, k, v, iters)


def stage_epilogue(args) -> dict:
    """Fused vs unfused transformer-epilogue micro-bench
    (ops/fused_adaln.py): the AdaLN dual-view LayerNorm+modulate, the
    gated residual, and the GEGLU activation, each timed fwd+bwd with
    the chained-grad harness, plus an analytic estimate of the HBM
    bytes each variant moves (the fused ops exist to cut activation
    round trips, so the bytes model IS the claim being measured).

    Runs on CPU too: the fused dispatch falls back to XLA off-TPU, so
    the cpu ratio is ~1.0 by construction — recorded as harness
    evidence (`fused_is_xla_fallback`), never passed off as a kernel
    win. On TPU the fused cells run the real Pallas kernels
    (force_pallas), the unfused cells the exact XLA composition."""
    _stage_init()
    import jax
    import jax.numpy as jnp

    from flaxdiff_tpu.ops import fused_adaln as fa
    from flaxdiff_tpu.ops.autotune import chained_grad_ms as _chained

    cpu = jax.devices()[0].platform == "cpu"
    on_tpu = not cpu
    if cpu or args.quick:
        B, L, C, iters = 2, 256, 128, 5
        dt = jnp.float32
    else:
        B, L, C, iters = 8, 1024, 768, 30
        dt = jnp.bfloat16
    F = C * 4
    bpe = jnp.dtype(dt).itemsize
    key = jax.random.PRNGKey
    x = jax.random.normal(key(0), (B, L, C), dt)
    s1 = jax.random.normal(key(1), (B, 1, C), dt) * 0.1
    b1 = jax.random.normal(key(2), (B, 1, C), dt) * 0.1
    s2 = jax.random.normal(key(3), (B, 1, C), dt) * 0.1
    b2 = jax.random.normal(key(4), (B, 1, C), dt) * 0.1
    gate = jax.random.normal(key(5), (B, 1, C), dt) * 0.1
    h = jax.random.normal(key(6), (B, L, C), dt)
    proj = jax.random.normal(key(7), (B, L, 2 * F), dt)

    def timed(fn, x0, *rest):
        """fwd+bwd wrt the chained first operand (dx feeds the next x,
        so nothing elides) — the flashtune harness, on epilogues."""
        g = jax.jit(jax.grad(
            lambda a, *r: fn(a, *r).astype(jnp.float32).sum()))
        return round(_chained(lambda a, k_, v_: g(a, *rest), x0, None,
                              None, iters=iters), 3)

    blc = B * L * C * bpe
    configs = {
        # (fused fn, unfused fn, chained operand, extra args,
        #  est bytes fused, est bytes unfused)
        "adaln_dual": (
            lambda a, *r: sum(fa.fused_ln_modulate2(
                a, *r, 1e-5, False, on_tpu)),
            lambda a, *r: sum(fa._xla_ln_modulate(
                a, ((r[0], r[1]), (r[2], r[3])), 1e-5)),
            x, (s1, b1, s2, b2),
            # fused: read x, write 2 views (+[B,L,1] stats)
            3 * blc,
            # unfused: read x, write norm, read norm x2, write 2 views
            6 * blc),
        "gate_residual": (
            lambda a, *r: fa.fused_gate_residual(a, r[0], r[1],
                                                 False, on_tpu),
            lambda a, *r: a + r[0] * r[1],
            x, (gate, h),
            3 * blc, 3 * blc),
        "geglu": (
            lambda a: fa.fused_geglu(a, False, on_tpu),
            fa._xla_geglu,
            proj, (),
            3 * B * L * F * bpe, 3 * B * L * F * bpe),
    }
    res = {"platform": jax.devices()[0].platform,
           "shape": [B, L, C], "dtype": str(jnp.dtype(dt)),
           "fused_is_xla_fallback": not on_tpu,
           "configs": {}}
    for name, (fused_fn, plain_fn, x0, rest, est_f, est_u) in \
            configs.items():
        cell = {"est_hbm_mb_fused": round(est_f / 2 ** 20, 2),
                "est_hbm_mb_unfused": round(est_u / 2 ** 20, 2)}
        for label, fn in (("fused_ms", fused_fn),
                          ("unfused_ms", plain_fn)):
            try:
                cell[label] = timed(fn, x0, *rest)
            except Exception:
                cell[label] = None
                cell[label.replace("_ms", "_error")] = \
                    traceback.format_exc()[-300:]
        if cell.get("fused_ms") and cell.get("unfused_ms"):
            cell["ratio_fused_over_unfused"] = round(
                cell["fused_ms"] / cell["unfused_ms"], 3)
        res["configs"][name] = cell
        log(f"epilogue {name}: {cell}")
        print(json.dumps(res), flush=True)   # salvage point
    return res


def stage_flashtune(args) -> dict:
    """On-chip flash-kernel block-size sweep (runs FIRST; the winner is
    exported to every later stage via FLAXDIFF_FLASH_BLOCK_Q/K and
    FLAXDIFF_FLASH_NATIVE_D).

    The r3 trace showed the kernel at ~7% in-step MFU with the old
    128x128 blocks — per-program overhead dominated. Rather than bake a
    guess, measure fwd+bwd on the flagship attention shape for a ladder
    of block shapes (and native-d64 vs padded on the winner) and let the
    rest of the bench run with the best combination."""
    _stage_init()
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        return {"platform": jax.devices()[0].platform,
                "skipped": "flash kernel needs TPU"}

    B, L, H, D = 8, 1024, 8, 64   # flagship 32x32-latent level shape
    q0 = jax.random.normal(jax.random.PRNGKey(0), (B, L, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, L, H, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, L, H, D), jnp.bfloat16)

    def timed(bq, bk, native):
        os.environ["FLAXDIFF_FLASH_BLOCK_Q"] = str(bq)
        os.environ["FLAXDIFF_FLASH_BLOCK_K"] = str(bk)
        if native:
            os.environ["FLAXDIFF_FLASH_NATIVE_D"] = "1"
        else:
            os.environ.pop("FLAXDIFF_FLASH_NATIVE_D", None)
        return chained_grad_ms("flash", q0, k, v)

    combos = [(128, 128), (256, 512), (512, 512), (512, 1024),
              (1024, 1024)]
    results = {}
    for bq, bk in combos:
        try:
            results[f"{bq}x{bk}"] = round(timed(bq, bk, native=False), 3)
        except Exception:
            results[f"{bq}x{bk}"] = traceback.format_exc()[-300:]
        log(f"flashtune {bq}x{bk}: {results[f'{bq}x{bk}']}")
    numeric = {kk: vv for kk, vv in results.items()
               if isinstance(vv, float)}
    if not numeric:
        return {"platform": "tpu", "shape": [B, L, H, D],
                "results_ms": results,
                "skipped": "every combo failed"}
    best_key = min(numeric, key=numeric.get)
    bq, bk = (int(x) for x in best_key.split("x"))
    best = {"block_q": bq, "block_k": bk, "native_d": 0,
            "ms": numeric[best_key]}
    try:
        native_ms = round(timed(bq, bk, native=True), 3)
        results[f"{best_key}+native_d"] = native_ms
        log(f"flashtune {best_key}+native_d: {native_ms}")
        if native_ms < best["ms"]:
            best.update(native_d=1, ms=native_ms)
    except Exception:
        results[f"{best_key}+native_d"] = traceback.format_exc()[-300:]

    # Head-to-head vs JAX's prebuilt TPU kernel — the exact kernel the
    # reference calls (reference flaxdiff/models/attention.py:100-102).
    # Same chained-grad harness, so differences are kernel differences.
    # Run at the tuned winner env (firstparty side) vs the prebuilt
    # wrapper's own 512x1024 default.
    os.environ["FLAXDIFF_FLASH_BLOCK_Q"] = str(best["block_q"])
    os.environ["FLAXDIFF_FLASH_BLOCK_K"] = str(best["block_k"])
    if best["native_d"]:
        os.environ["FLAXDIFF_FLASH_NATIVE_D"] = "1"
    else:
        os.environ.pop("FLAXDIFF_FLASH_NATIVE_D", None)
    key_all = jax.random.PRNGKey
    h2h_shapes = {
        "self_l1024": ((B, L, H, D), (B, L, H, D)),
        "self_l4096": ((2, 4096, H, D), (2, 4096, H, D)),
        "cross_kv77": ((B, L, H, D), (B, 77, H, D)),
        "self_l16384": ((1, 16384, 8, 64), (1, 16384, 8, 64)),
    }
    # the prebuilt backend warn-falls-back to XLA when the kernel can't
    # run — an XLA number must never be recorded under the prebuilt
    # label (it could even flip best["impl"])
    from flaxdiff_tpu.ops.attention import attention_backend_available
    prebuilt_ok = attention_backend_available("prebuilt")
    h2h = {}
    for name, (qs, kvs) in h2h_shapes.items():
        qh = jax.random.normal(key_all(3), qs, jnp.bfloat16)
        kh = jax.random.normal(key_all(4), kvs, jnp.bfloat16)
        vh = jax.random.normal(key_all(5), kvs, jnp.bfloat16)
        cell = {}
        for impl, be in (("firstparty", "flash"), ("prebuilt", "prebuilt")):
            if be == "prebuilt" and not prebuilt_ok:
                cell[impl] = "skipped: prebuilt kernel unavailable"
                continue
            try:
                cell[impl] = round(chained_grad_ms(be, qh, kh, vh,
                                                   iters=20), 3)
            except Exception:
                cell[impl] = traceback.format_exc()[-300:]
            log(f"flashtune h2h {name} {impl}: {cell[impl]}")
        if all(isinstance(cell.get(i), float)
               for i in ("firstparty", "prebuilt")):
            cell["ratio_fp_over_pb"] = round(
                cell["firstparty"] / cell["prebuilt"], 3)
        h2h[name] = cell
    # RECORD which impl wins the flagship shape (best["impl"]). This is
    # deliberately not exported to later stages (export_winner_env):
    # the ablate stage measures the impl in-context as its own explicit
    # attn=prebuilt cell, and production opt-in is the operator setting
    # FLAXDIFF_FLASH_IMPL=prebuilt ("auto" dispatch then routes to it;
    # explicit backend="flash" stays first-party).
    flag = h2h.get("self_l1024", {})
    if (isinstance(flag.get("prebuilt"), float)
            and isinstance(flag.get("firstparty"), float)
            and flag["prebuilt"] < flag["firstparty"]):
        best["impl"] = "prebuilt"
        best["ms_prebuilt"] = flag["prebuilt"]
    else:
        best["impl"] = "firstparty"
    out = {"platform": "tpu", "shape": [B, L, H, D],
           "results_ms": results, "head_to_head_ms": h2h, "best": best}
    # Persist the flagship winner into the per-shape autotuner cache
    # (ops/autotune.py): later tuned stages — and any training run
    # pointed at the same dir — pick the plan up per shape instead of
    # via the global env pair. The ladder results ride along as
    # evidence.
    try:
        from flaxdiff_tpu.ops.autotune import FlashAutotuner
        cache_dir = os.environ.get("FLAXDIFF_FLASH_TUNE_CACHE",
                                   "flash_tune_cache")
        aut = FlashAutotuner(cache_dir=cache_dir)
        aut.record(L, L, D, "bfloat16", best["block_q"], best["block_k"],
                   best.get("native_d", 0), ms=best["ms"],
                   probed_ms={kk: vv for kk, vv in results.items()
                              if isinstance(vv, float)})
        aut.save()
        out["autotune_cache"] = cache_dir
    except Exception:
        out["autotune_cache_error"] = traceback.format_exc()[-300:]
    return out


def stage_ablate(args) -> dict:
    """In-context kernel ablation at the headline batch: flash vs XLA
    attention x pallas vs XLA GroupNorm+SiLU, full train step.

    Micro-benches (flashtune/attnpad) time kernels in isolation; this
    stage answers the question that actually matters — do the custom
    kernels beat XLA *inside the compiled train step*, where the r3
    trace showed ~750 layout copies/step clustered around the pallas
    custom calls. If an XLA variant wins here, that is the next round's
    default."""
    _stage_init()
    import jax

    if jax.devices()[0].platform != "tpu":
        return {"platform": jax.devices()[0].platform,
                "skipped": "kernel ablation needs TPU"}

    timed = 20
    # ablate at the sweep's winning batch (the orchestrator exports it —
    # kernel-vs-XLA tradeoffs like layout-copy overhead scale with
    # batch, so measuring at a different batch than the headline would
    # answer the wrong question); standalone runs default to baseline
    batch = int(os.environ.get("FLAXDIFF_BENCH_ABLATE_BATCH",
                               BASELINE_BATCH))
    res = {"platform": "tpu", "batch": batch,
           "image_size": IMAGE_SIZE, "configs": {}}
    for attn_backend in ("flash", "xla"):
        for norm in ("pallas", "xla"):
            key = f"attn={attn_backend},norm={norm}"
            if norm == "xla":
                os.environ["FLAXDIFF_FUSED_NORM"] = "xla"
            else:
                os.environ.pop("FLAXDIFF_FUSED_NORM", None)
            try:
                trainer = build_trainer(tpu_native=True,
                                        attn_backend=attn_backend)
                ips, step_time, _ = run(
                    trainer, make_batches(batch), batch,
                    sync_every_step=False, timed_steps=timed)
                res["configs"][key] = {
                    "imgs_per_sec_per_chip": round(ips, 3),
                    "step_time_ms": round(step_time * 1e3, 2)}
                del trainer
            except Exception as e:
                res["configs"][key] = {
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-600:]}
            log(f"ablate {key}: {res['configs'][key]}")
            print(json.dumps(res), flush=True)   # salvage point
    os.environ.pop("FLAXDIFF_FUSED_NORM", None)
    # optimizer-path configs at default kernels: flat_opt fuses only the
    # optax transform (EMA + apply_updates stay leaf-wise); flat_params
    # flattens the WHOLE state so optimizer+EMA+apply are per-dtype
    # fused and grads arrive flat (the r3 trace's ~10 ms / 327-kernel
    # leaf-wise-update budget, measured in-context)
    for key, kwargs, env_add in (
            # fused-epilogue A/B in-context (the flagship UNet's GEGLU
            # FF rides ops/fused_adaln.py on TPU by default; =xla
            # restores the unfused composition — mirrors norm=xla)
            ("attn=flash,norm=pallas,adaln=xla", {},
             {"FLAXDIFF_FUSED_ADALN": "xla"}),
            ("attn=flash,norm=pallas,opt=flat", dict(flat_opt=True), {}),
            ("attn=flash,norm=pallas,opt=flatparams",
             dict(flat_params=True), {}),
            # BHLD layout: head permutation folded into the projections,
            # free reshapes into the kernel's native [B*H,L,D] grid —
            # measures the r3 trace's ~750 layout-copy claim in-context
            ("attn=flash,norm=pallas,layout=bhld", {},
             {"FLAXDIFF_ATTN_BHLD": "1"}),
            # both optimizations at once — the expected next default if
            # each wins alone
            ("attn=flash,norm=pallas,opt=flatparams,layout=bhld",
             dict(flat_params=True), {"FLAXDIFF_ATTN_BHLD": "1"}),
            # JAX's prebuilt TPU flash kernel in-context (the kernel the
            # reference calls) — the train-step complement to
            # flashtune's micro head-to-head (VERDICT r4 #2)
            ("attn=prebuilt,norm=pallas", dict(attn_backend="prebuilt"),
             {})):
        try:
            for ek, ev in env_add.items():
                os.environ[ek] = ev
            if kwargs.get("attn_backend") == "prebuilt":
                # dispatch would silently fall back to XLA where the
                # prebuilt kernel can't run (kernel unimportable /
                # multi-device mesh) — record a skip instead of a
                # mislabeled number. Mirrors _prebuilt_usable, whose
                # mesh check happens too late to consult here.
                import jax as _jax
                from flaxdiff_tpu.ops.attention import (
                    attention_backend_available)
                if (len(_jax.devices()) > 1
                        or not attention_backend_available("prebuilt")):
                    res["configs"][key] = {
                        "skipped": "prebuilt cell needs a single-device "
                                   "TPU + importable prebuilt kernel "
                                   f"(n_dev={len(_jax.devices())})"}
                    log(f"ablate {key}: {res['configs'][key]}")
                    continue
            trainer = build_trainer(tpu_native=True, **kwargs)
            ips, step_time, _ = run(trainer, make_batches(batch), batch,
                                    sync_every_step=False,
                                    timed_steps=timed)
            res["configs"][key] = {
                "imgs_per_sec_per_chip": round(ips, 3),
                "step_time_ms": round(step_time * 1e3, 2)}
        except Exception as e:
            res["configs"][key] = {
                "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-600:]}
        finally:
            # a failed config's state must not shrink the next cell's
            # memory frontier
            try:
                del trainer
            except UnboundLocalError:
                pass
            for ek in env_add:
                os.environ.pop(ek, None)
        log(f"ablate {key}: {res['configs'][key]}")
        print(json.dumps(res), flush=True)   # salvage point
    ok = {kk: vv for kk, vv in res["configs"].items()
          if "imgs_per_sec_per_chip" in vv}
    if ok:
        res["best"] = max(ok, key=lambda kk: ok[kk]["imgs_per_sec_per_chip"])
    return res


def stage_dispatch(args) -> dict:
    """Step-loop overhead: the r5 sync-free pipelined fit() measured at
    pipeline_depth 1/2/4 with telemetry off / on(sample_every=1) /
    on(sample_every=8).

    Uses a deliberately TINY model so the number is dominated by loop
    mechanics (dispatch, loss-window bookkeeping, phase timing, the
    telemetry sync policy), not model compute — the regime where a
    per-step host sync is the whole cost. The acceptance bar: telemetry-on (sampled) step time within
    2% of telemetry-off at depth 2. Each cell times fit() itself (the
    production loop), after a warm fit so compile stays out of the
    window. log_every is 50 — the production cadence floor — so the
    per-window work (loss fetch, export, goodput persist, pod gather)
    carries a REPRESENTATIVE amortized share: on a ~2 ms toy step,
    log_every=10 would charge window work 5-10x the share it has on
    any real run (where steps are 50-1000x longer and cadences 50+),
    and the cell would measure logging configuration, not the loop."""
    _stage_init()
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import flax.linen as nn
    from flaxdiff_tpu import telemetry as T
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    cpu = jax.devices()[0].platform == "cpu"
    steps = 150 if (cpu or args.quick) else 300

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, t, cond=None):
            h = nn.Conv(16, (3, 3))(x)
            return nn.Conv(x.shape[-1], (3, 3))(jnp.tanh(h))

    model = Tiny()

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, 16, 16, 1)),
                          jnp.zeros((1,)))["params"]

    mesh = create_mesh(axes={"data": -1})
    rng = np.random.default_rng(0)
    batches = [{"sample": rng.normal(size=(8, 16, 16, 1))
                .astype(np.float32)} for _ in range(4)]

    def data():
        i = 0
        while True:
            yield batches[i % len(batches)]
            i += 1

    def timed_fit(depth: int, sample_every: int, telemetry_on: bool,
                  repeats: int = 3):
        """Median step time over `repeats` timed fits (one stall — GC,
        another process on a shared CPU box — must not become the
        recorded cell)."""
        trainer = DiffusionTrainer(
            apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
            schedule=CosineNoiseSchedule(timesteps=100),
            transform=EpsilonPredictionTransform(), mesh=mesh,
            config=TrainerConfig(normalize=False, log_every=50,
                                 pipeline_depth=depth,
                                 telemetry_sample_every=sample_every))
        trainer.fit(data(), total_steps=5)      # compile out of band
        tmp = None
        if telemetry_on:
            tmp = tempfile.mkdtemp(prefix="bench_dispatch_tel_")
            trainer.telemetry = T.Telemetry.create(tmp)
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            trainer.fit(data(), total_steps=steps)
            times.append(time.perf_counter() - t0)
        if trainer.telemetry is not None:
            trainer.telemetry.close()
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
        del trainer
        return sorted(times)[len(times) // 2] / steps

    res = {"platform": jax.devices()[0].platform, "steps": steps,
           "configs": {}}
    for depth in (1, 2, 4):
        for key, kwargs in (
                ("tel_off", dict(sample_every=1, telemetry_on=False)),
                ("tel_on_s1", dict(sample_every=1, telemetry_on=True)),
                ("tel_on_s8", dict(sample_every=8, telemetry_on=True))):
            name = f"depth{depth}/{key}"
            try:
                st = timed_fit(depth, **kwargs)
                res["configs"][name] = {"step_time_ms": round(st * 1e3, 3)}
                log(f"dispatch {name}: {st * 1e3:.3f} ms/step")
            except Exception:
                res["configs"][name] = {
                    "error": traceback.format_exc()[-400:]}
                log(f"dispatch {name}: FAILED")
        print(json.dumps(res), flush=True)   # salvage point per depth
    off = res["configs"].get("depth2/tel_off", {}).get("step_time_ms")
    s8 = res["configs"].get("depth2/tel_on_s8", {}).get("step_time_ms")
    s1 = res["configs"].get("depth2/tel_on_s1", {}).get("step_time_ms")
    if off and s8:
        # the acceptance ratio: sampled telemetry must be ~free
        res["telemetry_sampled_overhead_depth2"] = round(s8 / off - 1, 4)
    if off and s1:
        res["telemetry_exact_overhead_depth2"] = round(s1 / off - 1, 4)
    return res


def stage_devprof(args) -> dict:
    """ISSUE 19 acceptance: a cadence-triggered profile window during a
    real fit parses into a devprof.jsonl row whose op families sum to
    the profiled device total, joins its program-registry row (measured
    MFU + predicted-vs-measured comm), and the write-back annotation
    lands in programs.jsonl — the automated path behind the old
    hand-run scripts/analyze_trace.py workflow."""
    _stage_init()
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import flax.linen as nn
    from flaxdiff_tpu import telemetry as T
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    cpu = jax.devices()[0].platform == "cpu"
    if cpu and not os.environ.get("FLAXDIFF_PEAK_FLOPS"):
        # the CPU backend has no entry in the peak-FLOPs table: pin a
        # nominal 1 TFLOP/s so measured MFU is populated (the number is
        # labeled platform=cpu; only the JOIN is under test here)
        os.environ["FLAXDIFF_PEAK_FLOPS"] = "1e12"

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, t, cond=None):
            h = nn.Conv(16, (3, 3))(x)
            return nn.Conv(x.shape[-1], (3, 3))(jnp.tanh(h))

    model = Tiny()

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, 16, 16, 1)),
                          jnp.zeros((1,)))["params"]

    mesh = create_mesh(axes={"data": -1})
    rng = np.random.default_rng(0)
    batches = [{"sample": rng.normal(size=(8, 16, 16, 1))
                .astype(np.float32)} for _ in range(4)]

    def data():
        i = 0
        while True:
            yield batches[i % len(batches)]
            i += 1

    tmp = tempfile.mkdtemp(prefix="bench_devprof_")
    res = {"platform": jax.devices()[0].platform}
    try:
        trainer = DiffusionTrainer(
            apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
            schedule=CosineNoiseSchedule(timesteps=100),
            transform=EpsilonPredictionTransform(), mesh=mesh,
            config=TrainerConfig(normalize=False, log_every=8,
                                 pipeline_depth=2,
                                 telemetry_sample_every=1,
                                 profile_cadence=16, profile_steps=4))
        trainer.telemetry = T.Telemetry.create(tmp)
        trainer.fit(data(), total_steps=40)
        trainer.telemetry.close()
        rows = T.read_devprof(os.path.join(tmp, T.DEVPROF_FILENAME))
        ok_rows = [r for r in rows if r.get("status") == "ok"]
        res["windows"] = len(rows)
        res["parsed"] = len(ok_rows)
        if not rows:
            res["error"] = "no profile window captured"
            return res
        last = ok_rows[-1] if ok_rows else rows[-1]
        res["window"] = {k: last.get(k) for k in (
            "status", "source", "step", "steps",
            "device_ms_per_step", "collective_ms", "compute_ms",
            "layout_copy_ms", "fusion_gap_ms", "measured_mfu",
            "roofline_verdict", "comm_predicted_bytes",
            "comm_measured_ms")}
        fam_ms = sum(float(f.get("ms", 0.0))
                     for f in (last.get("families") or {}).values()
                     if isinstance(f, dict))
        tot = float(last.get("device_total_ms") or 0.0)
        res["families_sum_ms"] = round(fam_ms, 3)
        res["device_total_ms"] = round(tot, 3)
        # the parser invariant the evidence rests on: leaf op families
        # tile the profiled device total (±1%)
        res["families_cover_total"] = bool(
            tot and abs(fam_ms - tot) <= 0.01 * tot)
        annotated = [r for r in T.read_registry(
                         os.path.join(tmp, "programs.jsonl"))
                     if r.get("measured_mfu") is not None]
        res["registry_annotated"] = len(annotated)
        log(f"devprof: {len(rows)} window(s), {len(ok_rows)} parsed, "
            f"{len(annotated)} registry row(s) annotated")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return res


def stage_plan(args) -> dict:
    """ISSUE 20 acceptance: the measurement-driven parallelism planner
    runs its full loop on a forced 8-way CPU mesh with a real tiny
    SimpleDiT — enumerate the factorization x rule-table space, prune
    on coverage + the HBM envelope, rank by the comm-proxy byte bill,
    probe the shortlist through the REAL DiffusionTrainer dispatch
    path (timed short fits under each candidate mesh + rule table),
    land the decision in the program registry, then re-plan on the
    warm cache and show ZERO probes."""
    # the search space needs devices to factor over; on hosts without
    # accelerators the cpu backend defaults to 1 device
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    _stage_init()
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from flaxdiff_tpu import telemetry as T
    from flaxdiff_tpu.models.dit import SimpleDiT
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.parallel.planner import (CandidatePlan,
                                               ParallelPlanner,
                                               evaluate_candidate)
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    model = SimpleDiT(output_channels=1, patch_size=2, emb_features=32,
                      num_layers=2, num_heads=2, backend="xla")

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, 16, 16, 1)),
                          jnp.zeros((1,)), None)["params"]

    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    total = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(shapes))
    devices = list(jax.devices())
    batch_shape = (8, 16, 16, 1)
    # tiny model, tiny thresholds: leaves are far below the production
    # 64 KiB partition floor, and a ~3x-params budget forces the HBM
    # prune branch to actually fire
    min_size, hbm_budget = 2 ** 8, total * 3.0

    rng = np.random.default_rng(0)
    batches = [{"sample": rng.normal(size=batch_shape)
                .astype(np.float32)} for _ in range(2)]

    def data():
        i = 0
        while True:
            yield batches[i % len(batches)]
            i += 1

    probe_log = []

    def probe(ev):
        # the dispatch-path probe: a real trainer under the candidate's
        # mesh + rule table, one fit to compile, a short timed fit after
        mesh = create_mesh(axes=dict(ev.axes), devices=devices)
        trainer = DiffusionTrainer(
            apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
            schedule=CosineNoiseSchedule(timesteps=100),
            transform=EpsilonPredictionTransform(), mesh=mesh,
            partition_rules=ev.rules,
            config=TrainerConfig(normalize=False, log_every=50))
        trainer.fit(data(), total_steps=1)
        steps = 3
        t0 = time.perf_counter()
        trainer.fit(data(), total_steps=steps)
        ms = (time.perf_counter() - t0) / steps * 1e3
        probe_log.append({"plan": ev.name, "ms": round(ms, 3)})
        return ms

    tmp = tempfile.mkdtemp(prefix="bench_plan_")
    res = {"platform": jax.devices()[0].platform,
           "devices": len(devices)}
    try:
        tele = T.Telemetry.create(tmp)
        planner = ParallelPlanner(cache_dir=tmp, probe_fn=probe,
                                  metrics=tele, min_size=min_size)
        decision = planner.plan(shapes, devices=devices,
                                batch_shape=batch_shape,
                                hbm_bytes=hbm_budget)
        planner.commit(tele.programs, decision)
        tele.close()

        res.update({
            "chosen": decision.name, "candidates": decision.candidates,
            "pruned_unmatched": decision.pruned_unmatched,
            "pruned_hbm": decision.pruned_hbm,
            "pruned_comm": decision.pruned_comm,
            "probes_cold": planner.probe_count,
            "shortlist": list(decision.shortlist),
            "probe_ms": decision.probe_ms,
            "comm_bytes": decision.comm_bytes,
            "comm_bytes_by_axis": dict(decision.comm_bytes_by_axis),
            "hbm_estimate_bytes": decision.hbm_estimate_bytes,
            "probe_log": probe_log})

        # the hand-tuned default a planner must at least match: the
        # data2 x fsdp2 x tensor2 cube on the inferred rule table
        base = evaluate_candidate(
            CandidatePlan(axes=(("data", 2), ("fsdp", 2), ("tensor", 2)),
                          table="inferred"),
            shapes, devices, min_size=min_size, batch_shape=batch_shape)
        if base is not None:
            res["baseline_comm_bytes"] = base.comm_bytes
            res["beats_baseline"] = bool(
                decision.comm_bytes <= base.comm_bytes)

        # warm-cache contract: a fresh planner over the same cache dir
        # must return the SAME plan without invoking probe_fn at all
        warm = ParallelPlanner(cache_dir=tmp, probe_fn=probe,
                               min_size=min_size)
        warm_decision = warm.plan(shapes, devices=devices,
                                  batch_shape=batch_shape,
                                  hbm_bytes=hbm_budget)
        res["warm_cache_hit"] = bool(warm_decision.cache_hit)
        res["probes_warm"] = warm.probe_count
        res["warm_same_plan"] = bool(warm_decision.name == decision.name)

        rows = [r for r in T.read_registry(os.path.join(tmp,
                                                        "programs.jsonl"))
                if r.get("kind") == "plan"]
        res["registry_rows"] = len(rows)
        res["registry_annotated"] = sum(
            1 for r in rows if r.get("plan_chosen"))
        log(f"plan: {decision.candidates} candidates, pruned "
            f"{decision.pruned_unmatched}/{decision.pruned_hbm}"
            f"/{decision.pruned_comm} (unmatched/hbm/comm), "
            f"{planner.probe_count} cold probes -> {decision.name}; "
            f"warm hit={res['warm_cache_hit']} "
            f"probes={res['probes_warm']}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return res


def stage_data_chaos(args) -> dict:
    """ISSUE 17 acceptance: the deterministic data plane under REAL
    injected corruption + a step.nan rollback, measured end to end.

    Builds a packed-record shard with genuinely corrupted record bytes
    (corruption that persists across replay — every decode of those
    records fails forever, so the reference stream and the chaos
    stream see the SAME placeholders), then runs a tiny fit through
    `DataPlane` with a step.nan fault forcing an anomaly rollback
    mid-run. Acceptance, all computed here:

      bit_identical        — every batch the plane served (including
                             re-served post-rollback batches) matches
                             the uninterrupted reference digest at its
                             index, and at least one index was served
                             twice (the rollback actually replayed);
      quarantine_accounted — the journal's record set equals the
                             injected-corruption set exactly;
      stranded_batches     — served indices are gap-free (no batch
                             dropped or served out of order across the
                             prefetcher teardown/rebuild);
      leaked_threads       — no live prefetch worker after fit;
      new_host_syncs       — the four counting-mock sync seams
                             (trainer._block_until_ready/_fetch_losses/
                             _fetch_ring/_fetch_gate_events) called
                             EXACTLY as often as an identical control
                             fit without the data plane — the plane
                             adds zero device syncs (docs/DATA.md
                             "Zero host syncs, by lint")."""
    _stage_init()
    import shutil
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import flax.linen as nn
    from flaxdiff_tpu import resilience as R
    from flaxdiff_tpu.data import DataPlane, QuarantineJournal
    from flaxdiff_tpu.data.dataplane import batch_digest
    from flaxdiff_tpu.data.packed_records import PackedRecordWriter
    from flaxdiff_tpu.data.sharded_source import ShardedPackedRecordSource
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import (Checkpointer, DiffusionTrainer,
                                      TrainerConfig)
    from flaxdiff_tpu.trainer import trainer as trainer_mod

    import cv2

    n_records, batch, size = 64, 8, 16
    corrupt = {5, 17, 40}
    total_steps, save_every, nan_at = 24, 8, 13
    work = tempfile.mkdtemp(prefix="bench_data_chaos_")
    res = {"platform": jax.devices()[0].platform,
           "total_steps": total_steps, "injected": sorted(corrupt)}
    try:
        # -- shard with REAL corruption (replays identically forever) --
        shard = os.path.join(work, "chaos.pr")
        rng = np.random.default_rng(7)
        with PackedRecordWriter(shard) as w:
            for i in range(n_records):
                if i in corrupt:
                    # undecodable image payload: cv2.imdecode -> None ->
                    # ValueError -> quarantine, on EVERY decode
                    w.write({"image": b"\xde\xad\xbe\xef" * 8,
                             "caption": f"torn {i}".encode()})
                    continue
                img = rng.integers(0, 255, (size, size, 3), dtype=np.uint8)
                ok, enc = cv2.imencode(".png", img)
                assert ok
                w.write({"image": enc.tobytes(),
                         "caption": f"img {i}".encode()})

        def make_factory(journal):
            src = ShardedPackedRecordSource(
                shards=[shard], quarantine=journal,
                placeholder_size=size).get_source()

            def factory(seed):
                def gen():
                    epoch = 0
                    while True:
                        order = np.random.default_rng(
                            seed + epoch).permutation(len(src))
                        for s in range(0, len(src) - batch + 1, batch):
                            imgs = [src[int(j)]["image"]
                                    for j in order[s:s + batch]]
                            x = (np.stack(imgs).astype(np.float32)
                                 / 127.5) - 1.0
                            yield {"sample": x}
                        epoch += 1
                return gen()
            return factory

        # -- uninterrupted reference digests ---------------------------
        ref_it = make_factory(QuarantineJournal())(0)
        reference = [batch_digest(next(ref_it)) for _ in range(64)]

        class Tiny(nn.Module):
            @nn.compact
            def __call__(self, x, t, cond=None):
                h = nn.Conv(8, (3, 3))(x)
                return nn.Conv(x.shape[-1], (3, 3))(jnp.tanh(h))

        model = Tiny()

        def apply_fn(params, x, t, cond):
            return model.apply({"params": params}, x, t, None)

        def init_fn(key):
            return model.init(key, jnp.zeros((1, size, size, 3)),
                              jnp.zeros((1,)))["params"]

        mesh = create_mesh(axes={"data": -1})

        def make_trainer(ckdir, ev):
            return DiffusionTrainer(
                apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
                schedule=CosineNoiseSchedule(timesteps=100),
                transform=EpsilonPredictionTransform(), mesh=mesh,
                config=TrainerConfig(normalize=False, log_every=2),
                # single-host ledger: commit semantics without a
                # coordinator, so data_state entries land beside commits
                checkpointer=Checkpointer(ckdir, event_log=ev,
                                          use_ledger=True))

        SEAMS = ("_block_until_ready", "_fetch_losses", "_fetch_ring",
                 "_fetch_gate_events")

        def counted_fit(with_plane: bool):
            counts = dict.fromkeys(SEAMS, 0)
            saved = {s: getattr(trainer_mod, s) for s in SEAMS}

            def wrap(name, fn):
                def inner(*a, **k):
                    counts[name] += 1
                    return fn(*a, **k)
                return inner
            for s in SEAMS:
                setattr(trainer_mod, s, wrap(s, saved[s]))
            ev = R.EventLog("bench")
            plan = R.FaultPlan([R.FaultSpec("step.nan", at=(nan_at,),
                                            error="flag", times=1)])
            served = []
            journal = QuarantineJournal()

            class RecordingPlane(DataPlane):
                def __next__(self):
                    idx = self.stream.cursor
                    b = super().__next__()
                    served.append((idx, self._digests[idx]))
                    return b

            ckdir = os.path.join(
                work, "ck_plane" if with_plane else "ck_ctrl")
            try:
                with R.use_event_log(ev), plan.installed():
                    trainer = make_trainer(ckdir, ev)
                    if with_plane:
                        plane = RecordingPlane(make_factory(journal),
                                               seed=0, journal=journal)
                        hist = trainer.fit(None, total_steps=total_steps,
                                           save_every=save_every,
                                           data_plane=plane)
                    else:
                        plane = None
                        hist = trainer.fit(
                            make_factory(journal)(0),
                            total_steps=total_steps,
                            save_every=save_every)
                trainer.checkpointer.wait_until_finished()
                ledger = trainer.checkpointer.ledger
                data_states = 0
                if plane is not None and ledger is not None:
                    data_states = sum(
                        1 for s in range(1, total_steps + 1)
                        if ledger.data_state_at(s) is not None and
                        ledger.data_state_at(s).get("cursor") == s)
                trainer.checkpointer.close()
            finally:
                for s in SEAMS:
                    setattr(trainer_mod, s, saved[s])
            return {"counts": counts, "served": served,
                    "journal": journal, "plane": plane, "hist": hist,
                    "rollbacks": ev.count("rollback", "train.step"),
                    "data_states": data_states}

        chaos = counted_fit(with_plane=True)
        control = counted_fit(with_plane=False)

        served = chaos["served"]
        mismatches = [(i, d) for i, d in served if reference[i] != d]
        replayed = [i for i in {i for i, _ in served}
                    if sum(1 for j, _ in served if j == i) > 1]
        idxs = sorted({i for i, _ in served})
        gap_free = idxs == list(range(len(idxs)))
        journaled = sorted(
            int(e["key"].split(":")[1])
            for e in chaos["journal"].entries())
        live = [t.name for t in threading.enumerate()
                if t.is_alive() and "flaxdiff-put-batch" in t.name]
        delta = {s: chaos["counts"][s] - control["counts"][s]
                 for s in SEAMS}

        res.update({
            "rollbacks": chaos["rollbacks"],
            "stream_rewinds": chaos["plane"].rewinds,
            "batches_served": len(served),
            "replayed_indices": len(replayed),
            "bit_identical": not mismatches and len(replayed) > 0,
            "digest_mismatches": mismatches[:8],
            "journaled": journaled,
            "quarantine_accounted": journaled == sorted(corrupt),
            "ledger_data_states": chaos["data_states"],
            "stranded_batches": 0 if gap_free else len(idxs),
            "leaked_threads": live,
            "host_syncs": {"with_plane": chaos["counts"],
                           "control": control["counts"],
                           "new": delta},
            "zero_new_host_syncs": all(v == 0 for v in delta.values()),
            "final_loss_finite": bool(
                np.isfinite(chaos["hist"]["final_loss"])),
        })
        res["accepted"] = bool(
            res["bit_identical"] and res["quarantine_accounted"]
            and res["stranded_batches"] == 0 and not live
            and res["zero_new_host_syncs"] and res["rollbacks"] >= 1
            and res["ledger_data_states"] >= 1)
        log(f"data_chaos: accepted={res['accepted']} "
            f"bit_identical={res['bit_identical']} "
            f"replayed={res['replayed_indices']} "
            f"quarantined={journaled} new_syncs={delta}")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return res


def stage_longseq(args) -> dict:
    """Long-context attention on hardware: flash fwd+bwd at 8k/16k/32k
    tokens, XLA attempted at the same shapes for contrast.

    The flash kernel's VMEM use is O(block) in sequence length while XLA
    attention materializes the [L, L] score matrix — at 16k tokens that
    is 1 GiB f32 per (batch, head) slice, so XLA is expected to fail
    where flash keeps running. This stage turns the long-context design
    claim (SURVEY aux: ring/sequence parallelism rests on the same
    blockwise kernel) into an on-chip number."""
    _stage_init()
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        return {"platform": jax.devices()[0].platform,
                "skipped": "needs TPU"}

    H, D = 8, 64
    res = {"platform": "tpu", "heads": H, "head_dim": D, "lengths": {}}
    # On-chip correctness FIRST (VERDICT r4 next #6: 16k correctness was
    # CPU-oracle/interpret-only): flash fwd at 16k tokens vs the XLA
    # oracle at the same shape, f32 inputs so the comparison measures
    # the kernel, not bf16 rounding. 16k XLA fwd-only fits (the [L,L]
    # f32 score slice is 1 GiB streamed, unlike fwd+bwd which also
    # stores probs for the backward).
    try:
        from flaxdiff_tpu.ops.attention import (_xla_attention,
                                                dot_product_attention)
        Lc = 16384
        qc = jax.random.normal(jax.random.PRNGKey(7), (1, Lc, 2, D),
                               jnp.float32)
        kc = jax.random.normal(jax.random.PRNGKey(8), (1, Lc, 2, D),
                               jnp.float32)
        vc = jax.random.normal(jax.random.PRNGKey(9), (1, Lc, 2, D),
                               jnp.float32)
        got = jax.jit(lambda a, b, c: dot_product_attention(
            a, b, c, backend="flash"))(qc, kc, vc)
        want = jax.jit(_xla_attention)(qc, kc, vc)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        res["correctness_16k"] = {"max_abs_err_vs_xla": err,
                                  "ok": bool(err < 5e-4),
                                  # smaller than the stage's 8-head
                                  # timing shapes — record the actual
                                  # validated shape, not the header's
                                  "shape": [1, Lc, 2, D], "dtype": "f32"}
        del qc, kc, vc, got, want
        log(f"longseq 16k correctness vs xla: {res['correctness_16k']}")
    except Exception:
        res["correctness_16k"] = {"error": traceback.format_exc()[-400:]}
    for L in (8192, 16384, 32768):
        q = jax.random.normal(jax.random.PRNGKey(0), (1, L, H, D),
                              jnp.bfloat16)
        k = jax.random.normal(jax.random.PRNGKey(1), (1, L, H, D),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(2), (1, L, H, D),
                              jnp.bfloat16)
        entry = {}
        for backend in ("flash", "xla"):
            try:
                entry[f"{backend}_ms"] = round(
                    chained_grad_ms(backend, q, k, v, iters=10), 3)
            except Exception as e:
                entry[f"{backend}_ms"] = None
                entry[f"{backend}_error"] = traceback.format_exc()[-400:]
        res["lengths"][str(L)] = entry
        log(f"longseq L={L}: {entry}")
        if entry.get("flash_ms") is None:
            break   # flash itself out of memory: longer L is pointless
    return res


def stage_diffcache(args) -> dict:
    """Training-free diffusion cache (ops/diffcache.py,
    docs/CACHING.md): device time + trajectory fidelity of the cached
    single-scan DDIM program across CachePlans on a DiT.

    For each plan the SAME noise/loop keys drive the full trajectory
    program, so `psnr_db` is the fidelity of the cached trajectory
    endpoint against the uncached one (pre-clip program outputs, PSNR
    over the uncached output's dynamic range — the untrained net
    saturates `clip_images`, which would fake perfect PSNR). The
    schedule is Karras-VE with karras spacing: on a VP schedule an
    untrained epsilon model explodes through the terminal `x/signal`
    amplification (~2e4 output scale), turning epsilon-level float
    noise into the whole PSNR signal; on VE (signal = 1) the
    trajectory stays bounded and the number measures the CACHE's
    error. Params are noise-perturbed after init because AdaLN-Zero
    blocks are exact identities at init (zero-init gates): the deep
    delta would be exactly zero and reuse would be trivially lossless.
    Acceptance (ISSUE 10): the default plan must show >= 1.8x device
    speedup at DDIM-50 with >= 30 dB trajectory PSNR; CPU numbers
    acceptable. The spatial axis (ISSUE 11): the composed
    spatial+timestep default plan must show >= 2.5x device speedup at
    >= 30 dB trajectory PSNR — the spatial top-k partial refresh on
    cached steps buys a sparser full-refresh cadence than the pure
    timestep default can afford at the same fidelity bar."""
    _stage_init()
    import jax
    import jax.numpy as jnp

    from flaxdiff_tpu.models.dit import SimpleDiT
    from flaxdiff_tpu.ops.diffcache import CachePlan, resolve_cache_fns
    from flaxdiff_tpu.ops.spatialcache import (DEFAULT_COMPOSED_PLAN,
                                               ComposedPlan, SpatialPlan,
                                               resolve_composed_fns,
                                               resolve_plan)
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.samplers import DDIMSampler, DiffusionSampler
    from flaxdiff_tpu.schedulers import KarrasVENoiseSchedule

    cpu = jax.devices()[0].platform == "cpu"
    if args.quick:
        image_size, patch, emb, layers, steps, repeats = 16, 4, 64, 8, 10, 2
    elif cpu:
        image_size, patch, emb, layers, steps, repeats = 32, 4, 128, 12, 50, 3
    else:
        image_size, patch, emb, layers, steps, repeats = 256, 16, 384, 12, 50, 3
    heads, batch = 4, 2

    model = SimpleDiT(output_channels=3, patch_size=patch,
                      emb_features=emb, num_layers=layers,
                      num_heads=heads)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, image_size, image_size, 3)),
                        jnp.zeros((1,)), None)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    pkeys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree_util.tree_unflatten(
        treedef, [l + 0.02 * jax.random.normal(k, l.shape, l.dtype)
                  for l, k in zip(leaves, pkeys)])

    schedule = KarrasVENoiseSchedule(timesteps=1000, sigma_max=20.0)
    shape = (batch, image_size, image_size, 3)
    x_init = jax.random.normal(jax.random.PRNGKey(2), shape) \
        * schedule.max_noise_std()
    loop_key = jax.random.PRNGKey(3)

    def engine(plan):
        plan = resolve_plan(plan)
        if plan is None:
            fns = None
        elif isinstance(plan, ComposedPlan):
            fns = resolve_composed_fns(model, plan)
        else:
            fns = resolve_cache_fns(model, plan)
        return DiffusionSampler(
            model_fn=lambda p, x, t, c: model.apply(p, x, t, None),
            schedule=schedule, transform=EpsilonPredictionTransform(),
            sampler=DDIMSampler(), cache_plan=plan, cache_fns=fns,
            timestep_spacing="karras")

    plans = [("off", None), ("default", CachePlan()),
             ("conservative", CachePlan(refresh_every=2,
                                        depth_fraction=0.5)),
             ("aggressive", CachePlan(refresh_every=5,
                                      depth_fraction=0.2)),
             # spatial axis (ops/spatialcache.py): top-k token refresh
             # on cached steps in exchange for a sparser full-refresh
             # cadence
             ("composed_default", DEFAULT_COMPOSED_PLAN),
             ("composed_conservative", ComposedPlan(
                 cache=CachePlan(refresh_every=6, depth_fraction=0.2,
                                 refresh_head=2, refresh_tail=1),
                 spatial=SpatialPlan(keep_fraction=0.25))),
             ("composed_aggressive", ComposedPlan(
                 cache=CachePlan(refresh_every=24, depth_fraction=0.2,
                                 refresh_head=2, refresh_tail=1),
                 spatial=SpatialPlan(keep_fraction=0.125, every=3)))]

    res = {"platform": jax.devices()[0].platform,
           "image_size": image_size, "num_layers": layers,
           "emb_features": emb, "steps": steps, "sampler": "ddim",
           "plans": []}
    base_ms = base_out = None
    for name, plan in plans:
        prog = engine(plan)._get_program(steps, shape, None, 0.0)
        out = prog(params, x_init, loop_key, None, None)
        float(jnp.sum(out).astype(jnp.float32))     # compile + settle
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = prog(params, x_init, loop_key, None, None)
            float(jnp.sum(out).astype(jnp.float32))
            times.append(time.perf_counter() - t0)
        ms = sorted(times)[len(times) // 2] * 1e3
        row = {"plan": name, "latency_ms": round(ms, 2)}
        if plan is None:
            base_ms, base_out = ms, out
            row["reused_fraction"] = 0.0
        else:
            if isinstance(plan, ComposedPlan):
                counts = plan.counts(steps)
                row.update(refresh_every=plan.cache.refresh_every,
                           depth_fraction=plan.cache.depth_fraction,
                           keep_fraction=plan.spatial.keep_fraction,
                           spatial_every=plan.spatial.every,
                           refresh_steps=counts["refresh"],
                           spatial_steps=counts["spatial"],
                           reused_steps=counts["reused"],
                           speedup=round(base_ms / ms, 3))
            else:
                row.update(refresh_every=plan.refresh_every,
                           depth_fraction=plan.depth_fraction,
                           reused_fraction=round(
                               plan.reused_fraction(steps), 3),
                           speedup=round(base_ms / ms, 3))
            mse = float(jnp.mean((out - base_out) ** 2))
            peak = float(base_out.max() - base_out.min())
            row["psnr_db"] = round(
                10.0 * math.log10(peak * peak / mse), 2) \
                if mse > 0 else None
        res["plans"].append(row)
        log(f"diffcache {name}: {ms:.1f} ms"
            + (f" speedup={row.get('speedup')} "
               f"psnr={row.get('psnr_db')} dB" if plan else ""))
    default = next(r for r in res["plans"] if r["plan"] == "default")
    res["speedup_default"] = default.get("speedup")
    res["psnr_default_db"] = default.get("psnr_db")
    res["meets_speedup_1_8x"] = bool(
        (default.get("speedup") or 0.0) >= 1.8)
    res["meets_psnr_30db"] = bool(
        default.get("psnr_db") is None
        or default["psnr_db"] >= 30.0)
    composed = next(r for r in res["plans"]
                    if r["plan"] == "composed_default")
    res["speedup_composed"] = composed.get("speedup")
    res["psnr_composed_db"] = composed.get("psnr_db")
    res["meets_composed_speedup_2_5x"] = bool(
        (composed.get("speedup") or 0.0) >= 2.5)
    res["meets_composed_psnr_30db"] = bool(
        composed.get("psnr_db") is None
        or composed["psnr_db"] >= 30.0)
    return res


def stage_serve(args) -> dict:
    """Serving-layer SLO bench: a seeded Poisson arrival process
    replayed against the batched sampler scheduler
    (flaxdiff_tpu/serving/, docs/SERVING.md) over a deliberately tiny
    pipeline — the number measures scheduler mechanics (grouping,
    bucketing, program-cache reuse, continuous admission, completion
    sync policy), not model compute, the same philosophy as the
    dispatch stage.

    Reports p50/p99 latency, throughput, batch occupancy, shed count,
    and program-cache hit rate for a COLD replay (compiles on the
    request path, the worst case) and a WARM replay of the identical
    workload — whose `re_traces` must be 0: repeat traffic through the
    compiled-program cache never re-traces (the ISSUE-8 acceptance
    bar, asserted in tests/test_serving.py as well)."""
    _stage_init()
    import jax
    import jax.numpy as jnp

    from flaxdiff_tpu.inference import (DiffusionInferencePipeline,
                                        build_model)
    from flaxdiff_tpu.serving import (PoissonWorkloadSpec,
                                      SchedulerConfig, ServingScheduler,
                                      build_workload, replay)
    from flaxdiff_tpu.telemetry import Telemetry

    cpu = jax.devices()[0].platform == "cpu"
    n = 24 if (cpu or args.quick) else 96
    rate_hz = 4.0 if cpu else 16.0

    config = {
        "model": {"name": "simple_dit", "emb_features": 32,
                  "num_heads": 4, "num_layers": 2, "patch_size": 4,
                  "output_channels": 1},
        "schedule": {"name": "cosine", "timesteps": 100},
        "predictor": "epsilon",
    }
    # 2 layers (not 1): the cached replay below needs a splittable
    # trunk (shallow + deep) for the diffusion-cache comparison row
    model = build_model("simple_dit", emb_features=32, num_heads=4,
                        num_layers=2, patch_size=4, output_channels=1)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)),
                        jnp.zeros((1,)), None)
    pipe = DiffusionInferencePipeline.from_config(config, params=params)

    # two NFEs x two samplers: four program families, NFE-heterogeneous
    # within each sampler group (continuous-admission masking at work)
    base = {"resolution": 8, "channels": 1, "use_ema": False,
            "deadline_s": 120.0}
    spec = PoissonWorkloadSpec(
        n_requests=n, rate_hz=rate_hz, seed=1234,
        mix=[{**base, "diffusion_steps": 4, "sampler": "ddim"},
             {**base, "diffusion_steps": 8, "sampler": "ddim"},
             {**base, "diffusion_steps": 4, "sampler": "euler_ancestral"},
             {**base, "diffusion_steps": 8,
              "sampler": "euler_ancestral"}])
    workload = build_workload(spec)

    tel = Telemetry(enabled=False)
    # ONE batch bucket: bucket choice depends on how many requests the
    # admission race catches per round, so multi-bucket configs can
    # legitimately meet a never-before-seen bucket size on a warm
    # replay and re-trace — a single bucket makes every program shape
    # deterministic and the retrace-free acceptance check exact
    sched = ServingScheduler(
        pipeline=pipe,
        config=SchedulerConfig(round_steps=4, batch_buckets=(4,),
                               max_inflight=2),
        telemetry=tel)

    def counters():
        snap = tel.registry.snapshot()
        return {k: snap.get(k, 0.0) for k in (
            "serving/program_cache_hits", "serving/program_cache_misses",
            "serving/shed", "serving/rows_real", "serving/rows_padded",
            "serving/backpressure_waits")}

    res = {"platform": jax.devices()[0].platform, "n_requests": n,
           "rate_hz": rate_hz, "rounds_per_request": None}

    def run_phase(phase, wl):
        before = counters()
        summary = replay(sched, wl, timeout_s=600 if cpu else 120)
        after = counters()
        delta = {k: after[k] - before[k] for k in after}
        occ_total = delta["serving/rows_real"] \
            + delta["serving/rows_padded"]
        summary["batch_occupancy"] = round(
            delta["serving/rows_real"] / occ_total, 3) \
            if occ_total else None
        lookups = delta["serving/program_cache_hits"] \
            + delta["serving/program_cache_misses"]
        summary["cache_hit_rate"] = round(
            delta["serving/program_cache_hits"] / lookups, 3) \
            if lookups else None
        summary["re_traces"] = delta["serving/program_cache_misses"]
        summary["shed_total"] = delta["serving/shed"]
        summary["backpressure_waits"] = delta[
            "serving/backpressure_waits"]
        res[phase] = summary
        log(f"serve {phase}: p50={summary['latency_ms']['p50']} "
            f"p99={summary['latency_ms']['p99']} ms, "
            f"{summary['throughput_rps']} req/s, "
            f"occ={summary['batch_occupancy']}, "
            f"ms/step={summary['device_ms_per_step_mean']}, "
            f"hit_rate={summary['cache_hit_rate']}, "
            f"re_traces={summary['re_traces']}, "
            f"shed={summary['shed_total']}")
        return summary

    try:
        for phase in ("cold", "warm"):
            run_phase(phase, workload)
        # cached-vs-uncached: the identical workload with every request
        # carrying a composed spatial+timestep plan (docs/CACHING.md).
        # Two passes: cached_cold compiles the composed program family,
        # cached_warm must be retrace-free — a FIXED plan is part of
        # the program cache key, so warm cached traffic never re-traces
        # (the ISSUE-10 bar, re-asserted for the spatial axis by
        # ISSUE 11). The per-step device comparison on this tiny pipe
        # measures serving-side plumbing cost; the compute win itself
        # is the diffcache stage's number. keep_fraction sized for the
        # tiny pipe's 4-token grid (k=2).
        from flaxdiff_tpu.ops.diffcache import CachePlan
        from flaxdiff_tpu.ops.spatialcache import (ComposedPlan,
                                                   SpatialPlan)
        serve_plan = ComposedPlan(
            cache=CachePlan(refresh_every=3),
            spatial=SpatialPlan(keep_fraction=0.5))
        spec_cached = PoissonWorkloadSpec(
            n_requests=n, rate_hz=rate_hz, seed=1234,
            mix=[{**m, "cache_plan": serve_plan} for m in spec.mix])
        workload_cached = build_workload(spec_cached)
        for phase in ("cached_cold", "cached_warm"):
            run_phase(phase, workload_cached)
    finally:
        sched.close()
    if args.serve_prewarm:
        # program-cache pre-warming (ISSUE 11 satellite): a FRESH
        # engine compiles the workload's (bucket, NFE, plan) tuples
        # via scheduler.prewarm BEFORE admission opens, then replays
        # the composed-plan workload once — its re_traces must be 0
        # and its p50 must look like the warm phase, never the cold
        # one, because no compile ever lands on the request path.
        tel2 = Telemetry(enabled=False)
        sched2 = ServingScheduler(
            pipeline=DiffusionInferencePipeline.from_config(
                config, params=params),
            config=SchedulerConfig(round_steps=4, batch_buckets=(4,),
                                   max_inflight=2),
            telemetry=tel2, autostart=False)
        try:
            protos = []
            seen = set()
            for _, req in workload_cached:
                sig = (req.diffusion_steps, req.sampler)
                if sig not in seen:
                    seen.add(sig)
                    protos.append(req)
            info = sched2.prewarm(protos)
            sched2.start()
            tel, sched = tel2, sched2   # counters() reads the phase tel
            summary = run_phase("prewarmed", workload_cached)
            summary["prewarm_programs"] = info["programs"]
            summary["prewarm_s"] = round(info["seconds"], 3)
        finally:
            sched2.close()
        res["prewarmed_retrace_free"] = bool(
            res.get("prewarmed", {}).get("re_traces", 1) == 0)
    if args.serve_chaos:
        # chaos-replay phase (ISSUE 15): the identical workload under
        # injected round / fetch / device faults. Acceptance: zero
        # stranded futures (every request resolves: completed, shed,
        # or typed fault), the device-lost round triggers exactly one
        # supervised engine rebuild (prewarmed — rebuilt traffic pays
        # no re-trace on the request path), and recovered requests
        # (attempts > 0) report their own p99.
        from flaxdiff_tpu import resilience as R
        tel4 = Telemetry(enabled=False)
        sched4 = ServingScheduler(
            pipeline=DiffusionInferencePipeline.from_config(
                config, params=params),
            config=SchedulerConfig(round_steps=4, batch_buckets=(4,),
                                   max_inflight=2),
            telemetry=tel4, autostart=False)
        try:
            protos, seen = [], set()
            for _, req in workload:
                sig = (req.diffusion_steps, req.sampler)
                if sig not in seen:
                    seen.add(sig)
                    protos.append(req)
            sched4.prewarm(protos)
            sched4.start()
            tel, sched = tel4, sched4
            fault_plan = R.FaultPlan([
                R.FaultSpec("serving.round", at=(3,), times=1),
                R.FaultSpec("serving.fetch", at=(2,), times=1),
                R.FaultSpec("serving.device_lost", at=(6,), times=1,
                            error="flag")], seed=0)
            with fault_plan.installed():
                summary = run_phase("chaos", workload)
        finally:
            sched4.close()
        snap4 = tel4.registry.snapshot()
        summary["rebuilds"] = snap4.get(
            "serving/supervisor_rebuilds", 0)
        summary["requeued"] = snap4.get("serving/requeued", 0)
        summary["quarantined"] = snap4.get("serving/quarantined", 0)
        res["chaos_zero_stranded"] = bool(
            summary["completed"] + summary["shed"]
            + summary["faulted"] + summary["errors"] == n)
        res["chaos_recovered_p99_ms"] = summary["recovered_p99_ms"]
        log(f"serve chaos: recovered={summary['recovered']} "
            f"p99={summary['recovered_p99_ms']} ms, "
            f"rebuilds={summary['rebuilds']}, "
            f"zero_stranded={res['chaos_zero_stranded']}")
    if args.serve_pool:
        # replicated front-door chaos (ISSUE 16): the identical
        # workload routed through a health-checked 2-replica pool
        # behind the FrontDoor, with a per-key serving.replica_lost
        # fault killing r0 mid-replay. Acceptance: zero stranded
        # futures — every request resolves (completed / shed / typed
        # fault) even though a replica died holding traffic — and the
        # SURVIVOR pays no re-trace for inherited traffic (every
        # replica is prewarmed, so failed-over requests land on warm
        # programs). Bit-identity of failed-over results vs solo runs
        # is the per-request assertion in tests/test_frontdoor_chaos.py.
        from flaxdiff_tpu import resilience as R
        from flaxdiff_tpu.serving import (FrontDoor, FrontDoorConfig,
                                          build_pool)
        from flaxdiff_tpu.telemetry import list_incidents
        tels = [Telemetry(enabled=False) for _ in range(2)]
        pool = build_pool(
            [DiffusionInferencePipeline.from_config(config, params=params)
             for _ in range(2)],
            scheduler_config=SchedulerConfig(
                round_steps=4, batch_buckets=(4,), max_inflight=2),
            telemetries=tels, autostart=False)
        # ENABLED door hub (ISSUE 18): Telemetry.create wires the
        # flight recorder to the global resilience event log, so the
        # replica kill below dumps a correlated incident-*.json bundle
        # into this directory — `scripts/diagnose_run.py <dir>` renders
        # it under "Incidents"
        door_dir = os.path.join(args.trace, "pool_door")
        door_tel = Telemetry.create(door_dir)
        door = FrontDoor(pool, telemetry=door_tel,
                         config=FrontDoorConfig(max_attempts=3))
        try:
            protos, seen = [], set()
            for _, req in workload:
                sig = (req.diffusion_steps, req.sampler)
                if sig not in seen:
                    seen.add(sig)
                    protos.append(req)
            door.prewarm(protos)
            for rep in pool.replicas:
                rep.scheduler.start()
            miss0 = tels[1].registry.snapshot().get(
                "serving/program_cache_misses", 0.0)
            kill_at = max(3, n // 3)
            fault_plan = R.FaultPlan([
                R.FaultSpec("serving.replica_lost", per_key=True,
                            match="replica:r0:", at=(kill_at,),
                            times=1, error="flag")], seed=0)
            with fault_plan.installed():
                summary = replay(door, workload,
                                 timeout_s=600 if cpu else 120)
        finally:
            door.close(drain=False)
        dsnap = door_tel.registry.snapshot()
        door_tel.close()
        summary["failovers"] = dsnap.get("frontdoor/failovers", 0)
        summary["replica_lost"] = dsnap.get("frontdoor/replica_lost", 0)
        summary["pool_exhausted"] = dsnap.get(
            "frontdoor/pool_exhausted", 0)
        summary["survivor_re_traces"] = tels[1].registry.snapshot().get(
            "serving/program_cache_misses", 0.0) - miss0
        incidents = list_incidents(door_dir)
        summary["incidents"] = [os.path.basename(p) for p in incidents]
        res["pool"] = summary
        res["pool_zero_stranded"] = bool(
            summary["completed"] + summary["shed"]
            + summary["faulted"] + summary["errors"] == n)
        res["pool_survivor_retrace_free"] = bool(
            summary["survivor_re_traces"] == 0)
        res["pool_incident_recorded"] = bool(
            summary["replica_lost"] == 0
            or any("replica_lost" in p for p in summary["incidents"]))
        res["pool_telemetry_dir"] = door_dir
        log(f"serve pool: completed={summary['completed']} "
            f"failovers={summary['failovers']}, "
            f"replica_lost={summary['replica_lost']}, "
            f"survivor_re_traces={summary['survivor_re_traces']}, "
            f"zero_stranded={res['pool_zero_stranded']}, "
            f"incidents={summary['incidents']}")
    res["warm_retrace_free"] = bool(
        res.get("warm", {}).get("re_traces", 1) == 0)
    res["cached_warm_retrace_free"] = bool(
        res.get("cached_warm", {}).get("re_traces", 1) == 0)
    warm_ps = res.get("warm", {}).get("device_ms_per_step_mean")
    cached_ps = res.get("cached_warm", {}).get("device_ms_per_step_mean")
    res["cached_vs_uncached_device_ms_per_step"] = (
        round(cached_ps / warm_ps, 3)
        if warm_ps and cached_ps else None)
    return res


STAGES = {"flashtune": stage_flashtune, "sweep": stage_sweep,
          "sweep256": stage_sweep256, "ref": stage_ref,
          "ddim": stage_ddim, "attnpad": stage_attnpad,
          "ablate": stage_ablate, "longseq": stage_longseq,
          "dispatch": stage_dispatch, "epilogue": stage_epilogue,
          "serve": stage_serve, "diffcache": stage_diffcache,
          "data_chaos": stage_data_chaos, "devprof": stage_devprof,
          "plan": stage_plan}

# info-value order: the headline sweep first, its baseline second;
# dispatch is the step-loop-overhead evidence (cheap — tiny model);
# flashtune is cheap and unblocks the tuned micros; ddim is the
# BASELINE.md inference target; the rest are diagnostics.
STAGE_ORDER = ("sweep", "ref", "dispatch", "devprof",
               "plan", "serve", "diffcache", "flashtune", "ddim",
               "attnpad", "epilogue", "ablate", "sweep256", "longseq")

# rough cost estimates (seconds) for budget scheduling — a stage is
# skipped when the remaining budget can't cover its MINIMUM useful
# runtime (est/2), and its timeout is capped by what remains
# flashtune covers the block ladder PLUS the prebuilt head-to-head
# (4 shapes x 2 impls, each a fresh compile)
STAGE_EST = {"sweep": 900, "ref": 450, "flashtune": 500,
             "ddim": 600, "attnpad": 90, "ablate": 1100, "sweep256": 800,
             # 3 epilogue chains x 2 variants, each one small jit(grad)
             # compile + `iters` chained steps
             "epilogue": 240,
             "longseq": 550,   # + r5 on-chip 16k correctness cell
             # 9 tiny-model fit cells (3 depths x 3 telemetry modes),
             # each ~steps x a-few-ms + one tiny-model compile
             "dispatch": 240,
             # cold/warm + cached_cold/cached_warm Poisson replays on a
             # tiny pipeline: arrival clock ~n/rate s each + small jit
             # compiles on the two cold passes (the composed spatial
             # programs carry a 3-branch switch; --serve_prewarm adds
             # one more pre-warmed replay on top)
             "serve": 480,
             # 7 plans (4 CachePlans + 3 composed spatial) x (one
             # scan-program compile of a 12-layer DiT + `repeats`
             # timed DDIM-50 trajectories)
             "diffcache": 720,
             # two tiny-model fits (chaos + control) + one tiny compile
             # + a 64-record packed shard written/decoded on the host
             "data_chaos": 180,
             # one tiny-model 40-step fit with two cadence-triggered
             # profiler windows + the capture parse (host-side)
             "devprof": 120,
             # the planner search is static (jaxpr traces, nothing
             # compiled) but each shortlist probe is a fresh tiny-DiT
             # trainer compile + a 4-step fit under its candidate mesh;
             # the warm re-plan is cache-only
             "plan": 240}

# stages that receive the flashtune winner env. Headline stages
# (sweep/ref/ddim/sweep256) run with code defaults: an unvalidated
# winner must never be able to take down the headline number (the r4
# mid-round session exported native_d to the sweep and lost it).
# epilogue is deliberately NOT tuned: its chains contain no attention,
# so the flashtune winner env / autotune cache cannot affect it
TUNED_STAGES = ("attnpad", "ablate", "longseq")


def export_winner_env(env: dict, stages: dict) -> dict:
    """Env additions from completed stages for LATER stages: the
    flashtune winner's block shape (+native_d) and the sweep's headline
    batch for the ablate stage."""
    add = {}
    best = stages.get("flashtune", {}).get("best")
    if best:
        add["FLAXDIFF_FLASH_BLOCK_Q"] = str(best["block_q"])
        add["FLAXDIFF_FLASH_BLOCK_K"] = str(best["block_k"])
        if best.get("native_d"):
            add["FLAXDIFF_FLASH_NATIVE_D"] = "1"
        cache = stages.get("flashtune", {}).get("autotune_cache")
        if cache:
            # per-shape plans for every OTHER attention shape the tuned
            # stages hit (the env pair above still wins where set —
            # autotuner env-precedence rule)
            add["FLAXDIFF_FLASH_TUNE_CACHE"] = cache
        # deliberately NOT exporting FLAXDIFF_FLASH_IMPL: the ablate
        # stage measures the impl choice as its own explicit cell
        # (attn=prebuilt) — an env switch would silently change the
        # kernel under every backend="auto" cell and confound the
        # optimizer/layout deltas that stage exists to isolate
    batch = stages.get("sweep", {}).get("batch_per_chip")
    if batch:
        add["FLAXDIFF_BENCH_ABLATE_BATCH"] = str(batch)
    env.update(add)
    return add


# ---------------------------------------------------------------------------
# Orchestrator (parent process; never imports jax)
# ---------------------------------------------------------------------------

# the stage child that currently owns the device (for the SIGTERM handler)
_ACTIVE_CHILD = [None]


def _kill_group(child):
    """Kill a stage child AND its descendants (they share a session via
    start_new_session=True at spawn)."""
    import signal as _sig
    try:
        os.killpg(child.pid, _sig.SIGKILL)
    except (ProcessLookupError, PermissionError, OSError):
        try:
            child.kill()
        except Exception as e:  # noqa: BLE001 — degrade, but visibly
            # both the group kill and the direct kill failed: the child
            # may be unkillable (already reaped / zombie) — note it on
            # stderr (stdout carries the JSON protocol) so a later hung
            # stage is attributable
            print(f"note: stage child kill failed "
                  f"({type(e).__name__}: {e}, pid={child.pid})",
                  file=sys.stderr)


def run_stage(name: str, args, env, timeout_s: int, retries: int,
              time_left=None) -> dict:
    """Run one stage in a subprocess with timeout + retries; returns
    {"status": "ok", ...stage result} or {"status": "failed: ..."}.
    `time_left()` (seconds, optional) gates retries: a retry whose
    minimum runtime no longer fits the budget is abandoned so the
    orchestrator can spend the remainder on later stages."""
    cmd = [sys.executable, os.path.abspath(__file__), "--stage", name,
           "--trace", args.trace]
    if args.quick:
        cmd.append("--quick")
    # serve-stage opt-in phases ride along (previously they only
    # worked in direct `--stage serve` child mode)
    if name == "serve":
        if getattr(args, "serve_prewarm", False):
            cmd.append("--serve_prewarm")
        if getattr(args, "serve_chaos", False):
            cmd.append("--serve_chaos")
        if getattr(args, "serve_pool", False):
            cmd.append("--serve_pool")
    last = "never ran"
    for attempt in range(1 + retries):
        if attempt:
            if time_left is not None and time_left() < 120:
                last += "; retry abandoned (budget)"
                break
            log(f"stage {name}: retry {attempt}")
        t0 = time.monotonic()
        # re-clamp every attempt: a retry must not inherit the
        # stage-start timeout and overrun the hard budget
        attempt_timeout = timeout_s
        if time_left is not None and time_left() != float("inf"):
            attempt_timeout = min(timeout_s, max(int(time_left()) - 60, 30))
        try:
            # Popen (not subprocess.run) so the SIGTERM handler can kill
            # the in-flight child — an orphan would keep the device, and
            # the next process to ask for it would fail or hang. Own
            # process group (start_new_session): killing the stage must
            # also kill its descendants.
            child = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                     stderr=subprocess.PIPE, text=True,
                                     env=env, start_new_session=True)
            _ACTIVE_CHILD[0] = child
            out_txt, err_txt = child.communicate(timeout=attempt_timeout)
            proc = subprocess.CompletedProcess(cmd, child.returncode,
                                               out_txt, err_txt)
        except subprocess.TimeoutExpired:
            _kill_group(child)
            out_txt, err_txt = child.communicate()
            # salvage: stages print their result-so-far before starting
            # risky addenda (e.g. ddim's batch-8 compile) — a killed
            # child may still have left a complete JSON line
            for line in reversed((out_txt or "").strip().splitlines()):
                try:
                    out = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if not isinstance(out, dict):
                    continue   # a stray 'null'/number line is not a result
                out["status"] = "ok"
                out["salvaged"] = f"timeout after {attempt_timeout}s"
                out["secs"] = round(time.monotonic() - t0, 1)
                log(f"stage {name}: timed out but salvaged a completed "
                    "result line")
                return out
            # keep the child's partial stderr: it says which phase
            # (build, warmup, batch N, trace) the stage was in
            tail = (err_txt or "")[-300:]
            last = f"timeout after {attempt_timeout}s (killed); last output: {tail}"
            log(f"stage {name}: {last}")
            continue
        finally:
            _ACTIVE_CHILD[0] = None
        sys.stderr.write(proc.stderr)
        if proc.returncode == 0:
            try:
                out = json.loads(proc.stdout.strip().splitlines()[-1])
            except (IndexError, json.JSONDecodeError):
                last = "no JSON on stage stdout"
                continue
            out["status"] = "ok"
            out["secs"] = round(time.monotonic() - t0, 1)
            return out
        last = (f"rc {proc.returncode}: "
                f"{(proc.stderr or proc.stdout).strip()[-300:]}")
        log(f"stage {name}: {last}")
    return {"status": f"failed: {last}"}


def emit(result: dict, partial: bool):
    """Print a cumulative results line + append to bench_partial.jsonl."""
    line = dict(result)
    if partial:
        line["partial"] = True
    txt = json.dumps(line)
    print(txt, flush=True)
    try:
        with open("bench_partial.jsonl", "a") as f:
            f.write(txt + "\n")
    except OSError:
        pass


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default="bench_trace",
                    help="profiler trace dir (always captured in sweep)")
    ap.add_argument("--quick", action="store_true")
    # the DRIVER's wall clock is the real deadline: everything — stages
    # and the final emit — must fit --budget; 0 disables the cap.
    ap.add_argument("--budget", type=int, default=1380)
    ap.add_argument("--retries", type=int, default=1)
    ap.add_argument("--stages", default=None,
                    help="comma list overriding the default stage order")
    # serve stage: also run a pre-warmed phase — a fresh engine whose
    # (bucket, NFE, plan) program tuples are compiled via
    # scheduler.prewarm BEFORE admission opens (zero re-traces, warm
    # p50 from the first request). Off by default: it re-compiles the
    # composed program family, ~1 extra cold pass of stage budget.
    ap.add_argument("--serve_prewarm", action="store_true")
    # serve stage: also run a chaos-replay phase — the same workload
    # under injected round/fetch/device faults (FaultPlan), reporting
    # recovered-request p99, rebuild count, and the zero-stranded
    # acceptance (docs/SERVING.md "Failure semantics"). Off by
    # default: the device-lost rebuild re-runs prewarm (~1 extra cold
    # compile pass of stage budget).
    ap.add_argument("--serve_chaos", action="store_true")
    # serve stage: also run a replicated front-door phase — the same
    # workload through a 2-replica health-checked pool with a
    # serving.replica_lost fault killing r0 mid-replay, reporting
    # failover count, survivor re-traces (must be 0: every replica
    # prewarmed), and the pool zero-stranded acceptance
    # (docs/SERVING.md "Front door"). Off by default: it builds and
    # prewarms two full engines (~2 extra cold passes of stage budget).
    ap.add_argument("--serve_pool", action="store_true")
    # data-plane chaos stage (docs/DATA.md): a packed shard with REAL
    # corrupted record bytes fed through DataPlane under a step.nan
    # rollback — reports bit-identical replay, quarantine accounting,
    # zero stranded batches and zero new host syncs vs a control fit.
    # Off by default (not in STAGE_ORDER): it is an acceptance drill,
    # not a throughput number, and costs two tiny fits of budget.
    ap.add_argument("--data_chaos", action="store_true")
    # stamp the final result with a hardware/software fingerprint
    # (platform, device kind, jax version) so scripts/compare_runs.py
    # can refuse to diff evidence from different experiments — two
    # BENCH files without matching fingerprints are not a regression,
    # they are different hardware
    ap.add_argument("--evidence", action="store_true")
    ap.add_argument("--stage", choices=sorted(STAGES))
    args = ap.parse_args()

    if args.stage:   # child mode
        out = STAGES[args.stage](args)
        out.update(_device_fields())
        print(json.dumps(out), flush=True)
        return

    t_run = time.monotonic()

    def left():
        return (float("inf") if args.budget <= 0
                else args.budget - (time.monotonic() - t_run))

    # fresh salvage file per run: a stale previous-run record must never
    # be read as THIS run's partial results after a SIGKILL
    try:
        with open("bench_partial.jsonl", "w") as f:
            f.write(json.dumps({"run_start": " ".join(sys.argv)}) + "\n")
    except OSError:
        pass

    # platform / device_kind / device_count are what the stage CHILDREN
    # report jax gave them; the parent never asks jax itself
    result = {
        "metric": "train_imgs_per_sec_per_chip_unet128_text_cond",
        "value": None, "unit": "imgs/sec/chip", "vs_baseline": None,
        "platform": None, "device_kind": None, "device_count": None,
        "stages": {},
        "baseline_kind": "same-framework-reference-semantics "
                         "(f32, XLA attn, per-step host sync, batch 16)",
    }

    # The driver kills with SIGTERM at ITS wall clock: emit the current
    # cumulative result as the final line first.
    import signal

    def _on_term(signum, frame):
        result["terminated"] = f"signal {signum}"
        # the signal may land mid-print of a cumulative emit: start on a
        # fresh line so the final JSON is parseable on its own
        sys.stdout.write("\n")
        emit(result, partial=False)
        child = _ACTIVE_CHILD[0]
        if child is not None:
            # an orphaned stage child would keep the device past our death
            _kill_group(child)
        os._exit(1)

    signal.signal(signal.SIGTERM, _on_term)

    env = os.environ.copy()
    if args.evidence:
        # package metadata only — the orchestrator must not import jax;
        # the platform fields are filled from the first stage child
        stamp = {"platform": None}
        try:
            from importlib import metadata as _md
            stamp["jax"] = _md.version("jax")
            stamp["jaxlib"] = _md.version("jaxlib")
        except Exception as e:  # noqa: BLE001 — stamp is best-effort
            stamp["version_error"] = str(e)
        import platform as _plat
        stamp["python"] = _plat.python_version()
        stamp["machine"] = _plat.machine()
        result["evidence"] = stamp
    emit(result, partial=True)   # parseable evidence exists from here on

    requested = (args.stages.split(",") if args.stages
                 else list(STAGE_ORDER))
    order = [s for s in requested if s in STAGES]
    for s in requested:
        if s not in STAGES:
            result["stages"][s] = {"status": "failed: unknown stage"}
    if args.quick:
        order = [s for s in order if s in ("sweep", "ref", "ddim",
                                           "flashtune")]
    if args.data_chaos and "data_chaos" not in order:
        order.append("data_chaos")
    if not order:
        # a typo'd --stages list must not end the run on a partial line
        result["terminated"] = "no runnable stages requested"
        emit(result, partial=False)
        raise SystemExit(2)
    for i, name in enumerate(order):
        est = STAGE_EST[name]
        # reserve a floor for the final emit; skip stages that can't do
        # useful work in the time left rather than truncating them all
        if left() < max(est // 2, 90):
            result["stages"][name] = {
                "status": f"skipped: budget ({int(max(left(), 0))}s left, "
                          f"stage needs ~{est}s)"}
        else:
            stage_env = dict(env)
            if name in TUNED_STAGES:
                # measured flashtune winner reaches the diagnostics; the
                # headline stages always run code defaults (an
                # unvalidated winner must not take down the headline)
                added = export_winner_env(stage_env, {
                    k: v for k, v in result["stages"].items()
                    if isinstance(v, dict)})
                if added:
                    log(f"stage {name}: tuned env {added}")
            timeout = int(min(est * 2, left() - 60))
            log(f"=== stage {name} (timeout {timeout}s, "
                f"{'inf' if left() == float('inf') else int(left())}s "
                "budget left) ===")
            result["stages"][name] = run_stage(
                name, args, stage_env, timeout, args.retries,
                time_left=left)
        done = result["stages"][name]
        if result["platform"] is None and done.get("platform"):
            for k in ("platform", "device_kind", "device_count"):
                result[k] = done.get(k)
            if isinstance(result.get("evidence"), dict):
                result["evidence"]["platform"] = done["platform"]
                result["evidence"]["device_kind"] = done.get("device_kind")
        sweep = result["stages"].get("sweep", {})
        ref = result["stages"].get("ref", {})
        # .get() throughout: a stage can finish rc 0 with NO throughput
        # (every batch failed) — an unguarded key here would kill the
        # orchestrator mid-aggregation and lose the final emit
        if sweep.get("status") == "ok" and sweep.get("platform") == "tpu" \
                and sweep.get("imgs_per_sec_per_chip"):
            # the device metric is published only from a child that ran
            # on the device; a cpu sweep keeps its numbers inside
            # stages["sweep"], labelled platform=cpu
            result["value"] = sweep["imgs_per_sec_per_chip"]
            result["mfu_hw"] = sweep.get("mfu_hw")
            result["mfu_model"] = sweep.get("mfu_model")
            result["batch_per_chip"] = sweep.get("batch_per_chip")
            result["step_time_ms"] = sweep.get("step_time_ms")
            result["trace_dir"] = sweep.get("trace_dir")
        if ref.get("status") == "ok" and result["value"] \
                and ref.get("platform") == "tpu" \
                and ref.get("imgs_per_sec_per_chip"):
            result["vs_baseline"] = round(
                result["value"] / ref["imgs_per_sec_per_chip"], 3)
            if ref.get("best_imgs_per_sec_per_chip"):
                # matched best-effort: our best batch vs the baseline's
                # best batch
                result["vs_baseline_best"] = round(
                    result["value"] / ref["best_imgs_per_sec_per_chip"],
                    3)
        ddim = result["stages"].get("ddim", {})
        if ddim.get("status") == "ok" and ddim.get("key") \
                and ddim.get("platform") == "tpu":
            result[ddim["key"]] = ddim.get("latency_ms")
        s256 = result["stages"].get("sweep256", {})
        if s256.get("status") == "ok" and s256.get("platform") == "tpu" \
                and s256.get("imgs_per_sec_per_chip"):
            result["sweep256_imgs_per_sec_per_chip"] = \
                s256["imgs_per_sec_per_chip"]
            result["sweep256_mfu_hw"] = s256.get("mfu_hw")
        dpf = result["stages"].get("devprof", {})
        if dpf.get("status") == "ok" and dpf.get("window"):
            # the measured device-time attribution rides in the
            # evidence stamp so compare_runs sees it next to the
            # hardware fingerprint
            if isinstance(result.get("evidence"), dict):
                result["evidence"]["devprof"] = dpf["window"]
        emit(result, partial=(i != len(order) - 1))

    # a run with no chip has no headline to publish: exit 1 so a caller
    # that wanted a device number cannot mistake a cpu harness run for one
    raise SystemExit(0 if result["value"] is not None else 1)


if __name__ == "__main__":
    main()
