#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the framework's main path once, through the entry
points a user calls, at the full width of the flagship text-conditional
UNet (depth and step counts cut, weights random from a seed):

  0 device   what jax found; anything but a TPU is exit 1 (no CPU
             fallback, no shrunk run); the found device_kind must have
             an exact entry in both peak tables
  1 kernels  every Pallas entry point on a default TPU path, compiled by
             Mosaic, forward and backward, bf16, at the flagship's and
             two DiT widths' shapes, against its XLA composition
  2 train    train.main: DiffusionTrainer.fit, a checkpoint save and a
             --val_every sampling pass; losses finite, MFU a number
  3 sample   DiffusionInferencePipeline.from_checkpoint, DDIM, guidance
  4 serve    ServingScheduler: four requests of mixed NFE, the fourth a
             repeat of the first that must compile nothing new
  5 fsdp     phase 2 again under --mesh_fsdp <device_count>, when there
             is more than one device

The last stdout line is one JSON object with exactly two keys,
  {"ok": true|false, "device": {"platform": ..., "kind": ..., "count": N}}
(the device as jax reports it). The line before it, `report {...}`,
carries per-phase wall and compile seconds and each phase's numbers; the
same report is written to chiprun_out/chip_smoke.json. The first failed
phase ends the run with a non-zero exit status and "ok": false; nothing
is caught that is not re-raised.

`--rehearse` is the only other mode: tiny shapes, the three interpret
hooks, JAX_PLATFORMS=cpu, report marked "rehearsal": true — for
debugging the command where there is no chip, and for the tests.
Without the flag and without a chip the script fails in phase 0, before
anything compiles, and prints no result line.

One process per chip: nothing here starts a child, because a parent
that has touched jax holds the device.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

# bf16 carries 8 bits of mantissa; fused and unfused paths round at
# different points (the kernels keep f32 until the store), so agreement
# is judged at a few ulps of the larger magnitude.
BF16_EPS = 2.0 ** -8
KERNEL_TOL = 8 * BF16_EPS

_ATTN = {"heads": 8, "dim_head": 64, "backend": "auto",
         "force_fp32_for_softmax": True}

# The flagship: the benchmark's `unet-flaxdiff-128` configuration (text-
# conditional UNet, 128^2, attention on the last two levels).
FLAGSHIP = {
    "model_config": {
        "emb_features": 512, "feature_depths": [64, 128, 256, 512],
        "attention_configs": [None, None, dict(_ATTN), dict(_ATTN)],
        "num_res_blocks": 2},
    "image_size": 128, "batch_per_chip": 16,
    "train_steps": 8, "val_every": 4, "val_steps": 4,
    "sample_steps": 10, "sample_n": 4, "serve_nfe": (4, 8, 4),
    "kernel_batch": 2,
    # (side, channels): every GroupNorm input the flagship produces
    # (down path, middle, the decoder's concatenated skips), plus the
    # 192/384/768 widths a keep-channels decoder would concatenate —
    # their block-row counts are odd multiples of the sublane tile
    "groupnorm": [(128, 64), (128, 128), (128, 192), (64, 64), (64, 128),
                  (64, 256), (64, 384), (32, 128), (32, 256), (32, 512),
                  (32, 768), (16, 256), (16, 512), (16, 1024)],
    "norm_groups": 8,
    # (tokens, 2F): the FF of the 256- and 512-channel attention levels
    "geglu": [(1024, 2048), (256, 4096)],
    # (q tokens, kv tokens): self at both levels, cross against text
    "flash": [(1024, 1024), (256, 256), (1024, 77), (256, 77)],
    "heads": 8, "dim_head": 64,
    # (tokens, channels): a 384-wide DiT and a DiT-XL-width block
    "adaln": [(256, 384), (1024, 1152)],
}

REHEARSAL = {
    "model_config": {
        "emb_features": 16, "feature_depths": [8, 16], "norm_groups": 4,
        "attention_configs": [dict(_ATTN, heads=2, dim_head=8), None],
        "num_res_blocks": 1},
    "image_size": 16, "batch_per_chip": 2,
    "train_steps": 4, "val_every": 2, "val_steps": 2,
    "sample_steps": 2, "sample_n": 2, "serve_nfe": (2, 3, 2),
    "kernel_batch": 1,
    "groupnorm": [(8, 16), (16, 24)], "norm_groups": 4,
    "geglu": [(16, 64)],
    "flash": [(128, 128), (128, 77)],
    "heads": 2, "dim_head": 8,
    "adaln": [(256, 384)],     # two row blocks: the partial-sum outputs
}

PROMPTS = ["a photo of a bright flower", "a dark field at night",
           "a bright square", "a dark square"]


class SmokeFailure(Exception):
    """A phase ran and its result was wrong."""


# ---------------------------------------------------------------------------
# compile accounting (jax's own monitoring events)
# ---------------------------------------------------------------------------

class CompileMeter:
    """Seconds jax spent in backend compilation (a persistent-cache hit
    is timed under the same event, so a warm run reports its load time)
    and the persistent cache's hit/miss counts."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    HIT_EVENT = "/jax/compilation_cache/cache_hits"
    MISS_EVENT = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == self.COMPILE_EVENT:
            self.seconds += secs

    def _event(self, event, **_):
        if event == self.HIT_EVENT:
            self.hits += 1
        elif event == self.MISS_EVENT:
            self.misses += 1


# ---------------------------------------------------------------------------
# phase 0: device
# ---------------------------------------------------------------------------

def phase_device(rehearse: bool) -> dict:
    import importlib.metadata as md

    import jax

    dev = jax.devices()[0]
    triple = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None
    print(f"device: platform={triple['platform']} "
          f"device_kind={triple['kind']!r} count={triple['count']}  "
          + " ".join(f"{k}={v}" for k, v in versions.items()), flush=True)
    if rehearse:
        if dev.platform != "cpu":
            raise SmokeFailure("--rehearse is a CPU run; jax gave "
                               f"{dev.platform!r}")
        return triple
    if dev.platform != "tpu":
        # no fallback: a smoke that passes on a CPU proves nothing
        # about the chip. No result line is printed.
        print(f"chip_smoke: no TPU (jax found {dev.platform!r}); "
              "use --rehearse to debug the command on a CPU",
              file=sys.stderr)
        sys.exit(1)

    from flaxdiff_tpu.profiling import device_peak_flops
    from flaxdiff_tpu.telemetry.devprof import device_peak_bytes_per_s

    # exact keys: a TPU kind missing from either table raises KeyError
    peak_flops = device_peak_flops(dev)
    peak_bw = device_peak_bytes_per_s(dev)
    print(f"peaks for {dev.device_kind!r}: {peak_flops / 1e12:.0f} "
          f"TFLOP/s bf16, {peak_bw / 1e9:.0f} GB/s HBM", flush=True)
    return triple


# ---------------------------------------------------------------------------
# phase 1: kernels
# ---------------------------------------------------------------------------

def _compare(name: str, shape: str, fused, reference, args) -> dict:
    """Forward + backward of `fused` and `reference` on the default
    device, under a seeded random cotangent; one printed line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    out_shapes = jax.eval_shape(reference, *args)
    leaves, treedef = jax.tree_util.tree_flatten(out_shapes)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    cts = jax.tree_util.tree_unflatten(treedef, [
        jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype)
        for k, s in zip(keys, leaves)])

    def fwd_bwd(fn):
        def run(*a):
            out, vjp = jax.vjp(fn, *a)
            return out, vjp(cts)
        return jax.jit(run)

    row = {"kernel": name, "shape": shape}
    try:
        got = jax.device_get(fwd_bwd(fused)(*args))
    except Exception as e:  # noqa: BLE001 — recorded, re-raised by caller
        row.update(ok=False, error=f"{type(e).__name__}: {e}")
        print(f"kernel {name:<22} {shape:<34} FAIL "
              f"{row['error'][:400]}", flush=True)
        row["exception"] = e
        return row
    want = jax.device_get(fwd_bwd(reference)(*args))
    err = rel = 0.0
    finite = True
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        finite = finite and bool(np.isfinite(g).all())
        e = float(np.abs(g - w).max())
        err = max(err, e)
        rel = max(rel, e / max(1.0, float(np.abs(w).max())))
    ok = finite and rel <= KERNEL_TOL
    row.update(ok=ok, max_abs_err=err, rel_err=rel)
    print(f"kernel {name:<22} {shape:<34} max_abs_err={err:.3e} "
          f"rel={rel:.3e} {'pass' if ok else 'FAIL'}", flush=True)
    return row


def phase_kernels(cfg: dict) -> list:
    import jax
    import jax.numpy as jnp

    from flaxdiff_tpu.ops import fused_adaln as fa
    from flaxdiff_tpu.ops import fused_norm as fn
    from flaxdiff_tpu.ops.attention import (_xla_attention,
                                            dot_product_attention)

    b = cfg["kernel_batch"]
    key = jax.random.PRNGKey(0)

    def rnd(i, shape, dtype=jnp.bfloat16, scale=1.0, shift=0.0):
        return (jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale + shift
                ).astype(dtype)

    rows = []
    groups = cfg["norm_groups"]
    for side, c in cfg["groupnorm"]:
        x = rnd(1, (b, side, side, c), shift=0.5)
        scale = rnd(2, (c,), jnp.float32, 0.2, 1.0)
        bias = rnd(3, (c,), jnp.float32, 0.2)
        rows.append(_compare(
            "fused_groupnorm_silu", f"[{b},{side},{side},{c}] bf16",
            lambda x, s, z: fn.fused_groupnorm_silu(x, s, z, groups=groups),
            lambda x, s, z: fn._xla_groupnorm_silu(x, s, z, groups, 1e-6,
                                                   True),
            (x, scale, bias)))
    for l, f2 in cfg["geglu"]:
        rows.append(_compare(
            "fused_geglu", f"[{b},{l},{f2}] bf16",
            fa.fused_geglu, fa._xla_geglu, (rnd(4, (b, l, f2)),)))
    h, d = cfg["heads"], cfg["dim_head"]
    for lq, lk in cfg["flash"]:
        q, k, v = (rnd(5, (b, lq, h, d)), rnd(6, (b, lk, h, d)),
                   rnd(7, (b, lk, h, d)))
        rows.append(_compare(
            "flash_attention", f"q[{b},{lq},{h},{d}] kv{lk} bf16",
            lambda q, k, v: dot_product_attention(q, k, v, backend="flash"),
            _xla_attention, (q, k, v)))
    for l, c in cfg["adaln"]:
        x, hres = rnd(8, (b, l, c), shift=0.3), rnd(9, (b, l, c))
        s1, b1, s2, b2, gate = (rnd(10 + i, (b, 1, c), scale=0.3)
                                for i in range(5))
        shape = f"[{b},{l},{c}] bf16"
        rows.append(_compare(
            "fused_ln_modulate", shape, fa.fused_ln_modulate,
            lambda x, s, z: fa._xla_ln_modulate(x, ((s, z),), 1e-5)[0],
            (x, s1, b1)))
        rows.append(_compare(
            "fused_ln_modulate2", shape, fa.fused_ln_modulate2,
            lambda x, s1, b1, s2, b2: fa._xla_ln_modulate(
                x, ((s1, b1), (s2, b2)), 1e-5),
            (x, s1, b1, s2, b2)))
        rows.append(_compare(
            "fused_gate_residual", shape, fa.fused_gate_residual,
            lambda x, g, hh: x + g * hh, (x, gate, hres)))

    bad = [r for r in rows if not r["ok"]]
    print(f"kernels: {len(rows) - len(bad)}/{len(rows)} pass "
          f"(tolerance {KERNEL_TOL:.3e} of max(1, |reference|))",
          flush=True)
    for r in bad:
        if "exception" in r:
            raise r["exception"]       # the compiler's own refusal
    if bad:
        raise SmokeFailure("kernel disagrees with its XLA composition: "
                           + ", ".join(f"{r['kernel']} {r['shape']}"
                                       for r in bad))
    return rows


# ---------------------------------------------------------------------------
# phase 2 / 5: train
# ---------------------------------------------------------------------------

def phase_train(cfg: dict, ckpt_dir: str, mesh_fsdp: int = 1) -> dict:
    import jax
    import numpy as np

    import train

    n_dev = jax.device_count()
    argv = [
        "--architecture", "unet",
        "--model_config", json.dumps(cfg["model_config"]),
        "--dtype", "bfloat16",
        "--image_size", str(cfg["image_size"]),
        "--batch_size", str(cfg["batch_per_chip"] * n_dev),
        "--dataset", "synthetic", "--text_encoder", "hash",
        "--total_steps", str(cfg["train_steps"]),
        "--warmup_steps", "2", "--log_every", "2",
        "--save_every", str(cfg["val_every"]),
        "--val_every", str(cfg["val_every"]),
        "--val_samples", "4", "--val_steps", str(cfg["val_steps"]),
        "--mesh_fsdp", str(mesh_fsdp),
        "--checkpoint_dir", ckpt_dir,
    ]
    # CLIP's 77x768 context needs weights from the network, which the
    # chip machine does not have; the hash encoder's context is 77x64.
    print("train: text context is the hash encoder's 77x64, not CLIP's "
          "77x768 (no network on the chip machine)", flush=True)
    hist = train.main(argv)

    losses = list(hist["loss"])
    if not losses or not np.isfinite(losses).all() \
            or not np.isfinite(hist["final_loss"]):
        raise SmokeFailure(f"train: non-finite loss {losses}")
    steps = sorted(int(d) for d in os.listdir(ckpt_dir) if d.isdigit())
    if not steps:
        raise SmokeFailure(f"train: no checkpoint step under {ckpt_dir}")
    log_path = os.path.join(ckpt_dir, "train_log.jsonl")
    with open(log_path) as f:
        logged = [json.loads(line) for line in f]
    if not any(k.startswith("val/") for rec in logged for k in rec):
        raise SmokeFailure("train: no validation pass in train_log.jsonl")
    mfu = list(hist["mfu"])
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu and (not mfu or any(m is None for m in mfu)):
        # a None on a TPU is a failed cost analysis or a missed peak
        raise SmokeFailure(f"train: MFU entries are not numbers: {mfu}")
    print(f"train: mesh_fsdp={mesh_fsdp} losses={losses} mfu={mfu} "
          f"imgs_per_sec={hist['imgs_per_sec']} checkpoints={steps}",
          flush=True)
    return {"losses": losses, "mfu": mfu,
            "imgs_per_sec": list(hist["imgs_per_sec"]),
            "checkpoint_steps": steps}


# ---------------------------------------------------------------------------
# phase 3: sample
# ---------------------------------------------------------------------------

def phase_sample(cfg: dict, ckpt_dir: str):
    import numpy as np

    from flaxdiff_tpu.inference import DiffusionInferencePipeline

    pipe = DiffusionInferencePipeline.from_checkpoint(ckpt_dir)
    n, size = cfg["sample_n"], cfg["image_size"]
    out = pipe.generate_samples(
        resolution=size, diffusion_steps=cfg["sample_steps"],
        sampler="ddim", guidance_scale=3.0, prompts=PROMPTS[:n])
    if out.shape != (n, size, size, 3):
        raise SmokeFailure(f"sample: shape {out.shape}, expected "
                           f"{(n, size, size, 3)}")
    if not np.isfinite(out).all():
        raise SmokeFailure("sample: non-finite values")
    print(f"sample: ddim x{cfg['sample_steps']} guidance 3.0 -> "
          f"{out.shape} min={out.min():.3f} max={out.max():.3f}",
          flush=True)
    return pipe


# ---------------------------------------------------------------------------
# phase 4: serve
# ---------------------------------------------------------------------------

def phase_serve(cfg: dict, pipe) -> dict:
    import numpy as np

    from flaxdiff_tpu.serving import SampleRequest, ServingScheduler

    size = cfg["image_size"]

    def request(i, nfe):
        return SampleRequest(resolution=size, diffusion_steps=nfe,
                             sampler="ddim", guidance_scale=3.0,
                             seed=100 + i, prompts=[PROMPTS[i]])

    reqs = [request(i, nfe) for i, nfe in enumerate(cfg["serve_nfe"])]
    reqs.append(request(0, cfg["serve_nfe"][0]))    # repeat of the first
    results = []
    with ServingScheduler(pipeline=pipe) as sched:
        programs_before_repeat = None
        for i, req in enumerate(reqs):
            if i == len(reqs) - 1:
                programs_before_repeat = sched.engine.program_cache_size
            # .result() re-raises a ServingFault (or any other failure)
            res = sched.submit(req).result(timeout=900)
            results.append(res)
            print(f"serve: request {i} nfe={req.diffusion_steps} "
                  f"latency_ms={res.latency_ms:.0f} "
                  f"compile_ms={res.compile_ms:.0f} rounds={res.rounds} "
                  f"attempts={res.attempts}", flush=True)
        programs_after = sched.engine.program_cache_size
    for i, res in enumerate(results):
        if res.samples.shape != (1, size, size, 3) \
                or not np.isfinite(res.samples).all():
            raise SmokeFailure(f"serve: request {i} samples "
                               f"{res.samples.shape} wrong or non-finite")
        if res.attempts:
            raise SmokeFailure(f"serve: request {i} needed "
                               f"{res.attempts} retried attempts")
    repeat = results[-1]
    if programs_after != programs_before_repeat or repeat.compile_ms:
        raise SmokeFailure(
            "serve: the repeated request compiled something new "
            f"(programs {programs_before_repeat} -> {programs_after}, "
            f"compile_ms {repeat.compile_ms})")
    if not np.array_equal(repeat.samples, results[0].samples):
        raise SmokeFailure("serve: the repeated request's samples differ "
                           "from the first's (same fields, same seed)")
    return {"latency_ms": [round(r.latency_ms, 1) for r in results],
            "compile_ms": [round(r.compile_ms, 1) for r in results],
            "programs": programs_after}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on the CPU through the Pallas "
                         "interpreter (debugging and tests)")
    args = ap.parse_args(argv)
    if args.rehearse:
        # before jax is imported: the platform and the three hooks that
        # route every fused op through the Pallas interpreter
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["FLAXDIFF_FLASH_INTERPRET"] = "1"
        os.environ["FLAXDIFF_FUSED_NORM"] = "interpret"
        os.environ["FLAXDIFF_FUSED_ADALN"] = "interpret"
    cfg = REHEARSAL if args.rehearse else FLAGSHIP

    report = {"ok": False, "device": None, "phases": {}}
    if args.rehearse:
        report["rehearsal"] = True
    t_start = time.perf_counter()

    # phase 0 runs before anything can compile; a missing chip exits
    # here with no result line
    device = phase_device(args.rehearse)
    report["device"] = device
    report["phases"]["device"] = {
        "s": round(time.perf_counter() - t_start, 2), "compile_s": 0.0}

    from flaxdiff_tpu.utils import configure_compilation_cache
    meter = CompileMeter()
    report["compile_cache_dir"] = configure_compilation_cache()
    print(f"compilation cache: {report['compile_cache_dir']}", flush=True)

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")   # checkpoints only

    def run_phase(name, fn):
        t0, c0 = time.perf_counter(), meter.seconds
        print(f"=== phase {name} ===", flush=True)
        try:
            out = fn()
        finally:
            report["phases"][name] = {
                "s": round(time.perf_counter() - t0, 2),
                "compile_s": round(meter.seconds - c0, 2)}
        return out

    try:
        rows = run_phase("kernels", lambda: phase_kernels(cfg))
        report["kernels_passed"] = len(rows)
        ckpt = os.path.join(workdir, "run")
        report["train"] = run_phase("train",
                                    lambda: phase_train(cfg, ckpt))
        pipe = run_phase("sample", lambda: phase_sample(cfg, ckpt))
        report["serve"] = run_phase("serve",
                                    lambda: phase_serve(cfg, pipe))
        if device["count"] > 1:
            report["train_fsdp"] = run_phase(
                "fsdp", lambda: phase_train(
                    cfg, os.path.join(workdir, "run_fsdp"),
                    mesh_fsdp=device["count"]))
        report["ok"] = True
    except BaseException as e:
        report["failed"] = f"{type(e).__name__}: {e}"[:500]
        raise
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        report["total_s"] = round(time.perf_counter() - t_start, 2)
        report["compile_s"] = round(meter.seconds, 2)
        report["compile_cache"] = {"hits": meter.hits,
                                   "misses": meter.misses}
        os.makedirs("chiprun_out", exist_ok=True)
        with open(os.path.join("chiprun_out", "chip_smoke.json"), "w") as f:
            json.dump(report, f, indent=1)
        sys.stderr.flush()
        print("report " + json.dumps(report), flush=True)
        # the last stdout line: these two keys and no others
        print(json.dumps({"ok": report["ok"], "device": device}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
