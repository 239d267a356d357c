"""The serving kinds' shared driver: a `DiffusionInferencePipeline`
whose weights are made on the device from the seed, behind a
`ServingScheduler` with its default configuration, under closed-loop
clients or an open-loop arrival clock.
"""
from __future__ import annotations

import gc
import os
import threading
import time
from typing import Any, Dict

import numpy as np

from . import check, device, layer_metrics, loadgen, models, trace, weights


class SeededContextEncoder:
    """Stand-in for the text encoder (as the program's hash encoder
    stands in for CLIP): whatever it is asked to encode, it returns the
    seeded null context. The pipeline only asks it for the embedding of
    the empty prompt; requests carry pre-encoded conditioning."""

    key = "text"

    def __init__(self, null_ctx: np.ndarray):
        self._null = null_ctx

    def __call__(self, data):
        return np.broadcast_to(self._null,
                               (len(data),) + self._null.shape[1:]).copy()

    def serialize(self):
        return {"type": "seeded_context"}


def build_pipeline(cfg: Dict[str, Any], seed: int, null_ctx: np.ndarray):
    from flaxdiff_tpu.inference import DiffusionInferencePipeline
    from flaxdiff_tpu.inputs import (ConditionalInputConfig,
                                     DiffusionInputConfig)

    _, _, _, shapes = models.build(cfg)
    raw_key, ema_key = serve_keys(seed)
    hold_ema = bool(cfg.get("serve", {}).get("hold_ema", True))
    # a top-level subtree at a time: the peak of set-up is the finished
    # trees plus one subtree's float32 normals, not every leaf's
    maker = weights.Maker(shapes)
    params = {"params": maker.make(raw_key)}
    ema = {"params": maker.make(ema_key)} if hold_ema else None
    pipe = DiffusionInferencePipeline.from_config(
        {"model": dict(cfg["model"], name=cfg["registry_name"]),
         "schedule": dict(cfg["schedule"]), "predictor": cfg["predictor"]},
        params=params, ema_params=ema)
    res, ch = cfg["input"]["resolution"], cfg["input"]["channels"]
    pipe.input_config = DiffusionInputConfig(
        sample_data_key="sample", sample_data_shape=(res, res, ch),
        conditions=[ConditionalInputConfig(
            encoder=SeededContextEncoder(null_ctx))])
    return pipe, shapes, (ema_key if hold_ema else raw_key)


def serve_keys(seed: int):
    import jax
    return tuple(jax.random.split(jax.random.PRNGKey(weights.seed32(seed))))


def request_fields(cfg, traffic, seed: int, index: int, nfe: int
                   ) -> Dict[str, Any]:
    return {"index": index, "nfe": int(nfe),
            "seed": (weights.seed32(seed) * 31 + index * 7 + 1) % (2 ** 31 - 1),
            "guidance": float(traffic["guidance_scale"]),
            "images": int(traffic["images_per_request"]),
            "sampler": traffic["sampler"]}


def make_request(cfg, fields: Dict[str, Any], bench_seed: int):
    from flaxdiff_tpu.serving import SampleRequest
    tok, feat = (cfg["conditioning"]["tokens"],
                 cfg["conditioning"]["features"])
    return SampleRequest(
        num_samples=fields["images"], resolution=cfg["input"]["resolution"],
        channels=cfg["input"]["channels"], diffusion_steps=fields["nfe"],
        sampler=fields["sampler"], guidance_scale=fields["guidance"],
        seed=fields["seed"],
        conditioning=np.repeat(weights.request_context(
            bench_seed, fields["index"], tok, feat), fields["images"], axis=0))


def warm_engine(engine, cfg, traffic, seed, buckets, round_steps) -> int:
    """Drive every shape the window can meet through the engine's own
    dispatch path before admission opens (as `engine.prewarm` does, for
    more shapes): a full round and a terminal for every batch bucket and
    every count of rows that finish together, and one request of every
    dealt NFE. Returns the number of compiled programs."""
    from flaxdiff_tpu.serving.request import ServingFuture
    from flaxdiff_tpu.serving.scheduler import _block_until_ready, bucket_up

    t = time.perf_counter()
    before = engine.program_cache_size

    def rows_of(nfes):
        return [engine.prepare(make_request(cfg, request_fields(
            cfg, traffic, seed, 10 ** 6 + i, nfe), seed),
            ServingFuture(), t, t) for i, nfe in enumerate(nfes)]

    def drive(rows):
        live = rows
        while live:
            finished, _ = engine.advance(live, bucket_up(len(live), buckets),
                                         round_steps)
            live = [r for r in live if r.remaining > 0]
            if finished:
                out, _ = engine.finalize(
                    finished, bucket_up(len(finished), buckets))
                _block_until_ready(out)

    rs = round_steps or 8
    lo = 0
    for b in sorted(set(buckets)):
        for n in range(lo + 1, b + 1):
            drive(rows_of([rs] * n))
        lo = b
    drive(rows_of([int(k) for k in traffic["nfe_deal"]]))
    return engine.program_cache_size - before


def _counters(tel, names) -> Dict[str, float]:
    return {n: float(tel.counter(n).value) for n in names}


# the counters the harness itself needs; a cell's per-layer files name
# the others they read (`layer_metrics.counters_named`)
COUNTERS = ("serving/rows_real", "serving/rounds", "serving/rows_padded")


def run(cell, args, found, meter, t_start) -> Dict[str, Any]:
    import jax

    from flaxdiff_tpu.serving import SchedulerConfig, ServingScheduler
    from flaxdiff_tpu.telemetry import Telemetry

    rehearse = args.rehearse
    cfg = models.effective_config(cell.config, rehearse)
    traffic, kind = cell.traffic, cell.traffic["kind"]
    tok, feat = (cfg["conditioning"]["tokens"],
                 cfg["conditioning"]["features"])
    peaks = device.peaks_for(found["kind"], rehearse)
    devices = jax.devices()[:cell.chips]
    null_ctx = weights.null_context(tok, feat)

    if args.control:
        return _control_only(cfg, traffic, null_ctx, devices, args, t_start)
    pipe, shapes, served_key = build_pipeline(cfg, args.seed, null_ctx)
    counters = tuple(dict.fromkeys(
        COUNTERS + layer_metrics.counters_named(cell.per_layer)))
    print(f"config: {cfg['name']} {models.count_params(shapes) / 1e6:.1f} M "
          "parameters per tree", flush=True)
    tel = Telemetry(enabled=False)
    sconf = SchedulerConfig()
    sched = ServingScheduler(pipeline=pipe, telemetry=tel, autostart=False,
                             config=sconf)
    n_prog = warm_engine(sched.engine, cfg, traffic, args.seed,
                         sconf.batch_buckets, sconf.round_steps)
    print(f"warmed {n_prog} programs (buckets {sconf.batch_buckets}, "
          f"round_steps {sconf.round_steps})", flush=True)
    sched.start()
    # a full collection walks every object set-up made (the traced
    # programs' jaxprs: millions at 28 blocks) with every thread stopped;
    # as a long-lived server does after warming up, collect once and put
    # what survives out of the collector's sight for the window (PERF.md)
    gc.collect()
    gc.freeze()

    horizon = 100000
    nfes = loadgen.dealt_nfe(args.seed, traffic["nfe_deal"], horizon)
    fields_of = lambda i: request_fields(cfg, traffic, args.seed, i, nfes[i])
    submit = lambda i: sched.submit(make_request(cfg, fields_of(i),
                                                 args.seed))
    rec, stop = loadgen.Recorder(), threading.Event()
    block = sum(int(c) for c in traffic["nfe_deal"].values())
    n_warm = int(traffic.get("warm_blocks", 1)) * block
    seconds = args.seconds
    trace_dir = os.path.join(args.out_dir, "trace")

    if kind == "closed_loop":
        threads = loadgen.closed_loop(submit, int(traffic["clients"]), stop,
                                      rec, fields_of)
        while len(rec.snapshot()) < n_warm:       # the loop reaches its
            time.sleep(0.005)                     # steady state in set-up
        t0 = time.perf_counter()
    else:
        # the arrival clock starts with the window; warm requests first
        for i in range(n_warm):
            submit(horizon - 1 - i).result(timeout=600)
        due = loadgen.arrivals(
            args.seed, int(traffic["rate_hz"] * seconds * 1.5) + 8,
            traffic["rate_hz"], traffic.get("shape", "poisson"),
            traffic.get("peak_factor", 1.0), traffic.get("burst_len", 1),
            traffic.get("burst_idle_s", 0.0))
        due = [d for d in due if d < seconds]
        t0 = time.perf_counter()
        threads = loadgen.open_loop(submit, due, t0, stop, rec, fields_of,
                                    int(traffic.get("submit_workers", 2)))
    setup_s = t0 - t_start
    before = meter.snapshot()
    c0 = _counters(tel, counters)

    window = None
    if args.trace:
        # a short traced window inside the run: a few scheduler rounds
        rounds = int(traffic["trace_rounds"])
        time.sleep(0.2)
        c_tr0, t_tr0 = _counters(tel, counters), time.perf_counter()
        n_done0 = len(rec.snapshot())
        with trace.capture(trace_dir):
            with trace.span("window"):
                while (tel.counter("serving/rounds").value
                       - c_tr0["serving/rounds"]) < rounds \
                        and time.perf_counter() - t_tr0 < 30:
                    time.sleep(0.002)
                t_tr1 = time.perf_counter()
                c_tr1 = _counters(tel, counters)
        done_tr = rec.snapshot()[n_done0:]
        seconds = 0.0       # the traced run's end-to-end numbers are not
        #                     the cell's: no further window
    else:
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    t1 = time.perf_counter()
    stop.set()
    after = meter.snapshot()
    c1 = _counters(tel, counters)
    for t in threads:
        t.join(600)
    gc.unfreeze()
    compiled = after["compiles"] - before["compiles"]
    print(f"compilations inside the window: {compiled}", flush=True)

    done = rec.snapshot()
    inside = [d for d in done if t0 <= d.done_t <= t1]
    good = [d for d in inside if d.result is not None]
    failed = [d for d in done if d.result is None and d.done_t >= t0]
    retried = sum(int(d.result.attempts) for d in good)
    degraded = sum(1 for d in good if d.result.degraded)
    out: Dict[str, Any] = {"metrics": {"setup_s": setup_s}}
    out["attempted"] = len([d for d in done if d.done_t >= t0])
    out["failed"] = len(failed)
    print(f"window: {len(good)} requests completed inside {t1 - t0:.3f} s "
          f"({len(inside) - len(good)} failed, {retried} retried attempts, "
          f"{degraded} degraded); rounds "
          f"{c1['serving/rounds'] - c0['serving/rounds']:.0f}", flush=True)
    if len(inside) > 1:
        ts = sorted(d.done_t for d in inside)
        k = int(np.argmax(np.diff(ts)))
        print(f"longest pause between completions: {ts[k + 1] - ts[k]:.3f} "
              f"s, {ts[k] - t0:.1f} s into the window", flush=True)
    if kind == "open_loop" and done:
        late = [(d.sent_t - d.due_t) * 1e3 for d in done if d.done_t >= t0]
        print(f"generator lateness ms: p50 {np.percentile(late, 50):.3f} "
              f"max {max(late):.3f}", flush=True)
    if not args.trace:
        lat = np.asarray([d.latency_ms for d in good], np.float64)
        images = sum(int(d.fields["images"]) for d in good)
        print(f"latency sample count: {len(lat)}", flush=True)
        out["metrics"]["gen_img_per_s"] = images / (t1 - t0)
        # the same completions, counted in row-turns between the first
        # and the last of them: the throughput of a cell whose requests
        # are few. A window too short for it fails a cell that lists it.
        try:
            out["metrics"]["gen_row_turns_per_s"] = \
                loadgen.row_turns_per_s(good)
        except ValueError as e:
            if any(m["name"] == "gen_row_turns_per_s"
                   for m in cell.end_to_end):
                raise RuntimeError(f"gen_row_turns_per_s: {e}") from e
        if len(lat):
            out["metrics"]["request_ms_p50"] = float(np.percentile(lat, 50))
            out["metrics"]["request_ms_p95"] = float(np.percentile(lat, 95))
    out["memory_peak_bytes"] = device.memory_peak_bytes(devices)
    print(f"memory_stats of the fullest chip: {device.fullest_memory_stats(devices)}",
          flush=True)

    if args.trace:
        tr = trace.read(trace_dir)
        if not tr.devices and not rehearse:
            raise RuntimeError("the trace holds no device operation")
        window = layer_metrics.Window(
            trace=tr, interval=tr.window(), wall_s=t_tr1 - t_tr0,
            steps=0,        # rounds run a length of their own: the
            #                 readers take it from the `serve.round` spans
            images=sum(int(d.fields["images"]) for d in done_tr
                       if d.result is not None),
            chips=cell.chips,
            results=[d.result for d in done if d.result is not None],
            counters={k: c_tr1[k] - c_tr0[k] for k in counters},
            memory=device.fullest_memory_stats(devices), peaks=peaks, cfg=cfg,
            evals_per_row_step=2 if float(traffic["guidance_scale"]) else 1)
        out["window"] = window

    # -- correct: one request served twice returns equal samples; then,
    # with the program's state freed, a seeded sample of the requests the
    # window finished against the plain reference's own trajectories
    pool = [d for d in done if d.result is not None]
    if not pool:
        raise RuntimeError("no request finished: nothing to compare")
    ok_shapes = all(np.isfinite(d.result.samples).all() for d in pool)
    limits = check.load_limits(cfg, "serve")
    compared = []
    # equal fields and seed, equal samples: the scheduler is idle now, so
    # both go alone through the same bucket's programs and have to agree
    # to the last bit
    twice = [np.asarray(sched.submit(make_request(
        cfg, pool[0].fields, args.seed)).result(timeout=600).samples,
        np.float64) for _ in range(2)]
    compared.append(("repeat_gap",
                     "one request served twice, alone: largest gap",
                     float(np.abs(twice[0] - twice[1]).max()),
                     limits["repeat_max_abs"]))
    sched.close(drain=True)
    served = pick_served(pool, int(traffic["check_requests"]), args.seed)
    del sched, pipe, pool, done, inside, good, rec, twice
    gc.collect()
    gaps = reference_gaps(cfg, served, shapes, served_key, null_ctx,
                          args.seed, "", devices)
    compared += gap_rows(gaps, len(served), limits, "served")
    ok = check.verdict(compared)
    out["compared"] = compared
    if compiled:
        print(f"check: {compiled} compilation(s) inside the timed window  "
              "FAIL", flush=True)
    out["correct"] = bool(ok and ok_shapes and not compiled
                          and not out["failed"])
    out["compiled_in_window"] = compiled
    out["readings"] = dict(gaps, repeat_gap=compared[0][2])
    return out


def pick_served(pool, n_check: int, seed: int):
    """A sample, drawn from the seed, of the requests the window
    finished, the longest among them: [(fields, samples)]."""
    rng = np.random.default_rng([weights.seed32(seed), 49979687])
    longest = max(pool, key=lambda d: (d.fields["nfe"], -d.index))
    rest = [d for d in pool if d is not longest]
    picks = [longest] + [rest[i] for i in
                         rng.permutation(len(rest))[:n_check - 1]]
    return [(d.fields, np.asarray(d.result.samples)) for d in picks]


def gap_rows(gaps, n, limits, what):
    return [("sample_gap", f"mean abs gap of {n} {what} requests' samples "
             "to the reference's", gaps["sample_gap"],
             limits["sample_mean_abs"]),
            ("worst_request_gap", "the same, the worst request (nfe "
             f"{gaps['worst_nfe']})", gaps["worst_request_gap"],
             limits["sample_worst_request"])]


def _control_only(cfg, traffic, null_ctx, devices, args, t_start):
    """The control: for the first requests of the run's own deal (the
    first 50-step one among them), the reference's trajectory with its
    products in the next precision down, put in the served samples'
    place. The program is not built; `correct` has to come out false."""
    _, _, _, shapes = models.build(cfg)
    keys = serve_keys(args.seed)
    served_key = keys[1] if cfg.get("serve", {}).get("hold_ema", True) \
        else keys[0]
    n = int(traffic["check_requests"])
    nfes = loadgen.dealt_nfe(args.seed, traffic["nfe_deal"], 64)
    longest = max(range(64), key=lambda i: (nfes[i], -i))
    picks = [longest] + [i for i in range(64) if i != longest][:n - 1]
    res, ch = cfg["input"]["resolution"], cfg["input"]["channels"]
    served = [(request_fields(cfg, traffic, args.seed, i, nfes[i]),
               np.zeros((int(traffic["images_per_request"]), res, res, ch)))
              for i in picks]
    gaps = reference_gaps(cfg, served, shapes, served_key, null_ctx,
                          args.seed, args.control, devices)
    compared = gap_rows(gaps, len(served), check.load_limits(cfg, "serve"),
                        f"{args.control}-reference")
    ok = check.verdict(compared)
    return {"metrics": {"setup_s": time.perf_counter() - t_start},
            "attempted": len(served), "failed": 0, "correct": ok,
            "compared": compared,
            "memory_peak_bytes": device.memory_peak_bytes(devices),
            "readings": dict(gaps, repeat_gap=None), "window": None}


def reference_stages(cfg, maker, key):
    """(stages_of, make) for `reference.sample.serve_staged`: the
    forward pass of the configuration's family as ordered stages, and
    the maker of a stage's weights, rounded to the program's type and
    widened to float32. A family whose reference states no `stages` is
    one stage, the whole tree, made once."""
    import importlib
    family = importlib.import_module(f"reference.{cfg['family']}")
    names = tuple(maker.names())
    held: Dict[Any, Any] = {}
    if hasattr(family, "stages"):
        def stages_of(shape):
            return family.stages(cfg["model"], shape)
    else:
        def whole(parts, carry):
            return family.forward(dict(zip(names, parts)), cfg["model"],
                                  carry["x"], carry["t"], carry["text"])
        stages_of = lambda shape: [("all", names, whole)]   # noqa: E731

    def make(needs):
        if needs == names:          # one stage: nothing to make room for
            if needs not in held:
                held[needs] = maker.make(key, needs, widen=True)
            made = held[needs]
        else:
            made = maker.make(key, needs, widen=True)
        return tuple(made[n] for n in needs)

    return stages_of, make


def reference_gaps(cfg, served, shapes, served_key, null_ctx, bench_seed,
                   control: str, devices) -> Dict[str, Any]:
    """Mean absolute gap between the served samples and the plain
    reference's for the same requests: over all of them, and of the
    worst request. The reference walks every request's trajectory with
    one stage of float32 weights on the device at a time
    (`reference.sample.serve_staged`). With `control`, the reference in
    that lower precision stands in the program's place."""
    from reference import nn as ref_nn, sample as ref_sample
    t0 = time.perf_counter()
    maker = weights.Maker(shapes)
    stages_of, make = reference_stages(cfg, maker, served_key)
    tok, feat = (cfg["conditioning"]["tokens"],
                 cfg["conditioning"]["features"])
    res, ch = cfg["input"]["resolution"], cfg["input"]["channels"]
    requests = [{"seed": fields["seed"], "nfe": fields["nfe"],
                 "guidance": fields["guidance"],
                 "shape": (fields["images"], res, res, ch),
                 "cond": np.repeat(weights.request_context(
                     bench_seed, fields["index"], tok, feat),
                     fields["images"], axis=0),
                 "uncond": np.repeat(null_ctx, fields["images"], axis=0)}
                for fields, _ in served]
    stage_names = [(n, needs) for n, needs, _ in
                   stages_of((2 * requests[0]["shape"][0], res, res, ch))]
    largest = max(maker.nbytes(needs, widen=True) for _, needs in stage_names)
    peak = [0]

    def probe(_name):
        peak[0] = max(peak[0], device.live_bytes(devices))

    def run(prec):
        with ref_nn.precision(prec):
            return [np.asarray(x, np.float64) for x in
                    ref_sample.serve_staged(
                        stages_of, make, requests,
                        cfg["schedule"]["timesteps"], cfg["predictor"],
                        probe)]

    want = run("f32")
    got = run(control) if control else [np.asarray(s, np.float64)
                                        for _, s in served]
    per_request = [float(np.abs(g - w).mean()) for g, w in zip(got, want)]
    print(f"reference: {len(served)} requests through {len(stage_names)} "
          f"stage(s) in {time.perf_counter() - t0:.1f} s; most bytes alive "
          f"on a device with a stage loaded {peak[0]}, the largest stage's "
          f"float32 weights {largest}"
          + (f" (control: products in {control})" if control else ""),
          flush=True)
    worst = int(np.argmax(per_request))
    return {"sample_gap": float(np.mean(per_request)),
            "worst_request_gap": per_request[worst],
            "worst_nfe": served[worst][0]["nfe"]}
