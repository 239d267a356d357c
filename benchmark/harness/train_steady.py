"""Traffic kind `train_steady`: seeded host batches through
`DiffusionTrainer.fit`, one object from set-up through the window.

Set-up builds the trainer (state made on the device from the seed, in
one jitted call), drives it through its first steps with the window's
own call and feed — the readings `correct` is decided from — and warms
the step. The window is one `fit` whose length is fixed beforehand from
the warm step time and is one log window: its clock runs from the call
of `fit` to the last step's loss on the host. (What `fit` does at a log
step besides, a copy of the best state leaf by leaf, costs half a second
on the UNet when the loss improved and nothing when it did not, so a
window with log steps inside would time the seed's luck: PERF.md.)
"""
from __future__ import annotations

import dataclasses
import gc
import itertools
import os
import time
from typing import Any, Dict, List

import numpy as np

from . import check, device, flops, layer_metrics, models, trace, weights


def _feed(batches: List[Dict[str, Any]], start: int):
    return (batches[i % len(batches)] for i in itertools.count(start))


def _adam_mu(opt_state):
    """Adam's first moment, wherever the optimizer's chain keeps it."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for node in opt_state:
            try:
                return _adam_mu(node)
            except ValueError:
                pass
    raise ValueError("no Adam first moment in the optimizer state")


def build_trainer(cfg: Dict[str, Any], traffic: Dict[str, Any], chips: int,
                  seed: int, null_ctx: np.ndarray):
    import jax
    import optax

    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import TRANSFORM_REGISTRY
    from flaxdiff_tpu.schedulers import get_schedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    tc = cfg["train"]
    if tc.get("remat"):
        cfg["model"]["remat"] = True
    _, apply_fn, init_fn, shapes = models.build(cfg)
    axes = {k: (chips if v == "chips" else int(v))
            for k, v in traffic["mesh"].items()}
    axes.setdefault("data", 1)
    mesh = create_mesh(axes=axes, devices=jax.devices()[:chips])
    sched = dict(cfg["schedule"])
    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn,
        tx=optax.adamw(tc["learning_rate"], b1=tc["b1"], b2=tc["b2"],
                       eps=tc["eps"], weight_decay=tc["weight_decay"]),
        schedule=get_schedule(sched.pop("name"), **sched),
        transform=TRANSFORM_REGISTRY[cfg["predictor"]](),
        mesh=mesh,
        config=TrainerConfig(
            uncond_prob=tc["uncond_prob"], normalize=tc["normalize"],
            weighted_loss=tc["weighted_loss"], ema_decay=tc["ema_decay"],
            seed=weights.seed32(seed), log_every=1),
        null_cond={"text": null_ctx})
    return trainer, init_fn, shapes


def _seed_step_flops(trainer, batch, per_device_flops: float) -> None:
    """`fit` asks `trainer.step_flops` for XLA's count at its first log
    window, which compiles the step a second time. The benchmark keeps
    its own count, so it answers the question beforehand."""
    import jax
    sub = trainer._numeric_subtree(trainer.put_batch(batch))
    key = tuple((jax.tree_util.keystr(p), x.shape) for p, x in
                jax.tree_util.tree_flatten_with_path(sub)[0])
    trainer._step_flops[key] = per_device_flops


def _fit(trainer, batches, start, steps, log_every, callbacks=()):
    trainer.config = dataclasses.replace(trainer.config, log_every=log_every)
    return trainer.fit(_feed(batches, start), total_steps=steps,
                       callbacks=callbacks)


def run(cell, args, found, meter, t_start) -> Dict[str, Any]:
    import jax

    rehearse = args.rehearse
    cfg = models.effective_config(cell.config, rehearse)
    traffic, chips = cell.traffic, cell.chips
    tc = cfg["train"]
    res, ch = cfg["input"]["resolution"], cfg["input"]["channels"]
    tok, feat = (cfg["conditioning"]["tokens"],
                 cfg["conditioning"]["features"])
    global_batch = tc["batch_per_chip"] * chips
    peaks = device.peaks_for(found["kind"], rehearse)
    devices = jax.devices()[:chips]

    null_ctx = weights.null_context(tok, feat)
    batches = weights.train_batches(args.seed, tc["host_batches"],
                                    global_batch, res, ch, tok, feat)
    if args.control:
        return _control_only(cfg, traffic, batches, null_ctx, devices,
                             args, t_start)
    (trainer, shapes, init_key, train_key, program_losses, first_grad,
     grad_norms, delta) = _first_steps(cfg, traffic, chips, args.seed,
                                       null_ctx, batches)
    n_check = int(traffic["check_steps"])
    print(f"config: {cfg['name']} "
          f"{models.count_params(trainer.state.params) / 1e6:.1f} M "
          f"parameters, {flops.forward_flops(cfg) / 1e9:.1f} GFLOP per image "
          f"forward, global batch {global_batch} on {chips} chip(s)",
          flush=True)

    # -- warm the window's shape of fit, and time a step -----------------
    warm = 12 if not rehearse else 6
    ticks: List[float] = []
    _fit(trainer, batches, n_check, warm, warm // 3,
         callbacks=[lambda *a: ticks.append(time.perf_counter())])
    # the shorter of the two intervals: the other may hold a best-state copy
    step_s = min(ticks[1] - ticks[0], ticks[2] - ticks[1]) / (warm // 3)
    n_steps = max(1, round(args.seconds / step_s))
    print(f"warm step {step_s * 1e3:.2f} ms; window = {n_steps} steps",
          flush=True)

    marks: List[float] = []
    losses: List[float] = []

    def on_log(step, loss, metrics):
        marks.append(time.perf_counter())
        losses.append(loss)

    setup_s = time.perf_counter() - t_start
    before = meter.snapshot()
    out: Dict[str, Any] = {"metrics": {}}
    window = None
    if not args.trace:
        t_fit = time.perf_counter()
        _fit(trainer, batches, n_check + warm, n_steps, n_steps,
             callbacks=[on_log])
        wall = marks[-1] - t_fit        # the last step's loss is on the host
        steps = n_steps
        rate = steps * global_batch / wall / chips
        print(f"window: {steps} steps, {steps * global_batch} images in "
              f"{wall:.3f} s", flush=True)
        out["metrics"]["train_img_per_s_chip"] = rate
        out["attempted"], out["failed"] = steps, 0
    else:
        n_tr = int(traffic["trace_steps"])
        trace_dir = os.path.join(args.out_dir, "trace")
        state = {"cap": None, "win": None}

        def on_log_traced(step, loss, metrics):
            losses.append(loss)
            if state["cap"] is None:
                state["cap"] = trace.capture(trace_dir).__enter__()
                state["win"] = trace.span("window").__enter__()
                marks.append(time.perf_counter())
            else:
                marks.append(time.perf_counter())
                state["win"].__exit__(None, None, None)
                state["cap"].__exit__(None, None, None)

        # steady steps only: without the best-state copy that `fit`
        # makes after a log step whose loss improved
        trainer.config = dataclasses.replace(trainer.config,
                                             keep_best_state=False)
        _fit(trainer, batches, n_check + warm, 2 * n_tr, n_tr,
             callbacks=[on_log_traced])
        # what a log step costs when the loss improved (`keep_best_state`,
        # the program's default, copies the state leaf by leaf): the same
        # two log windows again, untraced, with the copy forced at the
        # first log step; the second window's wall over the traced one's
        again: List[float] = []
        trainer.config = dataclasses.replace(trainer.config,
                                             keep_best_state=True)
        trainer.best_loss = float("inf")
        _fit(trainer, batches, n_check + warm + 2 * n_tr, 2 * n_tr, n_tr,
             callbacks=[lambda *a: again.append(time.perf_counter())])
        stall_ms = 1e3 * ((again[1] - again[0]) - (marks[-1] - marks[0]))
        print(f"log step with a best-state copy: {stall_ms:.1f} ms over a "
              f"window of {n_tr} steps without", flush=True)
        tr = trace.read(trace_dir)
        interval = tr.window()
        window = layer_metrics.Window(
            trace=tr, interval=interval, wall_s=marks[-1] - marks[0],
            steps=n_tr, images=n_tr * global_batch, chips=chips, results=[],
            counters={"fit/log_step_stall_ms": stall_ms},
            memory=device.fullest_memory_stats(devices), peaks=peaks,
            cfg=cfg)
        out["attempted"], out["failed"] = n_tr, 0
    after = meter.snapshot()
    compiled = after["compiles"] - before["compiles"]
    print(f"compilations inside the window: {compiled}", flush=True)
    out["memory_peak_bytes"] = device.memory_peak_bytes(devices)
    print(f"memory_stats of the fullest chip: {device.fullest_memory_stats(devices)}",
          flush=True)
    out["metrics"]["setup_s"] = setup_s
    finite = bool(np.isfinite(losses).all()) if losses else False

    if window is not None:
        if not window.trace.devices and not rehearse:
            raise RuntimeError("the trace holds no device operation")
        out["window"] = window

    # -- free the program's state, then follow it with the reference ------
    mesh_devices = list(trainer.mesh.devices.flat)
    del trainer
    gc.collect()
    ref = _reference(cfg, batches, shapes, init_key, train_key, null_ctx,
                     n_check, mesh_devices, "")
    ok, readings, compared = _compare(cfg, n_check, program_losses,
                                      first_grad, grad_norms, delta, ref)
    out["compared"] = compared
    if not finite:
        print("check: a loss in the window is not finite  FAIL", flush=True)
    if compiled:
        print(f"check: {compiled} compilation(s) inside the timed window  "
              "FAIL", flush=True)
    out["correct"] = bool(ok and finite and not compiled)
    out["compiled_in_window"] = compiled
    out["readings"] = readings
    return out


def _first_steps(cfg, traffic, chips, seed, null_ctx, batches):
    """Build the trainer from `seed` and drive it through its first steps
    with `fit`. Returns what `correct` compares, and the trainer."""
    import jax
    from reference import train as ref_train
    tc = cfg["train"]
    trainer, init_fn, shapes = build_trainer(cfg, traffic, chips, seed,
                                             null_ctx)
    _seed_step_flops(trainer, batches[0],
                     flops.train_flops_per_image(cfg) * tc["batch_per_chip"])
    n_check = int(traffic["check_steps"])
    h1 = _fit(trainer, batches, 0, 1, 1)
    first_grad = jax.device_get(jax.tree_util.tree_map(
        lambda m: m / (1.0 - tc["b1"]), _adam_mu(trainer.state.opt_state)))
    h2 = _fit(trainer, batches, 1, n_check - 1, 1) if n_check > 1 else \
        {"loss": []}
    init_key, train_key = ref_train.run_keys(weights.seed32(seed))
    with trainer.mesh:
        p0 = jax.jit(init_fn,
                     out_shardings=trainer.state_shardings.params)(init_key)
    delta = jax.device_get(ref_train.delta_norms(trainer.state.params, p0))
    del p0
    grad_norms = jax.tree_util.tree_map(
        lambda g: float(np.linalg.norm(np.asarray(g, np.float64))),
        first_grad)
    return (trainer, shapes, init_key, train_key,
            list(h1["loss"]) + list(h2["loss"]), first_grad, grad_norms,
            delta)


def _control_only(cfg, traffic, batches, null_ctx, devices, args, t_start):
    """The control: the reference with its products in the next
    precision down, put in the program's place and held to the same
    limits. The program is not built and no window is measured;
    `correct` has to come out false."""
    from reference import train as ref_train
    shapes = models.build(cfg)[3]
    init_key, train_key = ref_train.run_keys(weights.seed32(args.seed))
    n_check = int(traffic["check_steps"])
    ref = _reference(cfg, batches, shapes, init_key, train_key, null_ctx,
                     n_check, list(devices), "")
    ctl = _reference(cfg, batches, shapes, init_key, train_key, null_ctx,
                     n_check, list(devices), args.control)
    ok, readings, compared = _compare(
        cfg, n_check, ctl["losses"], ctl["first_grad"], ctl["grad_norms"],
        ctl["delta_norms"], ref)
    return {"metrics": {"setup_s": time.perf_counter() - t_start},
            "attempted": n_check, "failed": 0, "correct": ok,
            "compared": compared,
            "memory_peak_bytes": device.memory_peak_bytes(devices),
            "readings": readings, "window": None}


def _compare(cfg, n_check, program_losses, first_grad, grad_norms, delta,
             ref):
    limits = check.load_limits(cfg, "train")
    compared = []
    for i, (p, r) in enumerate(zip(program_losses, ref["losses"])):
        compared.append((f"loss_rel_{i}", f"loss[{i}] rel gap (program "
                         f"{p:.6f} reference {r:.6f})", abs(p - r) / abs(r),
                         limits["loss_rel"]))
    g, where = check.worst_leaf_gap(grad_norms, ref["grad_norms"])
    compared.append(("grad", f"first-gradient norm, worst leaf {where}", g,
                     limits["grad_norm_worst_leaf"]))
    gd, where = check.worst_leaf_difference(first_grad, ref["first_grad"])
    compared.append(("grad_diff", "first gradient, norm of the difference, "
                     f"worst leaf {where}", gd,
                     limits["grad_diff_worst_leaf"]))
    d, where = check.worst_leaf_gap(delta, ref["delta_norms"],
                                     ref["grad_norms"])
    compared.append(("delta", f"parameter-change norm after {n_check} "
                     f"steps, worst leaf {where}", d,
                     limits["delta_norm_worst_leaf"]))
    ok = check.verdict(compared)
    return ok, {"loss_rel": max(c[2] for c in compared[:-3]),
                "grad": g, "grad_diff": gd, "delta": d}, compared


def _reference(cfg, batches, shapes, init_key, train_key, null_ctx,
               steps, mesh_devices, control: str):
    """Follow the first steps in float32 (or, for the control, with the
    reference's products in a lower precision). Its float32 tree is made
    a subtree at a time, widened as it is made: never a tree in the
    program's type beside the float32 one."""
    import importlib

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from reference import nn as ref_nn, train as ref_train
    family = importlib.import_module(f"reference.{cfg['family']}")
    forward = family.forward
    n_dev = len(mesh_devices)
    mesh = Mesh(np.asarray(mesh_devices), ("rows",)) if n_dev > 1 else None
    t0 = time.perf_counter()
    params0 = weights.Maker(shapes).make(init_key, widen=True)
    if mesh is not None:
        params0 = jax.device_put(params0, NamedSharding(mesh, P()))
    block = int(cfg["train"].get("reference_block_rows",
                                 cfg["train"]["batch_per_chip"])) * n_dev
    block = min(block, batches[0]["sample"].shape[0])
    with ref_nn.precision(control or "f32"):
        out = ref_train.follow(
            forward, cfg["model"], params0, batches, train_key,
            jnp.asarray(null_ctx), cfg["train"], cfg["schedule"]["timesteps"],
            steps, block, mesh, cfg["predictor"])
    print(f"reference: {steps} steps in {time.perf_counter() - t0:.1f} s"
          + (f" (control: products in {control})" if control else ""),
          flush=True)
    return out
