#!/usr/bin/env python
"""Pipeline-parallel DiT training over a `pipe` mesh axis (no reference
analogue — the reference is single-host data-parallel only).

A SimpleDiT's transformer trunk is split into stages over the mesh's
`pipe` axis: each device holds a contiguous slice of the block stack,
GPipe microbatches march stage-to-stage via `ppermute` inside one
`lax.scan`, and reverse-mode AD through the scan is the backward
pipeline — the whole fill/steady/drain schedule lives inside a single
jitted train step. The embed/conditioning/final layers (a tiny share of
FLOPs) run replicated; `pipelined_dit_apply` reuses a normally-
initialized model's params, so the same checkpoint runs unpipelined on
one chip or pipelined on a pod.

Runs on an 8-virtual-device CPU mesh (data=2 x pipe=4) by default, and
checks the pipelined loss trajectory against plain `dit.apply` — same
params, same numbers.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--image_size", type=int, default=16)
    ap.add_argument("--pipe", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    if args.smoke:
        args.steps, args.pipe, args.microbatches = 4, 2, 2

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from flaxdiff_tpu.models.dit import SimpleDiT
    from flaxdiff_tpu.parallel import create_mesh, pipelined_dit_apply

    n = len(jax.devices())
    pipe = min(args.pipe, n)
    if n % pipe:
        raise SystemExit(f"--pipe {pipe} does not divide the "
                         f"{n}-device mesh")
    mesh = create_mesh(axes={"data": -1, "pipe": pipe})
    print(f"mesh: {dict(mesh.shape)}")

    dit = SimpleDiT(output_channels=3, patch_size=4, emb_features=32,
                    num_layers=2 * pipe, num_heads=2)
    key = jax.random.PRNGKey(0)
    x0 = jnp.zeros((1, args.image_size, args.image_size, 3))
    params = dit.init(key, x0, jnp.zeros((1,)),
                      jnp.zeros((1, 4, 32)))["params"]
    print(f"{2 * pipe} blocks -> {pipe} stages x {2} blocks, "
          f"{args.microbatches} microbatches "
          f"(bubble {(pipe - 1) / (args.microbatches + pipe - 1):.0%})")

    def loss_fn(params, x, t, txt, target, pipelined):
        if pipelined:
            out = pipelined_dit_apply(dit, params, x, t, txt, mesh,
                                      num_microbatches=args.microbatches)
        else:
            out = dit.apply({"params": params}, x, t, txt)
        return jnp.mean((out - target) ** 2)

    opt = optax.adam(2e-3)

    def make_step(pipelined):
        @jax.jit
        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(
                params, *batch, pipelined)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss
        return step

    def batch(i):
        r = np.random.default_rng(i)
        return (jnp.asarray(r.normal(size=(args.batch, args.image_size,
                                           args.image_size, 3)),
                            jnp.float32),
                jnp.asarray(r.uniform(size=(args.batch,)), jnp.float32),
                jnp.asarray(r.normal(size=(args.batch, 4, 32)),
                            jnp.float32),
                jnp.asarray(r.normal(size=(args.batch, args.image_size,
                                           args.image_size, 3)),
                            jnp.float32))

    fixed = batch(0)   # overfit one batch so the loss must descend

    # Pipeline-correctness check: at the SAME params, the pipelined
    # loss AND gradients must match the plain ones essentially bitwise
    # (measured 0.0 on the 8-virtual-device CPU mesh) — this is the
    # "same params, same numbers" claim, checked where it is exact.
    vg = jax.jit(lambda pl, p, b: jax.value_and_grad(loss_fn)(
        p, *b, pl), static_argnums=0)
    l_pipe, g_pipe = vg(True, params, fixed)
    l_plain, g_plain = vg(False, params, fixed)
    grad_drift = max(
        float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(g_pipe),
            jax.tree_util.tree_leaves(g_plain)))
    loss_drift0 = abs(float(l_pipe) - float(l_plain))
    print(f"same-params loss drift {loss_drift0:.2e}, "
          f"max grad drift {grad_drift:.2e}")
    assert loss_drift0 < 1e-6, loss_drift0
    assert grad_drift < 1e-5, grad_drift

    histories = {}
    for name, pipelined in (("pipelined", True), ("plain", False)):
        p, s = params, opt.init(params)
        step = make_step(pipelined)
        losses = []
        for _ in range(args.steps):
            p, s, loss = step(p, s, fixed)
            losses.append(float(loss))
        histories[name] = losses
        print(f"{name:9}: first {losses[0]:.5f} last {losses[-1]:.5f}")

    drift = max(abs(a - b) for a, b in zip(histories["pipelined"],
                                           histories["plain"]))
    print(f"max |pipelined - plain| loss drift over "
          f"{args.steps} steps: {drift:.2e}")
    # Trajectory drift is NOT a bitwise claim: the two train steps are
    # different XLA programs, so the fused adam epilogue rounds the
    # (identical — asserted above) gradients differently at the ulp
    # level, and adam's zero-init moments + sqrt(v)+eps normalization
    # amplify ulp-scale parameter differences to O(learning_rate) per
    # step — measured ~2.5 lr-quanta/step here (9.6e-3 over 4 steps at
    # lr=2e-3). The bound below is the amplification allowance; the
    # exactness claim lives in the same-params assert above.
    assert drift < args.steps * 5 * 2e-3, drift
    if args.steps >= 10:   # zero-init final_proj: a few steps barely move
        assert histories["pipelined"][-1] < histories["pipelined"][0]
    return {"final_loss": histories["pipelined"][-1], "drift": drift,
            "grad_drift": grad_drift}


if __name__ == "__main__":
    main()
