"""The diffusion train step: one pure function, jitted once over the mesh.

Parity with reference trainer/general_diffusion_trainer.py:248-349
(normalize -> optional VAE encode -> CFG uncond dropout -> timestep
sampling -> forward diffusion -> weighted MSE -> grad -> EMA), with the
TPU-native differences:

- No shard_map / lax.pmean / local_device_index plumbing: the step is
  `jax.jit` over NamedSharding; XLA SPMD inserts the gradient
  reduce-scatter and batch-collectives (reference needed
  general_diffusion_trainer.py:325 pmean + diffusion_trainer.py:158
  fold_in(local_device_index)).
- RNG: one global key folded with the step counter; noise for the global
  batch is generated inside the jit program, sharded like the batch.
- Loss stays on device; the caller reads it back only at log cadence
  (the reference syncs every step for its NaN check,
  simple_trainer.py:542).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..predictors import PredictionTransform
from ..schedulers.common import NoiseSchedule, bcast_right
from ..telemetry.numerics import NumericsConfig, numerics_aux, probe_aux
from ..typing import Policy, PyTree
from ..utils import cfg_uncond_splice, normalize_images
from .train_state import TrainState


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    """Static configuration closed over by the jitted step."""

    uncond_prob: float = 0.12          # CFG dropout (reference training.py:213)
    ema_decay: float = 0.999
    normalize: bool = True             # (x-127.5)/127.5 inside the step
    weighted_loss: bool = True         # schedule loss weights (P2 / EDM)


def _make_loss_builder(apply_fn, schedule, transform, config,
                       policy, autoencoder, null_cond):
    """`(state, batch) -> loss_fn` shared by the train step and the
    numerics probe: the same forward-diffusion prep and RNG derivation,
    so a provenance re-run reproduces EXACTLY the step that produced
    the non-finite values (same noise, same timesteps, same dropout)."""

    def build(state: TrainState, batch: PyTree):
        rng = jax.random.fold_in(state.rng, state.step)
        noise_key, t_key, uncond_key, vae_key = jax.random.split(rng, 4)

        x0 = batch["sample"]
        if config.normalize:
            x0 = normalize_images(x0)
        else:
            x0 = x0.astype(jnp.float32)

        if autoencoder is not None:
            x0 = autoencoder.encode(x0, key=vae_key)

        cond = batch.get("cond", None)
        if cond is not None and null_cond is not None and config.uncond_prob > 0:
            uncond_mask = jax.random.bernoulli(
                uncond_key, config.uncond_prob, (x0.shape[0],))
            if isinstance(cond, dict) and isinstance(null_cond, dict):
                # splice per intersecting key: a null_cond prepared for
                # more modalities than this batch carries (e.g. text null
                # with an audio-only AV batch) must not be a structural
                # error — unmatched conditions pass through undropped.
                cond = {k: (cfg_uncond_splice(c, null_cond[k], uncond_mask)
                            if k in null_cond else c)
                        for k, c in cond.items()}
            else:
                cond = jax.tree_util.tree_map(
                    lambda c, u: cfg_uncond_splice(c, u, uncond_mask),
                    cond, null_cond)

        B = x0.shape[0]
        t = schedule.sample_timesteps(t_key, B)
        noise = jax.random.normal(noise_key, x0.shape, dtype=x0.dtype)
        x_t, target = transform.forward(schedule, x0, noise, t)

        c_in = bcast_right(transform.input_scale(schedule, t), x_t.ndim)
        x_in, t_in = schedule.transform_inputs(x_t * c_in, t.astype(jnp.float32))

        weights = (schedule.loss_weights(t) if config.weighted_loss
                   else jnp.ones_like(t, dtype=jnp.float32))

        def loss_fn(params):
            if policy is not None:
                params_c = policy.cast_to_compute(params)
                x_net = x_in.astype(policy.compute_dtype)
            else:
                params_c, x_net = params, x_in
            raw = apply_fn(params_c, x_net, t_in, cond).astype(jnp.float32)
            pred = transform.transform_output(x_t, t.astype(jnp.float32),
                                              raw, schedule)
            per_sample = jnp.mean(
                (pred - target) ** 2,
                axis=tuple(range(1, pred.ndim)))
            return jnp.mean(per_sample * weights)

        return loss_fn

    return build


def _nonfinite_gate(new_state: TrainState, state: TrainState, grads,
                    loss: jax.Array) -> Tuple[TrainState, jax.Array]:
    """In-graph non-finite gate (the fp16 DynamicScale mechanism,
    generalized): when this step's gradients or loss are non-finite the
    params/opt-state/EMA keep their PREVIOUS values via `jnp.where` —
    the poisoned update never lands, so the live state (and therefore
    any checkpoint taken from it) stays finite without the host ever
    fetching the loss. The step counter still advances: the next step
    folds a fresh rng. Returns `(gated_state, ok)`.

    With `state.gate_events` carried (TrainerConfig.gate_counter), a
    withheld step also accumulates its non-finite element counts into
    the visibility counter — the monitored twin must count like the
    plain step's `_finite_only_gate` or cadence steps would be a hole
    in the gate-activation series."""
    from ..telemetry.numerics import tree_nonfinite_count
    ok = jnp.logical_and(tree_nonfinite_count(grads) == 0,
                         jnp.isfinite(loss))

    def gate(n, o):
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(ok, a, b), n, o)

    gate_events = state.gate_events
    if gate_events is not None:
        zero = jnp.zeros((), jnp.int32)
        counts = jnp.stack([
            tree_nonfinite_count(new_state.params),
            tree_nonfinite_count(new_state.opt_state),
            (tree_nonfinite_count(new_state.ema_params)
             if state.ema_params is not None else zero)])
        gate_events = gate_events + jnp.where(ok, 0, counts)

    gated = new_state.replace(
        params=gate(new_state.params, state.params),
        opt_state=gate(new_state.opt_state, state.opt_state),
        ema_params=(gate(new_state.ema_params, state.ema_params)
                    if state.ema_params is not None else None),
        gate_events=gate_events)
    return gated, ok


def _finite_only_gate(new_state: TrainState,
                      state: TrainState) -> TrainState:
    """Elementwise non-finite gate for the PLAIN (un-monitored) step:
    every element of the updated params/opt-state/EMA keeps its
    previous value where the new one is non-finite — the live state is
    finite BY CONSTRUCTION, which is all the sync-free save path needs
    ("never checkpoint a NaN" with zero host syncs).

    Deliberately elementwise, NOT the global any-non-finite verdict
    `_nonfinite_gate` computes for the monitored twin: a global verdict
    makes every state select depend on EVERY gradient leaf, which
    extends all gradient buffer lifetimes across the whole optimizer
    update and defeats backward/optimizer fusion — measured ~4x XLA CPU
    compile time on the text-conditional 128x128 UNet (131 s vs 27 s
    ungated). The
    elementwise select fuses into the update computation: compile and
    step time are at the ungated baseline. In practice a poisoned batch
    propagates NaN through the loss to every update element, so both
    forms withhold the whole step; they differ only for partially
    non-finite updates, where this one commits the still-finite
    elements and the anomaly detector (which sees the window losses at
    log cadence) remains the recovery mechanism.

    Visibility (PR 5 follow-up): with `state.gate_events` present
    (TrainerConfig.gate_counter) the gate also counts, IN-GRAPH, how
    many elements it masked in params / opt_state / ema_params and
    accumulates the three counts into the carried [3] int32 — masking
    is otherwise silent by design, and "the gate fired N times" is the
    difference between one poisoned batch and a quietly-diverging run.
    The count is per-leaf-summed via the same `tree_nonfinite_count`
    the monitored aux uses; note it re-introduces a reduction over
    every leaf, which is exactly the XLA-CPU compile blowup the
    elementwise gate exists to avoid — that is why the counter is
    opt-in instead of free with the gate."""
    def gate(n, o):
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(jnp.isfinite(a), a, b), n, o)

    gate_events = state.gate_events
    if gate_events is not None:
        from ..telemetry.numerics import tree_nonfinite_count
        zero = jnp.zeros((), jnp.int32)
        gate_events = gate_events + jnp.stack([
            tree_nonfinite_count(new_state.params),
            tree_nonfinite_count(new_state.opt_state),
            (tree_nonfinite_count(new_state.ema_params)
             if state.ema_params is not None else zero)])

    return new_state.replace(
        params=gate(new_state.params, state.params),
        opt_state=gate(new_state.opt_state, state.opt_state),
        ema_params=(gate(new_state.ema_params, state.ema_params)
                    if state.ema_params is not None else None),
        gate_events=gate_events)


def make_train_step(
    apply_fn: Callable[[PyTree, jax.Array, jax.Array, Any], jax.Array],
    schedule: NoiseSchedule,
    transform: PredictionTransform,
    config: TrainStepConfig = TrainStepConfig(),
    policy: Optional[Policy] = None,
    autoencoder: Optional[Any] = None,
    null_cond: Optional[PyTree] = None,
    numerics: Optional[NumericsConfig] = None,
    gate_nonfinite: bool = False,
) -> Callable[[TrainState, PyTree], Tuple[TrainState, jax.Array]]:
    """Build the pure train step.

    apply_fn(params, x_t, t, cond) -> raw network output.
    Batch contract: {"sample": [B,...] images (uint8 or [-1,1] float),
    "cond": optional conditioning pytree (e.g. {"text": [B,L,D]})}.
    `null_cond` is the cached unconditional embedding tree used for the
    jnp.where CFG-dropout splice (the reference's correct semantics,
    inputs/__init__.py:122-137 — not the prefix-splice variant).

    With `numerics`, the step additionally computes the in-graph
    health aux (telemetry/numerics.py) and returns
    `(new_state, loss, aux)`; with `numerics.skip_nonfinite` a step
    whose gradients or loss are non-finite keeps the PREVIOUS
    params/opt-state/EMA via `jnp.where` — the same gating the fp16
    DynamicScale overflow path uses, so a poisoned batch never
    contaminates state. The trainer compiles this as a SECOND program
    and dispatches it only at the numerics cadence; off-cadence steps
    run the unmonitored program unchanged.

    With `gate_nonfinite` the PLAIN step (numerics=None) applies an
    ELEMENTWISE in-graph non-finite gate (`_finite_only_gate`): any
    non-finite element of the updated params/opt-state/EMA keeps its
    previous value, so the live state is finite BY CONSTRUCTION. This
    is what lets the pipelined fit loop drop the save-cadence loss
    fetch ("never checkpoint a NaN" becomes structural instead of a
    per-save host sync); the elementwise select fuses into the update
    computation — measured at zero compile/step cost, unlike the
    global verdict (see `_finite_only_gate`).
    """
    build_loss = _make_loss_builder(apply_fn, schedule, transform, config,
                                    policy, autoencoder, null_cond)

    def train_step(state: TrainState, batch: PyTree):
        loss_fn = build_loss(state, batch)

        if state.dynamic_scale is not None:
            grad_fn = state.dynamic_scale.value_and_grad(loss_fn)
            dyn, is_fin, loss, grads = grad_fn(state.params)
            new_state = state.apply_gradients(grads)
            # restore params/opt_state where the scaled grads overflowed
            # (reference diffusion_trainer.py:229-240)
            new_state = new_state.replace(
                params=jax.tree_util.tree_map(
                    lambda n, o: jnp.where(is_fin, n, o),
                    new_state.params, state.params),
                opt_state=jax.tree_util.tree_map(
                    lambda n, o: jnp.where(is_fin, n, o),
                    new_state.opt_state, state.opt_state),
                dynamic_scale=dyn,
            )
        else:
            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            new_state = state.apply_gradients(grads)

        new_state = new_state.apply_ema(config.ema_decay)
        if state.loss_ring is not None:
            # in-graph loss ring: slot step % W gets this step's RAW
            # loss (pre-gate — the ring is visibility, not a verdict),
            # so the host reads a whole window with one fetch per W
            # steps instead of one per step at log_every=1
            w = state.loss_ring.shape[0]
            new_state = new_state.replace(
                loss_ring=state.loss_ring.at[state.step % w].set(
                    loss.astype(state.loss_ring.dtype)))
        if numerics is None:
            if gate_nonfinite:
                new_state = _finite_only_gate(new_state, state)
            return new_state, loss

        gated = numerics.skip_nonfinite or gate_nonfinite
        if gated:
            # in-graph skip_step: the aux is computed AFTER gating —
            # grad_norm stays non-finite (it is the evidence) but
            # update_norm reads 0, the state really did not move
            new_state, ok = _nonfinite_gate(new_state, state, grads, loss)
        aux = numerics_aux(loss, grads, state.params, new_state.params,
                           per_module=numerics.per_module)
        if gated:
            aux["skipped"] = (~ok).astype(jnp.float32)
        return new_state, loss, aux

    return train_step


def make_grad_probe(
    apply_fn: Callable[[PyTree, jax.Array, jax.Array, Any], jax.Array],
    schedule: NoiseSchedule,
    transform: PredictionTransform,
    config: TrainStepConfig = TrainStepConfig(),
    policy: Optional[Policy] = None,
    autoencoder: Optional[Any] = None,
    null_cond: Optional[PyTree] = None,
) -> Callable[[TrainState, PyTree], PyTree]:
    """NaN-provenance pass: `(state, batch) -> probe_aux pytree` of
    per-top-level-module non-finite counts for grads AND params, plus
    the loss. Shares `_make_loss_builder` with the train step, so the
    probe replays the exact rng/noise/timesteps of the offending step —
    it updates NOTHING (no optimizer, no EMA) and must be jitted
    WITHOUT donation so the live state survives the re-run."""
    build_loss = _make_loss_builder(apply_fn, schedule, transform, config,
                                    policy, autoencoder, null_cond)

    def probe(state: TrainState, batch: PyTree) -> PyTree:
        loss_fn = build_loss(state, batch)
        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return probe_aux(loss, grads, state.params)

    return probe
