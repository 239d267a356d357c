"""DiffusionInferencePipeline: rebuild model from a config dict, restore a
checkpoint, generate with cached samplers.

Reference inference/pipeline.py:42-272. The wandb run-config store is
replaced by a plain serialized config dict (saved next to checkpoints by
the CLI); wandb-based construction can layer on top by fetching that dict.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from ..inputs import DiffusionInputConfig
from ..predictors import TRANSFORM_REGISTRY, PredictionTransform
from ..samplers import SAMPLER_REGISTRY, DiffusionSampler, Sampler
from ..samplers.common import rows_apart
from ..schedulers import get_schedule
from ..utils import RngSeq
from .registry import build_model

CONFIG_FILENAME = "pipeline_config.json"
from ..trainer.optim import TEMPLATE_FILENAME  # noqa: E402


def _sampler_cache_key(sampler_obj: Sampler, guidance_scale: float) -> Tuple:
    """Cache key carrying the sampler's full config, not just its
    class: `DDIMSampler(eta=0.0)` and `DDIMSampler(eta=1.0)` are
    different samplers and must not share a compiled DiffusionSampler.
    Fields are flax.struct dataclass fields; unhashable values (arrays)
    degrade to repr — stable enough for identity, never a collision
    back to class-only."""
    import dataclasses as _dc
    cfg = []
    for f in _dc.fields(sampler_obj):
        v = getattr(sampler_obj, f.name)
        try:
            hash(v)
        except TypeError:
            v = repr(v)
        cfg.append((f.name, v))
    return (type(sampler_obj), tuple(cfg), float(guidance_scale))


class DiffusionInferencePipeline:
    """Holds model + params + diffusion math; caches one DiffusionSampler
    per (sampler class + config, guidance scale) tuple (reference
    pipeline.py:176-215)."""

    def __init__(self, model, params: Dict[str, Any],
                 schedule, transform: PredictionTransform,
                 input_config: Optional[DiffusionInputConfig] = None,
                 autoencoder=None,
                 ema_params: Optional[Dict[str, Any]] = None,
                 config: Optional[Dict[str, Any]] = None):
        self.model = model
        self.params = params
        self.ema_params = ema_params
        self.schedule = schedule
        self.transform = transform
        self.input_config = input_config
        self.autoencoder = autoencoder
        self.config = config or {}
        self._sampler_cache: Dict[Tuple, DiffusionSampler] = {}

    # -- construction --------------------------------------------------------
    @staticmethod
    def from_config(config: Dict[str, Any], params: Dict[str, Any],
                    ema_params: Optional[Dict[str, Any]] = None,
                    autoencoder=None) -> "DiffusionInferencePipeline":
        """config = {"model": {"name": ..., **kwargs}, "schedule":
        {"name": ..., **kwargs}, "predictor": name, "input_config": ...}."""
        model_cfg = dict(config["model"])
        model = build_model(model_cfg.pop("name"), **model_cfg)
        sched_cfg = dict(config.get("schedule", {"name": "cosine"}))
        schedule = get_schedule(sched_cfg.pop("name"), **sched_cfg)
        pred_name = config.get("predictor", "epsilon")
        if pred_name not in TRANSFORM_REGISTRY:
            raise ValueError(f"unknown predictor {pred_name!r}")
        transform = TRANSFORM_REGISTRY[pred_name]()
        input_config = None
        if config.get("input_config"):
            input_config = DiffusionInputConfig.deserialize(
                config["input_config"])
        return DiffusionInferencePipeline(
            model=model, params=params, ema_params=ema_params,
            schedule=schedule, transform=transform,
            input_config=input_config, autoencoder=autoencoder,
            config=config)

    @staticmethod
    def from_registry(registry_path: str, metric: str = "loss",
                      autoencoder=None) -> "DiffusionInferencePipeline":
        """Load the best run for `metric` from a ModelRegistry
        (reference pipeline.py:103-147 from_wandb_registry, over the local
        registry.json instead of the wandb model registry)."""
        from ..trainer.registry import ModelRegistry
        best = ModelRegistry(registry_path).best_run(metric)
        if best is None:
            raise FileNotFoundError(
                f"registry {registry_path} has no best run for "
                f"metric {metric!r}")
        # the registry records the STEP that achieved the best value;
        # load it if it is still on disk (max_to_keep rotates old steps)
        from ..trainer.checkpoints import Checkpointer
        ck = Checkpointer(best["checkpoint_dir"])
        steps = ck.all_steps()
        ck.close()
        step = best.get("step") if best.get("step") in steps else None
        if step is None and best.get("step") is not None:
            import warnings
            warnings.warn(
                f"registry best step {best['step']} no longer on disk "
                f"under {best['checkpoint_dir']}; loading latest",
                stacklevel=2)
        return DiffusionInferencePipeline.from_checkpoint(
            best["checkpoint_dir"], step=step, autoencoder=autoencoder)

    @staticmethod
    def from_wandb_run(run_path: str,
                       artifact: Optional[str] = None,
                       cache_dir: Optional[str] = None,
                       autoencoder=None) -> "DiffusionInferencePipeline":
        """Rebuild a pipeline from a wandb run's logged model artifact
        (reference inference/pipeline.py:59-147 from_wandb_run).

        `run_path` is "entity/project/run_id". The artifact directory is
        the checkpoint directory push_artifact uploaded — including
        pipeline_config.json — so this is a thin layer over
        from_checkpoint. `artifact` selects a specific "name:alias";
        default is the run's most recent model-type artifact."""
        import wandb
        api = wandb.Api()
        run = api.run(run_path)
        if artifact is not None:
            art = api.artifact(artifact, type="model")
        else:
            arts = [a for a in run.logged_artifacts()
                    if getattr(a, "type", None) == "model"]
            if not arts:
                raise FileNotFoundError(
                    f"run {run_path} logged no model artifacts")
            art = arts[-1]
        local = art.download(root=cache_dir)
        return DiffusionInferencePipeline.from_checkpoint(
            local, autoencoder=autoencoder)

    @staticmethod
    def from_checkpoint(checkpoint_dir: str,
                        step: Optional[int] = None,
                        autoencoder=None) -> "DiffusionInferencePipeline":
        """Load the config dict + state saved by the training CLI."""
        # epath for every sidecar read so gs:// checkpoint dirs work the
        # same as local ones (the shard restore already goes through
        # orbax's own object-store layer)
        from etils import epath
        cfg_path = epath.Path(checkpoint_dir) / CONFIG_FILENAME
        config = json.loads(cfg_path.read_text())

        from ..trainer.checkpoints import Checkpointer
        ckpt = Checkpointer(checkpoint_dir)
        # topology-free host restore: inference may run on a different
        # device layout than training wrote the shards from
        state, _meta = ckpt.restore_to_host(step)
        params = state["params"]
        ema = state.get("ema_params")
        ckpt.close()

        # a flat-params run (TrainerConfig.flat_params) checkpoints the
        # state as per-dtype vectors; the training CLI saved the param
        # template beside the config, so inference restores the
        # structured tree the model expects
        from ..trainer.optim import (deserialize_template, is_flat_params,
                                     unflatten_params)
        # the config flag is authoritative; the structural heuristic
        # covers checkpoints written before the flag existed
        if config.get("flat_params") or is_flat_params(params):
            tmpl_path = epath.Path(checkpoint_dir) / TEMPLATE_FILENAME
            if not tmpl_path.exists():
                raise FileNotFoundError(
                    f"{checkpoint_dir} holds a flat-params checkpoint "
                    f"but no {TEMPLATE_FILENAME}; re-save from the "
                    "trainer (train.py writes it automatically) or "
                    "unflatten manually with trainer.optim")
            template = deserialize_template(json.loads(
                tmpl_path.read_text()))
            params = unflatten_params(template, params)
            if ema is not None and is_flat_params(ema):
                ema = unflatten_params(template, ema)
        # one upload to the default device: host leaves would cross to
        # the device again on every sampler call and serving round
        params = jax.device_put(params)
        ema = jax.device_put(ema) if ema is not None else None
        return DiffusionInferencePipeline.from_config(
            config, params=params, ema_params=ema, autoencoder=autoencoder)

    # -- sampling ------------------------------------------------------------
    def get_sampler(self, sampler: str | Sampler | Type[Sampler] = "ddim",
                    guidance_scale: float = 0.0,
                    cache_plan=None) -> DiffusionSampler:
        """`cache_plan` (ops.diffcache.CachePlan, or an
        ops.spatialcache ComposedPlan/SpatialPlan for the token-level
        axis) activates the training-free activation cache
        (docs/CACHING.md). The plan is NORMALIZED first — degenerate
        axes route to the simpler program byte-for-byte (spatial
        keep 1.0 -> the timestep-cached program, refresh_every=1 ->
        the uncached one) — then folded into the sampler cache key, so
        two effective plans never share a compiled DiffusionSampler,
        mirroring the DDIM-eta key rule."""
        from ..ops.diffcache import resolve_cache_fns
        from ..ops.spatialcache import (ComposedPlan,
                                        resolve_composed_fns,
                                        resolve_plan)
        if isinstance(sampler, str):
            if sampler not in SAMPLER_REGISTRY:
                raise ValueError(f"unknown sampler {sampler!r}")
            sampler_obj = SAMPLER_REGISTRY[sampler]()
        elif isinstance(sampler, type):
            sampler_obj = sampler()
        else:
            sampler_obj = sampler
        plan = resolve_plan(cache_plan)
        key = _sampler_cache_key(sampler_obj, guidance_scale) \
            + (plan.key() if plan is not None else None,)
        if key not in self._sampler_cache:
            if plan is None:
                cache_fns = None
            elif isinstance(plan, ComposedPlan):
                cache_fns = resolve_composed_fns(self.model, plan)
            else:
                cache_fns = resolve_cache_fns(self.model, plan)
            # a model that counts what it does (routed experts: its held
            # picks by layer and expert; a learned selection: the keys
            # selected) also returns those counts by name: summed over
            # the batch, they ride the serving programs as each row's
            # tally (`moe/picks_*`, `dsa/keys_*`)
            tally_shapes = getattr(self.model, "tally_shapes", None)
            if tally_shapes is None:
                model_fn = lambda p, x, t, c: self.model.apply(p, x, t, c)
            else:
                def model_fn(p, x, t, c):
                    raw, tally = self.model.apply(p, x, t, c,
                                                  return_tally=True)
                    return raw, {k: v.sum(axis=0) for k, v in tally.items()}
            if getattr(self.model, "serve_rows_apart", False):
                model_fn = rows_apart(model_fn)
            self._sampler_cache[key] = DiffusionSampler(
                model_fn=model_fn, tally_shape=tally_shapes,
                schedule=self.schedule, transform=self.transform,
                autoencoder=self.autoencoder,
                guidance_scale=guidance_scale,
                sampler=sampler_obj,
                cache_plan=plan, cache_fns=cache_fns)
        return self._sampler_cache[key]

    def generate_samples(self,
                         num_samples: int = 4,
                         resolution: int = 64,
                         diffusion_steps: int = 50,
                         sampler: str | Sampler = "euler_ancestral",
                         guidance_scale: float = 0.0,
                         prompts=None,
                         use_ema: bool = True,
                         seed: int = 42,
                         sequence_length: Optional[int] = None,
                         channels: int = 3,
                         inpaint_reference=None,
                         inpaint_mask=None,
                         cache_plan=None) -> np.ndarray:
        """Generate images/videos; prompts are encoded through the input
        config when given (reference pipeline.py:217-272). Inpainting:
        see DiffusionSampler.generate_samples. `cache_plan` activates
        the training-free activation cache for this trajectory
        (docs/CACHING.md); None keeps the bit-exact uncached path."""
        params = (self.ema_params
                  if use_ema and self.ema_params is not None else self.params)
        conditioning = unconditional = None
        if prompts is not None:
            if self.input_config is None or not self.input_config.conditions:
                raise ValueError("pipeline has no conditioning inputs")
            cond = self.input_config.conditions[0]
            conditioning = jnp.asarray(cond.encoder(list(prompts)))
            num_samples = conditioning.shape[0]
            unconditional = self.input_config.get_unconditionals(
                batch_size=num_samples)[0]
        elif self.input_config is not None and self.input_config.conditions:
            # prompt-less sampling from a CONDITIONAL checkpoint: feed
            # the cached null-conditioning tokens (what uncond dropout
            # trained on). Passing None instead would trace the model
            # without its cross-attention branches and fail against the
            # checkpointed param tree (the branch structure depends on
            # whether context is present, e.g. Unet's mid block).
            conditioning = self.input_config.get_unconditionals(
                batch_size=num_samples)[0]
        ds = self.get_sampler(sampler, guidance_scale,
                              cache_plan=cache_plan)
        from ..telemetry import global_telemetry
        tel = global_telemetry()
        if ds.spatial_active:
            # plan accounting is pure host arithmetic on the static
            # schedule — no device syncs
            counts = ds.cache_plan.counts(diffusion_steps)
            tel.counter("diffcache/requests").inc()
            tel.counter("diffcache/spatial_requests").inc()
            tel.counter("diffcache/refresh_steps").inc(
                counts["refresh"])
            tel.counter("diffcache/spatial_steps").inc(
                counts["spatial"])
            tel.counter("diffcache/reused_steps").inc(counts["reused"])
        elif ds.cache_active:
            # plan accounting is pure host arithmetic on the static
            # schedule — no device syncs
            flags = ds.cache_plan.flags(diffusion_steps)
            tel.counter("diffcache/requests").inc()
            tel.counter("diffcache/refresh_steps").inc(
                int(flags.sum()))
            tel.counter("diffcache/reused_steps").inc(
                int((~flags).sum()))
        sampler_name = (sampler if isinstance(sampler, str)
                        else type(ds.sampler).__name__)
        import time as _time
        t0 = _time.perf_counter()
        with tel.span("sampler.generate", cat="inference",
                      args={"sampler": sampler_name,
                            "diffusion_steps": diffusion_steps,
                            "num_samples": num_samples,
                            "guidance_scale": guidance_scale}):
            out = ds.generate_samples(
                params=params, num_samples=num_samples,
                resolution=resolution,
                diffusion_steps=diffusion_steps, rngstate=RngSeq.create(seed),
                sequence_length=sequence_length, channels=channels,
                conditioning=conditioning, unconditional=unconditional,
                inpaint_reference=inpaint_reference,
                inpaint_mask=inpaint_mask)
            # the scan dispatches async; close the span on real work
            out = jax.block_until_ready(out)
        # solo inference measured with the serving layer's metric
        # family (docs/OBSERVABILITY.md): one observation per call,
        # compile included — this is the end-to-end client latency
        from ..serving.scheduler import MS_BUCKET_BOUNDS
        tel.histogram("inference/generate_ms",
                      bounds=MS_BUCKET_BOUNDS).observe(
            (_time.perf_counter() - t0) * 1e3)
        tel.counter("inference/samples_generated").inc(num_samples)
        return np.asarray(jax.device_get(out))


def save_pipeline_config(checkpoint_dir: str, config: Dict[str, Any]):
    """Write the config dict the pipeline rebuilds from (epath, so a
    gs:// checkpoint dir gets its config beside the shards — the
    from_checkpoint read side already goes through epath)."""
    from etils import epath
    d = epath.Path(checkpoint_dir)
    d.mkdir(parents=True, exist_ok=True)
    (d / CONFIG_FILENAME).write_text(json.dumps(config, indent=2))
