#!/usr/bin/env python
"""Training CLI for flaxdiff_tpu.

Capability parity with reference training.py:83-680 (dataset selection,
architecture registry with +hilbert/+zigzag/+2d suffixes, warmup-cosine LR
with grad clip and adam/adamw/lamb, EMA / CFG-dropout knobs, dtype policy,
checkpointing, validation sampling) — reworked for this framework: mesh
axes are explicit (data/fsdp/tensor/seq), checkpoints are sharded orbax,
logging is JSONL (+wandb when available), and the inference config is
saved next to the checkpoints for DiffusionInferencePipeline.
"""
from __future__ import annotations

import argparse
import json
import os


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="flaxdiff_tpu trainer")
    # data
    p.add_argument("--dataset", default="synthetic",
                   help="name in DATASET_REGISTRY")
    p.add_argument("--dataset_path", default=None)
    p.add_argument("--hf_text_key", default="text",
                   help="caption column for online:<hf-dataset> streaming")
    p.add_argument("--image_size", type=int, default=64)
    p.add_argument("--num_frames", type=int, default=0,
                   help=">0 trains a video model on [B,F,H,W,C] clips")
    p.add_argument("--audio_encoder", default="none",
                   choices=["none", "mel"],
                   help="condition video models on clip audio")
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--grain_workers", type=int, default=0)
    # grain throughput knobs (reference training.py:84-99 defaults at
    # corpus scale: 32 workers / 140 read threads / buffers 96/100)
    p.add_argument("--grain_worker_buffer", type=int, default=1)
    p.add_argument("--grain_read_threads", type=int, default=None)
    p.add_argument("--grain_read_buffer", type=int, default=None)
    # model
    p.add_argument("--architecture", default="unet",
                   help="registry name, e.g. unet, simple_dit+hilbert")
    p.add_argument("--model_config", default="{}",
                   help="JSON kwargs for the model constructor")
    p.add_argument("--autoencoder", default=None,
                   choices=["identity", "kl_vae", "sd_vae",
                            "stable_diffusion"],
                   help="latent-diffusion codec: the prior trains in the "
                        "codec's latent space and validation decodes "
                        "(reference training.py:192-195,339-345)")
    p.add_argument("--autoencoder_opts", default="{}",
                   help='JSON codec opts. sd_vae: {"npz": "sd_vae.npz"} '
                        "loads converted pretrained weights "
                        "(scripts/convert_sd_vae_weights.py); kl_vae/"
                        "sd_vae without weights init randomly (smoke "
                        "runs); stable_diffusion passes through to the "
                        "diffusers wrapper")
    p.add_argument("--dtype", default="bfloat16")
    # diffusion
    p.add_argument("--schedule", default="cosine")
    p.add_argument("--timesteps", type=int, default=1000)
    p.add_argument("--predictor", default="epsilon")
    # conditioning
    p.add_argument("--text_encoder", default="hash",
                   choices=["none", "hash", "clip"])
    p.add_argument("--uncond_prob", type=float, default=0.12)
    # optimization (reference defaults: training.py:185-189, 213)
    p.add_argument("--optimizer", default="adamw",
                   choices=["adam", "adamw", "lamb"])
    p.add_argument("--lr", type=float, default=2.7e-4)
    p.add_argument("--warmup_steps", type=int, default=10000)
    p.add_argument("--total_steps", type=int, default=100000)
    p.add_argument("--grad_clip", type=float, default=1.0)
    p.add_argument("--flat_optimizer", action="store_true",
                   help="run the optimizer over one raveled vector per "
                        "dtype (fused updates; elementwise optimizers "
                        "only — not lamb)")
    p.add_argument("--flat_params", action="store_true",
                   help="params/EMA/opt-state live as one padded vector "
                        "per dtype: fused optimizer+EMA+apply updates "
                        "AND flat grads via AD (supersedes "
                        "--flat_optimizer; elementwise optimizers only; "
                        "changes checkpoint layout)")
    p.add_argument("--attn_bhld", action="store_true",
                   help="project attention q/k/v straight into the "
                        "flash kernel's [B,H,L,D] layout (no per-op "
                        "transposes). Sets FLAXDIFF_ATTN_BHLD for the "
                        "whole process, so in multi-host runs every "
                        "host resolves the same layout from the same "
                        "command line (an env var set by hand on only "
                        "some hosts would compile divergent programs)")
    p.add_argument("--grad_accum", type=int, default=1,
                   help=">1 accumulates gradients over k micro-batches "
                        "per optimizer update (optax.MultiSteps)")
    p.add_argument("--ema_decay", type=float, default=0.999)
    # parallelism
    p.add_argument("--mesh_data", type=int, default=-1)
    p.add_argument("--mesh_fsdp", type=int, default=1)
    p.add_argument("--mesh_seq", type=int, default=1)
    p.add_argument("--mesh_tensor", type=int, default=1,
                   help=">1 enables Megatron tensor parallelism over the "
                        "tensor mesh axis (head-sharded attention)")
    # checkpoint / logging / validation
    p.add_argument("--checkpoint_dir", default="./checkpoints/run")
    p.add_argument("--save_every", type=int, default=1000)
    p.add_argument("--log_every", type=int, default=100)
    p.add_argument("--profile_dir", default=None,
                   help="capture a jax.profiler trace of a few post-warmup "
                        "steps into this directory")
    p.add_argument("--telemetry_dir", default=None,
                   help="enable the telemetry subsystem "
                        "(docs/OBSERVABILITY.md): per-step phase timings "
                        "+ pod-aggregated metrics into telemetry.jsonl, "
                        "a cumulative goodput/badput account in "
                        "goodput.json, host-side spans in a "
                        "Perfetto-loadable trace.json, and the program "
                        "evidence registry in programs.jsonl (per "
                        "compiled program: cache key, compile ms, "
                        "FLOPs, hardware fingerprint). Costs one device "
                        "sync per SAMPLED step (exact device-phase "
                        "timing; --telemetry_sample_every thins it). "
                        "Analyze with scripts/diagnose_run.py; diff two "
                        "runs with scripts/compare_runs.py")
    p.add_argument("--telemetry_sample_every", type=int, default=1,
                   help="with --telemetry_dir, close async dispatch for "
                        "exact device-phase timing only every N-th step "
                        "— off-sample steps add zero host syncs and "
                        "phase/goodput attribution moves to window "
                        "granularity (docs/OBSERVABILITY.md 'Sampled "
                        "phase timing'). 1 = per-step exact timing")
    p.add_argument("--pipeline_depth", type=int, default=2,
                   help="bounded-depth asynchronous dispatch: the fit "
                        "loop keeps up to N steps in flight so the "
                        "device pipeline stays full across step "
                        "boundaries; 0 disables the bound (the "
                        "log-cadence loss fetch is then the only "
                        "settle point)")
    p.add_argument("--no_nonfinite_gate", action="store_true",
                   help="disable the in-graph non-finite gate (an "
                        "elementwise select that keeps the previous "
                        "value wherever an update is non-finite, so "
                        "the live state is finite by construction); "
                        "disabling restores the legacy synchronous "
                        "save-cadence loss check")
    p.add_argument("--gate_counter", action="store_true",
                   help="carry an in-graph [3] int32 counter of the "
                        "elements the non-finite gate masked in "
                        "params/opt-state/EMA, surfaced once per log "
                        "window as numerics/gate_activations* counters "
                        "+ a gate_activated event. Opt-in: the count "
                        "reduces over every state leaf (slower XLA "
                        "compile) and adds a checkpoint pytree leaf — "
                        "flip per run, not mid-run. Requires the gate "
                        "(incompatible with --no_nonfinite_gate)")
    p.add_argument("--flash_tune_cache", default=None,
                   help="per-shape flash-attention autotuner cache dir "
                        "(ops/autotune.py): before the first step, a "
                        "shape-scouting eval_shape pass + measured "
                        "probes pick block sizes and the native-d "
                        "choice per attention shape and persist them "
                        "here; a warm cache re-measures nothing. "
                        "FLAXDIFF_FLASH_BLOCK_Q/K / _NATIVE_D env "
                        "overrides always win over cached plans")
    p.add_argument("--loss_ring", type=int, default=0,
                   help="device-resident in-graph loss ring of this "
                        "many slots: the jitted step records each "
                        "step's loss on device and the fit loop "
                        "fetches the whole window with ONE readback "
                        "per ring, so even log_every=1 costs one sync "
                        "per window (per-step losses arrive "
                        "retroactively as window_losses). 0 disables; "
                        "changes the checkpointed state tree by one "
                        "[N] leaf, so pick per run")
    p.add_argument("--compilation_cache_dir", default=None,
                   help="persistent XLA compilation cache directory "
                        "(default <checkout>/.jax_cache; ignored when "
                        "JAX_COMPILATION_CACHE_DIR is set, which jax "
                        "reads itself): relaunches and coordinated "
                        "restarts reload compiled programs instead of "
                        "paying the jit compile again — the fit loop "
                        "detects the warm first step and attributes it "
                        "productive instead of compile badput")
    p.add_argument("--prometheus_textfile", default=None,
                   help="also export the telemetry snapshot to this path "
                        "in Prometheus text format (atomic rename; "
                        "node-exporter textfile-collector convention). "
                        "Requires --telemetry_dir")
    p.add_argument("--numerics_cadence", type=int, default=0,
                   help="every N steps run the training-health monitor "
                        "inside the jitted step (per-module grad/param "
                        "norms, update ratios, non-finite counts; "
                        "docs/OBSERVABILITY.md). Off-cadence steps run "
                        "the unmonitored program unchanged; 0 disables")
    p.add_argument("--anomaly_action", default="warn",
                   choices=["warn", "skip_step", "rollback"],
                   help="what a detected numerics anomaly does: warn "
                        "(events/metrics only), skip_step (non-finite "
                        "updates gated in-graph, never applied), or "
                        "rollback (restore best state / newest "
                        "restorable checkpoint on hard anomalies)")
    p.add_argument("--watchdog_timeout", type=float, default=None,
                   help="seconds without a completed step before the "
                        "train-loop watchdog checkpoints and exits "
                        "cleanly (docs/RESILIENCE.md); default off. Size "
                        "it at several multiples of the step time.")
    p.add_argument("--coordinated_restart", default="auto",
                   choices=["auto", "on", "off"],
                   help="pod-consistent checkpointing: two-phase "
                        "ledger commits + consensus restore + crash "
                        "barriers (docs/RESILIENCE.md). auto = on "
                        "whenever jax.process_count() > 1")
    p.add_argument("--commit_barrier_timeout", type=float, default=600.0,
                   help="seconds survivors wait at a commit/restore "
                        "barrier before declaring a peer dead and "
                        "taking the checkpoint-and-exit path")
    p.add_argument("--elastic", default="off", choices=["on", "off"],
                   help="elastic world (docs/RESILIENCE.md): survivors "
                        "of a lost host SHRINK the world and keep "
                        "training instead of exiting on "
                        "coordination_lost; replacement hosts are "
                        "re-admitted live at commit boundaries; hard "
                        "numerics anomalies become pod quorum votes. "
                        "Implies coordinated checkpointing.")
    p.add_argument("--elastic_shrink_window", type=float, default=5.0,
                   help="seconds survivors wait for each peer's "
                        "presence answer in a shrink round before "
                        "declaring it dead")
    p.add_argument("--elastic_min_world", type=int, default=1,
                   help="refuse to shrink below this many hosts "
                        "(checkpoint-and-exit instead)")
    p.add_argument("--elastic_restart_cost", type=float, default=0.0,
                   help="estimated relaunch overhead (scheduler queue, "
                        "container pull) in seconds — feeds only the "
                        "badput-reclaimed estimate of elastic "
                        "transitions")
    p.add_argument("--val_every", type=int, default=0,
                   help="0 disables in-loop validation")
    p.add_argument("--val_samples", type=int, default=8)
    p.add_argument("--val_steps", type=int, default=200)
    p.add_argument("--val_guidance", type=float, default=3.0)
    p.add_argument("--val_metrics", default="",
                   help="comma list of {fid, clip, clip_score}")
    p.add_argument("--inception_weights", default=None,
                   help=".npz from scripts/convert_inception_weights.py "
                        "(standard FID; random features otherwise)")
    p.add_argument("--sampler", default="euler_ancestral")
    p.add_argument("--wandb_project", default=None)
    p.add_argument("--wandb_resume", default=None, metavar="RUN_ID",
                   help="resume this wandb run id; its logged model "
                        "artifact is auto-downloaded when no local "
                        "checkpoint exists (reference "
                        "simple_trainer.py:194-211)")
    p.add_argument("--registry", default=None,
                   help="path to registry.json for cross-run best tracking "
                        "(default: <checkpoint_dir>/../registry.json)")
    p.add_argument("--run_name", default=None)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)

    import jax

    from flaxdiff_tpu.utils import configure_compilation_cache
    cache_dir = configure_compilation_cache(args.compilation_cache_dir)
    print(f"compilation cache: {cache_dir}")
    if args.flash_tune_cache:
        from flaxdiff_tpu.ops import autotune as _flash_autotune
        _flash_autotune.activate(args.flash_tune_cache)
    import jax.numpy as jnp
    import numpy as np
    import optax

    from flaxdiff_tpu.data.dataloaders import get_dataset_grain
    from flaxdiff_tpu.data.dataset_map import get_dataset
    from flaxdiff_tpu.inference.pipeline import save_pipeline_config
    from flaxdiff_tpu.inference.registry import build_model
    from flaxdiff_tpu.inputs import (CLIPTextEncoder, ConditionalInputConfig,
                                     DiffusionInputConfig, HashTextEncoder)
    from flaxdiff_tpu.parallel import create_mesh, use_mesh
    from flaxdiff_tpu.predictors import get_transform
    from flaxdiff_tpu.samplers import SAMPLER_REGISTRY
    from flaxdiff_tpu.schedulers import get_schedule
    from flaxdiff_tpu.trainer import (Checkpointer, DiffusionTrainer,
                                      TrainerConfig, ValidationConfig,
                                      Validator, make_logger)

    if jax.process_count() > 1:
        jax.distributed.initialize()

    # mesh
    mesh = create_mesh(axes={"data": args.mesh_data, "fsdp": args.mesh_fsdp,
                             "seq": args.mesh_seq,
                             "tensor": args.mesh_tensor})
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    # conditioning
    encoder = None
    if args.text_encoder == "hash":
        encoder = HashTextEncoder.create()
    elif args.text_encoder == "clip":
        encoder = CLIPTextEncoder.from_modelname()
    conditions = []
    if encoder is not None:
        conditions.append(ConditionalInputConfig(encoder=encoder))
    input_config = DiffusionInputConfig(
        sample_data_key="sample",
        sample_data_shape=(args.image_size, args.image_size, 3),
        conditions=conditions)

    # data: tokenizer-free loader; text encoded host-side per batch.
    # "online:<name>" streams through OnlineStreamingDataLoader — a
    # registry name stays hermetic (records from the in-memory source),
    # anything else is fetched as a HuggingFace dataset (reference
    # onlineDatasetMap, online_loader.py:899-921).
    if args.dataset.startswith("online:"):
        from flaxdiff_tpu.data.dataloaders import to_trainer_batch
        from flaxdiff_tpu.data.dataset_map import DATASET_REGISTRY
        from flaxdiff_tpu.data.online_loader import OnlineStreamingDataLoader
        name = args.dataset.split(":", 1)[1]
        if name in DATASET_REGISTRY:
            media = get_dataset(name, image_size=args.image_size,
                                **({"root": args.dataset_path}
                                   if args.dataset_path else {}))
            src = media.source.get_source()
            records = [src[i] for i in range(len(src))]
            online = OnlineStreamingDataLoader(
                records, batch_size=args.batch_size,
                image_size=args.image_size, seed=args.seed)
        else:
            online = OnlineStreamingDataLoader.from_hf_dataset(
                name, text_key=args.hf_text_key,
                batch_size=args.batch_size,
                image_size=args.image_size, seed=args.seed)

        def _online_train(seed=0):
            for b in online:
                yield to_trainer_batch(b)

        loaded = {"train": _online_train}
    else:
        ds_kwargs = {"root": args.dataset_path} if args.dataset_path else {}
        if args.num_frames:
            ds_kwargs["num_frames"] = args.num_frames
        dataset = get_dataset(args.dataset, image_size=args.image_size,
                              **ds_kwargs)
        loaded = get_dataset_grain(dataset, batch_size=args.batch_size,
                                   image_size=args.image_size,
                                   worker_count=args.grain_workers,
                                   worker_buffer_size=args.grain_worker_buffer,
                                   read_threads=args.grain_read_threads,
                                   read_buffer_size=args.grain_read_buffer,
                                   seed=args.seed)

    # latent-diffusion codec (reference training.py:339-345): the prior
    # below trains over its latents — the encode happens INSIDE the
    # jitted train step, decode inside the validation sampler
    autoencoder = None
    if args.autoencoder:
        ae_opts = json.loads(args.autoencoder_opts)
        if args.autoencoder == "sd_vae" and "npz" in ae_opts:
            from flaxdiff_tpu.models.sd_vae import SDVAE
            autoencoder = SDVAE.from_npz(ae_opts.pop("npz"), **ae_opts)
        else:
            from flaxdiff_tpu.models.autoencoder import AUTOENCODER_REGISTRY
            builder = AUTOENCODER_REGISTRY[args.autoencoder]
            if args.autoencoder == "kl_vae":
                autoencoder = builder.create(
                    jax.random.PRNGKey(ae_opts.pop("seed", 0)), **ae_opts)
            else:
                autoencoder = builder(**ae_opts)
        if args.image_size % autoencoder.downscale_factor:
            raise SystemExit(
                f"--image_size {args.image_size} is not divisible by the "
                f"{autoencoder.name} codec's downscale factor "
                f"{autoencoder.downscale_factor}; the encoder would "
                "produce ceil-sized latents that disagree with the "
                "prior's sample shape")
        print(f"latent diffusion via {autoencoder.name}: "
              f"{autoencoder.downscale_factor}x downscale, "
              f"{autoencoder.latent_channels} latent channels")

    sample_channels = (autoencoder.latent_channels if autoencoder else 3)
    sample_size = (args.image_size // autoencoder.downscale_factor
                   if autoencoder else args.image_size)

    # model
    if args.attn_bhld:
        os.environ["FLAXDIFF_ATTN_BHLD"] = "1"
    model_kwargs = json.loads(args.model_config)
    model_kwargs.setdefault("dtype", args.dtype)
    if autoencoder is not None:
        model_kwargs.setdefault("output_channels", sample_channels)
    model = build_model(args.architecture, **model_kwargs)

    schedule = get_schedule(args.schedule, timesteps=args.timesteps)
    transform = get_transform(args.predictor)

    # audio conditioning for video models (one token per frame)
    audio_enc = None
    if args.audio_encoder == "mel":
        from flaxdiff_tpu.inputs import MelAudioEncoder
        audio_enc = MelAudioEncoder.create()

    ctx_shape = None
    if encoder is not None:
        ctx_shape = tuple(conditions[0].get_unconditional()[0].shape)
    elif audio_enc is not None and args.num_frames:
        ctx_shape = (args.num_frames, audio_enc.features)

    if args.num_frames:
        x0 = jnp.zeros((2, args.num_frames, sample_size,
                        sample_size, sample_channels))
    else:
        x0 = jnp.zeros((2, sample_size, sample_size, sample_channels))
    t0 = jnp.zeros((2,))
    c0 = (jnp.zeros((2,) + ctx_shape) if ctx_shape else None)

    def apply_fn(params, x, t, cond):
        ctx = None
        if cond is not None:
            ctx = cond.get("text", cond.get("audio"))
        return model.apply(params, x, t, ctx)

    def init_fn(key):
        return model.init(key, x0, t0, c0)

    # optimizer (reference training.py:594-608). MultiSteps advances the
    # inner schedule once per k micro-batches, so with --grad_accum the
    # horizons are scaled by k to keep warmup/decay aligned with the
    # total_steps micro-steps the fit loop actually runs.
    accum = max(args.grad_accum, 1)
    warmup = max(args.warmup_steps // accum, 1)
    # optax requires decay_steps > warmup_steps; short runs (resumes,
    # smoke tests) may configure total <= warmup
    lr = optax.warmup_cosine_decay_schedule(
        0.0, args.lr, warmup, max(args.total_steps // accum, warmup + 1))
    opt = {"adam": optax.adam, "adamw": optax.adamw,
           "lamb": optax.lamb}[args.optimizer]
    tx = optax.chain(optax.clip_by_global_norm(args.grad_clip), opt(lr))
    if args.flat_params:
        # the whole state lives flat (TrainerConfig.flat_params) — the
        # inner optimizer already sees flat vectors, so flat_optimizer
        # wrapping would be a redundant second flatten
        elementwise_safe = {"adam", "adamw"}
        if args.optimizer not in elementwise_safe:
            raise SystemExit(
                f"--flat_params is elementwise-only "
                f"({sorted(elementwise_safe)}); {args.optimizer!r} mixes "
                "information across a leaf's shape, which changes "
                "meaning under concatenation")
        args.flat_optimizer = False
    if args.flat_optimizer:
        # whitelist, not blacklist: a future optimizer added to `opt`
        # (lamb's trust ratio, adafactor's factored moments) silently
        # computes the WRONG thing over a concatenated vector
        elementwise_safe = {"adam", "adamw"}
        if args.optimizer not in elementwise_safe:
            raise SystemExit(
                f"--flat_optimizer is elementwise-only "
                f"({sorted(elementwise_safe)}); {args.optimizer!r} mixes "
                "information across a leaf's shape, which changes "
                "meaning under concatenation")
        from flaxdiff_tpu.trainer.optim import flat_optimizer
        # fuses the optax transform's per-leaf kernels into one update
        # per dtype (part of the r3 trace's ~330-kernel / 10 ms budget;
        # EMA and apply_updates remain leaf-wise — see trainer/optim.py).
        # Changes the optimizer-state checkpoint layout, so pick per run.
        tx = flat_optimizer(tx)
    if accum > 1:
        # micro-batch accumulation: k steps of summed grads per optimizer
        # update — effective batch k * batch_size without the memory.
        # EMA/step bookkeeping stays per-micro-step (ema_decay applies at
        # micro cadence, as with any MultiSteps wrapping).
        tx = optax.MultiSteps(tx, every_k_schedule=accum)

    null_cond = {}
    if encoder is not None:
        null_cond["text"] = jnp.asarray(conditions[0].get_unconditional())
    if audio_enc is not None and args.num_frames:
        null_cond["audio"] = jnp.zeros(
            (1, args.num_frames, audio_enc.features))
    null_cond = null_cond or None

    # fp16 gets a loss-scaling policy (DynamicScale constructed by the
    # trainer); bf16/f32 compute needs none.
    policy = None
    if args.dtype == "float16":
        from flaxdiff_tpu.typing import Policy
        policy = Policy(compute_dtype=jnp.float16)

    # The one name shared by the resume-pull and end-of-run push+registry
    # record: the two sites must never drift or resume stops finding the
    # pushed artifact.
    run_name = args.run_name or os.path.basename(
        os.path.normpath(args.checkpoint_dir))

    # Logger before checkpointer: wandb-run resume must be live so the
    # model artifact can be pulled back BEFORE restore looks at disk.
    os.makedirs(args.checkpoint_dir, exist_ok=True)
    wandb_kwargs = ({"id": args.wandb_resume, "resume": "must"}
                    if args.wandb_resume else {})
    logger = make_logger(project=args.wandb_project,
                         jsonl_path=os.path.join(args.checkpoint_dir,
                                                 "train_log.jsonl"),
                         **wandb_kwargs)
    # stream resilience events (retries, fallback restores, watchdog
    # stalls, ...) into the run log as structured records, in addition
    # to the counter metrics fit merges at log cadence
    from flaxdiff_tpu.trainer import attach_resilience
    detach_resilience = attach_resilience(logger)

    # Telemetry (docs/OBSERVABILITY.md): phase timings, goodput ledger,
    # trace spans, pod aggregation. Installed as the process-global hub
    # so layers without plumbing (the data loader's workers, the
    # checkpointer) land on the same account; the world-of-one in-memory
    # transport keeps single-host runs on the identical aggregation
    # code path.
    telemetry = None
    if args.telemetry_dir:
        from flaxdiff_tpu.resilience.coordination import (
            InMemoryTransport, JaxDistributedTransport)
        from flaxdiff_tpu.telemetry import Telemetry, set_global_telemetry
        tel_transport = (JaxDistributedTransport("flaxdiff.telemetry")
                         if jax.process_count() > 1
                         else InMemoryTransport.make_world(1)[0])
        telemetry = Telemetry.create(
            args.telemetry_dir, transport=tel_transport,
            prometheus_textfile=args.prometheus_textfile, logger=logger)
        set_global_telemetry(telemetry)
    elif args.prometheus_textfile:
        raise SystemExit("--prometheus_textfile requires --telemetry_dir")
    if args.wandb_resume:
        has_local = any(d.isdigit()
                        for d in os.listdir(args.checkpoint_dir))
        if not has_local:
            # Process 0 downloads into the shared checkpoint_dir; the
            # others wait at the barrier (concurrent downloads into one
            # directory can corrupt the orbax step layout).
            pulled = None
            if jax.process_index() == 0:
                from flaxdiff_tpu.trainer.registry import pull_artifact
                pulled = pull_artifact(run_name, args.checkpoint_dir)
                if pulled:
                    print(f"pulled wandb artifact {run_name} -> {pulled}")
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils
                multihost_utils.sync_global_devices("wandb_artifact_pull")
            has_local = any(d.isdigit()
                            for d in os.listdir(args.checkpoint_dir))
            if not has_local:
                # --wandb_resume is an explicit promise of prior state;
                # silently restarting from step 0 would also re-alias
                # "latest" to a from-scratch checkpoint at the end of the
                # run, clobbering the only copy of the real progress.
                raise SystemExit(
                    f"--wandb_resume {args.wandb_resume}: no local "
                    f"checkpoint under {args.checkpoint_dir} and the "
                    f"model artifact {run_name!r} could not be pulled "
                    "(no active wandb run / artifact missing / download "
                    "failed)")

    # Coordinated restart (docs/RESILIENCE.md): every host must restore
    # the SAME committed step after a crash — saves two-phase-commit
    # into ledger.jsonl and restores run a consensus round. The
    # in-memory world-of-one transport keeps single-host runs on the
    # identical code path (ledger included) without jax.distributed.
    coordinator = None
    elastic_manager = None
    want_elastic = args.elastic == "on"
    if want_elastic or args.coordinated_restart == "on" or (
            args.coordinated_restart == "auto"
            and jax.process_count() > 1):
        from flaxdiff_tpu.resilience.coordination import (
            RestartCoordinator, agree_epoch, default_transport)
        coord_transport = default_transport()
        # epoch-tagged vote payloads: the goodput ledger's incarnation
        # count IS the job-incarnation number, so a stale voter from a
        # previous life aborts the round instead of corrupting it
        # (docs/RESILIENCE.md). goodput.json is written by process 0
        # only, so non-0 hosts (host-local --telemetry_dir, torn read)
        # may hold a different local count — broadcast rank 0's value so
        # every host tags with the SAME epoch; divergent tags would
        # abort every future round.
        agreed = agree_epoch(
            coord_transport,
            (telemetry.goodput.incarnation
             if telemetry is not None else 0),
            timeout=args.commit_barrier_timeout)
        vote_transport = coord_transport
        if want_elastic:
            # Elastic world (docs/RESILIENCE.md "Elastic world"): the
            # manager owns membership; the coordinator's rounds run
            # over a MemberTransport so commits keep working unchanged
            # across shrink/grow transitions (keys are epoch-scoped,
            # ranks member-relative). The manager's ledger/validity
            # inputs are bound to the checkpointer below.
            from flaxdiff_tpu.resilience.elastic import (
                ElasticConfig, ElasticWorldManager, MemberTransport)
            elastic_manager = ElasticWorldManager(
                coord_transport,
                config=ElasticConfig(
                    shrink_window=args.elastic_shrink_window,
                    vote_timeout=args.commit_barrier_timeout,
                    min_world=args.elastic_min_world,
                    restart_cost_estimate=args.elastic_restart_cost))
            vote_transport = MemberTransport(elastic_manager)
        coordinator = RestartCoordinator(
            vote_transport,
            barrier_timeout=args.commit_barrier_timeout,
            epoch=agreed)
        if telemetry is not None:
            # stamp every raw telemetry row with the pod-agreed epoch:
            # a stale same-incarnation driver's rows stay attributable
            telemetry.set_epoch(agreed)
    ckpt = Checkpointer(args.checkpoint_dir, coordinator=coordinator)
    if elastic_manager is not None:
        elastic_manager.ledger = ckpt.ledger
        elastic_manager.valid_steps = ckpt.locally_valid_steps
    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=tx, schedule=schedule,
        transform=transform, mesh=mesh,
        config=TrainerConfig(ema_decay=args.ema_decay,
                             uncond_prob=args.uncond_prob,
                             log_every=args.log_every, seed=args.seed,
                             profile_dir=args.profile_dir,
                             flat_params=args.flat_params,
                             watchdog_timeout=args.watchdog_timeout,
                             numerics_cadence=args.numerics_cadence,
                             anomaly_action=args.anomaly_action,
                             pipeline_depth=args.pipeline_depth,
                             telemetry_sample_every=(
                                 args.telemetry_sample_every),
                             gate_nonfinite=not args.no_nonfinite_gate,
                             gate_counter=args.gate_counter,
                             loss_ring=args.loss_ring),
        policy=policy, null_cond=null_cond, checkpointer=ckpt,
        autoencoder=autoencoder, telemetry=telemetry,
        elastic=elastic_manager)

    if ckpt.latest_step() is not None:
        step = trainer.restore_checkpoint()
        print(f"resumed from step {step}")

    # persist the inference config next to the checkpoints
    save_pipeline_config(args.checkpoint_dir, {
        "model": {"name": args.architecture, **model_kwargs},
        "schedule": {"name": args.schedule, "timesteps": args.timesteps},
        "predictor": args.predictor,
        "input_config": (input_config.serialize() if conditions else None),
        # informational: inference must supply the codec object itself
        # (weights live outside the checkpoint), but the config records
        # which codec and shape the prior was trained against
        "autoencoder": ({"name": args.autoencoder,
                         **autoencoder.serialize()}
                        if autoencoder else None),
        "flat_params": args.flat_params,
    })
    # (flat-params runs: the trainer itself persists param_template.json
    # beside the checkpoints — see DiffusionTrainer._write_param_template)

    validator = None
    if args.val_every:
        val_metrics = []
        for name in filter(None, args.val_metrics.split(",")):
            if name == "fid":
                from flaxdiff_tpu.metrics import get_fid_metric
                val_metrics.append(get_fid_metric(
                    params_file=args.inception_weights))
            elif name == "clip":
                from flaxdiff_tpu.metrics import get_clip_metric
                val_metrics.append(get_clip_metric())
            elif name == "clip_score":
                from flaxdiff_tpu.metrics import get_clip_score_metric
                val_metrics.append(get_clip_score_metric())
            elif name == "psnr":
                from flaxdiff_tpu.metrics import get_psnr_metric
                val_metrics.append(get_psnr_metric())
            elif name == "ssim":
                from flaxdiff_tpu.metrics import get_ssim_metric
                val_metrics.append(get_ssim_metric())
            else:
                raise SystemExit(f"unknown --val_metrics entry {name!r}")
        validator = Validator(
            model_fn=apply_fn, schedule=schedule, transform=transform,
            sampler=SAMPLER_REGISTRY[args.sampler](),
            metrics=val_metrics, autoencoder=autoencoder,
            config=ValidationConfig(
                num_samples=args.val_samples,
                diffusion_steps=args.val_steps,
                guidance_scale=args.val_guidance if encoder else 0.0,
                resolution=args.image_size,
                sequence_length=args.num_frames or None))

    raw_iter = loaded["train"](seed=args.seed)

    def encode_text(batch):
        """Host-side conditioning encode: captions -> text embeddings,
        clip audio -> per-frame audio tokens. Raw strings stay in the
        batch (put_batch strips non-numerics before jit) so validation
        metrics that need prompts — CLIPScore — still see batch['text']."""
        if encoder is not None and isinstance(batch.get("text"), list):
            batch.setdefault("cond", {})["text"] = np.asarray(
                encoder(batch["text"]))
        if audio_enc is not None and isinstance(batch.get("audio"), dict):
            fw = batch["audio"].get("framewise_audio")
            if fw is not None:
                batch.setdefault("cond", {})["audio"] = np.asarray(
                    audio_enc(fw))
        # keep only what the step consumes — raw audio waveforms / mel /
        # mask side-channels would otherwise ride the H2D copy every step
        return {k: v for k, v in batch.items()
                if k in ("sample", "cond", "text")}

    # Background-thread text encoding, 2 batches ahead: encode cost hides
    # behind device compute (placement decision measured in
    # scripts/bench_text_encode.py; SURVEY §7.3(4)).
    from flaxdiff_tpu.data.prefetch import prefetch_map
    it = prefetch_map(encode_text, raw_iter, depth=2)

    # Elastic re-shard hook (docs/RESILIENCE.md "Shrink-to-survive"):
    # after a world change the trainer swaps in a pipeline rebuilt for
    # the surviving (rank, size) — the grain index sampler re-shards,
    # not just the online loader. Epoch-offset seed so the re-sharded
    # stream does not replay the pre-shrink order.
    data_factory = None
    if "reshard" in loaded:
        def data_factory(view):
            resharded = loaded["reshard"](view.rank, view.size)
            return prefetch_map(encode_text,
                                resharded(seed=args.seed + view.epoch),
                                depth=2)
    if args.flash_tune_cache:
        # shape-scouting + measured probes BEFORE the first compile, so
        # the train step picks the tuned per-shape plans up; the peeked
        # batch is chained back so no data is dropped
        import itertools as _it
        first = next(it)
        plans = trainer.autotune_flash(trainer.put_batch(first))
        if plans:
            print(f"flash autotuner probed {len(plans)} shape(s) -> "
                  f"{args.flash_tune_cache}")
        it = _it.chain([first], it)
    done = 0
    while done < args.total_steps:
        chunk = min(args.val_every or args.total_steps,
                    args.total_steps - done)
        hist = trainer.fit(
            it, total_steps=chunk, save_every=args.save_every,
            data_factory=data_factory,
            callbacks=[lambda s, l, m: logger.log(
                {"loss": l, **m}, step=done + s)])
        done += chunk
        if validator is not None and done < args.total_steps:
            cond = unc = None
            if encoder is not None:
                # conditioning must mirror the train-step cond pytree
                # ({"text": ...}) — apply_fn routes on the dict key
                prompts = ["a photo"] * args.val_samples
                cond = {"text": jnp.asarray(encoder(prompts))}
                unc = {"text": jnp.asarray(
                    input_config.get_unconditionals(args.val_samples)[0])}
            real_batch = next(it)  # real images for FID / CLIP references
            if telemetry is not None:
                import contextlib as _ctx
                eval_scope = _ctx.ExitStack()
                eval_scope.enter_context(
                    telemetry.span("validation", cat="eval",
                                   args={"step": done}))
                eval_scope.enter_context(
                    telemetry.goodput.measure_badput("eval"))
            else:
                eval_scope = None
            try:
                # under the training mesh: the params are sharded over
                # it, so the sampler compiles for every device, and a
                # Pallas kernel in a multi-device program must sit in a
                # shard_map (jax refuses to partition a Mosaic call) —
                # which the kernels' dispatch only does under a mesh
                with use_mesh(mesh):
                    result = validator.run(
                        trainer.get_params(use_ema=True),
                        conditioning=cond, unconditional=unc,
                        batch=real_batch)
            finally:
                if eval_scope is not None:
                    eval_scope.close()
            logger.log({f"val/{k}": v
                        for k, v in result["metrics"].items()}, step=done)
            logger.log_images("val/samples",
                              Validator.to_uint8(result["samples"]),
                              step=done)
    logger.log({"final_loss": hist["final_loss"]}, step=done)

    # The final save is ASYNC: it must be fully on disk before the
    # registry records it and push_artifact copies the directory — an
    # unfinalized step would upload a partial checkpoint.
    ckpt.wait_until_finished()

    # registry: record the run + per-metric best across runs; push a
    # wandb artifact when a run is live (reference
    # general_diffusion_trainer.py:560-727). Process 0 only — every host
    # sees the same final metrics and registry.json lives on a shared
    # filesystem.
    if jax.process_index() != 0:
        if telemetry is not None:
            telemetry.close()
        detach_resilience()
        logger.finish()
        ckpt.wait_until_finished()
        return hist
    from flaxdiff_tpu.trainer import ModelRegistry
    reg_path = args.registry or os.path.join(
        os.path.dirname(os.path.abspath(args.checkpoint_dir)),
        "registry.json")
    registry = ModelRegistry(reg_path)
    final_metrics = {"loss": hist["final_loss"]}
    directions = {"loss": False}
    if validator is not None:
        for m in validator.metrics:
            if m.name in validator.tracker.best:
                final_metrics[m.name] = validator.tracker.best[m.name]
                directions[m.name] = m.higher_is_better
    became_best = registry.register_run(
        run_name, checkpoint_dir=args.checkpoint_dir, step=done,
        metrics=final_metrics, metric_directions=directions,
        config={"architecture": args.architecture,
                "schedule": args.schedule, "dataset": args.dataset})
    registry.push_artifact(run_name, args.checkpoint_dir)
    logger.log({f"registry/best_{k}": v for k, v in became_best.items()},
               step=done)

    if telemetry is not None:
        # final snapshot + trace/goodput flush; the goodput line is the
        # run's one-sentence efficiency summary
        telemetry.export(step=done)
        telemetry.close()
        t = telemetry.goodput.totals()
        if t["goodput_fraction"] is not None:
            print(f"goodput: {t['goodput_fraction']:.1%} of "
                  f"{t['total_s']:.0f}s attributed wall-clock "
                  f"(incarnation {t['incarnations']}); report: "
                  f"python scripts/diagnose_run.py {args.telemetry_dir}")
    # the event log outlives this call: a subscriber left behind would
    # write the next in-process run's events into this closed logger
    detach_resilience()
    logger.finish()
    ckpt.wait_until_finished()
    print(f"done: {done} steps, final loss {hist['final_loss']:.4f}")
    return hist


if __name__ == "__main__":
    main()
