"""Tests: model registry, architecture suffixes, inference pipeline, CLI."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.inference import (
    DiffusionInferencePipeline,
    build_model,
    parse_architecture_name,
)
from flaxdiff_tpu.models.dit import SimpleDiT
from flaxdiff_tpu.models.unet import Unet


def test_parse_architecture_name():
    assert parse_architecture_name("unet") == ("unet", {})
    base, flags = parse_architecture_name("simple_dit+hilbert")
    assert base == "simple_dit" and flags == {"use_hilbert": True}
    base, flags = parse_architecture_name("hybrid_ssm+zigzag+2d")
    assert flags == {"use_zigzag": True, "use_2d_fusion": True}
    with pytest.raises(ValueError):
        parse_architecture_name("unet+bogus")


def test_build_model_resolves_strings():
    m = build_model("simple_dit", emb_features=32, num_heads=4,
                    num_layers=1, patch_size=4, dtype="bf16",
                    activation="gelu")
    assert isinstance(m, SimpleDiT)
    assert m.dtype == jnp.bfloat16


def test_build_model_drops_unknown_kwargs():
    with pytest.warns(UserWarning):
        m = build_model("unet", emb_features=32, bogus_flag=True)
    assert isinstance(m, Unet)


def test_pipeline_from_config_and_sampler_cache(rng):
    config = {
        "model": {"name": "simple_dit", "emb_features": 32, "num_heads": 4,
                  "num_layers": 1, "patch_size": 4, "output_channels": 1},
        "schedule": {"name": "cosine", "timesteps": 100},
        "predictor": "epsilon",
    }
    model = build_model("simple_dit", emb_features=32, num_heads=4,
                        num_layers=1, patch_size=4, output_channels=1)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)),
                        jnp.zeros((1,)), None)
    pipe = DiffusionInferencePipeline.from_config(config, params=params)

    s1 = pipe.get_sampler("ddim", guidance_scale=0.0)
    s2 = pipe.get_sampler("ddim", guidance_scale=0.0)
    s3 = pipe.get_sampler("ddim", guidance_scale=2.0)
    s4 = pipe.get_sampler("euler", guidance_scale=0.0)
    assert s1 is s2 and s1 is not s3 and s1 is not s4

    out = pipe.generate_samples(num_samples=2, resolution=8,
                                diffusion_steps=4, sampler="ddim",
                                channels=1, use_ema=False)
    assert out.shape == (2, 8, 8, 1)
    assert np.all(np.isfinite(out))


def _tiny_pipe(channels=1):
    config = {
        "model": {"name": "simple_dit", "emb_features": 32, "num_heads": 4,
                  "num_layers": 1, "patch_size": 4,
                  "output_channels": channels},
        "schedule": {"name": "cosine", "timesteps": 100},
        "predictor": "epsilon",
    }
    model = build_model("simple_dit", emb_features=32, num_heads=4,
                        num_layers=1, patch_size=4,
                        output_channels=channels)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, channels)),
                        jnp.zeros((1,)), None)
    return DiffusionInferencePipeline.from_config(config, params=params)


def test_sampler_cache_distinguishes_instance_config():
    """Regression (ISSUE 8 satellite): two Sampler INSTANCES of the same
    class with different hyperparameters must not collide in the
    sampler cache — the old key was (class, guidance) and the second
    instance silently reused the first's DiffusionSampler."""
    from flaxdiff_tpu.samplers import DDIMSampler, MultiStepDPMSampler

    pipe = _tiny_pipe()
    ode = pipe.get_sampler(DDIMSampler(eta=0.0), guidance_scale=0.0)
    ancestral = pipe.get_sampler(DDIMSampler(eta=1.0), guidance_scale=0.0)
    assert ode is not ancestral
    assert ode.sampler.eta == 0.0 and ancestral.sampler.eta == 1.0
    # same config -> still shared (the cache must keep caching)
    assert pipe.get_sampler(DDIMSampler(eta=1.0)) is ancestral
    o1 = pipe.get_sampler(MultiStepDPMSampler(order=1))
    o2 = pipe.get_sampler(MultiStepDPMSampler(order=2))
    assert o1 is not o2 and o1.sampler.order == 1 and o2.sampler.order == 2


def test_generate_samples_records_latency_histogram():
    """Solo inference must be measurable with the serving layer's
    metric family: one inference/generate_ms observation per call."""
    from flaxdiff_tpu.telemetry import Telemetry, use_telemetry

    pipe = _tiny_pipe()
    with use_telemetry(Telemetry(enabled=False)) as tel:
        pipe.generate_samples(num_samples=1, resolution=8, channels=1,
                              diffusion_steps=2, sampler="ddim",
                              use_ema=False)
        hist = tel.registry.histogram("inference/generate_ms")
        assert hist.count == 1 and hist.total > 0.0
        assert tel.registry.counter(
            "inference/samples_generated").value == 1


def test_promptless_conditional_feeds_null_tokens(monkeypatch):
    """Unit coverage for the prompt-less conditional path: with a
    non-empty input_config and prompts=None, the null-conditioning
    tokens (NOT None) must reach the sampler — a context-free trace
    would mismatch the checkpointed param tree."""
    from flaxdiff_tpu.inputs import (ConditionalInputConfig,
                                     DiffusionInputConfig)
    from flaxdiff_tpu.inputs.encoders import HashTextEncoder
    from flaxdiff_tpu.samplers import DiffusionSampler

    enc = HashTextEncoder.create(features=16, max_length=8)
    model = build_model("simple_dit", emb_features=32, num_heads=4,
                        num_layers=1, patch_size=4, output_channels=1)
    null_cond = jnp.asarray(enc([""]))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)),
                        jnp.zeros((1,)), null_cond)
    pipe = DiffusionInferencePipeline.from_config(
        {"model": {"name": "simple_dit", "emb_features": 32,
                   "num_heads": 4, "num_layers": 1, "patch_size": 4,
                   "output_channels": 1},
         "schedule": {"name": "cosine", "timesteps": 100},
         "predictor": "epsilon"}, params=params)
    pipe.input_config = DiffusionInputConfig(
        sample_data_key="sample", sample_data_shape=(8, 8, 1),
        conditions=[ConditionalInputConfig(encoder=enc)])

    seen = {}
    real = DiffusionSampler.generate_samples

    def spy(self, *a, **kw):
        seen["conditioning"] = kw.get("conditioning")
        seen["unconditional"] = kw.get("unconditional")
        return real(self, *a, **kw)

    monkeypatch.setattr(DiffusionSampler, "generate_samples", spy)
    pipe.generate_samples(num_samples=2, resolution=8, channels=1,
                          diffusion_steps=2, sampler="ddim",
                          use_ema=False)
    assert seen["conditioning"] is not None
    assert seen["unconditional"] is None      # promptless: CFG stays off
    expected = pipe.input_config.get_unconditionals(batch_size=2)[0]
    np.testing.assert_array_equal(np.asarray(seen["conditioning"]),
                                  np.asarray(expected))


def test_from_registry_stale_step_warns_and_falls_back(tmp_path):
    """The registry may point at a step max_to_keep already rotated off
    disk: from_registry must warn and load the latest step instead of
    failing."""
    from flaxdiff_tpu.inference.pipeline import save_pipeline_config
    from flaxdiff_tpu.trainer import ModelRegistry
    from flaxdiff_tpu.trainer.checkpoints import Checkpointer

    ckpt_dir = str(tmp_path / "ckpt")
    pipe = _tiny_pipe()
    save_pipeline_config(ckpt_dir, {
        "model": {"name": "simple_dit", "emb_features": 32,
                  "num_heads": 4, "num_layers": 1, "patch_size": 4,
                  "output_channels": 1},
        "schedule": {"name": "cosine", "timesteps": 100},
        "predictor": "epsilon"})
    ck = Checkpointer(ckpt_dir, max_to_keep=2)
    ck.save(1, {"params": pipe.params}, force=True)
    ck.close()

    reg_path = str(tmp_path / "registry.json")
    # registry records a step that is NOT on disk (rotated away)
    ModelRegistry(reg_path).register_run(
        "stale", checkpoint_dir=ckpt_dir, step=999,
        metrics={"loss": 0.1})
    with pytest.warns(UserWarning, match="no longer on disk"):
        loaded = DiffusionInferencePipeline.from_registry(
            reg_path, metric="loss")
    out = loaded.generate_samples(num_samples=1, resolution=8,
                                  channels=1, diffusion_steps=2,
                                  sampler="ddim", use_ema=False)
    assert out.shape == (1, 8, 8, 1)


@pytest.fixture(scope="module")
def cli_run(tmp_path_factory):
    """ONE `train.main` run that the three checkpoint-to-pipeline tests
    read: a CONDITIONAL UNet of two levels (hash text encoder,
    cross-attention at the second level and in the mid block: the suite's
    one `train.main` that down- and upsamples and reads a per-level
    `attention_configs`) on a (data 2, fsdp 4) mesh, saved at steps 3
    and 6 and entered in the registry as `regrun`. The tests only load
    from it."""
    from train import main
    root = tmp_path_factory.mktemp("cli_run")
    ckpt_dir = root / "runs" / "regrun"
    hist = main([
        "--dataset", "synthetic", "--image_size", "8",
        "--batch_size", "16", "--architecture", "unet",
        "--model_config", json.dumps({
            "emb_features": 16, "feature_depths": [8, 12],
            "num_res_blocks": 1, "norm_groups": 4,
            "attention_configs": [None, {"heads": 2, "dim_head": 4}]}),
        "--dtype", "fp32",
        "--total_steps", "6", "--warmup_steps", "2",
        "--save_every", "3", "--log_every", "3",
        "--text_encoder", "hash",
        "--checkpoint_dir", str(ckpt_dir), "--run_name", "regrun",
        "--mesh_data", "2", "--mesh_fsdp", "4",
    ])
    return hist, ckpt_dir, str(root / "runs" / "registry.json")


def test_cli_end_to_end(cli_run):
    """The CLI trains on the synthetic dataset and the inference pipeline
    reloads from its checkpoint dir."""
    hist, ckpt_dir, _ = cli_run
    assert np.isfinite(hist["final_loss"])
    log = (ckpt_dir / "train_log.jsonl").read_text().strip()
    assert "loss" in log

    pipe = DiffusionInferencePipeline.from_checkpoint(str(ckpt_dir))
    out = pipe.generate_samples(num_samples=2, resolution=8,
                                diffusion_steps=3, sampler="ddim",
                                guidance_scale=1.5,
                                prompts=["a photo", "another"],
                                use_ema=True)
    assert out.shape == (2, 8, 8, 3)
    assert np.all(np.isfinite(out))


def test_pipeline_from_registry(cli_run):
    """Registry -> best checkpoint -> pipeline (reference
    from_wandb_registry equivalent)."""
    from flaxdiff_tpu.trainer import ModelRegistry

    # the CLI run is a real checkpoint + config + registry entry
    reg_path = cli_run[2]
    assert ModelRegistry(reg_path).best_run("loss")["run"] == "regrun"

    pipe = DiffusionInferencePipeline.from_registry(reg_path, metric="loss")
    out = pipe.generate_samples(num_samples=2, resolution=8,
                                diffusion_steps=2, sampler="ddim")
    assert out.shape == (2, 8, 8, 3)

    with pytest.raises(FileNotFoundError, match="no best run"):
        DiffusionInferencePipeline.from_registry(reg_path, metric="fid")


def test_promptless_sampling_from_conditional_checkpoint(cli_run):
    """A CONDITIONAL checkpoint sampled without prompts must condition on
    the cached null tokens, not trace the model context-free: the param
    tree's branch structure depends on context presence (Unet's mid
    block forces use_self_and_cross=False, so attn1 is cross-attention
    when context exists) and a context-free trace fails param loading."""
    pipe = DiffusionInferencePipeline.from_checkpoint(str(cli_run[1]))
    out = pipe.generate_samples(num_samples=2, resolution=8,
                                diffusion_steps=2, sampler="ddim",
                                use_ema=False)
    assert out.shape == (2, 8, 8, 3)
    assert np.all(np.isfinite(out))
