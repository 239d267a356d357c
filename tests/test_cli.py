"""Training CLI end-to-end smoke tests (train.py).

VERDICT r1 next #9 done-criterion: the CLI trains via the online
streaming path. Runs on the virtual 8-device CPU mesh; tiny shapes.
"""
import json
import sys

import numpy as np
import pytest

sys.path.insert(0, ".")  # repo root (train.py lives there)

# One resolution level: no test here is about the UNet's depth, and every
# level is more to initialise, compile and checkpoint (tests/
# test_inference.py `cli_run` is the suite's two-level `train.main`).
TINY_MODEL = json.dumps({
    "feature_depths": [8], "attention_configs": [None],
    "emb_features": 16, "num_res_blocks": 1,
})


def _run(tmp_path, *extra):
    import train
    return train.main([
        "--image_size", "16", "--batch_size", "16",
        "--architecture", "unet", "--model_config", TINY_MODEL,
        "--total_steps", "4", "--log_every", "2", "--warmup_steps", "2",
        "--save_every", "100", "--text_encoder", "hash",
        "--checkpoint_dir", str(tmp_path / "ckpt"), *extra])


def test_cli_trains_via_online_path(tmp_path):
    hist = _run(tmp_path, "--dataset", "online:synthetic")
    assert np.isfinite(hist["final_loss"])
    log = [json.loads(line)
           for line in open(tmp_path / "ckpt" / "train_log.jsonl")]
    assert any("loss" in rec for rec in log)


def test_cli_rejects_unknown_val_metric(tmp_path):
    with pytest.raises(SystemExit, match="unknown --val_metrics"):
        _run(tmp_path, "--val_every", "2", "--val_metrics", "nope")


def test_cli_validation_with_text_encoder_and_image_metrics(tmp_path):
    """Guided validation sampling while a text encoder is active: the
    conditioning handed to the sampler must mirror the train-step cond
    pytree ({"text": ...}); psnr/ssim metrics ride the same run."""
    hist = _run(tmp_path, "--dataset", "synthetic",
                "--val_every", "2", "--val_samples", "4", "--val_steps", "2",
                "--val_metrics", "psnr,ssim")
    assert np.isfinite(hist["final_loss"])
    log = [json.loads(line)
           for line in open(tmp_path / "ckpt" / "train_log.jsonl")]
    assert any("val/psnr" in rec for rec in log)
    assert any("val/ssim" in rec for rec in log)


def test_cli_gradient_accumulation(tmp_path):
    """--grad_accum wraps the optimizer in optax.MultiSteps; training
    still runs and the FSDP sharding of the wrapped opt state compiles."""
    hist = _run(tmp_path, "--dataset", "synthetic", "--grad_accum", "2")
    assert np.isfinite(hist["final_loss"])


def test_cli_tensor_parallel_mesh(tmp_path):
    """--mesh_tensor 2 trains with Megatron TP specs on the virtual mesh."""
    hist = _run(tmp_path, "--dataset", "synthetic",
                "--mesh_data", "2", "--mesh_fsdp", "2", "--mesh_tensor", "2")
    assert np.isfinite(hist["final_loss"])


def test_cli_trains_video_with_audio_conditioning(tmp_path, make_av_file):
    """Video+audio end-to-end through the CLI: av_folder dataset ->
    MelAudioEncoder tokens -> UNet3D train steps."""
    vids = tmp_path / "vids"
    vids.mkdir()
    for i in range(8):   # >= one full batch after drop_remainder
        make_av_file(vids / f"{i}.mp4", size=32, dur=1)
    hist = _run(
        tmp_path, "--dataset", "av_folder",
        "--dataset_path", str(vids),
        "--architecture", "unet_3d",
        "--model_config", json.dumps({
            "feature_depths": [8], "attention_levels": [True],
            "emb_features": 16, "num_res_blocks": 1, "norm_groups": 4,
            "heads": 2}),
        "--num_frames", "2", "--audio_encoder", "mel",
        "--text_encoder", "none", "--batch_size", "8",
        "--total_steps", "2", "--log_every", "1")
    assert np.isfinite(hist["final_loss"])


def test_cli_latent_diffusion_with_autoencoder(tmp_path):
    """--autoencoder trains the prior in codec latent space (reference
    training.py:192-195,339-345): the UNet's sample shape shrinks by the
    codec's downscale and widens to its latent channels; validation
    decodes back to pixel space."""
    hist = _run(
        tmp_path, "--dataset", "synthetic",
        "--autoencoder", "kl_vae",
        "--autoencoder_opts", json.dumps({
            "block_channels": [8, 16], "latent_channels": 4,
            "norm_groups": 4, "layers_per_block": 1}),
        "--val_every", "3", "--val_samples", "4", "--val_steps", "2",
        "--val_metrics", "psnr")
    assert np.isfinite(hist["final_loss"])
    cfg = json.load(open(tmp_path / "ckpt" / "pipeline_config.json"))
    assert cfg["autoencoder"]["name"] == "kl_vae"
    assert cfg["autoencoder"]["latent_channels"] == 4
    assert cfg["model"]["output_channels"] == 4


def test_cli_latent_diffusion_sd_vae_npz(tmp_path):
    """--autoencoder sd_vae with converted pretrained weights loaded
    from the npz the converter script writes."""
    import jax

    from flaxdiff_tpu.models.sd_vae import SDVAE
    vae = SDVAE.create(jax.random.PRNGKey(0), block_out_channels=(8, 8),
                       norm_groups=4, layers_per_block=1, image_size=16)
    flat = {}

    def _walk(tree, prefix):
        for k, v in tree.items():
            if isinstance(v, dict):
                _walk(v, f"{prefix}{k}/")
            else:
                flat[f"{prefix}{k}"] = np.asarray(v)
    _walk(vae.params, "")
    npz = tmp_path / "sd_vae.npz"
    np.savez(npz, **flat)
    hist = _run(
        tmp_path, "--dataset", "synthetic",
        "--autoencoder", "sd_vae",
        "--autoencoder_opts", json.dumps({"npz": str(npz),
                                          "norm_groups": 4}))
    assert np.isfinite(hist["final_loss"])


def test_cli_flat_params_checkpoint_to_inference(tmp_path):
    """--flat_params trains, checkpoints flat per-dtype vectors, and
    DiffusionInferencePipeline.from_checkpoint unflattens via the saved
    param template and samples (the flat layout must never strand a
    checkpoint outside the inference path)."""
    hist = _run(tmp_path, "--dataset", "synthetic", "--flat_params",
                "--save_every", "2")
    assert np.isfinite(hist["final_loss"])
    ckpt_dir = str(tmp_path / "ckpt")
    assert (tmp_path / "ckpt" / "param_template.json").exists()

    from flaxdiff_tpu.inference import DiffusionInferencePipeline
    pipe = DiffusionInferencePipeline.from_checkpoint(ckpt_dir)
    out = pipe.generate_samples(num_samples=2, resolution=16,
                                diffusion_steps=2, sampler="ddim")
    assert out.shape[0] == 2 and bool(np.isfinite(np.asarray(out)).all())
