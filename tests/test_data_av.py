"""Audio/video pipeline tests (flaxdiff_tpu/data/sources/av.py).

Fixtures are synthesized in-process: cv2-encoded video + a scipy-written
sidecar WAV (the av module's no-ffmpeg path) — no network, no real
datasets. The end-to-end test drives a {video, audio} batch through one
UNet3D train step (VERDICT r1 #3 done-criterion).
"""
import numpy as np
import pytest

from flaxdiff_tpu.data.sources.av import (
    AudioVideoAugmenter, AVSyncSource, extract_audio, log_mel_spectrogram,
    read_av_random_clip, simple_face_mask, video_fps, video_frame_count)

FPS = 25
DUR = 3  # seconds
SR = 16000
SIDCAR_SR = 22050  # sidecar stored at a different rate to exercise resample
TONE_HZ = 440


def _make_av_file(path, size=64, dur=DUR, fps=FPS, tone=TONE_HZ):
    """cv2 mp4v video + sine-tone sidecar wav."""
    import cv2
    from scipy.io import wavfile
    path = str(path)
    w = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*"mp4v"), fps,
                        (size, size))
    assert w.isOpened()
    rng = np.random.default_rng(0)
    for i in range(int(dur * fps)):
        # frame index encoded in brightness so clips are distinguishable
        frame = np.full((size, size, 3), (i * 7) % 255, np.uint8)
        frame[: size // 4] = rng.integers(0, 255, (size // 4, size, 3),
                                          dtype=np.uint8)
        w.write(frame)
    w.release()
    t = np.arange(int(dur * SIDCAR_SR), dtype=np.float32) / SIDCAR_SR
    audio = (0.5 * np.sin(2 * np.pi * tone * t) * 32767).astype(np.int16)
    wavfile.write(path.rsplit(".", 1)[0] + ".wav", SIDCAR_SR, audio)
    return path


@pytest.fixture(scope="module")
def av_file(tmp_path_factory):
    return _make_av_file(tmp_path_factory.mktemp("av") / "clip.mp4")


@pytest.fixture(scope="module")
def av_tree(tmp_path_factory):
    """Identity-structured folder: root/<id>/clip.mp4 (voxceleb2 layout)."""
    root = tmp_path_factory.mktemp("avtree")
    for ident in ("id001", "id002"):
        d = root / ident
        d.mkdir()
        _make_av_file(d / "a.mp4", size=48, dur=2)
    return str(root)


def test_probes(av_file):
    assert video_fps(av_file) == pytest.approx(FPS, abs=1)
    assert video_frame_count(av_file) == pytest.approx(DUR * FPS, abs=3)


def test_extract_audio_window(av_file):
    audio, sr = extract_audio(av_file, start_time=0.5, duration=1.0,
                              target_sr=SR)
    assert sr == SR
    assert abs(audio.shape[0] - SR) < SR // 20  # ~1 s of samples
    assert np.abs(audio).max() <= 1.0
    # a sine tone has substantial energy
    assert np.abs(audio).std() > 0.05
    # dominant frequency is the synthesized tone
    spec = np.abs(np.fft.rfft(audio[:SR]))
    peak_hz = np.argmax(spec)  # bin width = 1 Hz for a 1 s window
    assert abs(peak_hz - TONE_HZ) < 15


def test_read_av_random_clip_contract(av_file):
    n, pad = 8, 2
    framewise, full, frames = read_av_random_clip(
        av_file, num_frames=n, audio_frame_padding=pad,
        target_sr=SR, target_fps=FPS, random_seed=7)
    spf = SR // FPS
    assert framewise.shape == (1, n, 1, spf)
    assert full.shape == (n + 2 * pad, spf)
    assert frames.shape[0] == n and frames.shape[3] == 3
    assert frames.dtype == np.uint8
    # central rows of the padded audio == the framewise audio
    np.testing.assert_allclose(full[pad:pad + n],
                               framewise[0, :, 0, :], atol=1e-6)


def test_read_av_random_clip_deterministic_seed(av_file):
    a = read_av_random_clip(av_file, num_frames=4, random_seed=3)
    b = read_av_random_clip(av_file, num_frames=4, random_seed=3)
    np.testing.assert_array_equal(a[2], b[2])
    np.testing.assert_allclose(a[1], b[1], atol=1e-6)


def test_read_av_random_clip_too_short_raises(av_file):
    with pytest.raises(ValueError, match="too short"):
        read_av_random_clip(av_file, num_frames=1000)


def test_read_av_clip_missing_file_raises(tmp_path):
    with pytest.raises(Exception):
        read_av_random_clip(str(tmp_path / "nope.mp4"), num_frames=4)


def test_log_mel_spectrogram_tone():
    t = np.arange(SR, dtype=np.float32) / SR
    audio = np.sin(2 * np.pi * TONE_HZ * t)
    mel = log_mel_spectrogram(audio, sr=SR, n_mels=80)
    assert mel.shape[1] == 80
    assert mel.shape[0] > 50
    # the tone bin dominates a silent signal's floor
    silent = log_mel_spectrogram(np.zeros(SR, np.float32), sr=SR, n_mels=80)
    assert mel.max() > silent.max() + 3  # orders of magnitude in log10


def test_simple_face_mask_geometry():
    m = simple_face_mask(64, face_hide_percentage=0.5)
    assert m.shape == (64, 64)
    assert set(np.unique(m)) <= {0.0, 1.0}
    # mask covers the lower-center face region only
    assert m[:10].sum() == 0            # top rows clear
    assert m[:, :5].sum() == 0          # left margin clear
    assert m[30:45, 20:44].mean() > 0.9  # lower-center covered
    bigger = simple_face_mask(64, face_hide_percentage=1.0)
    assert bigger.sum() > m.sum()


def test_augmenter_emits_av_contract(av_file):
    aug = AudioVideoAugmenter(num_frames=6, image_size=32,
                              audio_frame_padding=2, with_mel=True,
                              with_face_mask=True)
    tf = aug.create_transform()
    out = tf({"path": av_file}, rng=np.random.default_rng(0))
    assert out["video"].shape == (6, 32, 32, 3)
    assert out["audio"]["full_audio"].shape == (10, SR // FPS)
    assert out["audio"]["framewise_audio"].shape == (1, 6, 1, SR // FPS)
    assert out["mel"].ndim == 2
    assert out["mask"].shape == (32, 32)


def test_av_sync_source(av_tree):
    src = AVSyncSource(root=av_tree).get_source()
    assert len(src) == 2
    rec = src[0]
    assert rec["identity"] in ("id001", "id002")
    pair = AVSyncSource.sync_pair(rec["path"], num_frames=4,
                                  rng=np.random.default_rng(0))
    assert pair["frames"].shape[0] == 4
    assert pair["wrong_frames"].shape[0] == 4
    # windows must not overlap
    gap = abs(float(pair["start_time"]) - float(pair["wrong_start_time"]))
    assert gap >= 4 / FPS - 1e-6
    assert pair["audio"].shape == (4, SR // FPS)


def test_audio_encoder_tokens_align_with_frames():
    from flaxdiff_tpu.inputs import MelAudioEncoder
    enc = MelAudioEncoder.create(n_mels=16, features=32,
                                 samples_per_frame=SR // FPS)
    framewise = np.random.default_rng(0).normal(
        size=(2, 6, 1, SR // FPS)).astype(np.float32)
    emb = enc(framewise)
    assert emb.shape == (2, 6, 32)
    # deterministic
    np.testing.assert_allclose(emb, enc(framewise), atol=0)
    # raw waveform path gives the same token count
    raw = framewise.reshape(2, -1)
    emb2 = enc(raw)
    assert emb2.shape == (2, 6, 32)


def test_av_batch_trains_unet3d_step(av_file):
    """VERDICT r1 #3 done-criterion: a video+audio batch end-to-end into
    one UNet3D train step, audio as cross-attention context."""
    import jax.numpy as jnp
    import optax

    from flaxdiff_tpu.inputs import MelAudioEncoder
    from flaxdiff_tpu.models.unet3d import UNet3D
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    # two frames, one resolution level: the claim is that an AV batch
    # reaches the step with audio as the attention's context
    n_frames, size, feat = 2, 16, 32
    enc = MelAudioEncoder.create(n_mels=16, features=feat,
                                 samples_per_frame=SR // FPS)
    aug = AudioVideoAugmenter(num_frames=n_frames, image_size=size)
    tf = aug.create_transform()
    rng = np.random.default_rng(0)
    elems = [tf({"path": av_file}, rng=rng) for _ in range(8)]
    video = np.stack([e["video"] for e in elems]).astype(np.float32)
    audio_ctx = np.asarray(enc(np.stack(
        [e["audio"]["framewise_audio"][0] for e in elems])))
    batch = {"sample": video, "cond": {"audio": audio_ctx}}

    model = UNet3D(output_channels=3, emb_features=32,
                   feature_depths=(8,), attention_levels=(True,),
                   heads=2, num_res_blocks=1, norm_groups=4)

    def apply_fn(params, x, t, cond):
        ctx = cond["audio"] if cond is not None else None
        return model.apply({"params": params}, x, t, ctx)

    def init_fn(key):
        return model.init(
            key, jnp.zeros((1, n_frames, size, size, 3)), jnp.zeros((1,)),
            jnp.zeros((1, n_frames, feat)))["params"]

    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
        schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(),
        mesh=create_mesh(axes={"data": -1}),
        config=TrainerConfig(log_every=1, uncond_prob=0.0),
        null_cond={"audio": np.zeros((1, n_frames, feat), np.float32)})
    loss1 = float(trainer.train_step(trainer.put_batch(batch)))
    loss2 = float(trainer.train_step(trainer.put_batch(batch)))
    assert np.isfinite(loss1) and np.isfinite(loss2)


def test_av_decode_bench_harness(tmp_path, make_av_file):
    """The throughput/leak harness (scripts/bench_av_decode.py, reference
    benchmark_decord.py:140-274 analogue) runs and emits sane JSON."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "bench_av_decode", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "bench_av_decode.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    video = make_av_file(tmp_path / "clip.mp4", size=32, dur=2)
    out = mod.main(["--video", video, "--iters", "4",
                    "--num_frames", "4",
                    "--out", str(tmp_path / "av.json")])
    assert {r["mode"] for r in out["results"]} == {"av_clip", "frames_only"}
    for r in out["results"]:
        assert r["clips_per_sec"] > 0
        assert r["frames_per_sec"] > 0
        assert np.isfinite(r["rss_end_mib"])
    assert (tmp_path / "av.json").exists()
