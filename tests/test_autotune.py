"""Per-shape flash autotuner (ops/autotune.py): probe/winner logic, the
warm-cache zero-probe contract, env-override precedence, persistence
robustness, and the trainer's eval-shape scouting pass.

Probes use counting mocks throughout — no kernel is ever measured here
(CPU CI); the measured probe path has not run on a chip (ROADMAP D15)."""
import json
import os

import numpy as np
import pytest

from flaxdiff_tpu.ops import autotune as at


def _mock_probe_table(calls):
    table = {(128, 128): 30.0, (256, 512): 9.0, (512, 512): 8.2,
             (512, 1024): 5.6, (1024, 1024): 6.9}

    def probe(seq_q, seq_kv, d, dtype, bq, bk, native):
        calls.append((seq_q, seq_kv, d, dtype, bq, bk, native))
        base = table.get((bq, bk), 12.0)
        return base - 0.2 if native else base
    return probe


def test_probe_picks_winner_and_native_d(tmp_path):
    calls = []
    aut = at.FlashAutotuner(cache_dir=str(tmp_path),
                            probe_fn=_mock_probe_table(calls),
                            platform="tpu")
    plan = aut.get_plan(1024, 1024, 64, "bfloat16", allow_probe=True)
    assert (plan.block_q, plan.block_k) == (512, 1024)
    assert plan.native_d == 1          # native probed faster on winner
    assert plan.source == "probe"
    # 5 ladder rungs + 1 native candidate
    assert aut.probe_count == 6


def test_lane_multiple_head_dim_skips_native_probe(tmp_path):
    calls = []
    aut = at.FlashAutotuner(cache_dir=str(tmp_path),
                            probe_fn=_mock_probe_table(calls),
                            platform="tpu")
    plan = aut.get_plan(1024, 1024, 128, "bfloat16", allow_probe=True)
    assert plan.native_d == 0
    assert aut.probe_count == 5        # no native candidate at d=128


def test_warm_cache_performs_zero_probes(tmp_path):
    """The acceptance contract: a fresh PROCESS (modeled as a fresh
    registry over the same cache dir) re-measures nothing."""
    calls = []
    probe = _mock_probe_table(calls)
    at.FlashAutotuner(cache_dir=str(tmp_path), probe_fn=probe,
                      platform="tpu").get_plan(
        1024, 1024, 64, "bfloat16", allow_probe=True)
    warm = at.FlashAutotuner(cache_dir=str(tmp_path), probe_fn=probe,
                             platform="tpu")
    plan = warm.get_plan(1024, 1024, 64, "bfloat16", allow_probe=True)
    assert warm.probe_count == 0
    assert plan.source == "cache"
    assert (plan.block_q, plan.block_k, plan.native_d) == (512, 1024, 1)
    # probe_pending on a warm registry with no new observations: no-op
    assert warm.probe_pending() == {}
    assert warm.probe_count == 0


def test_env_overrides_win_over_cache(tmp_path, monkeypatch):
    calls = []
    aut = at.FlashAutotuner(cache_dir=str(tmp_path),
                            probe_fn=_mock_probe_table(calls),
                            platform="tpu")
    aut.get_plan(1024, 1024, 64, "bfloat16", allow_probe=True)
    n = aut.probe_count
    monkeypatch.setenv("FLAXDIFF_FLASH_BLOCK_Q", "256")
    monkeypatch.setenv("FLAXDIFF_FLASH_NATIVE_D", "0")
    plan = aut.get_plan(1024, 1024, 64, "bfloat16", allow_probe=True)
    assert plan.source == "env"
    assert (plan.block_q, plan.block_k, plan.native_d) == (256, 1024, 0)
    assert aut.probe_count == n        # env never triggers re-probing


def test_env_pinned_blocks_skip_probing_entirely(tmp_path, monkeypatch):
    """Both blocks pinned by env on a COLD shape: nothing to measure."""
    calls = []
    aut = at.FlashAutotuner(cache_dir=str(tmp_path),
                            probe_fn=_mock_probe_table(calls),
                            platform="tpu")
    monkeypatch.setenv("FLAXDIFF_FLASH_BLOCK_Q", "512")
    monkeypatch.setenv("FLAXDIFF_FLASH_BLOCK_K", "512")
    plan = aut.get_plan(2048, 2048, 64, "bfloat16", allow_probe=True)
    assert aut.probe_count == 0
    assert (plan.block_q, plan.block_k, plan.source) == (512, 512, "env")


def test_ladder_clamps_and_dedupes_short_sequences(tmp_path):
    calls = []
    aut = at.FlashAutotuner(cache_dir=str(tmp_path),
                            probe_fn=_mock_probe_table(calls),
                            platform="tpu")
    aut.get_plan(256, 77, 64, "bfloat16", allow_probe=True)
    block_calls = [(c[4], c[5]) for c in calls if not c[6]]
    # rq=256, rk=128: the five rungs collapse to two distinct candidates
    assert sorted(set(block_calls)) == [(128, 128), (256, 128)]
    assert len(block_calls) == len(set(block_calls))


def test_corrupt_cache_file_starts_fresh(tmp_path):
    path = tmp_path / at.CACHE_FILENAME
    path.write_text('{"version": 1, "plans": {"x": ')   # torn write
    aut = at.FlashAutotuner(cache_dir=str(tmp_path),
                            probe_fn=_mock_probe_table([]),
                            platform="tpu")
    assert aut.plans() == {}
    # and a probe rewrites a valid file
    aut.get_plan(1024, 1024, 64, "bfloat16", allow_probe=True)
    data = json.loads(path.read_text())
    assert "q1024_kv1024_d64_bfloat16_tpu" in data["plans"]


def test_dispatch_plan_precedence(tmp_path):
    """dispatch_plan: (None, None, None) when inactive OR when the shape
    has no cached plan (defaults keep today's env/arg behavior; the
    shape is recorded for probe_pending)."""
    at.deactivate()
    assert at.dispatch_plan(1024, 1024, 64, "bfloat16") == (None, None,
                                                            None)
    calls = []
    aut = at.FlashAutotuner(cache_dir=str(tmp_path),
                            probe_fn=_mock_probe_table(calls),
                            platform="tpu")
    aut.get_plan(1024, 1024, 64, "bfloat16", allow_probe=True)
    at._ACTIVE = aut
    try:
        assert at.dispatch_plan(1024, 1024, 64, "bfloat16") == \
            (512, 1024, True)
        # cold shape: defaults -> Nones, and observed for later probing
        assert at.dispatch_plan(4096, 4096, 64, "bfloat16") == \
            (None, None, None)
        assert any(k.startswith("q4096") for k in aut._observed)
        got = aut.probe_pending()
        assert any(k.startswith("q4096") for k in got)
    finally:
        at.deactivate()


def test_env_cache_dir_auto_activates(tmp_path, monkeypatch):
    """A child process inherits the tuned cache through
    FLAXDIFF_FLASH_TUNE_CACHE."""
    calls = []
    # platform must match what the env-activated registry detects on
    # this host (keys embed the platform)
    seed = at.FlashAutotuner(cache_dir=str(tmp_path),
                             probe_fn=_mock_probe_table(calls),
                             platform="cpu")
    seed.get_plan(1024, 1024, 64, "bfloat16", allow_probe=True)
    at.deactivate()
    monkeypatch.setenv("FLAXDIFF_FLASH_TUNE_CACHE", str(tmp_path))
    try:
        aut = at.active()
        assert aut is not None
        plan = aut.get_plan(1024, 1024, 64, "bfloat16")
        assert plan.source == "cache" and plan.block_q == 512
    finally:
        at.deactivate()


def test_trainer_autotune_flash_scouts_and_probes(tmp_path, mesh,
                                                 monkeypatch):
    """End-to-end: a trainer whose model dispatches flash attention
    (interpret hook makes the flash path reachable on CPU) records its
    attention shape via jax.eval_shape — NO device work, nothing
    compiled — then probe_pending measures it once; a second call
    re-measures nothing (warm in-process cache)."""
    import flax.linen as nn
    import jax.numpy as jnp
    import optax

    from flaxdiff_tpu.models.attention import AttentionLayer
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    monkeypatch.setenv("FLAXDIFF_FLASH_INTERPRET", "1")

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, t, cond=None):
            b, h, w, c = x.shape
            tok = nn.Dense(16)(x.reshape(b, h * w, c))
            tok = tok + AttentionLayer(heads=2, dim_head=8,
                                       backend="flash")(tok)
            return nn.Dense(c)(tok).reshape(b, h, w, c)

    model = Tiny()
    calls = []
    at.activate(str(tmp_path), probe_fn=_mock_probe_table(calls),
                platform="cpu")
    try:
        tr = DiffusionTrainer(
            apply_fn=lambda p, x, t, c: model.apply({"params": p}, x, t),
            init_fn=lambda k: model.init(k, jnp.zeros((1, 16, 16, 1)),
                                         jnp.zeros((1,)))["params"],
            tx=optax.adam(1e-3),
            schedule=CosineNoiseSchedule(timesteps=100),
            transform=EpsilonPredictionTransform(), mesh=mesh,
            config=TrainerConfig(normalize=False, uncond_prob=0.0))
        batch = tr.put_batch({"sample": np.zeros((8, 16, 16, 1),
                                                 np.float32)})
        plans = tr.autotune_flash(batch)
        assert plans, "eval_shape scouting recorded no attention shape"
        assert all(k.startswith("q256_kv256_d8") for k in plans)
        aut = at.active()
        n = aut.probe_count
        assert n > 0
        assert tr.autotune_flash(batch) == {}    # warm: zero new probes
        assert aut.probe_count == n
    finally:
        at.deactivate()
