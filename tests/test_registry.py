"""Model registry tests (flaxdiff_tpu/trainer/registry.py)."""
import json

import numpy as np

from flaxdiff_tpu.trainer import ModelRegistry


def test_registry_tracks_direction_aware_best(tmp_path):
    reg = ModelRegistry(str(tmp_path / "registry.json"))
    r1 = reg.register_run("run_a", checkpoint_dir="/ckpt/a", step=100,
                          metrics={"fid": 40.0, "clip_score": 0.2},
                          metric_directions={"fid": False,
                                             "clip_score": True})
    assert r1 == {"fid": True, "clip_score": True}  # first run is best

    r2 = reg.register_run("run_b", checkpoint_dir="/ckpt/b", step=100,
                          metrics={"fid": 55.0, "clip_score": 0.3},
                          metric_directions={"fid": False,
                                             "clip_score": True})
    assert r2 == {"fid": False, "clip_score": True}

    assert reg.best_run("fid")["run"] == "run_a"
    assert reg.best_run("clip_score")["run"] == "run_b"
    assert reg.best_checkpoint("fid") == "/ckpt/a"
    assert reg.best_run("nope") is None


def test_registry_persists_and_reloads(tmp_path):
    path = str(tmp_path / "registry.json")
    ModelRegistry(path).register_run(
        "r", checkpoint_dir="/c", step=5, metrics={"loss": 0.5})
    reloaded = ModelRegistry(path)
    assert "r" in reloaded.runs()
    assert reloaded.best_run("loss")["value"] == 0.5
    # updating the same run with a worse loss keeps the best pointer
    became = reloaded.register_run("r2", checkpoint_dir="/c2", step=9,
                                   metrics={"loss": 0.9})
    assert became["loss"] is False
    # file is valid json on disk
    data = json.load(open(path))
    assert set(data) >= {"runs", "best"}


def test_registry_push_artifact_offline_is_false(tmp_path):
    reg = ModelRegistry(str(tmp_path / "registry.json"))
    assert reg.push_artifact("r", str(tmp_path)) is False


def test_cli_writes_registry(tmp_path):
    import sys
    sys.path.insert(0, ".")
    import train
    hist = train.main([
        "--dataset", "synthetic", "--image_size", "16",
        "--batch_size", "16", "--architecture", "unet",
        "--model_config", json.dumps({
            "feature_depths": [8], "attention_configs": [None],
            "emb_features": 16, "num_res_blocks": 1}),
        "--total_steps", "4", "--log_every", "2", "--warmup_steps", "2",
        "--save_every", "100", "--text_encoder", "none",
        "--checkpoint_dir", str(tmp_path / "runs" / "exp1"),
        "--run_name", "exp1"])
    assert np.isfinite(hist["final_loss"])
    reg = ModelRegistry(str(tmp_path / "runs" / "registry.json"))
    assert "exp1" in reg.runs()
    assert reg.best_run("loss")["run"] == "exp1"

    # the run's final save, of a model WITHOUT conditioning, builds a
    # pipeline (tests/test_inference.py loads a conditional one)
    from flaxdiff_tpu.inference import DiffusionInferencePipeline
    pipe = DiffusionInferencePipeline.from_checkpoint(
        str(tmp_path / "runs" / "exp1"))
    out = pipe.generate_samples(num_samples=2, resolution=16,
                                diffusion_steps=2, sampler="ddim")
    assert out.shape == (2, 16, 16, 3)


def test_registry_top_k_ranked(tmp_path):
    """Ranked top-k per metric with run metadata (reference compares
    against sweep-history top-k, general_diffusion_trainer.py:596-703)."""
    from flaxdiff_tpu.trainer import ModelRegistry
    reg = ModelRegistry(str(tmp_path / "registry.json"))
    for i, loss in enumerate([0.5, 0.2, 0.9, 0.4]):
        reg.register_run(f"run{i}", checkpoint_dir=f"/ck/{i}", step=10 + i,
                         metrics={"loss": loss, "clip_score": 1 - loss},
                         metric_directions={"loss": False,
                                            "clip_score": True},
                         config={"arch": f"a{i}"})
    top = reg.top_k("loss", k=3)
    assert [r["run"] for r in top] == ["run1", "run3", "run0"]
    assert top[0]["value"] == 0.2 and top[0]["config"] == {"arch": "a1"}
    assert all(not r["higher_is_better"] for r in top)
    top_cs = reg.top_k("clip_score", k=2)
    assert [r["run"] for r in top_cs] == ["run1", "run3"]
    assert all(r["higher_is_better"] for r in top_cs)
    # persisted: a fresh instance ranks identically
    reg2 = ModelRegistry(str(tmp_path / "registry.json"))
    assert [r["run"] for r in reg2.top_k("loss")] == \
        ["run1", "run3", "run0", "run2"]


def test_compare_against_wandb_best_fake_api():
    """The wandb-API comparison (reference general_diffusion_trainer
    596-703) with an injected fake client: direction-aware ranking,
    top-k bounds, is_good/is_best, sweep vs project key selection."""
    from flaxdiff_tpu.trainer.registry import compare_against_wandb_best

    class Run:
        def __init__(self, id, **summary):
            self.id, self.summary = id, summary

    class FakeApi:
        def __init__(self, runs):
            self._runs = runs
            self.calls = []

        def runs(self, path=None, filters=None):
            self.calls.append(("runs", path, filters))
            return self._runs

        def sweep(self, path):
            self.calls.append(("sweep", path))
            api = self

            class Sweep:
                runs = api._runs
            return Sweep()

    # lower-is-better project query keys on best_<metric>
    api = FakeApi([Run("a", **{"best_train/loss": 0.5}),
                   Run("b", **{"best_train/loss": 0.3}),
                   Run("c", **{"best_train/loss": 0.9})])
    good, best, bounds, ranked = compare_against_wandb_best(
        0.4, metric="train/loss", top_k=2, api=api,
        entity="e", project="p")
    assert (good, best) == (True, False)       # inside top-2, not best
    assert bounds == (0.3, 0.5)
    assert [r["run"] for r in ranked] == ["b", "a"]
    assert api.calls[0][1] == "e/p"

    good, best, _, _ = compare_against_wandb_best(
        0.2, metric="train/loss", top_k=2, api=api,
        entity="e", project="p")
    assert (good, best) == (True, True)
    good, best, _, _ = compare_against_wandb_best(
        0.95, metric="train/loss", top_k=2, api=api,
        entity="e", project="p")
    assert (good, best) == (False, False)

    # higher-is-better sweep query keys on the bare metric
    api2 = FakeApi([Run("x", **{"val/clip": 0.8}),
                    Run("y", **{"val/clip": 0.6})])
    good, best, bounds, ranked = compare_against_wandb_best(
        0.9, metric="val/clip", top_k=2, higher_is_better=True,
        api=api2, entity="e", project="p", sweep_id="s1")
    assert (good, best) == (True, True)
    assert bounds == (0.6, 0.8)
    assert api2.calls[0] == ("sweep", "e/p/s1")

    # empty history: trivially best
    good, best, bounds, ranked = compare_against_wandb_best(
        1.0, api=FakeApi([]), entity="e", project="p")
    assert (good, best, bounds, ranked) == (True, True, None, [])


def test_compare_against_wandb_best_edge_cases():
    """Non-finite/missing summary values are dropped (not ranked at
    ±inf), the finishing run excludes itself, and sweep+filters raises."""
    import pytest

    from flaxdiff_tpu.trainer.registry import compare_against_wandb_best

    class Run:
        def __init__(self, id, **summary):
            self.id, self.summary = id, summary

    class FakeApi:
        def __init__(self, runs):
            self._runs = runs

        def runs(self, path=None, filters=None):
            return self._runs

        def sweep(self, path):
            api = self

            class Sweep:
                runs = api._runs
            return Sweep()

    # crashed run (no summary key) must not blow out the bounds
    api = FakeApi([Run("ok", **{"best_train/loss": 0.5}), Run("crashed")])
    good, best, bounds, ranked = compare_against_wandb_best(
        100.0, metric="train/loss", top_k=2, api=api,
        entity="e", project="p")
    assert (good, best) == (False, False)
    assert bounds == (0.5, 0.5)
    assert [r["run"] for r in ranked] == ["ok"]

    # a run that just set the project best must not compare against its
    # own live-synced summary
    api = FakeApi([Run("me", **{"best_train/loss": 0.1}),
                   Run("other", **{"best_train/loss": 0.5})])
    good, best, *_ = compare_against_wandb_best(
        0.1, metric="train/loss", top_k=2, api=api,
        entity="e", project="p", exclude_run_id="me")
    assert (good, best) == (True, True)

    with pytest.raises(ValueError, match="filters"):
        compare_against_wandb_best(
            0.1, api=FakeApi([]), entity="e", project="p",
            sweep_id="s", filters={"state": "finished"})
