"""wandb resume parity against a MOCKED wandb (VERDICT r2 next #6).

The reference auto-downloads the run's model artifact on wandb resume
(simple_trainer.py:194-211) and rebuilds inference pipelines from run
artifacts (inference/pipeline.py:59-147). Real wandb needs network; the
fake below implements the artifact store on the local filesystem with
the same API surface (init/Artifact/log_artifact/use_artifact/Api), so
the round trip — push on finish, pull on resume, from_wandb_run — is
exercised end to end.
"""
import json
import shutil
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, ".")  # repo root (train.py lives there)

# One resolution level: the tests here are about the artifact's round
# trip, and every level is more to initialise, compile and checkpoint.
TINY_MODEL = json.dumps({
    "feature_depths": [8], "attention_configs": [None],
    "emb_features": 16, "num_res_blocks": 1,
})


def make_fake_wandb(server_dir):
    """Filesystem-backed stand-in matching the API surface the package
    touches: wandb.init/run/Artifact/log_artifact/use_artifact/Api."""
    wandb = types.ModuleType("wandb")
    store = server_dir / "artifacts"
    store.mkdir(parents=True, exist_ok=True)

    class Artifact:
        def __init__(self, name, type):
            self.name = name
            self.type = type
            self._dir = None

        def add_dir(self, d):
            self._dir = str(d)

        def download(self, root=None):
            src = store / self.name
            dst = str(root) if root else str(server_dir / "dl" / self.name)
            shutil.copytree(src, dst, dirs_exist_ok=True)
            return dst

    class Image:
        def __init__(self, data):
            self.data = np.asarray(data)

    class Run:
        def __init__(self, id, project):
            self.id = id
            self.project = project
            self.logged = []
            self.artifacts = []

        def log(self, data, step=None):
            self.logged.append((step, data))

        def log_artifact(self, art, aliases=()):
            dst = store / art.name
            if dst.exists():
                shutil.rmtree(dst)
            shutil.copytree(art._dir, dst)
            self.artifacts.append(art)

        def use_artifact(self, spec, type=None):
            name = spec.split(":")[0]
            if not (store / name).exists():
                raise KeyError(f"no artifact {name}")
            return Artifact(name, type or "model")

        def finish(self):
            wandb.run = None

    def init(project=None, name=None, config=None, id=None, resume=None,
             **kw):
        if resume == "must" and id is None:
            raise ValueError("resume='must' needs an id")
        wandb.run = Run(id or "run0", project)
        wandb.init_calls.append({"project": project, "id": id,
                                 "resume": resume})
        return wandb.run

    class Api:
        def run(self, path):
            r = Run(path.split("/")[-1], path.split("/")[-2])
            r.logged_artifacts = lambda: [
                Artifact(p.name, "model") for p in sorted(store.iterdir())]
            return r

        def artifact(self, spec, type=None):
            return Artifact(spec.split(":")[0], type or "model")

    wandb.Artifact = Artifact
    wandb.Image = Image
    wandb.Api = Api
    wandb.init = init
    wandb.run = None
    wandb.init_calls = []
    return wandb


def _run_cli(tmp_path, *extra):
    import train
    return train.main([
        "--image_size", "16", "--batch_size", "16",
        "--architecture", "unet", "--model_config", TINY_MODEL,
        "--total_steps", "4", "--log_every", "2", "--warmup_steps", "2",
        "--save_every", "100", "--dataset", "synthetic",
        "--checkpoint_dir", str(tmp_path / "ckpt"),
        "--registry", str(tmp_path / "registry.json"),
        "--run_name", "resume-me", *extra])


@pytest.fixture()
def fake_wandb(tmp_path, monkeypatch):
    fake = make_fake_wandb(tmp_path / "wandb_server")
    monkeypatch.setitem(sys.modules, "wandb", fake)
    return fake


@pytest.fixture(scope="module")
def pushed(tmp_path_factory):
    """ONE train-and-push run against a fake server of its own: (history,
    its root). The two tests below start from a copy of what it left on
    the server and in the registry, and never from its checkpoint
    directory: a fresh host."""
    root = tmp_path_factory.mktemp("pushed")
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(sys.modules, "wandb",
                   make_fake_wandb(root / "wandb_server"))
        hist = _run_cli(root, "--wandb_project", "proj")
    return hist, root


def _fresh_host(pushed, tmp_path):
    """`tmp_path` as a host that has the server and the registry of the
    pushed run, and no local checkpoint."""
    shutil.copytree(pushed[1] / "wandb_server", tmp_path / "wandb_server",
                    dirs_exist_ok=True)
    shutil.copy(pushed[1] / "registry.json", tmp_path / "registry.json")


def test_wandb_resume_pulls_artifact_roundtrip(tmp_path, fake_wandb, pushed):
    """Train+push, wipe local checkpoints, resume by run id: the model
    artifact is pulled back and training continues from the saved step."""
    assert np.isfinite(pushed[0]["final_loss"])
    _fresh_host(pushed, tmp_path)
    # push_artifact stored the checkpoint dir server-side
    assert (tmp_path / "wandb_server" / "artifacts" / "resume-me").exists()
    assert not (tmp_path / "ckpt").exists()

    hist2 = _run_cli(tmp_path, "--wandb_project", "proj",
                     "--wandb_resume", "run0", "--total_steps", "2")
    assert np.isfinite(hist2["final_loss"])
    assert fake_wandb.init_calls[-1] == {"project": "proj", "id": "run0",
                                         "resume": "must"}
    # training continued FROM the pulled checkpoint: the restored step (4)
    # carried into the new run's steps
    assert hist2["steps"] and hist2["steps"][-1] <= 2  # fit counts locally
    from flaxdiff_tpu.trainer.checkpoints import Checkpointer
    ck = Checkpointer(str(tmp_path / "ckpt"))
    assert ck.latest_step() >= 4 + 2
    ck.close()


def test_from_wandb_run_builds_pipeline(tmp_path, fake_wandb, pushed):
    _fresh_host(pushed, tmp_path)
    from flaxdiff_tpu.inference.pipeline import DiffusionInferencePipeline
    pipe = DiffusionInferencePipeline.from_wandb_run(
        "ent/proj/run0", cache_dir=str(tmp_path / "cache"))
    out = pipe.generate_samples(num_samples=2, resolution=16,
                                diffusion_steps=2, sampler="ddim")
    assert out.shape == (2, 16, 16, 3)
    assert np.all(np.isfinite(out))


def test_pull_artifact_offline_returns_none(tmp_path):
    from flaxdiff_tpu.trainer.registry import pull_artifact
    assert pull_artifact("nope", str(tmp_path)) is None