"""Worked production data config end to end (VERDICT r3 next #8).

Drives the full documented pipeline at (scaled-down) realistic shard
structure: per-corpus webdataset tars -> scripts/pack_dataset.py packed
shards -> the named `combined_aesthetic` registry entry (reference
data/dataset_map.py:19-105 combined_msml612 shape) -> grain loader ->
text-conditioned train step.
"""
import io
import json
import subprocess
import sys
import tarfile

import numpy as np
import pytest

from flaxdiff_tpu.data.dataset_map import (COMBINED_AESTHETIC_PARTS,
                                           get_dataset)

PARTS = COMBINED_AESTHETIC_PARTS
PER_PART = 10          # records per corpus
SHARDS_PER_PART = 3    # scaled-down stand-in for 569-shard corpora


def _write_wds_tar(path, part: str, n: int):
    """img2dataset-layout tar: image + sibling .txt caption per sample."""
    import cv2
    rng = np.random.default_rng(abs(hash(part)) % 2**32)
    with tarfile.open(path, "w") as tf:
        for i in range(n):
            img = rng.integers(0, 255, (24, 24, 3), np.uint8)
            ok, enc = cv2.imencode(".jpg", img)
            assert ok
            for name, data in ((f"{i:06d}.jpg", enc.tobytes()),
                               (f"{i:06d}.txt",
                                f"{part} sample {i}".encode())):
                info = tarfile.TarInfo(name)
                info.size = len(data)
                tf.addfile(info, io.BytesIO(data))


@pytest.fixture(scope="module")
def corpus_root(tmp_path_factory):
    """One mount-root with every COMBINED_AESTHETIC_PARTS corpus packed
    through the real scripts/pack_dataset.py CLI (webdataset tar mode,
    verbatim byte write-through)."""
    root = tmp_path_factory.mktemp("corpus")
    for part in PARTS:
        wds = root / f"{part}_wds"
        wds.mkdir()
        _write_wds_tar(wds / "00000.tar", part, PER_PART)
        res = subprocess.run(
            [sys.executable, "scripts/pack_dataset.py",
             "--src", str(wds), "--out", str(root / part),
             "--shards", str(SHARDS_PER_PART)],
            capture_output=True, text=True)
        assert res.returncode == 0, res.stderr
        meta = json.loads(res.stdout.strip().splitlines()[-1])
        assert meta["total"] == PER_PART
    return root


def test_combined_entry_builds_one_global_index(corpus_root):
    ds = get_dataset("combined_aesthetic", root=str(corpus_root),
                     image_size=16)
    src = ds.get_source()
    assert len(src) == PER_PART * len(PARTS)
    # records from every corpus are reachable through the one index
    seen = {src[i]["text"].split()[0] for i in range(len(src))}
    assert seen == set(PARTS)


def test_combined_entry_missing_part_guard(corpus_root, tmp_path):
    """A corpus dir with no shards must fail loudly, naming the part —
    not silently train on a shrunken mix."""
    partial = tmp_path / "partial"
    partial.mkdir()
    (partial / PARTS[0]).mkdir()   # exists but empty
    with pytest.raises(FileNotFoundError, match=PARTS[0]):
        get_dataset("combined_aesthetic", root=str(partial))
    # deliberate subset via parts=[...] is allowed
    ds = get_dataset("combined_aesthetic", root=str(corpus_root),
                     parts=[PARTS[1]], image_size=16)
    assert len(ds.get_source()) == PER_PART


def test_combined_grain_to_train_step(corpus_root):
    """Grain pipeline over the combined corpus feeds a text-conditioned
    diffusion train step; batches mix corpora."""
    import jax.numpy as jnp
    import optax

    from flaxdiff_tpu.data.dataloaders import get_dataset_grain
    from flaxdiff_tpu.inputs import HashTextEncoder
    from flaxdiff_tpu.models.unet import Unet
    from flaxdiff_tpu.parallel import create_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    size, batch = 16, 8
    ds = get_dataset("combined_aesthetic", root=str(corpus_root),
                     image_size=size)
    data = get_dataset_grain(ds, batch_size=batch, image_size=size,
                             worker_count=0, seed=0)
    it = data["train"]()
    batches = [next(it) for _ in range(4)]
    parts_seen = set()
    for b in batches:
        assert b["sample"].shape == (batch, size, size, 3)
        assert len(b["text"]) == batch
        parts_seen |= {t.split()[0] for t in b["text"]}
    assert len(parts_seen) >= 2, "no corpus mixing in sampled batches"

    enc = HashTextEncoder.create(features=16, max_length=8)
    model = Unet(output_channels=3, emb_features=16,
                 feature_depths=(8,), attention_configs=(None,),
                 num_res_blocks=1)      # one level: the batch is the point

    def apply_fn(params, x, t, cond):
        ctx = (cond["text"] if cond is not None else
               jnp.zeros((x.shape[0], 8, 16), x.dtype))
        return model.apply({"params": params}, x, t, ctx)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, size, size, 3)),
                          jnp.zeros((1,)), jnp.zeros((1, 8, 16)))["params"]

    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
        schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(),
        mesh=create_mesh(axes={"data": -1}),
        config=TrainerConfig(log_every=1, uncond_prob=0.1),
        null_cond={"text": np.asarray(enc([""]), np.float32)})
    b = batches[0]
    tb = {"sample": (b["sample"].astype(np.float32) - 127.5) / 127.5,
          "cond": {"text": np.asarray(enc(b["text"]), np.float32)}}
    loss1 = float(trainer.train_step(trainer.put_batch(tb)))
    loss2 = float(trainer.train_step(trainer.put_batch(tb)))
    assert np.isfinite(loss1) and np.isfinite(loss2)
