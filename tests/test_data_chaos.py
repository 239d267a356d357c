"""Data-plane chaos suite (ISSUE 17 acceptance): injected corrupt
records + anomaly rollback, proved bit-identical.

Every scenario is deterministic by construction: corruption is either
REAL bytes in a packed shard (fails every decode, forever — replay sees
the same placeholder) or a `data.decode` fault spec firing on EVERY
decode of its key (prob=1.0, per_key), never a once-only spec that a
replay would sail past.
"""
import os

import numpy as np
import pytest

from flaxdiff_tpu import resilience as R
from flaxdiff_tpu.data import DataPlane, QuarantineJournal
from flaxdiff_tpu.data.dataplane import batch_digest
from flaxdiff_tpu.data.packed_records import PackedRecordWriter
from flaxdiff_tpu.data.sharded_source import ShardedPackedRecordSource
from flaxdiff_tpu.resilience.coordination import StepLedger

pytestmark = pytest.mark.chaos

SIZE = 8


def _write_shard(path, n=32, corrupt=(), seed=0):
    """Packed shard of PNG records; `corrupt` indices get garbage bytes
    that fail cv2 decode on every read."""
    import cv2
    rng = np.random.default_rng(seed)
    with PackedRecordWriter(str(path)) as w:
        for i in range(n):
            if i in corrupt:
                w.write({"image": b"\xba\xad\xf0\x0d" * 4,
                         "caption": f"torn {i}".encode()})
                continue
            img = rng.integers(0, 255, (SIZE, SIZE, 3), dtype=np.uint8)
            ok, enc = cv2.imencode(".png", img)
            assert ok
            w.write({"image": enc.tobytes(), "caption": f"img {i}".encode()})
    return str(path)


def _factory(shard, journal, batch=4):
    src = ShardedPackedRecordSource(
        shards=[shard], quarantine=journal,
        placeholder_size=SIZE).get_source()

    def factory(seed):
        def gen():
            epoch = 0
            while True:
                order = np.random.default_rng(
                    seed + epoch).permutation(len(src))
                for s in range(0, len(src) - batch + 1, batch):
                    imgs = [src[int(j)]["image"] for j in order[s:s + batch]]
                    yield {"sample": (np.stack(imgs).astype(np.float32)
                                      / 127.5) - 1.0}
                epoch += 1
        return gen()
    return factory


def test_quarantine_accounts_every_real_corruption(tmp_path):
    corrupt = {2, 9, 21}
    shard = _write_shard(tmp_path / "c.pr", corrupt=corrupt)
    journal = QuarantineJournal()
    it = _factory(shard, journal)(0)
    for _ in range(8):                  # one full epoch: every record read
        next(it)
    keys = sorted(int(e["key"].split(":")[1]) for e in journal.entries())
    assert keys == sorted(corrupt)
    assert all(e["reason"].startswith("ValueError")
               for e in journal.entries())
    # second epoch re-encounters the same records: journal dedupes
    for _ in range(8):
        next(it)
    assert len(journal) == len(corrupt)


def test_decode_fault_site_quarantines_deterministically(tmp_path):
    """`data.decode` armed per_key with prob=1.0 fires on EVERY decode
    of the matched record — the replay-safe way to poison a healthy
    shard (a once-only spec would decode clean on replay and break
    bit-identity)."""
    shard = _write_shard(tmp_path / "h.pr", corrupt=())
    journal = QuarantineJournal()
    plan = R.FaultPlan([R.FaultSpec("data.decode", prob=1.0, per_key=True,
                                    match=":3")])
    with plan.installed():
        it = _factory(shard, journal)(0)
        d1 = [batch_digest(next(it)) for _ in range(8)]
        it2 = _factory(shard, QuarantineJournal())(0)
        d2 = [batch_digest(next(it2)) for _ in range(8)]
    assert d1 == d2                     # poisoned stream replays exactly
    keys = [e["key"] for e in journal.entries()]
    assert keys and all(k.endswith(":3") or ":3" in k for k in keys)
    # without the plan the same record decodes clean -> different stream
    d3 = [batch_digest(b) for _, b in
          zip(range(8), _factory(shard, QuarantineJournal())(0))]
    assert d3 != d1


def test_placeholders_preserve_batch_geometry(tmp_path):
    shard = _write_shard(tmp_path / "g.pr", corrupt={0, 1, 2, 3})
    it = _factory(shard, QuarantineJournal())(0)
    for _ in range(8):
        b = next(it)
        assert b["sample"].shape == (4, SIZE, SIZE, 3)
        assert np.isfinite(b["sample"]).all()


def test_commit_restore_replays_bit_identical_stream(tmp_path):
    """Restart drill: consume k, commit k through a real StepLedger,
    then a FRESH plane restores from the ledger and the remainder of
    its stream is bit-identical to the uninterrupted reference."""
    corrupt = {4, 11}
    shard = _write_shard(tmp_path / "r.pr", corrupt=corrupt)
    ref_it = _factory(shard, QuarantineJournal())(0)
    reference = [batch_digest(next(ref_it)) for _ in range(20)]

    ledger = StepLedger(str(tmp_path / "ledger"))
    os.makedirs(tmp_path / "ledger", exist_ok=True)
    j1 = QuarantineJournal()
    plane = DataPlane(_factory(shard, j1), seed=0, journal=j1)
    for _ in range(9):
        next(plane)
    assert plane.commit(9, ledger=ledger) is True

    # process death + restart: everything rebuilt from disk state
    j2 = QuarantineJournal()
    plane2 = DataPlane(_factory(shard, j2), seed=0, journal=j2)
    plane2.restore(9, ledger=ledger)
    # the committed journal arrived before replay re-encountered anything
    assert {e["key"] for e in j2.entries()} == \
        {e["key"] for e in j1.entries()}
    replay = [batch_digest(next(plane2)) for _ in range(11)]
    assert replay == reference[9:20]


def test_rollback_seek_replays_bit_identical(tmp_path):
    shard = _write_shard(tmp_path / "s.pr", corrupt={7})
    plane = DataPlane(_factory(shard, QuarantineJournal()), seed=0)
    served = [batch_digest(next(plane)) for _ in range(13)]
    plane.seek(6)                       # rollback to committed step 6
    replay = [batch_digest(next(plane)) for _ in range(7)]
    assert replay == served[6:13]
    assert plane.rewinds == 1


SEAMS = ("_block_until_ready", "_fetch_losses", "_fetch_ring",
         "_fetch_gate_events")


def _rollback_fit(mesh, tmp_path, shard, with_plane):
    """A 10-step fit of a tiny model over `shard` with a step.nan fault
    at step 5 forcing an anomaly rollback, the trainer's four sync
    seams counted. With `with_plane` the batches come through a
    recording DataPlane; without, straight from the same generator (the
    control the plane's sync count is held against)."""
    import flax.linen as nn
    import jax.numpy as jnp
    import optax

    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import (Checkpointer, DiffusionTrainer,
                                      TrainerConfig)
    from flaxdiff_tpu.trainer import trainer as trainer_mod

    served = []
    journal = QuarantineJournal()

    class RecordingPlane(DataPlane):
        def __next__(self):
            idx = self.stream.cursor
            b = super().__next__()
            served.append((idx, self._digests[idx]))
            return b

    # batch=8: the mesh fixture shards batch dim over data*fsdp = 8 ways
    factory = _factory(shard, journal, batch=8)
    plane = (RecordingPlane(factory, seed=0, journal=journal)
             if with_plane else None)

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, t, cond=None):
            h = nn.Conv(8, (3, 3))(x)
            return nn.Conv(x.shape[-1], (3, 3))(jnp.tanh(h))

    model = Tiny()

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, SIZE, SIZE, 3)),
                          jnp.zeros((1,)))["params"]

    counts = dict.fromkeys(SEAMS, 0)
    real = {s: getattr(trainer_mod, s) for s in SEAMS}

    def counted(name):
        def inner(*a, **k):
            counts[name] += 1
            return real[name](*a, **k)
        return inner

    ev = R.EventLog("chaos")
    plan = R.FaultPlan(
        [R.FaultSpec("step.nan", at=(5,), error="flag", times=1)])
    with pytest.MonkeyPatch.context() as mp:
        for s in SEAMS:
            mp.setattr(trainer_mod, s, counted(s))
        with R.use_event_log(ev), plan.installed():
            trainer = DiffusionTrainer(
                apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
                schedule=CosineNoiseSchedule(timesteps=100),
                transform=EpsilonPredictionTransform(), mesh=mesh,
                config=TrainerConfig(normalize=False, log_every=2),
                checkpointer=Checkpointer(
                    str(tmp_path / ("ck_plane" if with_plane
                                    else "ck_ctrl")),
                    event_log=ev, use_ledger=True))
            hist = trainer.fit(None if with_plane else factory(0),
                               total_steps=10, save_every=4,
                               data_plane=plane)
            trainer.checkpointer.wait_until_finished()
            ledger = trainer.checkpointer.ledger
            trainer.checkpointer.close()
    return {"hist": hist, "served": served, "journal": journal,
            "plane": plane, "ledger": ledger, "counts": counts,
            "rollbacks": ev.count("rollback", "train.step")}


CORRUPT = {3, 12}


@pytest.fixture(scope="module")
def chaos_shard(tmp_path_factory):
    return _write_shard(tmp_path_factory.mktemp("shard") / "t.pr",
                        corrupt=CORRUPT)


@pytest.fixture(scope="module")
def chaos_run(mesh, chaos_shard, tmp_path_factory):
    """The fit through the DataPlane, once for both tests below."""
    return _rollback_fit(mesh, tmp_path_factory.mktemp("plane"),
                         chaos_shard, with_plane=True)


def test_trainer_rollback_rewinds_data_plane_bit_identical(
        chaos_run, chaos_shard):
    """The end-to-end acceptance scenario: a step.nan fault mid-fit
    triggers an anomaly rollback; with a DataPlane wired into fit(),
    the upload pipeline is torn down, the stream rewound, and every
    re-served batch is bit-identical to the uninterrupted reference —
    while the quarantine journal accounts for the injected corruption
    and no prefetch worker outlives the fit."""
    import threading

    reference = [batch_digest(b) for _, b in
                 zip(range(32),
                     _factory(chaos_shard, QuarantineJournal(),
                              batch=8)(0))]

    run = chaos_run
    hist, served, journal = run["hist"], run["served"], run["journal"]
    plane, ledger = run["plane"], run["ledger"]

    assert run["rollbacks"] == 1
    assert np.isfinite(hist["final_loss"])
    # every served batch — including re-served post-rollback ones —
    # matches the uninterrupted reference at its index
    assert all(reference[i] == d for i, d in served)
    counts = {}
    for i, _ in served:
        counts[i] = counts.get(i, 0) + 1
    assert any(c > 1 for c in counts.values())   # replay actually happened
    assert plane.rewinds >= 1
    # served indices are gap-free: nothing stranded across the
    # prefetcher teardown/rebuild
    idxs = sorted(counts)
    assert idxs == list(range(len(idxs)))
    # ...and neither the torn-down prefetcher's worker nor its
    # replacement's is still alive
    assert not [t.name for t in threading.enumerate()
                if t.is_alive() and "flaxdiff-put-batch" in t.name]
    # quarantine accounts for every injected corruption
    assert sorted(int(e["key"].split(":")[1])
                  for e in journal.entries()) == sorted(CORRUPT)
    # data-plane state was committed beside the model checkpoints, and
    # the committed cursor equals a committed MODEL step (the state step
    # counter rewinds with the restore, so the post-rollback save lands
    # on a recounted step — e.g. 6 — not the loop step 8)
    assert ledger is not None
    state = ledger.data_state_at(10)
    assert state is not None and state["cursor"] in (4, 6, 8)
    assert {e["key"] for e in state["journal"]["entries"]} == \
        {e["key"] for e in journal.entries()}


def test_data_plane_adds_no_host_sync_over_a_control_fit(
        mesh, tmp_path, chaos_run, chaos_shard):
    """docs/DATA.md "Zero host syncs": through the same fault and the
    same rollback, a fit fed by the DataPlane calls each of the
    trainer's four sync seams exactly as often as a fit fed by the bare
    generator."""
    chaos = chaos_run
    control = _rollback_fit(mesh, tmp_path, chaos_shard, with_plane=False)
    assert chaos["rollbacks"] == control["rollbacks"] == 1
    assert chaos["plane"].rewinds >= 1
    assert chaos["counts"] == control["counts"]
    assert chaos["counts"]["_fetch_losses"] > 0     # the seams counted
