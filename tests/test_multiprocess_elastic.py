"""Elastic-world chaos suite: REAL 2-process host loss, late join, and
anomaly-quorum eviction (ISSUE 12 acceptance scenarios).

Unlike tests/test_multiprocess.py these workers run WITHOUT
`jax.distributed` — its coordinator dies with process 0 and its world
is fixed at initialize(), the two assumptions an elastic world cannot
make. Coordination rides a FileTransport over a shared directory
(identical protocol/timeout semantics to the KV-service backend), each
host owns its local devices + its own checkpoint dir, and ONE shared
control ledger records commits and membership transitions.

Orphan safety: every phase joins/kills its children in `finally` (the
multiprocess-suite convention — an orphaned worker wedges later test
files into fake timeouts on this single-CPU box).
"""
import json
import os
import subprocess
import sys
import time

import pytest
from conftest import CHEAP_COMPILE_FLAGS

WORKER = os.path.join(os.path.dirname(__file__), "multiprocess_worker.py")

pytestmark = [pytest.mark.chaos, pytest.mark.multiprocess]


# A phase ends in 40-65 s beside five busy workers; a wedged one may not
# take a third of the suite's limit.
PHASE_TIMEOUT_S = 180


def _launch(phase: str, proc_id: int, ckpt_root: str):
    env = os.environ.copy()
    env["XLA_FLAGS"] = CHEAP_COMPILE_FLAGS      # and one device a host
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, WORKER, phase, str(proc_id), "0", ckpt_root],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)


def _finish(proc, phase, i, expect_rc=0, expect_result=True):
    out, err = proc.communicate(timeout=PHASE_TIMEOUT_S)
    assert proc.returncode == expect_rc, (
        f"{phase} proc {i} rc={proc.returncode} (wanted {expect_rc})\n"
        f"stdout:{out[-2000:]}\nstderr:{err[-2000:]}")
    if not expect_result:
        return None
    lines = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
    assert lines, f"{phase} proc {i} printed no RESULT line:\n{out[-2000:]}"
    return json.loads(lines[-1][len("RESULT "):])


def test_kill_one_mid_run_survivor_shrinks_and_trains(tmp_path):
    """Kill-one-mid-run: rank 1 dies hard (os._exit, no vote) at step 4;
    rank 0's commit barrier times out, it commits a `world_changed`
    shrink in the ledger, restores the consensus step 2, re-shards its
    data pipeline to (rank 0, world 1), and keeps training — history
    attributes the transition to `elastic_shrink` badput with a
    reclaimed estimate, and there is NO coordination_lost exit."""
    root = str(tmp_path / "elastic")
    procs = [_launch("elastic_kill", i, root) for i in range(2)]
    try:
        # rank 1 self-destructs with rc 17 and never prints a RESULT
        _finish(procs[1], "elastic_kill", 1, expect_rc=17,
                expect_result=False)
        r0 = _finish(procs[0], "elastic_kill", 0)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    assert r0["coordination_lost"] is False
    assert len(r0["elastic"]) == 1
    tr = r0["elastic"][0]
    assert tr["kind"] == "shrink" and tr["world"] == 1 and tr["step"] == 2
    assert tr["reclaimed_s"] >= 0.0
    assert r0["goodput_badput"].get("elastic_shrink", 0.0) > 0.0
    # the ledger carries the membership history and the shrunken-world
    # commits: step 2 committed by the world of 2, later steps by the
    # world of 1 — and the survivor made progress (>= 4 steps) past the
    # consensus step after the transition
    wc = r0["world_changes"]
    assert len(wc) == 1 and wc[0]["change"] == "shrink"
    assert wc[0]["world"] == 1 and wc[0]["members"] == [0]
    assert r0["commit_worlds"]["2"] == 2
    post = [int(s) for s in r0["committed"] if int(s) > 2]
    assert post, f"no committed step after the shrink: {r0['committed']}"
    assert all(r0["commit_worlds"][str(s)] == 1 for s in post)
    assert r0["state_step"] >= 6     # >= 4 steps past the restored 2
    # the data pipeline was re-sharded around the smaller world
    assert [0, 1] in r0["factory_calls"]


def test_late_joiner_readmitted_and_worlds_commit_in_lockstep(tmp_path):
    """Late-join: rank 0 trains alone; rank 1 launches late, parks via
    request_join, is admitted at a commit boundary (`world_changed`
    grow entry), restores the consensus step from rank 0's shard dir,
    and both hosts then commit the SAME final step with world 2
    recorded in the commit entries."""
    root = str(tmp_path / "elastic")
    p0 = _launch("elastic_join", 0, root)
    procs = [p0]
    try:
        time.sleep(5.0)     # rank 1 is genuinely LATE
        p1 = _launch("elastic_join", 1, root)
        procs.append(p1)
        r0 = _finish(p0, "elastic_join", 0)
        r1 = _finish(p1, "elastic_join", 1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    assert r0["coordination_lost"] is False
    assert r1["coordination_lost"] is False
    # the grow transition is in the shared ledger exactly once
    grows = [w for w in r0["world_changes"] if w["change"] == "grow"]
    assert len(grows) == 1
    assert grows[0]["members"] == [0, 1] and grows[0]["world"] == 2
    assert r1["joined_at"] == grows[0]["step"]
    assert r1["join_world"] == 2
    # both ended as members of the same world...
    assert r0["members"] == r1["members"] == [0, 1]
    # ...and committed the same final step, with the grown world size
    # recorded by the commit round itself
    assert r0["committed"] == r1["committed"]
    final = r0["committed"][-1]
    assert final == 16 == r0["state_step"] == r1["state_step"]
    assert r0["commit_worlds"][str(final)] == 2
    # pre-join commits were a world of 1
    assert r0["commit_worlds"]["2"] == 1
    # rank 0's incumbent fit observed the re-admission
    assert any(e["kind"] == "grow" for e in r0["elastic"])
    # both re-sharded to (rank, 2)
    assert [0, 2] in r0["factory_calls"]
    assert r1["factory_calls"] == []    # joiner started sharded already


def test_divergent_anomaly_quorum_evicts_outlier(tmp_path):
    """Divergent-anomaly: rank 1's params are poisoned (numerics.nan
    chaos site, one host only); at the numerics cadence the hard
    anomaly becomes a pod VOTE — the 1-of-2 outlier is evicted (ledger
    `quorum` + `world_changed` entries), rank 0 keeps training
    untouched in a world of 1, and rank 1 leaves WITHOUT committing."""
    root = str(tmp_path / "elastic")
    procs = [_launch("elastic_quorum", i, root) for i in range(2)]
    try:
        r0 = _finish(procs[0], "elastic_quorum", 0)
        r1 = _finish(procs[1], "elastic_quorum", 1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    # rank 1 saw its own eviction and stopped; its state never committed
    assert r1["quorum_evicted"] is True
    assert r1["quorum"] == ["evicted"]
    # rank 0 adopted the eviction, never rolled back, and kept going
    assert r0["quorum"] == ["evict"]
    assert r0["quorum_evicted"] is False
    assert r0["coordination_lost"] is False
    assert r0["members"] == [0]
    assert len(r0["elastic"]) == 1 and r0["elastic"][0]["kind"] == "evict"
    # the shared ledger records the vote and the transition
    q = r0["quorum_entries"]
    assert len(q) == 1 and q[0]["decision"] == "evict"
    assert q[0]["votes"] == {"0": False, "1": True}
    wc = [w for w in r0["world_changes"] if w["change"] == "evict"]
    assert len(wc) == 1 and wc[0]["members"] == [0]
    # the survivor committed steps after the eviction, as a world of 1
    assert r0["committed"], "survivor committed nothing"
    assert [0, 1] in r0["factory_calls"]
