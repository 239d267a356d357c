"""REAL multi-process distributed test: 2 `jax.distributed` CPU processes.

Single-process 8-device simulation (the rest of the suite) cannot
exercise process boundaries: per-process data sharding, global-array
assembly from process-local shards, cross-process collectives, and
multi-process orbax checkpointing only break multi-process (VERDICT r2
weak #4). This spawns the real thing — two coordinated JAX processes
with 4 local devices each — through train-and-save, then restores in a
FRESH 2-process run (the reference validated this path only empirically
on TPU pods, SURVEY §4).

Marked `multiprocess`; CI runs it as its own job.
"""
import json
import os
import socket
import subprocess
import sys

import pytest
from conftest import CHEAP_COMPILE_FLAGS

WORKER = os.path.join(os.path.dirname(__file__), "multiprocess_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


# A phase ends in 40-65 s beside five busy workers; a wedged one may not
# take a third of the suite's limit.
PHASE_TIMEOUT_S = 180


def _run_phase(phase: str, port: int, ckpt_dir: str,
               timeout: int = PHASE_TIMEOUT_S):
    env = os.environ.copy()
    # the worker adds its own device count to the suite's compile flags
    env["XLA_FLAGS"] = CHEAP_COMPILE_FLAGS
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, phase, str(i), str(port), ckpt_dir],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)
        for i in range(2)
    ]
    outs = []
    try:
        for i, p in enumerate(procs):
            out, err = p.communicate(timeout=timeout)
            assert p.returncode == 0, (
                f"{phase} proc {i} rc={p.returncode}\nstdout:{out[-2000:]}\n"
                f"stderr:{err[-2000:]}")
            result = [ln for ln in out.splitlines()
                      if ln.startswith("RESULT ")]
            assert result, f"{phase} proc {i} printed no RESULT line:\n{out}"
            outs.append(json.loads(result[-1][len("RESULT "):]))
    finally:
        # any failure must take the coordinated sibling down with it —
        # an orphaned jax.distributed worker wedges in gloo barriers and
        # outlives the test session
        for q in procs:
            if q.poll() is None:
                q.kill()
    return outs


@pytest.mark.multiprocess
def test_two_process_fsdp_train_save_restore(tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")

    train = _run_phase("train", _free_port(), ckpt_dir)
    # the global step is one SPMD program: both processes must observe
    # bit-identical losses, or global assembly / collectives are broken
    assert train[0]["losses"] == train[1]["losses"]
    assert len(train[0]["losses"]) == 3
    assert all(l > 0 for l in train[0]["losses"])

    restore = _run_phase("restore", _free_port(), ckpt_dir)
    assert restore[0]["losses"] == restore[1]["losses"]
    assert len(restore[0]["losses"]) == 1


@pytest.mark.multiprocess
def test_two_process_coordinated_restart_consensus(tmp_path):
    """The asymmetric-corruption acceptance scenario (ISSUE 2), over
    REAL jax.distributed: steps 2 and 4 two-phase-committed into the
    ledger, step 5 saved but never committed; then (a) one host's
    LOCAL view of step 4 goes bad (chaos site) and (b) one host
    truncates step 4 on disk — in both worlds the processes must agree
    on step 2: never different steps, never the corrupt 4, never the
    uncommitted 5."""
    ckpt_dir = str(tmp_path / "ckpt")

    train = _run_phase("train_coord", _free_port(), ckpt_dir)
    assert train[0]["losses"] == train[1]["losses"]
    assert len(train[0]["losses"]) == 5
    for t in train:
        # the commit round made exactly 2 and 4 restorable; the
        # ledgerless newest write (5) is on disk but uncommitted
        assert t["committed"] == [2, 4]
        assert t["all_steps"] == [2, 4, 5]
        assert t["latest"] == 4

    # (a) asymmetric OBSERVED corruption: process 1's valid set drops
    # step 4; the intersection forces both to the same earlier step
    asym = _run_phase("restore_coord_asym", _free_port(), ckpt_dir)
    assert asym[0]["restored"] == asym[1]["restored"] == 2
    assert asym[0]["losses"] == asym[1]["losses"]
    assert [a["step_after"] for a in asym] == [3, 3]

    # (b) asymmetric ON-DISK corruption, performed by process 1 only:
    # the newest COMMITTED step is truncated; consensus again lands on
    # 2 on BOTH hosts — and never on the intact-but-uncommitted 5
    corrupt = _run_phase("restore_coord_corrupt", _free_port(), ckpt_dir)
    assert corrupt[0]["restored"] == corrupt[1]["restored"]
    assert corrupt[0]["restored"] == 2
    for c in corrupt:
        assert c["restored"] not in (4, 5)
        assert 5 not in c["valid_after"]       # uncommitted: never valid
        assert 4 not in c["valid_after"]       # truncated: never valid
    assert corrupt[0]["losses"] == corrupt[1]["losses"]
