"""Coordinated multi-host restart: step-ledger commits, consensus
restore, and crash barriers.

PR 1's resilience layer recovers each host independently — after
asymmetric checkpoint corruption, `fallback_restore`'s walk-back can
pick DIFFERENT steps on different hosts, a divergent world that wedges
or silently corrupts a pod-scale run. Elastic-recovery systems (Pulse,
arXiv:2606.19163) treat restart as one coordinated, consensus-driven
event; this module provides the three primitives that make restore,
save-commit, and crash handling pod-consistent:

  StepLedger           external record of which checkpoint steps are
                       COMMITTED (every process finished writing) —
                       `ledger.jsonl` in the checkpoint dir, written
                       only by process 0, fsync'd per entry. A step
                       absent from the ledger is never restorable.
  Transport            pluggable world-communication: a real
                       `jax.distributed` coordination-service backend
                       (timeout-capable barriers + key-value store) and
                       an in-memory backend so every consensus path
                       runs single-process on CPU in tier-1 tests.
  RestartCoordinator   the protocol: two-phase checkpoint commit
                       (all-wrote barrier -> ledger entry -> ack
                       barrier), consensus restore (intersect the
                       hosts' locally-valid committed-step sets, take
                       the max, broadcast), and crash barriers (a dead
                       host turns into BarrierTimeout on the survivors
                       within a deadline, never an indefinite hang in
                       collectives).

Elastic re-admission: restore decisions derive only from shared state
(the ledger + the checkpoint dir), never from host identity, so a
replacement host joining the next launch participates in consensus
like any original member; `RestartCoordinator.on_lost` is the hook for
schedulers that want to trigger that relaunch.

Dependency direction: trainer/checkpoints.py imports this module;
this module imports nothing from trainer/.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Dict, Iterable, List, Optional

from .events import EventLog, global_event_log

LEDGER_FILENAME = "ledger.jsonl"

# Commit barriers guard against a host that died mid-save: survivors
# must notice within a bounded wait and take the checkpoint-and-exit
# path instead of hanging. Default sized for object-store flush tails.
DEFAULT_BARRIER_TIMEOUT = 600.0


class CoordinationError(RuntimeError):
    """Base class for coordination failures."""


class BarrierTimeout(CoordinationError):
    """A cross-host barrier (or gather) missed its deadline — some host
    is dead or wedged. The surviving caller should checkpoint locally
    and exit cleanly rather than retry into a hung world."""


class ConsensusError(CoordinationError):
    """Hosts could not agree on a restore step (disjoint valid sets or
    a broadcast/decision mismatch) — restarting blindly would build a
    divergent world, so this raises before any jitted state is used."""


# -- step ledger --------------------------------------------------------------

class StepLedger:
    """Append-only `ledger.jsonl` beside the checkpoints: the external
    source of truth for which steps are COMMITTED (restorable).

    Entry format (one JSON object per line):
        {"kind": "commit", "step": 400, "world": 16, "time": ...}
        {"kind": "invalidate", "step": 400, "reason": "...", "time": ...}
        {"kind": "note", "detail": "...", "time": ...}
        {"kind": "world_changed", "change": "shrink"|"grow"|"evict",
         "epoch": 2, "members": [...], "world": 3, "step": 400, ...}
        {"kind": "quorum", "votes": {...}, "decision": "...", ...}
        {"kind": "data_state", "step": 400, "state": {...}, "time": ...}

    `world_changed` entries are the committed membership history of an
    elastic run (resilience/elastic.py): one entry per transition,
    written by the transition's leader behind the same
    happens-before-the-ack ordering as commits. Readers that only care
    about restorable steps (`committed_steps`) skip them.

    Only process 0 writes (`record_*`); every host reads. Local writes
    are flushed + fsync'd per entry so a committed step survives a host
    crash immediately after the commit barrier; object-store paths
    (`gs://...`) go through epath with per-object atomicity instead.
    Reads tolerate a truncated trailing line (crash mid-append).
    """

    def __init__(self, directory: str):
        self.directory = directory
        self._remote = "://" in directory
        if self._remote:
            self.path = directory.rstrip("/") + "/" + LEDGER_FILENAME
        else:
            self.path = os.path.join(directory, LEDGER_FILENAME)

    def exists(self) -> bool:
        if self._remote:
            from etils import epath
            return epath.Path(self.path).exists()
        return os.path.exists(self.path)

    def _read_text(self) -> str:
        if self._remote:
            from etils import epath
            p = epath.Path(self.path)
            return p.read_text() if p.exists() else ""
        if not os.path.exists(self.path):
            return ""
        with open(self.path, "r", encoding="utf-8") as f:
            return f.read()

    def entries(self) -> List[Dict[str, object]]:
        """All parseable entries; a truncated trailing line (torn write)
        is skipped, not fatal — the entry it would have recorded never
        reached the ack barrier, so dropping it is the safe reading."""
        out: List[Dict[str, object]] = []
        for line in self._read_text().splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                entry = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(entry, dict):
                out.append(entry)
        return out

    def committed_steps(self) -> List[int]:
        """Sorted steps with a commit entry and no later invalidate."""
        live: Dict[int, bool] = {}
        for e in self.entries():
            kind, step = e.get("kind"), e.get("step")
            if not isinstance(step, int):
                continue
            if kind == "commit":
                live[step] = True
            elif kind == "invalidate":
                live[step] = False
        return sorted(s for s, ok in live.items() if ok)

    def is_committed(self, step: int) -> bool:
        return step in self.committed_steps()

    def record_commit(self, step: int, world_size: int,
                      extra: Optional[Dict[str, object]] = None) -> None:
        entry = {"kind": "commit", "step": int(step),
                 "world": int(world_size), "time": time.time()}
        if extra:
            entry.update(extra)
        self._append(entry)

    def record_invalidate(self, step: int, reason: str = "") -> None:
        self._append({"kind": "invalidate", "step": int(step),
                      "reason": reason, "time": time.time()})

    def record_note(self, detail: str) -> None:
        self._append({"kind": "note", "detail": detail, "time": time.time()})

    def record_world_changed(self, change: str, epoch: int,
                             members: List[int],
                             step: Optional[int], reason: str = "",
                             extra: Optional[Dict[str, object]] = None
                             ) -> None:
        """One committed membership transition (elastic layer; written
        only by the transition's leader). `step` is the consensus step
        the new world (re)starts from; None on a cold world."""
        entry: Dict[str, object] = {
            "kind": "world_changed", "change": change, "epoch": int(epoch),
            "members": [int(m) for m in members], "world": len(members),
            "step": (int(step) if step is not None else None),
            "reason": reason, "time": time.time()}
        if extra:
            entry.update(extra)
        self._append(entry)

    def record_quorum(self, votes: Dict[str, bool], decision: str,
                      step: Optional[int] = None, detail: str = "") -> None:
        """One pod anomaly-quorum round's verdict (elastic layer,
        leader-written): who voted anomalous and what the pod decided
        (`rollback_all` / `evict` / `none`)."""
        self._append({"kind": "quorum",
                      "votes": {str(k): bool(v) for k, v in votes.items()},
                      "decision": decision,
                      "step": (int(step) if step is not None else None),
                      "detail": detail, "time": time.time()})

    def record_data_state(self, step: int,
                          state: Dict[str, object]) -> None:
        """Data-plane iterator state committed beside the model
        checkpoint (ISSUE 17): stream cursor/seed, quarantine journal,
        breaker board. Written at the same commit boundary as the
        `commit` entry, so a restart that restores step S also rewinds
        the batch stream to S's exact boundary."""
        self._append({"kind": "data_state", "step": int(step),
                      "state": state, "time": time.time()})

    def data_state_at(self, step: int) -> Optional[Dict[str, object]]:
        """Newest data_state entry with entry.step <= step (a rollback
        target never needs FUTURE iterator state), or None."""
        best: Optional[Dict[str, object]] = None
        best_step = -1
        for e in self.entries():
            if e.get("kind") != "data_state":
                continue
            s = e.get("step")
            if isinstance(s, int) and best_step < s <= int(step):
                best, best_step = e.get("state"), s
        return best

    def world_changes(self) -> List[Dict[str, object]]:
        """All `world_changed` entries in append order — the world-size
        timeline diagnose_run/verify_checkpoint render."""
        return [e for e in self.entries() if e.get("kind") == "world_changed"]

    def quorum_decisions(self) -> List[Dict[str, object]]:
        return [e for e in self.entries() if e.get("kind") == "quorum"]

    def _append(self, entry: Dict[str, object]) -> None:
        line = json.dumps(entry)
        if self._remote:
            # object stores have no append; read-modify-write the whole
            # object (single writer: process 0 only, so no lost updates)
            from etils import epath
            p = epath.Path(self.path)
            p.write_text(self._read_text() + line + "\n")
            return
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        with open(self.path, "a", encoding="utf-8") as f:
            f.write(line + "\n")
            f.flush()
            os.fsync(f.fileno())


# -- transports ---------------------------------------------------------------

class Transport:
    """World communication used by the coordinator. Implementations
    provide a timeout-capable barrier plus small-JSON gather/broadcast;
    every operation either completes on ALL members or raises
    BarrierTimeout on the survivors within the deadline."""

    process_index: int = 0
    process_count: int = 1

    def barrier(self, name: str, timeout: float) -> None:
        raise NotImplementedError

    def allgather_json(self, name: str, obj, timeout: float) -> List:
        raise NotImplementedError

    def broadcast_json(self, name: str, obj, timeout: float):
        """Process 0's `obj` to everyone (non-0 callers' obj is ignored)."""
        raise NotImplementedError

    def offer_json(self, name: str, obj) -> None:
        """Non-blocking, best-effort contribution of this host's payload
        under a gather's key — the write half of `allgather_json` without
        the wait. Used to publish tombstones (e.g. "aggregation
        disabled") that unblock peers still gathering; overwrites any
        earlier contribution to the same round."""
        raise NotImplementedError

    # -- point reads/writes (the elastic layer's primitives) ----------------
    # Membership rounds cannot use barrier/allgather: those complete only
    # when EVERY world member participates, and the whole point of a
    # membership round is that some member is dead. The elastic layer
    # instead composes these three: publish a contribution, read one
    # specific member's contribution with a bounded wait (a dead member
    # is a None, not a hang), and read/write shared decision keys.

    def poll_json(self, name: str, rank: int, timeout: float = 0.0):
        """Read `rank`'s `offer_json`/`allgather_json` contribution to
        gather `name`, waiting up to `timeout`; None when that member
        never produced it (dead/parked member — NOT an error)."""
        raise NotImplementedError

    def put_json(self, name: str, obj) -> None:
        """Direct KV write at `name` (overwrites). Unlike offer_json the
        key carries NO rank suffix — any member (or a parked joiner)
        can read it back via get_json without knowing the writer."""
        raise NotImplementedError

    def get_json(self, name: str, timeout: float = 0.0):
        """Read a `put_json` key, waiting up to `timeout`; None when
        absent within the deadline."""
        raise NotImplementedError


class _InMemoryWorld:
    """Shared state behind a set of InMemoryTransports (one per
    simulated host, usually one thread each)."""

    def __init__(self, n: int):
        self.n = n
        self._cond = threading.Condition()
        self._store: Dict[str, object] = {}
        self._arrived: Dict[str, set] = {}
        self._released: set = set()

    def barrier(self, name: str, rank: int, timeout: float) -> None:
        with self._cond:
            self._arrived.setdefault(name, set()).add(rank)
            if len(self._arrived[name]) >= self.n:
                self._released.add(name)
                self._cond.notify_all()
            elif not self._cond.wait_for(
                    lambda: name in self._released, timeout):
                raise BarrierTimeout(
                    f"barrier {name!r}: {len(self._arrived[name])}/{self.n} "
                    f"arrived within {timeout}s")

    def put(self, key: str, value) -> None:
        with self._cond:
            self._store[key] = value
            self._cond.notify_all()

    def get(self, key: str, timeout: float):
        with self._cond:
            if not self._cond.wait_for(lambda: key in self._store, timeout):
                raise BarrierTimeout(
                    f"key {key!r} not produced within {timeout}s")
            return self._store[key]

    def try_get(self, key: str, timeout: float):
        """`get` that returns None instead of raising on a missing key —
        the membership-round read (a dead member is an answer)."""
        with self._cond:
            if self._cond.wait_for(lambda: key in self._store,
                                   max(timeout, 0.0)):
                return self._store[key]
            return None


class InMemoryTransport(Transport):
    """Single-process transport: a world of N members sharing one
    `_InMemoryWorld` (threads in tests; N=1 for plain single-host runs).
    Exercises the exact coordinator protocol on CPU without
    `jax.distributed`."""

    def __init__(self, world: _InMemoryWorld, rank: int):
        self._world = world
        self.process_index = rank
        self.process_count = world.n

    @classmethod
    def make_world(cls, n: int) -> List["InMemoryTransport"]:
        world = _InMemoryWorld(n)
        return [cls(world, i) for i in range(n)]

    def barrier(self, name: str, timeout: float) -> None:
        self._world.barrier(name, self.process_index, timeout)

    def allgather_json(self, name: str, obj, timeout: float) -> List:
        # json round-trip deliberately mirrors the distributed backend:
        # payloads must be serializable there too
        self._world.put(f"ag/{name}/{self.process_index}", json.dumps(obj))
        deadline = time.monotonic() + timeout
        out = []
        for j in range(self.process_count):
            remaining = max(deadline - time.monotonic(), 0.001)
            out.append(json.loads(self._world.get(f"ag/{name}/{j}",
                                                  remaining)))
        return out

    def broadcast_json(self, name: str, obj, timeout: float):
        if self.process_index == 0:
            self._world.put(f"bc/{name}", json.dumps(obj))
            return obj
        return json.loads(self._world.get(f"bc/{name}", timeout))

    def offer_json(self, name: str, obj) -> None:
        self._world.put(f"ag/{name}/{self.process_index}", json.dumps(obj))

    def poll_json(self, name: str, rank: int, timeout: float = 0.0):
        raw = self._world.try_get(f"ag/{name}/{rank}", timeout)
        return None if raw is None else json.loads(raw)

    def put_json(self, name: str, obj) -> None:
        self._world.put(f"kv/{name}", json.dumps(obj))

    def get_json(self, name: str, timeout: float = 0.0):
        raw = self._world.try_get(f"kv/{name}", timeout)
        return None if raw is None else json.loads(raw)


def _is_deadline_error(e: Exception) -> bool:
    text = str(e)
    return ("DEADLINE_EXCEEDED" in text or "deadline" in text.lower()
            or isinstance(e, TimeoutError))


class JaxDistributedTransport(Transport):
    """Real multi-host backend over the `jax.distributed` coordination
    service: `wait_at_barrier` gives barriers with genuine deadlines
    (unlike device collectives, which hang forever when a participant
    is gone), and the distributed KV store carries the small JSON
    payloads (step sets, decisions)."""

    def __init__(self, namespace: str = "flaxdiff.coord"):
        import jax
        from jax._src import distributed
        client = getattr(distributed.global_state, "client", None)
        if client is None:
            raise CoordinationError(
                "jax.distributed is not initialized — call "
                "jax.distributed.initialize() before building a "
                "JaxDistributedTransport (single-host runs should use "
                "InMemoryTransport.make_world(1)[0] instead)")
        self._client = client
        self._ns = namespace
        self.process_index = jax.process_index()
        self.process_count = jax.process_count()

    def barrier(self, name: str, timeout: float) -> None:
        try:
            self._client.wait_at_barrier(f"{self._ns}/{name}",
                                         int(timeout * 1000))
        except Exception as e:  # noqa: BLE001 — backend raises
            # XlaRuntimeError; only the deadline case is a crash signal
            if _is_deadline_error(e):
                raise BarrierTimeout(
                    f"barrier {name!r} timed out after {timeout}s: "
                    f"{e}") from e
            raise

    def allgather_json(self, name: str, obj, timeout: float) -> List:
        key = f"{self._ns}/ag/{name}"
        self._client.key_value_set(f"{key}/{self.process_index}",
                                   json.dumps(obj))
        deadline = time.monotonic() + timeout
        out = []
        for j in range(self.process_count):
            remaining_ms = max(int((deadline - time.monotonic()) * 1000), 1)
            try:
                out.append(json.loads(
                    self._client.blocking_key_value_get(f"{key}/{j}",
                                                        remaining_ms)))
            except Exception as e:  # noqa: BLE001
                if _is_deadline_error(e):
                    raise BarrierTimeout(
                        f"allgather {name!r}: process {j} did not "
                        f"contribute within {timeout}s: {e}") from e
                raise
        return out

    def broadcast_json(self, name: str, obj, timeout: float):
        key = f"{self._ns}/bc/{name}"
        if self.process_index == 0:
            self._client.key_value_set(key, json.dumps(obj))
            return obj
        try:
            return json.loads(
                self._client.blocking_key_value_get(key,
                                                    int(timeout * 1000)))
        except Exception as e:  # noqa: BLE001
            if _is_deadline_error(e):
                raise BarrierTimeout(
                    f"broadcast {name!r}: no value from process 0 "
                    f"within {timeout}s: {e}") from e
            raise

    def offer_json(self, name: str, obj) -> None:
        key = f"{self._ns}/ag/{name}/{self.process_index}"
        payload = json.dumps(obj)
        self._client.key_value_set(key, payload, allow_overwrite=True)

    def _try_get(self, key: str, timeout: float):
        try:
            return self._client.blocking_key_value_get(
                key, max(int(timeout * 1000), 1))
        except Exception as e:  # noqa: BLE001 — backend raises
            # XlaRuntimeError; the deadline case is the "absent" answer
            if _is_deadline_error(e):
                return None
            raise

    def poll_json(self, name: str, rank: int, timeout: float = 0.0):
        raw = self._try_get(f"{self._ns}/ag/{name}/{rank}", timeout)
        return None if raw is None else json.loads(raw)

    def put_json(self, name: str, obj) -> None:
        key = f"{self._ns}/kv/{name}"
        payload = json.dumps(obj)
        self._client.key_value_set(key, payload, allow_overwrite=True)

    def get_json(self, name: str, timeout: float = 0.0):
        raw = self._try_get(f"{self._ns}/kv/{name}", timeout)
        return None if raw is None else json.loads(raw)


class FileTransport(Transport):
    """Transport over a shared directory: barriers are arrival files,
    the KV store is atomic JSON files (tmp + rename).

    Two properties the elastic chaos suite needs that neither in-memory
    threads nor `jax.distributed` give on CPU: (1) the world SURVIVES a
    member's death — a killed process simply never produces its keys,
    so survivors see bounded Nones instead of a torn coordination
    service; (2) a process launched LATE (a replacement host) can mount
    the same directory and park, with no world-size handshake at init
    time. `jax.distributed` offers neither on CPU: its coordinator dies
    with process 0 and its world is fixed at initialize().

    Not a performance path — polls at `poll_interval` — but the
    protocol (and its timeout semantics) is identical to the other
    backends, so everything proven over it holds over the KV service.
    """

    def __init__(self, directory: str, rank: int, world: int,
                 poll_interval: float = 0.02):
        self.directory = directory
        self.process_index = int(rank)
        self.process_count = int(world)
        self._poll = poll_interval
        os.makedirs(directory, exist_ok=True)

    # keys become relative file paths; "/" is the hierarchy separator
    def _path(self, key: str) -> str:
        safe = "/".join(part.replace("..", "_") or "_"
                        for part in key.split("/"))
        return os.path.join(self.directory, safe)

    def _write(self, key: str, text: str) -> None:
        path = self._path(key)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{self.process_index}"
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(text)
        os.replace(tmp, path)       # atomic: readers never see a torn file

    def _read(self, key: str, timeout: float) -> Optional[str]:
        path = self._path(key)
        deadline = time.monotonic() + max(timeout, 0.0)
        while True:
            try:
                with open(path, "r", encoding="utf-8") as f:
                    return f.read()
            except OSError:
                pass
            if time.monotonic() >= deadline:
                return None
            time.sleep(self._poll)

    def barrier(self, name: str, timeout: float) -> None:
        self._write(f"bar/{name}/{self.process_index}", "1")
        deadline = time.monotonic() + timeout
        for j in range(self.process_count):
            remaining = max(deadline - time.monotonic(), 0.0)
            if self._read(f"bar/{name}/{j}", remaining) is None:
                raise BarrierTimeout(
                    f"barrier {name!r}: process {j} absent after "
                    f"{timeout}s")

    def allgather_json(self, name: str, obj, timeout: float) -> List:
        self.offer_json(name, obj)
        deadline = time.monotonic() + timeout
        out = []
        for j in range(self.process_count):
            remaining = max(deadline - time.monotonic(), 0.0)
            raw = self._read(f"ag/{name}/{j}", remaining)
            if raw is None:
                raise BarrierTimeout(
                    f"allgather {name!r}: process {j} did not "
                    f"contribute within {timeout}s")
            out.append(json.loads(raw))
        return out

    def broadcast_json(self, name: str, obj, timeout: float):
        if self.process_index == 0:
            self._write(f"bc/{name}", json.dumps(obj))
            return obj
        raw = self._read(f"bc/{name}", timeout)
        if raw is None:
            raise BarrierTimeout(
                f"broadcast {name!r}: no value from process 0 within "
                f"{timeout}s")
        return json.loads(raw)

    def offer_json(self, name: str, obj) -> None:
        self._write(f"ag/{name}/{self.process_index}", json.dumps(obj))

    def poll_json(self, name: str, rank: int, timeout: float = 0.0):
        raw = self._read(f"ag/{name}/{rank}", timeout)
        return None if raw is None else json.loads(raw)

    def put_json(self, name: str, obj) -> None:
        self._write(f"kv/{name}", json.dumps(obj))

    def get_json(self, name: str, timeout: float = 0.0):
        raw = self._read(f"kv/{name}", timeout)
        return None if raw is None else json.loads(raw)


def default_transport() -> Transport:
    """The right transport for this process: the jax.distributed backend
    when a multi-process world is initialized, else a world-of-one
    in-memory transport (coordination degenerates to local decisions
    but runs the same code paths)."""
    import jax
    if jax.process_count() > 1:
        return JaxDistributedTransport()
    return InMemoryTransport.make_world(1)[0]


def agree_epoch(transport: Transport, local_epoch: int,
                timeout: float = DEFAULT_BARRIER_TIMEOUT,
                event_log: Optional[EventLog] = None) -> int:
    """The pod-wide job-incarnation number: process 0's `local_epoch`,
    broadcast to everyone. Epoch tags only protect a round when every
    host tags with the SAME value, but the natural local source (the
    goodput ledger's incarnation) is written by process 0 only — with a
    host-local telemetry dir, or after a torn read on one host, local
    incarnations diverge and every tagged round would abort forever.
    Call this once at startup and hand the result to RestartCoordinator.

    A host whose local value differs records an `epoch_adopted` event
    (diagnosable skew, not an error: rank 0 is authoritative)."""
    agreed = int(transport.broadcast_json("epoch.agree", int(local_epoch),
                                          timeout))
    if agreed != int(local_epoch):
        log_ = event_log if event_log is not None else global_event_log()
        log_.record("epoch_adopted", "coord.epoch",
                    detail=f"local incarnation {int(local_epoch)} -> "
                           f"agreed epoch {agreed} (process 0's goodput "
                           f"account is authoritative)")
    return agreed


# -- the protocol -------------------------------------------------------------

class RestartCoordinator:
    """Pod-consistent commit / restore / crash handling over a Transport.

    Commit (two-phase): every host votes with the step it finished
    writing (phase 1, a timed allgather = the "all wrote" barrier);
    only a unanimous vote makes process 0 append the ledger entry
    (phase 2), and an ack barrier orders the fsync'd entry before any
    host proceeds. A host whose save failed votes None and the round
    aborts — a step some host never wrote must not become restorable.

    Restore (consensus): hosts exchange their locally-valid committed
    step sets; the agreed step is the max of the intersection, computed
    identically everywhere and cross-checked against process 0's
    broadcast decision. Disjoint non-empty sets raise ConsensusError —
    restoring anyway would build a divergent world.

    Crash barriers: every wait carries `barrier_timeout`; a missed
    deadline records a `barrier_timeout` event, marks the coordinator
    `lost`, and invokes `on_lost` (elastic-re-admission hook — e.g.
    request a relaunch with a replacement host). Once lost, further
    commits are skipped locally (`commit_skipped` events) so the
    checkpoint-and-exit path never re-enters a hung world.

    Epoch tags: every vote/set/decision payload carries the
    coordinator's `epoch` (the job-incarnation number — e.g. the
    telemetry GoodputLedger's incarnation, or a scheduler restart
    count). A payload from a different epoch — a late voter from a
    previous incarnation whose stale KV value survived into this
    round's key — ABORTS a commit round (no ledger entry) and raises
    ConsensusError on restore, instead of silently counting a dead
    process's opinion (docs/RESILIENCE.md "Open items", resolved).
    """

    def __init__(self, transport: Transport,
                 barrier_timeout: float = DEFAULT_BARRIER_TIMEOUT,
                 event_log: Optional[EventLog] = None,
                 on_lost: Optional[Callable[[str], None]] = None,
                 epoch: int = 0):
        self.transport = transport
        self.barrier_timeout = barrier_timeout
        self.on_lost = on_lost
        self.lost = False
        self.epoch = int(epoch)
        self._event_log = event_log
        self._seq = 0

    @property
    def _events(self) -> EventLog:
        return (self._event_log if self._event_log is not None
                else global_event_log())

    @property
    def is_coordinator(self) -> bool:
        return self.transport.process_index == 0

    def _next_seq(self) -> int:
        seq, self._seq = self._seq, self._seq + 1
        return seq

    def _mark_lost(self, what: str, err: Exception) -> None:
        self.lost = True
        self._events.record("barrier_timeout", "coord.barrier",
                            detail=f"{what}: {err}")
        if self.on_lost is not None:
            try:
                self.on_lost(what)
            except Exception:  # noqa: BLE001 — the hook must not mask
                from .events import log
                log.exception("on_lost hook failed")

    def barrier(self, name: str,
                timeout: Optional[float] = None) -> None:
        """A named crash barrier: completes everywhere or raises
        BarrierTimeout (marking the coordinator lost) on survivors."""
        try:
            self.transport.barrier(name, timeout if timeout is not None
                                   else self.barrier_timeout)
        except BarrierTimeout as e:
            self._mark_lost(f"barrier {name!r}", e)
            raise

    def rebirth(self, epoch: Optional[int] = None) -> None:
        """Re-arm a coordinator after an elastic world transition: clear
        `lost`, restart the round sequence at 0 (every surviving member
        resets identically, and a re-admitted joiner starts at 0 — the
        transition is the new time zero), and optionally adopt a new
        epoch. Only the elastic layer calls this: without a committed
        membership change, un-losing a coordinator would re-enter the
        hung world the crash barrier just escaped."""
        self.lost = False
        self._seq = 0
        if epoch is not None:
            self.epoch = int(epoch)

    # -- epoch/step-tagged payloads ------------------------------------------
    def _tag(self, value, step: Optional[int] = None) -> Dict[str, object]:
        tagged: Dict[str, object] = {"epoch": self.epoch, "value": value}
        if step is not None:
            tagged["step"] = int(step)
        return tagged

    def _untag(self, payloads: List, step: Optional[int] = None):
        """(values, why) from a gathered list of tagged payloads.
        `values` is None when ANY payload must invalidate the round:
        `why="epoch"` — a foreign/absent epoch tag (late voter from a
        previous incarnation); `why="step"` — same epoch but a foreign
        step tag: two drivers of the SAME incarnation drifted apart
        (e.g. by a save interval after an asymmetric restore), which
        must read as a stale-driver rejection, not an opaque
        non-unanimous vote (docs/RESILIENCE.md "Open items")."""
        values = []
        for p in payloads:
            if not isinstance(p, dict) or p.get("epoch") != self.epoch:
                return None, "epoch"
            if step is not None and p.get("step") is not None \
                    and int(p["step"]) != int(step):
                return None, "step"
            values.append(p.get("value"))
        return values, ""

    # -- two-phase commit ----------------------------------------------------
    def commit(self, step: Optional[int], ledger: StepLedger,
               meta: Optional[Dict[str, object]] = None) -> Optional[int]:
        """Commit `step` (the step this host finished writing; None if
        its save failed/was skipped). Returns the committed step, or
        None when the round aborted or there was nothing to commit."""
        if self.lost:
            self._events.record(
                "commit_skipped", "ckpt.commit",
                detail="coordination lost (earlier barrier timeout); "
                       "local save remains uncommitted", step=step)
            return None
        seq = self._next_seq()
        try:
            raw = self.transport.allgather_json(
                f"commit.{seq}", self._tag(step, step=step),
                self.barrier_timeout)
        except BarrierTimeout as e:
            self._mark_lost(f"commit vote for step {step}", e)
            raise
        votes, why = self._untag(raw, step=step)
        if votes is None:
            if why == "step":
                # same incarnation, different training step: a drifted
                # sibling driver (asymmetric restore / replayed rank) —
                # a distinct, diagnosable rejection rather than the
                # opaque non-unanimous abort it used to surface as
                self._events.record(
                    "commit_stale", "ckpt.commit",
                    detail=f"step drift in commit votes (this driver at "
                           f"step {step}, gathered {raw}) — a sibling "
                           f"driver of the same incarnation has drifted "
                           f"by at least a save interval; step stays "
                           f"uncommitted", step=step)
                return None
            self._events.record(
                "commit_aborted", "ckpt.commit",
                detail=f"epoch mismatch in commit votes (this epoch "
                       f"{self.epoch}, gathered {raw}) — stale voter "
                       f"from a previous incarnation; step stays "
                       f"uncommitted", step=step)
            return None
        if all(v is None for v in votes):
            return None                       # nothing to commit anywhere
        if any(v != step for v in votes):
            # some host failed its save (None) or wrote a different
            # step: the step is not globally durable — abort, no entry
            self._events.record(
                "commit_aborted", "ckpt.commit",
                detail=f"non-unanimous votes {votes}; step stays "
                       f"uncommitted", step=step)
            return None
        if self.is_coordinator:
            ledger.record_commit(step, self.transport.process_count,
                                 extra=meta)
        try:
            # ack barrier: the fsync'd ledger entry happens-before any
            # host treats the step as restorable
            self.transport.barrier(f"commit.{seq}.ack",
                                   self.barrier_timeout)
        except BarrierTimeout as e:
            self._mark_lost(f"commit ack for step {step}", e)
            raise
        self._events.record("commit", "ckpt.commit",
                            detail=f"step {step} committed by "
                                   f"{len(votes)} process(es)", step=step)
        return step

    # -- consensus restore ---------------------------------------------------
    def consensus_restore_step(
            self, local_valid_steps: Iterable[int]) -> Optional[int]:
        """Agree on the one step every host restores: max of the
        intersection of the hosts' locally-valid committed-step sets.
        Returns None iff NO host has any valid step (cold start);
        raises ConsensusError when hosts hold steps but share none."""
        if self.lost:
            raise CoordinationError(
                "cannot run consensus restore: coordination lost")
        local = sorted(set(int(s) for s in local_valid_steps))
        seq = self._next_seq()
        try:
            raw = self.transport.allgather_json(
                f"restore.{seq}", self._tag(local), self.barrier_timeout)
        except BarrierTimeout as e:
            self._mark_lost("consensus restore gather", e)
            raise
        sets, _ = self._untag(raw)
        if sets is None:
            raise ConsensusError(
                f"consensus restore saw a payload from another epoch "
                f"(this epoch {self.epoch}, gathered {raw}) — a stale "
                f"contribution from a previous incarnation cannot be "
                f"allowed to pick the restore step")
        common = set(sets[0]).intersection(*map(set, sets[1:])) \
            if sets else set()
        chosen = max(common) if common else None
        # process 0 broadcasts its decision; everyone computed the same
        # thing from the same gathered sets, so a mismatch means broken
        # transport or torn ledger views — fail before touching state
        try:
            raw_decision = self.transport.broadcast_json(
                f"restore.{seq}.decision", self._tag(chosen),
                self.barrier_timeout)
        except BarrierTimeout as e:
            self._mark_lost("consensus restore decision", e)
            raise
        decision, _ = self._untag([raw_decision])
        if decision is None:
            raise ConsensusError(
                f"restore decision carries a foreign epoch (this epoch "
                f"{self.epoch}, got {raw_decision}) — refusing a stale "
                f"coordinator's step")
        decided = decision[0]
        if decided != chosen:
            raise ConsensusError(
                f"restore decision diverged: coordinator chose {decided}, "
                f"this host computed {chosen} (local set {local}, "
                f"gathered {sets})")
        if decided is None and any(sets):
            raise ConsensusError(
                f"hosts hold checkpoints but share no committed step "
                f"(gathered sets {sets}); refusing to restore a "
                f"divergent world")
        if decided is not None:
            self._events.record(
                "consensus_restore", "ckpt.restore",
                detail=f"world of {len(sets)} agreed on step {decided} "
                       f"(set sizes {[len(s) for s in sets]})",
                step=decided)
        return decided
