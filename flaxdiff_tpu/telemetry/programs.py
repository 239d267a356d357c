"""Program evidence registry: per-compiled-program performance
provenance (docs/OBSERVABILITY.md "Program evidence registry").

Every compiled hot program — the train step, its monitored twin, every
serving chunk and hand-off program, solo sampler scans — registers ONE
record in `programs.jsonl` at trace/compile time:

    kind            train_step | chunk | chunk_cached | chunk_spatial |
                    handoff | solo | ...
    key             the program-cache key the owner compiled it under
                    (stringified; stable across runs of the same config)
    compile_ms      wall of the compiling call (first-call timing: on a
                    cold program this is trace+compile dominated; the
                    serving engine measures it around the miss call the
                    same way it attributes `SampleResult.compile_ms`)
    flops_jaxpr     analytic matmul+conv FLOPs at true shapes
                    (`profiling.jaxpr_flops` walk — the model-FLOPs MFU
                    numerator; None when tracing fails)
    flops_cost /    XLA `cost_analysis()` flops / bytes accessed where
    bytes_cost      the backend provides them (padding + remat included
                    — the hardware-FLOPs numerator); None elsewhere
    hbm_peak_bytes  allocator peak at registration
                    (`telemetry/memory.py`; None off-TPU)
    collectives /   static comm model of the traced program
    comm_bytes_by_axis
                    (`analysis/shard_rules.collective_summary`: every
                    psum/all_gather/reduce_scatter/ppermute/all_to_all
                    in the jaxpr nest, scan-multiplied, with per-mesh-
                    axis byte estimates) — gives the planner (ROADMAP 3)
                    and `scripts/compare_runs.py` a comm/compute ratio
                    per program; None / {} when the trace has no
                    collectives or the probe failed
    fingerprint     hardware/platform fingerprint (below)

This turns the single global `mfu_device` gauge into per-program
roofline attribution, and gives the flash autotuner / auto-parallelism
planner a persisted measured substrate: `scripts/compare_runs.py` diffs
two registries program-by-program, and `scripts/diagnose_run.py`
renders the registry as a "Programs" section.

Byte-stability contract: rows are serialized with sorted keys, fixed
separators, and rounded floats (`stable_json`), so a registry written
twice from the same inputs is byte-identical (tested in
tests/test_tools.py) — diffs show evidence changes, never encoding
noise.

Cost: registration happens only when a program MISSES its cache (it
just paid seconds of XLA compile; the extra `make_jaxpr` trace is tens
of ms) and only under a hub that carries a registry (`Telemetry.create`
— the disabled default hub has none, so the serving hot path and the
lint tracer see zero change). `cost_analysis` needs an AOT
lower+compile pass; pass `deep=False` to skip it where that second
compile is unwanted.
"""
from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List, Optional, Tuple

PROGRAMS_FILENAME = "programs.jsonl"


def hardware_fingerprint() -> Dict[str, Any]:
    """Platform identity for evidence comparability: two runs whose
    fingerprints differ are different experiments, not a regression
    (`scripts/compare_runs.py` enforces this). Lazy jax import: a
    process with no backend still gets a fingerprint ("unknown")."""
    out: Dict[str, Any] = {}
    try:
        import jax
        devs = jax.devices()
        out["platform"] = devs[0].platform
        out["device_kind"] = str(getattr(devs[0], "device_kind", ""))
        out["device_count"] = len(devs)
        out["jax"] = jax.__version__
    except Exception as e:  # noqa: BLE001 — no backend is a valid state
        out["platform"] = "unknown"
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def _round_floats(v, ndigits: int = 3):
    if isinstance(v, float):
        return round(v, ndigits)
    if isinstance(v, dict):
        return {k: _round_floats(x, ndigits) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_round_floats(x, ndigits) for x in v]
    return v


def stable_json(row: Dict[str, Any]) -> str:
    """Deterministic one-line encoding: sorted keys, fixed separators,
    floats rounded to 3 digits — the registry's byte-stable contract."""
    return json.dumps(_round_floats(row), sort_keys=True,
                      separators=(",", ":"))


def read_registry(path: str) -> List[Dict[str, Any]]:
    """Rows of a `programs.jsonl` file (torn tail tolerated).

    `program_update` rows — the append-only write-back channel
    `ProgramRegistry.annotate` uses for measured devprof fields — are
    MERGED into their `program` row (matched on kind+key) instead of
    returned, so readers see one row per program with measured fields
    in place and the file itself stays append-only/byte-stable. An
    orphan update (its program row lost to a torn tail) is dropped."""
    rows: List[Dict[str, Any]] = []
    index: Dict[Tuple[str, str], Dict[str, Any]] = {}
    if not os.path.exists(path):
        return rows
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue        # torn tail from a crash
            if not isinstance(rec, dict):
                continue
            if rec.get("type") == "program_update":
                tgt = index.get((rec.get("kind"), rec.get("key")))
                if tgt is not None:
                    tgt.update({k: v for k, v in rec.items()
                                if k not in ("type", "kind", "key")})
                continue
            if rec.get("type") == "program":
                index[(rec.get("kind"), rec.get("key"))] = rec
            rows.append(rec)
    return rows


class ProgramRegistry:
    """Append-only evidence registry; dedupes on (kind, key) — the
    first registration (the one that measured the compile) wins, later
    identical programs are cache hits with nothing new to say."""

    def __init__(self, path: Optional[str] = None, registry=None,
                 deep: bool = True):
        self.path = path
        self._metrics = registry      # MetricsRegistry for the counter
        self.deep = deep
        self._rows: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._lock = threading.Lock()
        self._fingerprint: Optional[Dict[str, Any]] = None

    # -- core ---------------------------------------------------------------
    def fingerprint(self) -> Dict[str, Any]:
        if self._fingerprint is None:
            self._fingerprint = hardware_fingerprint()
        return self._fingerprint

    def record(self, kind: str, key: Any, *,
               compile_ms: Optional[float] = None,
               flops_jaxpr: Optional[float] = None,
               flops_cost: Optional[float] = None,
               bytes_cost: Optional[float] = None,
               hbm_peak_bytes: Optional[float] = None,
               collectives: Optional[int] = None,
               comm_bytes_by_axis: Optional[Dict[str, int]] = None,
               extra: Optional[Dict[str, Any]] = None
               ) -> Optional[Dict[str, Any]]:
        """Register one program; returns the row, or None when (kind,
        key) was already registered."""
        row: Dict[str, Any] = {
            "type": "program", "kind": str(kind), "key": str(key),
            "compile_ms": (float(compile_ms)
                           if compile_ms is not None else None),
            "flops_jaxpr": (float(flops_jaxpr)
                            if flops_jaxpr is not None else None),
            "flops_cost": (float(flops_cost)
                           if flops_cost is not None else None),
            "bytes_cost": (float(bytes_cost)
                           if bytes_cost is not None else None),
            "hbm_peak_bytes": (float(hbm_peak_bytes)
                               if hbm_peak_bytes is not None else None),
            "collectives": (int(collectives)
                            if collectives is not None else None),
            "comm_bytes_by_axis": {
                str(k): int(v)
                for k, v in sorted((comm_bytes_by_axis or {}).items())},
            "fingerprint": self.fingerprint(),
        }
        if extra:
            row.update(extra)
        ident = (row["kind"], row["key"])
        with self._lock:
            if ident in self._rows:
                return None
            self._rows[ident] = row
            if self.path:
                os.makedirs(os.path.dirname(os.path.abspath(self.path))
                            or ".", exist_ok=True)
                with open(self.path, "a", encoding="utf-8") as f:
                    f.write(stable_json(row) + "\n")
        if self._metrics is not None:
            self._metrics.counter("telemetry/programs_registered").inc()
        return row

    def annotate(self, kind: str, key: Any,
                 fields: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """Write measured fields back onto an already-registered
        program (the devprof reconciliation channel): the in-memory
        row is updated and an append-only `program_update` row lands
        in the file — the base row's bytes never change, and
        `read_registry` merges the update on read. Returns the merged
        row, or None when (kind, key) was never registered (nothing to
        annotate — the measured window had no registered program)."""
        ident = (str(kind), str(key))
        clean = {k: v for k, v in fields.items()
                 if k not in ("type", "kind", "key")}
        with self._lock:
            row = self._rows.get(ident)
            if row is None:
                return None
            row.update(clean)
            if self.path:
                rec: Dict[str, Any] = {"type": "program_update",
                                       "kind": ident[0], "key": ident[1]}
                rec.update(clean)
                with open(self.path, "a", encoding="utf-8") as f:
                    f.write(stable_json(rec) + "\n")
        return row

    def record_jitted(self, kind: str, key: Any, jitted, args: tuple,
                      compile_ms: Optional[float] = None,
                      extra: Optional[Dict[str, Any]] = None
                      ) -> Optional[Dict[str, Any]]:
        """Register a jitted program WITH measured evidence: analytic
        jaxpr FLOPs (abstract trace, no device work), backend
        `cost_analysis` flops/bytes when `deep` (an AOT lower+compile
        pass — XLA's compile cache usually absorbs it right after the
        jit compile), and the allocator's HBM peak. Every probe is
        individually fallible; a probe failure degrades that field to
        None, never the registration."""
        with self._lock:
            if (str(kind), str(key)) in self._rows:
                return None
        flops_jaxpr = flops_cost = bytes_cost = None
        collectives: Optional[int] = None
        comm_by_axis: Optional[Dict[str, int]] = None
        try:
            import jax

            from ..profiling import jaxpr_flops
            closed = jax.make_jaxpr(jitted)(*args)
            flops_jaxpr = jaxpr_flops(closed.jaxpr)
        except Exception as e:  # noqa: BLE001 — evidence is best-effort
            closed = None
            flops_jaxpr = None
            _note_probe_failure("jaxpr", kind, e)
        if closed is not None:
            try:
                from ..analysis.shard_rules import collective_summary
                comm = collective_summary(closed)
                collectives = int(comm["collectives"])
                comm_by_axis = dict(comm["comm_bytes_by_axis"])
            except Exception as e:  # noqa: BLE001 — static model only
                _note_probe_failure("collectives", kind, e)
        if self.deep:
            try:
                cost = jitted.lower(*args).compile().cost_analysis() or {}
                f = cost.get("flops")
                b = cost.get("bytes accessed")
                flops_cost = float(f) if f and f > 0 else None
                bytes_cost = float(b) if b and b > 0 else None
            except Exception as e:  # noqa: BLE001 — backend-dependent
                _note_probe_failure("cost_analysis", kind, e)
        hbm = None
        try:
            from .memory import MemoryMonitor
            stats = MemoryMonitor().sample()
            hbm = stats.get("memory/peak_bytes_in_use")
        except Exception as e:  # noqa: BLE001 — allocator stats optional
            _note_probe_failure("memory", kind, e)
        return self.record(kind, key, compile_ms=compile_ms,
                           flops_jaxpr=flops_jaxpr,
                           flops_cost=flops_cost, bytes_cost=bytes_cost,
                           hbm_peak_bytes=hbm, collectives=collectives,
                           comm_bytes_by_axis=comm_by_axis, extra=extra)

    # -- views --------------------------------------------------------------
    def rows(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._rows.values())

    def keys(self) -> List[Tuple[str, str]]:
        with self._lock:
            return sorted(self._rows)

    def __len__(self) -> int:
        with self._lock:
            return len(self._rows)


def _note_probe_failure(probe: str, kind: str, e: BaseException) -> None:
    import logging
    logging.getLogger("flaxdiff_tpu.telemetry").debug(
        "program-evidence %s probe failed for %s: %s", probe, kind, e)


def register_on_first_call(jitted, kind: str, key: Any,
                           telemetry=None):
    """Wrap a jitted program so its FIRST invocation is timed and
    registered (the solo `DiffusionSampler` path — the serving engine
    registers its own programs where it already measures compile).

    Callers should only wrap when a registry is active at build time:
    the wrapper costs one flag check per call and, on the first call,
    a `perf_counter` pair — first-call wall is trace+compile dominated,
    the same approximation the serving engine's `compile_ms` makes."""
    done = [False]

    def wrapper(*args):
        if done[0]:
            return jitted(*args)
        import time as _time
        t0 = _time.perf_counter()
        out = jitted(*args)
        compile_ms = (_time.perf_counter() - t0) * 1e3
        done[0] = True
        tel = telemetry
        if tel is None:
            from .hub import global_telemetry
            tel = global_telemetry()
        reg = getattr(tel, "programs", None)
        if reg is not None:
            reg.record_jitted(kind, key, jitted, args,
                              compile_ms=compile_ms)
        return out

    return wrapper
