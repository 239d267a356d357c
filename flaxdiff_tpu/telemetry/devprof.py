"""Device-time attribution: automated profile windows parsed into
byte-stable evidence rows (docs/OBSERVABILITY.md "Device-time
attribution").

The observability stack before this module answered "what did the host
do" (phase timer), "what happened to a request" (traces), and "what
SHOULD the comm bill be" (the static byte model) — but "where did the
device time actually GO on-chip" lived in a manual CLI run against a
trace someone remembered to capture. This module promotes that
analysis to a first-class evidence subsystem:

- **Automated windows** (`DeviceProfiler`): the trainer opens a
  `jax.profiler` trace every `TrainerConfig.profile_cadence` steps (or
  on demand via a trigger file / the serving scheduler's per-round
  hook) and closes it `profile_steps` later. Window overhead lands in
  the `profile` step phase + goodput badput bucket, so MFU accounting
  stays honest; off-window steps cost two int compares — zero device
  work, zero host syncs (`analysis/budgets.py` pins this file's
  host-sync count at 0).
- **Attribution parser**: the Chrome-trace capture is parsed into ONE
  `devprof.jsonl` row per profiled window — device ms by op family
  (`op_family` strips the SSA counter, absorbed from
  `scripts/analyze_trace.py`, now a delegating shim) AND by model
  module (jax named-scope prefixes in op metadata where the backend
  surfaces them), collective-vs-compute split, layout-copy and
  fusion-gap counters. Families sum to the profiled device total by
  construction. Truncated/corrupt captures are skipped but REPORTED
  (`skipped_corrupt`), and a capture with no device timeline is an
  explicit `host_only` row, never a silent half-answer.
- **Reconciliation** (`reconcile`): the measured row joins its program
  registry row — achieved FLOP/s against `flops_jaxpr` gives measured
  MFU and a roofline verdict (compute-/memory-/comm-bound), measured
  collective ms against the static per-axis comm bytes gives the
  planner's calibration constant (achieved collective bytes/s). The
  fields are written back onto the registry row via
  `ProgramRegistry.annotate` (an append-only `program_update` row that
  `read_registry` merges), so `scripts/compare_runs.py` diffs them and
  `scripts/diagnose_run.py` renders them.

Source classification (empirical over jax CPU/TPU captures): a
process named "/device:..." is a real device timeline (`device`);
without one, XLA op events carrying an `hlo_op` arg (the CPU backend's
`tf_XLATfrtCpuClient` threads) are the best available proxy
(`host_xla`); neither means the window closed before any compiled work
ran (`host_only`).

No module-level jax import: readers (`scripts/compare_runs.py`,
`scripts/diagnose_run.py`) must be able to load rows without a
backend. Profiler start/stop imports jax lazily and degrades with a
`trace_failed` resilience event, same contract as `profiling.trace`.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

from .programs import read_registry, stable_json

DEVPROF_FILENAME = "devprof.jsonl"

# HBM bandwidth per chip, bytes/s — the roofline ridge denominator.
# Public numbers from Google's TPU system documentation; override with
# FLAXDIFF_PEAK_BYTES_PER_S where the table has no row (e.g. CPU).
_PEAK_HBM_BYTES_PER_S = {
    "TPU v2": 700e9,
    "TPU v3": 900e9,
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,       # v5p (kind string "TPU v5")
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,  # v6e / Trillium
    "TPU v6e": 1640e9,
}

# HLO collective family prefixes (matched against `op_family` output,
# so async start/done variants like "all-reduce-start" count too)
_COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                        "collective-permute", "all-to-all",
                        "collective-broadcast")

# op-metadata keys that may carry the framework op path (jax
# named_scope prefixes), in preference order; TPU xprof traces use
# tf_op, synthetic fixtures/other backends vary
_SCOPE_KEYS = ("tf_op", "scope", "op_name", "long_name")

# path segments that are tracing wrappers, not model modules
_WRAPPER_SEG = re.compile(
    r"^(jit|pjit|jvp|vjp|transpose|remat|checkpoint|named)\(")

_PARSE_ERRORS = (OSError, EOFError, ValueError, KeyError)


# -- trace loading -------------------------------------------------------------

def load_events(path: str) -> List[Dict[str, Any]]:
    """Parsed `traceEvents` of one Chrome-trace capture (gz or plain);
    raises on a truncated/corrupt file — callers classify, never
    swallow silently."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path) as f:
        return json.load(f)["traceEvents"]


def device_pids(events) -> Dict[Any, str]:
    """pid -> process name for real device timelines."""
    pids: Dict[Any, str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            name = (e.get("args") or {}).get("name", "")
            if "device:" in name.lower() and "cpu" not in name.lower():
                pids[e["pid"]] = name
    return pids


def op_family(name: str) -> str:
    """Strip the SSA counter: 'attn1.27' -> 'attn', 'fusion.4597' ->
    'fusion' (absorbed from scripts/analyze_trace.py)."""
    fam = re.split(r"[.\d]", name)[0]
    return fam or name


def module_of(args: Dict[str, Any]) -> str:
    """Model-module attribution of one op from its metadata: the first
    non-wrapper segment of a named-scope path where the backend
    surfaces one, else the owning HLO module (the CPU backend exposes
    only `hlo_module`), else 'unattributed'."""
    for k in _SCOPE_KEYS:
        path = args.get(k)
        if isinstance(path, str) and "/" in path:
            for seg in path.split("/"):
                seg = seg.strip()
                if seg and not _WRAPPER_SEG.match(seg):
                    return seg
    mod = args.get("hlo_module")
    if isinstance(mod, str) and mod:
        return mod
    return "unattributed"


def select_op_events(events) -> Tuple[str, List[Dict[str, Any]]]:
    """(source, leaf XLA op events): 'device' when a real device
    timeline exists, 'host_xla' when only host-side XLA op events
    (with an `hlo_op` arg) do, 'host_only' when neither. Step/module
    envelope events ('jit_*', bare step numbers) are dropped so leaf
    ops sum to the timeline total."""
    pids = device_pids(events)
    if pids:
        source = "device"
        picked = [e for e in events
                  if e.get("ph") == "X" and e.get("pid") in pids]
    else:
        picked = [e for e in events
                  if e.get("ph") == "X"
                  and isinstance(e.get("args"), dict)
                  and "hlo_op" in e["args"]]
        source = "host_xla" if picked else "host_only"
    out = []
    for e in picked:
        name = e.get("name", "?")
        if name.startswith("jit_") or name.isdigit():
            continue
        out.append(e)
    return source, out


def summarize_events(events) -> Dict[str, Any]:
    """One flat attribution summary of a parsed capture (durations in
    µs — `build_row` converts to ms). Families sum to
    `device_total_us` by construction."""
    source, ops = select_op_events(events)
    fam_us: collections.Counter = collections.Counter()
    fam_cnt: collections.Counter = collections.Counter()
    mod_us: collections.Counter = collections.Counter()
    coll_us = copy_us = 0.0
    coll_cnt = copy_cnt = 0
    lanes: Dict[Tuple[Any, Any], List[Tuple[float, float]]] = \
        collections.defaultdict(list)
    for e in ops:
        name = e.get("name", "?")
        dur = float(e.get("dur", 0) or 0)
        fam = op_family(name)
        fam_us[fam] += dur
        fam_cnt[fam] += 1
        mod_us[module_of(e.get("args") or {})] += dur
        if fam.startswith(_COLLECTIVE_PREFIXES):
            coll_us += dur
            coll_cnt += 1
        if fam.startswith("copy") or fam == "transpose":
            copy_us += dur
            copy_cnt += 1
        ts = e.get("ts")
        if ts is not None:
            lanes[(e.get("pid"), e.get("tid"))].append((float(ts), dur))
    total = float(sum(fam_us.values()))
    # fusion gaps: idle µs between consecutive ops on one device lane —
    # launch/fusion overhead the op durations themselves cannot show
    gap_us = 0.0
    gap_cnt = 0
    for evs in lanes.values():
        evs.sort()
        for (t0, d0), (t1, _) in zip(evs, evs[1:]):
            gap = t1 - (t0 + d0)
            if gap > 0:
                gap_us += gap
                gap_cnt += 1
    return {
        "source": source,
        "devices": sorted(device_pids(events).values()),
        "lanes": len(lanes),
        "device_total_us": total,
        "families": {f: {"us": fam_us[f], "count": fam_cnt[f]}
                     for f in fam_us},
        "modules": dict(mod_us),
        "collective_us": coll_us, "collective_count": coll_cnt,
        "compute_us": total - coll_us,
        "layout_copy_us": copy_us, "layout_copy_count": copy_cnt,
        "fusion_gap_us": gap_us, "fusion_gap_count": gap_cnt,
    }


def find_capture(path: str):
    """(capture path, parsed events or None, skipped corrupt paths):
    the newest capture under `path` that has an attributable timeline
    (device first, host-XLA second), skipping — but REPORTING —
    truncated/corrupt files. A lone file path is returned unparsed.
    Raises SystemExit when `path` holds no captures at all."""
    if os.path.isfile(path):
        return path, None, []
    hits = sorted(glob.glob(
        os.path.join(path, "**", "*.trace.json.gz"), recursive=True))
    if not hits:
        raise SystemExit(f"no *.trace.json.gz under {path!r}")
    skipped: List[str] = []
    host_only = None
    for hit in reversed(hits):
        try:
            events = load_events(hit)
            source, _ = select_op_events(events)
        except _PARSE_ERRORS:
            skipped.append(hit)
            continue
        if source != "host_only":
            return hit, events, skipped
        if host_only is None:
            host_only = (hit, events)
    if host_only is not None:
        return host_only[0], host_only[1], skipped
    # everything corrupt: hand back the newest raw so the caller's own
    # parse attempt reports the error class — don't pre-list it too
    return hits[-1], None, [h for h in skipped if h != hits[-1]]


# -- rows ----------------------------------------------------------------------

def build_row(summary: Optional[Dict[str, Any]], *,
              capture: Optional[str] = None,
              steps: int = 1,
              kind: Optional[str] = None, key: Optional[str] = None,
              window: Optional[int] = None, step: Optional[int] = None,
              skipped_corrupt=(),
              extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One byte-stable `devprof.jsonl` row from a parsed summary
    (`summary=None` means no capture parsed: status
    `skipped_corrupt`). Durations in ms; `steps` divides into the
    `_per_step` field only — family/module totals stay window totals
    so they keep summing to `device_total_ms`."""
    steps = max(int(steps or 1), 1)
    s = summary or {}
    total_ms = float(s.get("device_total_us", 0.0)) / 1e3
    if summary is None:
        status = "skipped_corrupt"
    elif s.get("source") == "host_only":
        status = "host_only"
    else:
        status = "ok"
    row: Dict[str, Any] = {
        "type": "devprof",
        "status": status,
        "source": s.get("source"),
        "capture": os.path.basename(capture) if capture else None,
        "kind": str(kind) if kind is not None else None,
        "key": str(key) if key is not None else None,
        "window": int(window) if window is not None else None,
        "step": int(step) if step is not None else None,
        "steps": steps,
        "devices": list(s.get("devices", [])),
        "lanes": int(s.get("lanes", 0)),
        "device_total_ms": total_ms,
        "device_ms_per_step": round(total_ms / steps, 3),
        "families": {f: {"ms": v["us"] / 1e3, "count": int(v["count"])}
                     for f, v in sorted(s.get("families", {}).items())},
        "modules": {m: us / 1e3
                    for m, us in sorted(s.get("modules", {}).items())},
        "collective_ms": float(s.get("collective_us", 0.0)) / 1e3,
        "collective_count": int(s.get("collective_count", 0)),
        "compute_ms": float(s.get("compute_us", 0.0)) / 1e3,
        "layout_copy_ms": float(s.get("layout_copy_us", 0.0)) / 1e3,
        "layout_copy_count": int(s.get("layout_copy_count", 0)),
        "fusion_gap_ms": float(s.get("fusion_gap_us", 0.0)) / 1e3,
        "fusion_gap_count": int(s.get("fusion_gap_count", 0)),
        "skipped_corrupt": [os.path.basename(p)
                            for p in skipped_corrupt],
    }
    if extra:
        row.update(extra)
    return row


def append_row(path: str, row: Dict[str, Any]) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".",
                exist_ok=True)
    with open(path, "a", encoding="utf-8") as f:
        f.write(stable_json(row) + "\n")


def read_devprof(path: str) -> List[Dict[str, Any]]:
    """devprof rows of a `devprof.jsonl` (torn tail tolerated)."""
    return [r for r in read_registry(path)
            if r.get("type") == "devprof"]


# -- reconciliation ------------------------------------------------------------

def _env_peak(name: str) -> Optional[float]:
    """A positive float from env `name`, else None (unset, malformed
    or non-positive)."""
    try:
        v = float(os.environ.get(name, ""))
    except ValueError:
        return None
    return v if v > 0 else None


def device_peak_bytes_per_s(device=None) -> Optional[float]:
    """Peak HBM bytes/s of `device` (default: first local device) by
    exact `device_kind`; None off-TPU, KeyError for an unlisted TPU —
    the contract of `profiling.device_peak_flops`."""
    from ..profiling import peak_for_kind
    if device is None:
        import jax
        device = jax.local_devices()[0]
    return peak_for_kind(_PEAK_HBM_BYTES_PER_S,
                         str(getattr(device, "device_kind", "")),
                         "peak HBM bytes/s")


def resolved_peak_flops() -> Optional[float]:
    """Peak FLOP/s: FLAXDIFF_PEAK_FLOPS env override first (the only
    way to get measured MFU on backends the table does not know, e.g.
    CPU CI), else the chip table via `profiling.device_peak_flops`."""
    if os.environ.get("FLAXDIFF_PEAK_FLOPS"):
        return _env_peak("FLAXDIFF_PEAK_FLOPS")
    from ..profiling import device_peak_flops
    return device_peak_flops()


def resolved_peak_bytes_per_s() -> Optional[float]:
    """Peak HBM bytes/s for the roofline ridge: env override
    FLAXDIFF_PEAK_BYTES_PER_S first, else the chip table."""
    if os.environ.get("FLAXDIFF_PEAK_BYTES_PER_S"):
        return _env_peak("FLAXDIFF_PEAK_BYTES_PER_S")
    return device_peak_bytes_per_s()


def reconcile(row: Dict[str, Any], program: Dict[str, Any], *,
              peak_flops: Optional[float] = None,
              peak_bytes_per_s: Optional[float] = None,
              comm_bound_fraction: float = 0.4) -> Dict[str, Any]:
    """Join one measured devprof row against its program-registry row.

    Returns the reconciliation fields (callers merge them into the
    devprof row AND annotate the registry row): achieved FLOP/s vs the
    registry's analytic `flops_jaxpr` -> measured MFU; roofline
    verdict — comm-bound when collectives eat >=
    `comm_bound_fraction` of the window, else arithmetic intensity
    (`flops_cost`/`bytes_cost`) against the ridge
    (peak_flops/peak_bytes_per_s), else the achieved peak fraction;
    measured collective ms vs the static per-axis comm bytes — the
    achieved collective bytes/s IS the planner's calibration
    constant."""
    steps = max(int(row.get("steps") or 1), 1)
    total_ms = float(row.get("device_total_ms") or 0.0)
    per_step_ms = total_ms / steps
    out: Dict[str, Any] = {
        "measured_device_ms_per_step": per_step_ms,
        "measured_flops_per_s": None,
        "measured_mfu": None,
    }
    pk_f = peak_flops if peak_flops is not None else resolved_peak_flops()
    flops_j = program.get("flops_jaxpr")
    measured_mfu = None
    if flops_j and per_step_ms > 0:
        achieved = float(flops_j) / (per_step_ms / 1e3)
        out["measured_flops_per_s"] = achieved
        if pk_f:
            measured_mfu = achieved / pk_f
            out["measured_mfu"] = measured_mfu
    coll_ms = float(row.get("collective_ms") or 0.0)
    comm_bytes = sum((program.get("comm_bytes_by_axis") or {}).values())
    out["comm_measured_ms"] = coll_ms
    out["comm_predicted_bytes"] = int(comm_bytes)
    out["comm_achieved_bytes_per_s"] = (
        comm_bytes * steps / (coll_ms / 1e3)
        if comm_bytes and coll_ms > 0 else None)
    verdict = basis = None
    if total_ms > 0 and coll_ms / total_ms >= comm_bound_fraction:
        verdict, basis = "comm-bound", "collective_fraction"
    else:
        fc = program.get("flops_cost")
        bc = program.get("bytes_cost")
        pk_b = (peak_bytes_per_s if peak_bytes_per_s is not None
                else resolved_peak_bytes_per_s())
        if fc and bc and pk_f and pk_b:
            verdict = ("compute-bound" if (fc / bc) >= (pk_f / pk_b)
                       else "memory-bound")
            basis = "intensity_vs_ridge"
        elif measured_mfu is not None:
            # no cost model: over half of peak can only be compute-bound
            verdict = ("compute-bound" if measured_mfu >= 0.5
                       else "memory-bound")
            basis = "mfu_fraction"
    out["roofline_verdict"] = verdict
    out["roofline_basis"] = basis
    return out


# registry fields `DeviceProfiler` writes back via annotate (the
# measured substrate ROADMAP item 3's planner calibrates against)
_ANNOTATE_FIELDS = ("measured_device_ms_per_step", "measured_flops_per_s",
                    "measured_mfu", "comm_measured_ms",
                    "comm_predicted_bytes", "comm_achieved_bytes_per_s",
                    "roofline_verdict", "roofline_basis")


def profile_window_row(logdir: str, *, steps: int = 1,
                       kind: Optional[str] = None,
                       key: Optional[str] = None,
                       programs=None,
                       window: Optional[int] = None,
                       step: Optional[int] = None,
                       extra: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, Any]:
    """Parse the newest usable capture under one window's logdir into
    a devprof row, reconciling against (and annotating) the program
    registry row identified by (kind, key) when one exists. Never
    raises on capture problems — a corrupt-only window yields a
    `skipped_corrupt` row, which is itself evidence."""
    summary = None
    capture = None
    skipped: List[str] = []
    try:
        capture, events, skipped = find_capture(logdir)
        if events is None:
            events = load_events(capture)
        summary = summarize_events(events)
    except SystemExit:
        capture = None        # no captures at all
    except _PARSE_ERRORS as e:
        skipped.append(f"{capture}: {type(e).__name__}")
        summary = None
    row = build_row(summary, capture=capture, steps=steps, kind=kind,
                    key=key, window=window, step=step,
                    skipped_corrupt=skipped, extra=extra)
    program = None
    if programs is not None and kind is not None and key is not None:
        rows = programs.rows() if hasattr(programs, "rows") else programs
        for r in rows:
            if r.get("kind") == str(kind) and r.get("key") == str(key):
                program = r
                break
    if program is not None and row["status"] == "ok":
        fields = reconcile(row, program)
        row.update(fields)
        if hasattr(programs, "annotate"):
            programs.annotate(kind, key, {
                **{f: fields.get(f) for f in _ANNOTATE_FIELDS},
                "devprof_window": window})
    return row


# -- automated windows ---------------------------------------------------------

class DeviceProfiler:
    """Cadence/trigger-armed `jax.profiler` windows parsed into
    `devprof.jsonl` evidence rows.

    The owner drives the window lifecycle (the trainer syncs the
    pipeline through its own seam BEFORE `close`, so this module never
    touches the device): `should_open`/`should_close` are two int
    compares — the entire off-window cost. `poll_trigger` (a host
    `stat`, polled only at log cadence) arms a one-shot window;
    `poll_round` is the serving scheduler's per-round hook (round
    cadence instead of step cadence, no reconciliation target). A
    failed profiler start/stop degrades with a `trace_failed`
    resilience event, never an exception — the same contract as
    `profiling.trace`."""

    def __init__(self, path: Optional[str], *,
                 cadence: int = 0, window: int = 5,
                 trigger_path: Optional[str] = None,
                 logdir: Optional[str] = None,
                 metrics=None):
        self.path = path
        self.cadence = max(int(cadence), 0)
        self.window = max(int(window), 1)
        self.trigger_path = trigger_path
        if logdir is None and path:
            logdir = os.path.join(
                os.path.dirname(os.path.abspath(path)), "devprof_traces")
        self.logdir = logdir
        self._metrics = metrics
        self._armed = False
        self._open_at: Optional[int] = None
        self._open_logdir: Optional[str] = None
        self._seq = 0
        self.rows: List[Dict[str, Any]] = []

    # -- window state (int compares only: the off-window hot path) ----------
    def active(self) -> bool:
        return self._open_at is not None

    @property
    def open_step(self) -> Optional[int]:
        return self._open_at

    def should_open(self, step: int) -> bool:
        if self._open_at is not None or self.logdir is None:
            return False
        if self._armed:
            return True
        return self.cadence > 0 and step % self.cadence == 0

    def should_close(self, step: int) -> bool:
        return (self._open_at is not None
                and step - self._open_at >= self.window)

    def poll_trigger(self) -> bool:
        """One host stat: an existing trigger file arms a one-shot
        window (and is consumed). Owners poll at log cadence only."""
        p = self.trigger_path
        if not p or self._armed or self._open_at is not None:
            return False
        if not os.path.exists(p):
            return False
        try:
            os.remove(p)
        except OSError:
            pass
        self._armed = True
        return True

    # -- lifecycle ----------------------------------------------------------
    def open(self, step: int) -> bool:
        if self._open_at is not None or self.logdir is None:
            return False
        self._armed = False
        self._seq += 1
        sub = os.path.join(self.logdir, f"window{self._seq:04d}")
        try:
            os.makedirs(sub, exist_ok=True)
            import jax
            jax.profiler.start_trace(sub)
        except Exception as e:  # noqa: BLE001 — degrade, but visibly
            from ..resilience.events import record_event
            record_event("trace_failed", "devprof.start_trace",
                         detail=f"{type(e).__name__}: {e} (logdir={sub})",
                         step=step)
            return False
        self._open_at = int(step)
        self._open_logdir = sub
        return True

    def close(self, at_step: Optional[int] = None, *,
              kind: Optional[str] = None, key: Optional[str] = None,
              programs=None,
              extra: Optional[Dict[str, Any]] = None
              ) -> Optional[Dict[str, Any]]:
        """Stop the trace, parse the capture, write + return the row.
        The caller has already settled in-flight device work (the
        trainer's `_block_until_ready` seam) so the capture covers
        every step dispatched inside the window. `at_step` is the step
        ABOUT to run (close-before-dispatch), so profiled steps =
        at_step - open_step; omitted (end-of-fit close) the nominal
        window length stands."""
        if self._open_at is None:
            return None
        open_at, sub = self._open_at, self._open_logdir
        self._open_at = self._open_logdir = None
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — degrade, but visibly
            from ..resilience.events import record_event
            record_event("trace_failed", "devprof.stop_trace",
                         detail=f"{type(e).__name__}: {e} (logdir={sub})")
        steps = (max(int(at_step) - open_at, 1)
                 if at_step is not None else self.window)
        row = profile_window_row(sub, steps=steps, kind=kind, key=key,
                                 programs=programs, window=self._seq,
                                 step=open_at, extra=extra)
        if self.path:
            append_row(self.path, row)
        self.rows.append(row)
        if self._metrics is not None:
            self._metrics.counter("devprof/windows").inc()
            if row["status"] != "ok":
                self._metrics.counter("devprof/parse_failures").inc()
            else:
                self._metrics.gauge(
                    "devprof/last_device_ms_per_step").set(
                        row["device_ms_per_step"])
                if row.get("measured_mfu") is not None:
                    self._metrics.gauge("devprof/last_measured_mfu").set(
                        row["measured_mfu"])
        return row

    def poll_round(self, round_no: int) -> Optional[Dict[str, Any]]:
        """Serving scheduler hook, called once per dispatch round
        (host-only; never touches the program cache, so an armed
        profiler keeps warm replays retrace-free). Rounds stand in for
        steps: a window opens on round cadence / trigger and closes
        `window` rounds later."""
        if self._open_at is not None:
            if round_no - self._open_at >= self.window:
                return self.close(at_step=round_no,
                                  extra={"owner": "serving"})
            return None
        if self.should_open(round_no):
            self.open(round_no)
        return None
