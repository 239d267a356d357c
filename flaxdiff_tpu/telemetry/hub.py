"""The `Telemetry` hub: one object bundling the metrics registry,
exporters, goodput ledger, trace recorder, and cross-host aggregator —
what the trainer/data/checkpoint/inference layers actually talk to.

Two modes share one API:

- **disabled** (the process-global default): in-memory registry and
  goodput account, no exporters, no recorder. Every instrumentation
  call still works (tests read the in-memory account) but `enabled` is
  False, so the trainer skips the per-step `block_until_ready` that
  exact device-phase timing requires — zero behavior change for
  un-instrumented runs.
- **enabled** (`Telemetry.create(directory)` / train.py
  `--telemetry_dir`): JSONL stream + optional Prometheus textfile +
  optional fan-out into the run's existing loggers, Chrome trace
  recorder, persistent goodput ledger, and (given a Transport)
  pod-wide aggregation.

Layers with no plumbing (the data loader's worker threads) record on
the process-global hub (`global_telemetry()`); tests scope one with
`use_telemetry(...)` — the same pattern as `resilience.events`.

Dependency direction: telemetry imports nothing from trainer/ or
data/; the Transport it aggregates over is duck-typed (resilience's
event log is imported lazily only to record a failed round).
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import Dict, List, Optional

from .aggregate import CrossHostAggregator
from .devprof import DEVPROF_FILENAME
from .flightrec import FlightRecorder
from .goodput import GOODPUT_FILENAME, GoodputLedger
from .metrics import (JsonlExporter, LoggerExporter, MetricsRegistry,
                      PrometheusTextfileExporter)
from .phases import StepPhaseTimer
from .programs import PROGRAMS_FILENAME, ProgramRegistry
from .tracing import TraceRecorder
from .tracing import span as profiler_span

TELEMETRY_JSONL = "telemetry.jsonl"
TRACE_FILENAME = "trace.json"


@contextlib.contextmanager
def _both(a, b):
    with a, b:
        yield


class Telemetry:
    def __init__(self,
                 registry: Optional[MetricsRegistry] = None,
                 exporters: List = (),
                 recorder: Optional[TraceRecorder] = None,
                 goodput: Optional[GoodputLedger] = None,
                 aggregator: Optional[CrossHostAggregator] = None,
                 enabled: Optional[bool] = None,
                 epoch: Optional[int] = None,
                 programs: Optional[ProgramRegistry] = None,
                 flightrec: Optional["FlightRecorder"] = None,
                 devprof_path: Optional[str] = None):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.exporters = list(exporters)
        self.recorder = recorder
        # fault flight recorder (telemetry/flightrec.py): None on the
        # disabled hub — write_record/export forward into its rings
        self.flightrec = flightrec
        # bounded-trace drop accounting must hold for EVERY hub that
        # carries a recorder, not only ones built via create(): a
        # recorder handed in bare (tests, ad-hoc front-door hubs) gets
        # the same counter wired here, so no lane can drop silently
        if recorder is not None and not recorder.has_on_drop:
            recorder.set_on_drop(
                lambda n: self.registry.counter(
                    "telemetry/trace_dropped_events").inc(n))
        self.goodput = goodput if goodput is not None else GoodputLedger()
        self.aggregator = aggregator
        # program evidence registry (telemetry/programs.py): None on
        # the disabled hub — compile sites check for it and skip
        # registration entirely, so the default path sees zero change
        self.programs = programs
        # device-profile evidence sink (telemetry/devprof.py): the
        # trainer/scheduler build a DeviceProfiler against this path
        # when profile windows are configured; None (the disabled hub)
        # keeps the profiler unbuilt — zero change off-telemetry
        self.devprof_path = devprof_path
        # every raw JSONL row is stamped with this epoch (the
        # pod-agreed job incarnation — see set_epoch); defaults to the
        # local goodput incarnation so even a solo host's rows are
        # distinguishable across restarts
        self.epoch = int(epoch) if epoch is not None \
            else int(self.goodput.incarnation)
        # enabled gates the COSTLY instrumentation (per-step device sync,
        # per-step JSONL rows); cheap counters/spans run regardless
        self.enabled = bool(enabled) if enabled is not None \
            else bool(self.exporters or self.recorder)

    @classmethod
    def create(cls, directory: str,
               transport=None,
               prometheus_textfile: Optional[str] = None,
               logger=None,
               process_index: Optional[int] = None) -> "Telemetry":
        """Fully-enabled hub rooted at `directory`. Per-host files get a
        `_p<rank>` suffix beyond rank 0 so a shared directory never
        interleaves hosts; the goodput account is job-level (process 0
        writes, everyone records)."""
        pid = process_index
        if pid is None:
            pid = transport.process_index if transport is not None else 0
        os.makedirs(directory, exist_ok=True)
        suffix = "" if pid == 0 else f"_p{pid}"

        def _in_dir(name: str) -> str:
            stem, ext = os.path.splitext(name)
            return os.path.join(directory, stem + suffix + ext)

        exporters: List = [JsonlExporter(_in_dir(TELEMETRY_JSONL))]
        if prometheus_textfile:
            exporters.append(PrometheusTextfileExporter(prometheus_textfile))
        if logger is not None:
            exporters.append(LoggerExporter(logger))
        registry = MetricsRegistry()
        # fault flight recorder: rings fed by write_record/export below,
        # resilience events via the CURRENT global event log (tests
        # that scope a log with use_event_log attach their own)
        flightrec = FlightRecorder(directory, registry=registry)
        from ..resilience.events import global_event_log
        flightrec.attach_events(global_event_log())
        return cls(
            registry=registry,
            exporters=exporters,
            # bounded-event drops surface as a counter, not only as the
            # saved file's flaxdiff_dropped_events field — a trace that
            # silently degraded must be visible in the metrics stream
            recorder=TraceRecorder(
                _in_dir(TRACE_FILENAME), pid=pid,
                on_drop=lambda n: registry.counter(
                    "telemetry/trace_dropped_events").inc(n)),
            goodput=GoodputLedger(os.path.join(directory, GOODPUT_FILENAME),
                                  process_index=pid),
            aggregator=(CrossHostAggregator(transport)
                        if transport is not None else None),
            programs=ProgramRegistry(_in_dir(PROGRAMS_FILENAME),
                                     registry=registry),
            flightrec=flightrec,
            devprof_path=_in_dir(DEVPROF_FILENAME),
            enabled=True)

    # -- instruments ---------------------------------------------------------
    def counter(self, name: str):
        return self.registry.counter(name)

    def gauge(self, name: str):
        return self.registry.gauge(name)

    def histogram(self, name: str, **kwargs):
        return self.registry.histogram(name, **kwargs)

    def step_timer(self, mfu_meter=None,
                   sample_every: int = 1) -> StepPhaseTimer:
        return StepPhaseTimer(registry=self.registry, mfu_meter=mfu_meter,
                              sample_every=sample_every)

    # -- tracing -------------------------------------------------------------
    def span(self, name: str, cat: str = "run",
             args: Optional[Dict[str, object]] = None):
        """One call site, one name, two sinks: always the profiler's
        span `fdt.<name>` (`tracing.span`: recorded only while a
        `jax.profiler` session runs; int / str `args` ride as its
        stats), and the recorder's span too when the hub has one."""
        attrs = ({k: v for k, v in args.items()
                  if isinstance(v, (int, str))} if args else {})
        prof = profiler_span(name, **attrs)
        if self.recorder is None:
            return prof
        return _both(prof, self.recorder.span(name, cat=cat, args=args))

    def instant(self, name: str, cat: str = "event",
                args: Optional[Dict[str, object]] = None) -> None:
        if self.recorder is not None:
            self.recorder.instant(name, cat=cat, args=args)

    def set_epoch(self, epoch: int) -> None:
        """Adopt the pod-agreed epoch (train.py calls this with the
        `agree_epoch` result). Every subsequent raw row carries it, so
        two drivers of the SAME incarnation that drifted apart — a
        stale process still writing after a coordinated restart voted a
        new epoch — are distinguishable row by row, not just file by
        file (the PR-3 carried-over follow-up)."""
        self.epoch = int(epoch)

    # -- export --------------------------------------------------------------
    def write_record(self, record: Dict[str, object]) -> None:
        """One raw typed record into the JSONL stream (a no-op on the
        disabled hub, which has no exporters), stamped with the current
        epoch tag unless the caller already set one."""
        if "epoch" not in record:
            record = {**record, "epoch": self.epoch}
        if self.flightrec is not None:
            self.flightrec.record(record)
        for ex in self.exporters:
            ex.write(record)

    def record_step(self, phases: Dict[str, float]) -> None:
        """One per-step phase row into the raw JSONL stream."""
        rec = {"type": "step_phases",
               "step": int(phases.get("step", -1))}
        rec.update({k: v for k, v in phases.items() if k != "step"})
        self.write_record(rec)

    def record_numerics(self, flat_aux: Dict[str, float],
                        step: Optional[int] = None) -> None:
        """One per-cadence training-health row (`type: "numerics"`) into
        the raw stream, and the global/summary series into registry
        gauges so the Prometheus textfile carries the latest values.
        Per-module series stay JSONL-only — module count times four
        stats would chew the registry's series budget on big models."""
        rec: Dict[str, object] = {"type": "numerics"}
        if step is not None:
            rec["step"] = int(step)
        rec.update(flat_aux)
        self.write_record(rec)
        for name, v in flat_aux.items():
            if not name.startswith("numerics/module/"):
                self.registry.gauge(name).set(v)

    def export(self, step: Optional[int] = None,
               extra: Optional[Dict[str, float]] = None) -> None:
        """Registry + goodput snapshot through every exporter, epoch-
        stamped like the raw rows (snapshots bypass write_record)."""
        snap = self.registry.snapshot()
        snap.update(self.goodput.snapshot())
        if extra:
            snap.update(extra)
        snap.setdefault("epoch", float(self.epoch))
        if self.flightrec is not None:
            self.flightrec.metrics(snap, step=step)
        for ex in self.exporters:
            ex.export(snap, step=step)

    def _goodput_contribution(self) -> Dict[str, float]:
        """THIS host's goodput account as aggregatable scalars. The
        persisted `goodput.json` is process 0's clock alone (it is the
        only writer); gathering every host's in-memory counters is what
        makes `pod/goodput/*` a pod-level fact — the spread of
        productive seconds across hosts IS the straggler/stall skew the
        persisted account cannot show."""
        prod, bad = self.goodput.raw_counters()
        out = {"goodput/productive_s": prod}
        for bucket, v in bad.items():
            out[f"goodput/badput/{bucket}_s"] = v
        total = prod + sum(bad.values())
        if total > 0:
            out["goodput/fraction"] = prod / total
        return out

    def aggregate(self, metrics: Dict[str, float],
                  step: Optional[int] = None
                  ) -> Optional[Dict[str, Dict[str, float]]]:
        """Pod-wide reduction of this host's metrics — merged with this
        host's goodput counters, so the pod report carries
        `pod/goodput/*` rows (no longer proc-0's clock alone). Rank 0
        writes the flattened stats as a `pod_metrics` JSONL record AND
        mirrors them into registry gauges, so the Prometheus textfile
        exposes `pod/<metric>/<stat>` for alerting
        (examples/alerting.rules.yml). ANY failed round (timed-out
        gather on a dead peer, malformed payload, transport error)
        disables further aggregation for this hub and records a
        `telemetry_lost` resilience event — metrics must never kill a
        run, so nothing is re-raised. The disabled aggregator keeps
        publishing a non-blocking tombstone each round (see
        CrossHostAggregator), so peers disable on their next gather
        instead of stalling a full timeout per log cadence."""
        if self.aggregator is None:
            return None
        contribution = dict(metrics)
        contribution.update(self._goodput_contribution())
        try:
            stats = self.aggregator.aggregate(contribution)
        except Exception as e:  # noqa: BLE001 — degrade, never die
            from ..resilience.events import record_event
            record_event("telemetry_lost", "telemetry.aggregate",
                         detail=f"{type(e).__name__}: {e}", step=step)
            return None
        if stats is None:       # disabled earlier: tombstone offered,
            return None         # event already recorded — stay quiet
        if self.aggregator.process_index == 0:
            flat = CrossHostAggregator.flatten(stats)
            rec: Dict[str, object] = {"type": "pod_metrics",
                                      "world": self.aggregator.world_size}
            if step is not None:
                rec["step"] = int(step)
            rec.update(flat)
            self.write_record(rec)
            for name, v in flat.items():
                self.registry.gauge(name).set(v)
        return stats

    # -- lifecycle -----------------------------------------------------------
    def flush(self) -> None:
        if self.recorder is not None:
            self.recorder.save()
        self.goodput.persist()

    def close(self) -> None:
        self.flush()
        if self.flightrec is not None:
            self.flightrec.close()
        for ex in self.exporters:
            ex.close()


# Process-global default hub (disabled): layers without plumbing record
# here; tests swap it via use_telemetry.
_GLOBAL = Telemetry(enabled=False)
_global_lock = threading.Lock()


def global_telemetry() -> Telemetry:
    return _GLOBAL


def set_global_telemetry(hub: Telemetry) -> Telemetry:
    """Replace the process-global hub; returns the previous one."""
    global _GLOBAL
    with _global_lock:
        prev, _GLOBAL = _GLOBAL, hub
    return prev


class use_telemetry:
    """Context manager: swap the global hub for a scope (tests)."""

    def __init__(self, hub: Telemetry):
        self._hub = hub
        self._prev: Optional[Telemetry] = None

    def __enter__(self) -> Telemetry:
        self._prev = set_global_telemetry(self._hub)
        return self._hub

    def __exit__(self, *exc):
        assert self._prev is not None
        set_global_telemetry(self._prev)
        return False
