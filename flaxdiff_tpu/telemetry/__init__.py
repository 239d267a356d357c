"""Telemetry subsystem: step-phase timing, goodput/badput accounting,
cross-host metric aggregation, and trace-span export.

The reference logs wall-clock epoch time only (SURVEY §5.1); after the
resilience PRs this framework *survives* faults but could not *account*
for them. This package is the observability layer every perf item on
the ROADMAP depends on — you cannot speed up what you cannot attribute:

  metrics     bounded-memory registry (counters / gauges / fixed-bucket
              streaming histograms) with pluggable exporters: JSONL
              (default system of record), Prometheus textfile (atomic
              rename, textfile-collector convention), and fan-out into
              the existing trainer loggers (JsonlLogger / wandb)
  phases      StepPhaseTimer: every training step decomposed into
              data_wait / host / device / checkpoint / eval / other,
              with the device phase closed by `block_until_ready` so
              async dispatch cannot lie; feeds profiling.MFUMeter
  goodput     GoodputLedger: ALL wall-clock classified productive vs.
              badput (compile, checkpoint_commit, restart, data_stall,
              coordination_lost, ...), persisted in goodput.json so the
              account accumulates across job incarnations
  aggregate   CrossHostAggregator: min/max/mean/p50/p99/spread of
              per-host metrics over the resilience Transport (real pods
              via jax.distributed; CPU tests via InMemoryTransport)
  tracing     `span(name)`: the one span primitive, a
              `jax.profiler.TraceAnnotation` named `fdt.<name>` on the
              profiler's clock (beside the device planes of any
              capture); `SPANS`, the closed list of names; and
              TraceRecorder, the second sink: the same spans as Chrome
              trace-event JSON (`trace.json`, Perfetto) for a whole
              job. `Telemetry.span` / `StepPhaseTimer.phase` write both
  reqtrace    RequestTracer: request-scoped serving traces — follow
              one SampleRequest through admission, queue, every
              micro-batch round (program key, bucket, step codes),
              and completion; spans + request_trace JSONL rows with
              zero added host syncs (counting-mock enforced). Trace
              ids PROPAGATE across hops: the front door mints one and
              the replica scheduler adopts it (`begin(parent=...)`),
              so one Chrome lane shows door + replica + rounds
  slo         SloEngine: online per-tenant SLO attainment and
              multi-window error-budget burn rates from the same
              timestamps the door already takes — the primary input
              to burn-rate brownout and SLO-weighted routing
  flightrec   FlightRecorder: bounded in-memory rings of recent trace
              rows / resilience events / metric snapshots; a declared
              incident (replica death, engine rebuild, pool
              exhaustion, quarantine spike, elastic transition,
              quorum eviction) dumps one correlated
              incident-<id>.json bundle for offline diagnosis
  programs    ProgramRegistry: per-compiled-program evidence rows in
              programs.jsonl (cache key, compile ms, jaxpr FLOPs,
              cost_analysis flops/bytes, HBM peak, hardware
              fingerprint) — per-program roofline attribution and the
              measured substrate scripts/compare_runs.py diffs
  numerics    training-health: in-graph NumericsConfig/numerics_aux
              (per-module grad/param norms, update ratios, non-finite
              counts inside the jitted step at a cadence) + host-side
              AnomalyDetector (EMA z-score, hard non-finite/floor
              triggers, warn|skip_step|rollback actions) + NaN
              provenance helpers that name the module that blew up
  memory      MemoryMonitor: HBM gauges from device.memory_stats()
              (bytes-in-use, peak, per-step watermark, utilization),
              falling back to host-RSS gauges (/proc/self/statm) on
              backends without allocator stats
  devprof     DeviceProfiler + trace attribution parser: automated
              jax.profiler windows (step/round cadence, trigger file)
              parsed into byte-stable devprof.jsonl rows — device ms
              by op family and model module, collective-vs-compute
              split, layout-copy/fusion-gap counters — reconciled
              against the program registry (measured MFU, roofline
              verdict, predicted-vs-measured comm calibration)
  hub         Telemetry: the bundle the other layers talk to, plus the
              process-global default (`global_telemetry`) for layers
              with no plumbing

Offline analysis: `python scripts/diagnose_run.py <telemetry_dir>`
renders the goodput / phase / pod-skew report from the JSONL stream.
See docs/OBSERVABILITY.md for metric names and the badput taxonomy.

Dependency direction: trainer/, data/, and inference/ import telemetry;
telemetry imports nothing from them (and from resilience only lazily,
to classify a failed aggregation round).
"""
from .aggregate import (
    DISABLED_SENTINEL,
    AggregationDisabled,
    CrossHostAggregator,
)
from .devprof import (
    DEVPROF_FILENAME,
    DeviceProfiler,
    read_devprof,
    reconcile,
    summarize_events,
)
from .goodput import GOODPUT_FILENAME, GoodputLedger
from .hub import (
    TELEMETRY_JSONL,
    TRACE_FILENAME,
    Telemetry,
    global_telemetry,
    set_global_telemetry,
    use_telemetry,
)
from .memory import MemoryMonitor
from .metrics import (
    DEFAULT_BUCKET_BOUNDS,
    Counter,
    Gauge,
    Histogram,
    JsonlExporter,
    LoggerExporter,
    MetricsRegistry,
    PrometheusTextfileExporter,
)
from .numerics import (
    ANOMALY_ACTIONS,
    Anomaly,
    AnomalyConfig,
    AnomalyDetector,
    NumericsConfig,
    flatten_aux,
    nonfinite_modules,
    numerics_aux,
    probe_aux,
    top_level_modules,
    tree_l2_norm,
    tree_nonfinite_count,
    unwrap_module_tree,
)
from .phases import PHASES, StepPhaseTimer
from .programs import (
    PROGRAMS_FILENAME,
    ProgramRegistry,
    hardware_fingerprint,
    read_registry,
    register_on_first_call,
    stable_json,
)
from .flightrec import (
    BUNDLE_SCHEMA_VERSION,
    INCIDENT_PREFIX,
    FlightRecorder,
    list_incidents,
)
from .reqtrace import RequestTrace, RequestTracer
from .slo import SloConfig, SloEngine
from .tracing import TraceRecorder

__all__ = [
    "Telemetry",
    "global_telemetry",
    "set_global_telemetry",
    "use_telemetry",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "DEFAULT_BUCKET_BOUNDS",
    "JsonlExporter",
    "PrometheusTextfileExporter",
    "LoggerExporter",
    "StepPhaseTimer",
    "PHASES",
    "GoodputLedger",
    "GOODPUT_FILENAME",
    "CrossHostAggregator",
    "AggregationDisabled",
    "DISABLED_SENTINEL",
    "TraceRecorder",
    "TELEMETRY_JSONL",
    "TRACE_FILENAME",
    "NumericsConfig",
    "numerics_aux",
    "probe_aux",
    "flatten_aux",
    "nonfinite_modules",
    "top_level_modules",
    "tree_l2_norm",
    "tree_nonfinite_count",
    "unwrap_module_tree",
    "AnomalyConfig",
    "AnomalyDetector",
    "Anomaly",
    "ANOMALY_ACTIONS",
    "MemoryMonitor",
    "DeviceProfiler",
    "DEVPROF_FILENAME",
    "read_devprof",
    "reconcile",
    "summarize_events",
    "ProgramRegistry",
    "PROGRAMS_FILENAME",
    "hardware_fingerprint",
    "read_registry",
    "register_on_first_call",
    "stable_json",
    "RequestTrace",
    "RequestTracer",
    "SloConfig",
    "SloEngine",
    "FlightRecorder",
    "INCIDENT_PREFIX",
    "BUNDLE_SCHEMA_VERSION",
    "list_incidents",
]
