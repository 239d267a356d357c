"""Span-based tracing in Chrome trace-event JSON (Perfetto-loadable).

`jax.profiler` traces answer "what did the DEVICE do" at kernel
granularity; the question this module answers is one level up: "what
did the RUN do" — fit phases, checkpoint save/restore/commit rounds,
sampler loops, recovery paths — as host-side spans cheap enough to
leave on for a whole job. The output is the Chrome trace-event format
(`{"traceEvents": [...]}`), so `chrome://tracing` / https://ui.perfetto.dev
render the run's life directly, and `scripts/analyze_trace.py`-style
tooling can post-process it.

Bounded memory: events accumulate in a capped in-memory list; past
`max_events` new spans are counted in `dropped` instead of stored (a
run that traces too finely degrades its trace, never its training).
`save()` rewrites the whole file atomically and may be called
repeatedly (the trainer flushes at the end of fit; crash loses at most
the spans since the last flush).

**One primitive, two sinks.** `span(name, **attrs)` below opens the
same span on the PROFILER's clock: a `jax.profiler.TraceAnnotation`
named `fdt.<name>` (a TraceMe: recorded only while a profiler session
runs, about a microsecond otherwise). `Telemetry.span` always enters
it, and the recorder's span too when the hub has one;
`StepPhaseTimer.phase` enters it beside its own clock. So `trace.json`
(this recorder's clock) and any `jax.profiler` capture (the
benchmark's `--trace 1`, `profiling.trace`, `DeviceProfiler`) hold the
same names, and the capture holds them on the clock of the device
planes. `SPANS` is the closed list of names; docs/OBSERVABILITY.md
"Trace spans" has the table.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, NamedTuple, Optional, Tuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation

# The program's prefix in a profiler capture, as `bench.` is the
# benchmark harness's.
SPAN_PREFIX = "fdt."

class SpanDoc(NamedTuple):
    thread: str                # dispatch | complete | fit | any
    parent: Optional[str]      # `a | b`: either; None: a top-level span
    attrs: Tuple[str, ...]     # integer / string stats it carries
    covers: str


# The closed list of span names, one place (tests walk every
# `.span("...")` / `.phase("...")` call site of the package against it).
SPANS: Dict[str, SpanDoc] = {k: SpanDoc(*v) for k, v in {
    # -- serving: dispatch thread (`serving-dispatch`), per loop turn
    "serve.pace": ("dispatch", None, (), "the loop's one wait on device "
                   "work: two rounds unfinished, `_block_until_ready` "
                   "of the older, before `serve.admit`"),
    "serve.wait": ("dispatch", None, (), "`_cv.wait` with nothing to "
                   "serve: queue and active empty, or only parked "
                   "entries"),
    "serve.admit": ("dispatch", None, (), "under the lock: shed expired, "
                    "pick group, pop active, admit (`engine.prepare`: two "
                    "launches a request), brownout tier"),
    "serve.round": ("dispatch", None, ("round", "bucket", "rows", "steps"),
                    "`_checked_advance`, first line to return; `steps` "
                    "the turns it ran, the rows' terminal denoises "
                    "among them: rows x steps is its model evaluations"),
    "serve.stack": ("dispatch", "serve.round | serve.handoff", (),
                    "host arithmetic: the pairs / n_act / offsets / term "
                    "in numpy and the tuple of row carries (no launch)"),
    "serve.launch": ("dispatch", "serve.round | serve.handoff", ("kind",),
                     "`_get_program` and the ONE jitted call: stack, "
                     "program, unstack (host side of the dispatch; a "
                     "compile on a miss is its length)"),
    "serve.unstack": ("dispatch", "serve.round", (), "each row takes its "
                      "own outputs of the program: x / rng / state / "
                      "taps / ref (no launch)"),
    "serve.handoff": ("dispatch", None, ("rows", "bucket"),
                      "`engine.finalize` whole, for rows whose terminal "
                      "turn ran: one launch for stack, optional decode, "
                      "clip, and no model evaluation"),
    "serve.backpressure": ("dispatch", None, (), "the wait while more "
                           "than `max_inflight` batches are in flight"),
    # -- serving: completion thread (`serving-complete`)
    "serve.fetch": ("complete", None, ("rows",),
                    "`_block_until_ready` + `_device_get` of a batch"),
    "serve.resolve": ("complete", None, ("rows",), "per-row histograms "
                      "and `set_result`"),
    # -- fit: the training loop's thread. `fit.step` is a
    # StepTraceAnnotation (xprof's step view keys on it); the rest are
    # `StepPhaseTimer.phase` names and two children of the log step
    "fit.step": ("fit", None, ("step_num",), "one loop turn"),
    "fit.host": ("fit", "fit.step", (), "host side of `train_step`: the "
                 "dispatch of the jitted step"),
    "fit.data_wait": ("fit", "fit.step", (), "`next(upload)`"),
    "fit.device": ("fit", "fit.step", (), "the sampled steps' "
                   "`block_until_ready` (enabled hub only)"),
    "fit.numerics": ("fit", "fit.step", (), "aux readback + detector"),
    "fit.profile": ("fit", "fit.step", (), "DeviceProfiler open / close"),
    "fit.elastic": ("fit", "fit.step", (), "pod quorum round"),
    "fit.checkpoint": ("fit", "fit.step", (), "save + commit round"),
    "fit.log_step": ("fit", "fit.step", (), "the log branch: loss-window "
                     "fetch to the `log_t0` that closes it"),
    "fit.loss_fetch": ("fit", "fit.log_step", (), "the blocking readback "
                       "of the loss window"),
    "fit.best_state_copy": ("fit", "fit.log_step", (),
                            "`keep_best_state`'s `tree_map(jnp.copy)`"),
    # -- operator only: rare paths, no benchmark metric reads them
    "ckpt.save": ("any", None, ("step",), "checkpoint save dispatch"),
    "ckpt.commit": ("any", None, ("step",), "two-phase commit round"),
    "ckpt.restore": ("any", None, ("step",), "checkpoint restore"),
    "ckpt.consensus_restore": ("any", None, (), "pod-agreed restore"),
    "ckpt.save_and_commit": ("fit", "fit.checkpoint", ("step",),
                             "in-loop save + commit"),
    "ckpt.final_save": ("fit", None, (), "the force-save after the loop"),
    "train.restore_at_start": ("fit", None, (), "resume before step 1"),
    "train.rollback": ("fit", None, (), "anomaly recovery"),
    "data.first_batch": ("fit", None, (), "the first `next(upload)`"),
    "data.rewind_refetch": ("fit", None, (), "replay after a rollback"),
    "elastic.restore": ("fit", None, (), "shrink / readmit restore"),
    "elastic.shrink": ("fit", None, (), "mesh shrink transition"),
    "elastic.quorum_rollback": ("fit", None, (), "pod-voted rollback"),
    "numerics.provenance": ("fit", None, (), "NaN provenance re-run"),
    "sampler.generate": ("any", None, (), "one `generate_samples` call"),
    "validation": ("any", None, (), "train.py's in-loop validation"),
}.items()}


def span(name: str, **attrs):
    """`with span("serve.stack"):` a host span on the profiler's clock,
    named `fdt.<name>`. Integer / string `attrs` ride as TraceMe stats
    (`round=`, `bucket=`, `rows=`, `steps=`, `kind=`): `round` is the
    join key to `RequestTracer`'s rows. Records only while a
    `jax.profiler` session runs."""
    return TraceAnnotation(SPAN_PREFIX + name, **attrs)


def traced_steps(n: int, name: str = "fit.step"):
    """`for i in traced_steps(n):` is `for i in range(n):` with each
    turn inside a `jax.profiler.StepTraceAnnotation` named
    `fdt.<name>` with `step_num=i + 1`, which xprof's step view keys
    on. A `break` or an exception in the loop body closes the open
    turn (the generator is closed with the loop)."""
    for i in range(n):
        with StepTraceAnnotation(SPAN_PREFIX + name, step_num=i + 1):
            yield i


class TraceRecorder:
    """Collects spans/instants; writes Chrome trace-event JSON.

    `on_drop` (optional, `callable(n)`) is invoked OUTSIDE the recorder
    lock each time events are dropped past the bound — the telemetry
    hub wires it to the `telemetry/trace_dropped_events` counter so a
    trace that silently degraded is visible in the metrics stream, not
    only in the saved file's `flaxdiff_dropped_events` field.
    """

    def __init__(self, path: str, pid: int = 0,
                 max_events: int = 100_000, clock=time.perf_counter,
                 on_drop=None):
        self.path = path
        self.pid = int(pid)
        self.max_events = max_events
        self._clock = clock
        self._t0 = clock()
        self._lock = threading.Lock()
        self._events: list = [
            {"ph": "M", "name": "process_name", "pid": self.pid,
             "args": {"name": f"host {self.pid}"}}]
        self.dropped = 0
        self._on_drop = on_drop

    @property
    def has_on_drop(self) -> bool:
        return self._on_drop is not None

    def set_on_drop(self, fn) -> None:
        """Late-wire the drop callback: a recorder handed to a
        `Telemetry` hub bare (not via `Telemetry.create`) gets the
        `telemetry/trace_dropped_events` counter attached here, so
        front-door and scheduler lanes share one accounting path."""
        self._on_drop = fn

    def _now_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    def _emit(self, ev: Dict[str, object]) -> None:
        dropped = False
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                dropped = True
            else:
                self._events.append(ev)
        if dropped and self._on_drop is not None:
            self._on_drop(1)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "run",
             args: Optional[Dict[str, object]] = None):
        """Complete-event ("X") span around a block. Exceptions
        propagate; the span still closes (marked `error: true`) so a
        crash is visible in the timeline at the exact span it died in."""
        ts = self._now_us()
        err = False
        try:
            yield
        except BaseException:
            err = True
            raise
        finally:
            ev: Dict[str, object] = {
                "ph": "X", "name": name, "cat": cat, "pid": self.pid,
                "tid": threading.get_ident() % 1_000_000,
                "ts": ts, "dur": self._now_us() - ts}
            a = dict(args or {})
            if err:
                a["error"] = True
            if a:
                ev["args"] = a
            self._emit(ev)

    def event_at(self, name: str, start_s: float, end_s: float,
                 cat: str = "run",
                 args: Optional[Dict[str, object]] = None,
                 tid: Optional[int] = None) -> None:
        """Complete event from EXPLICIT timestamps already taken on this
        recorder's clock (`time.perf_counter` by default). The serving
        request tracer records host timestamps inline in the dispatch
        and completion threads (zero device syncs) and emits the spans
        after the fact — this is the emission path."""
        ev: Dict[str, object] = {
            "ph": "X", "name": name, "cat": cat, "pid": self.pid,
            "tid": (int(tid) if tid is not None
                    else threading.get_ident() % 1_000_000),
            "ts": (start_s - self._t0) * 1e6,
            "dur": max(0.0, end_s - start_s) * 1e6}
        if args:
            ev["args"] = args
        self._emit(ev)

    def instant_at(self, name: str, at_s: float, cat: str = "event",
                   args: Optional[Dict[str, object]] = None,
                   tid: Optional[int] = None) -> None:
        """Instant event at an explicit recorder-clock timestamp."""
        ev: Dict[str, object] = {
            "ph": "i", "s": "p", "name": name, "cat": cat,
            "pid": self.pid,
            "tid": (int(tid) if tid is not None
                    else threading.get_ident() % 1_000_000),
            "ts": (at_s - self._t0) * 1e6}
        if args:
            ev["args"] = args
        self._emit(ev)

    def instant(self, name: str, cat: str = "event",
                args: Optional[Dict[str, object]] = None) -> None:
        ev: Dict[str, object] = {
            "ph": "i", "s": "p", "name": name, "cat": cat,
            "pid": self.pid, "tid": threading.get_ident() % 1_000_000,
            "ts": self._now_us()}
        if args:
            ev["args"] = args
        self._emit(ev)

    def save(self) -> str:
        """Atomic rewrite of the full trace file; safe to call often."""
        with self._lock:
            events = list(self._events)
            dropped = self.dropped
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        if dropped:
            doc["flaxdiff_dropped_events"] = dropped
        os.makedirs(os.path.dirname(os.path.abspath(self.path)) or ".",
                    exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        os.replace(tmp, self.path)
        return self.path
