"""Profiler capture and the reduction from a trace to intervals.

Read off a real v5e trace (PR 23): each chip is a plane
`/device:TPU:<n>`; its line `XLA Ops` holds one event per executed HLO
operation, in order, named by the operation's full HLO text (a Mosaic
kernel is a `custom-call` with `custom_call_target="tpu_custom_call"`);
`Async XLA Ops` holds the spans of asynchronous copies and collectives
from their start to their done; `XLA Modules` holds one event per
executed program. Host threads are lines of the plane `/host:CPU`, and
`jax.profiler.TraceAnnotation` spans land on its `python` line. Device
and host clocks differ by about a millisecond.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import shutil
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]           # (start_ns, end_ns)
Event = Tuple[str, float, float]         # (name, start_ns, dur_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "fdt."      # the program's own spans (telemetry/tracing.py)


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: List[Event]
    async_ops: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    spans: List[Event]                   # the benchmark's own host spans
    rows: List[Dict] = dataclasses.field(default_factory=list, repr=False)

    def window(self, name: str = "bench.window") -> Optional[Interval]:
        w = [(s, s + d) for n, s, d in self.spans if n == name]
        return (min(a for a, _ in w), max(b for _, b in w)) if w else None


# -- interval arithmetic ----------------------------------------------------

def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def measure(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def clip(intervals: List[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of union(a) that union(b) does not cover."""
    out: List[Interval] = []
    cover = union(b)
    for lo, hi in union(a):
        cur = lo
        for c, d in cover:
            if d <= cur:
                continue
            if c >= hi:
                break
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    return subtract([window], busy)


def as_intervals(events: List[Event]) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


# -- reading ----------------------------------------------------------------

def from_events(rows: List[Dict]) -> Trace:
    """A trace from recorded rows {plane, line, name, start_ns, dur_ns}:
    the form the tests' recorded v5e trace is kept in."""
    devs: Dict[str, DeviceTrace] = {}
    spans: List[Event] = []
    for r in rows:
        ev = (r["name"], float(r["start_ns"]), float(r["dur_ns"]))
        if DEVICE_PLANE.match(r["plane"]):
            d = devs.setdefault(r["plane"], DeviceTrace(r["plane"], [], []))
            if r["line"] == OPS_LINE:
                d.ops.append(ev)
            elif r["line"] == ASYNC_LINE:
                d.async_ops.append(ev)
        elif r["name"].startswith(SPAN_PREFIX):
            spans.append(ev)
    return Trace(devices=[devs[k] for k in sorted(devs)], spans=spans,
                 rows=rows)


def to_rows(pb_path: str) -> List[Dict]:
    """The events of an `.xplane.pb` the readers use, as plain rows:
    every device operation, asynchronous operation and executed program
    (`XLA Modules`), the benchmark's host spans, and the program's own
    `fdt.*` spans with their thread (`<line name>#<position in the
    plane>`) and stats."""
    from jax.profiler import ProfileData
    rows: List[Dict] = []
    for plane in ProfileData.from_file(pb_path).planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        for k, line in enumerate(plane.lines):
            if on_device and line.name not in (OPS_LINE, ASYNC_LINE,
                                               MODULES_LINE):
                continue
            for e in line.events:
                ours = e.name.startswith(PROGRAM_PREFIX)
                if not on_device and not ours \
                        and not e.name.startswith(SPAN_PREFIX):
                    continue
                row = {"plane": plane.name, "line": line.name,
                       "name": e.name, "start_ns": e.start_ns,
                       "dur_ns": e.duration_ns}
                if ours:
                    row["thread"] = f"{line.name}#{k}"
                    row["stats"] = {
                        str(a): (b if isinstance(b, (int, float, str))
                                 else str(b)) for a, b in e.stats}
                rows.append(row)
    return rows


def read(trace_dir: str) -> Trace:
    pbs = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not pbs:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_events(to_rows(pbs[-1]))


class capture:
    """`with capture(dir):` records a profiler trace into a fresh `dir`."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir

    def __enter__(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()


def span(name: str):
    """A host span of the benchmark's own, on the profiler's clock."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


# -- reductions -------------------------------------------------------------

def short_name(hlo: str) -> str:
    """`%fusion.7 = ... fusion(...)` -> `fusion.7`; Mosaic calls keep a
    marker so that a breakdown shows them as kernels."""
    m = re.match(r"^%?([^\s=]+)", hlo)
    name = m.group(1) if m else hlo[:40]
    if 'custom_call_target="tpu_custom_call"' in hlo:
        name += "[mosaic]"
    return name


def busy_intervals(dev: DeviceTrace, window: Interval) -> List[Interval]:
    return union(clip(as_intervals(dev.ops), window))


def matching(dev: DeviceTrace, pattern: str, window: Interval,
             with_async: bool = False) -> List[Interval]:
    rx = re.compile(pattern)
    evs = [e for e in dev.ops if rx.search(e[0])]
    if with_async:
        evs += [e for e in dev.async_ops if rx.search(e[0])]
    return clip(as_intervals(evs), window)


def busy_seconds(trace: Trace, window: Interval) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    return sum(measure(busy_intervals(d, window))
               for d in trace.devices) / len(trace.devices) / 1e9


def breakdown(trace: Trace, window: Interval, top: int = 10,
              gaps_top: int = 5) -> Dict[str, List]:
    """The device operations with most time (summed over devices and
    occurrences, by short name) and the longest idle gaps of the first
    device, each named by the benchmark span the host was in."""
    totals: Dict[str, float] = {}
    for d in trace.devices:
        for name, s, dur in d.ops:
            if s + dur > window[0] and s < window[1]:
                k = short_name(name)
                totals[k] = totals.get(k, 0.0) + dur
    n = max(len(trace.devices), 1)
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    out_gaps = []
    if trace.devices:
        inner = [s for s in trace.spans if s[0] != "bench.window"]
        for a, b in sorted(gaps(busy_intervals(trace.devices[0], window),
                                window), key=lambda g: g[0] - g[1])[:gaps_top]:
            mid = (a + b) / 2
            host = [nm for nm, s, d in inner if s <= mid <= s + d]
            out_gaps.append([host[-1] if host else "bench.window",
                             (b - a) / 1e9])
    return {"device_ops": [[k, v / n / 1e9] for k, v in ops],
            "idle_gaps": out_gaps}


def dump_rows(trace: Trace, out_path: str, limit: int = 150000) -> None:
    """Keep the trace's rows beside the run's other output (gzip JSON);
    long HLO texts are cut to their head and tail, which hold the name
    and the custom-call target. Where the device's operations outnumber
    `limit`, the first and the last half of them are kept, with every
    host span and executed program."""
    ops = [i for i, r in enumerate(trace.rows)
           if r["line"] in (OPS_LINE, ASYNC_LINE)]
    drop = set(ops[limit // 2:len(ops) - limit // 2]) \
        if len(ops) > limit else set()
    rows = [dict(r, name=(r["name"] if len(r["name"]) <= 300 else
                          r["name"][:120] + " ... " + r["name"][-170:]))
            for i, r in enumerate(trace.rows) if i not in drop]
    with gzip.open(out_path, "wt") as f:
        json.dump(rows, f)
