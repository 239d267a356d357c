"""The program's own spans in a profiler capture, beside the device.

`flaxdiff_tpu.telemetry.tracing.span` opens every span of the program
as a `jax.profiler.TraceAnnotation` named `fdt.<name>`, so a capture
holds them on the clock of the device planes. This module picks them
out of an `.xplane.pb`, computes a span's self time, and lays the spans
of the dispatching thread over the first device's idle gaps: what was
the host doing while the chip waited.

Where host threads land (read off v5e and CPU captures, PR 24): every
Python thread is its own line of the plane `/host:CPU`, and every one
of those lines is named after the PROCESS (`python3` where the run was
started as `python3`, `python` in PR 23's probe), whatever
`threading.Thread(name=)` says; the runtime's own threads have names
of their own or none. So a thread is known here by its line's position
in the plane (`python3#16`), and the dispatching thread by the spans it
holds: the line with `fdt.serve.round` (the scheduler's
`serving-dispatch`) or `fdt.fit.step` (`fit`'s caller). `harness/trace.py` keeps `bench.*`
host events only and names a gap by them; it is not edited by a PR
that is not a `benchmark` PR, so this reader stands beside it and
`benchmark/spans.py` prints what it reads (PERF.md, section 7).

A metric over spans is a `read` object, as the other kinds' are:

  {"from": "program_span", "reduce": "ms_per" | "self_ms_per",
   "match": [names], "per": name | "step"}
  {"from": "program_span", "reduce": "share_of_idle_pct",
   "match": [names]}            # [] = under no span at all: unattributed

`METRICS` holds the ones ISSUE 24 names; `reduce` computes one and
returns None where a capture holds no `fdt.*` span (the parent
commit's).
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Any, Dict, List, Optional, Tuple

from . import trace as tr

PREFIX = "fdt."
MODULES_LINE = "XLA Modules"
# the spans that open a loop turn of a dispatching thread, and the
# program each launching span starts on the device
DISPATCH_MARKS = ("serve.round", "fit.step", "fit.host")
LAUNCHES = {"serve.launch": "sampler_", "fit.host": "train_step"}


@dataclasses.dataclass
class Span:
    name: str                 # without the `fdt.` prefix
    thread: str               # `<line name>#<position in the plane>`
    start: float              # ns, the profiler's clock
    dur: float
    stats: Dict[str, Any]

    @property
    def end(self) -> float:
        return self.start + self.dur


# -- reading ----------------------------------------------------------------

def rows_of(pb_path: str) -> List[Dict]:
    """`trace.to_rows` and, besides: the `fdt.*` host events with their
    thread and stats, and the devices' `XLA Modules` lines."""
    from jax.profiler import ProfileData
    rows: List[Dict] = []
    for plane in ProfileData.from_file(pb_path).planes:
        on_device = bool(tr.DEVICE_PLANE.match(plane.name))
        for k, line in enumerate(plane.lines):
            if on_device and line.name not in (
                    tr.OPS_LINE, tr.ASYNC_LINE, MODULES_LINE):
                continue
            for e in line.events:
                ours = e.name.startswith(PREFIX)
                if not on_device and not ours \
                        and not e.name.startswith(tr.SPAN_PREFIX):
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


def from_rows(rows: List[Dict]) -> List[Span]:
    out = [Span(r["name"][len(PREFIX):], r.get("thread", r["line"]),
                float(r["start_ns"]), float(r["dur_ns"]),
                dict(r.get("stats") or {}))
           for r in rows
           if r["name"].startswith(PREFIX)
           and not tr.DEVICE_PLANE.match(r["plane"])]
    return sorted(out, key=lambda s: (s.start, -s.dur))


def modules_of(rows: List[Dict]) -> List[tr.Event]:
    """The executed programs of the first device, in order."""
    planes = sorted({r["plane"] for r in rows
                     if tr.DEVICE_PLANE.match(r["plane"])})
    if not planes:
        return []
    return sorted(((r["name"], float(r["start_ns"]), float(r["dur_ns"]))
                   for r in rows if r["plane"] == planes[0]
                   and r["line"] == MODULES_LINE), key=lambda e: e[1])


def read_rows(trace_dir: str) -> List[Dict]:
    pbs = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not pbs:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return rows_of(pbs[-1])


def split(rows: List[Dict]
          ) -> Tuple[tr.Trace, List[Span], List[tr.Event]]:
    """(the harness's trace, the program's spans, the first device's
    executed programs) of one capture's rows."""
    return tr.from_events(rows), from_rows(rows), modules_of(rows)


# -- spans ------------------------------------------------------------------

def dispatch_thread(spans: List[Span]) -> Optional[str]:
    """The thread that launches device work: the one holding most of
    the spans that open a loop turn."""
    count: Dict[str, int] = {}
    for s in spans:
        if s.name in DISPATCH_MARKS:
            count[s.thread] = count.get(s.thread, 0) + 1
    return max(count, key=count.get) if count else None


def children_cover(spans: List[Span]) -> List[List[tr.Interval]]:
    """For each span, the intervals its direct children on the same
    thread cover (spans nest properly on a thread)."""
    cover: List[List[tr.Interval]] = [[] for _ in spans]
    open_: Dict[str, List[int]] = {}
    for i, s in enumerate(spans):          # sorted by (start, -dur)
        stack = open_.setdefault(s.thread, [])
        while stack and spans[stack[-1]].end <= s.start:
            stack.pop()
        if stack:
            cover[stack[-1]].append((s.start, s.end))
        stack.append(i)
    return cover


def self_intervals(spans: List[Span]) -> List[List[tr.Interval]]:
    """For each span, its own interval less what its children cover."""
    return [tr.subtract([(s.start, s.end)], c)
            for s, c in zip(spans, children_cover(spans))]


def _inside(spans: List[Span], window: tr.Interval) -> List[int]:
    return [i for i, s in enumerate(spans)
            if s.end > window[0] and s.start < window[1]]


def ms_per(spans: List[Span], window: tr.Interval, match: List[str],
           per: str, steps: int = 0, self_time: bool = False
           ) -> Optional[float]:
    """Milliseconds of the matching spans inside the window (their self
    time, if asked) over `steps` where `per` is "step"; where `per` is a
    span's name, over the whole turns the window holds: from the start
    of its first `per` span to the start of its last, n - 1 turns for n
    such spans. A capture cuts the last turn short wherever it stops
    (a span still open then is not recorded at all), and a turn counted
    with half its parts would read too low."""
    if per != "step":
        marks = sorted(s.start for s in spans if s.name == per
                       and window[0] <= s.start < window[1])
        if len(marks) < 2:
            return None
        window, steps = (marks[0], marks[-1]), len(marks) - 1
    selfs = self_intervals(spans) if self_time else None
    total, found = 0.0, False
    for i in _inside(spans, window):
        if spans[i].name in match:
            found = True
            ivs = selfs[i] if selfs is not None \
                else [(spans[i].start, spans[i].end)]
            total += tr.measure(tr.clip(ivs, window))
    return total / 1e6 / steps if found and steps else None


def idle_of(trace: tr.Trace, window: tr.Interval) -> List[tr.Interval]:
    """The first device's idle gaps inside the window."""
    if not trace.devices:
        return []
    return tr.gaps(tr.busy_intervals(trace.devices[0], window), window)


def share_of_idle_pct(spans: List[Span], idle: List[tr.Interval],
                      match: List[str]) -> Optional[float]:
    """The share of the device's idle time that lies inside the
    matching spans of the dispatching thread; with no name to match,
    the share under no span of that thread at all."""
    thread = dispatch_thread(spans)
    total = tr.measure(idle)
    if thread is None or not total:
        return None
    mine = [(s.start, s.end) for s in spans if s.thread == thread
            and (not match or s.name in match)]
    under = tr.measure(idle) - tr.measure(tr.subtract(idle, mine))
    return 100.0 * (under if match else total - under) / total


def idle_by_span_pct(spans: List[Span], idle: List[tr.Interval]
                     ) -> Dict[str, float]:
    """The idle time put down to the INNERMOST span of the dispatching
    thread open in it (a span's self intervals), by name; what is under
    no span is `unattributed`. The parts sum to 100."""
    thread = dispatch_thread(spans)
    total = tr.measure(idle)
    if thread is None or not total:
        return {}
    out: Dict[str, float] = {}
    covered = 0.0
    for s, ivs in zip(spans, self_intervals(spans)):
        if s.thread != thread:
            continue
        hit = total - tr.measure(tr.subtract(idle, ivs))
        if hit:
            out[s.name] = out.get(s.name, 0.0) + hit
            covered += hit
    out["unattributed"] = max(total - covered, 0.0)
    return {k: 100.0 * v / total for k, v in
            sorted(out.items(), key=lambda kv: -kv[1])}


def named_gaps(spans: List[Span], idle: List[tr.Interval],
               top: int = 5) -> List[List]:
    """The longest idle gaps, each named by the innermost span open at
    its midpoint: the dispatching thread's before any other's."""
    thread = dispatch_thread(spans)
    out = []
    for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        open_ = [s for s in spans if s.start <= mid <= s.end]
        mine = [s for s in open_ if s.thread == thread] or open_
        out.append([PREFIX + mine[-1].name if mine else "no span",
                    (b - a) / 1e9])
    return out


def clock_offset_ms(spans: List[Span], modules: List[tr.Event],
                    window: tr.Interval, slack_ns: float = 5e6
                    ) -> Optional[float]:
    """The least, over the launches inside the window, of: the start of
    the launched program on the device less the start of its launching
    span on the host. It bounds the host-to-device clock offset plus
    the shortest dispatch latency; an idle gap shorter than it is
    attributed with that much doubt."""
    best = None
    for i in _inside(spans, window):
        s = spans[i]
        prog = LAUNCHES.get(s.name)
        if prog is None:
            continue
        for name, start, _ in modules:
            if prog in name and start >= s.start - slack_ns:
                d = start - s.start
                best = d if best is None else min(best, d)
                break
    return None if best is None else best / 1e6


# -- the metrics ISSUE 24 names ---------------------------------------------

def _m(reduce, match, per=None):
    r = {"from": "program_span", "reduce": reduce, "match": match}
    if per is not None:
        r["per"] = per
    return r


SERVE_HOST = ["serve.admit", "serve.round", "serve.finalize",
              "serve.backpressure"]
SERVE_METRICS: Dict[str, Dict[str, Any]] = {
    "serve.round_host_ms": _m("ms_per", SERVE_HOST, "serve.round"),
    "serve.stack_ms": _m("ms_per", ["serve.stack"], "serve.round"),
    "serve.unstack_ms": _m("ms_per", ["serve.unstack"], "serve.round"),
    "serve.launch_ms": _m("ms_per", ["serve.launch"], "serve.round"),
    "serve.fetch_ms": _m("ms_per", ["serve.fetch"], "serve.fetch"),
    "device.idle_in_stack_pct.gen": _m(
        "share_of_idle_pct", ["serve.stack", "serve.unstack"]),
    "device.idle_unattributed_pct.gen": _m("share_of_idle_pct", []),
}
FIT_METRICS: Dict[str, Dict[str, Any]] = {
    "fit.dispatch_ms": _m("ms_per", ["fit.host"], "step"),
    "fit.data_wait_ms": _m("ms_per", ["fit.data_wait"], "step"),
}
METRICS = {**SERVE_METRICS, **FIT_METRICS}


def reduce(read: Dict[str, Any], spans: List[Span], trace: tr.Trace,
           window: Optional[tr.Interval], steps: int) -> Optional[float]:
    if not spans or window is None:
        return None
    how = read["reduce"]
    if how in ("ms_per", "self_ms_per"):
        return ms_per(spans, window, read["match"], read["per"], steps,
                      self_time=how == "self_ms_per")
    if how == "share_of_idle_pct":
        return share_of_idle_pct(spans, idle_of(trace, window),
                                 read["match"])
    raise ValueError(f"program_span: unknown reduce {how!r}")


def report(trace: tr.Trace, spans: List[Span], modules: List[tr.Event],
           steps: int = 0) -> Dict[str, Any]:
    """Everything this module reads from one capture."""
    window = trace.window()
    if window is None or not spans:
        return {"spans": len(spans), "metrics": {}}
    idle = idle_of(trace, window)
    ins = [spans[i] for i in _inside(spans, window)]
    counts: Dict[str, int] = {}
    for s in ins:
        counts[s.name] = counts.get(s.name, 0) + 1
    turns = max((counts.get(m, 0) for m in DISPATCH_MARKS), default=0)
    serving = bool(counts.get("serve.round"))
    metrics = {}
    for name, rd in (SERVE_METRICS if serving else FIT_METRICS).items():
        v = reduce(rd, spans, trace, window, steps)
        if v is not None:
            metrics[name] = v
    per = "serve.round" if serving else "step"
    table = {}
    for n in sorted(counts):
        for key, st in ((n, False), (n + ".self", True)):
            v = ms_per(spans, window, [n], per, steps or turns, st)
            if v is not None:
                table[key] = v
    off = clock_offset_ms(spans, modules, window)
    return {
        "spans": len(ins), "per": per, "turns": turns,
        "spans_per_turn": len(ins) / turns if turns else None,
        "window_s": (window[1] - window[0]) / 1e9,
        "idle_s": tr.measure(idle) / 1e9,
        "clock_offset_ms": off,
        "clock_offset_note": (
            "least launch-to-device-start over the window: clock offset "
            "plus dispatch latency; gaps shorter than it are attributed "
            "with that much doubt"),
        "metrics": metrics, "ms_per_turn": table,
        "idle_by_span_pct": idle_by_span_pct(spans, idle),
        "idle_gaps": named_gaps(spans, idle),
    }
