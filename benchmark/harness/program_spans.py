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
`serving-dispatch`) or `fdt.fit.step` (`fit`'s caller). `harness/trace.py`
keeps these rows since PR 34; `benchmark/spans.py` prints what this
module reads from them, and `device_rounds` is what the serving cells'
device-clock metrics are reduced from.

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

PREFIX = tr.PROGRAM_PREFIX
MODULES_LINE = tr.MODULES_LINE
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

rows_of = tr.to_rows


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


# -- rounds on the device's clock --------------------------------------------

ROUND_SPAN, FINALIZE_SPAN = "serve.round", "serve.finalize"


@dataclasses.dataclass
class Round:
    """One serving round as the device ran it."""
    start: float              # its program's start on the device, ns
    end: float
    steps: int                # the round's own length (`serve.round`)
    rows: int                 # real rows in it
    finished: int             # rows finalised after it: a terminal
    #                           evaluation each
    whole: bool = True        # False for the capture's first or last
    #                           module, which it may have cut short


class PairingError(RuntimeError):
    """A capture's `serve.round` spans cannot be paired with the round
    programs they launched, or can be in two ways that differ."""


# two runs of one round program at the same bucket and steps take the
# same time on the device to this share (62.35 / 62.34 and 230.57 /
# 230.57 ms on `dit-xl-2.generate`, my chip run, PR 34); runs of
# different steps differ by more
ROUND_REPEATS = 0.02


def _unlike(kept: List[Tuple[int, int, float]]) -> str:
    """Why the (bucket, steps, device ns) of some pairing's rounds cannot
    all be true, or "": at one bucket, rounds of the same steps take the
    same time and a round of more steps takes longer."""
    for i, (bucket, steps, took) in enumerate(kept):
        for bucket2, steps2, took2 in kept[i + 1:]:
            if bucket2 != bucket:
                continue
            alike = abs(took - took2) <= ROUND_REPEATS * max(took, took2)
            if (steps == steps2) != alike \
                    or (not alike and (steps < steps2) != (took < took2)):
                return (f"rounds of {steps} and {steps2} steps at bucket "
                        f"{bucket} against programs of {took / 1e6:.2f} "
                        f"and {took2 / 1e6:.2f} ms")
    return ""


def device_rounds(spans: List[Span], modules: List[tr.Event], program: str,
                  rounds_ahead: int, slack_ns: float = 5e6) -> List[Round]:
    """The capture's `serve.round` spans, each paired with the round
    program (a module named like `program`) it launched.

    Spans and programs are both in launch order, so a pairing is one
    shift between the two lists; the capture cuts both at arbitrary
    points, so the shift has to be found. A shift is possible where, for
    every pair it makes (each bound with `slack_ns` for the difference
    between the two clocks): the program starts no earlier than its span
    opens; the device is never idle between the span's end and the
    program's start (a launched program starts as soon as the device is
    free); and, the dispatch thread running at most `rounds_ahead`
    rounds ahead of the device (the metric's file states it: the
    benchmark reads no constant of the program's), the program has ended
    when the span `rounds_ahead + 1` rounds later opens. With the device
    saturated round k's span opens just as round k-1's program starts,
    so times alone leave two shifts. The device's own time chooses: at
    one bucket, whole programs of the same steps take the same time to
    `ROUND_REPEATS` and one of more steps takes longer (`_unlike`); the
    capture's first and last module may be cut short, are not held to
    that, and come back with `whole` false (the first's end and the
    last's start are still right). Raises `PairingError` where spans and
    programs are there and no shift is possible, or where two are and
    give some whole program different steps or rows: a metric read from
    a guess would be wrong by up to the longest round over the
    shortest. A capture with fewer
    than two round spans or programs has nothing to read: []. `finished`
    counts the rows of the `serve.finalize` spans the thread opened
    before its next round."""
    import re
    rx = re.compile(program)
    progs = [(i, m) for i, m in enumerate(modules) if rx.search(m[0])]
    rounds = [s for s in spans if s.name == ROUND_SPAN]
    if len(progs) < 2 or len(rounds) < 2:
        return []
    busy = tr.union(tr.as_intervals(modules))

    def pairs(shift):
        return [(k, k + shift) for k in range(len(rounds))
                if 0 <= k + shift < len(progs)]

    def whole(j) -> bool:
        return 0 < progs[j][0] < len(modules) - 1

    def refused(shift) -> str:
        kept = []
        for k, j in pairs(shift):
            _, start, dur = progs[j][1]
            r, later = rounds[k], k + rounds_ahead + 1
            if start < r.start - slack_ns:
                return "a program would start before its span opens"
            if later < len(rounds) \
                    and start + dur > rounds[later].start + slack_ns:
                return ("a program would still run when the span "
                        f"{rounds_ahead + 1} rounds later opens")
            since = max(r.end + slack_ns, busy[0][0])
            if tr.measure(tr.gaps(busy, (since, start))) > slack_ns:
                return ("the device would idle between a round's launch "
                        "and its program")
            if whole(j):
                kept.append((int(r.stats.get("bucket", 1)),
                             int(r.stats["steps"]), dur))
        return _unlike(kept)

    why: Dict[str, List[int]] = {}
    for sh in range(1 - len(rounds), len(progs)):
        why.setdefault(refused(sh), []).append(sh)
    left = why.pop("", [])
    if not left:
        raise PairingError(
            f"{len(rounds)} {ROUND_SPAN} spans and {len(progs)} programs "
            f"like {program!r}: no pairing in launch order is possible ("
            + "; ".join(f"shifts {shs}: {no}" for no, shs in why.items())
            + ")")
    said: Dict[int, Tuple[int, int, int]] = {}
    for sh in left:
        for k, j in pairs(sh):
            if not whole(j):
                continue
            what = (sh, int(rounds[k].stats["steps"]),
                    int(rounds[k].stats["rows"]))
            if said.setdefault(j, what)[1:] != what[1:]:
                raise PairingError(
                    f"shifts {said[j][0]} and {sh} both pair the "
                    f"{ROUND_SPAN} spans with the programs like "
                    f"{program!r}, and give the program at "
                    f"{progs[j][1][1] / 1e6:.2f} ms (steps, rows) "
                    f"{said[j][1:]} and {what[1:]}")
    shift = max(left, key=lambda sh: (len(pairs(sh)), -sh))
    out: List[Round] = []
    for k, j in pairs(shift):
        r = rounds[k]
        until = rounds[k + 1].start if k + 1 < len(rounds) else float("inf")
        finished = sum(int(s.stats.get("rows", 0)) for s in spans
                       if s.name == FINALIZE_SPAN and s.thread == r.thread
                       and r.start <= s.start < until)
        _, start, dur = progs[j][1]
        out.append(Round(start, start + dur, int(r.stats["steps"]),
                         int(r.stats["rows"]), finished, whole(j)))
    return out


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
