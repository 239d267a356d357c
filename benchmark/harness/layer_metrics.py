"""Per-layer metrics: one general reader per declarative source kind.

A metric is a file `layer_metrics/<name>.json`; its `read` object says
where the number comes from. A reader that finds nothing to read returns
None, and the harness leaves that metric out of the result line. A reader
may return {"value", ...}: the other keys (which bound a roofline share
stands on) go into the result line's `notes`.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import flops, program_spans as ps, spec, trace as tr


@dataclasses.dataclass
class Window:
    """What the traced window left behind for the readers."""
    trace: Optional[tr.Trace]
    interval: Optional[tr.Interval]      # on the profiler's clock, ns
    wall_s: float                        # host clock, barrier to barrier
    steps: int                           # train steps / denoise steps
    images: int                          # images completed in the window
    chips: int
    results: List[Any]                   # SampleResult objects (serving)
    counters: Dict[str, float]           # program counters, window deltas
    memory: Dict[str, Any]               # memory_stats of the fullest chip
    peaks: Dict[str, Any]
    cfg: Dict[str, Any]
    evals_per_row_step: int = 1          # 2 where requests are guided


def _device_busy(read, w: Window) -> Optional[float]:
    if w.trace is None or not w.trace.devices or w.interval is None:
        return None
    busy_s = tr.busy_seconds(w.trace, w.interval)
    how = read["reduce"]
    if how == "idle_pct":
        span_s = (w.interval[1] - w.interval[0]) / 1e9
        return 100.0 * (1.0 - busy_s / span_s) if span_s > 0 else None
    if how == "ms_per_step":
        return 1e3 * busy_s / w.steps if w.steps else None
    raise ValueError(f"device_busy: unknown reduce {how!r}")


def _device_events(read, w: Window) -> Optional[float]:
    if w.trace is None or not w.trace.devices or w.interval is None:
        return None
    how = read["reduce"]
    vals = []
    for d in w.trace.devices:
        busy = tr.busy_intervals(d, w.interval)
        if how in ("share_of_busy_pct", "collective_share_of_busy_pct"):
            # collectives also count from their start to their done
            hit = tr.matching(d, read["match"], w.interval,
                              with_async=how.startswith("collective"))
            b = tr.measure(busy)
            vals.append(100.0 * tr.measure(hit) / b if b else 0.0)
        elif how == "exposed_ms_per_step":
            rx = re.compile(read["match"])
            coll = tr.matching(d, read["match"], w.interval, with_async=True)
            compute = tr.clip(tr.as_intervals(
                [e for e in d.ops if not rx.search(e[0])]), w.interval)
            exposed = tr.measure(tr.subtract(coll, compute))
            vals.append(exposed / 1e6 / w.steps if w.steps else 0.0)
        else:
            raise ValueError(f"device_events: unknown reduce {how!r}")
    return float(np.mean(vals)) if vals else None


def _host_window(read, w: Window) -> Optional[float]:
    if not w.steps or w.wall_s <= 0:
        return None
    return 1e3 * w.wall_s / w.steps


def _result_field(read, w: Window) -> Optional[float]:
    vals = [getattr(r, read["field"]) for r in w.results]
    if not vals:
        return None
    return float(np.percentile(np.asarray(vals, np.float64),
                               read["percentile"]))


def _counter_ratio(read, w: Window) -> Optional[float]:
    num = w.counters.get(read["numerator"])
    den = w.counters.get(read["denominator"]) if "denominator" in read \
        else 1.0
    if num is None or not den:
        return None
    return float(num) / float(den)


def _memory_stats(read, w: Window) -> Optional[float]:
    vals = [w.memory.get(k) for k in read["keys"]]
    if any(v is None for v in vals):
        return None
    return 100.0 * float(sum(vals)) / float(w.peaks[read["of"]])


def _required_ops(read, w: Window) -> Optional[float]:
    if w.wall_s <= 0 or not w.images:
        return None
    per_chip = w.images / w.wall_s / w.chips
    return 100.0 * flops.train_flops_per_image(w.cfg) * per_chip \
        / float(w.peaks[read["of"]])


def _whole_rounds(read, w: Window):
    """The traced window's serving rounds on the device's clock, whole
    periods only: (rounds, t0, t1), from the start of the first paired
    round program to the start of the last, the last left out. Neither
    the phase between the host's window and the device's rounds nor a
    program cut by the capture's start or end enters."""
    if w.trace is None or not w.trace.devices or w.interval is None:
        return None
    rounds = [r for r in ps.device_rounds(
        ps.from_rows(w.trace.rows), ps.modules_of(w.trace.rows),
        read["round"], int(read["rounds_ahead"]))
        if w.interval[0] <= r.start < w.interval[1]]
    if rounds and not rounds[0].whole:
        rounds = rounds[1:]
    if len(rounds) < 2:
        return None
    return rounds[:-1], rounds[0].start, rounds[-1].start


def _device_rounds(read, w: Window) -> Optional[float]:
    """Device time of the programs that `match`, over the steps the
    rounds ran."""
    found = _whole_rounds(read, w)
    if found is None:
        return None
    rounds, t0, t1 = found
    if read["reduce"] != "ms_per_step":
        raise ValueError(f"device_rounds: unknown reduce {read['reduce']!r}")
    rx = re.compile(read["match"])
    busy = sum(d for n, s, d in ps.modules_of(w.trace.rows)
               if rx.search(n) and t0 <= s < t1)
    steps = sum(r.steps for r in rounds)
    return busy / 1e6 / steps if steps else None


def _evaluations(read, w: Window):
    """(model evaluations of one row, seconds) the cell's traffic asked
    for in the traced window. Serving (`round` names the round program):
    over whole rounds on the device's clock, the real rows' live steps
    (rows x steps of each round, scaled by the `live` over `run`
    counters where the file names them) and one terminal evaluation for
    every row finalised, twice where requests are guided; padding rows
    count nothing. Otherwise one evaluation for each image of the
    window."""
    if "round" not in read:
        if w.interval is None or not w.images:
            return None
        return float(w.images), w.interval[0], w.interval[1]
    found = _whole_rounds(read, w)
    if found is None:
        return None
    rounds, t0, t1 = found
    live = 1.0
    if "live" in read and w.counters.get(read["run"]):
        live = w.counters[read["live"]] / w.counters[read["run"]]
    evals = w.evals_per_row_step * (
        live * sum(r.rows * r.steps for r in rounds)
        + sum(r.finished for r in rounds))
    return float(evals), t0, t1


def _served_ops(read, w: Window) -> Optional[float]:
    """The whole step's share of the chip's peak while serving: required
    operations of the evaluations the traffic asked for, over the
    device's time for them and the peak."""
    found = _evaluations(read, w)
    if found is None:
        return None
    evals, t0, t1 = found
    return 100.0 * flops.forward_flops(w.cfg) * evals \
        / ((t1 - t0) / 1e9 * w.chips) / float(w.peaks[read["of"]])


def _kernel_roofline(read, w: Window):
    """A named kernel's share of its roofline: the least time the chip
    could take for the kernel's required operations and bytes (the
    larger of operations over the peak rate and bytes over the peak
    bandwidth; `kernel_costs` beside the family's reference, per
    evaluation of one row), over the summed device time of the events
    that `match`. A share over 100 means the costs are counted too high
    or the events leave out part of the work: an error, never clipped."""
    found = _evaluations(read, w)
    if found is None or w.trace is None:
        return None
    evals, t0, t1 = found
    cost = flops.kernel_costs(w.cfg)[read["kernel"]]
    rx = re.compile(read["match"])
    took = sum(d for dev in w.trace.devices for n, s, d in dev.ops
               if rx.search(n) and t0 <= s < t1) / 1e9
    if not took:
        return None
    need = {k: cost[k] * evals / float(w.peaks[read["of"][k]])
            for k in ("flops", "bytes")}
    bound = max(need, key=need.get)
    share = 100.0 * need[bound] / took
    if share > 100.0:
        raise ValueError(
            f"kernel_roofline: {read['kernel']} reads {share:.1f}% of its "
            f"roofline ({evals:.0f} evaluations, {took:.6f} s in events "
            f"matching {read['match']!r}): the operations or bytes are "
            "counted too high, or the events leave out part of the work")
    return {"value": share,
            "bound": "operations" if bound == "flops" else "bytes"}


READERS: Dict[str, Callable[[Dict[str, Any], Window], Any]] = {
    "device_busy": _device_busy,
    "device_events": _device_events,
    "host_window": _host_window,
    "result_field": _result_field,
    "counter_ratio": _counter_ratio,
    "memory_stats": _memory_stats,
    "required_ops": _required_ops,
    "device_rounds": _device_rounds,
    "served_ops": _served_ops,
    "kernel_roofline": _kernel_roofline,
}


def counters_named(metrics: List[Dict[str, Any]]) -> tuple:
    """The program counters a cell's per-layer files read."""
    return tuple(dict.fromkeys(
        m["file"]["read"][k] for m in metrics for k in spec.COUNTER_KEYS
        if k in m["file"]["read"]))


def read_all(metrics: List[Dict[str, Any]], w: Window,
             notes: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """{name: {"value", "unit"}} for every metric whose reader found
    something to read; what a reader says besides its value goes into
    `notes[name]`."""
    out = {}
    for m in metrics:
        f = m["file"]
        v = READERS[f["read"]["from"]](f["read"], w)
        if isinstance(v, dict):
            if notes is not None:
                notes[m["name"]] = {k: x for k, x in v.items()
                                    if k != "value"}
            v = v["value"]
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
