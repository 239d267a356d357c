"""Per-layer metrics: one general reader per declarative source kind.

A metric is a file `layer_metrics/<name>.json`; its `read` object says
where the number comes from. A reader that finds nothing to read returns
None, and the harness leaves that metric out of the result line.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import flops, trace as tr


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


READERS: Dict[str, Callable[[Dict[str, Any], Window], Optional[float]]] = {
    "device_busy": _device_busy,
    "device_events": _device_events,
    "host_window": _host_window,
    "result_field": _result_field,
    "counter_ratio": _counter_ratio,
    "memory_stats": _memory_stats,
    "required_ops": _required_ops,
}


def read_all(metrics: List[Dict[str, Any]], w: Window) -> Dict[str, Any]:
    """{name: {"value", "unit"}} for every metric whose reader found
    something to read."""
    out = {}
    for m in metrics:
        f = m["file"]
        v = READERS[f["read"]["from"]](f["read"], w)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
