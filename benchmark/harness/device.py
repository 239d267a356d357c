"""The device the run is on: the chip check, the one table of peaks,
compile accounting and the `device` object of the result line."""
from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks() -> Dict[str, Dict[str, Any]]:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        return json.load(f)


def peaks_for(device_kind: str, rehearse: bool = False) -> Dict[str, Any]:
    """Exact `device_kind` lookup; a kind the table lacks is an error,
    never a default."""
    table = load_peaks()
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/harness/peaks.json ({sorted(table)})")
    row = table[device_kind]
    if row.get("rehearsal_only") and not rehearse:
        raise KeyError(f"peaks for {device_kind!r} are a rehearsal "
                       "placeholder")
    return row


def require_chips(chips: int, rehearse: bool) -> Dict[str, Any]:
    """What jax found. Anything but `chips` TPU devices ends the run with
    a non-zero status and no result line, unless this is a rehearsal."""
    import jax
    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    print(f"device: {found}", flush=True)
    if rehearse:
        if found["platform"] != "cpu":
            print("benchmark: --rehearse is a CPU run", file=sys.stderr)
            sys.exit(3)
        if found["count"] < chips:
            print(f"benchmark: the rehearsal needs {chips} virtual CPU "
                  f"devices, found {found['count']}", file=sys.stderr)
            sys.exit(3)
        return found
    if found["platform"] != "tpu" or found["count"] != chips:
        print(f"benchmark: this cell needs {chips} TPU chip(s); jax found "
              f"{found['count']} {found['platform']!r} device(s). No "
              "result line is printed (use --rehearse on a CPU).",
              file=sys.stderr)
        sys.exit(3)
    return found


PEAK_KEYS = ("peak_bytes_in_use", "peak_bytes_reserved")


def fullest_memory_stats(devices) -> Dict[str, Any]:
    """`memory_stats()` of the device with the highest allocator peak."""
    best: Dict[str, Any] = {}
    for d in devices:
        s = d.memory_stats() or {}
        if s.get("peak_bytes_in_use", 0) >= best.get("peak_bytes_in_use", -1):
            best = s
    return best


def memory_peak_bytes(devices) -> int:
    """Peak bytes on the fullest device: the allocator's peak plus the
    peak of the region the runtime reserves for the loaded programs'
    own temporaries, which the allocator's count leaves out (0 where
    the backend does not report it, as on the CPU)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, sum(int(stats.get(k, 0)) for k in PEAK_KEYS))
    return peak


def live_bytes(devices) -> int:
    """Bytes alive now on the fullest device: the allocator's
    `bytes_in_use`, or, where the backend reports none (the CPU), the
    sum of the live arrays'."""
    import jax
    best = 0
    for d in devices:
        stats = d.memory_stats() or {}
        if "bytes_in_use" in stats:
            best = max(best, int(stats["bytes_in_use"]))
        else:
            best = max(best, sum(int(a.nbytes) for a in jax.live_arrays()
                                 if d in a.devices()))
    return best


def configure_cache(bench_dir: str) -> str:
    """jax's persistent compilation cache at a fixed path inside the
    checkout (`benchmark/out/jax_cache`), without a size cap, whatever
    the environment says: a cache that evicts, or lives outside the
    checkout, makes every run of a cell a cold one. Must run before the
    process's first compile."""
    import jax
    path = os.path.join(bench_dir, "out", "jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileMeter:
    """Counts jax's backend compilations (its own monitoring events), so
    that a run can assert that nothing compiled inside the window."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    HIT_EVENT = "/jax/compilation_cache/cache_hits"
    MISS_EVENT = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_: Any) -> None:
        if event == self.COMPILE_EVENT:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_: Any) -> None:
        if event == self.HIT_EVENT:
            self.hits += 1
        elif event == self.MISS_EVENT:
            self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compiles": self.compiles, "seconds": self.seconds,
                "hits": self.hits, "misses": self.misses}
