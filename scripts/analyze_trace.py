#!/usr/bin/env python
"""Summarize a jax.profiler trace: device time by op family.

THIN SHIM: the parsing/attribution logic this script pioneered (the
r3 analysis — attention 35% of step, ~750 layout copies) now lives in
`flaxdiff_tpu/telemetry/devprof.py`, where the trainer's automated
profile windows use it to write `devprof.jsonl` evidence rows. This
CLI keeps the old flags and output format for hand-run captures, and
delegates every parsing decision to the library — plus two fixes the
old script silently lacked: truncated/corrupt captures are REPORTED
(`skipped_corrupt: ...`), and a capture with only host-side XLA events
(the CPU backend) is summarized with an explicit `host_xla` note
instead of being conflated with "no data".

Usage:
    python scripts/analyze_trace.py path/to/trace_dir
    python scripts/analyze_trace.py path/to/vm.trace.json.gz --steps 5
    python scripts/analyze_trace.py path/to/trace_dir --top 30 --raw

`--steps N` divides totals by N (pass the number of steps captured in
the trace window) so numbers read as ms/step. `--raw` lists individual
ops instead of family aggregates.
"""
from __future__ import annotations

import argparse
import collections
import sys

from flaxdiff_tpu.telemetry import devprof as _devprof

# re-exported for importers of the old module API
load_events = _devprof.load_events
device_pids = _devprof.device_pids


def family(name: str) -> str:
    """Strip the SSA counter: 'attn1.27' -> 'attn' (delegates to
    devprof.op_family)."""
    return _devprof.op_family(name)


def find_trace(path: str):
    """(path, parsed events or None): newest capture that actually has
    an attributable timeline — legacy signature kept for importers;
    corrupt captures are skipped here and REPORTED by main()."""
    hit, events, _skipped = _devprof.find_capture(path)
    return hit, events


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="trace dir or *.trace.json.gz file")
    ap.add_argument("--steps", type=int, default=1,
                    help="steps captured in the window (totals become "
                         "per-step)")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--raw", action="store_true",
                    help="per-op rows instead of family aggregates")
    args = ap.parse_args(argv)

    path, events, skipped = _devprof.find_capture(args.trace)
    for p in skipped:
        print(f"skipped_corrupt: {p} (truncated/unreadable capture)")
    if events is None:
        events = load_events(path)
    source, ops = _devprof.select_op_events(events)
    if source == "host_only":
        raise SystemExit(
            f"{path}: no device timeline (host_only capture — the trace "
            "window probably closed before any device work ran)")
    if source == "host_xla":
        print("host_xla: no device timeline; attributing host-side XLA "
              "op events (CPU backend capture)")

    agg = collections.Counter()
    cnt = collections.Counter()
    total = 0
    for e in ops:
        name = e.get("name", "?")
        key = name if args.raw else family(name)
        dur = e.get("dur", 0)
        agg[key] += dur
        cnt[key] += 1
        total += dur

    print(f"{path}")
    pids = device_pids(events)
    if pids:
        print(f"devices: {', '.join(pids.values())}")
    print(f"device op time: {total / 1e3 / args.steps:.2f} ms"
          + ("/step" if args.steps > 1 else ""))
    print(f"{'op family' if not args.raw else 'op':42} "
          f"{'ms' + ('/step' if args.steps > 1 else ''):>10} "
          f"{'%':>6} {'count':>8}")
    for key, dur in agg.most_common(args.top):
        print(f"{key[:42]:42} {dur / 1e3 / args.steps:10.2f} "
              f"{100 * dur / max(total, 1):6.1f} "
              f"{cnt[key] // args.steps:8d}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
