#!/usr/bin/env python3
"""What the program's own spans say about one traced run.

  python benchmark/spans.py <trace-dir> [--steps N] [--record out.json]

`<trace-dir>` is what a `--trace 1` run leaves behind
(`benchmark/out/<cell>/trace`). Prints, as one JSON line, the
`program_span` metrics ISSUE 24 names, every span's milliseconds per
round (or per step, with `--steps`, the traced window's train steps),
the device's idle time put down to the innermost span of the
dispatching thread, the longest idle gaps by span, and the clock
offset's bound (`harness/program_spans.py`). A builder's tool, like
`spread.py`: no run of the benchmark calls it, because wiring the
reader into `harness/layer_metrics.py` edits files only a `benchmark`
PR may edit (PERF.md, section 7).

`--record` keeps the capture small for a test: the window's `fdt.*` and
`bench.*` host events, the launched programs among the device's
executed ones, and its busy time as merged intervals named `%busy` in
place of single operations (intervals closer than 2 us are joined: the
record is for the reader's tests, not for a metric's value).
"""
from __future__ import annotations

import argparse
import json
import sys

from harness import program_spans as ps
from harness import trace as tr


def record(rows, window, out_path: str, pad_ns: float = 2e7,
           join_ns: float = 2e3) -> None:
    lo, hi = window[0] - pad_ns, window[1] + pad_ns
    keep, busy = [], {}
    for r in rows:
        a, b = r["start_ns"], r["start_ns"] + r["dur_ns"]
        if b < lo or a > hi or r["line"] == tr.ASYNC_LINE:
            continue
        if r["line"] == tr.OPS_LINE:
            busy.setdefault(r["plane"], []).append((a, b + join_ns))
        elif r["line"] != ps.MODULES_LINE \
                or any(p in r["name"] for p in ps.LAUNCHES.values()):
            keep.append(r)
    for plane, ivs in busy.items():
        keep += [{"plane": plane, "line": tr.OPS_LINE, "name": "%busy",
                  "start_ns": a, "dur_ns": b - join_ns - a}
                 for a, b in tr.union(ivs)]
    with open(out_path, "w") as f:
        json.dump({"rows": keep}, f, separators=(",", ":"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--record", default="")
    args = ap.parse_args(argv)
    rows = ps.read_rows(args.trace_dir)
    trace, spans, modules = ps.split(rows)
    rep = ps.report(trace, spans, modules, args.steps)
    if args.record and trace.window() is not None:
        record(rows, trace.window(), args.record)
    print(json.dumps(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
