#!/usr/bin/env python3
"""Spreads and readings of recorded runs, as the bound rule wants them.

  python benchmark/spread.py <dir-of-set-1> <dir-of-set-2> [<dir-of-all-runs>]

Each directory holds `run_seed*_trace0.json` reports written by
`run.py`. For every end-to-end metric: each set's median and its spread
(third minus first quartile of `statistics.quantiles(values, n=4)`, as a
share of the median), the wider of the two, and five times that. With a
third directory: the largest reading of each compared number over the
sound runs, and the smallest over the control runs.
"""
from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def load(d):
    out = []
    for f in sorted(glob.glob(os.path.join(d, "run_seed*_trace0.json"))):
        with open(f) as fh:
            out.append(json.load(fh))
    return out


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv):
    sets = [load(d) for d in argv[1:3]]
    names = sorted({k for s in sets for r in s
                    for k in r["result"]["metrics"]})
    for n in names:
        row, widest = [], 0.0
        for s in sets:
            vals = [r["result"]["metrics"][n]["value"] for r in s
                    if n in r["result"]["metrics"] and not r["control"]]
            sp = spread(vals)
            widest = max(widest, sp)
            row.append(f"median {statistics.median(vals):.6g} spread "
                       f"{100 * sp:.3f}% (n={len(vals)})")
        print(f"{n}: " + " | ".join(row)
              + f" | widest {100 * widest:.3f}% -> x5 {500 * widest:.2f}%")
    if len(argv) > 3:
        runs = load(argv[3])
        for ctl in (False, True):
            rs = [r for r in runs if bool(r["control"]) == ctl
                  and r.get("readings")]
            keys = sorted({k for r in rs for k, v in r["readings"].items()
                           if v is not None})
            for k in keys:
                vals = [r["readings"][k] for r in rs
                        if r["readings"].get(k) is not None]
                print(("control " if ctl else "sound ") + f"{k}: n="
                      f"{len(vals)} min {min(vals):.6g} max {max(vals):.6g}")


if __name__ == "__main__":
    main(sys.argv)
