#!/usr/bin/env python3
"""One run of a serving cell of the benchmark with the serving counters
its result line does not carry.

  python scripts/bench_serving_counters.py --workload dit-xl-2.generate \\
      --seed <n> --trace <0|1>        (benchmark/run.py's own arguments)

`benchmark/harness/serving.py` snapshots a closed tuple of counters
(`COUNTERS`); this wrapper adds `serving/launches` and
`serving/rounds_overlapped` to it for the run, lets `benchmark/run.py`
do everything else, and prints after its result line one `counters` line
per pair of snapshots and a `counters_window` line with
`launches_per_round` and `overlapped_per_round` (docs/OBSERVABILITY.md;
PERF.md section 3). A builder's tool: no run of the benchmark calls it.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]


def main() -> int:
    import run as bench_run
    from harness import serving

    serving.COUNTERS = serving.COUNTERS + ("serving/launches",
                                           "serving/rounds_overlapped")
    snaps = []
    real = serving._counters

    def logged(tel, names):
        snaps.append(real(tel, names))
        return snaps[-1]

    serving._counters = logged
    rc = bench_run.main()
    for a, b in zip(snaps, snaps[1:]):
        print("counters " + json.dumps({k: b[k] - a[k] for k in a}))
    if snaps:
        d = {k: snaps[-1][k] - snaps[0][k] for k in snaps[0]}
        rounds = d["serving/rounds"] or 1.0
        print("counters_window " + json.dumps(dict(
            d, launches_per_round=d["serving/launches"] / rounds,
            overlapped_per_round=d["serving/rounds_overlapped"] / rounds)))
    return rc


if __name__ == "__main__":
    sys.exit(main())
