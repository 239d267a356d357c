#!/usr/bin/env python3
"""One run of a serving cell of the benchmark with the serving counters
its result line does not carry.

  python scripts/bench_serving_counters.py --workload dit-xl-2.generate \\
      --seed <n> --trace <0|1>        (benchmark/run.py's own arguments)

`benchmark/harness/serving.py` snapshots a closed tuple of counters
(`COUNTERS`); this wrapper adds `serving/launches`,
`serving/rounds_overlapped`, `serving/row_steps_run` and
`serving/row_steps_live` to it for the run, lets `benchmark/run.py` do
everything else, and prints after its result line one `counters` line
per pair of snapshots and a `counters_window` line with
`launches_per_round`, `overlapped_per_round`, `step_occupancy` (live
row-steps over row-steps run) and `steps_per_round` (the mean round
length; docs/OBSERVABILITY.md; PERF.md section 3). A builder's tool: no
run of the benchmark calls it.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]


def main() -> int:
    import run as bench_run
    from harness import serving

    serving.COUNTERS = serving.COUNTERS + (
        "serving/launches", "serving/rounds_overlapped",
        "serving/row_steps_run", "serving/row_steps_live")
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
        run = d["serving/row_steps_run"]
        print("counters_window " + json.dumps(dict(
            d, launches_per_round=d["serving/launches"] / rounds,
            overlapped_per_round=d["serving/rounds_overlapped"] / rounds,
            step_occupancy=d["serving/row_steps_live"] / (run or 1.0),
            steps_per_round=run / (d["serving/rows_real"] or 1.0))))
    return rc


if __name__ == "__main__":
    sys.exit(main())
