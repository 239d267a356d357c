#!/usr/bin/env python3
"""Run one cell of the benchmark once.

  python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: it loads the cell's data files, builds the
system under test from the seed, warms the cell's own programs, measures
for `--seconds`, decides `correct` against the plain reference and
prints its result line last. Without a TPU holding the cell's chips it
exits non-zero and prints no result line; `--rehearse` is for CPU
rehearsals only, shrinks every size and labels its output `cpu`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes (tests only)")
    ap.add_argument("--control", default="", choices=("", "bf16", "fp8"),
                    help="put the reference, computed in this lower "
                         "precision, in the program's place: `correct` "
                         "has to come out false")
    ap.add_argument("--root", default=ROOT,
                    help="directory that holds BENCHMARK.json")
    return ap.parse_args(argv)


def prepare(args):
    """What every entry point does first: find the cell's data files,
    look for the chips (or, rehearsing, hold jax to the CPU), point the
    compilation cache into the checkout. Returns (cell, found, meter),
    or an exit code."""
    root = os.path.abspath(args.root)
    bench_dir = os.path.join(root, "benchmark")
    if not os.path.isdir(os.path.join(root, "flaxdiff_tpu")):
        print("benchmark: the system under test (flaxdiff_tpu/) is not in "
              f"{root}", file=sys.stderr)
        return 3
    for p in (root, bench_dir):
        if p not in sys.path:
            sys.path.insert(0, p)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("FLAXDIFF_FLASH_INTERPRET", "1")
        os.environ.setdefault("FLAXDIFF_FUSED_NORM", "interpret")
        os.environ.setdefault("FLAXDIFF_FUSED_ADALN", "interpret")

    from harness import spec
    bench = spec.load_benchmark(root)
    cell = bench.cell(args.workload)
    if args.seconds is None:
        args.seconds = float(bench.run_seconds)
    if args.rehearse and cell.chips > 1:
        flag = f"--xla_force_host_platform_device_count={cell.chips}"
        if flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + " " + flag).strip()
    args.out_dir = os.path.join(bench_dir, "out", cell.name)
    os.makedirs(args.out_dir, exist_ok=True)

    from harness import device
    found = device.require_chips(cell.chips, args.rehearse)
    if args.rehearse:
        found = dict(found, platform="cpu")

    import jax
    if args.rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        print(f"compilation cache: {device.configure_cache(bench_dir)}",
              flush=True)
    return cell, found, device.CompileMeter()


def main(argv=None) -> int:
    args = parse(argv)
    ready = prepare(args)
    if isinstance(ready, int):
        return ready
    cell, found, meter = ready

    import importlib
    kind = cell.traffic["kind"]
    driver = importlib.import_module(f"harness.{kind}")
    out = driver.run(cell, args, found, meter, T_START)

    dev = dict(found, memory_peak_bytes=int(out["memory_peak_bytes"]))
    notes: dict = {}    # what a reader says besides its value
    line = {"correct": bool(out["correct"]),
            "attempted": int(out["attempted"]),
            "failed": int(out["failed"])}
    if args.trace and out.get("window") is not None:
        from harness import layer_metrics, trace
        w = out["window"]
        if w.trace is not None:
            trace.dump_rows(w.trace, os.path.join(
                args.out_dir, f"trace_rows_seed{args.seed}.json.gz"))
        line["metrics"] = layer_metrics.read_all(cell.per_layer, w, notes)
        if w.trace is not None and w.trace.devices and w.interval:
            dev["busy_s"] = trace.busy_seconds(w.trace, w.interval)
            dev["window_s"] = (w.interval[1] - w.interval[0]) / 1e9
            line["breakdown"] = trace.breakdown(w.trace, w.interval)
        elif args.rehearse:
            dev["busy_s"], dev["window_s"] = 0.0, w.wall_s
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        line["metrics"] = {k: {"value": float(v), "unit": units[k]}
                           for k, v in out["metrics"].items() if k in units}
    line["device"] = dev
    if notes:
        line["notes"] = notes       # which bound a roofline share stands on
    # each number compared beside its limit: the line's last key, and the
    # last lines on standard error
    from harness import check
    line["compared"] = check.as_result(out.get("compared", []))
    report = {"workload": cell.name, "seed": args.seed,
              "seconds": args.seconds, "trace": args.trace,
              "rehearsal": bool(args.rehearse), "control": args.control,
              "compile": meter.snapshot(),
              "readings": out.get("readings"), "result": line}
    with open(os.path.join(args.out_dir,
                           f"run_seed{args.seed}_trace{args.trace}.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    print("report " + json.dumps({k: report[k] for k in
                                  ("compile", "readings")}), flush=True)
    sys.stdout.flush()
    for name, c in line["compared"].items():
        print(f"compared {name} {c['value']:.6g} limit {c['limit']:.6g}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
