#!/usr/bin/env python
"""Evidence diff: compare two runs' telemetry dirs or BENCH_*.json
files with per-stage regression thresholds and a hardware fingerprint
check (docs/OBSERVABILITY.md "Evidence diff").

The ROADMAP's recurring failure mode is a TPU window spent re-deriving
"did we get faster" by hand. This CLI makes the re-baseline one
command: point it at the previous evidence and the fresh evidence, and
the output IS the regression report.

Inputs (auto-detected per argument):

- a **telemetry directory** (`--telemetry_dir` of a run): compares the
  last `metrics` snapshot's serving histograms + goodput fraction, the
  aggregated `request_trace` latency decomposition, and the program
  registry (`programs.jsonl`) row by row — per-program compile ms and
  FLOPs line up by (kind, key), so "this program got slower to build"
  and "this program changed shape" are separate findings. Two ISSUE 18
  artifacts ride along when present: the byte-stable per-tenant SLO
  summary (`tenant_slo.json`, loadgen's `write_tenant_slo`) diffs as a
  `tenant_slo` stage where attainment DOWN is worse, and flight-
  recorder `incident-*.json` bundles diff as per-kind counts in an
  `incidents` stage where ANY increase is a regression (counts, not
  percentages — one new replica_lost incident is a finding even from a
  zero base). Device-profile windows (`devprof.jsonl`, ISSUE 19) diff
  as a `devprof` stage from the last parsed window: per-op-family ms
  UP is worse, measured MFU / achieved comm bandwidth DOWN is worse,
  op counts and predicted comm bytes are neutral program-shape facts.
- a **bench result file** (the final JSON line of `bench.py`, saved
  to a file): compares numeric leaves per stage.

Direction is inferred from the metric name: `*_ms` / `*latency*` /
`p50|p99|max` / `compile`-style names regress UP; `*speedup*` /
`*throughput*` / `imgs_per_sec` / `mfu*` / `hit_rate`-style names
regress DOWN; other numbers are reported informationally and never
fail the comparison.

Hardware fingerprint: both sides' `platform`/`device_kind` (bench
`evidence` stamp — `bench.py --evidence` — or any registry row's
`fingerprint`) must match; differing fingerprints are different
experiments, not regressions, and exit 2 unless
`--allow-fingerprint-mismatch`.

Exit codes: 0 = comparable, no regression above threshold;
1 = at least one regression above threshold; 2 = incomparable
(fingerprint mismatch / unreadable input).

`--json` output is byte-stable (sorted keys, rounded floats, no
timestamps or absolute paths) — tested as a contract in
tests/test_tools.py.

Usage:
    python scripts/compare_runs.py runA/telemetry runB/telemetry
    python scripts/compare_runs.py bench_A.json bench_B.json \
        --threshold 0.10 --stage-threshold serve=0.25 --json
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

# metric-name direction heuristics (checked on the LAST path component
# and the full path, lowercase)
_UP_IS_WORSE = ("_ms", "latency", "_s", "p50", "p99", "max", "mean",
                "compile", "re_traces", "shed", "dropped", "wall",
                "step_time", "bytes", "incident", "faulted", "errors",
                "burn")
_DOWN_IS_WORSE = ("speedup", "throughput", "imgs_per_sec", "mfu",
                  "hit_rate", "fraction", "psnr", "occupancy",
                  "samples_per_s", "goodput", "rps", "attainment",
                  "achieved")
# pure identity/config numbers: never a finding in either direction
# (flops is here too: a FLOPs change means the PROGRAM changed shape —
# report it, but it is a different experiment, not a regression)
_NEUTRAL = ("seed", "count", "n_requests", "rate_hz", "batch", "steps",
            "rounds", "requests", "completed", "incarnation", "epoch",
            "devices", "world", "num_", "resolution", "nfe", "secs",
            "budget", "attempts", "image_size", "flops", "slo_ms",
            "schema_version",
            # planner decision bookkeeping (parallel/planner.py): how
            # many candidates were enumerated/pruned/probed describes
            # the SEARCH, not run quality — only the chosen plan's
            # probe/predicted ms (the "_ms" rule) regress
            "candidates", "pruned_", "probes", "cache_hit")
# neutral checked on the FULL path (before the generic "bytes"-is-worse
# heuristic): the static comm model (`collectives`,
# `comm_bytes_by_axis/<axis>`) describes the PROGRAM, not the run — a
# change means the program changed shape, which the lint comm budgets
# gate; here it is reported informationally, never as a regression
_NEUTRAL_PATH = ("comm_bytes", "collectives",
                 # a plan's HBM-fit estimate describes the CHOSEN plan
                 # (a deliberate memory/comm tradeoff), not a leak
                 "hbm_estimate")


def direction(path: str) -> int:
    """+1 = regression when candidate is HIGHER, -1 = regression when
    candidate is LOWER, 0 = informational."""
    p = path.lower()
    leaf = p.rsplit("/", 1)[-1]
    for frag in _NEUTRAL_PATH:
        if frag in p:
            return 0
    for frag in _NEUTRAL:
        if frag in leaf:
            return 0
    for frag in _DOWN_IS_WORSE:
        if frag in p:
            return -1
    for frag in _UP_IS_WORSE:
        if frag in p:
            return 1
    return 0


def _flatten(obj: Any, prefix: str = "") -> Dict[str, float]:
    out: Dict[str, float] = {}
    if isinstance(obj, dict):
        for k in obj:
            out.update(_flatten(obj[k], f"{prefix}/{k}" if prefix else str(k)))
    elif isinstance(obj, bool):
        pass                        # flags are not measurements
    elif isinstance(obj, (int, float)) and obj is not None:
        out[prefix] = float(obj)
    return out


def read_jsonl(path: str) -> List[Dict]:
    out: List[Dict] = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue            # torn tail
            if isinstance(rec, dict):
                out.append(rec)
    return out


def _pct(xs: List[float], q: float) -> Optional[float]:
    if not xs:
        return None
    s = sorted(xs)
    k = (len(s) - 1) * q
    lo, hi = int(k), min(int(k) + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


# ---------------------------------------------------------------------------
# Loaders: one evidence dict per side — {"fingerprint", "stages"}
# ---------------------------------------------------------------------------

def load_bench(path: str) -> Dict[str, Any]:
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    fp = dict(doc.get("evidence") or {})
    if "platform" not in fp and doc.get("platform"):
        fp["platform"] = doc["platform"]
    stages: Dict[str, Dict[str, float]] = {}
    headline = {k: v for k, v in doc.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}
    if headline:
        stages["headline"] = _flatten(headline)
    for name, stage in (doc.get("stages") or {}).items():
        if isinstance(stage, dict) and stage.get("status") == "ok":
            stages[name] = _flatten(
                {k: v for k, v in stage.items() if k != "status"})
    return {"kind": "bench", "fingerprint": fp, "stages": stages}


def load_telemetry_dir(path: str) -> Dict[str, Any]:
    jsonl = os.path.join(path, "telemetry.jsonl")
    records = read_jsonl(jsonl) if os.path.exists(jsonl) else []
    metrics = [r for r in records if r.get("type") == "metrics"]
    traces = [r for r in records if r.get("type") == "request_trace"
              and r.get("outcome", "ok") == "ok"]
    stages: Dict[str, Dict[str, float]] = {}
    if metrics:
        last = metrics[-1]
        keep = {k: v for k, v in last.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)
                and k.startswith(("serving/", "goodput/", "phase/",
                                  "inference/", "diffcache/", "memory/",
                                  "train/"))}
        stages["metrics"] = _flatten(keep)
    if traces:
        agg: Dict[str, float] = {"count": float(len(traces))}
        for span in ("queue_ms", "compile_ms", "device_ms",
                     "latency_ms"):
            xs = [float(t.get(span, 0.0)) for t in traces]
            agg[f"{span}/p50"] = _pct(xs, 0.5)
            agg[f"{span}/p99"] = _pct(xs, 0.99)
        stages["request_traces"] = _flatten(agg)
    # per-tenant SLO artifact (loadgen's write_tenant_slo): attainment
    # DOWN is worse, per-tenant p50/p99 UP is worse
    slo_path = os.path.join(path, "tenant_slo.json")
    if os.path.exists(slo_path):
        try:
            with open(slo_path, "r", encoding="utf-8") as f:
                slo_doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            slo_doc = {}
        tenants = slo_doc.get("tenants")
        if isinstance(tenants, dict) and tenants:
            stages["tenant_slo"] = _flatten(tenants)
    # flight-recorder bundles: per-kind incident counts (always
    # emitted, so a base with zero bundles still compares — the
    # candidate growing ANY kind from 0 is the finding)
    counts: Dict[str, float] = {"total": 0.0}
    for inc_path in sorted(glob.glob(
            os.path.join(path, "incident-*.json"))):
        try:
            with open(inc_path, "r", encoding="utf-8") as f:
                inc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        kind = str(inc.get("kind") or "unknown")
        counts["total"] += 1.0
        counts[kind] = counts.get(kind, 0.0) + 1.0
    # keys carry the incidents/ prefix so direction() classifies them
    # (stage rows are compared by bare key, without the stage name)
    stages["incidents"] = {f"incidents/{k}": v
                           for k, v in counts.items()}
    # device-profile windows (devprof.jsonl, ISSUE 19): the LAST
    # successfully parsed window is the current device-time
    # attribution. Per-op-family ms regress UP ("attn got slower"),
    # measured MFU and achieved comm bandwidth regress DOWN, op counts
    # and predicted comm bytes are program-shape facts (neutral).
    dev_path = os.path.join(path, "devprof.jsonl")
    dev_rows = [r for r in (read_jsonl(dev_path)
                            if os.path.exists(dev_path) else [])
                if r.get("type") == "devprof"]
    ok_rows = [r for r in dev_rows if r.get("status") == "ok"]
    if ok_rows:
        last = ok_rows[-1]
        dp: Dict[str, Any] = {
            "windows": float(len(dev_rows)),
            "device_ms_per_step": last.get("device_ms_per_step"),
            "collective_ms": last.get("collective_ms"),
            "collective_count": last.get("collective_count"),
            "compute_ms": last.get("compute_ms"),
            "layout_copy_ms": last.get("layout_copy_ms"),
            "layout_copy_count": last.get("layout_copy_count"),
            "fusion_gap_ms": last.get("fusion_gap_ms"),
            "fusion_gap_count": last.get("fusion_gap_count"),
            "measured_mfu": last.get("measured_mfu"),
            "measured_flops_per_s": last.get("measured_flops_per_s"),
            "comm_measured_ms": last.get("comm_measured_ms"),
            # neutral via the comm_bytes path rule: predicted bytes
            # describe the PROGRAM, not the run
            "comm_bytes_predicted": last.get("comm_predicted_bytes"),
            "comm_achieved_bytes_per_s":
                last.get("comm_achieved_bytes_per_s"),
        }
        for fam, f in sorted((last.get("families") or {}).items()):
            if isinstance(f, dict):
                dp[f"families/{fam}_ms"] = f.get("ms")
                dp[f"families/{fam}_count"] = f.get("count")
        stages["devprof"] = {f"devprof/{k}": float(v)
                             for k, v in dp.items()
                             if isinstance(v, (int, float))
                             and not isinstance(v, bool)}
    fp: Dict[str, Any] = {}
    programs: Dict[str, Dict[str, float]] = {}
    from flaxdiff_tpu.telemetry.programs import (PROGRAMS_FILENAME,
                                                 read_registry)
    for row in read_registry(os.path.join(path, PROGRAMS_FILENAME)):
        if not fp and isinstance(row.get("fingerprint"), dict):
            fp = dict(row["fingerprint"])
        ident = f"{row.get('kind', '?')}::{row.get('key', '?')}"
        fields = {k: row[k] for k in ("compile_ms", "flops_jaxpr",
                                      "flops_cost", "bytes_cost",
                                      "hbm_peak_bytes", "collectives")
                  if isinstance(row.get(k), (int, float))}
        if isinstance(row.get("comm_bytes_by_axis"), dict):
            fields["comm_bytes_by_axis"] = row["comm_bytes_by_axis"]
        # planner decision rows (kind "plan"/"plan_infer") carry their
        # search/decision numbers as plan_* fields — diffable like any
        # other evidence (direction rules above)
        for k, v in row.items():
            if k.startswith("plan_") and isinstance(v, (int, float)) \
                    and not isinstance(v, bool):
                fields[k] = v
        programs[ident] = _flatten(fields)
    out = {"kind": "telemetry", "fingerprint": fp, "stages": stages}
    if programs:
        out["programs"] = programs
    return out


def load_side(path: str) -> Dict[str, Any]:
    if os.path.isdir(path):
        return load_telemetry_dir(path)
    return load_bench(path)


# ---------------------------------------------------------------------------
# Comparison
# ---------------------------------------------------------------------------

def compare_stage(base: Dict[str, float], cand: Dict[str, float],
                  threshold: float) -> List[Dict[str, Any]]:
    rows: List[Dict[str, Any]] = []
    for key in sorted(set(base) & set(cand)):
        b, c = base[key], cand[key]
        d = direction(key)
        if b == 0.0:
            delta = None
        else:
            delta = (c - b) / abs(b)
        regressed = False
        if d != 0 and delta is not None:
            regressed = (delta > threshold if d > 0
                         else delta < -threshold)
        rows.append({"metric": key, "base": b, "candidate": c,
                     "delta_pct": (round(delta * 100.0, 2)
                                   if delta is not None else None),
                     "direction": {1: "up_is_worse", -1: "down_is_worse",
                                   0: "info"}[d],
                     "regressed": regressed})
    return rows


def fingerprints_match(a: Dict[str, Any], b: Dict[str, Any]
                       ) -> Tuple[bool, str]:
    """Platform + device kind must agree when both sides carry them;
    a side with NO fingerprint is comparable-with-warning (older
    evidence predates the stamp)."""
    if not a or not b:
        return True, "missing on one side (pre-stamp evidence)"
    for field in ("platform", "device_kind"):
        va, vb = a.get(field), b.get(field)
        if va and vb and va != vb:
            return False, f"{field}: {va!r} != {vb!r}"
    return True, "ok"


def build_report(base_path: str, cand_path: str, threshold: float,
                 stage_thresholds: Dict[str, float]) -> Dict[str, Any]:
    base, cand = load_side(base_path), load_side(cand_path)
    fp_ok, fp_note = fingerprints_match(base["fingerprint"],
                                        cand["fingerprint"])
    report: Dict[str, Any] = {
        "base": os.path.basename(os.path.normpath(base_path)),
        "candidate": os.path.basename(os.path.normpath(cand_path)),
        "kind": {"base": base["kind"], "candidate": cand["kind"]},
        "fingerprint": {"match": fp_ok, "note": fp_note,
                        "base": base["fingerprint"],
                        "candidate": cand["fingerprint"]},
        "threshold": threshold,
        "stages": {},
        "regressions": [],
    }
    for name in sorted(set(base["stages"]) & set(cand["stages"])):
        th = stage_thresholds.get(name, threshold)
        rows = compare_stage(base["stages"][name], cand["stages"][name],
                             th)
        if name == "incidents":
            # counts, not percentages: one more replica_lost bundle is
            # a regression even from a zero base (where relative delta
            # is undefined and the generic threshold never fires)
            for r in rows:
                if r["direction"] == "up_is_worse":
                    r["regressed"] = r["candidate"] > r["base"]
        report["stages"][name] = {"threshold": th, "rows": rows}
        for r in rows:
            if r["regressed"]:
                report["regressions"].append(
                    {"stage": name, **r})
    only_base = sorted(set(base["stages"]) - set(cand["stages"]))
    only_cand = sorted(set(cand["stages"]) - set(base["stages"]))
    if only_base or only_cand:
        report["uncompared_stages"] = {"base_only": only_base,
                                       "candidate_only": only_cand}
    if "programs" in base and "programs" in cand:
        pb, pc = base["programs"], cand["programs"]
        prog_rows: List[Dict[str, Any]] = []
        for ident in sorted(set(pb) & set(pc)):
            th = stage_thresholds.get("programs", threshold)
            for r in compare_stage(pb[ident], pc[ident], th):
                r["program"] = ident
                prog_rows.append(r)
                if r["regressed"]:
                    report["regressions"].append(
                        {"stage": "programs", **r})
        report["programs"] = {
            "compared": len(set(pb) & set(pc)),
            "base_only": sorted(set(pb) - set(pc)),
            "candidate_only": sorted(set(pc) - set(pb)),
            "rows": prog_rows,
        }
    report["ok"] = fp_ok and not report["regressions"]
    return report


def _stable(obj):
    if isinstance(obj, float):
        return round(obj, 4)
    if isinstance(obj, dict):
        return {k: _stable(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_stable(v) for v in obj]
    return obj


def render_text(report: Dict[str, Any]) -> str:
    lines = [f"evidence diff: {report['base']} -> {report['candidate']}"]
    fp = report["fingerprint"]
    lines.append(f"fingerprint: {'MATCH' if fp['match'] else 'MISMATCH'}"
                 f" ({fp['note']})")
    for name in sorted(report["stages"]):
        st = report["stages"][name]
        flagged = [r for r in st["rows"] if r["regressed"]]
        moved = [r for r in st["rows"]
                 if r["delta_pct"] is not None
                 and abs(r["delta_pct"]) >= st["threshold"] * 100.0
                 and r["direction"] != "info"]
        lines.append(f"== {name} ({len(st['rows'])} shared metrics, "
                     f"threshold {st['threshold']:.0%}) ==")
        for r in (flagged or moved[:8]):
            mark = "REGRESSION" if r["regressed"] else "improved"
            pct = ("new" if r["delta_pct"] is None
                   else f"{r['delta_pct']:+.1f}%")
            lines.append(
                f"  {r['metric']:<44s} {r['base']:>12.4g} -> "
                f"{r['candidate']:>12.4g}  ({pct}) {mark}")
        if not flagged and not moved:
            lines.append("  (no movement beyond threshold)")
    progs = report.get("programs")
    if progs:
        lines.append(f"== programs ({progs['compared']} shared) ==")
        for r in progs["rows"]:
            if r["regressed"]:
                lines.append(
                    f"  {r['program']}\n    {r['metric']}: "
                    f"{r['base']:.4g} -> {r['candidate']:.4g} "
                    f"({r['delta_pct']:+.1f}%) REGRESSION")
        if progs["base_only"] or progs["candidate_only"]:
            lines.append(f"  only in base: {len(progs['base_only'])}, "
                         f"only in candidate: "
                         f"{len(progs['candidate_only'])}")
    n = len(report["regressions"])
    lines.append(f"verdict: "
                 + ("INCOMPARABLE (fingerprint mismatch)"
                    if not fp["match"] else
                    (f"{n} regression(s) above threshold" if n
                     else "no regressions above threshold")))
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="diff two runs' evidence (telemetry dirs or bench "
                    "JSON) with regression thresholds")
    ap.add_argument("base", help="baseline telemetry dir or BENCH json")
    ap.add_argument("candidate", help="candidate telemetry dir or "
                                      "BENCH json")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="default relative regression threshold "
                         "(0.10 = 10%%)")
    ap.add_argument("--stage-threshold", action="append", default=[],
                    metavar="STAGE=PCT",
                    help="per-stage override, e.g. serve=0.25 "
                         "(repeatable; 'programs' targets the registry "
                         "comparison)")
    ap.add_argument("--allow-fingerprint-mismatch", action="store_true",
                    help="compare across hardware anyway (exit codes "
                         "then reflect regressions only)")
    ap.add_argument("--json", action="store_true",
                    help="emit the byte-stable JSON report instead of "
                         "text")
    args = ap.parse_args(argv)

    stage_thresholds: Dict[str, float] = {}
    for spec in args.stage_threshold:
        if "=" not in spec:
            ap.error(f"--stage-threshold wants STAGE=PCT, got {spec!r}")
        name, _, val = spec.partition("=")
        stage_thresholds[name] = float(val)

    try:
        report = build_report(args.base, args.candidate, args.threshold,
                              stage_thresholds)
    except (OSError, json.JSONDecodeError, ValueError) as e:
        print(f"incomparable: {e}", file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps(_stable(report), sort_keys=True, indent=1))
    else:
        print(render_text(report))
    if not report["fingerprint"]["match"] \
            and not args.allow_fingerprint_mismatch:
        return 2
    return 1 if report["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
