"""Load and validate the benchmark's data files.

`BENCHMARK.json` names a configuration and a traffic mix per cell and the
metrics; this module finds `configs/<name>.json`, `traffic/<mix>.json`
and `layer_metrics/<metric>.json` by those names and refuses a key it
does not know, so that a typo never silently changes what is measured.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Dict, List, Optional

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

BENCH_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
              "end_to_end", "per_layer"}
CONFIG_ENTRY_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source", "workloads"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves",
              "workloads"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

CONFIG_FILE_KEYS = {
    "name", "source", "family", "registry_name", "model", "input",
    "conditioning", "schedule", "predictor", "train", "serve", "reduced",
    "assumed", "departures", "deployment", "required_gflop_per_image_fwd",
    "rehearse", "limits"}
TRAFFIC_KEYS = {
    "train_steady": {"kind", "why", "mesh", "trace_steps",
                     "check_steps"},
    "closed_loop": {"kind", "why", "clients", "nfe_deal", "guidance_scale",
                    "images_per_request", "sampler", "trace_rounds",
                    "check_requests", "warm_blocks"},
    "open_loop": {"kind", "why", "rate_hz", "shape", "peak_factor",
                  "burst_len", "burst_idle_s", "nfe_deal", "guidance_scale",
                  "images_per_request", "sampler", "trace_rounds",
                  "check_requests", "warm_blocks", "submit_workers"},
}
LAYER_FILE_KEYS = {"name", "layer", "unit", "better", "moves", "source",
                   "kinds", "read"}
READ_KEYS = {
    "device_events": {"from", "match", "reduce", "per"},
    "device_busy": {"from", "reduce", "per"},
    "host_window": {"from", "per"},
    "result_field": {"from", "field", "percentile"},
    "counter_ratio": {"from", "numerator", "denominator"},
    "memory_stats": {"from", "keys", "of"},
    "required_ops": {"from", "of"},
}


class SpecError(ValueError):
    """A data file of the benchmark is malformed."""


def _check_keys(what: str, got: Dict[str, Any], allowed: set,
                required: Optional[set] = None) -> None:
    unknown = set(got) - allowed
    if unknown:
        raise SpecError(f"{what}: unknown key(s) {sorted(unknown)}; "
                        f"known: {sorted(allowed)}")
    missing = (required or set()) - set(got)
    if missing:
        raise SpecError(f"{what}: missing key(s) {sorted(missing)}")


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of `workloads`, with its files resolved."""
    name: str
    chips: int
    why: str
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]      # BENCHMARK.json entry + "file"


@dataclasses.dataclass
class Benchmark:
    root: str                  # directory that holds BENCHMARK.json
    bench_dir: str             # the benchmark's own directory (paths[0])
    raw: Dict[str, Any]

    @property
    def run_seconds(self) -> int:
        return int(self.raw["run_seconds"])

    def cell_names(self) -> List[str]:
        return [w["name"] for w in self.raw["workloads"]]

    def cell(self, name: str) -> Cell:
        by_name = {w["name"]: w for w in self.raw["workloads"]}
        if name not in by_name:
            raise SpecError(f"unknown workload {name!r}; known: "
                            f"{sorted(by_name)}")
        w = by_name[name]
        cfg_entry = {c["name"]: c for c in self.raw["configs"]}.get(
            w["config"])
        if cfg_entry is None:
            raise SpecError(f"workload {name!r} names configuration "
                            f"{w['config']!r}, which `configs` lacks")
        config = load_config(os.path.join(self.root, cfg_entry["file"]))
        traffic = load_traffic(os.path.join(
            self.bench_dir, "traffic", w["traffic"] + ".json"))
        e2e = [m for m in self.raw["end_to_end"]
               if "workloads" not in m or name in m["workloads"]]
        layer = []
        for m in self.raw["per_layer"]:
            if "workloads" in m and name not in m["workloads"]:
                continue
            f = load_layer_metric(os.path.join(
                self.bench_dir, "layer_metrics", m["name"] + ".json"))
            for k in ("unit", "layer", "moves", "source", "better"):
                if f[k] != m[k]:
                    raise SpecError(
                        f"per-layer metric {m['name']!r}: {k!r} is "
                        f"{m[k]!r} in BENCHMARK.json and {f[k]!r} in "
                        "its file")
            if traffic["kind"] not in f["kinds"]:
                raise SpecError(
                    f"per-layer metric {m['name']!r} is listed for cell "
                    f"{name!r} but its file does not apply to traffic "
                    f"kind {traffic['kind']!r}")
            layer.append(dict(m, file=f))
        return Cell(name=name, chips=int(w["chips"]), why=w["why"],
                    config_name=w["config"], config=config,
                    traffic_name=w["traffic"], traffic=traffic,
                    end_to_end=e2e, per_layer=layer)


def load_config(path: str) -> Dict[str, Any]:
    cfg = _load_json(path)
    _check_keys(f"configuration {path}", cfg, CONFIG_FILE_KEYS,
                {"name", "source", "family", "registry_name", "model",
                 "input", "conditioning", "schedule", "predictor",
                 "reduced", "assumed"})
    return cfg


def load_traffic(path: str) -> Dict[str, Any]:
    t = _load_json(path)
    kind = t.get("kind")
    if kind not in TRAFFIC_KEYS:
        raise SpecError(f"traffic {path}: kind {kind!r} is not one of "
                        f"{sorted(TRAFFIC_KEYS)}")
    _check_keys(f"traffic {path}", t, TRAFFIC_KEYS[kind], {"kind", "why"})
    return t


def load_layer_metric(path: str) -> Dict[str, Any]:
    m = _load_json(path)
    _check_keys(f"layer metric {path}", m, LAYER_FILE_KEYS, LAYER_FILE_KEYS)
    read = m["read"]
    how = read.get("from")
    if how not in READ_KEYS:
        raise SpecError(f"layer metric {path}: read.from {how!r} is not "
                        f"one of {sorted(READ_KEYS)}")
    _check_keys(f"layer metric {path} read", read, READ_KEYS[how])
    return m


def lint(raw: Dict[str, Any]) -> None:
    """The contract's limits on names, units and keys, checked here so
    that a rehearsal fails before the driver refuses the file."""
    _check_keys("BENCHMARK.json", raw, BENCH_KEYS, BENCH_KEYS)
    names: List[str] = []
    for c in raw["configs"]:
        _check_keys(f"configs[{c.get('name')}]", c, CONFIG_ENTRY_KEYS,
                    CONFIG_ENTRY_KEYS)
        names.append(c["name"])
        names.extend(c["reduced"])
    for w in raw["workloads"]:
        _check_keys(f"workloads[{w.get('name')}]", w, WORKLOAD_KEYS,
                    WORKLOAD_KEYS)
        names += [w["name"], w["config"], w["traffic"]]
        if w["chips"] not in (1, 4):
            raise SpecError(f"{w['name']}: chips must be 1 or 4")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            raise SpecError(f"{w['name']}: why must be 1..200 chars, "
                            "one line")
    e2e_names = set()
    for m in raw["end_to_end"]:
        _check_keys(f"end_to_end[{m.get('name')}]", m, E2E_KEYS,
                    E2E_KEYS - {"workloads"})
        if m["source"] not in ("host_clock", "device_trace"):
            raise SpecError(f"{m['name']}: end-to-end source must be "
                            "host_clock or device_trace")
        if not 0 < m["bound"] <= 0.1:
            raise SpecError(f"{m['name']}: bound must be in (0, 0.1]")
        e2e_names.add(m["name"])
    if "setup_s" not in e2e_names:
        raise SpecError("end_to_end lacks setup_s")
    for m in raw["per_layer"]:
        _check_keys(f"per_layer[{m.get('name')}]", m, LAYER_KEYS,
                    LAYER_KEYS - {"workloads"})
        if m["source"] not in SOURCES:
            raise SpecError(f"{m['name']}: unknown source {m['source']!r}")
        if m["moves"] not in e2e_names:
            raise SpecError(f"{m['name']}: moves {m['moves']!r}, which "
                            "is not an end-to-end metric")
    metrics = raw["end_to_end"] + raw["per_layer"]
    for m in metrics:
        names.append(m["name"])
        if not UNIT_RE.match(m["unit"]):
            raise SpecError(f"{m['name']}: unit {m['unit']!r} is not 1..16 "
                            "of letters, digits, _ / % . -")
        if m["better"] not in ("lower", "higher"):
            raise SpecError(f"{m['name']}: better must be lower or higher")
    for n in names:
        if not NAME_RE.match(n):
            raise SpecError(f"name {n!r} is not 1..64 of letters, digits, "
                            "_ . - starting with a letter, digit or _")
    for group, label in ((raw["configs"], "configuration"),
                         (raw["workloads"], "workload"),
                         (metrics, "metric")):
        seen = [g["name"] for g in group]
        dup = {n for n in seen if seen.count(n) > 1}
        if dup:
            raise SpecError(f"duplicate {label} name(s) {sorted(dup)}")
    pairs = [(w["config"], w["traffic"]) for w in raw["workloads"]]
    if len(set(pairs)) != len(pairs):
        raise SpecError("a (configuration, traffic) pair appears twice")


def load_benchmark(root: str) -> Benchmark:
    raw = _load_json(os.path.join(root, "BENCHMARK.json"))
    lint(raw)
    return Benchmark(root=root,
                     bench_dir=os.path.join(root, raw["paths"][0]), raw=raw)
