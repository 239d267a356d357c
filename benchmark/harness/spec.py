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
from typing import Any, Dict, List, Optional, Tuple

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
    "assumed", "departures", "deployment", "published",
    "required_gflop_per_image_fwd", "rehearse", "limits"}
# a catalog row's own sizes outside its `config`: accepted at the top
# level of the file under their names, not required
ROW_SIZE_KEYS = {"layers", "expert_width", "dense_width", "context_length"}
# what `reduced` may name. A LIST is told by its shape: one entry a layer
# (as many as the row's `layers`) is the list that goes with depth,
# whatever it is called (`layer_types`, `sliding_window_layout`); a list
# of another length (a rope group's factors) is not. A SCALAR is told by
# its name: a count of layers, of experts held, of heads held or of
# vocabulary rows (COUNT_RE), unless the name says "a token's"
# (TOKEN_RE): the experts a token picks are a width by the contract.
COUNT_RE = re.compile(r"(^|_)(layers?|experts|heads|vocab_size)$")
TOKEN_RE = re.compile(r"(^|_)(active|per_tok|per_token|top_k|topk|used)(_|$)")
# the contract's widths by name: `models.build` refuses a run in which the
# model would drop such a key; `reduced` does not ask it
WIDTH_RE = re.compile(
    r"hidden|intermediate|latent|state|proj|width|window|expan|ratio|"
    r"per_tok|top_k|head_dim|head_size|_dim$|_rank$")
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
    "device_rounds": {"from", "round", "rounds_ahead", "match", "reduce"},
    "served_ops": {"from", "round", "rounds_ahead", "live", "run", "of"},
    "kernel_roofline": {"from", "match", "kernel", "round", "rounds_ahead",
                        "live", "run", "of"},
}
# the keys of a `read` object whose values name counters of the program
COUNTER_KEYS = ("numerator", "denominator", "live", "run")


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
        config = load_config(os.path.join(self.root, cfg_entry["file"]),
                             entry=cfg_entry)
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


def source_keys(cfg: Dict[str, Any]) -> List[str]:
    """The top-level keys of a loaded configuration that are its
    source's own (`load_config` admits no others beside the harness's)."""
    return [k for k in cfg if k not in CONFIG_FILE_KEYS
            and k not in ROW_SIZE_KEYS]


def _same(a: Any, b: Any) -> bool:
    """Equal as JSON: a number never equals a boolean or a null."""
    if isinstance(a, bool) or isinstance(b, bool) or a is None or b is None:
        return type(a) is type(b) and a == b
    if isinstance(a, dict) and isinstance(b, dict):
        return set(a) == set(b) and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, list) and isinstance(b, list):
        return len(a) == len(b) and all(map(_same, a, b))
    return a == b


def _within(short: list, whole: list) -> bool:
    """Whether `short` is a run of consecutive entries of `whole`."""
    n = len(short)
    return any(_same(short, whole[i:i + n])
               for i in range(len(whole) - n + 1))


def _pattern(kinds: list) -> Tuple[int, int]:
    """(leading, period) of a published per-layer list: the fewest
    layers in all for which, after `leading` of them, the rest repeats
    every `period` (a list that never repeats is one period)."""
    for total in range(1, len(kinds) + 1):
        for lead in range(total):
            rest, p = kinds[lead:], total - lead
            if all(_same(a, b) for a, b in zip(rest, rest[p:])):
                return lead, p
    return 0, 0


def _under_floor(k: str, got: Any, want: Any) -> str:
    """Why a reduced key leaves less than the model (the `model-configs`
    guide's floors: a whole period and at least four of the layers that
    follow the leading dense ones, at least 8 routed experts in each
    layer that has them, at least an eighth of the vocabulary), or ""."""
    if isinstance(want, list):
        lead, period = _pattern(want)
        need, n = max(period, 4), len(got)
        held = max((n - max(0, lead - i) for i in range(len(want) - n + 1)
                    if _same(got, want[i:i + n])), default=0)
        if held < need:
            return (f"the published list repeats every {period} layer(s) "
                    f"after {lead} leading: a cut keeps a whole period and "
                    f"at least four of the layers that follow the leading "
                    f"ones, {need} of them here, and this keeps {held}")
    elif re.search(r"(^|_)layers?$", k):
        if got < 4:
            return "a cut keeps at least four layers"
    elif k.endswith("experts"):
        if "shared" in k:
            return ("shared experts are computed alike on every chip: "
                    "each chip holds them all")
        if got < 8:
            return "a chip holds at least 8 routed experts of a layer"
    elif k.endswith("vocab_size") and got * 8 < want:
        return (f"a chip holds at least an eighth of the vocabulary, "
                f"{-(-want // 8)} rows")
    return ""


def _per_layer(want: Any, row: Dict[str, Any]) -> bool:
    """Whether a source value is a per-layer list: a list with one entry
    a layer, as many as the row's `layers`. Its name is not asked."""
    return isinstance(want, list) and len(want) == row.get("layers")


def lint_against_source(what: str, cfg: Dict[str, Any], row: Dict[str, Any],
                        entry: Optional[Dict[str, Any]] = None) -> None:
    """Hold a configuration file to its catalog row, in the driver's
    words for `config_differs`: the file holds every number and every
    nested group of the source's entry under the same key, at its top
    level; a key not listed in `reduced` equals the source's value
    exactly; a key listed there is a count of layers, of experts held,
    of heads held or of vocabulary rows (a scalar, told by its name:
    never one that says "a token's"), or a list with one entry a layer
    (told by its length, the row's `layers`, whatever its name), never
    a width, and only smaller; the file states the published count
    beside each (`published`) and the deployment."""
    src = row["config"]
    clash = sorted(set(src) & CONFIG_FILE_KEYS)
    if clash:
        raise SpecError(
            f"{what}: the source's key(s) {clash} collide with the "
            "harness's own keys of a configuration file: the file cannot "
            "hold both under one name")
    missing = sorted(set(src) - set(cfg))
    if missing:
        raise SpecError(
            f"{what}: the source's key(s) {missing} are missing: the file "
            "holds EVERY key of the row's `config` at its top level, "
            "whether the model uses it or not")
    reduced = list(cfg["reduced"])
    for where, other in [("the file", cfg)] + (
            [("BENCHMARK.json `configs`", entry)] if entry else []):
        if other["source"] != row["source_url"]:
            raise SpecError(
                f"{what}: `source` in {where} is {other['source']!r}, the "
                f"catalog row's `source_url` is {row['source_url']!r}")
        if list(other["reduced"]) != reduced:
            raise SpecError(
                f"{what}: `reduced` is {reduced} in the file and "
                f"{list(other['reduced'])} in {where}")
    unknown = sorted(set(reduced) - set(src))
    if unknown:
        raise SpecError(f"{what}: `reduced` names {unknown}, which the "
                        "source's `config` lacks")
    for k, want in src.items():
        got = cfg[k]
        if k not in reduced:
            if not _same(got, want):
                raise SpecError(
                    f"{what}: key {k!r} is {got!r} and its source gives "
                    f"{want!r}; it is not listed in `reduced`")
            continue
        count = not isinstance(want, list) and COUNT_RE.search(k)
        if count and TOKEN_RE.search(k):
            raise SpecError(
                f"{what}: `reduced` names {k!r}: the experts a token picks "
                "are a width, and a width is never reduced; only the "
                "experts HELD are a count")
        if not (count or _per_layer(want, row)):
            raise SpecError(
                f"{what}: `reduced` names {k!r}: only a count of layers, "
                "of experts held, of heads held or of vocabulary rows, or "
                "the list that goes with depth, may be reduced, never a "
                "width")
        smaller = (isinstance(got, list) and len(got) < len(want)
                   and _within(got, want)) if isinstance(want, list) else (
            isinstance(got, int) and not isinstance(got, bool)
            and isinstance(want, int) and 0 < got < want)
        if not smaller:
            raise SpecError(
                f"{what}: reduced key {k!r} is {got!r}; it may only be "
                f"smaller than the source's {want!r}")
        low = _under_floor(k, got, want)
        if low:
            raise SpecError(f"{what}: reduced key {k!r} is {got!r}: {low}")
        if not _same(cfg.get("published", {}).get(k), want):
            raise SpecError(
                f"{what}: `published` has to state the source's {k!r} "
                f"({want!r}) beside the reduced value")
    for k, want in src.items():
        # a per-layer list and the count of layers say one depth
        if not _per_layer(want, row):
            continue
        for n, depth in src.items():
            if re.search(r"(^|_)layers?$", n) and _same(depth, len(want)) \
                    and cfg[n] != len(cfg[k]):
                raise SpecError(
                    f"{what}: {k!r} lists {len(cfg[k])} layers and {n!r} "
                    f"is {cfg[n]!r}: reduce both, to the same depth")
    if reduced and not cfg.get("deployment"):
        raise SpecError(f"{what}: `deployment` has to say over how many "
                        "chips each layer is divided, and how")


def load_config(path: str, entry: Optional[Dict[str, Any]] = None
                ) -> Dict[str, Any]:
    """A configuration file. Where `sources/<name>.json` beside it holds
    a copy of the configuration's catalog row, the row's `config` keys
    are accepted at the top level, all of them are required, and the
    file is held to the row (`lint_against_source`); `entry` is the
    configuration's entry in BENCHMARK.json `configs`."""
    cfg = _load_json(path)
    allowed = CONFIG_FILE_KEYS
    row = None
    row_path = os.path.join(os.path.dirname(path), "sources",
                            os.path.basename(path))
    if os.path.exists(row_path):
        row = _load_json(row_path)
        allowed = CONFIG_FILE_KEYS | set(row["config"]) | ROW_SIZE_KEYS
    _check_keys(f"configuration {path}", cfg, allowed,
                {"name", "source", "family", "registry_name", "model",
                 "input", "conditioning", "schedule", "predictor",
                 "reduced", "assumed"})
    if row is not None:
        lint_against_source(f"configuration {path}", cfg, row, entry)
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
    # a reader that pairs rounds with their programs is told how far the
    # dispatch thread runs ahead: the benchmark assumes no such constant
    _check_keys(f"layer metric {path} read", read, READ_KEYS[how],
                {"rounds_ahead"} if "round" in read else None)
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
