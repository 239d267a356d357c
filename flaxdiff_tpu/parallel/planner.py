"""Measurement-driven auto-parallelism planner (ROADMAP item 3).

The closed loop the static analyzer (PR 14) and device-time attribution
(PR 19) were built for: given a model's param tree (via
`jax.eval_shape`), a pod topology, and the per-chip HBM budget
(`telemetry/memory.resolved_hbm_bytes`), the planner

  1. ENUMERATES candidate plans: every (data, fsdp, tensor) mesh-axis
     factorization of the device count, crossed with partition-rule
     tables — "generated" (an explicit `match_partition_rules` regex
     table emitted from the tree, one suffix-anchored rule per leaf) and
     "inferred" (rules=None, the TP/FSDP inference path) — plus
     pipeline-stage candidates (a "pipe" axis with a GPipe schedule,
     `parallel/pipeline.py`) where the tree has a homogeneous block
     stack the stage count divides.
  2. PRUNES statically with the PR-14 machinery: a candidate whose
     `partition_coverage` leaves an `unmatched` leaf is out (silently
     replicated HBM); a candidate whose HBM estimate — sharded params
     + optimizer moments + EMA + an activation envelope — exceeds the
     per-chip budget is out. Survivors are ranked by per-device comm
     bytes per step from the collective-inventory walker
     (`analysis/shard_rules.collective_summary`) over a comm PROXY
     program (below), converted to predicted milliseconds via the
     achieved-bandwidth calibration PR 19 writes onto registry rows
     (`comm_achieved_bytes_per_s`) when such rows are supplied — the
     ranking then trusts measured bandwidth, not raw byte counts.
  3. PROBES the top-k shortlist with short measured runs through an
     injectable `probe_fn` (tests feed counting mocks — the PR-7
     autotuner mold; no caller in the repo feeds a measured one, and
     a probed plan against the hand-written mesh is not measured on a
     chip: ROADMAP queue 1 item 7), persisting the decision in an
     atomic-JSON cache keyed on model-shape-signature x topology x
     hardware fingerprint. A warm cache performs ZERO probes.
  4. COMMITS the decision to the program evidence registry
     (`ProgramRegistry.record` + `annotate`), so
     `scripts/compare_runs.py` / `scripts/diagnose_run.py` diff plan
     decisions across runs like any other program evidence.

Why a comm PROXY program: the planner's candidates run under jit +
sharding constraints, where GSPMD inserts the collectives AFTER the
jaxpr the walker sees — a traced FSDP train step shows zero explicit
collectives (tests/test_shard_rules.py pins this). So for each
candidate the planner traces a tiny abstract program (`jax.make_jaxpr`
with an `axis_env`, nothing compiled, no devices touched) that emits
exactly the collective traffic the plan implies — the data-axis grad
psum sized to the per-device grad shard, the ZeRO-3 fsdp all-gathers
(fwd + bwd) and grad reduce-scatter sized to the fsdp-sharded leaf
bytes, one tensor-axis psum per row-parallel site sized to the
activation envelope, and the pipeline's ppermute chain over its
M + S - 1 ticks — and feeds it to the SAME `collective_summary` byte
model that prices every other program in the registry. The estimates
are envelope-level by design; the measured probes (and PR 19's
achieved-bandwidth write-back) are what the final choice trusts.

Consumer seams: `DiffusionTrainer(plan="auto")` resolves mesh +
partition rules from here instead of the hand-written table
(`resolve_plan`), and `SamplerProgramEngine.plan_parallelism` runs the
same search with optimizer/EMA multipliers zeroed to answer the
chips-per-request vs requests-per-chip question for inference.

Metric names emitted (docs/OBSERVABILITY.md): `planner/candidates`,
`planner/pruned_unmatched`, `planner/pruned_hbm`, `planner/pruned_comm`,
`planner/probes`, `planner/cache_hits`.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import re
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

import numpy as np

from .mesh import AXIS_DATA, AXIS_FSDP, AXIS_TENSOR, create_mesh
from .partition import (PartitionRule, _path_str, infer_fsdp_spec,
                        infer_tp_spec, partition_coverage)

log = logging.getLogger("flaxdiff_tpu.planner")

AXIS_PIPE = "pipe"

CACHE_FILENAME = "parallel_plans.json"
CACHE_ENV = "FLAXDIFF_PLAN_CACHE"

# state multipliers for the HBM-fit estimate: adam keeps two moments
# per param, the trainer keeps one EMA copy; inference zeroes both
OPT_MULT = 2.0
EMA_MULT = 1.0
# activation envelope: bytes live at once ~ act_mult x one batch (f32).
# An envelope, not a measurement — the measured probe is the authority.
ACT_MULT = 8.0

_ITEMSIZE = 4          # proxy payloads are f32
_BLOCK_RE = re.compile(r"(^|/)block_(\d+)(/|$)")


def _block_until_ready(x) -> None:
    """The probe helpers' one host sync (the trainer's blessed-seam
    pattern — analysis/ast_rules.py HostSyncRule)."""
    import jax
    jax.block_until_ready(x)


# ---------------------------------------------------------------------------
# PartitionSpec / rule (de)serialization — the plan cache and the
# registry row must round-trip byte-stably.
# ---------------------------------------------------------------------------

def _spec_to_json(spec) -> List[Any]:
    out: List[Any] = []
    for entry in spec:
        if isinstance(entry, (tuple, list)):
            out.append([str(e) for e in entry])
        else:
            out.append(None if entry is None else str(entry))
    return out


def _spec_from_json(entries: Sequence[Any]):
    from jax.sharding import PartitionSpec
    parts = []
    for entry in entries:
        if isinstance(entry, list):
            parts.append(tuple(entry))
        else:
            parts.append(entry)
    return PartitionSpec(*parts)


def _rules_to_json(rules: Optional[Sequence[PartitionRule]]
                   ) -> Optional[List[List[Any]]]:
    if rules is None:
        return None
    return [[pattern, _spec_to_json(spec)] for pattern, spec in rules]


def _rules_from_json(data) -> Optional[List[PartitionRule]]:
    if data is None:
        return None
    return [(str(pattern), _spec_from_json(spec)) for pattern, spec in data]


# ---------------------------------------------------------------------------
# Tree introspection
# ---------------------------------------------------------------------------

def _tree_leaves(tree) -> List[Tuple[str, Tuple[int, ...], int]]:
    """(path, shape, nbytes) per leaf, sorted by path (works on arrays
    and on `jax.eval_shape` ShapeDtypeStructs alike)."""
    import jax
    out: List[Tuple[str, Tuple[int, ...], int]] = []

    def visit(path, leaf):
        shape = tuple(int(s) for s in getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", None)
        itemsize = int(getattr(dtype, "itemsize", 4) or 4)
        nbytes = int(np.prod(shape, dtype=np.int64)) * itemsize \
            if shape else itemsize
        out.append((_path_str(path), shape, nbytes))
        return leaf

    jax.tree_util.tree_map_with_path(visit, tree)
    return sorted(out)


def tree_signature(tree) -> str:
    """Stable model-shape signature (the plan-cache key's first leg):
    sha1 over the sorted `path:shape:dtype` lines of the tree."""
    import jax
    items: List[str] = []

    def visit(path, leaf):
        shape = "x".join(str(int(s)) for s in getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", "f32"))
        items.append(f"{_path_str(path)}:{shape}:{dtype}")
        return leaf

    jax.tree_util.tree_map_with_path(visit, tree)
    return hashlib.sha1("|".join(sorted(items)).encode()).hexdigest()[:12]


def _block_stack_count(paths: Sequence[str]) -> int:
    """Number of homogeneous `block_{i}` subtrees — the pipeline
    schedule's stage-divisibility input (`pipeline_blocks` requires
    n_blocks % n_stages == 0)."""
    ids = set()
    for p in paths:
        m = _BLOCK_RE.search(p)
        if m:
            ids.add(int(m.group(2)))
    return len(ids)


def generate_rules(tree, mesh, min_size: int = 2 ** 16
                   ) -> List[PartitionRule]:
    """An explicit `match_partition_rules` regex table for this tree on
    this mesh: one suffix-anchored rule per leaf (so the same table
    covers `params/...`, `ema_params/...`, and optimizer-moment copies
    of each tensor), specs from the same TP-then-FSDP inference the
    executable path uses, longest-path-first so no rule shadows a more
    specific one, closed by the catch-all `('.*', P())`.

    Every leaf matches a rule by construction, so `partition_coverage`
    reports zero `unmatched` leaves for a generated table — a big
    undividable tensor becomes an EXPLICIT replication rule instead of
    a silent one (tested for DiT, MM-DiT, and UNet trees)."""
    from jax.sharding import PartitionSpec

    rules: List[PartitionRule] = []
    for name, shape, _ in _tree_leaves(tree):
        spec = infer_tp_spec(name, shape, mesh)
        if spec is None:
            spec = infer_fsdp_spec(shape, mesh, AXIS_FSDP, min_size)
        rules.append(("(^|/)" + re.escape(name) + "$", spec))
    rules.sort(key=lambda r: len(r[0]), reverse=True)
    rules.append((".*", PartitionSpec()))
    return rules


# ---------------------------------------------------------------------------
# Candidate enumeration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CandidatePlan:
    """One point in the search space: an ordered mesh-axis factorization
    plus the rule-table family that shards the tree on it."""

    axes: Tuple[Tuple[str, int], ...]
    table: str                  # "generated" | "inferred" | "pipeline"
    microbatches: int = 0       # >0 only for pipeline candidates

    @property
    def axes_dict(self) -> Dict[str, int]:
        return {a: s for a, s in self.axes}

    @property
    def name(self) -> str:
        mesh = "x".join(f"{a}{s}" for a, s in self.axes)
        return f"{mesh}/{self.table}"


def enumerate_candidates(n_devices: int,
                         tree_paths: Sequence[str] = (),
                         tables: Sequence[str] = ("generated", "inferred"),
                         include_pipeline: bool = True
                         ) -> List[CandidatePlan]:
    """Every ordered (data, fsdp, tensor) factorization of the device
    count crossed with the rule-table families, plus pipeline
    candidates (data x pipe, GPipe microbatches = stages) for each
    stage count that divides both the device count and the tree's
    `block_{i}` stack."""
    out: List[CandidatePlan] = []
    divisors = [d for d in range(1, n_devices + 1) if n_devices % d == 0]
    for d in divisors:
        for f in divisors:
            if (n_devices % (d * f)) != 0:
                continue
            t = n_devices // (d * f)
            axes = ((AXIS_DATA, d), (AXIS_FSDP, f), (AXIS_TENSOR, t))
            for table in tables:
                out.append(CandidatePlan(axes=axes, table=table))
    if include_pipeline:
        blocks = _block_stack_count(tree_paths)
        for p in divisors:
            if p <= 1 or p >= n_devices + 1 or blocks == 0 \
                    or blocks % p != 0:
                continue
            out.append(CandidatePlan(
                axes=((AXIS_DATA, n_devices // p), (AXIS_PIPE, p)),
                table="pipeline", microbatches=p))
    return out


# ---------------------------------------------------------------------------
# Static evaluation: coverage, HBM fit, comm proxy
# ---------------------------------------------------------------------------

def _shard_factor(spec, sizes: Dict[str, int]) -> int:
    f = 1
    for entry in spec:
        if entry is None:
            continue
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        for a in names:
            f *= max(1, int(sizes.get(a, 1)))
    return f


def _comm_proxy_summary(sizes: Dict[str, int], *,
                        data_payload: int, fsdp_shard: int,
                        tp_payload: int, tp_sites: int,
                        pipe_payload: int, pipe_ticks: int,
                        microbatches: int) -> Dict[str, Any]:
    """Trace the candidate's implied collective traffic abstractly and
    price it with the PR-14 walker. Payloads are BYTES; the proxy is
    f32 so element counts are bytes/4 (min 1). Nothing compiles and no
    device is touched — `make_jaxpr` over ShapeDtypeStructs with an
    `axis_env` carrying the candidate's axis sizes."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..analysis.shard_rules import collective_summary

    d = max(1, sizes.get(AXIS_DATA, 1))
    f = max(1, sizes.get(AXIS_FSDP, 1))
    t = max(1, sizes.get(AXIS_TENSOR, 1))
    p = max(1, sizes.get(AXIS_PIPE, 1))

    def elems(nbytes: int) -> int:
        return max(1, int(nbytes) // _ITEMSIZE)

    de = elems(data_payload)
    fe = elems(fsdp_shard)
    te = elems(tp_payload)
    pe = elems(pipe_payload)

    def body(dp, fs, ff, tp, pp):
        acc = jnp.float32(0)
        if d > 1:
            # grad all-reduce over data replicas: payload = the
            # per-device grad shard (grads share the param sharding)
            acc += lax.psum(dp, AXIS_DATA).sum()
        if f > 1:
            # ZeRO-3: params gathered on use in fwd AND bwd, grads
            # reduce-scattered back to their shards
            acc += lax.all_gather(fs, AXIS_FSDP).sum()
            acc += lax.all_gather(fs, AXIS_FSDP).sum()
            acc += lax.psum_scatter(ff, AXIS_FSDP, tiled=True).sum()
        if t > 1 and tp_sites > 0:
            # one partial-sum all-reduce per row-parallel projection
            def site(c, _):
                return lax.psum(c, AXIS_TENSOR), ()
            c, _ = lax.scan(site, tp, None, length=tp_sites)
            acc += c.sum()
        if p > 1:
            # GPipe: one ring ppermute per tick over M + S - 1 ticks,
            # then the masked psum that collects stage outputs
            perm = [(i, (i + 1) % p) for i in range(p)]

            def tick(c, _):
                return lax.ppermute(c, AXIS_PIPE, perm), ()
            c, _ = lax.scan(tick, pp, None, length=max(1, pipe_ticks))
            acc += c.sum()
            acc += lax.psum(pp, AXIS_PIPE).sum() * microbatches
        return acc

    axis_env = [(a, int(s)) for a, s in sizes.items() if int(s) > 1]
    sds = jax.ShapeDtypeStruct
    closed = jax.make_jaxpr(body, axis_env=axis_env)(
        sds((de,), jnp.float32), sds((fe,), jnp.float32),
        sds((fe * f,), jnp.float32), sds((te,), jnp.float32),
        sds((pe,), jnp.float32))
    return collective_summary(closed, axis_sizes={a: int(s)
                                                  for a, s in sizes.items()})


@dataclasses.dataclass
class EvaluatedPlan:
    """One candidate after static evaluation — what pruning, ranking,
    probing, and the final decision all read."""

    candidate: CandidatePlan
    rules: Optional[List[PartitionRule]]
    unmatched: int
    hbm_estimate_bytes: int
    comm_bytes: int
    comm_bytes_by_axis: Dict[str, int]
    collectives: int
    predicted_ms: Optional[float] = None
    probe_ms: Optional[float] = None

    @property
    def name(self) -> str:
        return self.candidate.name

    @property
    def axes(self) -> Tuple[Tuple[str, int], ...]:
        return self.candidate.axes

    @property
    def axes_dict(self) -> Dict[str, int]:
        return self.candidate.axes_dict

    @property
    def microbatches(self) -> int:
        return self.candidate.microbatches


def evaluate_candidate(cand: CandidatePlan, tree, devices,
                       *, min_size: int = 2 ** 16,
                       batch_shape: Optional[Sequence[int]] = None,
                       opt_mult: float = OPT_MULT,
                       ema_mult: float = EMA_MULT,
                       act_mult: float = ACT_MULT
                       ) -> Optional[EvaluatedPlan]:
    """Static evaluation of one candidate: coverage provenance, the
    HBM-fit estimate, and the comm-proxy byte bill. None when the
    factorization cannot form a mesh over `devices`."""
    from jax.sharding import PartitionSpec  # noqa: F401 — spec types below

    sizes = cand.axes_dict
    try:
        mesh = create_mesh(axes=dict(cand.axes), devices=list(devices))
    except (ValueError, AssertionError) as e:
        log.debug("candidate %s has no mesh over %d devices: %s",
                  cand.name, len(devices), e)
        return None

    rules = (generate_rules(tree, mesh, min_size)
             if cand.table == "generated" else None)
    cov = partition_coverage(tree, mesh, rules=rules, min_size=min_size)
    unmatched = sum(1 for a in cov if a.source == "unmatched")

    # -- HBM estimate: sharded state + activation envelope ------------------
    pipe = max(1, sizes.get(AXIS_PIPE, 1))
    sharded = 0.0
    fsdp_local = 0.0
    tp_row_sites = 0
    tp_any = False
    for a in cov:
        factor = _shard_factor(a.spec, sizes)
        leaf = a.nbytes / factor
        if pipe > 1 and _BLOCK_RE.search(a.path):
            leaf /= pipe            # stage-local block stack slice
        sharded += leaf
        spec_axes = set()
        for entry in a.spec:
            if entry is None:
                continue
            spec_axes.update(entry if isinstance(entry, (tuple, list))
                             else (entry,))
        if AXIS_FSDP in spec_axes:
            fsdp_local += a.nbytes / factor
        if AXIS_TENSOR in spec_axes:
            tp_any = True
            if a.path.endswith("kernel") and _ROW_SITE.search(a.path):
                tp_row_sites += 1
    state_bytes = sharded * (1.0 + opt_mult + ema_mult)

    d = max(1, sizes.get(AXIS_DATA, 1))
    f = max(1, sizes.get(AXIS_FSDP, 1))
    t = max(1, sizes.get(AXIS_TENSOR, 1))
    total_params = sum(n for _, _, n in _tree_leaves(tree))
    if batch_shape:
        act_ref = float(np.prod(tuple(batch_shape), dtype=np.int64)) \
            * _ITEMSIZE
    else:
        # no batch known (the trainer resolves plans before it has seen
        # data): a param-scale proxy keeps the envelope > 0 and the
        # ranking deterministic
        act_ref = float(total_params)
    act_local = act_ref * act_mult / (d * f * t)
    hbm_estimate = int(state_bytes + act_local)

    # -- comm proxy ---------------------------------------------------------
    if t > 1 and tp_any and tp_row_sites == 0:
        tp_row_sites = 1            # column-only TP still pays one reduce
    microbatches = max(1, cand.microbatches or pipe)
    summary = _comm_proxy_summary(
        sizes,
        data_payload=int(sharded),
        fsdp_shard=int(fsdp_local),
        tp_payload=int(act_ref / max(1, d * f)),
        tp_sites=tp_row_sites,
        pipe_payload=int(act_ref / max(1, d * microbatches)),
        pipe_ticks=microbatches + pipe - 1,
        microbatches=1)
    return EvaluatedPlan(
        candidate=cand, rules=rules, unmatched=unmatched,
        hbm_estimate_bytes=hbm_estimate,
        comm_bytes=int(summary["comm_bytes"]),
        comm_bytes_by_axis={str(k): int(v) for k, v in
                            sorted(summary["comm_bytes_by_axis"].items())},
        collectives=int(summary["collectives"]))


_ROW_SITE = re.compile(r"(to_out|proj_out|mlp_out)/kernel$")


def achieved_bandwidth(rows: Optional[Sequence[Dict[str, Any]]]
                       ) -> Optional[float]:
    """Median `comm_achieved_bytes_per_s` over registry rows — the
    PR-19 calibration constant the ranking converts bytes to
    milliseconds with. None when no row carries a positive value
    (ranking then falls back to raw bytes, same ordering)."""
    vals: List[float] = []
    for r in rows or ():
        if not isinstance(r, dict):
            continue
        v = r.get("comm_achieved_bytes_per_s")
        try:
            v = float(v) if v is not None else None
        except (TypeError, ValueError):
            v = None
        if v and v > 0:
            vals.append(v)
    if not vals:
        return None
    return float(np.median(vals))


# ---------------------------------------------------------------------------
# The decision record
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PlanDecision:
    """The committed output of one plan search — everything a consumer
    needs to build the mesh + rules, everything the evidence registry
    needs to diff the decision, and everything the cache needs to skip
    the next search."""

    cache_key: str
    axes: Tuple[Tuple[str, int], ...]
    table: str
    microbatches: int
    rules: Optional[List[PartitionRule]]
    comm_bytes: int
    comm_bytes_by_axis: Dict[str, int]
    collectives: int
    hbm_estimate_bytes: int
    hbm_budget_bytes: Optional[int]
    predicted_ms: Optional[float]
    probe_ms: Optional[float]
    candidates: int
    pruned_unmatched: int
    pruned_hbm: int
    pruned_comm: int
    probes: int
    cache_hit: bool
    shortlist: Tuple[str, ...]
    bandwidth_bytes_per_s: Optional[float] = None

    @property
    def name(self) -> str:
        mesh = "x".join(f"{a}{s}" for a, s in self.axes)
        return f"{mesh}/{self.table}"

    @property
    def axes_dict(self) -> Dict[str, int]:
        return {a: s for a, s in self.axes}

    @property
    def chips_per_request(self) -> int:
        """Inference reading of the plan: chips cooperating on ONE
        request = every non-data axis (ROADMAP item 1's
        chips-per-request vs requests-per-chip question)."""
        out = 1
        for a, s in self.axes:
            if a != AXIS_DATA:
                out *= int(s)
        return out

    def build_mesh(self, devices=None):
        if devices is None:
            import jax
            devices = jax.devices()
        return create_mesh(axes=dict(self.axes), devices=list(devices))

    def to_json(self) -> Dict[str, Any]:
        return {
            "cache_key": self.cache_key,
            "axes": [[a, int(s)] for a, s in self.axes],
            "table": self.table,
            "microbatches": int(self.microbatches),
            "rules": _rules_to_json(self.rules),
            "comm_bytes": int(self.comm_bytes),
            "comm_bytes_by_axis": {k: int(v) for k, v in
                                   sorted(self.comm_bytes_by_axis.items())},
            "collectives": int(self.collectives),
            "hbm_estimate_bytes": int(self.hbm_estimate_bytes),
            "hbm_budget_bytes": (int(self.hbm_budget_bytes)
                                 if self.hbm_budget_bytes else None),
            "predicted_ms": self.predicted_ms,
            "probe_ms": self.probe_ms,
            "candidates": int(self.candidates),
            "pruned_unmatched": int(self.pruned_unmatched),
            "pruned_hbm": int(self.pruned_hbm),
            "pruned_comm": int(self.pruned_comm),
            "probes": int(self.probes),
            "shortlist": list(self.shortlist),
            "bandwidth_bytes_per_s": self.bandwidth_bytes_per_s,
        }

    @classmethod
    def from_json(cls, data: Dict[str, Any],
                  cache_hit: bool = False) -> "PlanDecision":
        return cls(
            cache_key=str(data["cache_key"]),
            axes=tuple((str(a), int(s)) for a, s in data["axes"]),
            table=str(data["table"]),
            microbatches=int(data.get("microbatches", 0)),
            rules=_rules_from_json(data.get("rules")),
            comm_bytes=int(data.get("comm_bytes", 0)),
            comm_bytes_by_axis={str(k): int(v) for k, v in
                                (data.get("comm_bytes_by_axis")
                                 or {}).items()},
            collectives=int(data.get("collectives", 0)),
            hbm_estimate_bytes=int(data.get("hbm_estimate_bytes", 0)),
            hbm_budget_bytes=data.get("hbm_budget_bytes"),
            predicted_ms=data.get("predicted_ms"),
            probe_ms=data.get("probe_ms"),
            candidates=int(data.get("candidates", 0)),
            pruned_unmatched=int(data.get("pruned_unmatched", 0)),
            pruned_hbm=int(data.get("pruned_hbm", 0)),
            pruned_comm=int(data.get("pruned_comm", 0)),
            probes=int(data.get("probes", 0)),
            cache_hit=cache_hit,
            shortlist=tuple(str(s) for s in data.get("shortlist", ())),
            bandwidth_bytes_per_s=data.get("bandwidth_bytes_per_s"))


def plan_cache_key(signature: str, n_devices: int,
                   fingerprint: Optional[Dict[str, Any]] = None) -> str:
    """model-shape-signature x topology x hardware fingerprint."""
    if fingerprint is None:
        from ..telemetry.programs import hardware_fingerprint
        fingerprint = hardware_fingerprint()
    platform = str(fingerprint.get("platform", "unknown"))
    kind = str(fingerprint.get("device_kind", "") or "any")
    clean = re.sub(r"[^A-Za-z0-9_.-]+", "-", f"{platform}_{kind}")
    return f"{signature}_n{int(n_devices)}_{clean}"


# ---------------------------------------------------------------------------
# The planner
# ---------------------------------------------------------------------------

class ParallelPlanner:
    """Enumerate -> prune statically -> probe measured -> commit.

    `probe_fn(evaluated: EvaluatedPlan) -> ms` is injectable so unit
    tests can count probes with a mock (the autotuner mold —
    `self.probe_count` is the counting contract). A probe
    that raises simply loses (its candidate keeps only its static
    rank); when NO probe succeeds the static rank-1 survivor wins."""

    def __init__(self, cache_dir: Optional[str] = None,
                 probe_fn: Optional[Callable[[EvaluatedPlan], float]] = None,
                 top_k: int = 3,
                 metrics=None,
                 min_size: int = 2 ** 16,
                 opt_mult: float = OPT_MULT,
                 ema_mult: float = EMA_MULT,
                 act_mult: float = ACT_MULT,
                 registry_rows: Optional[Sequence[Dict[str, Any]]] = None,
                 bandwidth_bytes_per_s: Optional[float] = None):
        self.cache_dir = cache_dir
        self.probe_fn = probe_fn
        self.top_k = max(1, int(top_k))
        self.min_size = min_size
        self.opt_mult = opt_mult
        self.ema_mult = ema_mult
        self.act_mult = act_mult
        self.probe_count = 0        # total probe_fn invocations (tests)
        self._metrics = metrics
        self._plans: Dict[str, Dict[str, Any]] = {}
        self.bandwidth_bytes_per_s = (
            bandwidth_bytes_per_s
            if bandwidth_bytes_per_s is not None
            else achieved_bandwidth(registry_rows))
        if cache_dir:
            self._load()

    # -- persistence (the PR-7 atomic-JSON mold) ----------------------------
    def _cache_path(self) -> Optional[str]:
        if not self.cache_dir:
            return None
        return os.path.join(self.cache_dir, CACHE_FILENAME)

    def _load(self) -> None:
        path = self._cache_path()
        if not path or not os.path.exists(path):
            return
        try:
            with open(path) as f:
                data = json.load(f)
            plans = data.get("plans", {})
            if isinstance(plans, dict):
                self._plans.update(plans)
        except (OSError, ValueError, json.JSONDecodeError):
            # torn/corrupt cache: start fresh rather than half-trust it
            self._plans = {}

    def save(self) -> None:
        path = self._cache_path()
        if not path:
            return
        os.makedirs(self.cache_dir, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": 1, "plans": self._plans}, f, indent=1,
                      sort_keys=True)
        os.replace(tmp, path)       # atomic: readers never see a torn file

    def plans(self) -> Dict[str, Dict[str, Any]]:
        return dict(self._plans)

    def _count(self, name: str, n: float = 1.0) -> None:
        if self._metrics is not None:
            try:
                self._metrics.counter(name).inc(n)
            except Exception as e:  # noqa: BLE001 — metrics never gate
                log.debug("planner metric %s failed: %s", name, e)

    # -- the search ---------------------------------------------------------
    def plan(self, tree, *, devices=None,
             batch_shape: Optional[Sequence[int]] = None,
             hbm_bytes: Optional[float] = None,
             tables: Sequence[str] = ("generated", "inferred"),
             include_pipeline: bool = True) -> PlanDecision:
        """Search a plan for `tree` over `devices`.

        `hbm_bytes` is the per-chip budget; None resolves it via
        `telemetry.memory.resolved_hbm_bytes` (the FLAXDIFF_HBM_BYTES
        env override first, then allocator stats) and skips HBM
        pruning entirely when neither source exists."""
        import jax
        if devices is None:
            devices = jax.devices()
        n = len(devices)
        if hbm_bytes is None:
            from ..telemetry.memory import resolved_hbm_bytes
            hbm_bytes = resolved_hbm_bytes()

        signature = tree_signature(tree)
        key = plan_cache_key(signature, n)
        cached = self._plans.get(key)
        if cached is not None:
            self._count("planner/cache_hits")
            log.info("plan cache hit %s -> %s", key, cached.get("table"))
            return PlanDecision.from_json(cached, cache_hit=True)

        paths = [p for p, _, _ in _tree_leaves(tree)]
        cands = enumerate_candidates(n, tree_paths=paths, tables=tables,
                                     include_pipeline=include_pipeline)
        evals: List[EvaluatedPlan] = []
        for cand in cands:
            ev = evaluate_candidate(
                cand, tree, devices, min_size=self.min_size,
                batch_shape=batch_shape, opt_mult=self.opt_mult,
                ema_mult=self.ema_mult, act_mult=self.act_mult)
            if ev is not None:
                evals.append(ev)
        self._count("planner/candidates", len(evals))

        matched = [e for e in evals if e.unmatched == 0]
        pruned_unmatched = len(evals) - len(matched)
        self._count("planner/pruned_unmatched", pruned_unmatched)

        if hbm_bytes:
            fit = [e for e in matched
                   if e.hbm_estimate_bytes <= float(hbm_bytes)]
        else:
            fit = list(matched)
        pruned_hbm = len(matched) - len(fit)
        self._count("planner/pruned_hbm", pruned_hbm)
        if not fit:
            raise ValueError(
                f"no candidate plan fits: {len(evals)} enumerated, "
                f"{pruned_unmatched} unmatched, {pruned_hbm} over the "
                f"{hbm_bytes} byte HBM budget")

        bw = self.bandwidth_bytes_per_s
        for e in fit:
            if bw:
                e.predicted_ms = e.comm_bytes / bw * 1e3
        # stable comm ranking; name tie-break keeps the order (and the
        # committed evidence row) deterministic across runs
        fit.sort(key=lambda e: (e.comm_bytes, e.name))
        shortlist = fit[:self.top_k]
        pruned_comm = len(fit) - len(shortlist)
        self._count("planner/pruned_comm", pruned_comm)

        probes = 0
        if self.probe_fn is not None and len(shortlist) > 1:
            for e in shortlist:
                self.probe_count += 1
                probes += 1
                try:
                    e.probe_ms = float(self.probe_fn(e))
                except Exception as err:  # noqa: BLE001 — a failing
                    # candidate is just not chosen; keep the cause
                    log.warning("plan probe %s failed: %r", e.name, err)
                    e.probe_ms = None
            self._count("planner/probes", probes)
        measured = [e for e in shortlist if e.probe_ms is not None]
        chosen = (min(measured, key=lambda e: (e.probe_ms, e.name))
                  if measured else shortlist[0])

        decision = PlanDecision(
            cache_key=key,
            axes=chosen.axes, table=chosen.candidate.table,
            microbatches=chosen.microbatches, rules=chosen.rules,
            comm_bytes=chosen.comm_bytes,
            comm_bytes_by_axis=chosen.comm_bytes_by_axis,
            collectives=chosen.collectives,
            hbm_estimate_bytes=chosen.hbm_estimate_bytes,
            hbm_budget_bytes=int(hbm_bytes) if hbm_bytes else None,
            predicted_ms=chosen.predicted_ms, probe_ms=chosen.probe_ms,
            candidates=len(evals), pruned_unmatched=pruned_unmatched,
            pruned_hbm=pruned_hbm, pruned_comm=pruned_comm,
            probes=probes, cache_hit=False,
            shortlist=tuple(e.name for e in shortlist),
            bandwidth_bytes_per_s=bw)
        self._plans[key] = decision.to_json()
        self.save()
        log.info("plan %s: %d candidates, pruned %d unmatched / %d hbm "
                 "/ %d comm, %d probes -> %s (%d comm bytes)", key,
                 decision.candidates, pruned_unmatched, pruned_hbm,
                 pruned_comm, probes, decision.name, decision.comm_bytes)
        return decision

    # -- evidence -----------------------------------------------------------
    def commit(self, registry, decision: PlanDecision,
               kind: str = "plan") -> Optional[Dict[str, Any]]:
        """Land the decision in the program evidence registry: one
        byte-stable `record` row with the static fields, then the
        measured fields through the `annotate` write-back channel (the
        devprof mold) — re-planning on a warm cache re-annotates the
        same row instead of minting a new one."""
        if registry is None:
            return None
        registry.record(
            kind, decision.cache_key,
            collectives=decision.collectives,
            comm_bytes_by_axis=decision.comm_bytes_by_axis,
            extra={
                "plan": decision.name,
                "plan_axes": {a: int(s) for a, s in decision.axes},
                "plan_table": decision.table,
                "plan_microbatches": int(decision.microbatches),
                "plan_candidates": int(decision.candidates),
                "plan_pruned_unmatched": int(decision.pruned_unmatched),
                "plan_pruned_hbm": int(decision.pruned_hbm),
                "plan_pruned_comm": int(decision.pruned_comm),
                "plan_shortlist": list(decision.shortlist),
                "plan_hbm_estimate_bytes": int(decision.hbm_estimate_bytes),
                "plan_hbm_budget_bytes": (
                    int(decision.hbm_budget_bytes)
                    if decision.hbm_budget_bytes else None),
            })
        fields: Dict[str, Any] = {
            "plan_chosen": decision.name,
            "plan_probes": int(decision.probes),
            "plan_cache_hit": int(decision.cache_hit),
        }
        if decision.predicted_ms is not None:
            fields["plan_predicted_ms"] = float(decision.predicted_ms)
        if decision.probe_ms is not None:
            fields["plan_probe_ms"] = float(decision.probe_ms)
        return registry.annotate(kind, decision.cache_key, fields)


def resolve_plan(plan: Union[str, PlanDecision], tree, *,
                 devices=None, telemetry=None, kind: str = "plan",
                 planner: Optional[ParallelPlanner] = None,
                 **plan_kwargs) -> PlanDecision:
    """The consumer seam: `"auto"` runs a static search (cache dir from
    $FLAXDIFF_PLAN_CACHE; no probes: a caller that wants measured
    probing builds its own `ParallelPlanner(probe_fn=...)`), a
    `PlanDecision` passes through. Either way
    the decision is committed to `telemetry.programs` when the hub
    carries a registry."""
    if isinstance(plan, PlanDecision):
        decision = plan
        committer = planner or ParallelPlanner(metrics=_hub_metrics(telemetry))
    elif plan == "auto":
        if planner is None:
            planner = ParallelPlanner(
                cache_dir=os.environ.get(CACHE_ENV) or None,
                metrics=_hub_metrics(telemetry))
        # consumers execute plain jit train/sample steps, which cannot
        # run a GPipe schedule — pipeline candidates are for the
        # explicit `pipelined_dit_apply` path only
        plan_kwargs.setdefault("include_pipeline", False)
        decision = planner.plan(tree, devices=devices, **plan_kwargs)
        committer = planner
    else:
        raise ValueError(f"plan must be 'auto' or a PlanDecision, "
                         f"got {plan!r}")
    registry = getattr(telemetry, "programs", None)
    if registry is not None:
        committer.commit(registry, decision, kind=kind)
    return decision


def _hub_metrics(telemetry):
    """A Telemetry hub doubles as the metrics sink when it exposes
    `counter` (it does — the serving engine counts on it directly)."""
    return telemetry if hasattr(telemetry, "counter") else None
