"""Sharding & collective-traffic rules: what a program costs a pod.

graph_rules.py checks invariants any single-device program has; these
three see the axis that decides pod-scale behavior — sharding. They run
over the MESHED inventory (programs.py `meshed_programs`: the real
ring / Ulysses / pipeline / FSDP-train / sharded-serving programs traced
under multi-device CPU meshes) as well as the single-device programs
(where they degrade to zero-collective stats).

  collective-inventory  walks the jaxpr nest (scan bodies x trip count,
                        cond = max-byte branch, pjit/shard_map/custom-
                        vjp descended) counting every psum / all_gather /
                        reduce_scatter / ppermute / all_to_all with a
                        per-mesh-axis byte estimate — a static comm
                        model per program, budgeted by
                        budgets.COMM_BUDGET and exported into the
                        program evidence registry (telemetry/programs
                        rows gain `collectives` / `comm_bytes_by_axis`).
  partition-coverage    every param-tree leaf of a meshed program's
                        partition subject must be decided by an explicit
                        rule, TP/FSDP inference, or the deliberate
                        small-tensor replicate — an `unmatched` leaf is
                        silently replicated HBM on every device
                        (parallel/partition.py `partition_coverage`).
  implicit-reshard      flags boundary intermediates whose producer
                        sharding and consumer sharding disagree with no
                        explicit constraint between — XLA inserts an
                        unplanned transfer there (an all-to-all-class
                        reshard, invisible in the source).

Byte model (per-device SEND bytes per execution, ring/bidirectional
algorithms assumed, n = product of the collective's axis sizes):

  psum/pmax/pmin   2 * (n-1)/n * payload     (reduce-scatter+all-gather)
  all_gather       (n-1) * payload           (payload = local shard)
  reduce_scatter   (n-1)/n * payload
  ppermute         payload                   (one neighbor hop)
  all_to_all       (n-1)/n * payload
  pbroadcast       0                         (replication bookkeeping)

Inside shard_map the traced avals are already per-device local shards,
so `payload` is honest local bytes. Estimates are scheduling-free (no
overlap, no ICI topology): good for ratios and regression pinning, not
for absolute link-time prediction — the planner (ROADMAP 3) validates
candidates with measured probes, this model prunes its search space.

Known limitations (documented, deliberate): GSPMD-inserted collectives
(jit + sharding constraints, no shard_map) happen at compile time and
are invisible to a jaxpr walk — the FSDP train step therefore shows
zero *explicit* collectives; its sharding is gated by partition-coverage
instead. `while` bodies with non-static trip counts count once (the
real loops here are `fori_loop`s with mesh-derived static bounds, which
lower to `scan`). The reshard detector only compares NAMED shardings it
can see (shard_map boundaries, sharding_constraint sites, and
elementwise propagation between them); replicated->sharded boundaries
are NOT flagged (that is FSDP's normal gather-on-use pattern).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .framework import (COMM_BUDGET, COMM_DEFAULT_BUDGET, Finding,
                        GraphRule, register)

# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

_COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "pmax", "pmin", "ppermute", "all_to_all",
    "all_gather", "reduce_scatter", "pbroadcast",
})
# under shard_map's varying-axes check (check_vma, the default) a psum
# traces as psum_invariant — one logical collective, one name in every
# report
_PRIM_ALIASES = {"psum_invariant": "psum"}


def _numel(aval) -> int:
    n = 1
    for s in getattr(aval, "shape", ()):
        n *= int(s)
    return n


def _payload_bytes(eqn) -> int:
    total = 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        itemsize = int(getattr(getattr(aval, "dtype", None),
                               "itemsize", 4) or 4)
        total += _numel(aval) * itemsize
    return total


def _collective_axes(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _bytes_estimate(prim: str, payload: int, n: int) -> float:
    if n <= 1 or prim == "pbroadcast":
        return 0.0
    if prim in ("psum", "pmax", "pmin"):
        return 2.0 * (n - 1) / n * payload
    if prim == "all_gather":
        return float((n - 1) * payload)
    if prim in ("reduce_scatter", "all_to_all"):
        return (n - 1) / n * payload
    if prim == "ppermute":
        return float(payload)
    return float(payload)


def _sub_jaxprs(params):
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):
                yield x.jaxpr
            elif hasattr(x, "eqns"):
                yield x


class _CommAccount:
    """Accumulated collective inventory for one (sub)program walk."""

    def __init__(self):
        self.by_primitive: Dict[str, int] = {}
        self.bytes_by_axis: Dict[str, float] = {}
        self.total_bytes = 0.0
        self.count = 0
        self.unknown_axes = 0

    def add(self, prim: str, axes: Tuple[str, ...], payload: int,
            mult: int, axis_sizes: Dict[str, int]) -> None:
        self.count += mult
        self.by_primitive[prim] = self.by_primitive.get(prim, 0) + mult
        n = 1
        known = True
        for a in axes:
            if a in axis_sizes:
                n *= int(axis_sizes[a])
            else:
                known = False
        if not known:
            self.unknown_axes += mult
        est = _bytes_estimate(prim, payload, n) * mult
        self.total_bytes += est
        if est:
            key = ",".join(axes) if axes else "?"
            self.bytes_by_axis[key] = \
                self.bytes_by_axis.get(key, 0.0) + est

    def merge(self, other: "_CommAccount") -> None:
        self.count += other.count
        self.total_bytes += other.total_bytes
        self.unknown_axes += other.unknown_axes
        for k, v in other.by_primitive.items():
            self.by_primitive[k] = self.by_primitive.get(k, 0) + v
        for k, v in other.bytes_by_axis.items():
            self.bytes_by_axis[k] = self.bytes_by_axis.get(k, 0.0) + v


def _harvest_axis_sizes(jaxpr, sizes: Dict[str, int]) -> None:
    """Pick mesh axis sizes out of shard_map eqns so the byte model
    works even when the caller has no Mesh handle (e.g. the program
    registry probing an arbitrary jitted fn)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            mesh = eqn.params.get("mesh")
            shape = getattr(mesh, "shape", None)
            if shape:
                for name, size in dict(shape).items():
                    sizes.setdefault(str(name), int(size))
        for sub in _sub_jaxprs(eqn.params):
            _harvest_axis_sizes(sub, sizes)


def _comm_walk(jaxpr, mult: int, acct: _CommAccount,
               axis_sizes: Dict[str, int]) -> None:
    """scan bodies multiplied by trip count; cond takes the max-byte
    branch (at most one executes — summing would double-count a per-step
    refresh/reuse switch); everything else descended at the parent
    multiplier. `while` bodies count once (trip statically unknown —
    the repo's mesh loops are static fori_loops, which lower to scan)."""
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in _COLLECTIVE_PRIMS:
            acct.add(_PRIM_ALIASES.get(prim, prim),
                     _collective_axes(eqn), _payload_bytes(eqn),
                     mult, axis_sizes)
        if prim == "cond":
            kids = []
            for br in eqn.params.get("branches", ()):
                kid = _CommAccount()
                _comm_walk(br.jaxpr if hasattr(br, "consts") else br,
                           mult, kid, axis_sizes)
                kids.append(kid)
            if kids:
                acct.merge(max(kids, key=lambda k: (k.total_bytes,
                                                    k.count)))
            continue
        sub_mult = mult
        if prim == "scan":
            sub_mult = mult * int(eqn.params.get("length", 1) or 1)
        for sub in _sub_jaxprs(eqn.params):
            _comm_walk(sub, sub_mult, acct, axis_sizes)


def collective_summary(closed,
                       axis_sizes: Optional[Dict[str, int]] = None
                       ) -> Dict[str, object]:
    """Static comm model of one traced program.

    Returns {"collectives", "comm_bytes", "by_primitive",
    "comm_bytes_by_axis"} with deterministic (sorted, integer-byte)
    contents — the registry and the lint JSON both rely on
    byte-stability. `axis_sizes` defaults to whatever shard_map meshes
    the jaxpr itself carries.
    """
    jaxpr = getattr(closed, "jaxpr", closed)
    sizes: Dict[str, int] = dict(axis_sizes or {})
    if not sizes:
        _harvest_axis_sizes(jaxpr, sizes)
    acct = _CommAccount()
    _comm_walk(jaxpr, 1, acct, sizes)
    out: Dict[str, object] = {
        "collectives": acct.count,
        "comm_bytes": int(round(acct.total_bytes)),
        "by_primitive": {k: acct.by_primitive[k]
                         for k in sorted(acct.by_primitive)},
        "comm_bytes_by_axis": {k: int(round(acct.bytes_by_axis[k]))
                               for k in sorted(acct.bytes_by_axis)},
    }
    if acct.unknown_axes:
        out["unknown_axis_collectives"] = acct.unknown_axes
    return out


# ---------------------------------------------------------------------------
# collective-inventory
# ---------------------------------------------------------------------------

@register
class CollectiveInventoryRule(GraphRule):
    """Budgeted static comm model per traced program."""

    id = "collective-inventory"
    doc = ("per-program collective inventory (psum/all_gather/"
           "reduce_scatter/ppermute/all_to_all counts + per-axis byte "
           "estimates) exceeds its budgets.COMM_BUDGET pin")

    def check(self, program: str, closed) -> Tuple[List[Finding], Dict]:
        summary = collective_summary(
            closed, getattr(closed, "axis_sizes", None))
        budget = COMM_BUDGET.get(program, COMM_DEFAULT_BUDGET)
        findings: List[Finding] = []
        comm_bytes = int(summary["comm_bytes"])
        if comm_bytes > budget:
            findings.append(Finding(
                self.id, f"jaxpr:{program}", 0,
                f"static comm model moved {comm_bytes} bytes/device/"
                f"execution ({summary['collectives']} collective "
                f"dispatches) against a budget of {budget} — a new "
                f"collective or a bigger payload joined this program; "
                f"raise budgets.COMM_BUDGET deliberately or fix the "
                f"sharding"))
        stats = dict(summary)
        if program in COMM_BUDGET:
            stats["budget"] = budget
        return findings, stats


# ---------------------------------------------------------------------------
# partition-coverage
# ---------------------------------------------------------------------------

@register
class PartitionCoverageRule(GraphRule):
    """Every param leaf of a meshed program's partition subject is
    decided — rule, TP/FSDP inference, or deliberate small-tensor
    replicate. `unmatched` = silently replicated HBM."""

    id = "partition-coverage"
    doc = ("param-tree leaf of a meshed program matched no partition "
           "rule and no inference — silently replicated into every "
           "device's HBM (parallel/partition.py partition_coverage)")

    def check(self, program: str, closed) -> Tuple[List[Finding], Dict]:
        assignments = getattr(closed, "partition", None)
        if assignments is None:
            return [], {}
        findings: List[Finding] = []
        by_source: Dict[str, int] = {}
        replicated_bytes = 0
        for leaf in assignments:
            by_source[leaf.source] = by_source.get(leaf.source, 0) + 1
            if leaf.source in ("replicated-small", "unmatched"):
                replicated_bytes += leaf.nbytes
            if leaf.source == "unmatched":
                findings.append(Finding(
                    self.id, f"jaxpr:{program}", 0,
                    f"leaf {leaf.path!r} {leaf.shape} "
                    f"({leaf.nbytes} bytes) matched no partition rule "
                    f"and no dimension divides the mesh axis — "
                    f"silently replicated on every device; add a rule "
                    f"in parallel/partition.py or an explicit "
                    f"replicate entry"))
        stats = {"leaves": len(assignments),
                 "replicated_bytes": replicated_bytes}
        for source in sorted(by_source):
            stats[source.replace("-", "_")] = by_source[source]
        return findings, stats


# ---------------------------------------------------------------------------
# implicit-reshard
# ---------------------------------------------------------------------------

# layout-preserving prims a named sharding propagates through (output
# shape equals the operand's shape; anything shape-changing or
# permuting — transpose, reshape, gather — deliberately DROPS tracking:
# a lost spec can never produce a false positive)
_ELEMENTWISE = frozenset({
    "convert_element_type", "copy", "stop_gradient", "neg", "sign",
    "floor", "ceil", "round", "exp", "log", "log1p", "expm1", "tanh",
    "logistic", "sqrt", "rsqrt", "cbrt", "abs", "sin", "cos",
    "integer_pow", "not", "is_finite", "erf",
    "add", "sub", "mul", "div", "rem", "max", "min", "pow", "atan2",
    "and", "or", "xor", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "eq", "ne", "ge", "gt", "le", "lt",
    "select_n", "nextafter", "clamp", "square",
})


def _canon_spec(spec, rank: int) -> Tuple[Tuple[str, ...], ...]:
    """PartitionSpec -> per-dim tuple of axis names, padded to rank."""
    dims: List[Tuple[str, ...]] = []
    for entry in tuple(spec):
        if entry is None:
            dims.append(())
        elif isinstance(entry, str):
            dims.append((entry,))
        else:
            dims.append(tuple(entry))
    while len(dims) < rank:
        dims.append(())
    return tuple(dims[:rank])


def _sharded(canon: Tuple[Tuple[str, ...], ...]) -> bool:
    return any(canon)


def _rank(var) -> int:
    return len(getattr(getattr(var, "aval", None), "shape", ()))


class _ReshardState:
    def __init__(self):
        self.boundaries = 0          # annotated sites seen
        self.mismatches: List[str] = []


def _walk_specs(jaxpr, in_specs: List, st: _ReshardState) -> List:
    """Propagate NAMED shardings through one (raw) jaxpr; returns the
    outvar specs. Only comparisons between two KNOWN, both-sharded
    layouts ever produce a mismatch — unknown stays unknown."""
    env: Dict = {}

    def read(atom):
        if not hasattr(atom, "aval") or type(atom).__name__ == "Literal":
            return None
        return env.get(atom)

    def bind(var, spec):
        if spec is not None:
            env[var] = spec

    for var, spec in zip(jaxpr.invars, in_specs):
        bind(var, spec)

    def closed_parts(obj):
        return obj.jaxpr if hasattr(obj, "consts") else obj

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        ins = [read(v) for v in eqn.invars]
        outs: List = [None] * len(eqn.outvars)

        if prim == "sharding_constraint":
            st.boundaries += 1
            sharding = eqn.params.get("sharding")
            spec = getattr(sharding, "spec", None)
            if spec is not None and eqn.outvars:
                outs[0] = _canon_spec(spec, _rank(eqn.outvars[0]))
            # an explicit constraint is a PLANNED reshard: never a
            # finding, and it resets tracking to the declared layout
        elif prim == "shard_map":
            st.boundaries += 1
            # the shard_map eqn carries its specs as PartitionSpecs
            for i, (tok, spec) in enumerate(
                    zip(ins, eqn.params["in_specs"])):
                if tok is None:
                    continue
                expect = _canon_spec(spec, _rank(eqn.invars[i]))
                if _sharded(tok) and _sharded(expect) and tok != expect:
                    st.mismatches.append(
                        f"operand {i} enters shard_map as {expect} but "
                        f"was last laid out as {tok}")
            outs = [_canon_spec(spec, _rank(v))
                    for spec, v in zip(eqn.params["out_specs"],
                                       eqn.outvars)]
        elif prim == "scan":
            body = closed_parts(eqn.params["jaxpr"])
            n_consts = eqn.params.get("num_consts", 0)
            n_carry = eqn.params.get("num_carry", 0)
            sub_in = (ins[:n_consts + n_carry]
                      + [None] * (len(body.invars) - n_consts - n_carry))
            sub_out = _walk_specs(body, sub_in, st)
            outs = (list(sub_out[:n_carry])
                    + [None] * (len(outs) - n_carry))
        elif prim == "while":
            body = closed_parts(eqn.params["body_jaxpr"])
            cn = eqn.params.get("cond_nconsts", 0)
            bn = eqn.params.get("body_nconsts", 0)
            body_ins = ins[cn:cn + bn] + ins[cn + bn:]
            _walk_specs(body, body_ins, st)
        elif prim == "cond":
            branch_outs = []
            for br in eqn.params.get("branches", ()):
                branch_outs.append(
                    _walk_specs(closed_parts(br), ins[1:], st))
            if branch_outs and all(b == branch_outs[0]
                                   for b in branch_outs[1:]):
                outs = list(branch_outs[0][:len(outs)]) \
                    + [None] * max(0, len(outs) - len(branch_outs[0]))
        elif prim in _ELEMENTWISE:
            out_shape = getattr(getattr(eqn.outvars[0], "aval", None),
                                "shape", None)
            known = []
            for tok, v in zip(ins, eqn.invars):
                if tok is None:
                    continue
                if getattr(getattr(v, "aval", None), "shape",
                           None) == out_shape:
                    known.append(tok)
            sharded = [k for k in known if _sharded(k)]
            if len(set(sharded)) > 1:
                st.mismatches.append(
                    f"`{prim}` combines operands laid out as "
                    f"{sorted(set(sharded))} — XLA reshards one "
                    f"implicitly")
            elif known:
                outs[0] = sharded[0] if sharded else known[0]
        else:
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None and (hasattr(sub, "eqns")
                                        or hasattr(sub, "consts")):
                    raw = closed_parts(sub)
                    n = len(raw.invars)
                    sub_in = (ins[:n] + [None] * (n - len(ins)))[:n]
                    sub_out = _walk_specs(raw, sub_in, st)
                    outs = list(sub_out[:len(outs)]) \
                        + [None] * max(0, len(outs) - len(sub_out))
                    break

        for var, spec in zip(eqn.outvars, outs):
            bind(var, spec)

    return [read(v) for v in jaxpr.outvars]


@register
class ImplicitReshardRule(GraphRule):
    """Unplanned sharding changes between annotated boundaries."""

    id = "implicit-reshard"
    doc = ("intermediate value crosses between differently-sharded "
           "boundaries with no explicit constraint — XLA inserts an "
           "unplanned reshard transfer there")

    def check(self, program: str, closed) -> Tuple[List[Finding], Dict]:
        st = _ReshardState()
        jaxpr = closed.jaxpr
        in_specs = list(getattr(closed, "in_specs", None)
                        or [None] * len(jaxpr.invars))
        in_specs = (in_specs + [None] * len(jaxpr.invars)
                    )[:len(jaxpr.invars)]
        canon_in = []
        for spec, var in zip(in_specs, jaxpr.invars):
            canon_in.append(None if spec is None
                            else _canon_spec(spec, _rank(var)))
        _walk_specs(jaxpr, canon_in, st)
        findings = [Finding(
            self.id, f"jaxpr:{program}", 0,
            f"implicit reshard: {msg} — constrain the boundary "
            f"explicitly (parallel.partition.with_named_constraint) "
            f"or align the specs") for msg in st.mismatches]
        return findings, {"annotated_boundaries": st.boundaries,
                          "reshards": len(st.mismatches)}
