"""Rule framework for the graph-hygiene analyzer.

PRs 5-8 made the training and serving hot paths fast by hand-enforced
conventions: every host sync routed through counted module seams, no
callbacks inside jitted programs, per-row RNG carries that never reuse
a key, Pallas kernels that never lane-slice (docs/KERNELS.md). Prose
conventions rot; this package turns them into gates. Two rule families
share one registry, one allowlist, and one report:

  AST rules    (ast_rules.py) parse every production Python file once
               and check source-level conventions — host-sync hygiene,
               the never-lane-slice kernel convention, silent exception
               swallowing, metric-name drift.
  graph rules  (graph_rules.py + shard_rules.py) trace the REAL hot
               programs on CPU via `jax.make_jaxpr` (programs.py builds
               them, including the MESHED parallel programs over a
               forced multi-device host platform) and walk the jaxprs
               the way `profiling.jaxpr_flops` does — RNG-key reuse,
               callback leaks, a budgeted bf16->f32 upcast audit, the
               collective-traffic inventory, partition-rule coverage,
               and the implicit-resharding detector.

Allowlists live in ONE place — `budgets.py`, re-exported here:
`ALLOWLIST[rule_id][relpath]` is a MAXIMUM number of findings a file
may carry. Budgets are debt, not permission — when a fix drops a file
below its budget the text report says so and `scripts/lint.py
--tighten` rewrites the entry down (the same doctrine the standalone
`scripts/check_bare_except.py` gate established; that script and
`scripts/check_metric_names.py` are now thin shims over rules
`silent-except` and `metric-name`).

Entry points: `scripts/lint.py`, `python -m flaxdiff_tpu.analysis`
(both -> cli.py), and tier-1 via `tests/test_tools.py`.
"""
from __future__ import annotations

import ast
import dataclasses
import json
import os
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

# ---------------------------------------------------------------------------
# Budgets — grandfathered findings and per-program numeric ceilings live
# in budgets.py (machine-rewritten by `scripts/lint.py --tighten`);
# re-exported here so framework.ALLOWLIST / framework.UPCAST_BUDGET stay
# the live objects every caller mutates and reads. Every entry is debt:
# budgets are MAXIMA, lower actual counts pass and the report then asks
# you to tighten. `silent-except` was emptied in PR 9; keep it empty.
#
# UPCAST_BUDGET doctrine: the audit is a report, not a verdict — upcasts
# are often correct (f32 loss reduction, f32 norm accumulation) but
# their TOTAL is an HBM-traffic tax that should only ever change
# deliberately. Budgets are elements per trace, calibrated against the
# tiny representative programs in programs.py.
#
# COMM_BUDGET doctrine: estimated per-device collective bytes per
# program execution (shard_rules.py documents the per-primitive byte
# model). Growth = a new collective or a bigger payload on the ICI —
# raise deliberately or fix the sharding.
# ---------------------------------------------------------------------------

from .budgets import ALLOWLIST, COMM_BUDGET, UPCAST_BUDGET  # noqa: E402

# default budgets for programs not pinned in budgets.py: effectively
# unlimited — stats still land in the JSON report for trend tracking
UPCAST_DEFAULT_BUDGET = 1 << 62
COMM_DEFAULT_BUDGET = 1 << 62


# ---------------------------------------------------------------------------
# Findings and rules
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, order=True)
class Finding:
    """One defect: rule id + location + message. Graph findings use
    `file="jaxpr:<program>"` and line 0 — the location is a traced
    program, not a source line."""

    rule: str
    file: str
    line: int
    message: str

    def render(self) -> str:
        loc = f"{self.file}:{self.line}" if self.line else self.file
        return f"{loc}: [{self.rule}] {self.message}"


class Rule:
    """Base: id + one-line doc (the catalogue entry) + docs anchor."""

    id: str = ""
    doc: str = ""
    docs: str = "docs/ANALYSIS.md"


class AstRule(Rule):
    """A rule over parsed source files.

    `roots` are the repo paths the rule scans in repo mode; `dirs`
    optionally narrows to files having one of these path components
    (e.g. host-sync only looks under trainer/serving/samplers). In
    custom-root mode (--root) scoping is dropped — the caller chose the
    tree — matching the old standalone-script semantics.
    """

    roots: Tuple[str, ...] = ("flaxdiff_tpu", "scripts", "train.py",
                              "chip_smoke.py")
    dirs: Tuple[str, ...] = ()

    def applies(self, relpath: str, scoped: bool = True) -> bool:
        if not scoped:
            return True
        parts = relpath.replace(os.sep, "/").split("/")
        under_root = any(
            relpath == r or relpath.startswith(r.rstrip("/") + "/")
            or parts[0] == r for r in self.roots)
        if not under_root:
            return False
        return not self.dirs or any(d in parts for d in self.dirs)

    def check(self, relpath: str, tree: ast.AST,
              src: str) -> List[Finding]:
        raise NotImplementedError


class GraphRule(Rule):
    """A rule over a traced program (a ClosedJaxpr). `check` returns
    (findings, stats) — stats land in the JSON report even when no
    finding fires (the upcast audit is all stats)."""

    def check(self, program: str, closed) -> Tuple[List[Finding], Dict]:
        raise NotImplementedError


AST_RULES: Dict[str, AstRule] = {}
GRAPH_RULES: Dict[str, GraphRule] = {}


def register(rule_cls):
    """Class decorator: instantiate + add to the matching registry."""
    rule = rule_cls()
    if not rule.id:
        raise ValueError(f"rule {rule_cls.__name__} has no id")
    target = GRAPH_RULES if isinstance(rule, GraphRule) else AST_RULES
    if rule.id in AST_RULES or rule.id in GRAPH_RULES:
        raise ValueError(f"duplicate rule id {rule.id!r}")
    target[rule.id] = rule
    return rule_cls


def all_rules() -> Dict[str, Rule]:
    out: Dict[str, Rule] = {}
    out.update(AST_RULES)
    out.update(GRAPH_RULES)
    return out


# ---------------------------------------------------------------------------
# File walking + the AST pass (one parse per file, every rule sees it)
# ---------------------------------------------------------------------------

def iter_py_files(root: str) -> Iterable[str]:
    if os.path.isfile(root):
        yield root
        return
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", ".git")]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def run_ast_rules(rules: Sequence[AstRule], roots: Sequence[str],
                  base: str, scoped: bool = True) -> List[Finding]:
    """Parse each file under `roots` once and run every applicable
    rule. Unparseable files are a finding for every rule that would
    have scanned them — a syntax error must not silently shrink
    coverage."""
    findings: List[Finding] = []
    seen: set = set()
    for root in roots:
        if not os.path.exists(root):
            continue
        for path in iter_py_files(root):
            real = os.path.realpath(path)
            if real in seen:
                continue
            seen.add(real)
            rel = os.path.relpath(path, base).replace(os.sep, "/")
            active = [r for r in rules if r.applies(rel, scoped=scoped)]
            if not active:
                continue
            try:
                with open(path, "r", encoding="utf-8") as f:
                    src = f.read()
                tree = ast.parse(src, filename=path)
            except (OSError, SyntaxError) as e:
                findings.extend(
                    Finding(r.id, rel, 0, f"unparseable: {e}")
                    for r in active)
                continue
            for rule in active:
                findings.extend(rule.check(rel, tree, src))
    return findings


def run_graph_rules(rules: Sequence[GraphRule],
                    programs: Sequence[Tuple[str, object]]
                    ) -> Tuple[List[Finding], Dict[str, Dict]]:
    findings: List[Finding] = []
    stats: Dict[str, Dict] = {}
    for name, closed in programs:
        per_prog = stats.setdefault(name, {})
        for rule in rules:
            found, st = rule.check(name, closed)
            findings.extend(found)
            if st:
                per_prog[rule.id] = st
    return findings, stats


# ---------------------------------------------------------------------------
# Budgets + report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Report:
    findings: List[Finding]                   # everything found
    failures: List[Finding]                   # over-budget (fail CI)
    notes: List[str]                          # shrinkable budgets
    graph_stats: Dict[str, Dict]
    rules_run: List[str]

    @property
    def ok(self) -> bool:
        return not self.failures

    def to_json(self) -> Dict:
        """Stable machine form: sorted, no timestamps, no abs paths —
        byte-identical across runs on an unchanged tree."""
        def row(f: Finding, over: bool) -> Dict:
            return {"rule": f.rule, "file": f.file, "line": f.line,
                    "message": f.message, "over_budget": over}
        over = set(id(f) for f in self.failures)
        return {
            "version": 1,
            "ok": self.ok,
            "rules": {rid: all_rules()[rid].doc
                      for rid in sorted(self.rules_run)},
            "findings": [row(f, id(f) in over)
                         for f in sorted(self.findings)],
            "notes": sorted(self.notes),
            "graph": {k: dict(sorted(v.items()))
                      for k, v in sorted(self.graph_stats.items())},
        }

    def render_text(self, stream=None) -> None:
        stream = stream or sys.stdout
        for note in self.notes:
            print(f"note: {note}", file=stream)
        for prog in sorted(self.graph_stats):
            for rid, st in sorted(self.graph_stats[prog].items()):
                kv = " ".join(f"{k}={v}" for k, v in sorted(st.items()))
                print(f"stat: {prog}: [{rid}] {kv}", file=stream)
        if self.failures:
            for f in sorted(self.failures):
                print(f.render(), file=sys.stderr)
            print(f"\n{len(self.failures)} finding(s) over budget "
                  f"across {len(set(f.rule for f in self.failures))} "
                  f"rule(s) — see docs/ANALYSIS.md for the rule "
                  f"catalogue and the allowlist policy.",
                  file=sys.stderr)
        else:
            n = len(self.rules_run)
            print(f"ok: {n} rule(s) clean "
                  f"({len(self.findings)} finding(s), all within "
                  f"allowlist budgets)" if self.findings else
                  f"ok: {n} rule(s) clean", file=stream)


def apply_budgets(findings: Sequence[Finding],
                  allowlist: Dict[str, Dict[str, int]]
                  ) -> Tuple[List[Finding], List[str]]:
    """Old-gate semantics, generalized: findings group per (rule, file);
    over budget -> every finding in the group fails (each message gains
    the budget context); at/under budget -> pass, with a shrink note
    when the budget has slack."""
    groups: Dict[Tuple[str, str], List[Finding]] = {}
    for f in findings:
        groups.setdefault((f.rule, f.file), []).append(f)
    failures: List[Finding] = []
    notes: List[str] = []
    for (rule, file), hits in sorted(groups.items()):
        budget = allowlist.get(rule, {}).get(file, 0)
        if len(hits) > budget:
            failures.extend(dataclasses.replace(
                h, message=f"{h.message} ({len(hits)} in file, "
                           f"allowlist budget {budget})")
                for h in hits)
        elif len(hits) < budget:
            notes.append(
                f"{file}: {len(hits)} `{rule}` finding(s), budget "
                f"{budget} — shrink the ALLOWLIST entry "
                f"(`scripts/lint.py --tighten`)")
    # budgets for files that no longer have ANY finding are pure slack
    for rule, files in sorted(allowlist.items()):
        for file, budget in sorted(files.items()):
            if budget > 0 and (rule, file) not in groups:
                notes.append(
                    f"{file}: 0 `{rule}` finding(s), budget {budget} — "
                    f"shrink the ALLOWLIST entry "
                    f"(`scripts/lint.py --tighten`)")
    return failures, notes


# ---------------------------------------------------------------------------
# One-call orchestration (the CLI and the tier-1 test drive this)
# ---------------------------------------------------------------------------

def run(rule_ids: Optional[Sequence[str]] = None,
        root: Optional[str] = None,
        docs_path: Optional[str] = None,
        with_graph: bool = True,
        programs: Optional[Sequence[Tuple[str, object]]] = None
        ) -> Report:
    """Run the suite.

    Default (root=None): scan the repo's production roots with the
    central ALLOWLIST and trace the real hot programs. With `root`,
    scan that file/tree with EMPTY allowlists and rule scoping dropped
    (fixture mode — the old standalone-script `--root` contract);
    graph rules then only run when `programs` is passed explicitly.
    """
    # import registers the rules (they live in separate modules so the
    # framework has no jax dependency for pure-AST runs)
    from . import ast_rules as _ast_rules  # noqa: F401
    ids = list(rule_ids) if rule_ids else None
    ast_sel = [r for rid, r in sorted(AST_RULES.items())
               if ids is None or rid in ids]
    # registry instances are singletons: (re)set the docs override every
    # run — None restores the repo default, so a custom --docs run never
    # leaks into the next invocation
    for r in ast_sel:
        if hasattr(r, "docs_path"):
            r.docs_path = docs_path

    if root is not None:
        roots = [root]
        base = (os.path.dirname(os.path.abspath(root)) or "."
                if os.path.isfile(root) else os.path.abspath(root))
        allow: Dict[str, Dict[str, int]] = {}
        scoped = False
    else:
        roots_set: List[str] = []
        for r in ast_sel:
            for rt in r.roots:
                if rt not in roots_set:
                    roots_set.append(rt)
        roots = [os.path.join(REPO_ROOT, rt) for rt in roots_set]
        base, allow, scoped = REPO_ROOT, ALLOWLIST, True

    findings = run_ast_rules(ast_sel, roots, base, scoped=scoped)

    graph_stats: Dict[str, Dict] = {}
    graph_sel: List[GraphRule] = []
    if with_graph and (root is None or programs is not None):
        from . import graph_rules as _graph_rules  # noqa: F401
        from . import shard_rules as _shard_rules  # noqa: F401
        graph_sel = [r for rid, r in sorted(GRAPH_RULES.items())
                     if ids is None or rid in ids]
        if graph_sel:
            if programs is None:
                from .programs import hot_programs, meshed_programs
                programs = list(hot_programs()) + list(meshed_programs())
            gfound, graph_stats = run_graph_rules(graph_sel, programs)
            findings = findings + gfound

    unknown = set(ids or []) - set(r.id for r in ast_sel) \
        - set(r.id for r in graph_sel)
    if unknown:
        raise SystemExit(f"unknown rule id(s): {sorted(unknown)}; "
                         f"known: {sorted(all_rules())}")

    # budget slack for a rule that was not run is not this run's news
    ran = set(r.id for r in ast_sel) | set(r.id for r in graph_sel)
    failures, notes = apply_budgets(
        findings, {rid: files for rid, files in allow.items()
                   if rid in ran})
    return Report(findings=findings, failures=failures, notes=notes,
                  graph_stats=graph_stats,
                  rules_run=[r.id for r in ast_sel]
                  + [r.id for r in graph_sel])


def stable_json(report: Report) -> str:
    return json.dumps(report.to_json(), indent=1, sort_keys=True)
