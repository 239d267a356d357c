"""Source-level rules: the conventions a reviewer can see in the diff.

Four rules, all single-pass over a parsed AST (framework.py parses each
file once and hands the tree to every applicable rule):

  host-sync          no host synchronization outside the blessed seams
                     in the pipelined hot-path packages
  pallas-lane-slice  never lane-slice inside a Pallas kernel body
  silent-except      no `except Exception: pass` (the old
                     scripts/check_bare_except.py gate, absorbed)
  metric-name        every emitted metric name is documented (the old
                     scripts/check_metric_names.py gate, absorbed)
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from .framework import REPO_ROOT, AstRule, Finding, register


# ---------------------------------------------------------------------------
# host-sync: the sync-free-loop contract, statically
# ---------------------------------------------------------------------------

@register
class HostSyncRule(AstRule):
    """Flag host-synchronizing calls in trainer/, serving/ and
    samplers/ outside the blessed seams.

    The pipelined fit loop (PR 5) and the serving scheduler (PR 8) route
    EVERY host sync through module-level seams — `_block_until_ready`,
    `_fetch_losses`, `_fetch_ring`, `_fetch_gate_events`, `_device_get`
    — so counting-mock tests can assert "off-sample steps perform zero
    syncs". A sync added anywhere else re-serializes the pipeline
    silently: it still *works*, it's just slow, which is why it needs a
    static gate rather than a correctness test. Flagged forms:

      jax.device_get(...)   .block_until_ready()   jax.block_until_ready
      .item()               np.asarray(...) / np.array(...)
      float(jnp.f(...)) / int(jnp.f(...))   — compute-then-fetch hiding
                                              the sync in a cast

    `jnp.asarray` is NOT flagged (H2D upload, not a host sync). Cold
    paths (eval, logging, save/load) carry grandfathered budgets in
    framework.ALLOWLIST — route them through a seam and shrink the
    entry.
    """

    id = "host-sync"
    doc = ("host synchronization outside the blessed "
           "_block_until_ready/_fetch_losses/_device_get/_host_asarray "
           "seams in trainer/, serving/, samplers/, data/, parallel/")
    roots = ("flaxdiff_tpu",)
    dirs = ("trainer", "serving", "samplers", "data", "parallel")

    BLESSED = frozenset({"_block_until_ready", "_fetch_losses",
                         "_fetch_ring", "_fetch_gate_events",
                         "_device_get", "_host_asarray"})
    _NP_NAMES = frozenset({"np", "numpy"})

    def check(self, relpath: str, tree: ast.AST,
              src: str) -> List[Finding]:
        findings: List[Finding] = []
        rule = self

        class V(ast.NodeVisitor):
            def __init__(self):
                self.fstack: List[str] = []

            def _in_seam(self) -> bool:
                return any(n in rule.BLESSED for n in self.fstack)

            def visit_FunctionDef(self, node):
                self.fstack.append(node.name)
                self.generic_visit(node)
                self.fstack.pop()

            visit_AsyncFunctionDef = visit_FunctionDef

            def _flag(self, node, what: str):
                findings.append(Finding(
                    rule.id, relpath, node.lineno,
                    f"{what} is a host sync — route it through a "
                    f"blessed seam (docs/ANALYSIS.md `host-sync`)"))

            def visit_Call(self, node):
                if not self._in_seam():
                    f = node.func
                    if isinstance(f, ast.Attribute):
                        if f.attr == "item" and not node.args:
                            self._flag(node, "`.item()`")
                        elif f.attr == "block_until_ready":
                            self._flag(node, "`block_until_ready`")
                        elif f.attr == "device_get":
                            self._flag(node, "`jax.device_get`")
                        elif (f.attr in ("asarray", "array")
                              and isinstance(f.value, ast.Name)
                              and f.value.id in rule._NP_NAMES):
                            self._flag(node, f"`np.{f.attr}` on a "
                                             f"possibly-device value")
                    elif (isinstance(f, ast.Name)
                          and f.id in ("float", "int")
                          and len(node.args) == 1
                          and isinstance(node.args[0], ast.Call)
                          and isinstance(node.args[0].func,
                                         ast.Attribute)
                          and isinstance(node.args[0].func.value,
                                         ast.Name)
                          and node.args[0].func.value.id == "jnp"):
                        self._flag(node, f"`{f.id}(jnp.…)`")
                self.generic_visit(node)

        V().visit(tree)
        return findings


# ---------------------------------------------------------------------------
# pallas-lane-slice: the docs/KERNELS.md kernel convention
# ---------------------------------------------------------------------------

@register
class LaneSliceRule(AstRule):
    """Flag bounded last-axis slicing inside Pallas kernel bodies in
    ops/.

    The TPU vector layout puts the last axis on the 128 lanes; slicing
    it inside a kernel produces the Mosaic lane-resize failures the r3
    attnpad stage hit (`mul got incompatible shapes … (128, 0)` from a
    `pltpu.repeat` resize). The convention (docs/KERNELS.md): resize
    via block specs, `pltpu.repeat`/broadcast from width 1, or
    full-width stores — never `ref[..., a:b]` in the body. Detected
    form: a multi-axis subscript whose LAST element is a bounded slice
    (or a `pl.ds`/`pl.dslice` call) inside a function that looks like a
    kernel body (name ends `_kernel`, or takes `*_ref` params / a
    `*refs` vararg). `ref[0]`, `ref[...]`, `ref[0, 0]` and python-tuple
    slicing (`refs[1:3]`) all pass.
    """

    id = "pallas-lane-slice"
    doc = ("bounded last-axis (lane) slicing inside a Pallas kernel "
           "body in ops/ — resize via block specs, never in-kernel")
    docs = "docs/KERNELS.md"
    roots = ("flaxdiff_tpu",)
    dirs = ("ops",)

    @staticmethod
    def _is_kernel(node: ast.FunctionDef) -> bool:
        if node.name.endswith("_kernel"):
            return True
        args = node.args
        names = [a.arg for a in args.args + args.posonlyargs
                 + args.kwonlyargs]
        if any(n.endswith("_ref") or n == "refs" for n in names):
            return True
        return args.vararg is not None and args.vararg.arg == "refs"

    @staticmethod
    def _bounded_last(index: ast.expr) -> bool:
        if not isinstance(index, ast.Tuple) or len(index.elts) < 2:
            return False
        last = index.elts[-1]
        if isinstance(last, ast.Slice):
            return last.lower is not None or last.upper is not None
        if isinstance(last, ast.Call) \
                and isinstance(last.func, ast.Attribute) \
                and last.func.attr in ("ds", "dslice"):
            return True
        return False

    def check(self, relpath: str, tree: ast.AST,
              src: str) -> List[Finding]:
        findings: List[Finding] = []
        rule = self

        class V(ast.NodeVisitor):
            def __init__(self):
                self.depth = 0      # inside-kernel nesting

            def visit_FunctionDef(self, node):
                is_k = rule._is_kernel(node)
                self.depth += int(is_k)
                self.generic_visit(node)
                self.depth -= int(is_k)

            visit_AsyncFunctionDef = visit_FunctionDef

            def visit_Subscript(self, node):
                if self.depth and rule._bounded_last(node.slice):
                    findings.append(Finding(
                        rule.id, relpath, node.lineno,
                        "bounded slice on the last (lane) axis inside "
                        "a kernel body — use block specs / "
                        "`pltpu.repeat` / full-width stores "
                        "(docs/KERNELS.md, never-lane-slice)"))
                self.generic_visit(node)

        V().visit(tree)
        return findings


# ---------------------------------------------------------------------------
# silent-except (absorbed scripts/check_bare_except.py)
# ---------------------------------------------------------------------------

@register
class SilentExceptRule(AstRule):
    """No NEW silent exception swallowing.

    The observability layer's worst enemy is `except Exception: pass` —
    a failure that leaves no counter, no event, no log line is
    invisible to the telemetry/goodput accounting the repo runs on.
    Fails on handlers catching everything (bare `except`,
    `except Exception`, `except BaseException`) whose body does NOTHING
    (only `pass`/`...`/a docstring). Handlers that log, record an
    event, re-raise, or return a fallback pass; narrow catches may be
    silent. The historical allowlist was emptied in PR 9 — keep it
    empty.
    """

    id = "silent-except"
    doc = ("silent catch-all exception handler (`except Exception: "
           "pass`) — record a resilience event or log before "
           "swallowing")
    docs = "docs/OBSERVABILITY.md"
    roots = ("flaxdiff_tpu", "scripts", "train.py", "chip_smoke.py")

    @staticmethod
    def _catches_everything(handler: ast.ExceptHandler) -> bool:
        if handler.type is None:
            return True
        t = handler.type
        names: List[str] = []
        if isinstance(t, ast.Name):
            names = [t.id]
        elif isinstance(t, ast.Tuple):
            names = [e.id for e in t.elts if isinstance(e, ast.Name)]
        return any(n in ("Exception", "BaseException") for n in names)

    @staticmethod
    def _is_silent(handler: ast.ExceptHandler) -> bool:
        for stmt in handler.body:
            if isinstance(stmt, ast.Pass):
                continue
            if isinstance(stmt, ast.Expr) \
                    and isinstance(stmt.value, ast.Constant):
                continue        # docstring or bare `...`
            return False        # does SOMETHING: logs, records, ...
        return True

    def check(self, relpath: str, tree: ast.AST,
              src: str) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(tree):
            if isinstance(node, ast.ExceptHandler) \
                    and self._catches_everything(node) \
                    and self._is_silent(node):
                what = (ast.unparse(node.type) if node.type else "bare")
                out.append(Finding(
                    self.id, relpath, node.lineno,
                    f"silent `except {what}` with empty body — a "
                    f"swallowed failure is invisible to telemetry "
                    f"(docs/OBSERVABILITY.md)"))
        return out


# ---------------------------------------------------------------------------
# metric-name (absorbed scripts/check_metric_names.py)
# ---------------------------------------------------------------------------

@register
class MetricNameRule(AstRule):
    """Every metric name emitted in `flaxdiff_tpu/` must appear in the
    docs/OBSERVABILITY.md reference table.

    Collects the first argument of every `.counter(...)` / `.gauge(...)`
    / `.histogram(...)` call — string literals exactly, f-strings by
    their leading literal prefix (`f"phase/{name}"` -> wildcard) — and
    checks each against the docs' backtick-quoted names
    (`<placeholder>` segments make an entry a wildcard). Calls whose
    first argument is a plain variable are invisible to the gate
    (re-export loops): their names must arrive through a gated call
    site or be documented by hand.
    """

    id = "metric-name"
    doc = ("metric name emitted in flaxdiff_tpu/ missing from the "
           "docs/OBSERVABILITY.md reference table")
    docs = "docs/OBSERVABILITY.md"
    roots = ("flaxdiff_tpu",)

    INSTRUMENT_METHODS = ("counter", "gauge", "histogram")
    _METRIC_RE = re.compile(r"^[a-z0-9_.<>-]+(/[a-z0-9_.<>-]+)+$")

    def __init__(self):
        self.docs_path: Optional[str] = None    # None -> repo default

    # -- docs side -----------------------------------------------------------
    def documented_names(self) -> Tuple[Set[str], Set[str]]:
        path = self.docs_path or os.path.join(
            REPO_ROOT, "docs", "OBSERVABILITY.md")
        with open(path, "r", encoding="utf-8") as f:
            text = f.read()
        exact: Set[str] = set()
        prefixes: Set[str] = set()
        for span in re.findall(r"`([^`\n]+)`", text):
            span = span.strip()
            if not self._METRIC_RE.match(span):
                continue
            if "<" in span:
                prefixes.add(span.split("<", 1)[0])
            else:
                exact.add(span)
        return exact, prefixes

    @staticmethod
    def is_documented(name: str, is_prefix: bool,
                      exact: Set[str], prefixes: Set[str]) -> bool:
        if not is_prefix:
            return name in exact \
                or any(p and name.startswith(p) for p in prefixes)
        # an f-string emission is covered only by a docs wildcard that
        # contains its literal prefix (or vice versa)
        return any(p and (name.startswith(p) or p.startswith(name))
                   for p in prefixes if name)

    # -- code side -----------------------------------------------------------
    def emitted_names(self, tree: ast.AST
                      ) -> List[Tuple[int, str, bool]]:
        out: List[Tuple[int, str, bool]] = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in self.INSTRUMENT_METHODS
                    and node.args):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) \
                    and isinstance(arg.value, str):
                out.append((node.lineno, arg.value, False))
            elif isinstance(arg, ast.JoinedStr):
                prefix = ""
                for part in arg.values:
                    if isinstance(part, ast.Constant) \
                            and isinstance(part.value, str):
                        prefix += part.value
                    else:
                        break
                out.append((node.lineno, prefix, True))
        return out

    def check(self, relpath: str, tree: ast.AST,
              src: str) -> List[Finding]:
        emitted = self.emitted_names(tree)
        if not emitted:
            return []
        try:
            exact, prefixes = self.documented_names()
        except OSError as e:
            return [Finding(self.id, relpath, 0,
                            f"metric reference docs unreadable: {e}")]
        out: List[Finding] = []
        for lineno, name, is_prefix in emitted:
            if self.is_documented(name, is_prefix, exact, prefixes):
                continue
            shown = f"{name}{{...}}" if is_prefix else name
            out.append(Finding(
                self.id, relpath, lineno,
                f"metric {shown!r} is not in the OBSERVABILITY.md "
                f"reference — add a table row (use <placeholders> "
                f"for dynamic segments)"))
        return out
