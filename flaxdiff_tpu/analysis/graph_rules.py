"""Jaxpr-level rules: invariants only visible in the traced program.

The AST pass sees what a reviewer sees; these rules see what XLA sees.
`programs.py` traces the REAL hot programs (the train step, its
monitored twin, the serving chunk programs) with `jax.make_jaxpr` on
CPU — tracing only, nothing compiles — and each rule walks the jaxpr
recursively the way `profiling.jaxpr_flops` does (pjit / custom-vjp /
remat sub-jaxprs descended, scan bodies multiplied by trip count, cond
branches treated alternatively).

  rng-key-reuse   a PRNG key consumed by >=2 random draws (or split
                  twice) without an intervening split/fold_in — the
                  serving layer's bit-identity contract dies here
                  (two "independent" noises become equal)
  callback-leak   pure_callback / io_callback / debug_callback inside
                  a jitted hot program — each is a host round-trip the
                  sync-free pipeline exists to avoid
  bf16-upcast     budgeted audit of bf16 -> f32 convert_element_type
                  traffic (report, not verdict: deliberate f32
                  accumulation is correct; its TOTAL should only ever
                  change deliberately)
"""
from __future__ import annotations

import itertools
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Tuple

from .framework import (UPCAST_BUDGET, UPCAST_DEFAULT_BUDGET, Finding,
                        GraphRule, register)

# ---------------------------------------------------------------------------
# generic recursive eqn iteration (callback + upcast walkers)
# ---------------------------------------------------------------------------


def _sub_jaxprs(params):
    """Every (closed)jaxpr nested in an eqn's params (the
    profiling._iter_subjaxprs idiom)."""
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):
                yield x.jaxpr          # ClosedJaxpr
            elif hasattr(x, "eqns"):
                yield x                # raw Jaxpr


def iter_eqns(jaxpr, mult: int = 1):
    """Yield (eqn, multiplier) over the whole nest; scan bodies carry
    their trip count, cond branches each yield at the parent multiplier
    (at most one executes — callers wanting max-branch semantics can
    group on branch identity, the audits here just sum, which is the
    conservative direction for "is this present at all")."""
    for eqn in jaxpr.eqns:
        yield eqn, mult
        sub_mult = mult
        if eqn.primitive.name == "scan":
            sub_mult = mult * int(eqn.params.get("length", 1) or 1)
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub, sub_mult)


# ---------------------------------------------------------------------------
# rng-key-reuse: identity tracking through the typed-key primitives
# ---------------------------------------------------------------------------

class _KeyUse:
    """Per-program key-consumption account.

    Tokens identify key VALUES: a fresh token per program input /
    unknown producer, propagated through random_wrap/random_unwrap and
    shape-only ops, and through `slice` by its static start/limit (two
    identical slices of one split result are the same child key; two
    different slices are different children). Consumers:

      draws   random_bits (every jax.random sampler bottoms out here)
      splits  random_split (a second split of the same key yields the
              SAME children — as much a reuse as a double draw)

    random_fold_in derives a fresh key and is NOT a consumption: folding
    one key with distinct data is the sanctioned per-step derivation
    (train_step folds state.rng with the step counter). Folding twice
    with the SAME data is undetectable statically — documented
    limitation.
    """

    def __init__(self):
        self.draws: Counter = Counter()
        self.splits: Counter = Counter()
        self.sites: Dict = defaultdict(list)
        self._fresh = itertools.count()

    def fresh(self, tag: str = "t"):
        return (tag, next(self._fresh))

    def consume(self, tok, kind: str, where: str):
        if tok is None:         # literal operand: no identity to reuse
            return
        (self.draws if kind == "draw" else self.splits)[tok] += 1
        self.sites[tok].append(where)

    def merge_max(self, branches: List["_KeyUse"]) -> None:
        """cond semantics: one branch executes — a key consumed once in
        EACH branch is consumed once, not len(branches) times."""
        for field in ("draws", "splits"):
            mine = getattr(self, field)
            toks = set()
            for b in branches:
                toks |= set(getattr(b, field))
            for tok in toks:
                mine[tok] += max(getattr(b, field).get(tok, 0)
                                 for b in branches)
        for b in branches:
            for tok, sites in b.sites.items():
                self.sites[tok].extend(
                    s for s in sites if s not in self.sites[tok])

    def reused(self) -> List[Tuple[object, int, int]]:
        out = []
        for tok in set(self.draws) | set(self.splits):
            d, s = self.draws.get(tok, 0), self.splits.get(tok, 0)
            if d >= 2 or s >= 2 or (d >= 1 and s >= 1):
                out.append((tok, d, s))
        return out


_PROPAGATE_1IN = frozenset({
    "squeeze", "reshape", "broadcast_in_dim", "transpose", "copy",
    "convert_element_type", "stop_gradient",
})


def _walk_keys(jaxpr, in_toks: List, use: _KeyUse) -> List:
    """Walk one (raw) jaxpr with `in_toks` bound to its invars; returns
    the tokens of its outvars. `use` accumulates consumptions across
    the whole nest."""
    env: Dict = {}

    def bind(var, tok):
        env[var] = tok

    def read(atom):
        # Literal atoms have no identity worth tracking; Vars not yet
        # bound (constvars, values produced by untracked prims) get a
        # stable fresh token on first sight
        if not hasattr(atom, "aval") or type(atom).__name__ == "Literal":
            return None
        if atom not in env:
            env[atom] = use.fresh("var")
        return env[atom]

    for var, tok in zip(jaxpr.invars, in_toks):
        bind(var, tok if tok is not None else use.fresh("in"))
    for var in jaxpr.constvars:
        bind(var, use.fresh("const"))

    def closed_parts(obj):
        """(raw_jaxpr) from a ClosedJaxpr or raw Jaxpr."""
        return obj.jaxpr if hasattr(obj, "consts") else obj

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        ins = [read(v) for v in eqn.invars]
        outs: List = [use.fresh("out") for _ in eqn.outvars]

        if prim in ("random_wrap", "random_unwrap"):
            outs[0] = ins[0]
        elif prim == "random_bits":
            use.consume(ins[0], "draw", prim)
        elif prim == "random_split":
            use.consume(ins[0], "split", prim)
        elif prim == "random_fold_in":
            pass                                    # fresh derivation
        elif prim in _PROPAGATE_1IN and len(ins) >= 1:
            outs[0] = ins[0]
        elif prim == "slice":
            outs[0] = ("slice", ins[0],
                       str(eqn.params.get("start_indices")),
                       str(eqn.params.get("limit_indices")))
        elif prim == "scan":
            body = closed_parts(eqn.params["jaxpr"])
            n_consts = eqn.params.get("num_consts", 0)
            n_carry = eqn.params.get("num_carry", 0)
            length = int(eqn.params.get("length", 1) or 1)
            const_toks = ins[:n_consts]
            carry_toks = ins[n_consts:n_consts + n_carry]
            xs_toks = [use.fresh("xs") for _ in ins[n_consts + n_carry:]]
            before = {t: (use.draws.get(t, 0), use.splits.get(t, 0))
                      for t in const_toks if t is not None}
            sub_out = _walk_keys(body, const_toks + carry_toks + xs_toks,
                                 use)
            if length > 1:
                # a key riding into the body as a loop CONSTANT is the
                # same key every iteration: one in-body consumption is
                # length consumptions
                for t, (d0, s0) in before.items():
                    if use.draws.get(t, 0) > d0:
                        use.consume(t, "draw", "scan-const")
                    if use.splits.get(t, 0) > s0:
                        use.consume(t, "split", "scan-const")
            # scan outs: [carry..., ys...]; carries may propagate a key
            outs = (list(sub_out[:n_carry])
                    + [use.fresh("ys") for _ in outs[n_carry:]])
        elif prim == "while":
            body = closed_parts(eqn.params["body_jaxpr"])
            cn = eqn.params.get("cond_nconsts", 0)
            bn = eqn.params.get("body_nconsts", 0)
            body_ins = ins[cn:cn + bn] + ins[cn + bn:]
            before = {t: (use.draws.get(t, 0), use.splits.get(t, 0))
                      for t in body_ins[:bn] if t is not None}
            _walk_keys(body, body_ins, use)
            # trip count unknown: assume >1 (the conservative read)
            for t, (d0, s0) in before.items():
                if use.draws.get(t, 0) > d0:
                    use.consume(t, "draw", "while-const")
                if use.splits.get(t, 0) > s0:
                    use.consume(t, "split", "while-const")
        elif prim == "cond":
            branches = eqn.params.get("branches", ())
            kids = []
            for br in branches:
                kid = _KeyUse()
                kid._fresh = use._fresh      # disjoint token ids
                _walk_keys(closed_parts(br), ins[1:], kid)
                kids.append(kid)
            if kids:
                use.merge_max(kids)
        else:
            descended = False
            for key in ("jaxpr", "call_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None and (hasattr(sub, "eqns")
                                        or hasattr(sub, "consts")):
                    raw = closed_parts(sub)
                    n = len(raw.invars)
                    sub_out = _walk_keys(raw, ins[:n], use)
                    outs = list(sub_out[:len(outs)]) \
                        + outs[len(sub_out):]
                    descended = True
                    break
            if not descended:
                # untracked primitive: outputs are fresh (identity lost
                # — e.g. manual uint32 arithmetic on a key defeats the
                # analyzer, by design: that code deserves review anyway)
                pass

        for var, tok in zip(eqn.outvars, outs):
            # a None token (literal-valued sub-output) must not alias
            # every other None — give it its own identity
            bind(var, tok if tok is not None else use.fresh("out"))

    return [read(v) for v in jaxpr.outvars]


@register
class RngReuseRule(GraphRule):
    """Detect PRNG key reuse in a traced program (see _KeyUse)."""

    id = "rng-key-reuse"
    doc = ("a PRNG key consumed by >=2 random draws/splits without an "
           "intervening split/fold_in in a traced hot program")

    def check(self, program: str, closed) -> Tuple[List[Finding], Dict]:
        use = _KeyUse()
        jaxpr = closed.jaxpr
        _walk_keys(jaxpr, [use.fresh("in") for _ in jaxpr.invars], use)
        findings = []
        for tok, d, s in sorted(use.reused(), key=str):
            sites = ",".join(use.sites.get(tok, [])[:6])
            findings.append(Finding(
                self.id, f"jaxpr:{program}", 0,
                f"PRNG key reused: {d} random draw(s) + {s} split(s) "
                f"of one key value (sites: {sites}) — derive fresh "
                f"keys with split/fold_in; reuse breaks the serving "
                f"layer's bit-identity and silently correlates noise"))
        return findings, {"keys_drawn": sum(use.draws.values()),
                          "keys_split": sum(use.splits.values()),
                          "reused": len(findings)}


# ---------------------------------------------------------------------------
# callback-leak
# ---------------------------------------------------------------------------

# jax 0.9 gives jax.debug.print its own primitive (debug_print); only
# jax.debug.callback still traces to debug_callback
_CALLBACK_PRIMS = frozenset({"pure_callback", "io_callback",
                             "debug_callback", "debug_print"})


@register
class CallbackLeakRule(GraphRule):
    """No host callbacks inside jitted hot programs."""

    id = "callback-leak"
    doc = ("pure_callback/io_callback/debug_callback primitive inside "
           "a traced hot program — each dispatch is a host round-trip")

    def check(self, program: str, closed) -> Tuple[List[Finding], Dict]:
        findings: List[Finding] = []
        count = 0
        for eqn, mult in iter_eqns(closed.jaxpr):
            if eqn.primitive.name in _CALLBACK_PRIMS:
                count += mult
                findings.append(Finding(
                    self.id, f"jaxpr:{program}", 0,
                    f"`{eqn.primitive.name}` inside the jitted program "
                    f"(x{mult} per execution counting scan trips) — "
                    f"host work belongs outside the program, behind "
                    f"the module seams"))
        return findings, {"callbacks": count}


# ---------------------------------------------------------------------------
# bf16-upcast audit
# ---------------------------------------------------------------------------

@register
class UpcastAuditRule(GraphRule):
    """Budgeted bf16 -> f32 `convert_element_type` audit."""

    id = "bf16-upcast"
    doc = ("bf16->f32 upcast traffic in a traced hot program exceeds "
           "its budget (framework.UPCAST_BUDGET) — deliberate f32 "
           "accumulation is fine, silent growth is not")

    @staticmethod
    def _numel(aval) -> int:
        n = 1
        for s in aval.shape:
            n *= int(s)
        return n

    def check(self, program: str, closed) -> Tuple[List[Finding], Dict]:
        casts = elements = 0
        for eqn, mult in iter_eqns(closed.jaxpr):
            if eqn.primitive.name != "convert_element_type":
                continue
            src = getattr(eqn.invars[0], "aval", None)
            new = eqn.params.get("new_dtype")
            if src is None or new is None:
                continue
            if str(src.dtype) == "bfloat16" and str(new) == "float32":
                casts += mult
                elements += mult * self._numel(eqn.outvars[0].aval)
        budget = UPCAST_BUDGET.get(program, UPCAST_DEFAULT_BUDGET)
        findings: List[Finding] = []
        if elements > budget:
            findings.append(Finding(
                self.id, f"jaxpr:{program}", 0,
                f"bf16->f32 upcasts moved {elements} elements "
                f"({casts} casts) against a budget of {budget} — "
                f"raise the budget deliberately or drop the casts"))
        stats = {"casts": casts, "elements": elements}
        if program in UPCAST_BUDGET:
            stats["budget"] = budget
        return findings, stats
