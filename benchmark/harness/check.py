"""How `correct` is decided: each number compared is printed beside its
limit, and the run is correct when every number is within its limit.
The limits are the configuration's own (`limits` in its file, one block
per comparison: `train`, `serve`); PERF.md gives the readings each was
set from."""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import numpy as np


def load_limits(cfg: Dict[str, Any], which: str) -> Dict[str, float]:
    """The `which` ("train" or "serve") limits of an effective
    configuration. A configuration that has none was never read at that
    comparison on the chip: the run stops rather than borrow another's.
    (A rehearsal's toy-sized model has leaves of a few elements, whose
    norms swing more than any leaf's at the published widths: its limits
    are the `limits` block of the file's `rehearse` section.)"""
    try:
        return dict(cfg["limits"][which])
    except KeyError:
        raise KeyError(
            f"configuration {cfg['name']!r} states no `limits.{which}`: "
            "read the sound runs' largest and the control's smallest on "
            "the chip first (PERF.md, section 6)") from None


ZERO_GRADIENT = 1e-4     # of the median leaf's gradient norm


def worst_leaf_gap(program_norms, reference_norms,
                   reference_grad_norms=None) -> Tuple[float, str]:
    """The largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger.

    With `reference_grad_norms` (for the parameters' change), leaves
    whose gradient is zero in exact arithmetic are left out: a bias
    added before a softmax over keys, say. Adam divides such a leaf's
    rounding noise by its own size, so its change is full-sized in
    bfloat16 and a tenth of that in float32 (the noise is under Adam's
    epsilon there), and says nothing about the step. They are told by
    the reference's own gradient: under `ZERO_GRADIENT` of the median
    leaf's."""
    import jax
    p_leaves = jax.tree_util.tree_flatten_with_path(program_norms)[0]
    r_leaves = jax.tree_util.tree_leaves(reference_norms)
    ref = np.asarray([float(r) for r in r_leaves], np.float64)
    floor = float(np.median(ref))
    keep = np.ones(len(ref), bool)
    if reference_grad_norms is not None:
        g = np.asarray([float(x) for x in
                        jax.tree_util.tree_leaves(reference_grad_norms)])
        keep = g >= ZERO_GRADIENT * float(np.median(g))
        print(f"check: {int((~keep).sum())} of {len(keep)} leaves have a "
              "gradient that is zero in exact arithmetic and are left out "
              "of the parameter-change comparison", flush=True)
    worst, where = 0.0, ""
    for ((path, p), r), use in zip(zip(p_leaves, ref), keep):
        if not use:
            continue
        gap = abs(float(p) - r) / max(r, floor, 1e-30)
        if not math.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, where = gap, jax.tree_util.keystr(path)
    return float(worst), where


def worst_leaf_difference(program_tree, reference_tree) -> Tuple[float, str]:
    """The largest norm of (program leaf - reference leaf), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Unlike a gap between two norms, which random rounding moves
    only in the second order, this moves in the first: it is the number
    that a lower precision has to fail."""
    import jax
    p_leaves = jax.tree_util.tree_flatten_with_path(program_tree)[0]
    r_leaves = [np.asarray(r, np.float64)
                for r in jax.tree_util.tree_leaves(reference_tree)]
    norms = np.asarray([np.linalg.norm(r) for r in r_leaves])
    floor = float(np.median(norms))
    worst, where = 0.0, ""
    for ((path, p), r), n in zip(zip(p_leaves, r_leaves), norms):
        d = np.linalg.norm(np.asarray(p, np.float64) - r) \
            / max(n, floor, 1e-30)
        if not math.isfinite(d):
            d = float("inf")
        if d >= worst:
            worst, where = d, jax.tree_util.keystr(path)
    return float(worst), where


Row = Tuple[str, str, float, float]      # short name, what, value, limit


def verdict(compared: List[Row]) -> bool:
    """Print each number compared beside its limit and return whether
    all hold."""
    ok = True
    for _, what, value, limit in compared:
        good = bool(math.isfinite(value) and value <= limit)
        ok = ok and good
        print(f"check: {what} = {value:.6g}  limit {limit:.6g}  "
              f"{'ok' if good else 'FAIL'}", flush=True)
    return ok


def as_result(compared: List[Row]) -> Dict[str, Dict[str, float]]:
    """The result line's last key: {short name: {"value", "limit"}}."""
    return {short: {"value": float(value), "limit": float(limit)}
            for short, _, value, limit in compared}
