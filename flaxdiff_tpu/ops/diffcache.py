"""Training-free activation cache for DiT-family sampling.

Adjacent sampler timesteps produce highly redundant deep-block
activations (Just-in-Time / DeepCache, PAPERS.md): across one denoising
step the deep trunk's *residual contribution* changes far more slowly
than the input tokens do. A `CachePlan` exploits that without any
retraining: shallow blocks always run, and on non-refresh steps the
deep trunk is replaced by a cached residual delta re-centered on the
fresh shallow activations:

    refresh step:   out = tail(deep(shallow(x)))
                    taps = deep(shallow(x)) - shallow(x)     (recorded)
    cached step:    out = tail(shallow(x) + taps)            (reused)

Everything here is HOST-SIDE and static: the plan is a frozen,
hashable dataclass; its per-step refresh schedule is a numpy bool
array computed once per trajectory and folded into the sampling scan
as an input (`DiffusionSampler._get_program` branches with a
`lax.cond` on the per-step flag — branch-local gating, no host syncs,
no global reductions). Model support is the `cache_mode` forward
contract (models/dit.py, models/uvit.py, models/mmdit.py):

    apply(params, x, t, c, cache_mode="record", cache_split=k)
        -> (out, taps)
    apply(params, x, t, c, cache_mode="reuse",  cache_split=k,
          cache_taps=taps) -> out

See docs/CACHING.md for plan semantics and the measured
quality/latency trade-off table.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class CachePlan:
    """Static per-trajectory refresh schedule + depth split.

    refresh_every   full model evaluation every k-th trajectory step;
                    the steps in between reuse the cached deep delta.
                    1 = refresh every step (bit-identical to no cache,
                    tested), 3 = the default 2x-ish compute cut.
    depth_fraction  fraction of the transformer trunk that ALWAYS runs
                    (the shallow part the reuse step re-centers on).
                    Models map it to a concrete block split with
                    `cache_split_index` (U-shaped models count both
                    sides of the U).
    refresh_head    first N steps always refresh — early steps move the
                    trajectory the most and fill the cache (step 0 is
                    unconditionally a refresh regardless of this knob:
                    the cache starts empty).
    refresh_tail    last N steps always refresh — terminal detail is
                    where reuse error would be most visible.
    """

    enabled: bool = True
    refresh_every: int = 3
    depth_fraction: float = 0.2
    refresh_head: int = 2
    refresh_tail: int = 1

    def __post_init__(self):
        if self.refresh_every < 1:
            raise ValueError("refresh_every must be >= 1")
        if not 0.0 < self.depth_fraction < 1.0:
            raise ValueError("depth_fraction must be in (0, 1)")
        if self.refresh_head < 0 or self.refresh_tail < 0:
            raise ValueError("refresh_head/refresh_tail must be >= 0")

    def flags(self, num_steps: int) -> np.ndarray:
        """[num_steps] bool, True = full evaluation at that trajectory
        step. Step 0 is always True (the cache starts empty); disabled
        plans refresh everywhere."""
        if num_steps < 1:
            raise ValueError("num_steps must be >= 1")
        if not self.enabled:
            return np.ones((num_steps,), dtype=bool)
        idx = np.arange(num_steps)
        flags = (idx % self.refresh_every) == 0
        flags |= idx < max(1, self.refresh_head)
        if self.refresh_tail:
            flags |= idx >= num_steps - self.refresh_tail
        flags[0] = True
        return flags

    def key(self) -> Tuple:
        """Hashable identity for compiled-program cache keys: two
        different plans must never share a program."""
        return ("diffcache", self.enabled, self.refresh_every,
                self.depth_fraction, self.refresh_head,
                self.refresh_tail)

    def reused_fraction(self, num_steps: int) -> float:
        """Fraction of trajectory steps served from the cache."""
        f = self.flags(num_steps)
        return float((~f).sum()) / float(num_steps)


# the serving layer's per-request default when a request asks for
# caching without a specific plan
DEFAULT_CACHE_PLAN = CachePlan()


def active_plan(plan: Optional[CachePlan]) -> Optional[CachePlan]:
    """None unless the plan is present, enabled, and can actually reuse
    something. `refresh_every=1` refreshes every step for ANY
    trajectory length, so the optimal implementation IS the plain
    uncached program — routing it there makes the always-refresh plan
    bit-identical to pre-cache sampling BY CONSTRUCTION at every model
    scale (XLA may tile the cached program's `cond` branches
    differently from the inline program, so running the cached
    machinery with all-True flags is only exact-to-rounding), and
    drops the dead taps carry."""
    if plan is None or not plan.enabled or plan.refresh_every == 1:
        return None
    return plan


def model_supports_cache(model: Any,
                         plan: Optional[CachePlan] = None) -> bool:
    """A model supports the cache when it implements the `cache_mode`
    forward contract AND can actually split at the plan's depth (a
    1-layer DiT has no deep trunk to cache)."""
    if not hasattr(model, "cache_split_index"):
        return False
    frac = (plan.depth_fraction if plan is not None
            else DEFAULT_CACHE_PLAN.depth_fraction)
    try:
        model.cache_split_index(frac)
    except ValueError:
        return False
    return True


def resolve_cache_fns(model: Any, plan: CachePlan
                      ) -> Tuple[Callable, Callable]:
    """(record_fn, reuse_fn) closures over the model's `cache_mode`
    forward for `DiffusionSampler(cache_fns=...)`:

        record_fn(params, x, t, cond) -> (raw, taps)
        reuse_fn(params, x, t, cond, taps) -> raw

    Raises ValueError when the model cannot honor the plan.
    """
    if not hasattr(model, "cache_split_index"):
        raise ValueError(
            f"{type(model).__name__} does not implement the cache_mode "
            f"forward contract (docs/CACHING.md); diffusion caching "
            f"supports the DiT/UDiT/MM-DiT families")
    split = model.cache_split_index(plan.depth_fraction)

    def record_fn(params, x, t, cond):
        return model.apply(params, x, t, cond, cache_mode="record",
                           cache_split=split)

    def reuse_fn(params, x, t, cond, taps):
        return model.apply(params, x, t, cond, cache_mode="reuse",
                           cache_split=split, cache_taps=taps)

    return record_fn, reuse_fn
