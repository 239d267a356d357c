"""Spatial token cache: training-free token-level reuse for DiT sampling.

The timestep cache (ops/diffcache.py) reuses the deep trunk's residual
delta across *steps*; this module adds the *space* axis (Just-in-Time
training-free spatial acceleration, PAPERS.md): on cached steps most
tokens barely change, so only the highest-change tokens re-enter the
deep trunk. A `SpatialPlan` composes with a `CachePlan` into one
static `ComposedPlan` whose per-step behavior is a host-side code row:

    code 2  refresh  full deep trunk on every token, taps + score
                     reference re-recorded (the PR-10 record step)
    code 1  spatial  shallow runs on all tokens; a STATIC-size top-k of
                     per-token change scores (vs. the shallow
                     activations recorded when each token's taps entry
                     was last refreshed) selects the tokens that run
                     the deep trunk; their taps/reference entries are
                     scattered back, every other token reuses its
                     cached delta
    code 0  reuse    pure timestep reuse (the PR-10 cached step)

Everything stays static and in-graph: k = round(keep_fraction * L) is
a trace-time constant (no dynamic-shape gathers), selection is
`lax.top_k` + gather/scatter with static shapes, and the per-step
decision is a scalar `lax.switch` on the code row — branch-local, zero
host syncs, so the plan folds into the same compiled-program caches
the timestep cache uses (`DiffusionSampler._get_program`, the serving
engine) and warm traffic never re-traces.

Model support is two extra `cache_mode` forward values on top of the
PR-10 contract (models/dit.py, models/uvit.py, models/mmdit.py):

    apply(..., cache_mode="record_ref", cache_split=k)
        -> (out, taps, ref)             # ref = trunk-input activations
    apply(..., cache_mode="spatial", cache_split=k, cache_taps=taps,
          cache_ref=ref, cache_keep=f, cache_metric=m)
        -> (out, taps, ref)

Token selection is batch-shared (scores averaged over the batch axis):
one index vector serves the whole block — under CFG the cond/uncond
halves refresh the same tokens, and the RoPE tables gather to plain
[k, d/2] tables that flow through the existing attention path.

See docs/CACHING.md for plan semantics and the measured speedup/PSNR
trade-off table.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .diffcache import CachePlan, active_plan, resolve_cache_fns

# per-step behavior codes shared by the host schedule and the compiled
# programs' `lax.switch` branch order: (reuse, spatial, record)
CODE_REUSE = 0
CODE_SPATIAL = 1
CODE_REFRESH = 2

METRICS = ("l2", "linf")


@dataclasses.dataclass(frozen=True)
class SpatialPlan:
    """Static token-level reuse policy for the cached steps.

    keep_fraction  fraction of tokens that re-enter the deep trunk on a
                   spatial step (k = max(1, round(f * num_tokens)),
                   fixed at trace time). 1.0 disables the spatial axis:
                   refreshing every token is the timestep cache's
                   record step, so the plan routes to the EXISTING
                   timestep-cached program byte-for-byte.
    metric         per-token change score between the fresh shallow
                   activations and the reference recorded when the
                   token's cache entry was last refreshed:
                   "l2" (mean squared change over channels, default) or
                   "linf" (max absolute change).
    every          spatial-refresh cadence among the cached steps,
                   counted from the last full refresh (the alignment
                   with the CachePlan schedule): 1 = every cached step
                   runs the top-k partial refresh, 2 = every other
                   (the rest are pure timestep reuse), ...
    """

    enabled: bool = True
    keep_fraction: float = 0.25
    metric: str = "l2"
    every: int = 1

    def __post_init__(self):
        if not 0.0 < self.keep_fraction <= 1.0:
            raise ValueError("keep_fraction must be in (0, 1]")
        if self.metric not in METRICS:
            raise ValueError(f"unknown metric {self.metric!r}; "
                             f"one of {METRICS}")
        if self.every < 1:
            raise ValueError("every must be >= 1")

    def key(self) -> Tuple:
        return ("spatialcache", self.enabled, self.keep_fraction,
                self.metric, self.every)


@dataclasses.dataclass(frozen=True)
class ComposedPlan:
    """One static plan over both reuse axes: the timestep `CachePlan`
    decides WHEN the deep trunk fully refreshes, the `SpatialPlan`
    decides WHICH tokens partially refresh in between. Frozen and
    hashable; `key()` feeds the sampler and serving program caches so
    two plans never share a compiled program."""

    cache: CachePlan = dataclasses.field(default_factory=CachePlan)
    spatial: SpatialPlan = dataclasses.field(default_factory=SpatialPlan)

    def __post_init__(self):
        if not isinstance(self.cache, CachePlan):
            raise ValueError("ComposedPlan.cache must be a CachePlan")
        if not isinstance(self.spatial, SpatialPlan):
            raise ValueError(
                "ComposedPlan.spatial must be a SpatialPlan")

    @property
    def enabled(self) -> bool:
        return self.cache.enabled

    @property
    def depth_fraction(self) -> float:
        return self.cache.depth_fraction

    def key(self) -> Tuple:
        return ("composed", self.cache.key(), self.spatial.key())

    def step_codes(self, num_steps: int) -> np.ndarray:
        """[num_steps] int32 of CODE_* values — host-side numpy, the
        spatial analogue of `CachePlan.flags` and, like it, folded into
        the compiled scan as an input row."""
        flags = self.cache.flags(num_steps)
        codes = np.zeros((num_steps,), np.int32)
        codes[flags] = CODE_REFRESH
        since = 0
        for i in range(num_steps):
            if flags[i]:
                since = 0
                continue
            since += 1
            if since % self.spatial.every == 0:
                codes[i] = CODE_SPATIAL
        return codes

    def counts(self, num_steps: int) -> dict:
        codes = self.step_codes(num_steps)
        return {"refresh": int((codes == CODE_REFRESH).sum()),
                "spatial": int((codes == CODE_SPATIAL).sum()),
                "reused": int((codes == CODE_REUSE).sum())}


# the serving layer's default when a request asks for composed caching
# without a specific plan. The spatial axis buys a much sparser
# full-refresh cadence than the pure-timestep default can afford:
# between full refreshes, every other cached step re-runs the deep
# trunk on the top-1/8 highest-change tokens, the rest reuse
# (trajectory PSNR against the uncached run: docs/CACHING.md trade-off
# table). Speed: not measured on a chip (cell
# `dit-xl-2.generate-cached`, PERF.md section 7).
DEFAULT_SPATIAL_PLAN = SpatialPlan(keep_fraction=0.125, every=2)
DEFAULT_COMPOSED_PLAN = ComposedPlan(
    cache=CachePlan(refresh_every=16, depth_fraction=0.2,
                    refresh_head=2, refresh_tail=1),
    spatial=DEFAULT_SPATIAL_PLAN)


def active_spatial(spatial: Optional[SpatialPlan]
                   ) -> Optional[SpatialPlan]:
    """None unless the spatial axis can actually skip something:
    keep_fraction=1.0 refreshes every token, which IS the timestep
    cache's record step — routing it away keeps the keep-1.0 plan on
    the existing timestep-cached program byte-for-byte (tested)."""
    if spatial is None or not spatial.enabled \
            or spatial.keep_fraction >= 1.0:
        return None
    return spatial


def resolve_plan(plan: Any) -> Union[None, CachePlan, ComposedPlan]:
    """Normalize any per-request cache knob to the program that
    actually serves it: None (uncached), a `CachePlan` (the PR-10
    timestep-cached program, byte-for-byte), or a `ComposedPlan` (both
    axes). A bare `SpatialPlan` composes with the default `CachePlan`.
    Degenerate axes fall off one at a time: spatial disabled / keep 1.0
    drops to the timestep program; refresh_every=1 (never any cached
    step for the spatial axis to act on) drops to the uncached one."""
    if plan is None:
        return None
    if isinstance(plan, SpatialPlan):
        plan = ComposedPlan(spatial=plan)
    if isinstance(plan, ComposedPlan):
        base = active_plan(plan.cache)
        if base is None:
            return None
        spatial = active_spatial(plan.spatial)
        if spatial is None:
            return base
        if plan.cache is base and plan.spatial is spatial:
            return plan
        return ComposedPlan(cache=base, spatial=spatial)
    return active_plan(plan)


# ---------------------------------------------------------------------------
# In-graph selection helpers (shared by the three model families)
# ---------------------------------------------------------------------------

def token_change_scores(h: jax.Array, ref: jax.Array,
                        metric: str) -> jax.Array:
    """[L] batch-shared per-token change score between fresh trunk
    inputs `h` and the recorded reference `ref` (both [B, L, C]).
    Batch-shared (mean over B) so one static index vector serves the
    whole block — under CFG the cond/uncond halves stay aligned."""
    d = (h - ref).astype(jnp.float32)
    if metric == "l2":
        per = jnp.mean(d * d, axis=-1)
    elif metric == "linf":
        per = jnp.max(jnp.abs(d), axis=-1)
    else:
        raise ValueError(f"unknown metric {metric!r}")
    return jnp.mean(per, axis=0)


def spatial_k(num_tokens: int, keep_fraction: float) -> int:
    """Static top-k size: trace-time constant, never a traced value."""
    return max(1, min(num_tokens, round(num_tokens * keep_fraction)))


def select_tokens(h: jax.Array, ref: jax.Array, keep_fraction: float,
                  metric: str) -> jax.Array:
    """[k] indices of the highest-change tokens (static k). Tokens
    whose cache entries go stale accumulate change against their
    frozen reference, so every token is eventually re-selected —
    starvation-free by construction."""
    scores = token_change_scores(h, ref, metric)
    k = spatial_k(h.shape[1], keep_fraction)
    _, idx = jax.lax.top_k(scores, k)
    return idx


def gather_tokens(x: jax.Array, idx: jax.Array) -> jax.Array:
    """[B, L, C] -> [B, k, C] with a shared [k] index vector."""
    return jnp.take(x, idx, axis=1)


def scatter_tokens(full: jax.Array, idx: jax.Array,
                   values: jax.Array) -> jax.Array:
    """Write [B, k, C] `values` into `full` at token positions `idx`
    (static shapes throughout; XLA scatter, no host round-trip)."""
    return full.at[:, idx, :].set(values)


def gather_freqs(freqs: Optional[Tuple[jax.Array, jax.Array]],
                 idx: jax.Array
                 ) -> Optional[Tuple[jax.Array, jax.Array]]:
    """Gather RoPE (cos, sin) tables to the selected token positions so
    attention inside the gathered deep trunk rotates each token by its
    TRUE position, not its position within the subset."""
    if freqs is None:
        return None
    cos, sin = freqs
    return cos[idx], sin[idx]


# ---------------------------------------------------------------------------
# Model-facing closures
# ---------------------------------------------------------------------------

class ComposedCacheFns(NamedTuple):
    """The model's cache_mode forwards, closed over one ComposedPlan,
    for `DiffusionSampler(cache_fns=...)`:

        record(params, x, t, cond) -> (raw, taps)
        reuse(params, x, t, cond, taps) -> raw
        record_ref(params, x, t, cond) -> (raw, taps, ref)
        spatial(params, x, t, cond, taps, ref) -> (raw, taps, ref)
    """
    record: Callable
    reuse: Callable
    record_ref: Callable
    spatial: Callable


def resolve_composed_fns(model: Any, plan: ComposedPlan
                         ) -> ComposedCacheFns:
    """Closures over the model's `cache_mode` forward for a composed
    plan. Raises ValueError when the model cannot honor the plan (no
    cache contract / unsplittable trunk), same gate as
    `diffcache.resolve_cache_fns`."""
    record, reuse = resolve_cache_fns(model, plan.cache)
    split = model.cache_split_index(plan.cache.depth_fraction)
    keep = plan.spatial.keep_fraction
    metric = plan.spatial.metric

    def record_ref_fn(params, x, t, cond):
        return model.apply(params, x, t, cond, cache_mode="record_ref",
                           cache_split=split)

    def spatial_fn(params, x, t, cond, taps, ref):
        return model.apply(params, x, t, cond, cache_mode="spatial",
                           cache_split=split, cache_taps=taps,
                           cache_ref=ref, cache_keep=keep,
                           cache_metric=metric)

    return ComposedCacheFns(record=record, reuse=reuse,
                            record_ref=record_ref_fn,
                            spatial=spatial_fn)
