"""Per-shape flash-attention autotuner with a persistent JSON cache.

The flash kernel's block sizes are one GLOBAL env pair
(FLAXDIFF_FLASH_BLOCK_Q/K) — every (seq, head_dim) the model runs
shares it — and the native-vs-padded head-dim decision is a second
global toggle. This module makes both per-shape. Whether a probed plan
beats the defaults in a step: not measured (ROADMAP D2, D15).

- A registry keyed on ``(seq_q, seq_kv, head_dim, dtype, platform)``.
- On first encounter (and ONLY outside jit — dispatch never probes at
  trace time), measured probes over a block-size ladder pick the
  winner, timed by the chained fwd+bwd grad harness below
  (``chained_grad_ms``).
- Winners persist to a JSON cache dir (the PR-5
  ``--compilation_cache_dir`` pattern): a warm cache re-measures
  NOTHING — the next process loads plans and compiles directly.
- Explicit overrides always win: ``FLAXDIFF_FLASH_BLOCK_Q/K`` and
  ``FLAXDIFF_FLASH_NATIVE_D`` env vars override the corresponding plan
  fields, and block args passed explicitly to ``flash_attention``
  override everything (``_block_sizes`` arg-beats-env rule).
- The native-d decision is part of the plan: shapes whose head_dim is a
  sublane (but not lane) multiple probe the winning blocks with the
  true head dim vs 128-padded and record which is faster.

Activation: ``activate(cache_dir)`` in-process, or the
``FLAXDIFF_FLASH_TUNE_CACHE`` env var (a child process inherits the
tuned cache through it). When inactive, dispatch behavior is exactly
the pre-autotuner env/default path.

Trace-time contract: ``ops/attention.py`` calls ``dispatch_plan`` while
TRACING a jitted model. That call is a pure dict lookup (plus an
observed-shape set add) — probing runs only from ``probe_pending()``,
which callers invoke eagerly (trainer ``autotune_flash`` via a
``jax.eval_shape`` scouting pass).
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Callable, Dict, Optional, Tuple

log = logging.getLogger("flaxdiff_tpu.autotune")

LANES = 128

# the candidate ladder, smallest to largest; 512x1024 is jax's own TPU
# kernel default and this kernel's (DEFAULT_BLOCK_Q/K). Not measured
# against each other on the chip (ROADMAP D2).
DEFAULT_LADDER = ((128, 128), (256, 512), (512, 512), (512, 1024),
                  (1024, 1024))

# probe operand sizing: batch*heads large enough that the grid's
# parallel dimensions hide per-program latency differences the real
# models would also hide
PROBE_BATCH = 4
PROBE_HEADS = 8
PROBE_ITERS = 20

CACHE_FILENAME = "flash_autotune.json"


@dataclasses.dataclass(frozen=True)
class FlashPlan:
    block_q: int
    block_k: int
    native_d: int               # 1 = run the kernel at the true head dim
    source: str                 # "env" | "cache" | "probe" | "default"
    ms: Optional[float] = None  # measured winner time (probe/cache only)


def shape_key(seq_q: int, seq_kv: int, head_dim: int, dtype: str,
              platform: str) -> str:
    return f"q{seq_q}_kv{seq_kv}_d{head_dim}_{dtype}_{platform}"


def chained_grad_ms(grad_fn: Callable, q0, k, v,
                    iters: int = PROBE_ITERS) -> float:
    """Time one attention fwd+bwd via jit(grad): compile+sync first,
    then `iters` steps with each iteration's dq fed into the next q (so
    no execution can be elided), synced by a scalar readback of the
    last dq — a completion barrier by data dependence.
    `grad_fn(q, k, v) -> dq`. The autotuner's probe."""
    import jax
    qi = q0
    float(jax.device_get(grad_fn(qi, k, v).sum()))   # compile + sync
    t0 = time.perf_counter()
    for _ in range(iters):
        qi = grad_fn(qi, k, v)
    float(jax.device_get(qi.sum()))
    return (time.perf_counter() - t0) / iters * 1e3


def _default_probe_fn(seq_q: int, seq_kv: int, head_dim: int, dtype: str,
                      block_q: int, block_k: int, native_d: bool) -> float:
    """Measured probe: the first-party flash kernel fwd+bwd at the
    given blocks, head_dim padded to a lane multiple unless native_d.
    Runs OUTSIDE jit (its own jit(grad) program per candidate)."""
    import jax
    import jax.numpy as jnp

    from .flash_attention import flash_attention

    jdt = jnp.dtype(dtype)
    d = head_dim
    pad = 0 if native_d else (-d) % LANES
    d_run = d + pad
    q = jax.random.normal(jax.random.PRNGKey(0),
                          (PROBE_BATCH, seq_q, PROBE_HEADS, d_run), jdt)
    k = jax.random.normal(jax.random.PRNGKey(1),
                          (PROBE_BATCH, seq_kv, PROBE_HEADS, d_run), jdt)
    v = jax.random.normal(jax.random.PRNGKey(2),
                          (PROBE_BATCH, seq_kv, PROBE_HEADS, d_run), jdt)
    # scale at the TRUE head dim, matching _maybe_pad_head_dim's
    # exact-padding contract
    scale = 1.0 / (d ** 0.5)

    def loss(q_, k_, v_):
        return flash_attention(q_, k_, v_, scale, block_q, block_k,
                               False).astype(jnp.float32).sum()

    g = jax.jit(jax.grad(loss))
    return chained_grad_ms(g, q, k, v)


def _ladder_for(seq_q: int, seq_kv: int, ladder) -> Tuple[Tuple[int, int],
                                                          ...]:
    """Clamp the candidate ladder to the padded sequence lengths and
    dedupe (a 256-token shape collapses most rungs)."""
    rq = -(-seq_q // LANES) * LANES
    rk = -(-seq_kv // LANES) * LANES
    seen, out = set(), []
    for bq, bk in ladder:
        cand = (min(bq, rq), min(bk, rk))
        if cand not in seen:
            seen.add(cand)
            out.append(cand)
    return tuple(out)


def _env_overrides() -> Dict[str, int]:
    out = {}
    for env, field in (("FLAXDIFF_FLASH_BLOCK_Q", "block_q"),
                       ("FLAXDIFF_FLASH_BLOCK_K", "block_k")):
        val = os.environ.get(env)
        if val:
            try:
                out[field] = int(val)
            except ValueError:
                pass
    nat = os.environ.get("FLAXDIFF_FLASH_NATIVE_D")
    if nat is not None:
        out["native_d"] = 1 if nat == "1" else 0
    return out


class FlashAutotuner:
    """Per-shape plan registry + prober + JSON persistence.

    `probe_fn(seq_q, seq_kv, head_dim, dtype, block_q, block_k,
    native_d) -> ms` is injectable so unit tests can count probes with
    a mock; the default runs the measured kernel harness."""

    def __init__(self, cache_dir: Optional[str] = None,
                 probe_fn: Optional[Callable] = None,
                 ladder=DEFAULT_LADDER,
                 platform: Optional[str] = None):
        self.cache_dir = cache_dir
        self.ladder = ladder
        self.probe_fn = probe_fn or _default_probe_fn
        self.probe_count = 0        # total probe_fn invocations (tests)
        self._platform = platform
        self._plans: Dict[str, Dict] = {}
        self._observed: Dict[str, Tuple[int, int, int, str]] = {}
        if cache_dir:
            self._load()

    # -- platform ----------------------------------------------------------
    @property
    def platform(self) -> str:
        if self._platform is None:
            import jax
            self._platform = jax.devices()[0].platform
        return self._platform

    # -- persistence -------------------------------------------------------
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
            # (the GoodputLedger all-or-nothing rule)
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

    # -- lookup ------------------------------------------------------------
    def default_plan(self, seq_q: int, seq_kv: int) -> FlashPlan:
        rq = -(-seq_q // LANES) * LANES
        rk = -(-seq_kv // LANES) * LANES
        from .flash_attention import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q
        return FlashPlan(block_q=min(DEFAULT_BLOCK_Q, rq),
                         block_k=min(DEFAULT_BLOCK_K, rk),
                         native_d=0, source="default")

    def get_plan(self, seq_q: int, seq_kv: int, head_dim: int,
                 dtype: str, allow_probe: bool = False) -> FlashPlan:
        """Resolve a plan: env overrides > cached winner > (optionally)
        a fresh probe > code defaults. Never probes unless
        `allow_probe` — trace-time dispatch lookups stay pure."""
        env = _env_overrides()
        key = shape_key(seq_q, seq_kv, head_dim, dtype, self.platform)
        rec = self._plans.get(key)
        plan = None
        if rec is not None:
            plan = FlashPlan(block_q=int(rec["block_q"]),
                             block_k=int(rec["block_k"]),
                             native_d=int(rec.get("native_d", 0)),
                             source="cache", ms=rec.get("ms"))
        elif allow_probe and ("block_q" not in env
                              or "block_k" not in env):
            plan = self.probe(seq_q, seq_kv, head_dim, dtype)
        if plan is None:
            self._observed.setdefault(
                key, (seq_q, seq_kv, head_dim, dtype))
            plan = self.default_plan(seq_q, seq_kv)
        if env:
            plan = dataclasses.replace(plan, source="env", **env)
        return plan

    def observe(self, seq_q: int, seq_kv: int, head_dim: int,
                dtype: str) -> None:
        """Record a shape seen at trace time for a later
        `probe_pending()` (no device work, no probe)."""
        key = shape_key(seq_q, seq_kv, head_dim, dtype, self.platform)
        if key not in self._plans:
            self._observed.setdefault(
                key, (seq_q, seq_kv, head_dim, dtype))

    # -- probing -----------------------------------------------------------
    def probe(self, seq_q: int, seq_kv: int, head_dim: int,
              dtype: str) -> FlashPlan:
        """Measure the ladder (plus the native-d candidate on the
        winner) and persist the result. Returns the winning plan."""
        results: Dict[str, float] = {}
        best: Optional[Tuple[float, int, int]] = None
        for bq, bk in _ladder_for(seq_q, seq_kv, self.ladder):
            self.probe_count += 1
            try:
                ms = float(self.probe_fn(seq_q, seq_kv, head_dim, dtype,
                                         bq, bk, False))
            except Exception as e:
                # a failing candidate is just not chosen; keep the
                # cause in the cache evidence
                results[f"{bq}x{bk}"] = f"failed: {e!r}"[:200]
                log.warning("flash probe %dx%d failed: %r", bq, bk, e)
                continue
            results[f"{bq}x{bk}"] = ms
            if best is None or ms < best[0]:
                best = (ms, bq, bk)
        if best is None:
            return self.default_plan(seq_q, seq_kv)
        ms, bq, bk = best
        native = 0
        if head_dim % 8 == 0 and head_dim % LANES != 0:
            self.probe_count += 1
            try:
                nat_ms = float(self.probe_fn(seq_q, seq_kv, head_dim,
                                             dtype, bq, bk, True))
                results[f"{bq}x{bk}+native_d"] = nat_ms
                if nat_ms < ms:
                    native, ms = 1, nat_ms
            except Exception as e:  # native path broken here: stay
                # padded, but leave the cause in the cache evidence
                results[f"{bq}x{bk}+native_d"] = f"failed: {e!r}"[:200]
                log.warning("native-d probe failed for d=%d: %r",
                            head_dim, e)
        key = shape_key(seq_q, seq_kv, head_dim, dtype, self.platform)
        self._plans[key] = {
            "seq_q": seq_q, "seq_kv": seq_kv, "head_dim": head_dim,
            "dtype": dtype, "block_q": bq, "block_k": bk,
            "native_d": native, "ms": ms, "probed_ms": results,
        }
        self._observed.pop(key, None)
        self.save()
        return FlashPlan(block_q=bq, block_k=bk, native_d=native,
                         source="probe", ms=ms)

    def probe_pending(self) -> Dict[str, FlashPlan]:
        """Probe every shape observed at trace time that has no cached
        plan. The warm-cache contract: a process whose shapes are all
        cached performs ZERO probes here."""
        out = {}
        for key, (sq, skv, d, dt) in list(self._observed.items()):
            out[key] = self.probe(sq, skv, d, dt)
        return out

    def plans(self) -> Dict[str, Dict]:
        return dict(self._plans)


# ---------------------------------------------------------------------------
# Process-global activation
# ---------------------------------------------------------------------------

_ACTIVE: Optional[FlashAutotuner] = None
_ENV_CHECKED = False


def activate(cache_dir: Optional[str] = None,
             probe_fn: Optional[Callable] = None,
             platform: Optional[str] = None) -> FlashAutotuner:
    """Install a process-global autotuner; dispatch consults it from
    then on. Idempotent per cache_dir."""
    global _ACTIVE
    _ACTIVE = FlashAutotuner(cache_dir=cache_dir, probe_fn=probe_fn,
                             platform=platform)
    return _ACTIVE


def deactivate() -> None:
    global _ACTIVE, _ENV_CHECKED
    _ACTIVE = None
    _ENV_CHECKED = False


def active() -> Optional[FlashAutotuner]:
    """The installed autotuner, auto-activating from
    FLAXDIFF_FLASH_TUNE_CACHE on first use (a child process inherits
    the cache through the env)."""
    global _ACTIVE, _ENV_CHECKED
    if _ACTIVE is None and not _ENV_CHECKED:
        _ENV_CHECKED = True
        env_dir = os.environ.get("FLAXDIFF_FLASH_TUNE_CACHE")
        if env_dir:
            _ACTIVE = FlashAutotuner(cache_dir=env_dir)
    return _ACTIVE


def dispatch_plan(seq_q: int, seq_kv: int, head_dim: int, dtype
                  ) -> Tuple[Optional[int], Optional[int], Optional[bool]]:
    """Trace-time lookup for ops/attention.py: (block_q, block_k,
    native) from the active autotuner's cache, or (None, None, None)
    when no autotuner is installed — dispatch then keeps the exact
    pre-autotuner env/default behavior. Never probes; unseen shapes
    are recorded for `probe_pending()` and run the defaults."""
    aut = active()
    if aut is None:
        return None, None, None
    plan = aut.get_plan(seq_q, seq_kv, head_dim, str(dtype),
                        allow_probe=False)
    if plan.source == "default":
        # defaults == what _block_sizes would pick anyway; returning
        # None keeps explicit-arg/env precedence identical to the
        # inactive path (and records the shape for later probing)
        return None, None, None
    return plan.block_q, plan.block_k, bool(plan.native_d)
