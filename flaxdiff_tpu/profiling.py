"""Profiling and MFU accounting.

The reference has no profiling at all (reference trainer/simple_trainer.py
logs wall-clock epoch time only; no jax.profiler anywhere) — this module is
the TPU-native observability layer SURVEY §5.1 calls for: per-step FLOPs
from XLA's own cost model, model-FLOPs-utilization against the chip's peak,
and `jax.profiler` trace capture for xplane/perfetto inspection.

Usage:
    flops = compiled_flops(jitted_step, state, batch)   # per-device FLOPs
    meter = MFUMeter(flops_per_step=flops)
    with meter.step():                                  # times one step
        loss = step(...)
    meter.mfu()                                         # fraction of peak

    with trace("/tmp/trace"):                           # profiler capture
        run_steps()
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Optional

import jax

# Peak dense matmul throughput per chip, FLOP/s. bf16 (the MXU-native
# dtype this framework trains in). Public numbers from Google's TPU
# system documentation.
_PEAK_FLOPS_BF16 = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p (kind string "TPU v5")
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e / Trillium
    "TPU v6e": 918e12,
    "TPU7x": 2307e12,
}


def peak_for_kind(table: dict, kind: str, what: str) -> Optional[float]:
    """Exact-key lookup of a per-chip peak. A TPU kind the table does
    not list is an error (a prefix match would hand an unknown
    "TPU v5..." the v5p peak and every utilization after it would be
    wrong); a non-TPU kind has no peak, so utilization is unreportable
    there rather than wrong."""
    if kind in table:
        return table[kind]
    if kind.startswith("TPU"):
        raise KeyError(f"no {what} entry for device_kind {kind!r}: add "
                       "the published figure and its source to the table")
    return None


def device_peak_flops(device: Optional[Any] = None) -> Optional[float]:
    """Peak bf16 FLOP/s of `device` (default: first local device); None
    off-TPU (e.g. CPU test meshes), KeyError for an unlisted TPU."""
    if device is None:
        device = jax.local_devices()[0]
    return peak_for_kind(_PEAK_FLOPS_BF16,
                         getattr(device, "device_kind", ""),
                         "peak bf16 FLOP/s")


def compiled_flops(jitted_fn, *args, **kwargs) -> Optional[float]:
    """Per-device FLOPs of one execution of `jitted_fn(*args, **kwargs)`.

    Uses XLA's cost analysis on the compiled executable — the same numbers
    the compiler schedules against, so rematerialization (jax.checkpoint)
    and fusion decisions are included, unlike hand-derived analytic counts.
    Under SPMD jit the executable is the per-device program, so the figure
    is already per-chip. Returns None only when the backend's analysis
    carries no flops entry; a failed lowering or compile raises.
    """
    compiled = jitted_fn.lower(*args, **kwargs).compile()
    cost = compiled.cost_analysis() or {}
    flops = cost.get("flops")
    return float(flops) if flops and flops > 0 else None


def _dot_general_flops(eqn) -> float:
    lhs = eqn.invars[0].aval
    rhs = eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = 1.0
    for i in lb:
        batch *= lhs.shape[i]
    contract = 1.0
    for i in lc:
        contract *= lhs.shape[i]
    m = 1.0
    for i in range(len(lhs.shape)):
        if i not in lc and i not in lb:
            m *= lhs.shape[i]
    n = 1.0
    for i in range(len(rhs.shape)):
        if i not in rc and i not in rb:
            n *= rhs.shape[i]
    return 2.0 * batch * m * n * contract


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    n_out = 1.0
    for s in out.shape:
        n_out *= s
    # kernel: rhs_spec = (out_ch_dim, in_ch_dim, *spatial_dims)
    in_ch_per_group = rhs.shape[dn.rhs_spec[1]]
    k_spatial = 1.0
    for i in dn.rhs_spec[2:]:
        k_spatial *= rhs.shape[i]
    return 2.0 * n_out * in_ch_per_group * k_spatial


def _iter_subjaxprs(params):
    """Yield every (closed)jaxpr nested in an eqn's params."""
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):   # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):                            # raw Jaxpr
                yield x


def jaxpr_flops(jaxpr) -> float:
    """Matmul+conv FLOPs of a jaxpr with TRUE (unpadded) shapes.

    The analytic "model FLOPs" counter VERDICT r2 weak #2 calls for:
    `compiled_flops` reads XLA's cost analysis of the program that actually
    runs, which includes padding work (e.g. the flash path's head_dim
    64->128 lane pad) and rematerialized recompute — honest about the
    hardware, inflated as a *model* FLOPs numerator. This walks the traced
    jaxpr instead, counting only dot_general / conv_general_dilated at
    their traced shapes (the standard model-FLOPs convention: elementwise
    and softmax work excluded). Trace the step with the "xla" attention
    backend so attention isn't hidden inside an opaque pallas_call.

    Recurses into nested jaxprs (pjit, custom_vjp, remat); scan bodies are
    multiplied by trip count; cond counts the most expensive branch.
    """
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_general_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "cond":
            branches = eqn.params.get("branches", ())
            total += max((jaxpr_flops(b.jaxpr) for b in branches),
                         default=0.0)
        else:
            mult = eqn.params.get("length", 1) if name == "scan" else 1
            for sub in _iter_subjaxprs(eqn.params):
                total += mult * jaxpr_flops(sub)
    return total


def traced_model_flops(fn, *args, **kwargs) -> float:
    """`jaxpr_flops` of `fn(*args, **kwargs)` (abstract trace, no device).

    Per-call FLOPs at true shapes. NOTE: pallas_call bodies are opaque to
    tracing — call this on a variant of the program whose attention uses
    the "xla" backend to get the full model count."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return jaxpr_flops(closed.jaxpr)


def mfu(flops_per_step: float, step_time_s: float,
        peak_flops: Optional[float] = None) -> Optional[float]:
    """Model FLOPs utilization: achieved FLOP/s over peak FLOP/s."""
    if peak_flops is None:
        peak_flops = device_peak_flops()
    if not peak_flops or step_time_s <= 0:
        return None
    return flops_per_step / step_time_s / peak_flops


class MFUMeter:
    """Accumulates step timings and reports throughput + MFU.

    `flops_per_step` is per-device FLOPs (from `compiled_flops`); timings
    are wall-clock per step. Call `.observe(dt)` or use `.step()` as a
    context manager around one synchronous step."""

    def __init__(self, flops_per_step: Optional[float] = None,
                 peak_flops: Optional[float] = None):
        self.flops_per_step = flops_per_step
        self.peak_flops = peak_flops if peak_flops is not None \
            else device_peak_flops()
        self.total_time = 0.0
        self.steps = 0

    def observe(self, dt: float, steps: int = 1):
        self.total_time += dt
        self.steps += steps

    @contextlib.contextmanager
    def step(self):
        t0 = time.perf_counter()
        yield
        self.observe(time.perf_counter() - t0)

    def mean_step_time(self) -> Optional[float]:
        return self.total_time / self.steps if self.steps else None

    def mfu(self) -> Optional[float]:
        dt = self.mean_step_time()
        if dt is None or self.flops_per_step is None:
            return None
        return mfu(self.flops_per_step, dt, self.peak_flops)

    def achieved_tflops(self) -> Optional[float]:
        dt = self.mean_step_time()
        if dt is None or self.flops_per_step is None:
            return None
        return self.flops_per_step / dt / 1e12

    def reset(self):
        self.total_time = 0.0
        self.steps = 0


@contextlib.contextmanager
def trace(logdir: str, host_tracer_level: int = 2):
    """jax.profiler capture around a block; view with xprof/tensorboard
    or perfetto. Degrades to a no-op context if the profiler cannot
    start (e.g. a second concurrent trace) — but records a
    `trace_failed` resilience event either way, because "the profile I
    asked for silently doesn't exist" is undiagnosable after the run
    (the pre-telemetry bare `except: pass` here was exactly that)."""
    started = False
    try:
        jax.profiler.start_trace(logdir)
        started = True
    except Exception as e:  # noqa: BLE001 — degrade, but visibly
        from .resilience.events import record_event
        record_event("trace_failed", "profiler.start_trace",
                     detail=f"{type(e).__name__}: {e} (logdir={logdir})")
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001 — degrade, but visibly
                from .resilience.events import record_event
                record_event("trace_failed", "profiler.stop_trace",
                             detail=f"{type(e).__name__}: {e} "
                                    f"(logdir={logdir})")
