"""Sampler engine: every sampler runs under ONE compiled lax.scan.

Capability parity with reference flaxdiff/samplers/common.py:60-433
(DiffusionSampler: CFG batching, timestep spacing, generate_samples) but
TPU-native: the reference drives a host-side Python loop with one jit
dispatch per step (samplers/common.py:376-389); here the full trajectory
— CFG doubling, the sampler update, even multi-NFE steps and multistep
history — lives inside a single lax.scan, so N-step inference is one XLA
program with zero host round-trips.

Unified step space: samplers update in the VE-ified coordinates
x_hat = x / signal(t), sigma_hat = sigma(t) / signal(t); this makes one
step function exact for both VP (discrete/cosine) and VE (Karras/EDM)
schedules (the reference implements each sampler against a specific
schedule family instead).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
from jax.extend.core import Literal

from ..predictors import PredictionTransform
from ..profiling import _iter_subjaxprs
from ..schedulers.common import NoiseSchedule, bcast_right
from ..typing import PRNGKey
from ..utils import RngSeq, clip_images


# --------------------------------------------------------------------------
# Timestep spacing strategies (reference samplers/common.py:184-243)
# --------------------------------------------------------------------------

def get_timestep_spacing(method: str, num_steps: int, timesteps: int,
                         start: Optional[float] = None,
                         end: float = 0.0, rho: float = 7.0,
                         schedule: Optional[NoiseSchedule] = None
                         ) -> jnp.ndarray:
    """Return [num_steps+1] descending step values in the schedule's domain,
    ending at `end` (terminal). method: linear|quadratic|karras|exponential.

    "karras" is rho-spacing in SIGMA domain (Karras et al. 2022 eq. 5:
    sigma_i = (sigma_max^(1/rho) + i/N (sigma_min^(1/rho) -
    sigma_max^(1/rho)))^rho), which is what the reference computes
    (reference samplers/common.py:210-227) — it needs the schedule to map
    sigma back to t. Pass a SigmaSchedule (exposing sigmas /
    timesteps_from_sigmas); without one, rho-spacing falls back to the
    t-domain approximation (exact only for schedules whose sigma is
    already a rho-power of t)."""
    hi = float(timesteps - 1) if start is None else float(start)
    lo = float(end)
    if method == "linear":
        steps = jnp.linspace(hi, lo, num_steps + 1)
    elif method == "quadratic":
        steps = jnp.linspace(hi ** 0.5, lo ** 0.5, num_steps + 1) ** 2
    elif method == "exponential":
        steps = jnp.exp(jnp.linspace(jnp.log(hi + 1.0), jnp.log(lo + 1.0),
                                     num_steps + 1)) - 1.0
    elif method == "karras":
        inv = 1.0 / rho
        if schedule is not None and hasattr(schedule, "sigmas") \
                and hasattr(schedule, "timesteps_from_sigmas"):
            # sigma-domain rho spacing, mapped back through the
            # schedule's inverse (the reference's semantics)
            sig_hi = schedule.sigmas(jnp.asarray(hi))
            sig_lo = schedule.sigmas(jnp.asarray(lo))
            sig = (jnp.linspace(sig_hi ** inv, sig_lo ** inv,
                                num_steps + 1)) ** rho
            steps = schedule.timesteps_from_sigmas(sig)
        else:
            # t-domain approximation (round-1 behavior); exact when
            # sigma(t) is itself a rho-power ramp (KarrasVE schedules)
            steps = (jnp.linspace((hi + 1.0) ** inv, (lo + 1.0) ** inv,
                                  num_steps + 1)) ** rho - 1.0
    else:
        raise ValueError(f"Unknown timestep spacing {method!r}")
    # Pin the endpoints analytically: the nonlinear spacings round-trip
    # hi/lo through f32 powers/logs (and the karras sigma inverse), so
    # the first value can drift ABOVE the schedule domain (999.0002 for
    # timesteps=1000) and the terminal can miss `end` — at few-step
    # trajectories (num_steps 1-3) that drift is the whole step budget.
    steps = steps.at[0].set(hi).at[-1].set(lo)
    return steps


# --------------------------------------------------------------------------
# Sampler step functions
# --------------------------------------------------------------------------

class Sampler(flax.struct.PyTreeNode):
    """A sampler is a pure step function over the VE-ified state.

    `step` receives `denoise(x, t) -> (x0_hat, eps_hat)` so higher-order
    samplers can take extra NFEs inside the scanned step.
    """

    def init_state(self, x: jax.Array) -> Any:
        """Extra scan carry (e.g. multistep history). Default: none."""
        return ()

    def step(self, denoise: Callable, x: jax.Array, t_cur: jax.Array,
             t_next: jax.Array, key: PRNGKey, state: Any,
             schedule: NoiseSchedule, step_index: jax.Array) -> Tuple[jax.Array, Any]:
        raise NotImplementedError

    # helpers ---------------------------------------------------------------
    @staticmethod
    def _coords(schedule: NoiseSchedule, t: jax.Array, ndim: int):
        signal, sigma = schedule.rates(t)
        signal = bcast_right(signal, ndim)
        sigma = bcast_right(sigma, ndim)
        return signal, sigma / jnp.maximum(signal, 1e-12)


# --------------------------------------------------------------------------
# How a program reads its parameters
# --------------------------------------------------------------------------

# equations that run ONE jaxpr on their own operands, in order (`remat2`
# is `jax.checkpoint`; a name missing here is only a leaf not narrowed)
_CALLS = frozenset({"jit", "closed_call", "custom_jvp_call",
                    "custom_vjp_call", "remat2"})


def _is_var(v) -> bool:
    return not isinstance(v, Literal)


def _call_jaxpr(eqn):
    """The jaxpr a call-like equation runs with the equation's operands
    as its inputs, one for one; None for anything else. A loop or a
    branch slices, carries or selects its operands: the walker below
    follows no leaf into one."""
    if eqn.primitive.name not in _CALLS:
        return None
    subs = list(_iter_subjaxprs(eqn.params))
    if len(subs) != 1 or len(subs[0].invars) != len(eqn.invars):
        return None
    return subs[0]


def _collect_reads(jaxpr, leaf_of, reads) -> None:
    """Add every read of the variables `leaf_of` maps to a leaf's index
    to that leaf's set in `reads`: the dtype a `convert_element_type`
    converts it to, or None for any other use (an equation that is
    neither a convert nor a call, or the jaxpr's own result). A call
    that takes the variable whole is descended into."""
    for eqn in jaxpr.eqns:
        sub, inner = None, {}
        for pos, v in enumerate(eqn.invars):
            leaf = leaf_of.get(v) if _is_var(v) else None
            if leaf is None:
                continue
            if eqn.primitive.name == "convert_element_type":
                reads[leaf].add(jnp.dtype(eqn.params["new_dtype"]))
                continue
            sub = sub or _call_jaxpr(eqn)
            if sub is None:
                reads[leaf].add(None)
            else:
                inner[sub.invars[pos]] = leaf
        if inner:
            _collect_reads(sub, inner, reads)
    for v in jaxpr.outvars:
        if _is_var(v) and v in leaf_of:
            reads[leaf_of[v]].add(None)


def _operations(jaxpr, skip=frozenset()):
    """The program as a nested list of (primitive, result types), the
    converts of the `skip` variables left out: two traces of one
    function that are equal here run the same operations on the same
    types in the same order."""
    out = []
    for eqn in jaxpr.eqns:
        held = [_is_var(v) and v in skip for v in eqn.invars]
        if eqn.primitive.name == "convert_element_type" and held[0]:
            continue
        out.append((eqn.primitive.name,
                    tuple(str(v.aval) for v in eqn.outvars)))
        sub = _call_jaxpr(eqn)
        if sub is not None:
            out.append(_operations(sub, frozenset(
                s for s, h in zip(sub.invars, held) if h)))
        else:
            out.extend(_operations(j)
                       for j in _iter_subjaxprs(eqn.params))
    return out


def _signature(tree) -> tuple:
    """The (shape, dtype) of every leaf, in order: what a memo of an
    abstract trace is keyed on."""
    return tuple((tuple(a.shape), str(a.dtype))
                 for a in jax.tree_util.tree_leaves(tree))


def narrow_tree(params, dtypes):
    """`params` with leaf i (flatten order) held at `dtypes[i]`; None
    leaves the leaf as it is: the same array, not a copy. The very tree
    when no leaf narrows."""
    if all(d is None for d in dtypes):      # (a dtype is falsy)
        return params
    leaves, treedef = jax.tree_util.tree_flatten(params)
    return treedef.unflatten([l if d is None else l.astype(d)
                              for l, d in zip(leaves, dtypes)])


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------

def _run_steps(step, carry, xs, steps):
    """`carry = step(carry, xs[i], i)` for i in [0, steps): the serving
    chunk programs' loop. `steps` is a traced int32 scalar, so how many
    steps run is DATA and one compiled program runs rounds of every
    length up to the leading size of `xs`: a scan of that size whose
    step is a scalar `lax.cond`, so a step past the bound costs a loop
    turn and no model evaluation. Under a `vmap` over rows `steps` is
    shared by all of them, so the `cond` stays ONE real branch (a
    batched predicate would lower to a `select` that runs both sides).
    (A `fori_loop` bounded by `steps` costs the same a live step on a
    v5e, but its first call lowers 2-4 s slower per program there:
    PERF.md, PR 31.)"""
    def scan_step(c, inp):
        x_i, i = inp
        return jax.lax.cond(i < steps, lambda c: step(c, x_i, i),
                            lambda c: c, c), ()

    size = jax.tree_util.tree_leaves(xs)[0].shape[0]
    return jax.lax.scan(scan_step, carry, (xs, jnp.arange(size)))[0]


def rows_apart(model_fn: Callable) -> Callable:
    """`model_fn(params, *args)` as a `vmap` over `args` evaluates it:
    one entry after another (`lax.map`), not side by side. The serving
    round programs `vmap` a row's turn over the round's rows; a model
    whose activations for ONE row's batch already fill the chip (rows of
    thousands of tokens at wide heads) asks for this
    (`serve_rows_apart`), and loses nothing where a row's tokens fill
    the matrix units alone. `params` stay whole (a batch of them has no
    such reading); no reverse mode: the serving path takes none."""
    apart = jax.custom_batching.custom_vmap(model_fn)

    @apart.def_vmap
    def one_at_a_time(axis_size, in_batched, params, *args):
        if any(jax.tree_util.tree_leaves(in_batched[0])):
            raise NotImplementedError("rows_apart: a batch of parameters")
        args = jax.tree_util.tree_map(
            lambda a, batched: a if batched else jnp.broadcast_to(
                a, (axis_size,) + a.shape), args, tuple(in_batched[1:]))
        out = jax.lax.map(lambda a: apart(params, *a), args)
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return apart


class DiffusionSampler:
    """Builds and caches jitted scan programs for trajectory generation.

    model_fn(params, x, t, cond) -> raw network output. Conditioning enters
    through `cond` (a pytree); CFG doubles the batch inside the scan
    (reference samplers/common.py:60-97).
    """

    def __init__(self, model_fn: Callable, schedule: NoiseSchedule,
                 transform: PredictionTransform, sampler: Sampler,
                 guidance_scale: float = 0.0,
                 autoencoder: Optional[Any] = None,
                 clip_denoised: bool = False,
                 timestep_spacing: str = "linear",
                 cache_plan: Optional[Any] = None,
                 cache_fns: Optional[Tuple[Callable, Callable]] = None,
                 tally_shape: Optional[Dict[str, Tuple[int, ...]]] = None):
        # ONE trace of the network for every program of this sampler:
        # the solo scan and each serving bucket's round programs
        # call it with the same per-row shapes (the batch axis
        # of requests is a vmap outside it), so jit's trace cache hands
        # every later program the first one's jaxpr. Tracing the network
        # is most of what a program costs before its first launch
        # (PERF.md, PR 25); XLA inlines the call.
        def sampler_model(params, x, t, cond):
            return model_fn(params, x, t, cond)

        self.model_fn = jax.jit(sampler_model)
        self.schedule = schedule
        self.transform = transform
        self.sampler = sampler
        self.guidance_scale = float(guidance_scale)
        self.autoencoder = autoencoder
        self.clip_denoised = clip_denoised
        self.timestep_spacing = timestep_spacing
        # training-free diffusion cache (ops/diffcache.py,
        # docs/CACHING.md): a static CachePlan plus the model's
        # (record_fn, reuse_fn) cache_mode closures. Both must be
        # present for the cached programs to build; otherwise every
        # program below is byte-for-byte the pre-cache one.
        self.cache_plan = cache_plan
        self.cache_fns = cache_fns
        # a model that counts what it does (routed experts: the picks
        # that landed on the experts held; a learned selection: the keys
        # selected): `model_fn` then returns (raw, tally), a small NAMED
        # SET of int32 arrays of these shapes ({name: shape}), each
        # summed over the batch it was given, and the serving programs
        # carry each row's sums over its evaluations
        # (`make_chunk_program`). None: no program differs by an operand.
        self.tally_shape = tally_shape
        self._compiled = {}
        self._taps_specs = {}
        self._narrowings = {}

    @property
    def cache_active(self) -> bool:
        return (self.cache_plan is not None
                and getattr(self.cache_plan, "enabled", False)
                and self.cache_fns is not None)

    @property
    def spatial_active(self) -> bool:
        """True when the plan composes the spatial token axis on top of
        the timestep cache (ops/spatialcache.py): the plan carries a
        `spatial` sub-plan and the cache_fns expose the
        record_ref/spatial forwards."""
        return (self.cache_active
                and getattr(self.cache_plan, "spatial", None) is not None
                and hasattr(self.cache_fns, "spatial"))

    # -- model evaluation with CFG ------------------------------------------
    def _evaluate(self, cond, uncond, x, t, net):
        """One evaluation at (x, t) around `net(x_net, t_net, c_net) ->
        (raw, *carries)`: the input scaling, the CFG doubling and
        recombination, the prediction transform and the clip. Returns
        (x0, eps, *carries). Every way a program evaluates the network
        (plain, and each mode of the diffusion caches) goes through
        here, so a record-every-step plan is bit-identical to the
        uncached path (tested)."""
        schedule, transform = self.schedule, self.transform
        use_cfg = self.guidance_scale > 0.0 and uncond is not None
        t_b = jnp.broadcast_to(t, (x.shape[0],)).astype(jnp.float32)
        c_in = bcast_right(transform.input_scale(schedule, t_b), x.ndim)
        x_net, t_net = schedule.transform_inputs(x * c_in, t_b)
        c_net = cond
        if use_cfg:
            x_net = jnp.concatenate([x_net, x_net], axis=0)
            t_net = jnp.concatenate([t_net, t_net], axis=0)
            c_net = jax.tree_util.tree_map(
                lambda c, u: jnp.concatenate([c, u], axis=0), cond, uncond)
        raw, *carries = net(x_net, t_net, c_net)
        if use_cfg:
            raw_c, raw_u = jnp.split(raw, 2, axis=0)
            raw = raw_u + self.guidance_scale * (raw_c - raw_u)
        pred = transform.transform_output(x, t_b, raw.astype(jnp.float32),
                                          schedule)
        x0, eps = transform.to_x0_eps(x, t_b, pred, schedule)
        if self.clip_denoised:
            x0 = clip_images(x0)
            signal, sigma = schedule.rates(t_b)
            eps = (x - bcast_right(signal, x.ndim) * x0) / jnp.maximum(
                bcast_right(sigma, x.ndim), 1e-12)
        return (x0, eps, *carries)

    def _denoise_fn(self, params, cond, uncond, tally=None):
        """`denoise(x, t) -> (x0, eps)`. `tally`: a list that receives
        the tally of each evaluation `denoise` makes while it is traced
        (a model with `tally_shape`); without one the tallies are
        dropped."""
        def net(*args):
            raw = self.model_fn(params, *args)
            if self.tally_shape is None:
                return (raw,)
            if tally is not None:
                tally.append(raw[1])
            return (raw[0],)

        return lambda x, t: self._evaluate(cond, uncond, x, t, net)

    # -- cached model evaluation (training-free diffusion cache) ------------
    def _denoise_taps_mode_fn(self, params, cond, uncond, mode: str):
        """`denoise(x, t, taps) -> (x0, eps, taps_out)` for ONE cache
        mode — "record" (full evaluation, fresh taps) or "reuse"
        (shallow-only, cached taps re-centered)."""
        # first two entries by position: works for both the plain
        # (record, reuse) pair and a ComposedCacheFns
        record_fn, reuse_fn = self.cache_fns[0], self.cache_fns[1]

        def denoise(x, t, taps):
            if mode == "record":
                return self._evaluate(cond, uncond, x, t,
                                      lambda *a: record_fn(params, *a))
            return self._evaluate(
                cond, uncond, x, t,
                lambda *a: (reuse_fn(params, *a, taps), taps))

        return denoise

    def _denoise_taps_fn(self, params, cond, uncond):
        """`denoise(x, t, taps, refresh) -> (x0, eps, taps)`: a scalar
        `lax.cond` between the record and reuse modes. The predicate is
        always a per-STEP scalar (solo scan input / round-level serving
        flag), never batched — a vmapped cond degenerates to select and
        would execute BOTH branches, erasing the speedup."""
        record = self._denoise_taps_mode_fn(params, cond, uncond, "record")
        reuse = self._denoise_taps_mode_fn(params, cond, uncond, "reuse")

        def denoise(x, t, taps, refresh):
            return jax.lax.cond(refresh, record, reuse, x, t, taps)

        return denoise

    # -- composed (timestep x spatial) cached evaluation --------------------
    def _denoise_composed_mode_fn(self, params, cond, uncond, mode: str):
        """`denoise(x, t, taps, ref) -> (x0, eps, taps, ref)` for ONE
        composed-cache mode — "record" (full evaluation, fresh taps +
        score reference), "spatial" (static top-k token refresh,
        ops/spatialcache.py) or "reuse" (pure timestep reuse; taps and
        ref pass through). All three share one carry structure so they
        can be `lax.switch` branches."""
        fns = self.cache_fns

        def denoise(x, t, taps, ref):
            if mode == "record":
                net = lambda *a: fns.record_ref(params, *a)
            elif mode == "spatial":
                net = lambda *a: fns.spatial(params, *a, taps, ref)
            else:
                net = lambda *a: (fns.reuse(params, *a, taps), taps, ref)
            return self._evaluate(cond, uncond, x, t, net)

        return denoise

    def _denoise_composed_fn(self, params, cond, uncond):
        """`denoise(x, t, taps, ref, code) -> (x0, eps, taps, ref)`: a
        scalar `lax.switch` over the composed-plan step codes
        (ops/spatialcache.py CODE_REUSE/CODE_SPATIAL/CODE_REFRESH). Same
        rule as the timestep cache's cond: the predicate is always a
        per-STEP scalar — a vmapped switch degenerates to select and
        executes every branch."""
        branches = tuple(
            self._denoise_composed_mode_fn(params, cond, uncond, m)
            for m in ("reuse", "spatial", "record"))

        def denoise(x, t, taps, ref, code):
            return jax.lax.switch(code, branches, x, t, taps, ref)

        return denoise

    def _cache_carry_zeros(self, key, params, x, cond, uncond, record):
        """Zero-filled cache carries shaped like what `record` (a
        record branch: `(params, x_net, t_net, c_net) -> (raw,
        *carries)`) returns after `raw`; CFG doubles the batch they
        cover. `jax.eval_shape` only — no device compute — and the spec
        is memoized per input-shape signature: the abstract model trace
        costs tens of ms, which must not recur on every serving
        admission (it would serialize the dispatch loop). Step 0 of
        every plan refreshes, so the zeros are never consumed."""
        spec_key = (key, _signature(x), _signature(cond), _signature(uncond))
        spec = self._taps_specs.get(spec_key)
        if spec is None:
            spec = self._taps_specs[spec_key] = jax.eval_shape(
                lambda x: self._evaluate(
                    cond, uncond, x, 0.0,
                    lambda *a: record(params, *a))[2:], x)
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), spec)

    def cache_taps_init(self, params, x, cond, uncond):
        """The timestep cache's zero `taps` carry."""
        return self._cache_carry_zeros("taps", params, x, cond, uncond,
                                       self.cache_fns[0])[0]

    def cache_carry_init(self, params, x, cond, uncond):
        """(taps0, ref0) zero carries for the composed spatial cache —
        the record_ref branch's taps AND score-reference outputs."""
        return self._cache_carry_zeros("composed", params, x, cond, uncond,
                                       self.cache_fns.record_ref)

    # -- the dtype each leaf is served at -----------------------------------
    def _evaluations(self, params, x, cond, uncond):
        """Every way this sampler's programs evaluate the network, once
        each at a row's shapes: the plain evaluation (the uncached
        rounds, the solo scan's terminal denoise) and, with a cache
        plan, each mode of the cached rounds."""
        t = jnp.zeros((x.shape[0],), jnp.float32)
        outs = [self._denoise_fn(params, cond, uncond)(x, t)]
        if self.spatial_active:
            carry = self.cache_carry_init(params, x, cond, uncond)
            outs += [self._denoise_composed_mode_fn(
                params, cond, uncond, m)(x, t, *carry)
                for m in ("reuse", "spatial", "record")]
        elif self.cache_active:
            taps = self.cache_taps_init(params, x, cond, uncond)
            outs += [self._denoise_taps_mode_fn(
                params, cond, uncond, m)(x, t, taps)
                for m in ("record", "reuse")]
        return outs

    def narrowing(self, params, x, cond, uncond) -> tuple:
        """For each leaf of `params` (flatten order) the dtype a serving
        engine may hold it at, or None for as it is stored.

        Read from the program, not from the model: `_evaluations` is
        traced abstractly (`jax.make_jaxpr` over shapes: nothing
        compiles, nothing runs) and a leaf is narrowed to D iff EVERY
        read of it is a `convert_element_type` to the one D and D is
        narrower than the dtype stored. Two dtypes, a read at the
        stored dtype (a `dot_general`, a `reshape`), a read inside a
        loop or a branch, a widening convert: the leaf stays. Then the
        programs are traced again at the narrowed dtypes, and unless
        that trace is the first one less exactly those converts nothing
        narrows (a model that asks a leaf's dtype in Python shows in no
        equation). So what a round program computes from the narrowed
        tree is what it computed from `params`, the converts made once
        by whoever holds the tree and not once a launch. `x`, `cond`,
        `uncond`: one row's carries, for their shapes. Memoised per
        (tree structure, shapes, dtypes)."""
        leaves, treedef = jax.tree_util.tree_flatten(params)
        key = (treedef,) + tuple(
            _signature(v) for v in (leaves, x, cond, uncond))
        if key in self._narrowings:
            return self._narrowings[key]

        def spec(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

        def trace(specs):
            return jax.make_jaxpr(
                lambda ls, *row: self._evaluations(
                    treedef.unflatten(ls), *row))(
                specs, spec(x), spec(cond), spec(uncond)).jaxpr

        first = trace(spec(leaves))
        inputs = first.invars[:len(leaves)]
        reads = [set() for _ in leaves]
        _collect_reads(first, {v: i for i, v in enumerate(inputs)}, reads)

        def narrower(read, stored):
            (d,) = read if len(read) == 1 else (None,)
            return d if d is not None \
                and d.itemsize < jnp.dtype(stored).itemsize else None

        dtypes = tuple(narrower(r, l.dtype) for r, l in zip(reads, leaves))
        if any(d is not None for d in dtypes):
            again = trace([jax.ShapeDtypeStruct(
                l.shape, l.dtype if d is None else d)
                for l, d in zip(leaves, dtypes)])
            held = frozenset(
                v for v, d in zip(inputs, dtypes) if d is not None)
            if _operations(first, held) != _operations(again):
                dtypes = (None,) * len(leaves)
        self._narrowings[key] = dtypes
        return dtypes

    # -- one compiled program per (steps, shape) ----------------------------
    def _get_program(self, num_steps: int, shape: Tuple[int, ...],
                     start: Optional[float], end: float,
                     inpaint: bool = False):
        cached = self.cache_active
        spatial = self.spatial_active
        plan_key = self.cache_plan.key() if cached else None
        cache_key = (num_steps, shape, start, end, inpaint, plan_key)
        if cache_key in self._compiled:
            return self._compiled[cache_key]

        steps = get_timestep_spacing(self.timestep_spacing, num_steps,
                                     self.schedule.timesteps, start, end,
                                     schedule=self.schedule)
        # static per-step refresh schedule, folded into the scan as an
        # input row; with the cache off this is absent and the program
        # below is byte-for-byte the pre-cache one. A composed plan
        # (ops/spatialcache.py) carries a three-way code row instead of
        # boolean flags.
        flags = codes = None
        if spatial:
            codes = jnp.asarray(self.cache_plan.step_codes(num_steps))
        elif cached:
            flags = jnp.asarray(self.cache_plan.flags(num_steps))

        def sampler_scan(params, x_init, key, cond, uncond, mask=None,
                         known=None):
            denoise = self._denoise_fn(params, cond, uncond)
            if spatial:
                denoise_comp = self._denoise_composed_fn(
                    params, cond, uncond)
            elif cached:
                denoise_taps = self._denoise_taps_fn(params, cond, uncond)
            pairs = jnp.stack([steps[:-1], steps[1:]], axis=1)

            def scan_step(carry, inp):
                if spatial:
                    x, rng, state, taps, ref = carry
                    pair, idx, code = inp
                    # the box threads BOTH cache carries (taps + score
                    # reference) through every denoise call of a
                    # multi-NFE sampler step, all under the one
                    # per-step scalar switch
                    carry_box = [taps, ref]

                    def step_denoise(x_, t_):
                        x0, eps, tp, rf = denoise_comp(
                            x_, t_, carry_box[0], carry_box[1], code)
                        carry_box[0], carry_box[1] = tp, rf
                        return x0, eps
                elif cached:
                    x, rng, state, taps = carry
                    pair, idx, refresh = inp
                    # higher-order samplers call denoise several times
                    # per step; the box threads the taps carry through
                    # every call (each full eval re-records, each
                    # cached eval reuses — all under the one per-step
                    # scalar cond)
                    taps_box = [taps]

                    def step_denoise(x_, t_):
                        x0, eps, tp = denoise_taps(
                            x_, t_, taps_box[0], refresh)
                        taps_box[0] = tp
                        return x0, eps
                else:
                    x, rng, state = carry
                    pair, idx = inp
                    step_denoise = denoise
                t_cur, t_next = pair[0], pair[1]
                rng, sub = jax.random.split(rng)
                x_next, state = self.sampler.step(
                    step_denoise, x, t_cur, t_next, sub, state,
                    self.schedule, idx)
                if inpaint:
                    # Masked generation (SD-inpainting "replacement"
                    # semantics): outside the mask the trajectory is
                    # pinned to the reference, re-noised to the step's
                    # noise level so the generated region blends against
                    # a statistically consistent neighborhood.
                    rng, nk = jax.random.split(rng)
                    noise = jax.random.normal(nk, known.shape, known.dtype)
                    t_b = jnp.full((x.shape[0],), t_next)
                    known_t = self.schedule.add_noise(known, noise, t_b)
                    x_next = mask * x_next + (1.0 - mask) * known_t
                if spatial:
                    return (x_next, rng, state, carry_box[0],
                            carry_box[1]), ()
                if cached:
                    return (x_next, rng, state, taps_box[0]), ()
                return (x_next, rng, state), ()

            state0 = self.sampler.init_state(x_init)
            if spatial:
                taps0, ref0 = self.cache_carry_init(params, x_init,
                                                    cond, uncond)
                (x, _, _, _, _), _ = jax.lax.scan(
                    scan_step, (x_init, key, state0, taps0, ref0),
                    (pairs, jnp.arange(num_steps), codes))
            elif cached:
                taps0 = self.cache_taps_init(params, x_init, cond, uncond)
                (x, _, _, _), _ = jax.lax.scan(
                    scan_step, (x_init, key, state0, taps0),
                    (pairs, jnp.arange(num_steps), flags))
            else:
                (x, _, _), _ = jax.lax.scan(
                    scan_step, (x_init, key, state0),
                    (pairs, jnp.arange(num_steps)))
            # terminal denoise: plain model call at the final step value
            # (reference samplers/common.py:384-388)
            x0, _ = denoise(x, jnp.full((x.shape[0],), steps[-1]))
            if inpaint:
                x0 = mask * x0 + (1.0 - mask) * known
            return x0

        compiled = jax.jit(sampler_scan)
        # Program-evidence plumb-through (telemetry/programs.py): when
        # the active hub carries a registry, the first invocation of
        # this solo program is timed and registered under its cache
        # key, like every serving chunk program. Wrapped ONLY when a
        # registry is active at BUILD time, so the default path — and
        # the analysis suite's `make_jaxpr` over this return value —
        # gets the raw jitted program, byte-for-byte unchanged.
        from ..telemetry import global_telemetry
        if getattr(global_telemetry(), "programs", None) is not None:
            from ..telemetry.programs import register_on_first_call
            compiled = register_on_first_call(
                compiled, kind="solo",
                key=("solo", type(self.sampler).__name__,
                     self.timestep_spacing,
                     self.guidance_scale) + cache_key)
        self._compiled[cache_key] = compiled
        return compiled

    # -- public API ----------------------------------------------------------
    def generate_samples(self, params, num_samples: int = 4,
                         resolution: int = 64,
                         diffusion_steps: int = 50,
                         rngstate: Optional[RngSeq] = None,
                         conditioning: Any = None,
                         unconditional: Any = None,
                         init_samples: Optional[jax.Array] = None,
                         start_step: Optional[float] = None,
                         end_step: float = 0.0,
                         sequence_length: Optional[int] = None,
                         channels: int = 3,
                         decode: bool = True,
                         inpaint_reference: Optional[jax.Array] = None,
                         inpaint_mask: Optional[jax.Array] = None) -> jax.Array:
        """Run the scan program; returns decoded samples in [-1, 1] space.

        Image shape: [N, R, R, C]; video when sequence_length is given:
        [N, T, R, R, C] (reference samplers/common.py:412-430).

        Inpainting (capability the reference lacks): pass
        `inpaint_reference` ([-1,1] pixel/video space, full sample shape)
        and `inpaint_mask` (1 = generate, 0 = keep reference; spatial
        shape, broadcastable over channels). With an autoencoder the
        reference is encoded and the mask is nearest-resized to the
        latent grid. The whole masked trajectory still runs in the one
        compiled scan.
        """
        rngstate = rngstate or RngSeq.create(42)
        rngstate, noise_key = rngstate.next_key()
        rngstate, loop_key = rngstate.next_key()

        if self.autoencoder is not None:
            resolution = resolution // self.autoencoder.downscale_factor
            channels = self.autoencoder.latent_channels

        if sequence_length is not None:
            shape = (num_samples, sequence_length, resolution, resolution, channels)
        else:
            shape = (num_samples, resolution, resolution, channels)

        inpaint = inpaint_reference is not None
        mask = known = None
        if inpaint:
            if inpaint_mask is None:
                raise ValueError("inpaint_reference requires inpaint_mask")
            known = jnp.asarray(inpaint_reference, jnp.float32)
            if self.autoencoder is not None:
                known = self.autoencoder.encode(known)
            if known.shape != shape:
                raise ValueError(f"inpaint_reference encodes to "
                                 f"{known.shape}, expected {shape}")
            mask = jnp.asarray(inpaint_mask, jnp.float32)
            if mask.ndim == known.ndim - 1:      # no channel dim: add one
                mask = mask[..., None]
            elif mask.ndim != known.ndim:
                raise ValueError(
                    f"inpaint_mask rank {mask.ndim} incompatible with "
                    f"sample rank {known.ndim} (pass [batch, (frames,) "
                    f"H, W] or with a trailing channel dim)")
            if mask.shape[-3:-1] != known.shape[-3:-1]:
                mask = jax.image.resize(
                    mask, mask.shape[:-3] + known.shape[-3:-1]
                    + mask.shape[-1:], method="nearest")
            mask = jnp.broadcast_to(mask, known.shape).astype(jnp.float32)

        if init_samples is None:
            noise = self._compiled.get(("noise", tuple(shape)))
            if noise is None:
                noise = self.make_noise_program(tuple(shape))
                self._compiled[("noise", tuple(shape))] = noise
            x = noise(noise_key)
        else:
            x = init_samples

        program = self._get_program(diffusion_steps, tuple(shape),
                                    start_step, end_step, inpaint=inpaint)
        if inpaint:
            x0 = program(params, x, loop_key, conditioning, unconditional,
                         mask, known)
        else:
            x0 = program(params, x, loop_key, conditioning, unconditional)

        if decode and self.autoencoder is not None:
            x0 = self.autoencoder.decode(x0)
        return clip_images(x0)

    # Reference alias (samplers/common.py:433)
    generate_images = generate_samples

    # -- serving programs ----------------------------------------------------
    # Builders for the serving layer's continuous-batching rounds
    # (flaxdiff_tpu/serving/engine.py). All are UNCACHED — the serving
    # engine owns the compiled-program cache and its hit/miss counters;
    # a second cache here would hide misses from the SLO metrics. The
    # engine launches the round programs with the rows' carries as a
    # tuple and stacks them INSIDE the compiled program
    # (engine.py `_round_program`); the layouts below are the stacked
    # ones.
    #
    # Row model: the batch axis is REQUESTS, each row a block of
    # `block_shape` samples (the request's own num_samples). Everything
    # per-row — trajectory position, remaining turns, timestep pairs,
    # which turn is the terminal denoise, RNG — is vmapped, so one
    # program serves rows at different points of different-length
    # trajectories, a row that ends among them (`make_chunk_program`:
    # a trajectory is `nfe + 1` turns; no program but the round
    # programs evaluates the network). vmap (not reshape-to-one-batch) is
    # what keeps per-row RNG exact: stochastic samplers draw
    # `normal(key, x.shape)` per row with the row's own key, the same
    # call a solo `generate_samples` makes, so a batched request is
    # bit-identical to its solo run (tested in tests/test_serving.py).

    def make_noise_program(self, shape: Tuple[int, ...]):
        """A trajectory's starting noise, `normal(key, shape) *
        max_noise_std`, as a program of its own: program(key) -> x.
        `generate_samples` and the serving engine both start from THIS
        program's output, because the same two operations fused into a
        larger program round differently in the last bit (tested), and
        batched-equals-solo holds to the last bit. `max_noise_std` is
        read once, here, and closed over."""
        std = self.schedule.max_noise_std()

        def sampler_noise(key):
            return jax.random.normal(key, shape) * std

        return jax.jit(sampler_noise)

    def make_init_program(self, shape: Tuple[int, ...], params=None,
                          uncond=None):
        """Everything of the carry a trajectory starts from but its
        noise, as ONE program of the seed: the two key splits
        `generate_samples` makes (integer arithmetic: the same bits in
        any program), the sampler state and (with a cache plan) the
        zero cache carries. `params` and `uncond` give the cache
        carries their shapes and nothing else.

        program(seed, cond) -> (noise_key, loop_key, state, cond, taps,
                                ref)
          seed   `np.int64(request seed)`: what `PRNGKey` makes of a
                 Python int, wrap-around past 32 bits included
          cond   the request's conditioning, host or device: it comes
                 back as a device array (its upload rides this launch)
        """
        def sampler_init(seed, cond):
            rngstate = RngSeq.create(jax.random.PRNGKey(seed))
            rngstate, noise_key = rngstate.next_key()
            rngstate, loop_key = rngstate.next_key()
            x = jnp.zeros(shape)            # its shape is all that counts
            taps = ref = None
            if self.spatial_active:
                taps, ref = self.cache_carry_init(params, x, cond, uncond)
            elif self.cache_active:
                taps = self.cache_taps_init(params, x, cond, uncond)
            return (noise_key, loop_key, self.sampler.init_state(x), cond,
                    taps, ref)

        return jax.jit(sampler_init)

    def _turn(self, denoise, x, pair, key, state, index, terminal,
              seen=()):
        """One turn of a row in a round program: the sampler's step over
        `pair`, or, where `terminal` (a traced bool; None: never), the
        row's terminal denoise. Every sampler opens a step with
        `denoise(x, t_cur)`, and a terminal turn's pair is `(t_term,
        t_term)`, so the `x0` of the step's FIRST evaluation is the solo
        program's closing `denoise(x, steps[-1])`; what the step makes
        of a zero-length step is dropped by the select. Returns (x,
        state, tally): `seen` is the list `denoise` adds its
        evaluations' tallies to, and a terminal turn is charged one."""
        first = []

        def remembering(x_, t_):
            out = denoise(x_, t_)
            if not first:
                first.append(out[0])
            return out

        x_n, s_n = self.sampler.step(remembering, x, pair[0], pair[1], key,
                                     state, self.schedule, index)
        counted = jax.tree_util.tree_map(lambda *n: sum(n), *seen) \
            if seen else None
        if terminal is not None:
            x_n = jnp.where(terminal, first[0], x_n)
            if seen:
                counted = jax.tree_util.tree_map(
                    lambda one, every: jnp.where(terminal, one, every),
                    seen[0], counted)
        return x_n, s_n, counted

    def make_chunk_program(self, round_steps: int):
        """One continuous-batching round: advance every row by `steps`
        turns of ITS OWN trajectory, `steps` <= `round_steps`.

        A row's trajectory is `nfe + 1` turns: its `nfe` sampler steps
        and then its terminal denoise (`_turn`).

        `round_steps` is the size the program is compiled for (the
        `pairs` operand's width) and nothing else: how many turns a
        round runs is DATA, the scalar `steps`, so one program serves
        every length and no row spends a model evaluation on a turn it
        throws away (`_run_steps`). The serving engine ends a round
        where its first row ends (`serving/engine.py` `round_length`).

        program(params, x, keys, pairs, n_act, offsets, steps, cond,
                uncond, state, tally, term)
          x        [R, *block]            row carries (trajectory state)
          keys     [R, 2] uint32          per-row scan RNG carries
          pairs    [R, round_steps, 2]    this round's (t_cur, t_next)
                                          pairs, inert-padded past n_act
          n_act    [R] int32              live turns this round, at most
                                          `steps`: a row with fewer keeps
                                          its carry for the rest (rows
                                          of different lengths run to
                                          completion together; padding)
          offsets  [R] int32              global step index of the row's
                                          first turn this round (multistep
                                          samplers key history on it)
          steps    [] int32               turns this round runs, shared
                                          by all rows; never a Python int
          state    [R, ...] pytree        per-row sampler state carry
                                          (init_state at admission)
          tally    {name: [R, *shape] int32}  a counting model's sums
                                          so far by name (`tally_shape`;
                                          zeros at admission); None and
                                          absent from the result for any
                                          other model
          term     [R] int32              the turn of this round that is
                                          the row's terminal denoise, -1
                                          for none; data like `n_act`,
                                          never a Python int (None: no
                                          row ends in this program)
        Returns (x, keys, state) carries, with a `tally_shape` (x, keys,
        state, tally); after its terminal turn a row's `x` is its
        denoised sample. Rows never interact, so a
        padded round is output-invariant for the real rows, and a row's
        samples do not depend on where its rounds were cut (tested).
        """
        def sampler_chunk(params, x, keys, pairs, n_act, offsets, steps,
                          cond, uncond, state, tally=None, term=None):
            def row(x_r, key, row_pairs, n, off, c, u, st, tl, tm):
                def step(carry, pair, i):
                    x_c, rng, s, tl_c = carry
                    rng, sub = jax.random.split(rng)
                    seen = []       # the tallies of this turn's evaluations
                    x_n, s_n, counted = self._turn(
                        self._denoise_fn(params, c, u, seen), x_c, pair, sub,
                        s, off + i, None if tm is None else i == tm, seen)
                    active = i < n
                    x_n = jnp.where(active, x_n, x_c)
                    s_n = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(active, a, b), s_n, s)
                    if seen:
                        tl_c = jax.tree_util.tree_map(
                            lambda n, more: jnp.where(active, n + more, n),
                            tl_c, counted)
                    return x_n, rng, s_n, tl_c

                out = _run_steps(step, (x_r, key, st, tl), row_pairs, steps)
                return out if tl is not None else out[:3]

            return jax.vmap(row)(x, keys, pairs, n_act, offsets,
                                 cond, uncond, state, tally, term)

        return jax.jit(sampler_chunk)

    def make_cached_chunk_program(self, round_steps: int):
        """Continuous-batching round WITH the diffusion cache: the
        chunk-program contract plus

          flags [round_steps] bool   round-level refresh schedule
          taps  [R, ...] pytree      per-row cache carry (rides the
                                     RequestState like x/rng/state)

        and `(x, keys, state, taps)` carries out. A row's terminal turn
        (`term`, as in `make_chunk_program`) is the same per-row select
        inside whichever branch the round's flag takes; the engine
        schedules it as a refresh, so it is a full evaluation.

        Structure flips to loop-outside / vmap-inside: the refresh
        decision must be a SCALAR `lax.cond` — vmapping a cond over
        per-row predicates lowers to `select`, which executes both
        branches and erases the speedup. The round flags are therefore
        shared by every row: the engine ORs each row's own
        offset-aligned schedule into them, so a row never misses its
        scheduled refresh (it may get extra free refreshes from its
        round-mates, which only improves fidelity). Per-row RNG
        lineage, active-step masking, and the sampler-state carry are
        unchanged from `make_chunk_program` — a refresh-every-step
        plan is bit-identical to the uncached chunk path (tested).
        """
        def sampler_chunk_cached(params, x, keys, pairs, n_act, offsets,
                                 steps, cond, uncond, state, flags, taps,
                                 term=None):
            def make_step(mode):
                def step_all(x_c, subs, st, tp, pair_i, i):
                    def row(x_r, sub, s_r, tp_r, pr, off, c, u, tm):
                        dn = self._denoise_taps_mode_fn(
                            params, c, u, mode)
                        taps_box = [tp_r]

                        def step_denoise(x_, t_):
                            x0, eps, tpn = dn(x_, t_, taps_box[0])
                            taps_box[0] = tpn
                            return x0, eps

                        x_n, s_n, _ = self._turn(
                            step_denoise, x_r, pr, sub, s_r, off + i,
                            None if tm is None else i == tm)
                        return x_n, s_n, taps_box[0]

                    return jax.vmap(row)(x_c, subs, st, tp, pair_i,
                                         offsets, cond, uncond, term)
                return step_all

            record_step = make_step("record")
            reuse_step = make_step("reuse")

            def step(carry, inp, i):
                x_c, rngs, st, tp = carry
                pair_i, refresh = inp
                # per-row split, same lineage as the uncached row loop:
                # rng, sub = split(rng) at every step
                both = jax.vmap(jax.random.split)(rngs)
                rngs_n, subs = both[:, 0], both[:, 1]
                x_n, s_n, tp_n = jax.lax.cond(
                    refresh, record_step, reuse_step,
                    x_c, subs, st, tp, pair_i, i)
                active = i < n_act

                def sel(a, b):
                    return jnp.where(bcast_right(active, a.ndim), a, b)

                x_n = sel(x_n, x_c)
                s_n = jax.tree_util.tree_map(sel, s_n, st)
                tp_n = jax.tree_util.tree_map(sel, tp_n, tp)
                return x_n, rngs_n, s_n, tp_n

            return _run_steps(step, (x, keys, state, taps),
                              (jnp.swapaxes(pairs, 0, 1), flags), steps)

        return jax.jit(sampler_chunk_cached)

    def make_spatial_chunk_program(self, round_steps: int):
        """Continuous-batching round with the COMPOSED timestep x
        spatial cache (ops/spatialcache.py): the cached-chunk contract
        with

          codes [round_steps] int32  round-level step codes
                                     (CODE_REUSE/CODE_SPATIAL/
                                     CODE_REFRESH)
          taps  [R, ...] pytree      per-row residual-delta carry
          refs  [R, ...] pytree      per-row score-reference carry

        and `(x, keys, state, taps, refs)` carries out.

        Same loop-outside / vmap-inside shape as the cached chunk
        program — the per-step decision must be a SCALAR `lax.switch`
        (a vmapped switch lowers to select: every branch executes and
        the speedup is gone). The engine builds the round codes as the
        per-step MAX over each row's own offset-aligned code schedule:
        refresh beats spatial beats reuse, so no row ever gets LESS
        refresh than its plan scheduled — round-mates can only grant
        extra fidelity. Token selection runs per-row inside the vmap
        (each row picks its own top-k from its own carries). A row's
        terminal turn (`term`) is the cached program's per-row select,
        scheduled by the engine as a refresh."""
        def sampler_chunk_spatial(params, x, keys, pairs, n_act, offsets,
                                  steps, cond, uncond, state, codes, taps,
                                  refs, term=None):
            def make_step(mode):
                def step_all(x_c, subs, st, tp, rf, pair_i, i):
                    def row(x_r, sub, s_r, tp_r, rf_r, pr, off, c, u, tm):
                        dn = self._denoise_composed_mode_fn(
                            params, c, u, mode)
                        carry_box = [tp_r, rf_r]

                        def step_denoise(x_, t_):
                            x0, eps, tpn, rfn = dn(
                                x_, t_, carry_box[0], carry_box[1])
                            carry_box[0], carry_box[1] = tpn, rfn
                            return x0, eps

                        x_n, s_n, _ = self._turn(
                            step_denoise, x_r, pr, sub, s_r, off + i,
                            None if tm is None else i == tm)
                        return x_n, s_n, carry_box[0], carry_box[1]

                    return jax.vmap(row)(x_c, subs, st, tp, rf, pair_i,
                                         offsets, cond, uncond, term)
                return step_all

            # branch order == CODE_* values (ops/spatialcache.py)
            steps_by_code = (make_step("reuse"), make_step("spatial"),
                             make_step("record"))

            def step(carry, inp, i):
                x_c, rngs, st, tp, rf = carry
                pair_i, code = inp
                # per-row split, same lineage as the uncached row loop
                both = jax.vmap(jax.random.split)(rngs)
                rngs_n, subs = both[:, 0], both[:, 1]
                x_n, s_n, tp_n, rf_n = jax.lax.switch(
                    code, steps_by_code, x_c, subs, st, tp, rf,
                    pair_i, i)
                active = i < n_act

                def sel(a, b):
                    return jnp.where(bcast_right(active, a.ndim), a, b)

                x_n = sel(x_n, x_c)
                s_n = jax.tree_util.tree_map(sel, s_n, st)
                tp_n = jax.tree_util.tree_map(sel, tp_n, tp)
                rf_n = jax.tree_util.tree_map(sel, rf_n, rf)
                return x_n, rngs_n, s_n, tp_n, rf_n

            return _run_steps(step, (x, keys, state, taps, refs),
                              (jnp.swapaxes(pairs, 0, 1), codes), steps)

        return jax.jit(sampler_chunk_spatial)

    def trajectory_inputs(self, num_steps: int,
                          start: Optional[float] = None,
                          end: float = 0.0):
        """Host-side per-request trajectory constant for the serving
        programs: the `[num_steps + 1, 2]` (t_cur, t_next) pairs of a
        row's turns, from the same spacing the solo program closes
        over. The last is the terminal turn's, `(t_term, t_term)`: the
        row's OWN terminal value (spacings of different NFE need not
        end at bit-identical values)."""
        steps = get_timestep_spacing(self.timestep_spacing, num_steps,
                                     self.schedule.timesteps, start, end,
                                     schedule=self.schedule)
        return jnp.stack(
            [steps, jnp.concatenate([steps[1:], steps[-1:]])], axis=1)
