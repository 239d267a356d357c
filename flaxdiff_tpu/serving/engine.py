"""Compiled-program engine for the serving scheduler.

Owns the **compiled-program cache**: jitted wrappers around the
existing single-`lax.scan` `DiffusionSampler`, keyed on

    (kind, batch_bucket, resolution, sequence_length, scan_steps,
     sampler, guidance, use_ema, num_samples, channels,
     has_cond, has_uncond, cache_plan)

so repeat traffic never re-traces. `scan_steps` is the size the round
program is compiled for — the (power-of-two-bucketed) longest
trajectory in run-to-completion mode, `SchedulerConfig.round_steps` in
continuous mode — and NOT the number of turns a round runs: that is an
operand (`round_length`: a round ends where its first row ends), as
each row's timestep pairs, live-turn count and terminal turn are, so
rounds of every length and NFE-heterogeneous rows share one program.
Cache hits/misses are counted at `serving/program_cache_hits` /
`serving/program_cache_misses`. Program kinds: "init" and "noise" (a
request's starting carry from its seed), "chunk" (uncached),
"chunk_cached" (timestep diffusion cache), "chunk_spatial" (composed
timestep x spatial cache, ops/spatialcache.py): the only ones that
hold the network; and "handoff" (stack, decode, clip of finished rows:
no evaluation). `prewarm` compiles the hot tuples before admission
opens.

**A row's trajectory is `nfe + 1` turns of the round programs, and the
last is its terminal denoise** (`DiffusionSampler.make_chunk_program`):
a row that ends rides the round its mates ride, every slot of every
turn is a row somebody asked for, and no program is launched at a
padded bucket for the rows that happened to finish together.
`serving/terminal_turns` counts them.

**A turn of the dispatch thread is a handful of launches and no
read-back** (docs/SERVING.md "Run-ahead"): the runtime queues only so
many launches behind a running program and then blocks the caller, and
a device-to-host read waits for everything queued before it, so either
one would stop the thread from preparing the next round under the
running one. `prepare` is two launches (the "init" and "noise"
programs); what depends only on (sampler, NFE, schedule) — the
trajectory's turn pairs, as HOST values — is computed once and kept
here. A round is one launch: its pairs / live-turn counts / offsets /
terminal turns are built in numpy, and the rows' carries go in as a
tuple and come back as a tuple, stacked and unstacked INSIDE the
compiled program. `finalize` is one small launch (stack, decode, clip).
Every launch goes through `_launch`, counted at `serving/launches`.

**What the programs take is the served tree, not the pipeline's**
(`_params_for`, docs/SERVING.md "What the engine holds"): a leaf the
network does nothing with but convert it to a narrower dtype is held at
that dtype, cast once before the first round and not once a launch;
every other leaf is the pipeline's own array.

Batching model (see `DiffusionSampler.make_chunk_program`): the batch
axis is requests, each row an independent block of the request's
`num_samples` samples with its own RNG carry. Rows never interact, so
grouping, padding to a batch bucket, and chunked rounds are all
output-invariant: a batched request is bit-identical to the same
request run solo through `DiffusionInferencePipeline.generate_samples`
(tested).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import clip_images
from .request import SampleRequest, ServingFuture

# batch buckets the scheduler pads micro-batches up to; the largest is
# also the admission cap per round
DEFAULT_BATCH_BUCKETS: Tuple[int, ...] = (1, 2, 4, 8)


def bucket_up(n: int, buckets: Tuple[int, ...]) -> int:
    """Smallest bucket >= n (the scheduler never builds a group larger
    than max(buckets))."""
    for b in sorted(buckets):
        if b >= n:
            return b
    return max(buckets)


def nfe_bucket(n: int) -> int:
    """Next power of two >= n: the size a run-to-completion round
    program is compiled for, so nearby NFEs share one program (the
    round runs the exact longest length; shorter rows mask their own
    tail)."""
    b = 1
    while b < n:
        b *= 2
    return b


def round_length(rows, round_steps: int) -> Tuple[int, int]:
    """(size the round's program is compiled for, turns the round runs)
    for `rows` under `SchedulerConfig.round_steps`, from the rows'
    remaining turns and nothing else (host integers: `done` advances at
    launch, so the dispatch thread one round ahead knows them without a
    read-back).

    Continuous mode (`round_steps` > 0): the round ends where its first
    row ends (at that row's terminal turn), after at most
    `round_steps`. Every row is live on every turn, so no model
    evaluation is thrown away, and a finished row's slot is refilled at
    the next round. Run-to-completion
    (`round_steps` 0): the exact longest remaining length, in the
    program of its power-of-two bucket; shorter rows keep their carry
    past their own end."""
    left = [r.remaining for r in rows]
    if round_steps:
        return round_steps, min(left + [round_steps])
    return nfe_bucket(max(left)), max(left)


def _stacked(rows):
    """Per-row pytrees -> one pytree of `[R, ...]` leaves (traced: part
    of the program that calls it)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *rows)


def _round_program(program):
    """A chunk program (`DiffusionSampler.make_*chunk_program`: stacked
    carries in and out) as the engine launches it:

        run(params, rows, batch) -> one tuple of carries per row

    `rows` holds a dict of the program's per-row arguments by name for
    every slot of the bucket, `batch` the per-round ones (numpy). Stack,
    scan and unstack are ONE launch; the compiled module keeps the
    chunk program's name."""
    def run(params, rows, batch):
        outs = program(params, **_stacked(rows), **batch)
        return tuple(jax.tree_util.tree_map(lambda a: a[i], outs)
                     for i in range(len(rows)))

    run.__name__ = program.__name__
    return jax.jit(run)


def _handoff_program(autoencoder):
    """What is left to do for rows whose terminal turn ran, in one
    launch and with no model evaluation: stack their `x`, decode, clip.
    Returns the whole bucket, `[bucket, num_samples, *sample_shape]` (a
    cut to the real rows would be a program per row count); for rows
    that carry a `tally` (a counting model): (that, the rows' tallies
    `{name: [bucket, *shape]}`)."""
    def sampler_handoff(rows):
        out = _stacked(rows)
        x0 = out["x"]
        if autoencoder is not None:
            flat = autoencoder.decode(x0.reshape((-1,) + x0.shape[2:]))
            x0 = flat.reshape(x0.shape[:2] + flat.shape[1:])
        x0 = clip_images(x0)
        return (x0, out["tally"]) if "tally" in out else x0

    return jax.jit(sampler_handoff)


class RequestState:
    """One admitted request's device-resident trajectory carry."""

    __slots__ = ("req", "future", "submit_t", "admit_t", "group",
                 "x", "rng", "state", "pairs", "nfe",
                 "done", "cond", "uncond", "compile_ms", "rounds",
                 "first_dispatch_t", "plan", "flags", "taps", "codes",
                 "ref", "trace", "attempts", "orig_req", "degraded",
                 "tally", "tally_out")

    def __init__(self, req: SampleRequest, future: ServingFuture,
                 submit_t: float, admit_t: float, group: tuple,
                 x, rng, state, pairs, cond, uncond, plan=None, flags=None,
                 taps=None, codes=None, ref=None, tally=None):
        self.req = req
        self.future = future
        self.submit_t = submit_t
        self.admit_t = admit_t
        self.group = group
        self.x = x                  # [num_samples, *sample_shape]
        self.rng = rng              # scan RNG carry (loop key lineage)
        self.state = state          # sampler state pytree
        self.pairs = pairs          # [nfe + 1, 2] turn pairs (numpy)
        self.nfe = int(req.diffusion_steps)
        self.done = 0               # completed turns of nfe + 1
        self.cond = cond
        self.uncond = uncond
        self.compile_ms = 0.0
        self.rounds = 0
        self.first_dispatch_t: Optional[float] = None
        # training-free diffusion cache (docs/CACHING.md): the
        # request's plan, its host-side [nfe + 1] refresh schedule (the
        # terminal turn is a refresh), and the
        # device-resident activation-cache carry. A composed
        # (timestep x spatial, ops/spatialcache.py) plan carries a
        # three-way code row instead of boolean flags plus the
        # score-reference carry `ref` riding rounds like taps.
        self.plan = plan
        self.flags = flags
        self.taps = taps
        self.codes = codes
        self.ref = ref
        # a counting model's sums over this row's evaluations, a small
        # named set (routed experts: `picks`, held picks by layer and
        # expert; a learned selection: `keys`): host zeros at
        # admission, then a device carry like `x`; `tally_out` is
        # (the handed-off batch's tallies on the device, this row's
        # index), which the completion thread fetches with the samples
        self.tally = tally
        self.tally_out = None
        # request-scoped trace accumulator (telemetry/reqtrace.py);
        # None on the disabled hub — the scheduler attaches it
        self.trace = None
        # serving resilience (serving/supervision.py), attached by the
        # scheduler after prepare: failed-attempt count carried across
        # requeues, the pre-brownout request for bit-exact replay, and
        # the brownout degradation flags surfaced on SampleResult
        self.attempts = 0
        self.orig_req = req
        self.degraded: tuple = ()

    @property
    def remaining(self) -> int:
        """Turns left: the sampler's steps and the terminal denoise."""
        return self.nfe + 1 - self.done


class SamplerProgramEngine:
    """Prepares request carries and advances them in batched rounds
    over a `DiffusionInferencePipeline`."""

    def __init__(self, pipeline, telemetry=None):
        self.pipeline = pipeline
        if telemetry is None:
            from ..telemetry import global_telemetry
            telemetry = global_telemetry()
        self.telemetry = telemetry
        self._programs: Dict[tuple, Any] = {}
        # constants of (sampler, NFE, schedule) and of num_samples,
        # computed once: the [nfe + 1, 2] turn pairs as host values, and
        # the null context tiled to a request's num_samples
        self._trajectories: Dict[tuple, np.ndarray] = {}
        self._null_contexts: Dict[int, Any] = {}
        # what `_params_for` holds: use_ema -> (the pipeline's tree,
        # {group: served tree}, {narrowing: served tree})
        self._served: Dict[bool, Tuple[Any, dict, dict]] = {}
        # last dispatched round's provenance (program kind/key, bucket,
        # live steps, cache-plan codes) — written by advance() on the
        # single dispatch thread, read by the
        # scheduler's request tracer right after the call. Host-side
        # dicts only; None until the first round.
        self.last_round_info: Optional[Dict[str, Any]] = None

    @property
    def rows_apart(self) -> bool:
        """Does a round evaluate this pipeline's model ONE ROW AT A TIME
        (the model's `serve_rows_apart`, `samplers/common.py`
        `rows_apart`)? A turn of b rows then costs b turns of one, so a
        wide round buys no throughput and holds every row until the
        round's end: the scheduler serves such a model in rounds of its
        smallest bucket (`ServingScheduler.batch_buckets`)."""
        return bool(getattr(getattr(self.pipeline, "model", None),
                            "serve_rows_apart", False))

    # -- keys -----------------------------------------------------------------
    def _plan_for(self, req: SampleRequest):
        """The request's effective plan — None, a `CachePlan`
        (timestep axis) or a `ComposedPlan` (timestep x spatial,
        ops/spatialcache.py), normalized so degenerate axes route to
        the simpler program. None when absent, disabled, or the
        pipeline's model cannot honor it (counted at
        `serving/cache_unsupported` — the request still runs, uncached,
        preserving the bit-exact default)."""
        from ..ops.diffcache import model_supports_cache
        from ..ops.spatialcache import resolve_plan
        plan = resolve_plan(req.cache_plan)
        if plan is None:
            return None
        if not model_supports_cache(self.pipeline.model, plan):
            self.telemetry.counter("serving/cache_unsupported").inc()
            return None
        return plan

    def group_key(self, req: SampleRequest) -> tuple:
        """Compatibility key: requests sharing it may ride one round.
        NFE is deliberately absent — rows mask their own trajectory
        length, so short requests don't queue behind long ones. The
        cache plan IS present (last element): plans change the compiled
        program (taps carry + depth split), so two plans must never
        share a round or a program (collision-tested)."""
        use_ema = bool(req.use_ema
                       and self.pipeline.ema_params is not None)
        ic = self.pipeline.input_config
        conditional = bool(ic is not None and ic.conditions)
        has_cond = bool(req.prompts is not None
                        or req.conditioning is not None or conditional)
        # CFG pairs a null embedding with the prompt — mirror
        # generate_samples: uncond exists only on the prompted path
        has_uncond = bool((req.prompts is not None
                           or req.conditioning is not None)
                          and conditional)
        plan = self._plan_for(req)
        return (int(req.resolution), req.sequence_length,
                int(req.channels), int(req.num_samples),
                str(req.sampler), float(req.guidance_scale),
                use_ema, has_cond, has_uncond,
                plan.key() if plan is not None else None)

    def _program_key(self, kind: str, group: tuple, bucket: int,
                     scan_steps: int) -> tuple:
        return (kind, int(bucket), int(scan_steps)) + group

    def _get_program(self, kind: str, group: tuple, bucket: int,
                     scan_steps: int, build) -> Tuple[Any, bool]:
        key = self._program_key(kind, group, bucket, scan_steps)
        prog = self._programs.get(key)
        if prog is not None:
            self.telemetry.counter("serving/program_cache_hits").inc()
            return prog, False
        self.telemetry.counter("serving/program_cache_misses").inc()
        prog = build()
        self._programs[key] = prog
        return prog, True

    @property
    def program_cache_size(self) -> int:
        return len(self._programs)

    def _register_evidence(self, kind: str, group: tuple, bucket: int,
                           scan_steps: int, program, args: tuple,
                           compile_s: float) -> None:
        """Program evidence registry hook (telemetry/programs.py):
        called ONLY on a cache miss, right after the compiling call, so
        every program ever cached by this engine has a `programs.jsonl`
        row under its exact dispatch key — compile ms measured the same
        way `SampleResult.compile_ms` is. No registry on the hub (the
        disabled default) -> no work at all."""
        reg = getattr(self.telemetry, "programs", None)
        if reg is None:
            return
        key = self._program_key(kind, group, bucket, scan_steps)
        reg.record_jitted(kind, key, program, args,
                          compile_ms=compile_s * 1e3)

    # -- request admission ----------------------------------------------------
    def _sampler_for(self, req: SampleRequest):
        return self.pipeline.get_sampler(req.sampler, req.guidance_scale,
                                         cache_plan=self._plan_for(req))

    def _params_for(self, group: tuple, ds, x, cond, uncond):
        """The tree every program of `group` takes: the pipeline's
        (`ema_params` or `params`) with each leaf held at the dtype the
        network converts it to, wherever that conversion is the leaf's
        only use (`DiffusionSampler.narrowing`, read from the group's
        own programs at one row's `x`, `cond`, `uncond`). Every other
        leaf is the pipeline's own array and the pipeline's trees are
        untouched; a tree with nothing to narrow is served as it is.
        Made at first use, which warm-up reaches before admission
        (`serving/served_trees`: 0 inside a steady window), and held by
        the identity of the pipeline's tree: replace
        `pipeline.ema_params` and the next round makes a new one and
        drops the old. Groups whose programs read the tree alike share
        one served tree."""
        use_ema = group[6]
        tree = (self.pipeline.ema_params
                if use_ema else self.pipeline.params)
        held = self._served.get(use_ema)
        if held is None or held[0] is not tree:
            # first use, or the pipeline's tree was replaced: what was
            # made from the old one is dropped here
            held = self._served[use_ema] = (tree, {}, {})
        _, by_group, by_narrowing = held
        served = by_group.get(group)
        if served is None:
            from ..samplers.common import narrow_tree
            dtypes = ds.narrowing(tree, x, cond, uncond)
            if dtypes not in by_narrowing:
                by_narrowing[dtypes] = narrow_tree(tree, dtypes)
                nbytes = [l.nbytes for l in jax.tree_util.tree_leaves(tree)]
                self.telemetry.counter("serving/served_trees").inc()
                self.telemetry.gauge("serving/served_tree_bytes").set(
                    sum(nbytes))
                self.telemetry.gauge(
                    "serving/served_tree_narrowed_bytes").set(
                    sum(n for n, d in zip(nbytes, dtypes) if d is not None))
            served = by_group[group] = by_narrowing[dtypes]
        return served

    def _launch(self, program, *args):
        """Every device launch of the dispatch thread goes through
        here, counted at `serving/launches`: over `serving/rounds` it is
        the number that has to stay under what the runtime queues
        behind a running program (module docstring)."""
        self.telemetry.counter("serving/launches").inc()
        return program(*args)

    def _trajectory(self, ds, nfe: int) -> np.ndarray:
        """The `[nfe + 1, 2]` turn pairs as HOST values, the last the
        terminal turn's `(t_term, t_term)`: `ds.trajectory_inputs`
        computes them, the same spacing the solo program closes over,
        and they are read back ONCE per (sampler, NFE) — at warm-up, or
        at the first sight of an NFE."""
        pairs = self._trajectories.get((ds, nfe))
        if pairs is None:
            from .scheduler import _device_get
            pairs = self._trajectories[(ds, nfe)] = _device_get(
                ds.trajectory_inputs(nfe))
        return pairs

    def _null_context(self, k: int):
        """The cached null tokens at `num_samples` k, exactly as
        `generate_samples` feeds them."""
        if k not in self._null_contexts:
            self._null_contexts[k] = \
                self.pipeline.input_config.get_unconditionals(
                    batch_size=k)[0]
        return self._null_contexts[k]

    def prepare(self, req: SampleRequest, future: ServingFuture,
                submit_t: float, admit_t: float) -> RequestState:
        """Build the device-resident carry for one request — the exact
        state a solo `generate_samples` call reaches right before its
        scan, so the batched trajectory continues bit-identically. Two
        launches (the group's "init" program: keys, sampler state, zero
        cache carries, the conditioning's upload; and its "noise"
        program) and no read-back."""
        pipe = self.pipeline
        k = req.num_samples
        conditional = bool(pipe.input_config is not None
                           and pipe.input_config.conditions)
        cond = uncond = None
        if req.conditioning is not None:
            cond = req.conditioning
            if conditional:
                uncond = self._null_context(k)
        elif req.prompts is not None:
            if not conditional:
                raise ValueError("pipeline has no conditioning inputs")
            c = pipe.input_config.conditions[0]
            cond = c.encoder(list(req.prompts))
            uncond = self._null_context(k)
        elif conditional:
            # prompt-less conditional checkpoint: the cached null
            # tokens, exactly as generate_samples feeds them
            cond = self._null_context(k)

        ds = self._sampler_for(req)
        group = self.group_key(req)
        nfe = int(req.diffusion_steps)

        def shape():
            resolution, channels = int(req.resolution), int(req.channels)
            if ds.autoencoder is not None:
                resolution //= ds.autoencoder.downscale_factor
                channels = ds.autoencoder.latent_channels
            if req.sequence_length is not None:
                return (k, req.sequence_length, resolution, resolution,
                        channels)
            return (k, resolution, resolution, channels)

        t0 = time.perf_counter()
        program, miss = self._get_program(
            "init", group, 0, 0, lambda: ds.make_init_program(
                shape(),
                self._params_for(
                    group, ds, jax.ShapeDtypeStruct(shape(), jnp.float32),
                    cond, uncond) if ds.cache_active else None,
                uncond))
        args = (np.int64(req.seed), cond)
        noise_key, loop_key, state, cond, taps, ref = \
            self._launch(program, *args)
        # the noise is a program of its own, the one the solo path
        # starts from (`make_noise_program`)
        noise, _ = self._get_program(
            "noise", group, 0, 0, lambda: ds.make_noise_program(shape()))
        x = self._launch(noise, noise_key)
        pairs = self._trajectory(ds, nfe)
        # host-side numpy schedules of a cache plan (zero device work);
        # step 0 of every plan refreshes, so the zero carries the init
        # program made are never consumed, and so does the terminal
        # turn (the solo scan's terminal denoise is a full evaluation)
        plan = ds.cache_plan if ds.cache_active else None
        flags = codes = None
        if ref is not None:
            from ..ops.spatialcache import CODE_REFRESH
            codes = np.append(plan.step_codes(nfe), np.int32(CODE_REFRESH))
        elif taps is not None:
            flags = np.append(plan.flags(nfe), True)
        st = RequestState(
            req=req, future=future, submit_t=submit_t, admit_t=admit_t,
            group=group, x=x, rng=loop_key, state=state, pairs=pairs,
            cond=cond, uncond=uncond, plan=plan,
            flags=flags, taps=taps, codes=codes, ref=ref,
            tally=(None if ds.tally_shape is None else
                   {name: np.zeros(shape, np.int32)
                    for name, shape in ds.tally_shape.items()}))
        if miss:            # both: they share the group's key
            compile_s = time.perf_counter() - t0
            st.compile_ms = compile_s * 1e3
            self._register_evidence("init", group, 0, 0, program, args,
                                    compile_s)
            self._register_evidence("noise", group, 0, 0, noise,
                                    (noise_key,), compile_s)
        return st

    # -- batched rounds -------------------------------------------------------
    def _span(self, name: str, **args):
        """A phase of a round on the dispatch thread (`serve.stack`,
        `serve.launch`, `serve.unstack`): per phase, never per row."""
        return self.telemetry.span(name, cat="serving", args=args)

    def advance(self, rows: List[RequestState], bucket: int,
                round_steps: int) -> Tuple[List[RequestState], float]:
        """Run one round of `round_length(rows, round_steps)` turns:
        `round_steps` is `SchedulerConfig.round_steps`, the longest
        round and the size of the compiled program (0 = run to
        completion); every row advances min(remaining, the round's
        length) turns of its own trajectory, the last of them its
        terminal denoise. Returns (rows whose terminal turn ran this
        round: their `x` is the denoised sample, compile seconds spent —
        0 on a cache hit). One launch; `serve.stack` is host arithmetic,
        and `serve.unstack` hands each row its own outputs of the
        program."""
        if bucket > 1 and self.rows_apart:
            # a model evaluated a row at a time has ONE round program, a
            # row's: a round of b rows is b launches of it (the turns a
            # wider program would run one after another, less the padded
            # slots', and no wider program to compile and hold)
            finished, compile_s = [], 0.0
            for r in rows:
                done, spent = self.advance([r], 1, round_steps)
                finished += done
                compile_s += spent
            return finished, compile_s
        group = rows[0].group
        ds = self._sampler_for(rows[0].req)
        plan = rows[0].plan             # group-uniform (plan is in the key)
        span = self._span
        sched_row = None        # cache-plan step codes this round ran
        size, steps = round_length(rows, round_steps)
        with span("serve.stack"):
            # padding slots replicate row 0 (their output is discarded)
            srcs = rows + [rows[0]] * (bucket - len(rows))
            pairs = np.empty((bucket, size, 2), np.float32)
            n_act = np.empty((bucket,), np.int32)
            offsets = np.empty((bucket,), np.int32)
            term = np.empty((bucket,), np.int32)
            for i, r in enumerate(srcs):
                sl = r.pairs[r.done:r.done + steps]
                pairs[i, :len(sl)] = sl     # inert past n_act: the last
                pairs[i, len(sl):] = sl[-1]     # pair again
                n_act[i] = min(r.remaining, steps)
                offsets[i] = r.done
                # the turn that is the row's terminal denoise, if this
                # round reaches it
                term[i] = r.nfe - r.done if r.remaining <= steps else -1
            carries = [{"x": r.x, "keys": r.rng, "state": r.state,
                        "cond": r.cond, "uncond": r.uncond} for r in srcs]
            # the round's length is data: one program for every length
            batch = {"pairs": pairs, "n_act": n_act, "offsets": offsets,
                     "steps": np.int32(steps), "term": term}
            if plan is None:
                kind_used, build = "chunk", ds.make_chunk_program
                if ds.tally_shape is not None:
                    for c, r in zip(carries, srcs):
                        c["tally"] = r.tally
            elif rows[0].ref is not None:
                # composed (timestep x spatial) plan: round-level step
                # codes = per-step MAX over each row's own offset-aligned
                # schedule (host-side numpy, zero syncs) — refresh beats
                # spatial beats reuse, so no row gets LESS refresh than
                # ITS plan scheduled; round-mates can only add fidelity
                want = np.zeros((size,), np.int32)
                for r in rows:
                    w = r.codes[r.done:r.done + steps]
                    want[:len(w)] = np.maximum(want[:len(w)], w)
                sched_row = want.tolist()
                kind_used = "chunk_spatial"
                build = ds.make_spatial_chunk_program
                batch["codes"] = want
                for c, r in zip(carries, srcs):
                    c["taps"], c["refs"] = r.taps, r.ref
            else:
                # round-level refresh flags: OR of each row's own
                # offset-aligned schedule (host-side numpy, zero syncs) —
                # no row ever misses ITS scheduled refresh; round-mates
                # may grant extra free refreshes (fidelity can only
                # improve)
                want = np.zeros((size,), bool)
                for r in rows:
                    w = r.flags[r.done:r.done + steps]
                    want[:len(w)] |= w
                sched_row = want.astype(int).tolist()
                kind_used = "chunk_cached"
                build = ds.make_cached_chunk_program
                batch["flags"] = want
                for c, r in zip(carries, srcs):
                    c["taps"] = r.taps
            prog_args = (self._params_for(group, ds, srcs[0].x, srcs[0].cond,
                                          srcs[0].uncond),
                         tuple(carries), batch)

        with span("serve.launch", kind=kind_used):
            t0 = time.perf_counter()
            program, miss = self._get_program(
                kind_used, group, bucket, size,
                lambda: _round_program(build(size)))
            # per row (x, key, state), then the taps and the
            # score-reference carries of the cached programs
            outs = self._launch(program, *prog_args)
            compile_s = (time.perf_counter() - t0) if miss else 0.0
        n_live = [int(n) for n in n_act[:len(rows)]]
        # turn occupancy: live / run is 1.0 when every row is live on
        # every turn of its rounds (continuous mode); and the real
        # rows' terminal denoises that rode this round
        count = self.telemetry.counter
        count("serving/row_steps_run").inc(len(rows) * steps)
        count("serving/row_steps_live").inc(sum(n_live))
        count("serving/terminal_turns").inc(
            int((term[:len(rows)] >= 0).sum()))
        if sched_row is not None:
            # codes of a composed plan: 2 refresh, 1 spatial, 0 reuse;
            # flags of a timestep plan: 1 refresh, 0 reuse
            spatial = kind_used == "chunk_spatial"
            top = 2 if spatial else 1
            ran = [c for n in n_live for c in sched_row[:n]]
            count("serving/cache_rows").inc(len(rows))
            count("serving/cache_refresh_steps").inc(ran.count(top))
            count("serving/cache_reused_steps").inc(ran.count(0))
            if spatial:
                count("serving/spatial_rows").inc(len(rows))
                count("serving/spatial_steps").inc(ran.count(1))
        if miss:
            # evidence registry (telemetry/programs.py): the program
            # just paid its compile — register it under the exact
            # dispatch key with measured compile ms. No-op without a
            # registry (the disabled default hub), so the warm path and
            # the zero-retrace acceptance see no change.
            self._register_evidence(kind_used, group, bucket, size,
                                    program, prog_args, compile_s)
        self.last_round_info = {
            "kind": kind_used,
            "key": str(self._program_key(kind_used, group, bucket,
                                         size)),
            "bucket": int(bucket), "rows": len(rows),
            "steps": int(steps), "miss": bool(miss),
            "n_act": n_live,
        }
        if sched_row is not None:
            self.last_round_info["codes"] = sched_row

        finished: List[RequestState] = []
        with span("serve.unstack"):
            for r, out, n in zip(rows, outs, n_live):
                if r.tally is not None:     # (x, key, state, tally)
                    *out, r.tally = out
                r.x, r.rng, r.state, r.taps, r.ref = \
                    (tuple(out) + (None, None))[:5]
                r.done += n
                r.rounds += 1
                r.compile_ms += compile_s * 1e3
                if r.remaining <= 0:
                    finished.append(r)
        return finished, compile_s

    def finalize(self, rows: List[RequestState],
                 bucket: int) -> Tuple[jax.Array, float]:
        """Hand off rows whose terminal turn ran (`advance`'s
        `finished`): stack + (optional) decode + clip in one launch that
        evaluates no model. Returns (`[bucket, num_samples,
        *sample_shape]` device array whose first `len(rows)` entries
        are the rows' samples in row order, compile seconds). A counting
        model's tallies come out of the same launch and stay on the
        device, on the rows (`tally_out`), for `count_tally`."""
        group = rows[0].group
        ds = self._sampler_for(rows[0].req)
        tallied = ds.tally_shape is not None
        with self._span("serve.stack"):
            srcs = rows + [rows[0]] * (bucket - len(rows))
            carries = tuple({"x": r.x, "tally": r.tally} if tallied
                            else {"x": r.x} for r in srcs)

        with self._span("serve.launch", kind="handoff"):
            program, miss = self._get_program(
                "handoff", group, bucket, 0,
                lambda: _handoff_program(ds.autoencoder))
            t0 = time.perf_counter()
            out = self._launch(program, carries)
            compile_s = (time.perf_counter() - t0) if miss else 0.0
        if tallied:
            out, tallies = out
            for i, r in enumerate(rows):
                r.tally_out = (tallies, i)
        if miss:
            self._register_evidence("handoff", group, bucket, 0,
                                    program, (carries,), compile_s)
        return out, compile_s

    def count_tally(self, rows: List[RequestState], fetch) -> None:
        """Add a handed-off batch's tallies to the telemetry counters
        (docs/OBSERVABILITY.md): called by the completion thread where it
        fetches the samples, with its `fetch`, for rows that carry a
        `tally_out` (a counting model). What a tally's names mean is the
        model's to say (`tally_counters`: the `moe/picks_*` three of a
        model with routed experts, `dsa/keys_*` of a learned selection),
        from the row's sums and the evaluations its request asked for
        (host arithmetic: steps and the terminal one, twice guided)."""
        tallies = fetch(rows[0].tally_out[0])   # {name: [bucket, ...]}
        count = self.telemetry.counter
        for r in rows:
            evals = (r.nfe + 1) * int(r.req.num_samples) * (
                2 if r.uncond is not None and r.req.guidance_scale > 0
                else 1)
            added = self.pipeline.model.tally_counters(
                {name: n[r.tally_out[1]] for name, n in tallies.items()},
                evals, r.x.shape[1:],
                0 if r.cond is None else r.cond.shape[-2])
            for name, n in added.items():
                count(name).inc(n)

    # -- program-cache pre-warming -------------------------------------------
    def prewarm(self, reqs: List[SampleRequest], round_steps: int,
                batch_buckets: Tuple[int, ...]) -> Dict[str, Any]:
        """Compile the hot (bucket, NFE, plan) program tuples BEFORE
        admission opens, so cold-compile latency never hits user
        traffic (docs/SERVING.md).

        Each request in `reqs` is a traffic prototype: for every batch
        bucket, one synthetic row is prepared and driven through the
        EXACT dispatch path — `prepare` -> `advance` rounds ->
        `finalize` — so the compiled programs land under the very keys
        warm traffic computes (`jax.jit` compiles synchronously at the
        first call; a later identical-shape round is a guaranteed
        cache hit). Outputs are discarded; the synthetic rounds DO
        count into the `serving/cache_*` step counters (they ran), and
        the compile work is reported here rather than on any request's
        latency. Returns {"programs", "seconds"}; counted at
        `serving/prewarm_programs` / `serving/prewarm_ms`."""
        from .scheduler import _block_until_ready
        t0 = time.perf_counter()
        before = self.program_cache_size
        for req in reqs:
            for bucket in sorted(set(batch_buckets)):
                rows = [self.prepare(req, ServingFuture(), t0, t0)]
                while rows[0].remaining > 0:
                    finished, _ = self.advance(rows, bucket, round_steps)
                out, _ = self.finalize(finished, bucket)
                # settle before admission opens: the compile itself is
                # synchronous, this only keeps the warmup device work
                # from overlapping the first real round
                _block_until_ready(out)
        seconds = time.perf_counter() - t0
        programs = self.program_cache_size - before
        self.telemetry.counter("serving/prewarm_programs").inc(programs)
        self.telemetry.gauge("serving/prewarm_ms").set(seconds * 1e3)
        return {"programs": programs, "seconds": seconds}

    def plan_parallelism(self, param_shapes=None, batch_shape=None,
                         devices=None, probe_fn=None, **plan_kwargs):
        """The chips-per-request vs requests-per-chip decision from the
        same measured search the trainer uses (`parallel/planner.py`),
        with optimizer/EMA multipliers zeroed — inference holds params
        only, so far more aggressive replication fits per chip and the
        planner decides from HBM + comm evidence whether one request
        should span chips (tensor/fsdp axes) or each chip should take
        its own requests (data axis). The decision is committed to the
        program registry under kind "plan_infer" so
        `scripts/compare_runs.py` diffs serving layout decisions like
        any other program evidence. Returns the `PlanDecision`;
        `decision.chips_per_request` is the layout answer."""
        import os

        from ..parallel.planner import CACHE_ENV, ParallelPlanner
        if param_shapes is None:
            params = getattr(self.pipeline, "params", None)
            if params is None:
                raise ValueError("plan_parallelism needs param_shapes "
                                 "when the pipeline carries no params")
            param_shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    tuple(getattr(x, "shape", ())),
                    getattr(x, "dtype", jnp.float32)), params)
        ctor = {}
        if "min_size" in plan_kwargs:
            ctor["min_size"] = plan_kwargs.pop("min_size")
        planner = ParallelPlanner(
            cache_dir=os.environ.get(CACHE_ENV) or None,
            probe_fn=probe_fn, metrics=self.telemetry,
            opt_mult=0.0, ema_mult=0.0, **ctor)
        plan_kwargs.setdefault("include_pipeline", False)
        decision = planner.plan(param_shapes, batch_shape=batch_shape,
                                devices=devices, **plan_kwargs)
        registry = getattr(self.telemetry, "programs", None)
        if registry is not None:
            planner.commit(registry, decision, kind="plan_infer")
        return decision
