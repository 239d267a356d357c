"""DiffusionTrainer: wires mesh, shardings, the jitted step, and the fit loop.

Parity with reference SimpleTrainer/DiffusionTrainer fit/train_loop
(trainer/simple_trainer.py:148-677, diffusion_trainer.py:41-370):
init/load state, epoch loop, NaN/abnormal-loss recovery with best-state
rollback, periodic logging, checkpoint save on improvement. TPU-native
differences: params + optimizer + EMA sharded over the `fsdp` axis from
initialization on (the reference replicates everything), the step is one
jit program with donated state, and the loss readback that the reference
pays every step (simple_trainer.py:542) happens only at log cadence.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel import fsdp_sharding_tree, sharding_tree
from ..parallel.mesh import batch_spec
from ..profiling import MFUMeter, compiled_flops, device_peak_flops, mfu
from ..predictors import PredictionTransform
from ..resilience import events as _res_events
from ..resilience import faults as _res_faults
from ..telemetry import global_telemetry as _global_telemetry
from ..telemetry.tracing import traced_steps
from ..schedulers.common import NoiseSchedule
from ..typing import Policy, PyTree
from .train_state import TrainState
from .train_step import TrainStepConfig, make_train_step


# The fit loop's ONLY host-synchronization primitives, routed through
# module-level names so tests can count them: the sync-free-loop
# contract ("off-sample steps perform no block_until_ready and no
# scalar loss fetch") is asserted by monkeypatching these with counting
# wrappers — a future refactor that sneaks a per-step sync back in
# fails that test instead of silently re-serializing the pipeline.

def _block_until_ready(x) -> None:
    jax.block_until_ready(x)


def _is_ready(x) -> bool:
    """Non-blocking completion query (False = still in flight)."""
    return bool(x.is_ready())


def _fetch_losses(arrs):
    """The one host sync of a log window: read the device-resident loss
    window back as floats (blocks until the newest step settles)."""
    return [float(v) for v in jax.device_get(list(arrs))]


def _fetch_ring(ring):
    """The loss-ring variant of the window sync: ONE device_get of the
    in-graph ring array covers every step in the window (blocks until
    the newest step settles). Module-level for the same counting-mock
    contract as _fetch_losses."""
    return np.asarray(jax.device_get(ring))


def _fetch_gate_events(arr):
    """Read the [3] in-graph gate-activation counter
    (TrainState.gate_events) back to host as int64 — piggybacks on the
    log window, where the window fetch has already settled the
    pipeline. Module-level for the same counting-mock contract as
    _fetch_losses."""
    return np.asarray(jax.device_get(arr)).astype(np.int64)


# a "compile" first step no slower than this multiple of the median
# steady step did not actually compile (warm persistent cache) and is
# re-attributed productive — see GoodputLedger.reattribute
_COMPILE_RECLASS_RATIO = 2.0


@dataclasses.dataclass
class TrainerConfig:
    ema_decay: float = 0.999
    uncond_prob: float = 0.12
    weighted_loss: bool = True
    normalize: bool = True
    log_every: int = 100
    # loss <= this, NaN or Inf triggers best-state rollback
    # (reference simple_trainer.py:542-575)
    abnormal_loss_floor: float = 1e-8
    keep_best_state: bool = True
    seed: int = 0
    # Preemption safety: on SIGTERM (the TPU-pod eviction signal) the fit
    # loop checkpoints and returns cleanly instead of dying mid-step.
    # The reference has no preemption handling (a host loss kills the
    # job, SURVEY §5.3).
    checkpoint_on_sigterm: bool = True
    # Flat-parameter training (trainer/optim.py rationale): params, EMA
    # and optimizer state live as ONE padded vector per dtype; the model
    # unflattens inside the loss, AD returns flat grads, and every
    # optimizer/EMA/apply update is a handful of fused HBM-floor kernels
    # instead of ~2 launch-bound kernels per leaf. Requires an
    # ELEMENTWISE optax chain (adam/adamw/sgd/lion [+ global-norm
    # clip]; NOT lamb/adafactor/per-block transforms). Checkpoint
    # layout changes (flat vectors) — choose per run.
    flat_params: bool = False
    # In-training profiler capture: when set, a jax.profiler trace of
    # `profile_steps` steps starting at `profile_at_step` (post-warmup)
    # lands in profile_dir.
    profile_dir: Optional[str] = None
    profile_at_step: int = 10
    profile_steps: int = 5
    # Automated device-profile windows (telemetry/devprof.py): > 0
    # opens a jax.profiler window of `profile_steps` steps every
    # `profile_cadence` steps under an ENABLED telemetry hub, parses
    # the capture into a `devprof.jsonl` attribution row (op families,
    # modules, collective split) reconciled against the program
    # registry (measured MFU, roofline verdict, comm calibration).
    # Window overhead lands in the `profile` phase + goodput bucket;
    # off-window steps pay two int compares — no device work, no host
    # syncs. Independent of the one-shot profile_dir capture above.
    profile_cadence: int = 0
    # On-demand arming: when this path exists at a log step, it is
    # consumed and ONE profile window opens at the next step — the
    # "profile the live run NOW" knob (also reachable while
    # profile_cadence is 0).
    profile_trigger: Optional[str] = None
    # Heartbeat watchdog (resilience/watchdog.py): None disables. When a
    # step (or the loader feeding it) stalls past this many seconds, a
    # `watchdog_stall` event is recorded and the stall action runs:
    # "sigterm" re-uses the preemption path (clean checkpoint-and-exit),
    # "flag" only marks stop so the loop exits at its next iteration.
    # The first step is exempt (jit compile can legitimately exceed it).
    watchdog_timeout: Optional[float] = None
    watchdog_action: str = "sigterm"
    # Resume INSIDE fit, before the first step: with a coordinated
    # checkpointer this is the consensus-restore round (every host
    # restores the same committed step or fit raises before stepping);
    # without one it is the ordinary fallback restore. A missing
    # checkpoint is a cold start, not an error.
    restore_at_start: bool = False
    # Training-health monitor (telemetry/numerics.py): every N steps the
    # trainer dispatches a SECOND compiled step that also computes
    # per-module grad/param norms, update ratios and non-finite counts
    # in-graph; 0 disables and off-cadence steps run the unmonitored
    # program unchanged (zero extra device work). Cadence steps pay one
    # aux readback (a host sync) plus the host-side detector.
    numerics_cadence: int = 0
    # What a detected anomaly does: "warn" records events/metrics only;
    # "skip_step" compiles the monitored step with an in-graph
    # non-finite gate (a poisoned step's update never lands — z-score
    # spikes still only warn, the state is donated by the time the host
    # sees them); "rollback" restores the best state (or walks back to
    # the newest restorable checkpoint when no best state exists yet —
    # the PR-1/2 fallback-restore path) on any hard anomaly.
    anomaly_action: str = "warn"
    anomaly_zscore: float = 6.0
    anomaly_window: int = 50
    # Bounded-depth asynchronous dispatch: the fit loop keeps up to
    # this many steps in flight (dispatch is async; the host runs
    # ahead). Exceeding the bound waits — non-blockingly checked first
    # — on the OLDEST in-flight step, so the device stays at most
    # `pipeline_depth` steps behind the host instead of the host
    # enqueueing unbounded work (and pinning unbounded batch buffers).
    # 1 ~= classic one-deep double buffering; 0/negative disables the
    # bound (the log-cadence loss fetch is then the only settle point).
    pipeline_depth: int = 2
    # Sampled device-phase timing (telemetry/phases.py): with an
    # enabled telemetry hub, close async dispatch with
    # block_until_ready only every N-th step — off-sample steps add
    # ZERO host syncs, and phase/goodput attribution degrades to
    # window granularity (docs/OBSERVABILITY.md "Sampled phase
    # timing"). 1 = exact per-step device timing (the pre-pipelining
    # behavior). Ignored when telemetry is disabled.
    telemetry_sample_every: int = 1
    # In-graph loss ring (train_step.py / train_state.py): > 0 carries
    # a device-resident [W] ring in the TrainState that the jitted step
    # writes at slot step % W. The fit loop then fetches losses ONCE
    # per W steps — one readback per window even at log_every=1 — and
    # emits the whole window's per-step losses retroactively
    # (`window_losses` in the log metrics; recovery checks see every
    # value, delayed by at most W steps). 0 (default) keeps the
    # pre-ring behavior AND the pre-ring TrainState pytree — ring
    # checkpoints carry one extra [W] leaf, so flip it per run, not
    # mid-run.
    loss_ring: int = 0
    # In-graph non-finite gate on EVERY step (train_step.py
    # _finite_only_gate): any non-finite element of the updated
    # params/opt-state/EMA keeps its previous value (elementwise — a
    # global verdict would ~4x compile time, see the gate's docstring),
    # so the live state — and any checkpoint taken from it — is finite
    # by construction. This is what lets the save path skip the
    # per-save loss fetch; disabling it restores the exact ungated
    # step program AND the legacy synchronous save-cadence loss check.
    gate_nonfinite: bool = True
    # Gate-activation visibility (PR 5 follow-up): carry a [3] int32
    # counter in the TrainState that the in-graph gate increments with
    # the number of params/opt-state/EMA elements it masked; the fit
    # loop reads it once per log window (no extra pipeline sync — the
    # window fetch already settled everything) and surfaces deltas as
    # `numerics/gate_activations*` counters + a `gate_activated`
    # event. OPT-IN: the count is a reduction over every state leaf,
    # which measurably blows up XLA CPU compile of the step (the exact
    # pathology `_finite_only_gate`'s elementwise design avoids), and
    # the extra leaf changes the checkpoint pytree — flip per run, not
    # mid-run. Requires gate_nonfinite.
    gate_counter: bool = False


class DiffusionTrainer:
    """Owns sharded state + the compiled step; drives the training loop."""

    def __init__(self,
                 apply_fn: Callable,
                 init_fn: Callable[[jax.Array], PyTree],
                 tx: optax.GradientTransformation,
                 schedule: NoiseSchedule,
                 transform: PredictionTransform,
                 mesh: Optional[Mesh] = None,
                 config: TrainerConfig = TrainerConfig(),
                 policy: Optional[Policy] = None,
                 autoencoder: Optional[Any] = None,
                 null_cond: Optional[PyTree] = None,
                 checkpointer: Optional[Any] = None,
                 telemetry: Optional[Any] = None,
                 elastic: Optional[Any] = None,
                 plan: Optional[Any] = None,
                 partition_rules: Optional[Sequence] = None):
        """apply_fn(params, x_t, t, cond) -> raw output;
        init_fn(key) -> params (closes over example input shapes).

        `plan`: "auto" resolves mesh AND partition rules from the
        auto-parallelism planner (`parallel/planner.resolve_plan` —
        static search over the param tree, cached in
        $FLAXDIFF_PLAN_CACHE, committed to the telemetry hub's program
        registry), replacing the hand-written mesh/rule table; a
        `PlanDecision` applies a previously-searched plan verbatim.
        With a plan, `mesh` may be None. `partition_rules` pins an
        explicit `match_partition_rules` table (the planner's probe
        harness and tests use it; a resolved plan overrides it).

        `telemetry`: a telemetry.Telemetry hub; None falls back to the
        process-global hub at fit time (disabled by default, so
        un-instrumented runs keep fully-async step dispatch).

        `elastic`: a resilience.ElasticWorldManager. The fit loop then
        survives a lost peer by shrinking the world (instead of
        checkpoint-and-exit on coordination_lost), admits parked
        replacement hosts at commit boundaries, and turns hard
        numerics anomalies into pod quorum votes
        (docs/RESILIENCE.md "Elastic world")."""
        self.mesh = mesh
        self.config = config
        self.telemetry = telemetry
        self.elastic = elastic
        self.schedule = schedule
        self.transform = transform
        self.checkpointer = checkpointer
        self._apply_fn = apply_fn

        self._param_template = None
        if config.flat_params:
            from .optim import param_template, unflatten_params
            key_t = jax.random.PRNGKey(config.seed)
            self._param_template = param_template(
                jax.eval_shape(lambda k: init_fn(k),
                               jax.random.split(key_t)[0]))
            template = self._param_template
            inner_apply, inner_init = apply_fn, init_fn

            def apply_fn(flats, x, t, cond):        # noqa: F811
                # the unflatten runs INSIDE the differentiated function:
                # its AD transpose re-assembles leaf gradients into the
                # flat vector, so grads arrive flat for free
                return inner_apply(unflatten_params(template, flats),
                                   x, t, cond)

            def init_fn(key):                       # noqa: F811
                from .optim import flatten_params
                return flatten_params(inner_init(key), 1024)

        from ..telemetry.numerics import ANOMALY_ACTIONS
        if config.anomaly_action not in ANOMALY_ACTIONS:
            raise ValueError(f"anomaly_action {config.anomaly_action!r} "
                             f"not in {ANOMALY_ACTIONS}")
        if config.gate_counter and not config.gate_nonfinite:
            raise ValueError("gate_counter counts the in-graph gate's "
                             "activations — it requires gate_nonfinite")

        step_cfg = TrainStepConfig(
            uncond_prob=config.uncond_prob,
            ema_decay=config.ema_decay,
            normalize=config.normalize,
            weighted_loss=config.weighted_loss,
        )
        # kept for the lazily-jitted NaN-provenance probe (the rebound
        # flat-params apply_fn, not the caller's original)
        self._probe_inputs = (apply_fn, schedule, transform,
                              dict(config=step_cfg, policy=policy,
                                   autoencoder=autoencoder,
                                   null_cond=null_cond))
        step_fn = make_train_step(apply_fn, schedule, transform, step_cfg,
                                  policy=policy, autoencoder=autoencoder,
                                  null_cond=null_cond,
                                  gate_nonfinite=config.gate_nonfinite)
        monitored_step_fn = None
        if config.numerics_cadence > 0:
            from ..telemetry.numerics import NumericsConfig
            monitored_step_fn = make_train_step(
                apply_fn, schedule, transform, step_cfg,
                policy=policy, autoencoder=autoencoder,
                null_cond=null_cond,
                # the monitored twin must gate whenever the plain step
                # does — an ungated cadence step would be the one hole
                # in the "state is finite by construction" save guard
                gate_nonfinite=config.gate_nonfinite,
                numerics=NumericsConfig(
                    # a flat-param state has no module structure
                    per_module=not config.flat_params,
                    # both recovery actions gate in-graph: under
                    # `rollback` the restore replaces the step anyway,
                    # and an unapplied poisoned update keeps the
                    # provenance pass exact (an applied one smears NaNs
                    # into EVERY module's params before the host can
                    # react). Only `warn` leaves updates untouched —
                    # its contract is strictly observational.
                    skip_nonfinite=(config.anomaly_action
                                    in ("skip_step", "rollback"))))

        # fp16 compute needs loss scaling (reference diffusion_trainer.py
        # :214-240 DynamicScale path); bf16's exponent range does not.
        dynamic_scale = None
        if policy is not None and policy.compute_dtype == jnp.float16:
            from flax.training.dynamic_scale import DynamicScale
            dynamic_scale = DynamicScale()

        def create_state(key):
            init_key, train_key = jax.random.split(key)
            params = init_fn(init_key)
            return TrainState.create(
                apply_fn=apply_fn, params=params, tx=tx, rng=train_key,
                ema_decay=config.ema_decay, dynamic_scale=dynamic_scale,
                loss_ring_size=max(config.loss_ring, 0),
                gate_counter=config.gate_counter)

        key = jax.random.PRNGKey(config.seed)
        state_shapes = jax.eval_shape(create_state, key)

        self.plan_decision = None
        self._partition_rules = partition_rules
        if plan is not None:
            from ..parallel.planner import resolve_plan
            decision = resolve_plan(plan, state_shapes.params,
                                    telemetry=telemetry)
            mesh = decision.build_mesh()
            self._partition_rules = decision.rules
            self.plan_decision = decision
        if mesh is None:
            raise ValueError("DiffusionTrainer needs a mesh or a plan")
        self.mesh = mesh

        self.state_specs = fsdp_sharding_tree(
            state_shapes, mesh, rules=self._partition_rules)
        self.state_shardings = sharding_tree(self.state_specs, mesh)

        with mesh:
            self.state = jax.jit(
                create_state, out_shardings=self.state_shardings)(key)

        self._batch_axis = batch_spec(mesh)

        # kept so an elastic mesh rebuild can re-jit the same programs
        # against the new mesh/shardings (_compile_programs)
        self._step_fn = step_fn
        self._monitored_fn = monitored_step_fn
        self._compile_programs()

        self.best_loss = float("inf")
        self.best_state: Optional[TrainState] = None
        # the step the best state was snapshotted at — the data plane's
        # rewind target when a rollback restores it
        self.best_step: Optional[int] = None

        if self._param_template is not None and checkpointer is not None:
            # flat-state checkpoints are unreadable without the template
            # (inference/pipeline.py from_checkpoint): persist it beside
            # the shards from whoever owns the flat state — every
            # producer, not just the CLI
            self._write_param_template()

    def _write_param_template(self):
        import json as _json

        from .optim import TEMPLATE_FILENAME, serialize_template
        if jax.process_index() != 0:
            return
        # epath, not builtin open: the checkpointer itself writes through
        # it, so object-store directories (gs://...) that hold a valid
        # flat checkpoint get a readable template beside it instead of a
        # local-only warn + guaranteed inference FileNotFoundError
        from etils import epath
        path = epath.Path(self.checkpointer.directory) / TEMPLATE_FILENAME
        try:
            path.write_text(
                _json.dumps(serialize_template(self._param_template)))
        except OSError as e:
            import warnings
            warnings.warn(f"could not write {path}: {e}; flat-params "
                          "checkpoints need it for inference restore",
                          stacklevel=2)

    def _compile_programs(self):
        """(Re)bind the jitted step programs to the CURRENT mesh and
        state shardings — at construction, and again after an elastic
        mesh rebuild (the old programs bake in the old device
        assignment)."""
        mesh = self.mesh
        self._step = jax.jit(
            self._step_fn,
            donate_argnums=(0,),
            out_shardings=(self.state_shardings, NamedSharding(mesh, P())),
        )
        # the monitored twin: same program + in-graph numerics aux
        # (replicated scalars). Compiled separately so off-cadence steps
        # keep running the EXACT unmonitored program.
        self._step_monitored = None
        if self._monitored_fn is not None:
            self._step_monitored = jax.jit(
                self._monitored_fn,
                donate_argnums=(0,),
                out_shardings=(self.state_shardings,
                               NamedSharding(mesh, P()),
                               NamedSharding(mesh, P())),
            )
        self._probe = None      # lazily-jitted NaN-provenance pass
        self._step_flops: Dict[Any, Optional[float]] = {}

    # -- elastic world transitions -------------------------------------------
    def _rebuild_world_mesh(self, force: bool = False) -> bool:
        """Rebuild a 1-D `'data'` mesh over THIS host's local devices
        and re-shard/re-jit around it (elastic shrink helper).

        After a peer is lost, a mesh that spanned its devices is dead —
        every collective over it would hang — so the survivors' world
        re-forms over the devices they still own. A mesh that was
        already local-only (the per-host data-parallel layout the
        elastic chaos suite runs) survives unchanged, keeping its
        compiled programs and in-flight state (returns False).
        `force=True` rebuilds even a live local mesh."""
        local_count = sum(1 for d in self.mesh.devices.flat
                          if d.process_index == jax.process_index())
        all_local = local_count == self.mesh.devices.size
        if all_local and not force:
            return False
        from ..parallel.mesh import local_data_mesh
        new_mesh = local_data_mesh()
        shapes = jax.tree_util.tree_map(
            lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)
                       if isinstance(x, jax.Array) else x), self.state)
        self.mesh = new_mesh
        # a searched plan is dead with the mesh it was searched for —
        # the shrunken world re-infers (and can re-plan at next launch)
        self._partition_rules = None
        self.plan_decision = None
        self.state_specs = fsdp_sharding_tree(shapes, new_mesh)
        self.state_shardings = sharding_tree(self.state_specs, new_mesh)
        self._batch_axis = batch_spec(new_mesh)
        if all_local:
            # live state is fully addressable: move it onto the new
            # mesh. (Post-shrink the old arrays reference dead devices
            # and are NOT moved — the consensus-step restore that
            # follows places fresh shards directly on the new mesh.)
            self.state = jax.device_put(self.state, self.state_shardings)
        self.best_state = None      # old-mesh arrays; re-seeded on restore
        self.best_step = None
        self._compile_programs()
        _res_events.global_event_log().record(
            "mesh_rebuilt", "elastic.world",
            detail=f"1-D 'data' mesh over {new_mesh.devices.size} local "
                   f"device(s); step programs re-jitted")
        return True

    def _elastic_restore(self, step: int) -> int:
        """Restore exactly `step` with shards placed onto the CURRENT
        mesh, independent of the live state's (possibly dead) old
        shardings — the post-transition variant of
        `restore_checkpoint`."""
        def absify(x, s):
            if isinstance(x, jax.Array) or hasattr(x, "shape"):
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
            return x
        abstract = jax.tree_util.tree_map(absify, self.state,
                                          self.state_shardings)
        self.state, meta = self.checkpointer.restore(abstract, step=step)
        best = float(meta.get("best_loss", float("inf")))
        self.best_loss = best if best > 0 else float("inf")
        if self.config.keep_best_state:
            self.best_state = jax.tree_util.tree_map(jnp.copy, self.state)
            self.best_step = int(step)
        return int(step)

    # -- flash autotuning ----------------------------------------------------
    def autotune_flash(self, global_batch: PyTree):
        """Per-shape flash-attention autotuning (ops/autotune.py): a
        `jax.eval_shape` scouting pass over the train step records every
        attention shape the model dispatches (no device work, nothing
        compiled), then measured probes pick block sizes / native-d per
        shape and persist them to the active autotuner's cache dir.
        Returns {shape_key: FlashPlan} for the shapes probed — empty
        when no autotuner is active (`ops.autotune.activate` /
        FLAXDIFF_FLASH_TUNE_CACHE) or every shape was already cached
        (the warm-cache contract: zero probes). Call BEFORE the first
        train step so the real compile picks the tuned plans up."""
        from ..ops import autotune as _autotune
        aut = _autotune.active()
        if aut is None:
            return {}
        from ..parallel.context import use_mesh
        batch = self._numeric_subtree(global_batch)
        with use_mesh(self.mesh):
            jax.eval_shape(self._step, self.state, batch)
        return aut.probe_pending()

    # -- profiling -----------------------------------------------------------
    def step_flops(self, global_batch: PyTree) -> Optional[float]:
        """Per-device FLOPs of the compiled train step (XLA cost analysis);
        cached per batch shape. None on backends without a cost model."""
        batch = self._numeric_subtree(global_batch)
        key = tuple((jax.tree_util.keystr(p), x.shape)
                    for p, x in jax.tree_util.tree_flatten_with_path(batch)[0])
        if key not in self._step_flops:
            from ..parallel.context import use_mesh
            with use_mesh(self.mesh):
                self._step_flops[key] = compiled_flops(
                    self._step, self.state, batch)
        return self._step_flops[key]

    def step_model_flops(self, global_batch: PyTree) -> Optional[float]:
        """Analytic per-STEP matmul+conv FLOPs at true shapes (jaxpr walk,
        no compile, no device work) — the unpadded "model FLOPs" MFU
        numerator. This is the whole-mesh count (the jaxpr is traced
        pre-partitioning); divide by device count for a per-chip figure.
        Meaningful only when the model's attention backend is visible to
        tracing ("xla"): pallas_call bodies are opaque, so a flash-backend
        trainer undercounts — build an xla-backend twin for counting."""
        from ..parallel.context import use_mesh
        from ..profiling import traced_model_flops
        batch = self._numeric_subtree(global_batch)
        with use_mesh(self.mesh):
            return traced_model_flops(self._step, self.state, batch)

    def _register_program_evidence(self, tel, global_batch,
                                   registered: set,
                                   compile_s, monitored_compiled: bool,
                                   flops_cost) -> Optional[str]:
        """Program evidence registry hook (telemetry/programs.py): one
        `programs.jsonl` row per compiled step program — the plain step
        at the first log window, the monitored twin once it has
        compiled. The jaxpr-FLOPs walk is tens of ms of host work and
        runs once per program; `flops_cost` is the XLA cost-analysis
        figure fit already computed when the backend has a peak (never
        triggered here — an AOT recompile of the train step on XLA CPU
        is the documented compile blowup)."""
        reg = getattr(tel, "programs", None)
        if reg is None:
            return None
        from ..parallel.context import use_mesh
        from ..profiling import jaxpr_flops
        batch = self._numeric_subtree(global_batch)
        sig = ",".join(
            f"{jax.tree_util.keystr(p)}{tuple(x.shape)}"
            for p, x in jax.tree_util.tree_flatten_with_path(batch)[0])
        targets = [("train_step", self._step, compile_s)]
        if monitored_compiled and self._step_monitored is not None:
            targets.append(("train_step_monitored",
                            self._step_monitored, None))
        for kind, prog, comp_s in targets:
            if kind in registered:
                continue
            registered.add(kind)
            flops_jaxpr = None
            collectives = comm_by_axis = None
            try:
                with use_mesh(self.mesh):
                    closed = jax.make_jaxpr(prog)(self.state, batch)
                flops_jaxpr = jaxpr_flops(closed.jaxpr)
                from ..analysis.shard_rules import collective_summary
                comm = collective_summary(
                    closed, dict(zip(self.mesh.axis_names,
                                     self.mesh.devices.shape))
                    if self.mesh is not None else None)
                collectives = int(comm["collectives"])
                comm_by_axis = dict(comm["comm_bytes_by_axis"])
            except Exception as e:  # noqa: BLE001 — evidence is
                # best-effort; a failed probe degrades the field only
                import logging
                logging.getLogger("flaxdiff_tpu.trainer").debug(
                    "train-step jaxpr probe failed: %s", e)
            from ..telemetry.memory import MemoryMonitor
            hbm = MemoryMonitor().sample().get("memory/peak_bytes_in_use")
            reg.record(
                kind, key=f"{kind}:{sig}",
                compile_ms=(comp_s * 1e3 if comp_s else None),
                flops_jaxpr=flops_jaxpr,
                flops_cost=(flops_cost if kind == "train_step"
                            else None),
                hbm_peak_bytes=hbm,
                collectives=collectives,
                comm_bytes_by_axis=comm_by_axis,
                extra={"compile_source": "first_step_busy"})
        # the plain step's registry identity — the devprof window-close
        # path reconciles its measured row against exactly this key
        return f"train_step:{sig}"

    # -- checkpointing -------------------------------------------------------
    def save_checkpoint(self, force: bool = False) -> bool:
        """Sharded async save of the live state (+best_loss meta)."""
        if self.checkpointer is None:
            return False
        step = int(jax.device_get(self.state.step))
        return self.checkpointer.save(
            step, self.state, meta={"best_loss": float(self.best_loss)},
            force=force)

    def restore_checkpoint(self, step: Optional[int] = None,
                           fallback: bool = True) -> int:
        """Restore state (sharded, shards placed directly on the mesh);
        returns the restored step (reference simple_trainer.py:339-367).

        With `fallback` (default) a corrupt/incomplete latest checkpoint
        walks back to the newest readable step instead of killing the
        run (`fallback_restore` events record each skip); an explicit
        `step` is always restored exactly or raises."""
        if self.checkpointer is None:
            raise ValueError("trainer has no checkpointer")
        from .checkpoints import abstract_state_like
        abstract = abstract_state_like(self.state)
        self.state, meta = self.checkpointer.restore(abstract, step=step,
                                                     fallback=fallback)
        best = float(meta.get("best_loss", float("inf")))
        # best_loss == 0 is the reference's corrupt-checkpoint sentinel
        # (simple_trainer.py:352) — reset rather than trust it.
        self.best_loss = best if best > 0 else float("inf")
        # Seed best_state from the restored state so NaN rollback stays
        # armed after resume (the restored best_loss may never be beaten).
        restored = int(jax.device_get(self.state.step))
        if self.config.keep_best_state:
            self.best_state = jax.tree_util.tree_map(jnp.copy, self.state)
            self.best_step = restored
        return restored

    # -- data movement -------------------------------------------------------
    def put_batch(self, batch: PyTree) -> PyTree:
        """Host-local numpy batch -> global sharded jax arrays.

        Non-numeric entries (e.g. raw caption strings kept for validation
        logging) are dropped here: the jitted step's contract only covers
        "sample" and the numeric "cond" tree (train_step.py:57)."""
        def put(x):
            x = np.asarray(x)
            spec_axes = (self._batch_axis[0] if len(self._batch_axis) else None)
            spec = P(*((spec_axes,) + (None,) * (x.ndim - 1)))
            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, spec), x)
        return jax.tree_util.tree_map(put, self._numeric_subtree(batch))

    # -- core loop -----------------------------------------------------------
    @staticmethod
    def _numeric_subtree(batch: PyTree) -> PyTree:
        """Keep only the leaves the jitted step consumes — numpy string
        arrays (raw captions) cannot be traced."""
        def keep(x):
            if isinstance(x, (str, bytes)):
                return False
            if isinstance(x, (list, tuple)):
                return not any(isinstance(e, (str, bytes)) for e in x)
            return not (isinstance(x, np.ndarray)
                        and x.dtype.kind in ("U", "S", "O"))
        if isinstance(batch, dict):
            out = {}
            for k, v in batch.items():
                if isinstance(v, dict):
                    sub = DiffusionTrainer._numeric_subtree(v)
                    if sub:
                        out[k] = sub
                elif keep(v):
                    out[k] = v
            return out
        return batch

    def train_step(self, batch: PyTree):
        # Scoped mesh declaration: mesh-aware modules (attention backend
        # "ring") read it during the lazy first-call trace. Scoping per
        # call (rather than a global set in __init__) keeps two trainers
        # with different meshes in one process from cross-capturing, and
        # works when steps are driven from a worker thread.
        from ..parallel.context import use_mesh
        with use_mesh(self.mesh):
            self.state, loss = self._step(self.state,
                                          self._numeric_subtree(batch))
        return loss

    def train_step_monitored(self, batch: PyTree):
        """The numerics-cadence step: returns (loss, aux) where `aux` is
        the in-graph health pytree (telemetry/numerics.py). Requires
        `numerics_cadence > 0` at construction."""
        from ..parallel.context import use_mesh
        with use_mesh(self.mesh):
            self.state, loss, aux = self._step_monitored(
                self.state, self._numeric_subtree(batch))
        return loss, aux

    # -- training-health internals -------------------------------------------
    def _poison_module_params(self) -> str:
        """`numerics.nan` chaos site: corrupt the params of ONE
        deterministic module (first in sorted key order, at the same
        module level the numerics breakdown reports) with NaNs — the
        planted non-finite gradient the provenance pass must localize.
        Flat-param states have no modules; the whole vector is poisoned
        (provenance then degrades to the global count)."""
        from ..telemetry.numerics import unwrap_module_tree
        params = self.state.params

        def nan_like(tree):
            return jax.tree_util.tree_map(
                lambda x: x * jnp.float32(jnp.nan).astype(x.dtype), tree)

        inner, path = unwrap_module_tree(params)
        if isinstance(inner, dict) and inner:
            name = sorted(inner)[0]
            poisoned = dict(inner)
            poisoned[name] = nan_like(inner[name])
            for key in reversed(path):      # re-wrap the envelope
                poisoned = {key: poisoned}
        else:
            name, poisoned = "<flat>", nan_like(params)
        self.state = self.state.replace(params=poisoned)
        return name

    def _nan_provenance(self, batch: PyTree, tel, step: int):
        """On first non-finite detection: re-run ONE gradient pass (no
        update, no donation — the live state survives) and name the
        top-level module(s) whose grads or params hold non-finite
        values. The probe shares the step's loss builder, so it replays
        the exact rng/noise/timesteps of the offending step."""
        from ..telemetry.numerics import nonfinite_modules
        if self._probe is None:
            from .train_step import make_grad_probe
            apply_fn, schedule, transform, kw = self._probe_inputs
            self._probe = jax.jit(make_grad_probe(
                apply_fn, schedule, transform, **kw))
        from ..parallel.context import use_mesh
        # the live state's step counter already advanced past the
        # offending step; rewind it for the probe so the rng fold —
        # and with it noise/timesteps/dropout — replays exactly
        probe_state = self.state.replace(
            step=jnp.maximum(self.state.step - 1, 0))
        with tel.span("numerics.provenance", cat="numerics",
                      args={"step": step}):
            with use_mesh(self.mesh):
                probe = self._probe(probe_state,
                                    self._numeric_subtree(batch))
            modules = nonfinite_modules(probe)
        detail = (f"non-finite values localized to module(s) "
                  f"{modules}" if modules else
                  "no per-module non-finite values found (non-finite "
                  "loss without non-finite grads/params — bad batch?)")
        _res_events.global_event_log().record(
            "nan_provenance", "numerics.provenance",
            detail=detail, step=step)
        tel.write_record({"type": "nan_provenance", "step": int(step),
                          "modules": modules})
        return modules

    def fit(self,
            data: Iterator[PyTree],
            total_steps: int,
            callbacks: Sequence[Callable[[int, float, Dict], None]] = (),
            save_every: Optional[int] = None,
            data_factory: Optional[Callable[[Any], Iterator[PyTree]]]
            = None,
            data_plane: Optional[Any] = None) -> Dict[str, Any]:
        """Run `total_steps` steps from `data` (host-local numpy batches).

        Returns summary metrics. The hot loop is sync-free pipelined:
        dispatch runs up to `pipeline_depth` steps ahead of the device,
        H2D upload rides a background `prefetch_to_device` thread, and
        per-step losses accumulate in a device-resident window read
        back with ONE host sync per `log_every` window — NaN / abnormal
        loss anywhere in the window triggers a rollback to the best
        state seen, and (with `gate_nonfinite`, the default) a poisoned
        update never lands in the state at all. Because upload
        prefetches ahead, up to `pipeline_depth + 1` batches of `data`
        may be consumed-but-unused when fit returns — an accepted cost
        on streaming data (the background worker is joined before
        return, so handing `data` to another consumer afterwards is
        safe).

        `data_factory(world_view) -> iterator` re-shards the input
        pipeline around an elastic world transition (requires the
        trainer's `elastic` manager): after a committed shrink /
        re-admission / eviction the old upload worker is closed and a
        fresh pipeline for the NEW (rank, size) starts. One
        already-prefetched batch from the old shard may still be
        consumed — an accepted off-by-one on streaming data, recorded
        nowhere because it changes nothing the ledger cares about.

        `data_plane` (a `data.dataplane.DataPlane`) supersedes `data`
        with a DETERMINISTIC batch stream: the plane's cursor is the
        replay coordinate. Every rollback (anomaly, quorum, elastic
        restore) closes the upload worker, rewinds the stream to the
        landed step's batch boundary, and rebuilds the pipeline, so
        replayed steps see bit-identical batches; the plane's screen
        gates each batch before H2D upload (poisoned batches are
        quarantined with blast radius one batch); each checkpoint
        commit persists the plane's state through the StepLedger and
        runs the cross-host batch-hash skew vote. With `data_factory`
        too, elastic transitions swap the resharded factory INTO the
        plane (`adopt`) so journal/breaker/digest state survives the
        world change.
        """
        cfg = self.config
        losses, log_t0 = [], time.perf_counter()
        steps_in_window = 0
        pending_loss = None
        loss_window: list = []      # (step_no, device scalar), unfetched
        inflight: list = []         # dispatched-step losses, oldest first
        # In-graph loss ring: the window boundary becomes the ring size
        # (ONE readback per W steps regardless of log_every); per-step
        # device scalars are no longer retained host-side. Slot mapping
        # anchors on the LIVE step counter at fetch time, so resumed
        # fits and mid-run rollbacks (which rewind the counter) stay
        # correct without bookkeeping.
        ring_n = max(cfg.loss_ring, 0)
        if ring_n and self.state.loss_ring is None:
            raise ValueError(
                "TrainerConfig.loss_ring > 0 but the TrainState carries "
                "no ring (state restored from a pre-ring checkpoint?)")
        ring_pending = [0]          # count of steps since the last fetch
        # gate-activation visibility: baseline the cumulative in-graph
        # counter ONCE at fit start (the state is at rest here — a
        # resumed/rolled-back state legitimately carries prior counts),
        # then surface per-window deltas at log cadence
        gate_prev = (_fetch_gate_events(self.state.gate_events)
                     if self.state.gate_events is not None else None)
        peak = device_peak_flops()
        flops = None
        history: Dict[str, Any] = {"steps": [], "loss": [], "imgs_per_sec": [],
                                   "mfu": [], "preempted": False,
                                   "watchdog_fired": False,
                                   "coordination_lost": False,
                                   "elastic": [], "quorum_evicted": False,
                                   "saves": {"started": 0,
                                             "skipped_exists": 0,
                                             "failed": 0}}
        events = _res_events.global_event_log()
        fault_plan = _res_faults.active_plan()
        nan_pending = False     # step.nan fault armed for next loss read
        elastic = self.elastic
        # transition seconds spent INSIDE the checkpoint phase this step
        # (commit-triggered shrink/admit): settle_step subtracts them so
        # the time is attributed once, to its elastic bucket, not twice
        elastic_spent = [0.0]

        # Telemetry: phase timing + goodput attribution always run (an
        # in-memory account on the default hub costs microseconds); the
        # per-step device sync and JSONL rows only under an ENABLED hub
        # — exact device-phase timing requires closing async dispatch
        # with block_until_ready, which trades the one-deep pipeline for
        # attribution. MFU from device-phase time rides the same meter.
        tel = self.telemetry if self.telemetry is not None \
            else _global_telemetry()
        timed = tel.enabled
        device_meter = MFUMeter(peak_flops=peak) if timed else None
        timer = tel.step_timer(mfu_meter=device_meter,
                               sample_every=max(
                                   cfg.telemetry_sample_every, 1))
        goodput = tel.goodput
        # per-fit goodput delta: the hub may be process-global/cumulative
        gp_base_prod, gp_base_bad = goodput.raw_counters()

        # Training-health: the detector owns BOTH the cadence anomaly
        # checks and the historical abnormal-loss trigger (non-finite /
        # <= floor), so fault-injected and real NaNs take one code path.
        from ..telemetry.memory import MemoryMonitor
        from ..telemetry.numerics import AnomalyConfig, AnomalyDetector
        detector = AnomalyDetector(
            AnomalyConfig(zscore=cfg.anomaly_zscore,
                          window=cfg.anomaly_window,
                          abnormal_loss_floor=cfg.abnormal_loss_floor,
                          action=cfg.anomaly_action),
            telemetry=tel)
        memory = MemoryMonitor()
        # Automated device-profile windows (telemetry/devprof.py):
        # built only when configured AND the hub is enabled with a
        # devprof sink — the default path carries no profiler object
        # at all, so un-configured fits see zero change.
        devprof = None
        if timed and getattr(tel, "devprof_path", None) and (
                cfg.profile_cadence > 0
                or cfg.profile_trigger is not None):
            from ..telemetry.devprof import DeviceProfiler
            devprof = DeviceProfiler(
                tel.devprof_path,
                cadence=cfg.profile_cadence,
                window=max(cfg.profile_steps, 1),
                trigger_path=cfg.profile_trigger,
                metrics=tel.registry)
        history["anomalies"] = 0
        last_health = {"grad_norm": None}   # latest cadence grad norm
        provenance_done = False     # the debug re-run happens ONCE per fit
        monitored_compiled = False  # first cadence step pays a 2nd compile

        # Resume-at-start: under coordination this is the consensus
        # round — it must run BEFORE any step so a divergent world
        # raises here, never trains. ConsensusError propagates.
        if cfg.restore_at_start and self.checkpointer is not None:
            try:
                with tel.span("train.restore_at_start", cat="restore"), \
                        goodput.measure_badput("restart"):
                    step0 = self.restore_checkpoint()
                if data_plane is not None:
                    # rewind the stream to the restored step's batch
                    # boundary (journal/breakers reload from the ledger's
                    # data_state entry, so replay skips the same records)
                    data_plane.restore(step0,
                                       ledger=self.checkpointer.ledger)
                events.record("restored", "train.start",
                              detail=f"resumed from step {step0}",
                              step=step0)
            except FileNotFoundError:
                events.record("cold_start", "train.start",
                              detail="no restorable checkpoint; "
                                     "training from scratch")

        def count_save():
            res = (self.checkpointer.last_save_result
                   if self.checkpointer is not None else "none")
            if res in history["saves"]:
                history["saves"][res] += 1

        from ..data.prefetch import prefetch_to_device

        def _new_upload(src):
            """Build the H2D upload worker; with a data plane its screen
            gates every batch BEFORE the put and its journal records the
            quarantined ones."""
            return prefetch_to_device(
                self.put_batch, src, depth=max(cfg.pipeline_depth, 1),
                screen=(data_plane.screen if data_plane is not None
                        else None),
                quarantine=(data_plane.journal if data_plane is not None
                            else None))

        def _rewind_data(step) -> None:
            """Rewind the deterministic data plane to `step`'s batch
            boundary and rebuild the upload pipeline: prefetched-but-
            unconsumed batches are DISCARDED (never replayed out of
            order), and the next batch consumed is exactly batch index
            `step` — the bit-identical replay contract. No-op without a
            data plane or with an unknown landing step (best-state /
            fresh-rng recoveries that never rewound the step counter
            to a determinate boundary keep the stream position)."""
            nonlocal upload, global_batch
            if data_plane is None or step is None:
                return
            upload.close()
            data_plane.seek(int(step))
            upload = _new_upload(data_plane)
            with goodput.measure_badput("data_stall"), \
                    tel.span("data.rewind_refetch", cat="data",
                             args={"step": int(step)}):
                global_batch = next(upload)

        def _adopt_change(change, bucket: str, restore_step, t0: float,
                          in_ckpt_phase: bool) -> None:
            """Common adoption of a committed WorldChange: re-arm the
            coordinator in the new epoch namespace, rebuild the mesh if
            it spanned lost devices, restore the consensus step when
            the transition demands one, swap the data shard, and put
            the transition on the books (goodput bucket + reclaimed
            estimate, elastic/* metrics, JSONL row, history)."""
            nonlocal upload, global_batch
            coord = (self.checkpointer.coordinator
                     if self.checkpointer is not None else None)
            if coord is not None:
                coord.rebirth()
            self._rebuild_world_mesh()
            if restore_step is not None:
                with tel.span("elastic.restore", cat="restore",
                              args={"step": restore_step}):
                    self._elastic_restore(restore_step)
                # the restore rewound the step counter: unfetched loss
                # slots no longer map to live steps
                ring_pending[0] = 0
                loss_window.clear()
                inflight.clear()
            if data_factory is not None and elastic is not None:
                upload.close()
                if data_plane is not None:
                    # swap the resharded factory INTO the plane: the
                    # journal/breaker/digest state survives the world
                    # change, and the surviving view resumes at the
                    # consensus batch boundary — a shrink never
                    # re-serves samples the survivors already consumed
                    data_plane.adopt(
                        data_factory(elastic.world_view()),
                        cursor=(restore_step if restore_step is not None
                                else change.step))
                    upload = _new_upload(data_plane)
                    with goodput.measure_badput("data_stall"):
                        global_batch = next(upload)
                else:
                    upload = prefetch_to_device(
                        self.put_batch, data_factory(elastic.world_view()),
                        depth=max(cfg.pipeline_depth, 1))
            elif restore_step is not None:
                # no factory swap, but the restore rewound the step
                # counter: replay must see the same batches again
                _rewind_data(restore_step)
            dt = time.perf_counter() - t0
            goodput.record_badput(bucket, dt)
            reclaimed = elastic.reclaimed_estimate(change.step, dt,
                                                   goodput=goodput)
            goodput.record_reclaimed(bucket, reclaimed)
            if in_ckpt_phase:
                elastic_spent[0] += dt
            tel.counter("elastic/transitions").inc()
            kind_counter = {"shrink": "elastic/shrinks",
                            "grow": "elastic/readmits",
                            "evict": "elastic/evictions"}.get(change.kind)
            if kind_counter:
                tel.counter(kind_counter).inc()
            tel.gauge("elastic/world_size").set(float(change.world))
            tel.gauge("elastic/epoch").set(float(change.epoch))
            tel.gauge("elastic/last_transition_s").set(dt)
            tel.write_record({
                "type": "elastic_transition", "kind": change.kind,
                "epoch": change.epoch, "world": change.world,
                "members": list(change.members),
                "removed": list(change.removed),
                "added": list(change.added), "step": change.step,
                "duration_s": round(dt, 6),
                "reclaimed_s": round(reclaimed, 6),
                "reason": change.reason})
            history["elastic"].append({
                "kind": change.kind, "epoch": change.epoch,
                "world": change.world, "step": change.step,
                "duration_s": dt, "reclaimed_s": reclaimed})

        def _elastic_shrink(reason: str,
                            in_ckpt_phase: bool = True) -> bool:
            """Shrink-to-survive: returns True when a smaller world was
            committed and adopted (training continues), False when the
            caller must fall back to checkpoint-and-exit."""
            from ..resilience.elastic import ElasticError
            t0 = time.perf_counter()
            try:
                with tel.span("elastic.shrink", cat="elastic",
                              args={"reason": reason}):
                    change = elastic.shrink(reason)
            except ElasticError as e:
                events.record("elastic_error", "elastic.shrink",
                              detail=repr(e))
                return False
            if change is None:
                return False
            _adopt_change(change, bucket="elastic_shrink",
                          restore_step=change.step, t0=t0,
                          in_ckpt_phase=in_ckpt_phase)
            return True

        def _elastic_boundary(committed_step) -> None:
            """Healthy-commit-boundary hooks: the re-admission check.
            KV traffic only — zero device syncs (the counting-mock
            elasticity tests pin this)."""
            from ..resilience.elastic import ElasticError
            t0 = time.perf_counter()
            try:
                change = elastic.maybe_admit(current_step=committed_step)
            except ElasticError as e:
                # a member vanished between the commit ack and this
                # round: same recovery as a commit timeout
                events.record("elastic_error", "elastic.join",
                              detail=repr(e))
                if not _elastic_shrink(f"admission round failed: {e}"):
                    history["coordination_lost"] = True
                    stop["flag"] = True
                return
            if change is not None:
                # members keep their live state (they ARE the consensus
                # step); only the joiner restores
                _adopt_change(change, bucket="elastic_readmit",
                              restore_step=None, t0=t0,
                              in_ckpt_phase=True)

        def _elastic_quorum(hard: bool, step_no: int) -> Optional[str]:
            """Pod anomaly quorum at a collective step (the numerics
            cadence, or — with `numerics_cadence=0` — the log-step
            window fetch): every member votes; a sick-pod majority
            rolls everyone back to the consensus step, an outlier
            minority is evicted. Returns the decision kind (None when
            the round itself failed) so the caller knows whether the
            anomaly was handled collectively."""
            from ..resilience.elastic import ElasticError
            t0 = time.perf_counter()
            try:
                decision = elastic.quorum_round(hard, step=step_no)
            except ElasticError as e:
                events.record("elastic_error", "elastic.quorum",
                              detail=repr(e))
                if not _elastic_shrink(f"quorum round failed: {e}",
                                       in_ckpt_phase=False):
                    history["coordination_lost"] = True
                    stop["flag"] = True
                return None
            if decision.kind == "none":
                return "none"
            tel.write_record({
                "type": "quorum_decision", "kind": decision.kind,
                "step": step_no,
                "votes": {str(k): v for k, v in decision.votes.items()}})
            history.setdefault("quorum", []).append(decision.kind)
            if decision.kind == "rollback_all":
                if decision.step is not None:
                    with tel.span("elastic.quorum_rollback", cat="restore",
                                  args={"step": decision.step}):
                        self._elastic_restore(decision.step)
                    _rewind_data(decision.step)
                else:
                    # pod-sick with nothing committed: best-state path
                    landed = self._recover(float("nan"), step=step_no)
                    _rewind_data(landed)
                ring_pending[0] = 0
                loss_window.clear()
                inflight.clear()
                dt = time.perf_counter() - t0
                goodput.record_badput("quorum_rollback", dt)
                goodput.record_reclaimed(
                    "quorum_rollback",
                    elastic.reclaimed_estimate(decision.step, dt,
                                               goodput=goodput))
                tel.counter("elastic/quorum_rollbacks").inc()
            elif decision.kind == "evicted":
                # this host's anomaly was the outlier: the survivors
                # continue without it — leave WITHOUT committing (the
                # final local save stays uncommitted, exactly like the
                # coordination-lost exit)
                history["quorum_evicted"] = True
                coord = (self.checkpointer.coordinator
                         if self.checkpointer is not None else None)
                if coord is not None:
                    coord.lost = True
                stop["flag"] = True
            elif decision.kind == "evict" and decision.change is not None:
                _adopt_change(decision.change, bucket="quorum_rollback",
                              restore_step=None, t0=t0,
                              in_ckpt_phase=False)
            return decision.kind

        def commit_save(final: bool = False) -> None:
            """Two-phase-commit the save just dispatched (no-op without
            a ledger). A BarrierTimeout means a peer died mid-round:
            with an elastic manager the survivors shrink the world and
            KEEP TRAINING; otherwise (or when the shrink round itself
            cannot complete) mark coordination lost in the history and
            stop — the final local save still happens, uncommitted, on
            the checkpoint-and-exit path instead of hanging in
            collectives. A healthy commit boundary additionally runs
            the re-admission check for parked replacement hosts."""
            if self.checkpointer is None:
                return
            from ..resilience.coordination import BarrierTimeout
            try:
                committed = self.checkpointer.commit_pending()
            except BarrierTimeout:
                if elastic is not None and not final \
                        and _elastic_shrink("commit barrier timeout"):
                    return
                # the coordinator recorded barrier_timeout and marked
                # itself lost; later commits degrade to local skips
                history["coordination_lost"] = True
                if not final:
                    stop["flag"] = True
                return
            if data_plane is not None and committed is not None:
                # data-plane state commits BESIDE the model commit (same
                # ledger), and the cross-host batch-hash vote runs here —
                # KV/ledger traffic only, zero device syncs
                data_plane.commit(committed,
                                  ledger=self.checkpointer.ledger)
            if elastic is not None and not final and not stop["flag"]:
                _elastic_boundary(committed)

        def handle_numerics(step_no: int, aux, step_batch) -> bool:
            """Cadence-step health handling: flatten the aux (the host
            readback), export gauges + the `numerics` JSONL row + HBM
            gauges, run the detector, and on the first HARD (non-finite)
            anomaly run the provenance pass and the configured action.
            Soft z-score anomalies always only warn under `skip_step`
            (state is already donated); under `rollback` only hard
            anomalies roll back — a 6-sigma loss spike is evidence, a
            NaN is proof. Returns whether a hard anomaly was detected
            (the elastic quorum's vote). With an elastic manager the
            `rollback` action is NOT taken unilaterally: one host's
            rollback would silently fork the fleet, so the verdict goes
            to the pod quorum instead."""
            nonlocal provenance_done
            from ..telemetry.numerics import flatten_aux
            flat = flatten_aux(aux)
            last_health["grad_norm"] = flat.get("numerics/grad_norm")
            tel.record_numerics(flat, step=step_no)
            memory.record(tel.registry)
            if flat.get("numerics/skipped", 0.0) > 0:
                tel.counter("numerics/skipped_steps").inc()
                events.record("skip_step", "numerics.skip",
                              detail="non-finite grads/loss; update "
                                     "gated in-graph (state unchanged)",
                              step=step_no)
            anomalies = detector.observe_aux(step_no, flat)
            if not anomalies:
                return False
            history["anomalies"] += len(anomalies)
            hard = [a for a in anomalies if a.hard]
            if hard and not provenance_done:
                provenance_done = True
                self._nan_provenance(step_batch, tel, step_no)
            if hard and cfg.anomaly_action == "rollback" \
                    and elastic is None:
                landed = self._recover(
                    flat.get("numerics/loss", float("nan")), step=step_no)
                # the restore rewound the step counter: unfetched ring
                # slots no longer map to live steps — drop them (the
                # rollback event records what happened to the window)
                ring_pending[0] = 0
                _rewind_data(landed)
            return bool(hard)

        # SIGTERM -> finish the current step, checkpoint, return. Only
        # the main thread may install handlers; elsewhere (e.g. fit
        # driven from a worker thread) preemption safety cannot arm —
        # surfaced as a resilience warning, not a silent skip.
        import signal
        stop = {"flag": False}
        prev_handler = None
        handler_installed = False
        if cfg.checkpoint_on_sigterm:
            def _on_term(signum, frame):
                stop["flag"] = True
                if callable(prev_handler):
                    prev_handler(signum, frame)
            try:
                prev_handler = signal.signal(signal.SIGTERM, _on_term)
                handler_installed = True
            except ValueError:
                events.record(
                    "warning", "train.sigterm",
                    detail="checkpoint_on_sigterm requested but the "
                           "SIGTERM handler could not be installed "
                           "(fit is not running on the main thread); "
                           "preemption safety disabled for this run")

        # Heartbeat watchdog: turns a wedged step/loader into a clean
        # checkpoint-and-exit (resilience/watchdog.py). The "sigterm"
        # action reuses the preemption path above; the kill only fires
        # when the handler is actually installed, else it would be a
        # real termination.
        watchdog = None
        if cfg.watchdog_timeout is not None:
            import os as _os

            from ..resilience.watchdog import Watchdog

            def _on_stall(gap: float):
                history["watchdog_fired"] = True
                stop["flag"] = True
                if cfg.watchdog_action == "sigterm" and handler_installed:
                    _os.kill(_os.getpid(), signal.SIGTERM)
            watchdog = Watchdog(cfg.watchdog_timeout, on_stall=_on_stall,
                                site="train.step", event_log=events)
            watchdog.start()

        profile_ctx = None
        # Clamp the capture window into [1, total_steps] so a short fit
        # with profile_dir set still produces a trace instead of silently
        # never reaching the default start step (the close is handled in
        # `finally` when the window runs past the last step).
        profile_at = max(1, min(cfg.profile_at_step,
                                max(total_steps - cfg.profile_steps + 1, 1)))

        # Pipelined dispatch (per-step host syncs serialize the host
        # against the device): H2D upload rides a background thread
        # (prefetch_to_device), dispatch runs up to pipeline_depth
        # steps ahead of the device, and the ONLY mandatory host sync
        # is the log-cadence loss-window fetch. try/finally: an
        # exception escaping the loop (exhausted iterator, raising
        # callback) must still restore the SIGTERM handler — a leaked
        # _on_term would swallow every later SIGTERM — close any open
        # profiler trace, and stop the upload worker (it shares the
        # caller's iterator with later consumers).
        # compile-badput bookkeeping for the warm-cache fix: each
        # first-step/compile-step attribution is remembered alongside a
        # bounded sample of steady-state busy times; once steady
        # evidence exists, a "compile" step that was no slower than an
        # ordinary step (persistent compilation cache hit) is
        # re-attributed productive (goodput.reattribute). The old
        # heuristic admitted this bug: "a warm cache mislabels one
        # cheap step".
        compile_busies: list = []
        steady_busies: list = []
        registered_programs: set = set()    # program-evidence dedupe

        def settle_step(idx: int, compile_step: bool = False
                        ) -> Dict[str, float]:
            """Close the step's phase window, emit the per-step row, and
            attribute its wall-clock to the goodput account: host +
            device + residual of step 1 — and of the FIRST
            numerics-cadence step, which compiles the monitored twin —
            is `compile` badput (provisionally: warm-cache first steps
            are re-attributed productive at fit end once steady-state
            steps exist to compare against), later steps are
            productive; data waits are `data_stall`; the checkpoint
            phase is `checkpoint_commit`, or `coordination_lost` when
            this step's commit round timed out discovering a dead
            peer; the `numerics` phase (aux readback + detector + any
            provenance re-run/rollback) is its own badput bucket —
            monitoring overhead must not masquerade as training. With
            `telemetry_sample_every > 1` the device phase is lumpy
            (zero off-sample, a window's worth on-sample): attribution
            is exact at window granularity, not per step."""
            phases = timer.end_step()
            if timed and timer.last_row is not None:
                # one row per SAMPLE WINDOW (== per step at
                # sample_every=1): off-sample steps emit nothing — their
                # phases ride in the sampled step's window sums
                tel.record_step(timer.last_row)
            busy = (phases.get("host", 0.0) + phases.get("device", 0.0)
                    + phases.get("log_step", 0.0)
                    + phases.get("other", 0.0))
            if idx == 0 or compile_step:
                goodput.record_badput("compile", busy)
                compile_busies.append(busy)
            else:
                goodput.record_productive(busy)
                if len(steady_busies) < 512:
                    steady_busies.append(busy)
            goodput.record_badput("data_stall", phases.get("data_wait", 0.0))
            goodput.record_badput("numerics", phases.get("numerics", 0.0))
            # device-profile window overhead (open/close + the close's
            # pipeline drain + capture parse) is measurement, not
            # training — its own bucket keeps the MFU account honest
            goodput.record_badput("profile", phases.get("profile", 0.0))
            # elastic transitions that ran inside this step's checkpoint
            # phase were already attributed to their own bucket
            # (elastic_shrink/elastic_readmit) — subtract them so each
            # second lands in exactly one bucket
            ckpt_s = max(phases.get("checkpoint", 0.0) - elastic_spent[0],
                         0.0)
            elastic_spent[0] = 0.0
            goodput.record_badput(
                "coordination_lost" if history["coordination_lost"]
                else "checkpoint_commit", ckpt_s)
            return phases

        def reclassify_warm_compile() -> None:
            """The compile-badput time-threshold fix: a first step that
            ran no slower than _COMPILE_RECLASS_RATIO x the median
            steady step did not compile (persistent cache hit / an
            already-warm program on a re-entered fit) — move its busy
            time back to productive. Needs >= 3 steady samples; with
            fewer, the conservative (badput) attribution stands."""
            if not compile_busies or len(steady_busies) < 3:
                return
            med = sorted(steady_busies)[len(steady_busies) // 2]
            for busy in compile_busies:
                if busy <= _COMPILE_RECLASS_RATIO * max(med, 1e-9):
                    moved = goodput.reattribute("compile", busy)
                    if moved > 0:
                        events.record(
                            "warm_compile_reclassified", "train.step",
                            detail=f"first-step busy {busy:.3f}s ~ "
                                   f"steady median {med:.3f}s: warm "
                                   "compilation cache; re-attributed "
                                   "productive")
            compile_busies.clear()

        # with a data plane, the plane IS the batch stream (its cursor
        # is the replay coordinate every rollback rewinds to)
        upload = _new_upload(data_plane if data_plane is not None else data)
        try:
            with goodput.measure_badput("data_stall"), \
                    tel.span("data.first_batch", cat="data"):
                global_batch = next(upload)
            for i in traced_steps(total_steps):
                if watchdog is not None:
                    watchdog.beat()
                if stop["flag"]:
                    # the post-loop force-save persists the state; here
                    # only mark and stop
                    history["preempted"] = True
                    events.record("preempt", "train.step",
                                  detail="SIGTERM (or watchdog) — "
                                         "checkpointing and returning",
                                  step=i)
                    break
                if fault_plan is not None:
                    # chaos sites (use error="flag" specs): a NaN poisons
                    # the next loss readback so the rollback path runs; a
                    # sigterm exercises the preemption path end-to-end; a
                    # numerics.nan corrupts ONE module's params so the
                    # numerics monitor + provenance pass must catch AND
                    # localize it.
                    if fault_plan.check("step.nan", step=i + 1):
                        nan_pending = True
                    if fault_plan.check("numerics.nan", step=i + 1):
                        self._poison_module_params()
                    if fault_plan.check("host.sigterm", step=i + 1):
                        import os as _os
                        _os.kill(_os.getpid(), signal.SIGTERM)
                if cfg.profile_dir is not None:
                    from ..profiling import trace
                    if i + 1 == profile_at and profile_ctx is None:
                        profile_ctx = trace(cfg.profile_dir)
                        profile_ctx.__enter__()
                    elif (profile_ctx is not None
                            and i + 1 == profile_at + cfg.profile_steps):
                        _block_until_ready(pending_loss)
                        profile_ctx.__exit__(None, None, None)
                        profile_ctx = None
                current = global_batch
                monitored = (self._step_monitored is not None
                             and (i + 1) % cfg.numerics_cadence == 0)
                compile_step = monitored and not monitored_compiled
                fetch_every = ring_n if ring_n else cfg.log_every
                log_step = ((i + 1) % fetch_every == 0
                            or i == total_steps - 1)
                timer.begin_step(i + 1)
                if compile_step or log_step:
                    # these steps close dispatch anyway (twin compile /
                    # window fetch): take the free exact device sample
                    timer.mark_sampled()
                if devprof is not None:
                    # automated profile windows: open BEFORE this
                    # step's dispatch, close before the first dispatch
                    # PAST the window — both inside the `profile`
                    # phase, which settle_step books to its own badput
                    # bucket so window overhead never pollutes MFU.
                    # The close drains the pipeline through the counted
                    # sync seam (every step dispatched inside the
                    # window lands in the capture) and reconciles the
                    # parsed row against the step's registry program;
                    # off-window steps reach neither branch — two int
                    # compares, zero syncs.
                    if devprof.should_close(i + 1):
                        with timer.phase("profile"):
                            if pending_loss is not None:
                                _block_until_ready(pending_loss)
                            inflight.clear()
                            prog_key = self._register_program_evidence(
                                tel, current, registered_programs,
                                (compile_busies[0] if compile_busies
                                 else None),
                                monitored_compiled, flops)
                            devprof.close(i + 1, kind="train_step",
                                          key=prog_key,
                                          programs=tel.programs)
                    elif devprof.should_open(i + 1):
                        with timer.phase("profile"):
                            devprof.open(i + 1)
                if watchdog is not None and (i == 0 or compile_step):
                    # first call of either program pays jit compile —
                    # not a stall
                    watchdog.pause()
                pending_aux = None
                with timer.phase("host"):
                    if monitored:
                        pending_loss, pending_aux = \
                            self.train_step_monitored(current)
                        monitored_compiled = True
                    else:
                        pending_loss = self.train_step(current)
                if watchdog is not None and (i == 0 or compile_step):
                    watchdog.resume()
                if ring_n:
                    ring_pending[0] += 1
                else:
                    loss_window.append((i + 1, pending_loss))
                inflight.append(pending_loss)
                if cfg.pipeline_depth > 0:
                    # bounded in-flight dispatch: the device may lag
                    # the host by at most pipeline_depth steps. The
                    # oldest in-flight step is checked non-blockingly
                    # first — on a healthy pipeline it has long
                    # settled and this costs one host query; only
                    # genuine backpressure (device > depth behind)
                    # waits, and it waits exactly the surplus.
                    while len(inflight) > cfg.pipeline_depth:
                        oldest = inflight.pop(0)
                        if not _is_ready(oldest):
                            tel.counter("pipeline/backpressure_waits").inc()
                            _block_until_ready(oldest)
                if i + 1 < total_steps:
                    with timer.phase("data_wait"):
                        global_batch = next(upload)
                if timed and timer.sampled:
                    # close async dispatch so the device phase is real
                    # device time, not whatever later host op happens to
                    # block first (the async-dispatch lie). In sampled
                    # mode (telemetry_sample_every > 1) only sampled
                    # steps pay this sync; their device phase covers
                    # every step dispatched since the previous sample.
                    with timer.phase("device"):
                        _block_until_ready(pending_loss)
                    inflight.clear()    # everything older has settled
                if pending_aux is not None:
                    # the one host sync a cadence step pays: aux
                    # readback, gauges + JSONL row, detector verdicts,
                    # and (first hard anomaly only) provenance + action
                    with timer.phase("numerics"):
                        hard_anomaly = handle_numerics(i + 1, pending_aux,
                                                       current)
                    if elastic is not None \
                            and cfg.anomaly_action == "rollback":
                        # the pod quorum rides the numerics cadence —
                        # every member reaches this step in lockstep, so
                        # the vote is collective by construction. KV
                        # traffic only; its time lands in the `elastic`
                        # phase, attributed to quorum_rollback when a
                        # decision fires.
                        with timer.phase("elastic"):
                            _elastic_quorum(bool(hard_anomaly), i + 1)
                steps_in_window += 1

                recovered = False
                if log_step:
                    with timer.phase("log_step"):
                        # THE one mandatory host sync of the window: fetch
                        # the device-resident loss window (blocks until the
                        # newest step settles, so it also closes dispatch —
                        # this step was marked sampled above and the wait
                        # landed in the device phase already).
                        inflight.clear()
                        if ring_n:
                            # one device_get of the in-graph ring covers the
                            # whole window; the newest r steps wrote slots
                            # (step_now - r) .. (step_now - 1) mod W
                            with tel.span("fit.loss_fetch", cat="train"):
                                ring_vals = _fetch_ring(
                                    self.state.loss_ring)
                                step_now = int(
                                    jax.device_get(self.state.step))
                            r = min(ring_pending[0], ring_n)
                            vals = [float(
                                ring_vals[(step_now - r + t) % ring_n])
                                for t in range(r)]
                            ring_pending[0] = 0
                        else:
                            window = loss_window
                            loss_window = []
                            with tel.span("fit.loss_fetch", cat="train"):
                                vals = _fetch_losses(
                                    [v for _, v in window])
                        if not vals:
                            # an elastic transition (quorum rollback /
                            # shrink restore) emptied the window mid-cadence:
                            # every retained slot mapped to a rewound step.
                            # Nothing to report; treat like a recovery so
                            # the save guard below re-arms on fresh steps.
                            steps_in_window = 0
                            log_t0 = time.perf_counter()
                            recovered = True
                        if nan_pending and vals:
                            vals[-1], nan_pending = float("nan"), False
                        if gate_prev is not None \
                                and self.state.gate_events is not None:
                            # per-window delta of the in-graph gate counter
                            # (the window fetch above already settled the
                            # pipeline; this read costs no extra sync).
                            # Clamped at 0: a rollback rewinds the
                            # cumulative counter below the baseline.
                            ge = _fetch_gate_events(self.state.gate_events)
                            delta = np.maximum(ge - gate_prev, 0)
                            gate_prev = ge
                            if int(delta.sum()):
                                tel.counter("numerics/gate_activations") \
                                    .inc(int(delta.sum()))
                                for part, d in zip(
                                        ("params", "opt_state", "ema"),
                                        delta):
                                    if int(d):
                                        tel.counter(
                                            f"numerics/gate_activations/"
                                            f"{part}").inc(int(d))
                                events.record(
                                    "gate_activated", "train.step",
                                    detail=f"in-graph non-finite gate "
                                           f"masked {int(delta.sum())} "
                                           f"element(s) this window "
                                           f"(params/opt/ema = "
                                           f"{delta.tolist()})",
                                    step=i + 1)
                        # Mid-window non-finite losses are VISIBILITY, not a
                        # verdict: with the in-graph gate a poisoned batch's
                        # update never landed, so a finite cadence loss
                        # means the state recovered on its own (the
                        # skip_step contract) — recovery stays keyed to the
                        # cadence-step loss exactly as before, but the
                        # window now shows transients the old single-value
                        # fetch could never see.
                        n_bad = sum(1 for v in vals[:-1]
                                    if not np.isfinite(v))
                        if n_bad:
                            gated = ("; update(s) withheld in-graph"
                                     if cfg.gate_nonfinite else "")
                            events.record(
                                "window_nonfinite", "train.step",
                                detail=f"{n_bad} non-finite loss(es) inside "
                                       f"the window ending at step "
                                       f"{i + 1}{gated}",
                                step=i + 1)
                        # ONE code path for fault-injected and real NaNs:
                        # the detector's hard triggers subsume the old
                        # `isfinite or <= floor` ad-hoc check
                        loss = vals[-1] if vals else float("nan")
                        anomaly = (None if recovered
                                   else detector.abnormal_loss(loss,
                                                               step=i + 1))
                        if not recovered and elastic is not None \
                                and cfg.anomaly_action == "rollback" \
                                and cfg.numerics_cadence == 0:
                            # numerics_cadence=0 quorum hole, closed: with
                            # no cadence step the hard verdict surfaces
                            # HERE, and a unilateral local rollback would
                            # silently fork the pod. Every member reaches
                            # every log step in lockstep, so the vote is
                            # collective by construction — healthy members
                            # vote False each window, the anomalous one
                            # votes True, and the pod decides together
                            # (rollback_all restores + clears the window
                            # inside _elastic_quorum). A failed round never
                            # falls back to the unilateral path: that is
                            # the fork this guard exists to prevent.
                            with timer.phase("elastic"):
                                verdict = _elastic_quorum(
                                    anomaly is not None, i + 1)
                            if anomaly is not None \
                                    or verdict in ("rollback_all", "evicted"):
                                steps_in_window = 0
                                log_t0 = time.perf_counter()
                                recovered = True
                        if recovered:
                            pass    # transition emptied the window above
                        elif anomaly is not None:
                            landed = self._recover(loss, step=i + 1)
                            _rewind_data(landed)
                            steps_in_window = 0
                            log_t0 = time.perf_counter()
                            recovered = True
                        else:
                            losses.append(loss)
                            dt = time.perf_counter() - log_t0
                            # global batch size: `current` holds global
                            # sharded arrays, so the leading dim IS the
                            # global batch (no process_count multiply)
                            bsz = jax.tree_util.tree_leaves(
                                current)[0].shape[0]
                            ips = steps_in_window * bsz / max(dt, 1e-9)
                            if flops is None and peak:
                                flops = self.step_flops(global_batch)
                            step_mfu = (mfu(flops, dt / steps_in_window, peak)
                                        if flops else None)
                            if tel.programs is not None:
                                # program evidence registry: one row per
                                # compiled step program, at the first log
                                # window (plus the monitored twin once it
                                # has compiled) — per-program roofline
                                # attribution beside the global mfu gauges
                                self._register_program_evidence(
                                    tel, global_batch, registered_programs,
                                    (compile_busies[0] if compile_busies
                                     else None),
                                    monitored_compiled, flops)
                            window_steps = steps_in_window
                            steps_in_window = 0
                            history["steps"].append(i + 1)
                            history["loss"].append(loss)
                            history["imgs_per_sec"].append(ips)
                            history["mfu"].append(step_mfu)
                            metrics = {"imgs_per_sec": ips}
                            finite = [v for v in vals if np.isfinite(v)]
                            if finite:
                                # the window fetch makes every step's loss
                                # visible at no extra sync: report the
                                # window mean beside the spot value
                                metrics["loss_window_mean"] = \
                                    float(np.mean(finite))
                            if ring_n and len(vals) <= 64:
                                # retroactive per-step visibility: the
                                # JsonlLogger serializes small numeric seqs,
                                # so log_every=1 users still get every
                                # step's loss — delivered once per window
                                metrics["window_losses"] = list(vals)
                            if step_mfu is not None:
                                metrics["mfu"] = step_mfu
                            if timed and flops and device_meter.steps:
                                # utilization against DEVICE time (phase-
                                # timed), not end-to-end step time: the gap
                                # between the two numbers IS the host/input
                                # overhead the phase breakdown localizes
                                device_meter.flops_per_step = flops
                                mfu_dev = device_meter.mfu()
                                if mfu_dev is not None:
                                    metrics["mfu_device"] = mfu_dev
                            # resilience counters ride the normal metric
                            # stream (JSONL/wandb via the callback's logger)
                            metrics.update(events.summary())
                            for cb in callbacks:
                                cb(i + 1, loss, metrics)
                            if cfg.keep_best_state and loss < self.best_loss:
                                self.best_loss = loss
                                with tel.span("fit.best_state_copy",
                                              cat="train"):
                                    self.best_state = \
                                        jax.tree_util.tree_map(
                                            jnp.copy, self.state)
                                self.best_step = i + 1
                            if timed:
                                tel.gauge("train/loss").set(loss)
                                tel.gauge("train/imgs_per_sec").set(ips)
                                # HBM gauges ride the log cadence even when
                                # the numerics monitor is off (host-only
                                # allocator read; self-disables off-TPU)
                                memory.record(tel.registry)
                                # pod-wide skew: every host contributes its
                                # window means; rank 0 logs min/max/p50/p99.
                                # A collective — all hosts hit log cadence
                                # in lockstep (same SPMD-driver assumption
                                # as the commit rounds).
                                agg = {"step_time": dt / max(window_steps, 1),
                                       "imgs_per_sec": ips, "loss": loss}
                                if last_health["grad_norm"] is not None:
                                    # pod/grad_norm/spread: divergence skew —
                                    # one host drifting shows before it NaNs
                                    agg["grad_norm"] = last_health["grad_norm"]
                                if timer.last is not None:
                                    agg["data_wait"] = timer.last.get(
                                        "data_wait", 0.0)
                                    agg["device_time"] = timer.last.get(
                                        "device", 0.0)
                                tel.aggregate(agg, step=i + 1)
                                tel.export(step=i + 1)
                            log_t0 = time.perf_counter()

                if not recovered and save_every and (i + 1) % save_every == 0:
                    # "Never checkpoint a NaN" (VERDICT r1 weak #4),
                    # rebuilt sync-free: with gate_nonfinite (default)
                    # the in-graph gate withheld any non-finite update,
                    # so the live state is finite BY CONSTRUCTION and
                    # the save needs no loss fetch — the old
                    # float(pending_loss) here was a forced pipeline
                    # serialization every save_every steps. Without the
                    # gate, the legacy synchronous check stands: the
                    # fetch is then the only protection.
                    with timer.phase("checkpoint"):
                        do_save = True
                        if not cfg.gate_nonfinite:
                            loss_now = _fetch_losses([pending_loss])[0]
                            if nan_pending:
                                loss_now, nan_pending = float("nan"), False
                            if detector.abnormal_loss(
                                    loss_now, step=i + 1) is not None:
                                landed = self._recover(loss_now, step=i + 1)
                                ring_pending[0] = 0   # slots rewound
                                _rewind_data(landed)
                                do_save = False
                        if do_save:
                            with tel.span("ckpt.save_and_commit",
                                          cat="checkpoint",
                                          args={"step": i + 1}):
                                self.save_checkpoint()
                                count_save()
                                commit_save()
                            goodput.persist()
                settle_step(i, compile_step=compile_step)
                if devprof is not None and log_step:
                    # on-demand arming rides the log cadence (one host
                    # stat per window, zero cost on other steps): an
                    # existing trigger file opens a window next step
                    devprof.poll_trigger()

            # The final save can legitimately outlast the watchdog timeout
            # (sync flush of an async save) — stand the watchdog down
            # first so it cannot SIGTERM a healthy shutdown.
            if watchdog is not None:
                watchdog.stop()
            # warm-cache compile fix: with steady-state evidence in
            # hand, re-attribute "compile" first steps that ran at
            # ordinary speed BEFORE the account is flushed/persisted
            reclassify_warm_compile()
            # Final force-save runs BEFORE the handler restore in `finally`:
            # a second SIGTERM arriving during this save — the exact window
            # preemption handling exists to protect — must hit _on_term (a
            # harmless re-mark of stop["flag"]), not the default action.
            with tel.span("ckpt.final_save", cat="checkpoint"), \
                    goodput.measure_badput(
                        "coordination_lost" if history["coordination_lost"]
                        else "checkpoint_commit"):
                self.save_checkpoint(force=True)
                count_save()
                commit_save(final=True)
        finally:
            # stop the upload worker FIRST: the caller may hand the
            # source iterator to another consumer (validation) the
            # moment fit returns, and two threads driving one generator
            # is a race (close() joins the worker, bounded)
            upload.close()
            if watchdog is not None:
                watchdog.stop()
            if profile_ctx is not None:
                # sync before closing so async-dispatched steps' device
                # activity lands in the trace (windows that run past the
                # last step close here instead of in-loop)
                if pending_loss is not None:
                    _block_until_ready(pending_loss)
                profile_ctx.__exit__(None, None, None)
            if devprof is not None and devprof.active():
                # a cadence window still open past the last step:
                # drain, close and parse it here so the capture still
                # becomes a devprof row — attributed to the same
                # `profile` bucket as in-loop closes
                with goodput.measure_badput("profile"):
                    if pending_loss is not None:
                        _block_until_ready(pending_loss)
                    devprof.close(kind="train_step",
                                  programs=tel.programs)
            if handler_installed:
                signal.signal(signal.SIGTERM,
                              prev_handler if prev_handler is not None
                              else signal.SIG_DFL)
            # persist trace + goodput even on an exceptional exit — the
            # post-mortem needs the account most exactly then. I/O
            # failure must not mask the original exception.
            try:
                tel.flush()
            except OSError as e:
                events.record("telemetry_lost", "telemetry.flush",
                              detail=repr(e))
        history["final_loss"] = losses[-1] if losses else float("nan")
        history["best_loss"] = self.best_loss
        history["resilience"] = events.summary()
        prod, bad = goodput.raw_counters()
        history["goodput"] = {
            "productive_s": prod - gp_base_prod,
            "badput_s": {k: round(v - gp_base_bad.get(k, 0.0), 6)
                         for k, v in bad.items()
                         if v - gp_base_bad.get(k, 0.0) > 0.0}}
        return history

    def _recover(self, bad_loss: float,
                 step: Optional[int] = None) -> Optional[int]:
        """Abnormal-loss / anomaly recovery (reference
        simple_trainer.py:542-575): restore the best state if we have
        one; with no best state yet but a checkpointer holding a
        restorable step, walk back to it (the PR-1/2 fallback-restore
        path — corrupt newer steps are skipped, ledger mode restores
        only committed steps). Only with neither does the run continue
        on a fresh rng fold.

        Returns the step the run landed on (the best state's snapshot
        step / the restored checkpoint step), or None when it continued
        in place — the data plane rewinds its stream to this boundary
        so replayed steps see bit-identical batches."""
        tel = self.telemetry if self.telemetry is not None \
            else _global_telemetry()
        if self.best_state is not None:
            _res_events.global_event_log().record(
                "rollback", "train.step",
                detail=f"abnormal loss {bad_loss}; restored best state",
                step=step)
            with tel.span("train.rollback", cat="restore",
                          args={"step": step, "loss": repr(bad_loss)}):
                self.state = jax.tree_util.tree_map(jnp.copy,
                                                    self.best_state)
            return self.best_step
        if self.checkpointer is not None \
                and self.checkpointer.latest_step() is not None:
            with tel.span("train.rollback", cat="restore",
                          args={"step": step, "loss": repr(bad_loss),
                                "source": "checkpoint"}):
                restored = self.restore_checkpoint()
            _res_events.global_event_log().record(
                "rollback", "train.step",
                detail=f"abnormal loss {bad_loss}; no best state — "
                       f"restored checkpoint step {restored}",
                step=step)
            return restored
        _res_events.global_event_log().record(
            "rollback", "train.step",
            detail=f"abnormal loss {bad_loss}; no best state — "
                   "continuing with fresh rng fold",
            step=step)
        # keep going with fresh RNG fold — the step folds rng by step
        # counter, so the next batch draws different noise.
        return None

    # -- inference-side helpers ---------------------------------------------
    def get_params(self, use_ema: bool = True) -> PyTree:
        params = (self.state.ema_params
                  if use_ema and self.state.ema_params is not None
                  else self.state.params)
        if self._param_template is not None:
            # flat-params mode: callers (samplers, validation, export)
            # expect the structured tree
            from .optim import unflatten_params
            return unflatten_params(self._param_template, params)
        return params
