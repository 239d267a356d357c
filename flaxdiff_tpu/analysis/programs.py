"""The REAL hot programs, traced for the graph analyzers.

Builds every program the framework actually dispatches on the hot
paths — `make_train_step` (plain, gated), its monitored twin, a
bf16-policy variant (the upcast audit's subject), and the serving
layer's DDIM / Euler-ancestral chunk programs (a row's terminal
denoise is a turn of theirs) plus the solo single-scan program — around a deliberately tiny conv model. The model
interior is irrelevant to the invariants being checked (RNG lineage,
callbacks, upcast traffic live in the STEP/SAMPLER code, not the
backbone); tiny keeps `jax.make_jaxpr` tracing sub-second per program.
Nothing here compiles or touches a device: `make_jaxpr` is abstract
evaluation, so the global-reduction XLA-CPU compile trap
(`_finite_only_gate` docstring) does not apply.

Used by the CLI (scripts/lint.py) and the tier-1 clean-pass tests in
tests/test_analysis.py: the acceptance bar is ZERO rng-key-reuse and
callback-leak findings on every program below.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp


def _tiny_model():
    import flax.linen as nn

    class Tiny(nn.Module):
        # implements the diffusion-cache `cache_mode` forward contract
        # (ops/diffcache.py) so the cached sampler programs can be
        # traced around the same tiny backbone: the first conv is the
        # always-run shallow part, the middle conv the cached deep
        # delta. The spatial modes (ops/spatialcache.py) treat grid
        # positions as tokens and scatter through a top-k mask — a
        # conv backbone can't gather a token subset out of the grid
        # (windows need neighbors), but the lint invariants live in
        # the SAMPLER code (switch structure, RNG lineage, carries),
        # which this traces exactly; param tree stays mode-invariant.

        @nn.compact
        def __call__(self, x, t, cond=None, cache_mode=None,
                     cache_taps=None, cache_ref=None):
            # explicit names: the reuse path skips the deep conv, so
            # compact auto-numbering would shift the tail conv's name
            h = nn.Conv(8, (3, 3), name="shallow")(x)
            if cache_mode == "reuse":
                h = h + cache_taps
                taps = cache_taps
            elif cache_mode == "spatial":
                scores = jnp.mean(
                    jnp.square(h - cache_ref), axis=(0, 3)).reshape(-1)
                k = max(1, scores.shape[0] // 4)
                _, idx = jax.lax.top_k(scores, k)
                mask = jnp.zeros_like(scores).at[idx].set(1.0) \
                    .reshape(h.shape[1], h.shape[2])[None, :, :, None]
                deep = nn.Conv(8, (3, 3), name="deep")(jnp.tanh(h))
                taps = mask * deep + (1.0 - mask) * cache_taps
                ref = mask * h + (1.0 - mask) * cache_ref
                h = h + taps
            else:
                taps = nn.Conv(8, (3, 3), name="deep")(jnp.tanh(h))
                ref = h
                h = h + taps
            out = nn.Conv(x.shape[-1], (3, 3), name="tail")(jnp.tanh(h))
            if cache_mode == "record":
                return out, taps
            if cache_mode in ("record_ref", "spatial"):
                return out, taps, ref
            return out

    model = Tiny()

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, 8, 8, 1)),
                          jnp.zeros((1,)))["params"]

    def record_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None,
                           cache_mode="record")

    def reuse_fn(params, x, t, cond, taps):
        return model.apply({"params": params}, x, t, None,
                           cache_mode="reuse", cache_taps=taps)

    def record_ref_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None,
                           cache_mode="record_ref")

    def spatial_fn(params, x, t, cond, taps, ref):
        return model.apply({"params": params}, x, t, None,
                           cache_mode="spatial", cache_taps=taps,
                           cache_ref=ref)

    from ..ops.spatialcache import ComposedCacheFns
    fns = ComposedCacheFns(record=record_fn, reuse=reuse_fn,
                           record_ref=record_ref_fn,
                           spatial=spatial_fn)
    return apply_fn, init_fn, fns


@functools.lru_cache(maxsize=None)
def _train_pieces():
    import optax

    from ..predictors import EpsilonPredictionTransform
    from ..schedulers import CosineNoiseSchedule
    from ..trainer.train_state import TrainState

    apply_fn, init_fn, _ = _tiny_model()
    key = jax.random.PRNGKey(0)
    init_key, train_key = jax.random.split(key)
    state = TrainState.create(apply_fn=apply_fn,
                              params=init_fn(init_key),
                              tx=optax.adam(1e-3), rng=train_key)
    batch = {"sample": jnp.zeros((2, 8, 8, 1), jnp.float32)}
    schedule = CosineNoiseSchedule(timesteps=100)
    transform = EpsilonPredictionTransform()
    return apply_fn, state, batch, schedule, transform


def train_step_jaxpr(monitored: bool = False, bf16: bool = False):
    from ..telemetry.numerics import NumericsConfig
    from ..trainer.train_step import TrainStepConfig, make_train_step
    from ..typing import Policy

    apply_fn, state, batch, schedule, transform = _train_pieces()
    numerics = (NumericsConfig(per_module=True, skip_nonfinite=True)
                if monitored else None)
    step = make_train_step(
        apply_fn, schedule, transform,
        TrainStepConfig(normalize=False),
        policy=Policy() if bf16 else None,
        numerics=numerics,
        gate_nonfinite=True)
    return jax.make_jaxpr(step)(state, batch)


@functools.lru_cache(maxsize=None)
def _sampler_pieces(sampler_name: str, cached: bool = False,
                    spatial: bool = False):
    from ..ops.diffcache import CachePlan
    from ..ops.spatialcache import ComposedPlan, SpatialPlan
    from ..predictors import EpsilonPredictionTransform
    from ..samplers import SAMPLER_REGISTRY, DiffusionSampler
    from ..schedulers import CosineNoiseSchedule

    apply_fn, state, _, _, _ = _train_pieces()
    _, _, cache_fns = _tiny_model()
    params = state.params

    def model_fn(p, x, t, cond):
        return apply_fn(p, x, t, cond)

    plan = None
    if spatial:
        plan = ComposedPlan(cache=CachePlan(refresh_every=2),
                            spatial=SpatialPlan(keep_fraction=0.25))
    elif cached:
        plan = CachePlan(refresh_every=2)
    ds = DiffusionSampler(
        model_fn, CosineNoiseSchedule(timesteps=100),
        EpsilonPredictionTransform(),
        SAMPLER_REGISTRY[sampler_name](),
        cache_plan=plan,
        cache_fns=cache_fns if plan is not None else None)
    return ds, params


def chunk_program_jaxpr(sampler_name: str, rows: int = 2,
                        round_steps: int = 2):
    """The serving layer's continuous-batching round program
    (`DiffusionSampler.make_chunk_program`) with the stacked input
    layout `SamplerProgramEngine.advance`'s round program builds from
    the rows' carries."""
    ds, params = _sampler_pieces(sampler_name)
    prog = ds.make_chunk_program(round_steps)
    x = jnp.zeros((rows, 1, 8, 8, 1), jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(rows)])
    pairs = jnp.zeros((rows, round_steps, 2), jnp.float32)
    n_act = jnp.zeros((rows,), jnp.int32)
    offsets = jnp.zeros((rows,), jnp.int32)
    # per-row sampler state, stacked the way engine._stack_rows does
    # (stateless samplers carry an empty pytree; multistep ones stack)
    row_states = [ds.sampler.init_state(
        jnp.zeros((1, 8, 8, 1), jnp.float32)) for _ in range(rows)]
    state = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                   *row_states)
    term = jnp.full((rows,), -1, jnp.int32)
    return jax.make_jaxpr(prog)(params, x, keys, pairs, n_act, offsets,
                                jnp.int32(round_steps), None, None, state,
                                None, term)


def solo_program_jaxpr(sampler_name: str = "ddim", steps: int = 4,
                       cached: bool = False, spatial: bool = False):
    """The solo single-scan trajectory program generate_samples runs;
    with `cached`, the diffusion-cache variant (taps carry + per-step
    `lax.cond` refresh gating, ops/diffcache.py); with `spatial`, the
    composed timestep x spatial variant (taps + score-reference
    carries, per-step `lax.switch` over the three-way code row,
    ops/spatialcache.py)."""
    ds, params = _sampler_pieces(sampler_name, cached=cached,
                                 spatial=spatial)
    shape = (2, 8, 8, 1)
    prog = ds._get_program(steps, shape, None, 0.0)
    x = jnp.zeros(shape, jnp.float32)
    key = jax.random.PRNGKey(0)
    return jax.make_jaxpr(prog)(params, x, key, None, None)


def cached_chunk_program_jaxpr(sampler_name: str = "ddim",
                               rows: int = 2, round_steps: int = 2):
    """The serving layer's cached continuous-batching round
    (`make_cached_chunk_program`) with the stacked input layout
    `SamplerProgramEngine.advance`'s round program builds on the
    cached path: round-level refresh flags + per-row taps carries."""
    ds, params = _sampler_pieces(sampler_name, cached=True)
    prog = ds.make_cached_chunk_program(round_steps)
    x = jnp.zeros((rows, 1, 8, 8, 1), jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(rows)])
    pairs = jnp.zeros((rows, round_steps, 2), jnp.float32)
    n_act = jnp.zeros((rows,), jnp.int32)
    offsets = jnp.zeros((rows,), jnp.int32)
    row_states = [ds.sampler.init_state(
        jnp.zeros((1, 8, 8, 1), jnp.float32)) for _ in range(rows)]
    state = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                   *row_states)
    flags = jnp.zeros((round_steps,), bool)
    taps = jnp.zeros((rows, 1, 8, 8, 8), jnp.float32)
    return jax.make_jaxpr(prog)(params, x, keys, pairs, n_act, offsets,
                                jnp.int32(round_steps), None, None, state,
                                flags, taps, jnp.full((rows,), -1, jnp.int32))


def spatial_chunk_program_jaxpr(sampler_name: str = "ddim",
                                rows: int = 2, round_steps: int = 2):
    """The serving layer's composed spatially-cached round
    (`make_spatial_chunk_program`) with the stacked input layout
    `SamplerProgramEngine.advance`'s round program builds on the
    composed path: round-level step codes + per-row taps AND
    score-reference carries."""
    ds, params = _sampler_pieces(sampler_name, spatial=True)
    prog = ds.make_spatial_chunk_program(round_steps)
    x = jnp.zeros((rows, 1, 8, 8, 1), jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(rows)])
    pairs = jnp.zeros((rows, round_steps, 2), jnp.float32)
    n_act = jnp.zeros((rows,), jnp.int32)
    offsets = jnp.zeros((rows,), jnp.int32)
    row_states = [ds.sampler.init_state(
        jnp.zeros((1, 8, 8, 1), jnp.float32)) for _ in range(rows)]
    state = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                   *row_states)
    codes = jnp.zeros((round_steps,), jnp.int32)
    taps = jnp.zeros((rows, 1, 8, 8, 8), jnp.float32)
    refs = jnp.zeros((rows, 1, 8, 8, 8), jnp.float32)
    return jax.make_jaxpr(prog)(params, x, keys, pairs, n_act, offsets,
                                jnp.int32(round_steps), None, None, state,
                                codes, taps, refs,
                                jnp.full((rows,), -1, jnp.int32))


# ---------------------------------------------------------------------------
# Meshed inventory: the REAL parallel programs, traced under forced
# multi-device CPU meshes (the tests' conftest and the lint CLI both pin
# `--xla_force_host_platform_device_count=8`). Still `jax.make_jaxpr`
# only — shard_map puts its collectives IN the jaxpr, so nothing
# compiles and the global-reduction XLA-CPU compile trap never applies.
# Each program is wrapped in a TracedProgram carrying the mesh facts the
# sharding rules (shard_rules.py) need: axis sizes for the byte model,
# declared input specs for the reshard detector, and (for the train
# step) the partition-coverage subject.
# ---------------------------------------------------------------------------

class TracedProgram:
    """ClosedJaxpr + the mesh facts the sharding rules consume.

    Quacks like a ClosedJaxpr for the single-program rules (`.jaxpr`);
    `axis_sizes` maps mesh axis name -> size, `in_specs` optionally
    declares the PartitionSpec each program invar was built for, and
    `partition` optionally carries a `parallel.partition_coverage`
    report (the partition-coverage rule's subject)."""

    def __init__(self, closed, axis_sizes: Optional[Dict[str, int]] = None,
                 in_specs: Optional[List] = None, partition=None):
        self.closed = closed
        self.axis_sizes = dict(axis_sizes or {})
        self.in_specs = in_specs
        self.partition = partition

    @property
    def jaxpr(self):
        return self.closed.jaxpr


def _mesh_for(axes: Dict[str, int]):
    """A mesh over the first prod(axes) local devices, or None when the
    host platform doesn't expose enough (the builders then skip — the
    tier-1 conftest and the lint CLI force 8 virtual CPU devices, so in
    gating runs nothing skips)."""
    from ..parallel.mesh import create_mesh
    need = math.prod(axes.values())
    devs = jax.devices()
    if len(devs) < need:
        return None
    return create_mesh(axes=axes, devices=devs[:need])


def _seq_specs(mesh, n: int):
    from ..parallel.ring_attention import seq_shard_spec
    return [seq_shard_spec(mesh)] * n


@functools.lru_cache(maxsize=None)
def meshed_ring_attention_jaxpr(grad: bool = False):
    """`ring_self_attention` (shard_map + ppermute K/V ring) on a
    data x seq mesh; with `grad`, the custom-vjp backward ring (dK/dV
    accumulators riding home) traced through jax.grad."""
    from ..parallel.ring_attention import ring_self_attention
    mesh = _mesh_for({"data": 2, "seq": 4})
    if mesh is None:
        return None
    q = jnp.zeros((2, 16, 4, 8), jnp.float32)

    def fwd(q, k, v):
        return ring_self_attention(q, k, v, mesh)

    if grad:
        def loss(q, k, v):
            return jnp.sum(fwd(q, k, v) ** 2)
        closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
            q, q, q)
    else:
        closed = jax.make_jaxpr(fwd)(q, q, q)
    return TracedProgram(closed, {"data": 2, "seq": 4},
                         in_specs=_seq_specs(mesh, 3))


@functools.lru_cache(maxsize=None)
def meshed_ulysses_attention_jaxpr():
    """`ulysses_self_attention` (2 all_to_all re-shards) on the same
    data x seq mesh; heads (4) divide the seq axis."""
    from ..parallel.ulysses import ulysses_self_attention
    mesh = _mesh_for({"data": 2, "seq": 4})
    if mesh is None:
        return None
    q = jnp.zeros((2, 16, 4, 8), jnp.float32)
    closed = jax.make_jaxpr(
        lambda q, k, v: ulysses_self_attention(q, k, v, mesh))(q, q, q)
    return TracedProgram(closed, {"data": 2, "seq": 4},
                         in_specs=_seq_specs(mesh, 3))


@functools.lru_cache(maxsize=None)
def meshed_pipeline_jaxpr():
    """`pipeline_blocks` (GPipe ticks: ppermute activation march +
    masked psum collection) over a data x pipe mesh."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.pipeline import pipeline_blocks, stack_block_params
    mesh = _mesh_for({"data": 2, "pipe": 4})
    if mesh is None:
        return None
    stacked = stack_block_params(
        [{"w": jnp.zeros((8, 8), jnp.float32)} for _ in range(4)])
    x = jnp.zeros((8, 8), jnp.float32)
    cond = jnp.zeros((8, 4), jnp.float32)

    def block_fn(p, h, c):
        return jnp.tanh(h @ p["w"])

    closed = jax.make_jaxpr(
        lambda sp, x, c: pipeline_blocks(block_fn, sp, x, c, mesh,
                                         axis="pipe"))(stacked, x, cond)
    # x/cond are reshaped into microbatch layout before the shard_map
    # boundary (reshape deliberately drops spec tracking), so only the
    # stacked block params carry a declared input layout
    return TracedProgram(closed, {"data": 2, "pipe": 4},
                         in_specs=[P("pipe"), None, None])


@functools.lru_cache(maxsize=None)
def meshed_train_step_jaxpr():
    """The REAL `make_train_step` around a tiny SimpleDiT on a
    data x fsdp x tensor mesh. GSPMD inserts this program's collectives
    at compile time (no shard_map), so its comm inventory is legally
    zero — its subject is partition-rule COVERAGE: every leaf of the
    real DiT param tree (to_q/to_k/to_v/to_out, mlp kernels, AdaLN
    tables, norm scales) must be decided by TP inference, FSDP
    inference, or the deliberate small-tensor replicate. min_size is
    scaled down so the tiny trace exercises the same decision paths a
    production-size tree takes."""
    import optax

    from ..models.dit import SimpleDiT
    from ..parallel.partition import partition_coverage
    from ..predictors import EpsilonPredictionTransform
    from ..schedulers import CosineNoiseSchedule
    from ..trainer.train_state import TrainState
    from ..trainer.train_step import TrainStepConfig, make_train_step

    mesh = _mesh_for({"data": 2, "fsdp": 2, "tensor": 2})
    if mesh is None:
        return None
    model = SimpleDiT(patch_size=2, emb_features=32, num_layers=1,
                      num_heads=2, output_channels=1, backend="xla")

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, None)

    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8, 8, 1)), jnp.zeros((1,)),
                        None)["params"]
    state = TrainState.create(apply_fn=apply_fn, params=params,
                              tx=optax.adam(1e-3),
                              rng=jax.random.PRNGKey(1))
    batch = {"sample": jnp.zeros((2, 8, 8, 1), jnp.float32)}
    step = make_train_step(apply_fn, CosineNoiseSchedule(timesteps=100),
                           EpsilonPredictionTransform(),
                           TrainStepConfig(normalize=False),
                           gate_nonfinite=True)
    closed = jax.make_jaxpr(step)(state, batch)
    coverage = partition_coverage(params, mesh, min_size=2 ** 8)
    return TracedProgram(closed,
                         {"data": 2, "fsdp": 2, "tensor": 2},
                         partition=coverage)


@functools.lru_cache(maxsize=None)
def meshed_chunk_program_jaxpr(sampler_name: str = "ddim",
                               rows: int = 2, round_steps: int = 2):
    """The serving chunk program with its request rows sharded over a
    `data` engine group — the layout pod-scale serving (ROADMAP 1)
    dispatches — via explicit row-axis constraints, so the reshard
    detector sees the declared boundary layout."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.partition import with_named_constraint
    mesh = _mesh_for({"data": 2})
    if mesh is None:
        return None
    ds, params = _sampler_pieces(sampler_name)
    prog = ds.make_chunk_program(round_steps)

    def sharded_prog(params, x, keys, pairs, n_act, offsets, steps, state):
        x = with_named_constraint(x, P("data"), mesh)
        keys = with_named_constraint(keys, P("data"), mesh)
        return prog(params, x, keys, pairs, n_act, offsets, steps, None,
                    None, state)

    x = jnp.zeros((rows, 1, 8, 8, 1), jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(rows)])
    pairs = jnp.zeros((rows, round_steps, 2), jnp.float32)
    n_act = jnp.zeros((rows,), jnp.int32)
    offsets = jnp.zeros((rows,), jnp.int32)
    row_states = [ds.sampler.init_state(
        jnp.zeros((1, 8, 8, 1), jnp.float32)) for _ in range(rows)]
    state = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                   *row_states)
    closed = jax.make_jaxpr(sharded_prog)(params, x, keys, pairs,
                                          n_act, offsets,
                                          jnp.int32(round_steps), state)
    return TracedProgram(closed, {"data": 2})


MESHED_PROGRAM_BUILDERS = {
    "meshed_ring_attention": lambda: meshed_ring_attention_jaxpr(),
    "meshed_ring_attention_grad":
        lambda: meshed_ring_attention_jaxpr(grad=True),
    "meshed_ulysses_attention":
        lambda: meshed_ulysses_attention_jaxpr(),
    "meshed_pipeline": lambda: meshed_pipeline_jaxpr(),
    "meshed_train_step_fsdp": lambda: meshed_train_step_jaxpr(),
    "meshed_chunk_ddim": lambda: meshed_chunk_program_jaxpr("ddim"),
}


def meshed_programs(names: Optional[List[str]] = None
                    ) -> List[Tuple[str, TracedProgram]]:
    """[(name, TracedProgram)] for the sharding rules. Programs whose
    mesh cannot form on this host platform (too few devices — the CLI
    and conftest force 8) are omitted rather than faked."""
    sel = names if names is not None else sorted(MESHED_PROGRAM_BUILDERS)
    unknown = [n for n in sel if n not in MESHED_PROGRAM_BUILDERS]
    if unknown:
        raise ValueError(f"unknown meshed program(s) {unknown}; known: "
                         f"{sorted(MESHED_PROGRAM_BUILDERS)}")
    out: List[Tuple[str, TracedProgram]] = []
    for name in sel:
        prog = MESHED_PROGRAM_BUILDERS[name]()
        if prog is not None:
            out.append((name, prog))
    return out


# the inventory the CLI and the tier-1 clean-pass tests iterate
PROGRAM_BUILDERS = {
    "train_step": lambda: train_step_jaxpr(),
    "train_step_monitored": lambda: train_step_jaxpr(monitored=True),
    "train_step_bf16": lambda: train_step_jaxpr(bf16=True),
    "chunk_ddim": lambda: chunk_program_jaxpr("ddim"),
    "chunk_euler_ancestral":
        lambda: chunk_program_jaxpr("euler_ancestral"),
    "chunk_ddim_cached": lambda: cached_chunk_program_jaxpr("ddim"),
    "chunk_euler_ancestral_cached":
        lambda: cached_chunk_program_jaxpr("euler_ancestral"),
    "solo_ddim": lambda: solo_program_jaxpr("ddim"),
    "solo_ddim_cached":
        lambda: solo_program_jaxpr("ddim", cached=True),
    "solo_ddim_spatial":
        lambda: solo_program_jaxpr("ddim", spatial=True),
    "chunk_ddim_spatial":
        lambda: spatial_chunk_program_jaxpr("ddim"),
    "chunk_euler_ancestral_spatial":
        lambda: spatial_chunk_program_jaxpr("euler_ancestral"),
}


def hot_programs(names: Optional[List[str]] = None
                 ) -> List[Tuple[str, object]]:
    """[(name, ClosedJaxpr)] for the graph rules. Traces on whatever
    backend jax resolves — the CLI pins JAX_PLATFORMS=cpu before any
    backend initializes so lint never grabs an accelerator."""
    sel = names if names is not None else sorted(PROGRAM_BUILDERS)
    unknown = [n for n in sel if n not in PROGRAM_BUILDERS]
    if unknown:
        raise ValueError(f"unknown program(s) {unknown}; known: "
                         f"{sorted(PROGRAM_BUILDERS)}")
    return [(name, PROGRAM_BUILDERS[name]()) for name in sel]
