"""Spatial token cache (ops/spatialcache.py, docs/CACHING.md).

Acceptance bars from ISSUE 11:
- a composed plan with the spatial axis disabled (or keep_fraction 1.0)
  routes to the EXISTING timestep-cached program byte-for-byte (same
  sampler instance, same outputs)
- chunked-cached == solo-cached with spatial reuse genuinely engaged
- composed plan keys never collide with each other or with plain
  CachePlans (mirrors the PR-8 eta and PR-10 plan-folding fixes)
- warm serving traffic with a fixed composed plan never re-traces
- prewarmed engines serve the prototype traffic with zero new misses
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.ops.diffcache import CachePlan
from flaxdiff_tpu.ops.spatialcache import (CODE_REFRESH, CODE_REUSE,
                                           CODE_SPATIAL, ComposedPlan,
                                           SpatialPlan, resolve_plan,
                                           spatial_k)


# ---------------------------------------------------------------------------
# Plan semantics
# ---------------------------------------------------------------------------

def test_spatial_plan_validation():
    with pytest.raises(ValueError):
        SpatialPlan(keep_fraction=0.0)
    with pytest.raises(ValueError):
        SpatialPlan(keep_fraction=1.5)
    with pytest.raises(ValueError):
        SpatialPlan(metric="cosine")
    with pytest.raises(ValueError):
        SpatialPlan(every=0)
    with pytest.raises(ValueError):
        ComposedPlan(cache="not-a-plan")
    assert spatial_k(64, 0.125) == 8
    assert spatial_k(4, 0.01) == 1          # never zero tokens
    assert spatial_k(4, 1.0) == 4


def test_step_codes_semantics():
    p = ComposedPlan(cache=CachePlan(refresh_every=4, refresh_head=1,
                                     refresh_tail=1),
                     spatial=SpatialPlan(keep_fraction=0.25))
    codes = p.step_codes(9)
    # flags: [T,F,F,F,T,F,F,F,T] -> refresh at 0/4/8, spatial between
    assert codes.tolist() == [2, 1, 1, 1, 2, 1, 1, 1, 2]
    assert p.counts(9) == {"refresh": 3, "spatial": 6, "reused": 0}
    # every=2: the spatial cadence counts from the last full refresh
    # (first cached step after a refresh is pure reuse)
    p2 = ComposedPlan(cache=CachePlan(refresh_every=4, refresh_head=1,
                                      refresh_tail=1),
                      spatial=SpatialPlan(keep_fraction=0.25, every=2))
    assert p2.step_codes(9).tolist() == [2, 0, 1, 0, 2, 0, 1, 0, 2]
    assert {CODE_REUSE, CODE_SPATIAL, CODE_REFRESH} == {0, 1, 2}


def test_resolve_plan_routing():
    cache = CachePlan(refresh_every=3)
    # spatial disabled / keep 1.0 -> the plain CachePlan object (the
    # sampler cache key is then IDENTICAL to the timestep-only plan:
    # byte-for-byte the existing program)
    assert resolve_plan(ComposedPlan(
        cache=cache, spatial=SpatialPlan(enabled=False))) is cache
    assert resolve_plan(ComposedPlan(
        cache=cache, spatial=SpatialPlan(keep_fraction=1.0))) is cache
    # refresh_every=1 leaves no cached step for the spatial axis to act
    # on -> fully uncached
    assert resolve_plan(ComposedPlan(
        cache=CachePlan(refresh_every=1))) is None
    assert resolve_plan(None) is None
    # a live composed plan resolves to itself; a bare SpatialPlan
    # composes with the default CachePlan
    live = ComposedPlan(cache=cache, spatial=SpatialPlan())
    assert resolve_plan(live) is live
    bare = resolve_plan(SpatialPlan(keep_fraction=0.5))
    assert isinstance(bare, ComposedPlan)
    assert bare.spatial.keep_fraction == 0.5
    # plain CachePlans route exactly as before
    assert resolve_plan(cache) is cache
    assert resolve_plan(CachePlan(refresh_every=1)) is None


def test_plan_keys_never_collide():
    cache = CachePlan(refresh_every=3)
    a = ComposedPlan(cache=cache, spatial=SpatialPlan())
    b = ComposedPlan(cache=cache,
                     spatial=SpatialPlan(keep_fraction=0.5))
    c = ComposedPlan(cache=cache, spatial=SpatialPlan(every=2))
    d = ComposedPlan(cache=cache, spatial=SpatialPlan(metric="linf"))
    keys = {a.key(), b.key(), c.key(), d.key(), cache.key()}
    assert len(keys) == 5                   # composed != composed != plain
    assert hash(a) is not None              # usable in program caches
    assert a.key() == ComposedPlan(cache=CachePlan(refresh_every=3),
                                   spatial=SpatialPlan()).key()


# ---------------------------------------------------------------------------
# Model forward contract (spatial + record_ref modes, 3 families)
# ---------------------------------------------------------------------------

@jax.jit
def _perturb(params, scale=0.05, seed=7):
    # AdaLN-Zero blocks are exact identities at init (zero-init gates)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [l + scale * jax.random.normal(k, l.shape, l.dtype)
                  for l, k in zip(leaves, keys)])


def _models():
    from flaxdiff_tpu.models.dit import SimpleDiT
    from flaxdiff_tpu.models.mmdit import SimpleMMDiT
    from flaxdiff_tpu.models.uvit import SimpleUDiT
    text = jnp.ones((2, 3, 16))
    return [
        ("dit", SimpleDiT(output_channels=1, patch_size=4,
                          emb_features=32, num_layers=3, num_heads=4),
         None, 0.2),
        ("udit", SimpleUDiT(output_channels=1, patch_size=4,
                            emb_features=32, num_layers=4, num_heads=4),
         None, 0.5),
        ("mmdit", SimpleMMDiT(output_channels=1, patch_size=4,
                              emb_features=32, num_layers=3,
                              num_heads=4), text, 0.2),
    ]


@pytest.mark.parametrize("name,model,text,frac",
                         _models(), ids=lambda v: v if isinstance(v, str)
                         else "")
def test_spatial_forward_contract(name, model, text, frac):
    """record_ref is bit-identical to the plain forward; spatial with
    every token selected reproduces the record output to rounding
    (gather/scatter is a permutation; attention is permutation-
    equivariant with gathered RoPE tables); partial keep touches
    exactly k token slots of the carries; the param tree is
    mode-invariant."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 1))
    t = jnp.full((2,), 10.0)
    params = _perturb(jax.jit(model.init)(jax.random.PRNGKey(1), x, t, text))
    split = model.cache_split_index(frac)

    def apply(mode=None, keep=None, **carries):
        # mode / split / keep are Python values of the program, the
        # carries its operands: one compiled program a call
        static = {} if mode is None else dict(cache_mode=mode,
                                              cache_split=split)
        if keep is not None:
            static["cache_keep"] = keep
        return jax.jit(lambda p, c: model.apply(p, x, t, text, **static,
                                                **c))(params, carries)

    plain = apply()
    out, taps, ref = apply("record_ref")
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(out))
    L = taps.shape[1]
    # all-token spatial step ~= a full record step
    o_all, taps_all, ref_all = apply(
        "spatial", 1.0, cache_taps=jnp.zeros_like(taps),
        cache_ref=jnp.zeros_like(ref))
    np.testing.assert_allclose(np.asarray(plain), np.asarray(o_all),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ref_all),
                               rtol=2e-4, atol=2e-5)
    # partial keep: finite output, exactly k carry slots rewritten
    # (the zero ref forces every token to score > 0, so selection is
    # the top-k of a strictly positive vector)
    o_p, taps_p, ref_p = apply("spatial", 0.5, cache_taps=taps,
                               cache_ref=jnp.zeros_like(ref))
    assert np.isfinite(np.asarray(o_p)).all()
    k = spatial_k(L, 0.5)
    changed_ref = np.any(np.asarray(ref_p) != 0.0, axis=(0, 2))
    assert int(changed_ref.sum()) == k
    unchanged_taps = np.all(np.asarray(taps_p) == np.asarray(taps),
                            axis=(0, 2))
    assert int(unchanged_taps.sum()) >= L - k
    # param tree is mode-invariant
    p_sp = jax.eval_shape(
        lambda key: model.init(key, x, t, text, cache_mode="spatial",
                               cache_split=split, cache_taps=taps,
                               cache_ref=ref, cache_keep=0.5),
        jax.random.PRNGKey(1))
    assert (jax.tree_util.tree_structure(p_sp)
            == jax.tree_util.tree_structure(params))
    # spatial requires both carries
    with pytest.raises(ValueError, match="spatial"):
        apply("spatial", cache_taps=taps)


# ---------------------------------------------------------------------------
# Solo sampling
# ---------------------------------------------------------------------------

def _pipe(num_layers=3, perturb=True):
    from flaxdiff_tpu.inference import (DiffusionInferencePipeline,
                                        build_model)
    config = {
        "model": {"name": "simple_dit", "emb_features": 32,
                  "num_heads": 4, "num_layers": num_layers,
                  "patch_size": 4, "output_channels": 1},
        "schedule": {"name": "cosine", "timesteps": 100},
        "predictor": "epsilon",
    }
    model = build_model("simple_dit", emb_features=32, num_heads=4,
                        num_layers=num_layers, patch_size=4,
                        output_channels=1)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)), jnp.zeros((1,)),
        None)
    if perturb:
        params = _perturb(params)
    return DiffusionInferencePipeline.from_config(config, params=params)


@pytest.fixture(scope="module")
def tiny_pipe():
    return _pipe()


_PLAN = ComposedPlan(cache=CachePlan(refresh_every=3, refresh_head=1,
                                     refresh_tail=1),
                     spatial=SpatialPlan(keep_fraction=0.5))


def test_degenerate_spatial_routes_to_timestep_program(tiny_pipe):
    """keep 1.0 / disabled spatial = the SAME DiffusionSampler
    instance as the plain CachePlan — byte-for-byte the existing
    timestep-cached program — and identical samples."""
    cache = CachePlan(refresh_every=3)
    a = tiny_pipe.get_sampler("ddim", 0.0, cache_plan=cache)
    b = tiny_pipe.get_sampler("ddim", 0.0, cache_plan=ComposedPlan(
        cache=cache, spatial=SpatialPlan(keep_fraction=1.0)))
    c = tiny_pipe.get_sampler("ddim", 0.0, cache_plan=ComposedPlan(
        cache=cache, spatial=SpatialPlan(enabled=False)))
    assert a is b and a is c
    assert not a.spatial_active
    kw = dict(num_samples=1, resolution=8, channels=1,
              diffusion_steps=5, sampler="ddim", seed=11, use_ema=False)
    base = tiny_pipe.generate_samples(**kw, cache_plan=cache)
    routed = tiny_pipe.generate_samples(**kw, cache_plan=ComposedPlan(
        cache=cache, spatial=SpatialPlan(keep_fraction=1.0)))
    np.testing.assert_array_equal(base, routed)


def test_composed_plan_folds_into_sampler_cache(tiny_pipe):
    a = tiny_pipe.get_sampler("ddim", 0.0, cache_plan=_PLAN)
    b = tiny_pipe.get_sampler("ddim", 0.0, cache_plan=dataclasses
                              .replace(_PLAN))
    c = tiny_pipe.get_sampler(
        "ddim", 0.0,
        cache_plan=dataclasses.replace(
            _PLAN, spatial=SpatialPlan(keep_fraction=0.25)))
    d = tiny_pipe.get_sampler("ddim", 0.0, cache_plan=_PLAN.cache)
    assert a is b and a is not c and a is not d
    assert a.spatial_active and not d.spatial_active


def test_solo_spatial_reuse_engages(tiny_pipe):
    """The composed trajectory must differ from BOTH the uncached and
    the pure timestep-cached one (pre-clip program outputs: the
    untrained net saturates clip_images)."""
    ds_u = tiny_pipe.get_sampler("ddim", 0.0)
    ds_t = tiny_pipe.get_sampler("ddim", 0.0, cache_plan=_PLAN.cache)
    ds_c = tiny_pipe.get_sampler("ddim", 0.0, cache_plan=_PLAN)
    shape = (2, 8, 8, 1)
    x = jax.random.normal(jax.random.PRNGKey(3), shape) \
        * ds_u.schedule.max_noise_std()
    key = jax.random.PRNGKey(4)
    params = tiny_pipe.params
    out_u = ds_u._get_program(8, shape, None, 0.0)(params, x, key,
                                                   None, None)
    out_t = ds_t._get_program(8, shape, None, 0.0)(params, x, key,
                                                   None, None)
    out_c = ds_c._get_program(8, shape, None, 0.0)(params, x, key,
                                                   None, None)
    assert np.isfinite(np.asarray(out_c)).all()
    assert not np.array_equal(np.asarray(out_u), np.asarray(out_c))
    assert not np.array_equal(np.asarray(out_t), np.asarray(out_c))


def test_solo_spatial_metrics_recorded(tiny_pipe):
    from flaxdiff_tpu.telemetry import Telemetry, use_telemetry
    plan = ComposedPlan(cache=CachePlan(refresh_every=3,
                                        refresh_head=1,
                                        refresh_tail=1),
                        spatial=SpatialPlan(keep_fraction=0.5,
                                            every=2))
    with use_telemetry(Telemetry(enabled=False)) as tel:
        tiny_pipe.generate_samples(
            num_samples=1, resolution=8, channels=1, diffusion_steps=6,
            sampler="ddim", seed=2, use_ema=False, cache_plan=plan)
        snap = tel.registry.snapshot()
    # codes(6): flags [T,F,F,T,F,T] + every=2 -> [2,0,1,2,0,2]
    assert snap["diffcache/requests"] == 1
    assert snap["diffcache/spatial_requests"] == 1
    assert snap["diffcache/refresh_steps"] == 3
    assert snap["diffcache/spatial_steps"] == 1
    assert snap["diffcache/reused_steps"] == 2


# ---------------------------------------------------------------------------
# Serving: chunked bit-identity, prewarm, warm cache
# ---------------------------------------------------------------------------

def _sched(pipe, tel=None, **cfg):
    from flaxdiff_tpu.serving import SchedulerConfig, ServingScheduler
    from flaxdiff_tpu.telemetry import Telemetry
    return ServingScheduler(
        pipeline=pipe, telemetry=tel or Telemetry(enabled=False),
        autostart=False,
        config=SchedulerConfig(**{"round_steps": 2,
                                  "batch_buckets": (4,), **cfg}))


def test_chunked_spatial_matches_solo(tiny_pipe):
    """With single-row rounds the round codes ARE the row's own
    schedule: the chunked composed trajectory equals the solo composed
    one bitwise (taps + ref carries survive round boundaries
    exactly)."""
    from flaxdiff_tpu.serving import SampleRequest
    sched = _sched(tiny_pipe, batch_buckets=(1,))
    f = sched.submit(SampleRequest(
        resolution=8, channels=1, diffusion_steps=6, sampler="ddim",
        seed=21, use_ema=False, cache_plan=_PLAN))
    sched.start()
    out = f.result(timeout=300)
    sched.close()
    solo = tiny_pipe.generate_samples(
        num_samples=1, resolution=8, channels=1, diffusion_steps=6,
        sampler="ddim", seed=21, use_ema=False, cache_plan=_PLAN)
    np.testing.assert_array_equal(out.samples, solo)


def test_chunked_spatial_stochastic_sampler_matches_solo(tiny_pipe):
    """Per-row RNG lineage through the spatial chunk program: a
    stochastic sampler batched with padding still equals its solo
    composed run bitwise."""
    from flaxdiff_tpu.serving import SampleRequest
    from flaxdiff_tpu.telemetry import Telemetry
    tel = Telemetry(enabled=False)
    sched = _sched(tiny_pipe, tel)
    reqs = [SampleRequest(resolution=8, channels=1, diffusion_steps=n,
                          sampler="euler_ancestral", seed=s,
                          use_ema=False, cache_plan=_PLAN)
            for n, s in ((4, 7), (6, 11))]
    futs = [sched.submit(r) for r in reqs]
    sched.start()
    outs = [f.result(timeout=300) for f in futs]
    sched.close()
    for r, o in zip(reqs, outs):
        solo = tiny_pipe.generate_samples(
            num_samples=1, resolution=8, channels=1,
            diffusion_steps=r.diffusion_steps, sampler=r.sampler,
            seed=r.seed, use_ema=False, cache_plan=_PLAN)
        np.testing.assert_array_equal(o.samples, solo)
    snap = tel.registry.snapshot()
    assert snap["serving/rows_padded"] > 0      # padding was forced
    assert snap["serving/spatial_rows"] > 0     # composed rounds ran


def test_engine_group_and_program_keys_separate_plans(tiny_pipe):
    """Mirrors the PR-8 eta and PR-10 plan-folding fixes: composed
    plans over identical request shapes never share a group or a
    compiled program — not with each other, not with the plain
    timestep plan, not with uncached."""
    from flaxdiff_tpu.serving import SampleRequest, SamplerProgramEngine
    from flaxdiff_tpu.telemetry import Telemetry
    eng = SamplerProgramEngine(tiny_pipe,
                               telemetry=Telemetry(enabled=False))
    r1 = SampleRequest(resolution=8, channels=1, diffusion_steps=4,
                       sampler="ddim", use_ema=False, cache_plan=_PLAN)
    r2 = dataclasses.replace(r1, cache_plan=dataclasses.replace(
        _PLAN, spatial=SpatialPlan(keep_fraction=0.25)))
    r3 = dataclasses.replace(r1, cache_plan=_PLAN.cache)
    r4 = dataclasses.replace(r1, cache_plan=None)
    # keep 1.0 routes to the SAME group as the plain timestep plan
    r5 = dataclasses.replace(r1, cache_plan=dataclasses.replace(
        _PLAN, spatial=SpatialPlan(keep_fraction=1.0)))
    g1, g2, g3, g4, g5 = (eng.group_key(r) for r in
                          (r1, r2, r3, r4, r5))
    assert len({g1, g2, g3, g4}) == 4
    assert g5 == g3
    assert g1[:-1] == g2[:-1] == g3[:-1] == g4[:-1]
    assert eng._program_key("chunk_spatial", g1, 4, 2) \
        != eng._program_key("chunk_spatial", g2, 4, 2)


def test_composed_warm_traffic_never_retraces(tiny_pipe):
    """Warm serving traffic with a FIXED composed plan is served
    entirely from the compiled-program cache: zero new misses on the
    second pass, identical samples."""
    from flaxdiff_tpu.serving import SampleRequest
    from flaxdiff_tpu.telemetry import Telemetry
    tel = Telemetry(enabled=False)
    sched = _sched(tiny_pipe, tel, batch_buckets=(1, 2))

    def pass_once():
        futs = [sched.submit(SampleRequest(
            resolution=8, channels=1, diffusion_steps=n, sampler="ddim",
            seed=s, use_ema=False, cache_plan=_PLAN))
            for n, s in ((3, 1), (3, 2), (5, 9))]
        sched.start()
        return [f.result(timeout=300) for f in futs]

    first = pass_once()
    misses_cold = tel.registry.counter(
        "serving/program_cache_misses").value
    assert misses_cold > 0
    second = pass_once()
    sched.close()
    assert tel.registry.counter(
        "serving/program_cache_misses").value == misses_cold
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a.samples, b.samples)


def test_prewarm_compiles_before_admission(tiny_pipe):
    """`scheduler.prewarm(prototypes)` compiles every (bucket, NFE,
    plan) tuple the prototype traffic hits: subsequent submits cause
    ZERO new program-cache misses and no per-request compile stalls,
    and the samples still match solo runs bitwise."""
    from flaxdiff_tpu.serving import SampleRequest
    from flaxdiff_tpu.telemetry import Telemetry
    tel = Telemetry(enabled=False)
    sched = _sched(tiny_pipe, tel, batch_buckets=(2,))
    protos = [
        SampleRequest(resolution=8, channels=1, diffusion_steps=4,
                      sampler="ddim", use_ema=False, cache_plan=_PLAN),
        SampleRequest(resolution=8, channels=1, diffusion_steps=3,
                      sampler="euler_ancestral", use_ema=False),
    ]
    info = sched.prewarm(protos)
    assert info["programs"] > 0
    assert tel.registry.counter(
        "serving/prewarm_programs").value == info["programs"]
    misses0 = tel.registry.counter(
        "serving/program_cache_misses").value
    futs = [sched.submit(dataclasses.replace(p, seed=s))
            for s, p in ((5, protos[0]), (6, protos[1]),
                         (7, protos[0]))]
    sched.start()
    outs = [f.result(timeout=300) for f in futs]
    sched.close()
    assert tel.registry.counter(
        "serving/program_cache_misses").value == misses0
    assert all(o.compile_ms == 0.0 for o in outs)
    for o in outs:
        solo = tiny_pipe.generate_samples(
            num_samples=1, resolution=8, channels=1,
            diffusion_steps=o.request.diffusion_steps,
            sampler=o.request.sampler, seed=o.request.seed,
            use_ema=False, cache_plan=o.request.cache_plan)
        np.testing.assert_array_equal(o.samples, solo)


def test_unsupported_model_drops_composed_plan():
    """A 1-layer DiT cannot split: the composed plan is dropped
    (counted) and the request matches the uncached solo run exactly."""
    from flaxdiff_tpu.serving import SampleRequest
    from flaxdiff_tpu.telemetry import Telemetry
    pipe = _pipe(num_layers=1)
    tel = Telemetry(enabled=False)
    sched = _sched(pipe, tel, batch_buckets=(1,))
    f = sched.submit(SampleRequest(
        resolution=8, channels=1, diffusion_steps=3, sampler="ddim",
        seed=5, use_ema=False, cache_plan=_PLAN))
    sched.start()
    out = f.result(timeout=300)
    sched.close()
    solo = pipe.generate_samples(
        num_samples=1, resolution=8, channels=1, diffusion_steps=3,
        sampler="ddim", seed=5, use_ema=False)
    np.testing.assert_array_equal(out.samples, solo)
    assert tel.registry.counter("serving/cache_unsupported").value > 0
    assert tel.registry.snapshot().get("serving/spatial_rows", 0) == 0
