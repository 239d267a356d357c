"""Training-free diffusion cache (ops/diffcache.py, docs/CACHING.md).

Acceptance bars from ISSUE 10:
- cache-off requests are bit-identical to pre-cache sampling (the
  uncached program is byte-for-byte unchanged; asserted solo + chunked)
- refresh-every-step plans are bit-identical to the uncached paths
  (DDIM + euler_ancestral, padding forced, CFG prompted)
- two plans with identical shapes never share a compiled program
- warm serving traffic with a fixed plan causes zero re-traces
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.ops.diffcache import (CachePlan, DEFAULT_CACHE_PLAN,
                                        active_plan, model_supports_cache,
                                        resolve_cache_fns)


# ---------------------------------------------------------------------------
# CachePlan semantics
# ---------------------------------------------------------------------------

def test_plan_flags_semantics():
    p = CachePlan(refresh_every=3, refresh_head=2, refresh_tail=1)
    f = p.flags(10)
    assert f.shape == (10,) and f.dtype == bool
    assert f[0] and f[1]                   # head
    assert f[-1]                           # tail
    assert f[3] and f[6] and f[9]          # cadence
    assert not f[2] and not f[4] and not f[5]
    # step 0 refreshes even with head 0 — the cache starts empty
    assert CachePlan(refresh_every=5, refresh_head=0,
                     refresh_tail=0).flags(5)[0]
    # refresh-every-step plan = all True; disabled plan = all True
    assert CachePlan(refresh_every=1).flags(4).all()
    assert CachePlan(enabled=False).flags(4).all()
    # single-step trajectory: the one step refreshes
    assert CachePlan().flags(1).tolist() == [True]


def test_plan_validation_and_keys():
    with pytest.raises(ValueError):
        CachePlan(refresh_every=0)
    with pytest.raises(ValueError):
        CachePlan(depth_fraction=0.0)
    with pytest.raises(ValueError):
        CachePlan(depth_fraction=1.0)
    with pytest.raises(ValueError):
        CachePlan(refresh_head=-1)
    a, b = CachePlan(), CachePlan(refresh_every=2)
    assert a.key() != b.key()
    assert a.key() == CachePlan().key()
    assert hash(a) is not None              # usable in cache keys
    assert active_plan(None) is None
    assert active_plan(CachePlan(enabled=False)) is None
    # refresh_every=1 can never reuse: routed to the uncached program
    # (bit-identical by construction, see active_plan docstring)
    assert active_plan(CachePlan(refresh_every=1)) is None
    assert active_plan(a) is a
    frac = CachePlan(refresh_every=2, refresh_head=0,
                     refresh_tail=0).reused_fraction(10)
    assert frac == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# Model cache_mode forward contract
# ---------------------------------------------------------------------------

@jax.jit
def _perturb(params, scale=0.05, seed=7):
    # AdaLN-Zero blocks are exact identities at init (zero-init gates):
    # without this the deep delta is zero and reuse is trivially exact
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
         None),
        ("udit", SimpleUDiT(output_channels=1, patch_size=4,
                            emb_features=32, num_layers=4, num_heads=4),
         None),
        ("mmdit", SimpleMMDiT(output_channels=1, patch_size=4,
                              emb_features=32, num_layers=3,
                              num_heads=4), text),
    ]


@pytest.mark.parametrize("name,model,text",
                         _models(), ids=lambda v: v if isinstance(v, str)
                         else "")
def test_record_reuse_forward_contract(name, model, text):
    """record runs the exact plain block sequence (bit-identical
    output) and its taps make reuse exact-to-rounding at the SAME
    input (`shallow + (deep - shallow)` re-associates, so last-ulp
    differences are expected); the param tree is mode-independent."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 1))
    t = jnp.full((2,), 10.0)
    params = _perturb(jax.jit(model.init)(jax.random.PRNGKey(1), x, t, text))
    split = model.cache_split_index(DEFAULT_CACHE_PLAN.depth_fraction)

    def apply(x, mode=None, **carries):
        # mode / split are Python values of the program, the taps its
        # operand: one compiled program a call
        static = {} if mode is None else dict(cache_mode=mode,
                                              cache_split=split)
        return jax.jit(lambda p, x, c: model.apply(p, x, t, text, **static,
                                                   **c))(params, x, carries)

    plain = apply(x)
    rec, taps = apply(x, "record")
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(rec))
    reu = apply(x, "reuse", cache_taps=taps)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(reu),
                               rtol=1e-5, atol=1e-6)
    # stale taps (from a different input) give a DIFFERENT, finite
    # output — the reuse path is genuinely engaged
    x2 = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 8, 1))
    _, taps2 = apply(x2, "record")
    approx = apply(x, "reuse", cache_taps=taps2)
    assert np.isfinite(np.asarray(approx)).all()
    assert not np.array_equal(np.asarray(plain), np.asarray(approx))


def test_cache_split_and_support_gates():
    from flaxdiff_tpu.models.dit import SimpleDiT
    from flaxdiff_tpu.models.unet import Unet
    from flaxdiff_tpu.models.uvit import SimpleUDiT
    deep = SimpleDiT(num_layers=12)
    assert deep.cache_split_index(0.2) == 2
    assert deep.cache_split_index(0.99) == 11      # clamped below top
    assert deep.cache_split_index(0.01) == 1       # never zero shallow
    with pytest.raises(ValueError):
        SimpleDiT(num_layers=1).cache_split_index(0.2)
    with pytest.raises(ValueError):
        SimpleUDiT(num_layers=2).cache_split_index(0.2)
    assert model_supports_cache(deep)
    assert not model_supports_cache(SimpleDiT(num_layers=1))
    assert not model_supports_cache(Unet())
    with pytest.raises(ValueError, match="cache_mode"):
        resolve_cache_fns(Unet(), CachePlan())


# ---------------------------------------------------------------------------
# Solo sampling: bit-identity + engagement
# ---------------------------------------------------------------------------

def _pipe(num_layers=2, perturb=True):
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


@pytest.mark.parametrize("sampler", ["ddim", "euler_ancestral"])
def test_solo_refresh_every_step_bit_identity(tiny_pipe, sampler):
    kw = dict(num_samples=2, resolution=8, channels=1,
              diffusion_steps=5, sampler=sampler, seed=11,
              use_ema=False)
    base = tiny_pipe.generate_samples(**kw)
    every = tiny_pipe.generate_samples(
        **kw, cache_plan=CachePlan(refresh_every=1))
    np.testing.assert_array_equal(base, every)
    # disabled plan routes through the plain (pre-cache) program
    off = tiny_pipe.generate_samples(
        **kw, cache_plan=CachePlan(enabled=False))
    np.testing.assert_array_equal(base, off)


def test_solo_cached_reuse_engages(tiny_pipe):
    """A reuse-heavy plan must actually change the trajectory (on the
    pre-clip program outputs: the untrained net saturates clip_images,
    which would mask any difference)."""
    ds_u = tiny_pipe.get_sampler("ddim", 0.0)
    ds_c = tiny_pipe.get_sampler(
        "ddim", 0.0, cache_plan=CachePlan(refresh_every=4,
                                          refresh_head=1,
                                          refresh_tail=0))
    shape = (2, 8, 8, 1)
    x = jax.random.normal(jax.random.PRNGKey(3), shape) \
        * ds_u.schedule.max_noise_std()
    key = jax.random.PRNGKey(4)
    params = tiny_pipe.params
    out_u = ds_u._get_program(8, shape, None, 0.0)(params, x, key,
                                                   None, None)
    out_c = ds_c._get_program(8, shape, None, 0.0)(params, x, key,
                                                   None, None)
    assert np.isfinite(np.asarray(out_c)).all()
    assert not np.array_equal(np.asarray(out_u), np.asarray(out_c))


def test_solo_cfg_prompted_refresh_every_step_identity():
    """CFG doubles the batch inside the cached scan (taps cover 2B):
    prompted + guided sampling with an always-refresh plan stays
    bit-identical."""
    from flaxdiff_tpu.inference import (DiffusionInferencePipeline,
                                        build_model)
    from flaxdiff_tpu.inputs import (ConditionalInputConfig,
                                     DiffusionInputConfig)
    from flaxdiff_tpu.inputs.encoders import HashTextEncoder

    enc = HashTextEncoder.create(features=16, max_length=8)
    model = build_model("simple_dit", emb_features=32, num_heads=4,
                        num_layers=2, patch_size=4, output_channels=1)
    params = _perturb(jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)),
        jnp.zeros((1,)), jnp.asarray(enc([""]))))
    pipe = DiffusionInferencePipeline.from_config(
        {"model": {"name": "simple_dit", "emb_features": 32,
                   "num_heads": 4, "num_layers": 2, "patch_size": 4,
                   "output_channels": 1},
         "schedule": {"name": "cosine", "timesteps": 100},
         "predictor": "epsilon"}, params=params)
    pipe.input_config = DiffusionInputConfig(
        sample_data_key="sample", sample_data_shape=(8, 8, 1),
        conditions=[ConditionalInputConfig(encoder=enc)])
    kw = dict(prompts=["a red flower"], resolution=8, channels=1,
              diffusion_steps=4, sampler="ddim", guidance_scale=2.0,
              seed=21, use_ema=False)
    base = pipe.generate_samples(**kw)
    every = pipe.generate_samples(
        **kw, cache_plan=CachePlan(refresh_every=1))
    np.testing.assert_array_equal(base, every)


def test_get_sampler_folds_plan_into_cache_key(tiny_pipe):
    a = tiny_pipe.get_sampler("ddim", 0.0)
    b = tiny_pipe.get_sampler("ddim", 0.0, cache_plan=CachePlan())
    c = tiny_pipe.get_sampler("ddim", 0.0, cache_plan=CachePlan())
    d = tiny_pipe.get_sampler(
        "ddim", 0.0, cache_plan=CachePlan(refresh_every=2))
    assert a is not b and b is c and b is not d
    assert not a.cache_active and b.cache_active
    # disabled plan == no plan == always-refresh plan: all route to the
    # same (uncached, bit-exact) sampler instance
    assert tiny_pipe.get_sampler(
        "ddim", 0.0, cache_plan=CachePlan(enabled=False)) is a
    assert tiny_pipe.get_sampler(
        "ddim", 0.0, cache_plan=CachePlan(refresh_every=1)) is a


def test_solo_cached_metrics_recorded(tiny_pipe):
    from flaxdiff_tpu.telemetry import Telemetry, use_telemetry
    with use_telemetry(Telemetry(enabled=False)) as tel:
        tiny_pipe.generate_samples(
            num_samples=1, resolution=8, channels=1, diffusion_steps=6,
            sampler="ddim", seed=2, use_ema=False,
            cache_plan=CachePlan(refresh_every=3, refresh_head=1,
                                 refresh_tail=1))
        snap = tel.registry.snapshot()
    assert snap["diffcache/requests"] == 1
    # flags(6) with every=3/head1/tail1: [T,F,F,T,F,T] -> 3 refresh
    assert snap["diffcache/refresh_steps"] == 3
    assert snap["diffcache/reused_steps"] == 3


# ---------------------------------------------------------------------------
# Serving: chunked bit-identity, plan keys, warm cache
# ---------------------------------------------------------------------------

def _sched(pipe, tel=None, **cfg):
    from flaxdiff_tpu.serving import SchedulerConfig, ServingScheduler
    from flaxdiff_tpu.telemetry import Telemetry
    return ServingScheduler(
        pipeline=pipe, telemetry=tel or Telemetry(enabled=False),
        autostart=False,
        config=SchedulerConfig(**{"round_steps": 2,
                                  "batch_buckets": (4,), **cfg}))


def test_chunked_refresh_every_step_bit_identity(tiny_pipe):
    """Requests carrying an always-refresh plan == uncached solo
    samples, under padding + NFE masking + chunked rounds, for a
    stochastic and a deterministic sampler (the plan routes to the
    uncached chunk program — bit-exact by construction)."""
    from flaxdiff_tpu.serving import SampleRequest
    from flaxdiff_tpu.telemetry import Telemetry
    always = CachePlan(refresh_every=1)
    tel = Telemetry(enabled=False)
    sched = _sched(tiny_pipe, tel)
    reqs = [
        SampleRequest(resolution=8, channels=1, diffusion_steps=3,
                      sampler="euler_ancestral", seed=7, use_ema=False,
                      cache_plan=always),
        SampleRequest(resolution=8, channels=1, diffusion_steps=5,
                      sampler="euler_ancestral", seed=11,
                      use_ema=False, cache_plan=always),
        SampleRequest(resolution=8, channels=1, diffusion_steps=4,
                      sampler="ddim", seed=3, use_ema=False,
                      cache_plan=always),
    ]
    futs = [sched.submit(r) for r in reqs]
    sched.start()
    outs = [f.result(timeout=300) for f in futs]
    sched.close()
    for r, o in zip(reqs, outs):
        solo = tiny_pipe.generate_samples(
            num_samples=1, resolution=8, channels=1,
            diffusion_steps=r.diffusion_steps, sampler=r.sampler,
            seed=r.seed, use_ema=False)
        np.testing.assert_array_equal(o.samples, solo)
    snap = tel.registry.snapshot()
    assert snap["serving/rows_padded"] > 0      # padding was forced
    # an always-refresh plan is routed to the UNCACHED chunk program
    # (bit-exact by construction): no cached rounds ran
    assert snap.get("serving/cache_rows", 0) == 0


def test_chunked_cached_matches_cached_solo(tiny_pipe):
    """With single-row rounds the round flags ARE the row's own
    schedule: the chunked cached trajectory must equal the solo cached
    one bitwise (taps carry survives round boundaries exactly)."""
    from flaxdiff_tpu.serving import SampleRequest
    plan = CachePlan(refresh_every=3, refresh_head=1, refresh_tail=1)
    sched = _sched(tiny_pipe, batch_buckets=(1,))
    f = sched.submit(SampleRequest(
        resolution=8, channels=1, diffusion_steps=6, sampler="ddim",
        seed=21, use_ema=False, cache_plan=plan))
    sched.start()
    out = f.result(timeout=300)
    sched.close()
    solo = tiny_pipe.generate_samples(
        num_samples=1, resolution=8, channels=1, diffusion_steps=6,
        sampler="ddim", seed=21, use_ema=False, cache_plan=plan)
    np.testing.assert_array_equal(out.samples, solo)


def test_chunked_cfg_prompted_refresh_every_step_identity():
    """Prompted CFG requests with an always-refresh plan through the
    scheduler match solo prompted generation bitwise."""
    from flaxdiff_tpu.inference import (DiffusionInferencePipeline,
                                        build_model)
    from flaxdiff_tpu.inputs import (ConditionalInputConfig,
                                     DiffusionInputConfig)
    from flaxdiff_tpu.inputs.encoders import HashTextEncoder
    from flaxdiff_tpu.serving import SampleRequest

    enc = HashTextEncoder.create(features=16, max_length=8)
    model = build_model("simple_dit", emb_features=32, num_heads=4,
                        num_layers=2, patch_size=4, output_channels=1)
    params = _perturb(jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)),
        jnp.zeros((1,)), jnp.asarray(enc([""]))))
    pipe = DiffusionInferencePipeline.from_config(
        {"model": {"name": "simple_dit", "emb_features": 32,
                   "num_heads": 4, "num_layers": 2, "patch_size": 4,
                   "output_channels": 1},
         "schedule": {"name": "cosine", "timesteps": 100},
         "predictor": "epsilon"}, params=params)
    pipe.input_config = DiffusionInputConfig(
        sample_data_key="sample", sample_data_shape=(8, 8, 1),
        conditions=[ConditionalInputConfig(encoder=enc)])
    always = CachePlan(refresh_every=1)
    sched = _sched(pipe, batch_buckets=(1, 2))
    futs = [sched.submit(SampleRequest(
        resolution=8, channels=1, diffusion_steps=3, sampler="ddim",
        guidance_scale=2.0, prompts=[p], seed=s, use_ema=False,
        cache_plan=always))
        for p, s in (("a red flower", 21), ("blue sky", 22))]
    sched.start()
    outs = [f.result(timeout=300) for f in futs]
    sched.close()
    for (p, s), o in zip((("a red flower", 21), ("blue sky", 22)), outs):
        solo = pipe.generate_samples(
            prompts=[p], resolution=8, channels=1, diffusion_steps=3,
            sampler="ddim", guidance_scale=2.0, seed=s, use_ema=False)
        np.testing.assert_array_equal(o.samples, solo)


def test_plan_key_no_program_collision(tiny_pipe):
    """Regression (mirrors the PR-8 DDIM-eta key fix): two plans over
    identical request shapes must not share a group or a compiled
    program."""
    from flaxdiff_tpu.serving import SampleRequest, SamplerProgramEngine
    from flaxdiff_tpu.telemetry import Telemetry
    eng = SamplerProgramEngine(tiny_pipe,
                               telemetry=Telemetry(enabled=False))
    r1 = SampleRequest(resolution=8, channels=1, diffusion_steps=4,
                       sampler="ddim", use_ema=False,
                       cache_plan=CachePlan(refresh_every=2))
    r2 = dataclasses.replace(r1, cache_plan=CachePlan(refresh_every=4))
    r3 = dataclasses.replace(r1, cache_plan=None)
    g1, g2, g3 = (eng.group_key(r) for r in (r1, r2, r3))
    assert g1 != g2 and g1 != g3 and g2 != g3
    assert eng._program_key("chunk_cached", g1, 4, 2) \
        != eng._program_key("chunk_cached", g2, 4, 2)
    # shapes/sampler otherwise identical: only the plan separates them
    assert g1[:-1] == g2[:-1] == g3[:-1]


def test_cached_warm_traffic_never_retraces(tiny_pipe):
    """Warm serving traffic with a FIXED plan is served entirely from
    the compiled-program cache: zero new misses on the second pass."""
    from flaxdiff_tpu.serving import SampleRequest
    from flaxdiff_tpu.telemetry import Telemetry
    plan = CachePlan()
    tel = Telemetry(enabled=False)
    sched = _sched(tiny_pipe, tel, batch_buckets=(1, 2))

    def pass_once():
        futs = [sched.submit(SampleRequest(
            resolution=8, channels=1, diffusion_steps=n, sampler="ddim",
            seed=s, use_ema=False, cache_plan=plan))
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


def test_unsupported_model_drops_plan_and_stays_bit_exact():
    """A 1-layer DiT has no deep trunk: the plan is dropped (counted),
    and the request's samples match the uncached solo run exactly."""
    from flaxdiff_tpu.serving import SampleRequest
    from flaxdiff_tpu.telemetry import Telemetry
    pipe = _pipe(num_layers=1)
    tel = Telemetry(enabled=False)
    sched = _sched(pipe, tel, batch_buckets=(1,))
    f = sched.submit(SampleRequest(
        resolution=8, channels=1, diffusion_steps=3, sampler="ddim",
        seed=5, use_ema=False, cache_plan=CachePlan()))
    sched.start()
    out = f.result(timeout=300)
    sched.close()
    solo = pipe.generate_samples(
        num_samples=1, resolution=8, channels=1, diffusion_steps=3,
        sampler="ddim", seed=5, use_ema=False)
    np.testing.assert_array_equal(out.samples, solo)
    assert tel.registry.counter("serving/cache_unsupported").value > 0
    assert tel.registry.snapshot().get("serving/cache_rows", 0) == 0
