"""A row's last turn of the round program is its terminal denoise
(ISSUE 39; samplers/common.py `make_chunk_program`, serving/engine.py).

What holds, on the CPU and to the last bit: a request's `nfe + 1`
evaluations are all turns of the round programs (no other program of
the engine holds the network), its samples are the solo scan's whatever
turn of whatever round its terminal denoise falls on, a counting model
is charged exactly one evaluation for that turn, a cache plan schedules
it as a refresh, and what the benchmark counts from the spans is what
the network was given.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.inference import DiffusionInferencePipeline, build_model
from flaxdiff_tpu.inputs import ConditionalInputConfig, DiffusionInputConfig
from flaxdiff_tpu.inputs.encoders import HashTextEncoder
from flaxdiff_tpu.predictors import EpsilonPredictionTransform
from flaxdiff_tpu.schedulers import CosineNoiseSchedule
from flaxdiff_tpu.serving import (SampleRequest, SchedulerConfig,
                                  ServingFuture, ServingScheduler)
from flaxdiff_tpu.serving.engine import SamplerProgramEngine
from flaxdiff_tpu.telemetry import Telemetry

KW = {"emb_features": 32, "num_heads": 4, "patch_size": 4,
      "output_channels": 1}


def _pipe(num_layers):
    """The tiny epsilon model on which batched equals solo to the bit
    (ROADMAP D9); with three blocks the cache plans can split it."""
    model = build_model("simple_dit", num_layers=num_layers, **KW)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)), jnp.zeros((1,)),
        None)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    params = jax.tree_util.tree_unflatten(
        treedef, [l + 0.05 * jax.random.normal(k, l.shape, l.dtype)
                  for l, k in zip(leaves, keys)])
    return DiffusionInferencePipeline.from_config(
        {"model": dict(KW, name="simple_dit", num_layers=num_layers),
         "schedule": {"name": "cosine", "timesteps": 100},
         "predictor": "epsilon"}, params=params)


@pytest.fixture(scope="module")
def tiny_pipe():
    return _pipe(1)


@pytest.fixture(scope="module")
def deep_pipe():
    return _pipe(3)


def _request(nfe, seed, sampler="ddim", **kw):
    return SampleRequest(resolution=8, channels=1, diffusion_steps=nfe,
                         sampler=sampler, seed=seed, use_ema=False, **kw)


def _serve(pipe, reqs, tel=None, **cfg):
    sched = ServingScheduler(
        pipeline=pipe, telemetry=tel or Telemetry(enabled=False),
        autostart=False, config=SchedulerConfig(**cfg))
    futs = [sched.submit(r) for r in reqs]
    sched.start()
    outs = [f.result(timeout=600) for f in futs]
    sched.close()
    return sched, outs


def _solo(pipe, r):
    return pipe.generate_samples(
        num_samples=r.num_samples, resolution=8, channels=1,
        diffusion_steps=r.diffusion_steps, sampler=r.sampler, seed=r.seed,
        use_ema=False, cache_plan=r.cache_plan)


# -- every sampler, terminal turns on different turns of one round ----------

@pytest.mark.parametrize("round_steps", [0, 2], ids=["one-round", "rs2"])
@pytest.mark.parametrize("sampler", ["ddim", "euler_ancestral", "heun",
                                     "multistep_dpm"])
def test_each_sampler_equals_its_solo_run(tiny_pipe, sampler, round_steps):
    """Rows of NFE 2, 3 and 5 in one bucket of 4 (a padding slot beside
    them). Run to completion, their terminal denoises are turns 2, 3
    and 5 of ONE round, each at the row's own terminal value; in rounds
    of at most 2 turns they fall in different rounds, the 2-step row's
    in a round of its own length 1. Either way the samples are the solo
    scan's to the last bit: the first evaluation of the sampler's step
    on that turn is the terminal denoise, and what a second-order or
    multistep sampler makes of the zero-length step is dropped."""
    tel = Telemetry(enabled=False)
    reqs = [_request(n, 30 + n, sampler) for n in (2, 3, 5)]
    sched, outs = _serve(tiny_pipe, reqs, tel, round_steps=round_steps,
                         batch_buckets=(4,))
    for r, o in zip(reqs, outs):
        np.testing.assert_array_equal(o.samples, _solo(tiny_pipe, r))
    snap = tel.registry.snapshot()
    assert snap["serving/terminal_turns"] == 3
    assert snap["serving/row_steps_live"] == 3 + 4 + 6
    assert snap["serving/rows_padded"] > 0
    if round_steps == 0:
        assert snap["serving/rounds"] == 1
        assert sched.engine.last_round_info["n_act"] == [3, 4, 6]
    else:
        assert [o.rounds for o in outs] == [2, 3, 4]
    # no program but the round program holds the network
    assert {k[0] for k in sched.engine._programs} \
        == {"init", "noise", "chunk", "handoff"}


def test_terminal_values_of_different_nfe_are_each_rows_own(tiny_pipe):
    """A row's last pair is `(t_term, t_term)` from ITS spacing; the
    quadratic spacing's terminal values differ by NFE in the last bits,
    so a shared terminal value would show."""
    engine = SamplerProgramEngine(tiny_pipe,
                                  telemetry=Telemetry(enabled=False))
    rows = [engine.prepare(_request(n, n), ServingFuture(), 0.0, 0.0)
            for n in (2, 7)]
    ds = engine._sampler_for(rows[0].req)
    for r in rows:
        assert r.pairs.shape == (r.nfe + 1, 2) and r.remaining == r.nfe + 1
        want = np.asarray(ds.trajectory_inputs(r.nfe))
        np.testing.assert_array_equal(r.pairs, want)
        assert r.pairs[-1, 0] == r.pairs[-1, 1] == r.pairs[-2, 1]
        np.testing.assert_array_equal(r.pairs[:-1, 1], r.pairs[1:, 0])


# -- the cache plans: the terminal turn is a refresh -------------------------

def _plan_of(kind):
    from flaxdiff_tpu.ops.diffcache import CachePlan
    from flaxdiff_tpu.ops.spatialcache import ComposedPlan, SpatialPlan
    cache = CachePlan(refresh_every=4, refresh_head=1, refresh_tail=0)
    return cache if kind == "chunk_cached" else ComposedPlan(
        cache=cache, spatial=SpatialPlan(keep_fraction=0.5))


@pytest.mark.parametrize("kind", ["chunk_cached", "chunk_spatial"])
def test_a_cached_plans_terminal_turn_is_a_refresh(deep_pipe, kind):
    """A plan with no refresh tail reuses on a row's last steps; the
    row's host-side schedule still gains one entry, a refresh, at index
    `nfe`, so the round that holds its terminal turn evaluates in full
    (as the solo scan's terminal denoise does) and a round-mate on that
    turn is granted the refresh too. Alone in its rounds, the request's
    samples are the cached solo scan's to the last bit."""
    top = {"chunk_cached": 1, "chunk_spatial": 2}[kind]
    plan = _plan_of(kind)
    engine = SamplerProgramEngine(deep_pipe,
                                  telemetry=Telemetry(enabled=False))
    row, mate = (engine.prepare(_request(n, 5, cache_plan=plan),
                                ServingFuture(), 0.0, 0.0) for n in (6, 11))
    sched = row.flags if kind == "chunk_cached" else row.codes
    assert len(sched) == 7 and int(sched[-1]) == top
    assert int(sched[-2]) != top            # the plan's own last step
    codes = []
    while row.remaining:
        engine.advance([row, mate], 2, 4)
        codes += engine.last_round_info["codes"][
            :engine.last_round_info["steps"]]
        assert engine.last_round_info["kind"] == kind
    # turn 6 is the row's terminal denoise: a refresh for the round,
    # where the mate's own schedule (11 steps: 0, 4, 8) says reuse
    assert len(codes) == 7 and codes[6] == top
    mate_sched = mate.flags if kind == "chunk_cached" else mate.codes
    assert int(mate_sched[6]) != top
    req = _request(6, 5, cache_plan=plan)
    _, (out,) = _serve(deep_pipe, [req], round_steps=4, batch_buckets=(1,))
    np.testing.assert_array_equal(out.samples, _solo(deep_pipe, req))


# -- a counting model: one evaluation for the terminal turn ------------------

class CountingModel:
    """A model that counts the rows it is given, as the routed model
    counts its held picks: one 'pick' a row of every evaluation."""
    tally_shapes = {"picks": (1, 1)}

    def apply(self, params, x, t, cond, return_tally=False):
        raw = x * params["w"] + 0.01 * jnp.mean(cond, axis=(1, 2))[
            :, None, None, None]
        if return_tally:
            return raw, {"picks": jnp.ones((x.shape[0], 1, 1), jnp.int32)}
        return raw

    def tally_counters(self, tally, evaluations, sample_shape, cond_tokens):
        from flaxdiff_tpu.ops.moe import pick_counters
        return pick_counters(tally["picks"], evaluations,
                             tally["picks"].sum(axis=-1))


@pytest.fixture(scope="module")
def counting_pipe():
    enc = HashTextEncoder.create(features=16, max_length=8)
    pipe = DiffusionInferencePipeline(
        model=CountingModel(), params={"w": jnp.float32(0.5)},
        ema_params=None, schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(),
        input_config=DiffusionInputConfig(
            sample_data_key="sample", sample_data_shape=(8, 8, 1),
            conditions=[ConditionalInputConfig(encoder=enc)]))
    return pipe


@pytest.mark.parametrize("sampler,guidance,per_step", [
    ("ddim", 2.0, 1), ("ddim", 0.0, 1), ("heun", 2.0, 2)],
    ids=["ddim-guided", "ddim-unguided", "heun-guided"])
def test_a_counting_models_tally_is_the_rows_evaluations(
        counting_pipe, sampler, guidance, per_step):
    """The tally a row carries out of its rounds is the rows the network
    was given for it: `per_step` evaluations a step and exactly ONE for
    the terminal turn (Heun's second evaluation on that turn is a lane
    of the batched call and is not charged), twice under guidance.
    `count_tally`'s `(nfe + 1)` evaluations a row are therefore exact
    for a one-evaluation sampler."""
    tel = Telemetry(enabled=False)
    nfes = (2, 3, 4, 3, 2)
    reqs = [_request(n, 40 + i, sampler, guidance_scale=guidance,
                     prompts=[f"p{i}"]) for i, n in enumerate(nfes)]
    _serve(counting_pipe, reqs, tel, round_steps=8, batch_buckets=(1, 2, 4))
    twice = 2 if guidance > 0 else 1
    held = tel.counter("moe/picks_held").value
    assert held == twice * sum(per_step * n + 1 for n in nfes)
    assert tel.counter("moe/picks_routed").value \
        == twice * sum(n + 1 for n in nfes)
    assert tel.counter("serving/terminal_turns").value == len(nfes)


def test_the_benchmarks_count_of_evaluations_is_the_networks(
        counting_pipe, tmp_path):
    """Ties the count `benchmark/harness/layer_metrics.py` `_evaluations`
    makes from a traced window's spans to the network's own calls (the
    benchmark's files are not this repo's tests' to import, so the rule
    is written out): over the dispatch thread's spans,

        per guided request twice:  live x sum(rows x steps of every
        `serve.round`)  +  sum(rows of every `serve.finalize`)

    with `live` the `serving/row_steps_live` over `serving/row_steps_run`
    counters. A mixed deal of NFE 2/3/4, guided, over 8 slots with
    padded rounds at the tail, served by a model that counts the rows it
    is given: the rule's count equals the evaluations the real rows
    received, and `serving/terminal_turns` the requests finished.

    It fails on a tree that folds the terminal denoise into the round
    and still opens a `serve.finalize` span around the hand-off (every
    request counted `nfe + 2` times). On the parent of ISSUE 39 the
    rule's count passes by the other term: rounds of `nfe` steps plus
    one `serve.finalize` row a request (the counter
    `serving/terminal_turns` is this PR's: the parent has none)."""
    tel = Telemetry.create(str(tmp_path))
    nfes = (2, 3, 4, 3, 2, 4, 2, 3, 4, 2, 3)
    reqs = [_request(n, 60 + i, guidance_scale=3.0, prompts=[f"q{i}"])
            for i, n in enumerate(nfes)]
    _serve(counting_pipe, reqs, tel, round_steps=8,
           batch_buckets=(1, 2, 4, 8))
    tel.close()
    events = json.load(open(tmp_path / "trace.json"))["traceEvents"]
    rounds = [e for e in events if e.get("name") == "serve.round"]
    (thread,) = {e["tid"] for e in rounds}
    finalised = [e for e in events if e.get("name") == "serve.finalize"
                 and e["tid"] == thread]
    count = tel.counter
    live = count("serving/row_steps_live").value \
        / count("serving/row_steps_run").value
    by_rule = 2 * (
        live * sum(e["args"]["rows"] * e["args"]["steps"] for e in rounds)
        + sum(e["args"]["rows"] for e in finalised))
    given = count("moe/picks_held").value   # rows the network was given
    assert by_rule == given == 2 * sum(n + 1 for n in nfes)
    assert count("serving/terminal_turns").value \
        == count("serving/requests_ok").value == len(nfes)
    assert max(e["args"]["bucket"] for e in rounds) == 8
    assert count("serving/rows_padded").value > 0
    # what is left after a row's last turn is timed under its own name
    handed = [e for e in events if e.get("name") == "serve.handoff"]
    assert sum(e["args"]["rows"] for e in handed) == len(nfes)
