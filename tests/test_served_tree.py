"""The tree a serving engine holds (docs/SERVING.md "What the engine
holds"): each leaf at the dtype the network converts it to, wherever
that conversion is the leaf's only use; read from the program
(`DiffusionSampler.narrowing`), made once (`SamplerProgramEngine.
_params_for`), and the same arithmetic as the pipeline's own tree."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.inference import DiffusionInferencePipeline, build_model
from flaxdiff_tpu.predictors import EpsilonPredictionTransform
from flaxdiff_tpu.samplers import DDIMSampler
from flaxdiff_tpu.samplers.common import DiffusionSampler, narrow_tree
from flaxdiff_tpu.schedulers import CosineNoiseSchedule
from flaxdiff_tpu.serving import SampleRequest, ServingFuture
from flaxdiff_tpu.serving.engine import SamplerProgramEngine
from flaxdiff_tpu.telemetry import Telemetry
from tests.test_serving import _plan_of

BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
# what SimpleDiT computes in float32 whatever its `dtype`: the timestep
# embedding's two products and the head (norm and projection)
READ_AT_F32 = ("['cond']['t_proj']", "['final_norm']", "['final_proj']")


def _dit(dtype, layers=3):
    """A perturbed tiny SimpleDiT (an AdaLN-Zero block is an identity at
    init) held in float32, v-prediction: every step shows in a sample."""
    kw = {"emb_features": 32, "num_heads": 4, "num_layers": layers,
          "patch_size": 4, "output_channels": 1}
    if dtype is not None:
        kw["dtype"] = dtype
    params = jax.jit(build_model("simple_dit", **kw).init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)), jnp.zeros((1,)),
        None)

    @jax.jit
    def perturbed(params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
        return treedef.unflatten(
            [l + 0.05 * jax.random.normal(k, l.shape, l.dtype)
             for l, k in zip(leaves, keys)])

    params = perturbed(params)
    return DiffusionInferencePipeline.from_config(
        {"model": dict(kw, name="simple_dit"),
         "schedule": {"name": "cosine", "timesteps": 100},
         "predictor": "v"}, params=params)


@pytest.fixture(scope="module")
def bf16_pipe():
    return _dit("bfloat16")


def _engine(pipe):
    return SamplerProgramEngine(pipe, telemetry=Telemetry(enabled=False))


def _request(**kw):
    return SampleRequest(**{"resolution": 8, "channels": 1,
                            "diffusion_steps": 20,
                            "sampler": "euler_ancestral", "seed": 5,
                            "use_ema": False, **kw})


def _serve(engine, req, round_steps=8):
    row = engine.prepare(req, ServingFuture(), 0.0, 0.0)
    while row.remaining > 0:
        engine.advance([row], 1, round_steps)
    out, _ = engine.finalize([row], 1)
    return np.asarray(out[0]), row


def _served_tree(engine, row):
    return engine._params_for(
        row.group, engine._sampler_for(row.req), row.x, row.cond, row.uncond)


# -- the rule, on real models -------------------------------------------------

def test_a_bf16_dit_is_served_narrow_but_for_the_leaves_it_reads_at_f32(
        bf16_pipe):
    engine = _engine(bf16_pipe)
    _, row = _serve(engine, _request(diffusion_steps=2))
    served = _served_tree(engine, row)
    own = jax.tree_util.tree_leaves_with_path(bf16_pipe.params)
    kept = 0
    for (path, leaf), got in zip(own, jax.tree_util.tree_leaves(served)):
        name = jax.tree_util.keystr(path)
        if any(part in name for part in READ_AT_F32):
            assert got is leaf, name            # the SAME array, no copy
            kept += 1
        else:
            assert got.dtype == BF16 and leaf.dtype == F32, name
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(leaf.astype(jnp.bfloat16)))
    assert kept == 8
    # the pipeline's own tree is untouched
    assert all(l.dtype == F32 for _, l in own)
    tel = engine.telemetry
    nbytes = [l.size * 4 for _, l in own]
    assert tel.gauge("serving/served_tree_bytes").value == sum(nbytes)
    assert tel.gauge("serving/served_tree_narrowed_bytes").value == sum(
        n for n, got in zip(nbytes, jax.tree_util.tree_leaves(served))
        if got.dtype == BF16)


def _cohere_bf16():
    small = dict(
        hidden_size=64, head_dim=16, num_attention_heads=8,
        num_key_value_heads=2, intermediate_size=48, num_hidden_layers=2,
        layer_types=("sliding_attention", "full_attention"),
        sliding_window=40, num_experts=4, router_experts=16, first_expert=4,
        num_experts_per_tok=3, num_shared_experts=2, rope_theta=50000,
        layer_norm_eps=1e-5, norm_topk_prob=True, dtype="bfloat16",
        patch_size=2, output_channels=2)
    cond = jnp.zeros((1, 5, 12))
    params = jax.jit(build_model("cohere2_moe_dn", **small).init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 2)), jnp.zeros((1,)),
        cond)
    pipe = DiffusionInferencePipeline.from_config(
        {"model": dict(small, name="cohere2_moe_dn"),
         "schedule": {"name": "cosine", "timesteps": 1000},
         "predictor": "v"},
        params=jax.jit(lambda p: jax.tree_util.tree_map(
            lambda l: l.astype(jnp.bfloat16), p))(params))
    return pipe, jnp.zeros((1, 8, 8, 2)), cond


def _dit_f32():
    return _dit(None, layers=1), jnp.zeros((1, 8, 8, 1)), None


@pytest.mark.parametrize("make", [_cohere_bf16, _dit_f32],
                         ids=["cohere2_moe_dn-held-in-bf16", "f32-model"])
def test_a_tree_with_nothing_to_narrow_is_served_as_it_is(make):
    pipe, x, cond = make()
    engine = _engine(pipe)
    ds = pipe.get_sampler("ddim", 3.0 if cond is not None else 0.0)
    group = engine.group_key(_request())
    served = engine._params_for(group, ds, x, cond, cond)
    assert served is pipe.params                # no second copy
    assert engine.telemetry.gauge(
        "serving/served_tree_narrowed_bytes").value == 0


# -- the rule, on synthetic programs -----------------------------------------

def _narrowing_of(model_fn, params, x=jnp.ones((2, 4, 4))):
    ds = DiffusionSampler(
        model_fn=model_fn, schedule=CosineNoiseSchedule(100),
        transform=EpsilonPredictionTransform(), sampler=DDIMSampler())
    return ds.narrowing(params, x, None, None)


def _bf16(w):
    return w.astype(jnp.bfloat16)


def _once(p, x, t, c):
    return (x.astype(jnp.bfloat16) @ _bf16(p["w"])).astype(x.dtype)


def _twice_one_dtype(p, x, t, c):
    return _once(p, x, t, c) + (x.astype(jnp.bfloat16)
                                * _bf16(p["w"])[0]).astype(x.dtype)


def _two_dtypes(p, x, t, c):
    return _once(p, x, t, c) + x * p["w"].astype(jnp.float16)[0]


def _also_at_f32(p, x, t, c):
    return _once(p, x, t, c) + x @ p["w"]


def _through_nested_jits(p, x, t, c):
    inner = jax.jit(lambda q, y: y.astype(jnp.bfloat16) @ _bf16(q["w"]))
    return jax.jit(lambda q, y: inner(q, y))(p, x).astype(x.dtype)


def _nested_jit_reads_f32(p, x, t, c):
    return _once(p, x, t, c) + jax.jit(lambda q, y: y @ q["w"])(p, x)


def _through_remat_and_custom_jvp(p, x, t, c):
    @jax.custom_jvp
    def f(w, y):
        return y.astype(jnp.bfloat16) @ _bf16(w)
    f.defjvp(lambda primals, tangents: (f(*primals), f(*primals)))
    return jax.checkpoint(f)(p["w"], x).astype(x.dtype)


def _inside_a_scan(p, x, t, c):
    def step(y, _):
        return (y.astype(jnp.bfloat16) @ _bf16(p["w"])).astype(y.dtype), ()
    return jax.lax.scan(step, x, None, length=2)[0]


def _inside_a_cond(p, x, t, c):
    return jax.lax.cond(t[0] > 0, lambda: _once(p, x, t, c), lambda: x)


def _transposed_first(p, x, t, c):
    return (x.astype(jnp.bfloat16) @ _bf16(p["w"].T)).astype(x.dtype)


def _widened(p, x, t, c):
    return x @ p["w"].astype(jnp.float32)


def _returned(p, x, t, c):
    return _once(p, x, t, c) + p["w"][None]


def _unused(p, x, t, c):
    return x


def _asks_the_dtype_in_python(p, x, t, c):
    if p["w"].dtype == jnp.float32:
        return _once(p, x, t, c)
    return 2 * _once(p, x, t, c)        # no equation shows the question


@pytest.mark.parametrize("model_fn,stored,want", [
    (_once, F32, BF16),
    (_twice_one_dtype, F32, BF16),
    (_through_nested_jits, F32, BF16),
    (_through_remat_and_custom_jvp, F32, BF16),
    (_two_dtypes, F32, None),
    (_also_at_f32, F32, None),
    (_nested_jit_reads_f32, F32, None),
    (_inside_a_scan, F32, None),
    (_inside_a_cond, F32, None),
    (_transposed_first, F32, None),
    (_widened, BF16, None),
    (_once, BF16, None),
    (_returned, F32, None),
    (_unused, F32, None),
    (_asks_the_dtype_in_python, F32, None),
], ids=lambda v: getattr(v, "__name__", str(v)).lstrip("_"))
def test_a_leaf_narrows_iff_every_read_converts_it_to_one_narrower_dtype(
        model_fn, stored, want):
    params = {"w": jnp.ones((4, 4), stored), "b": jnp.ones((4,), F32)}
    got = dict(zip(("b", "w"), _narrowing_of(model_fn, params)))
    assert got["w"] == want
    assert got["b"] is None                     # never read: as it is
    # and the narrowed tree computes what the stored one does
    x, t = jnp.linspace(-1, 1, 32).reshape(2, 4, 4), jnp.ones((2,))
    np.testing.assert_array_equal(
        model_fn(narrow_tree(params, (None, got["w"])), x, t, None),
        model_fn(params, x, t, None))


def test_no_leaf_is_stored_narrower_than_its_narrowest_read():
    """Two leaves, one model: each leaf's own reads decide for it."""
    def model_fn(p, x, t, c):
        y = x.astype(jnp.bfloat16) @ _bf16(p["a"])
        return y.astype(jnp.float32) @ p["b"]

    params = {"a": jnp.ones((4, 4)), "b": jnp.ones((4, 4))}
    assert _narrowing_of(model_fn, params) == (BF16, None)
    served = narrow_tree(params, (BF16, None))
    assert served["a"].dtype == BF16 and served["b"] is params["b"]
    assert narrow_tree(params, (None, None)) is params


# -- the programs that take it ------------------------------------------------

def _converted_inputs(jaxpr, leaf_of, found):
    """The leaves (by index, through `leaf_of`: variable -> index) that
    some `convert_element_type` anywhere in the nest takes: followed
    into calls, into a scan (operands and the body's inputs line up one
    for one) and into a cond's branches (which lack the index)."""
    from flaxdiff_tpu.profiling import _iter_subjaxprs
    for eqn in jaxpr.eqns:
        held = {pos: leaf_of[v] for pos, v in enumerate(eqn.invars)
                if not isinstance(v, jax.extend.core.Literal)
                and v in leaf_of}
        if eqn.primitive.name == "convert_element_type":
            found.update(held.values())
        shift = 1 if eqn.primitive.name == "cond" else 0
        for sub in _iter_subjaxprs(eqn.params):
            if len(sub.invars) == len(eqn.invars) - shift:
                _converted_inputs(
                    sub, {sub.invars[pos - shift]: leaf
                          for pos, leaf in held.items() if pos >= shift},
                    found)
    return found


def test_the_round_program_converts_no_narrowed_leaf(bf16_pipe):
    """Given the served tree, the bucket's round program holds no
    `convert_element_type` of a parameter input that was narrowed: the
    cast is not paid per round. (Given the pipeline's tree it holds one
    for each: the parent's program.)"""
    from flaxdiff_tpu.serving.engine import _round_program
    engine = _engine(bf16_pipe)
    row = engine.prepare(_request(), ServingFuture(), 0.0, 0.0)
    ds = engine._sampler_for(row.req)
    served = _served_tree(engine, row)
    program = _round_program(ds.make_chunk_program(8))
    rows = ({"x": row.x, "keys": row.rng, "state": row.state,
             "cond": row.cond, "uncond": row.uncond},)
    batch = {"pairs": np.zeros((1, 8, 2), np.float32),
             "n_act": np.ones((1,), np.int32),
             "offsets": np.zeros((1,), np.int32), "steps": np.int32(1)}
    n = len(jax.tree_util.tree_leaves(served))
    narrowed = [i for i, l in enumerate(jax.tree_util.tree_leaves(served))
                if l.dtype == BF16]
    assert len(narrowed) == n - 8

    def converted(tree):
        jaxpr = jax.make_jaxpr(program)(tree, rows, batch).jaxpr
        return sorted(_converted_inputs(
            jaxpr, {v: i for i, v in enumerate(jaxpr.invars[:n])}, set()))

    assert converted(served) == []
    assert converted(bf16_pipe.params) == narrowed


@pytest.mark.parametrize("kind", ["chunk", "chunk_cached", "chunk_spatial"])
def test_served_samples_are_those_of_the_pipelines_own_tree(
        bf16_pipe, kind, monkeypatch):
    """Through every kind of round program, the served tree gives the
    samples the pipeline's float32 tree gives through the same
    programs, to the last bit: the same converts, made once. Against
    the solo scan on the float32 tree the gap is the one a bfloat16
    model's batched and solo programs have had all along on the CPU
    (XLA fuses them differently: ROADMAP D9), narrowed or not."""
    req = _request(cache_plan=_plan_of(kind))
    engine = _engine(bf16_pipe)
    got, row = _serve(engine, req)
    assert engine.last_round_info["kind"] == kind
    assert any(l.dtype == BF16 for l in jax.tree_util.tree_leaves(
        _served_tree(engine, row)))
    monkeypatch.setattr(
        DiffusionSampler, "narrowing",
        lambda self, params, *row: (None,) * len(
            jax.tree_util.tree_leaves(params)))
    plain = _engine(bf16_pipe)
    want, row = _serve(plain, req)
    assert _served_tree(plain, row) is bf16_pipe.params
    np.testing.assert_array_equal(got, want)
    assert (np.abs(got) < 1.0).mean() > 0.5         # not saturated
    solo = bf16_pipe.generate_samples(
        num_samples=1, resolution=8, channels=1, diffusion_steps=20,
        sampler="euler_ancestral", seed=5, use_ema=False,
        cache_plan=_plan_of(kind))
    assert np.abs(got - solo).max() < 5e-3


# -- the hold -----------------------------------------------------------------

def test_one_served_tree_a_tree_and_a_new_one_when_the_tree_is_replaced():
    pipe = _dit("bfloat16", layers=1)
    pipe.ema_params = jax.tree_util.tree_map(lambda l: l * 1.5, pipe.params)
    engine = _engine(pipe)
    built = engine.telemetry.counter("serving/served_trees")
    ema = _request(use_ema=True, diffusion_steps=4)

    first, row = _serve(engine, ema)
    assert built.value == 1
    held = _served_tree(engine, row)
    # rounds, terminals and requests after the first leave it alone
    again, row = _serve(engine, ema)
    assert built.value == 1 and _served_tree(engine, row) is held
    np.testing.assert_array_equal(again, first)
    # another sampler's group reads the tree alike: the same served tree
    _, other = _serve(engine, _request(use_ema=True, diffusion_steps=4,
                                       sampler="ddim"))
    assert other.group != row.group
    assert built.value == 1 and _served_tree(engine, other) is held
    # the raw tree is a tree of its own
    raw, _ = _serve(engine, _request(diffusion_steps=4))
    assert built.value == 2
    assert np.abs(raw - first).max() > 1e-3

    # replace the EMA tree: the next round serves the new one
    old_leaves = jax.tree_util.tree_leaves(held)
    pipe.ema_params = jax.tree_util.tree_map(lambda l: l * 0.5, pipe.params)
    moved, row = _serve(engine, ema)
    assert built.value == 3
    new = _served_tree(engine, row)
    assert new is not held and built.value == 3
    assert np.abs(moved - first).max() > 1e-3
    # ... and the old one is dropped, in every group that held it
    assert not any(s is held for _, by_group, by_narrowing
                   in engine._served.values()
                   for s in (*by_group.values(), *by_narrowing.values()))
    assert all(a is not b for a, b in zip(
        old_leaves, jax.tree_util.tree_leaves(new)))
    # what the pipeline holds stayed float32 all along
    for tree in (pipe.params, pipe.ema_params):
        assert all(l.dtype == F32 for l in jax.tree_util.tree_leaves(tree))


def test_prewarm_builds_the_served_tree_before_admission(bf16_pipe):
    engine = _engine(bf16_pipe)
    built = engine.telemetry.counter("serving/served_trees")
    engine.prewarm([_request(diffusion_steps=3)], 8, (1, 2))
    assert built.value == 1
    _serve(engine, _request(diffusion_steps=3, seed=9))
    assert built.value == 1             # 0 inside any window after it
