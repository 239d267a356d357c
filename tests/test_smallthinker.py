"""The `smallthinker` denoiser trunk (models/smallthinker.py) and what it
forced in `ops/moe.py` (a router that picks on logits and weighs by the
softmax over the picked; a ReLU gate) against the plain reference
(benchmark/reference/smallthinker.py) at small sizes on the CPU."""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _benchmark_package(name):
    """`benchmark/<name>` as the top-level package the benchmark's own
    code imports it as, WITHOUT `benchmark/` on `sys.path` (see
    tests/test_cohere2_moe.py)."""
    if name not in sys.modules:
        where = os.path.join(ROOT, "benchmark", name)
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(where, "__init__.py"),
            submodule_search_locations=[where])
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])


for _name in ("reference", "harness"):
    _benchmark_package(_name)

from flaxdiff_tpu.inference import (DiffusionInferencePipeline,  # noqa: E402
                                    build_model)
from flaxdiff_tpu.models.smallthinker import (SmallThinkerBlock,  # noqa: E402
                                              visible_pairs)
from flaxdiff_tpu.ops import moe  # noqa: E402

# group 7 as published (14 query heads over 2); every expert held, as in
# the benchmark's cell; rows of 22 tokens against a window of 12, so a
# windowed layer and a full one differ; layer 1 has both bits, layer 2
# RoPE and no window, layer 3 a window and no RoPE
SMALL = dict(
    hidden_size=64, head_dim=16, num_attention_heads=14,
    num_key_value_heads=2, moe_ffn_hidden_size=24, num_hidden_layers=4,
    rope_layout=(0, 1, 1, 0), sliding_window_layout=(0, 1, 0, 1),
    sliding_window_size=12, moe_num_primary_experts=16, router_experts=16,
    first_expert=0, moe_num_active_primary_experts=3,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    rope_theta=1500000.0, rms_norm_eps=1e-6, dtype="float32", patch_size=2,
    output_channels=2)
RES, CH, TOK, FEAT = 8, 2, 5, 12
TOKENS = 1 + TOK + (RES // 2) ** 2


def _seeded(model, key=7):
    from harness import weights
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, RES, RES, CH)), jnp.zeros((1,)),
                             jnp.zeros((1, TOK, FEAT)))["params"],
        jax.random.PRNGKey(0))
    return jax.jit(lambda k: weights.fill_params(shapes, k))(
        jax.random.PRNGKey(key))


def _inputs(batch=2, key=3):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(ks[0], (batch, RES, RES, CH)),
            jnp.linspace(20.0, 900.0, batch),
            jax.random.normal(ks[1], (batch, TOK, FEAT)))


def _forward(over):
    """(program's output and tally, reference's output) at SMALL with
    `over`, on the same seeded weights."""
    from reference import smallthinker as ref
    cfg = dict(SMALL, **over)
    model = build_model("smallthinker_dn", **cfg)
    params = _seeded(model)
    x, t, text = _inputs()
    got, tally = jax.jit(lambda p: model.apply(
        {"params": p}, x, t, text, return_tally=True))(params)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.forward(
            jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p), cfg,
            x, t, text))(params)
    return model, got, tally, want


@pytest.fixture(scope="module")
def small():
    return _forward({})


# -- the model against the plain reference ---------------------------------

def test_forward_equals_the_plain_reference_in_float32(small):
    model, got, tally, want = small
    # float32 against float32: what is left is the order of the sums
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert set(tally) == set(model.tally_shapes) == {"picks", "fitted"}
    # every expert is held (`held == total`): every pick lands here and
    # one pass holds them all
    assert tally["picks"].shape == (2, 4, 16)
    np.testing.assert_array_equal(tally["picks"].sum(axis=-1),
                                  np.full((2, 4), TOKENS * 3))
    np.testing.assert_array_equal(tally["fitted"],
                                  tally["picks"].sum(axis=-1))
    added = model.tally_counters(
        jax.tree_util.tree_map(lambda a: np.asarray(a[0]), tally), 2,
        (RES, RES, CH), TOK)
    assert added["moe/picks_routed"] == 2 * TOKENS * 3 * 4
    assert added["moe/picks_held"] == added["moe/picks_fitted"] \
        == TOKENS * 3 * 4
    # 22 tokens under a window of 12 on layers 1 and 3: 12 x 13 / 2 +
    # 10 x 12 = 198 of 253 causal pairs
    assert visible_pairs(TOKENS, 12) == 198
    assert visible_pairs(TOKENS, None) == visible_pairs(TOKENS, 22) == 253
    assert added["attn/pairs_read"] == 2 * (2 * 253 + 2 * 198)
    assert added["attn/pairs_causal"] == 2 * 4 * 253


# the same four layers with one key changed (a list's bit, the window's
# size, the router's arm): the output has to move, and to follow the
# reference, which reads each list for what it names
CHANGED = {
    "window_where_rope_was_alone": dict(
        sliding_window_layout=(0, 1, 1, 1)),
    "rope_where_window_was_alone": dict(rope_layout=(0, 1, 1, 1)),
    "lists_swapped": dict(rope_layout=(0, 1, 0, 1),
                          sliding_window_layout=(0, 1, 1, 0)),
    "window_never_binds": dict(sliding_window_size=22),
    "sigmoid_of_the_picked": dict(moe_primary_router_apply_softmax=False),
}


@pytest.mark.parametrize("case", sorted(CHANGED))
def test_each_key_is_read_for_what_it_names(small, case):
    _, base, _, _ = small
    _, got, _, want = _forward(CHANGED[case])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(got - base).max()) > 1e-3, (
        "the change of configuration changed nothing")


def test_forward_in_bfloat16_stays_near_the_float32_reference():
    """Weights made in bfloat16 (the reference reads them widened),
    products in bfloat16 with float32 accumulation, the residual stream,
    the norms, the router and the softmax in float32: the gap is
    bfloat16's rounding of the products of four layers, and of a pick
    near the third logit changing sides under it (at 64 channels one
    changed pick is a large share of a token). It reads 0.044 of the
    output's mean size here; the limit leaves it twice that."""
    _, got, _, want = _forward({"dtype": "bfloat16"})
    scale = float(jnp.abs(want).mean())
    assert float(jnp.abs(got.astype(jnp.float32) - want).mean()) \
        < 0.09 * scale


def _block(**over):
    cfg = dict(
        head_dim=16, num_attention_heads=14, num_key_value_heads=2,
        moe_ffn_hidden_size=24, moe_num_primary_experts=16,
        moe_num_active_primary_experts=3,
        moe_primary_router_apply_softmax=True, norm_topk_prob=True,
        router_experts=16, first_expert=0, rms_norm_eps=1e-6,
        rope_theta=1500000.0, rope=True, window=12, dtype=jnp.float32)
    return SmallThinkerBlock(**dict(cfg, **over))


def _layer_params():
    uncut = build_model("smallthinker_dn", **dict(
        SMALL, num_hidden_layers=1, rope_layout=(1,),
        sliding_window_layout=(1,)))
    return _seeded(uncut)["layer_0"]


def test_the_router_reads_the_layers_input():
    """The picks are those of `x W_r`: not of `RMSNorm(x) W_r`, and not
    of `x' W_r` after the attention."""
    from reference import smallthinker as ref
    layer = _layer_params()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 22, 64))
    y, picks, _ = jax.jit(_block().apply)({"params": layer}, x)

    def counts(tokens):
        logits = np.asarray(tokens, np.float64).reshape(-1, 64) @ np.asarray(
            layer["router"]["kernel"], np.float64)
        idx = np.argsort(-logits, axis=-1)[:, :3].reshape(2, -1)
        return np.stack([np.bincount(i, minlength=16) for i in idx])

    np.testing.assert_array_equal(picks, counts(x))
    normed = ref._rms(x, 1e-6, layer["norm"])
    with jax.default_matmul_precision("highest"):
        after = x + ref._attention(SMALL, layer, normed, jnp.asarray(True),
                                   jnp.asarray(True))
    assert (counts(normed) != np.asarray(picks)).any()
    assert (counts(after) != np.asarray(picks)).any()
    # and the experts read RMSNorm(x'), weighted by what the router said
    # of x: the reference's layer
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref._layer(
            SMALL, p, x, jnp.asarray(True), jnp.asarray(True)))(layer)
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=2e-5)


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference():
    """16 experts in 4 shares of 4: the routed parts of the four shares
    plus everything else counted once equal the uncut reference's layer."""
    from reference import smallthinker as ref
    layer = _layer_params()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 22, 64))
    bits = (jnp.asarray(True), jnp.asarray(True))
    experts = ("experts_gate", "experts_up", "experts_down")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref._layer(SMALL, p, x, *bits))(layer)
        none = dict(layer, **{k: {"kernel": layer[k]["kernel"][:0]}
                              for k in experts})
        base = jax.jit(lambda p: ref._layer(
            dict(SMALL, moe_num_primary_experts=0), p, x, *bits))(none)
    total, picks = 0.0, []
    for share in range(4):
        block = _block(moe_num_primary_experts=4, first_expert=4 * share)
        held = dict(layer, **{
            k: {"kernel": layer[k]["kernel"][4 * share:4 * share + 4]}
            for k in experts})
        y, n, _ = jax.jit(block.apply)({"params": held}, x)
        total = total + (y - base)
        picks.append(n)
    np.testing.assert_allclose(total + base, want, atol=2e-5, rtol=2e-5)
    # every pick lands on exactly one share
    assert int(sum(p.sum() for p in picks)) == 2 * 22 * 3


# -- ops/moe.py: the router's two ways, the gate's two ---------------------

def test_route_weighs_the_picked_logits_by_their_own_softmax():
    h = jax.random.normal(jax.random.PRNGKey(1), (9, 16))
    w = jax.random.normal(jax.random.PRNGKey(2), (16, 8))
    logits = np.asarray(h, np.float64) @ np.asarray(w, np.float64)
    order = np.argsort(-logits, axis=-1)[:, :3]
    top = np.take_along_axis(logits, order, axis=-1)
    idx, vals = jax.jit(lambda: moe.route(h, w, 3, weigh="softmax_picked"))()
    np.testing.assert_array_equal(idx, order)
    soft = np.exp(top) / np.exp(top).sum(axis=-1, keepdims=True)
    np.testing.assert_allclose(vals, soft, atol=1e-6)
    np.testing.assert_allclose(vals.sum(axis=-1), 1.0, atol=1e-6)
    # the sigmoid arm picks the same experts (a sigmoid keeps the order)
    # and weighs them otherwise
    idx_s, vals_s = jax.jit(lambda: moe.route(h, w, 3, True))()
    np.testing.assert_array_equal(idx_s, order)
    sig = 1 / (1 + np.exp(-top))
    np.testing.assert_allclose(vals_s, sig / sig.sum(axis=-1, keepdims=True),
                               atol=1e-6)
    assert np.abs(np.asarray(vals) - np.asarray(vals_s)).max() > 1e-2
    scaled = jax.jit(lambda: moe.route(h, w, 3, scale=2.5,
                                       weigh="softmax_picked"))()[1]
    np.testing.assert_allclose(scaled, 2.5 * soft, atol=1e-6)
    with pytest.raises(AssertionError, match="selection bias"):
        moe.route(h, w, 3, select_bias=jnp.zeros((8,)),
                  weigh="softmax_picked")


def _experts(n=40, d=32, f=48, e=4, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    return (jax.random.normal(ks[0], (n, d)),
            jax.random.normal(ks[1], (e, d, f)) / 6,
            jax.random.normal(ks[2], (e, d, f)) / 6,
            jax.random.normal(ks[3], (e, f, d)) / 7)


def _dense(x, local, w, wg, wu, wd, act):
    y = jnp.zeros_like(x)
    for e in range(wg.shape[0]):
        g = x @ wg[e]
        g = jnp.maximum(g, 0) if act == "relu" else g * jax.nn.sigmoid(g)
        y = y + ((g * (x @ wu[e])) @ wd[e]) * jnp.sum(
            jnp.where(local == e, w, 0.0), axis=1)[:, None]
    return y


@pytest.mark.parametrize("form", ["xla", "pallas_interpret"])
def test_the_grouped_product_gates_by_relu_where_the_model_says_so(form):
    x, wg, wu, wd = _experts()
    rng = np.random.default_rng(0)
    local = jnp.asarray(rng.integers(0, 4, size=(40, 2)), jnp.int32)
    picks = moe.held_order(local, 4)
    rows, src, padded, tile_group, num_tiles = moe.dispatch(
        local, picks, 4, tile_m=8)

    def ffn(act):
        if form == "xla":
            return moe._expert_ffn_xla(x[src], wg, wu, wd, padded, act)
        return moe._expert_ffn_pallas(x[src], wg, wu, wd, tile_group,
                                      num_tiles, tile_m=8, tile_n=16,
                                      interpret=True, act=act)
    live = int(padded.sum())
    xs, group = np.asarray(x[src]), np.asarray(tile_group)
    got = {act: np.asarray(jax.jit(ffn, static_argnums=0)(act))[:live]
           for act in ("relu", "silu")}
    for row in range(0, live, 5):
        e = int(group[row // 8])
        g = xs[row] @ np.asarray(wg[e])
        up = xs[row] @ np.asarray(wu[e])
        np.testing.assert_allclose(
            got["relu"][row], (np.maximum(g, 0) * up) @ np.asarray(wd[e]),
            atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(
            got["silu"][row], (g / (1 + np.exp(-g)) * up) @ np.asarray(wd[e]),
            atol=2e-5, rtol=2e-5)
    assert np.abs(got["relu"] - got["silu"]).max() > 1e-2


def test_routed_experts_with_a_relu_gate_and_its_gradient():
    """Every expert held (`held == total`: one pass holds every pick),
    the forward and the backward's composition gated by relu."""
    x, wg, wu, wd = _experts()
    rng = np.random.default_rng(1)
    local = jnp.asarray(rng.integers(0, 4, size=(40, 2)), jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(4), (40, 2))
    assert moe.capacity(80, 4, 4) == 80

    def routed(x, wg, wu, wd, act):
        return moe.routed_experts(x, local, w, wg, wu, wd, 4, act)
    for act in ("relu", "silu"):
        got, fitted = jax.jit(routed, static_argnums=4)(x, wg, wu, wd, act)
        np.testing.assert_allclose(
            got, _dense(x, local, w, wg, wu, wd, act), atol=5e-5, rtol=5e-5)
        assert int(fitted.sum()) == 80
    silu = jax.jit(lambda *a: moe.routed_experts(
        a[0], local, w, *a[1:], 4))(x, wg, wu, wd)[0]
    np.testing.assert_array_equal(silu, got)     # the default is silu's
    grads = jax.jit(jax.grad(lambda *a: (routed(*a, "relu")[0] ** 2).sum(),
                             argnums=(0, 1, 2, 3)))(x, wg, wu, wd)
    wants = jax.jit(jax.grad(
        lambda *a: (_dense(a[0], local, w, *a[1:], "relu") ** 2).sum(),
        argnums=(0, 1, 2, 3)))(x, wg, wu, wd)
    for g, want in zip(grads, wants):
        np.testing.assert_allclose(g, want, atol=1e-4, rtol=1e-4)


# -- serving: rounds of one row, the counters ------------------------------

def test_a_served_request_rides_one_round_of_one_row_and_repeats_itself():
    from flaxdiff_tpu.inputs import (ConditionalInputConfig,
                                     DiffusionInputConfig)
    from flaxdiff_tpu.serving import (SampleRequest, SchedulerConfig,
                                      ServingScheduler)
    from flaxdiff_tpu.telemetry import Telemetry
    from harness.serving import SeededContextEncoder
    from reference import sample, smallthinker as ref

    model = build_model("smallthinker_dn", **SMALL)
    params = _seeded(model)
    null_ctx = 0.5 * np.random.default_rng(1).standard_normal(
        (1, TOK, FEAT)).astype(np.float32)
    pipe = DiffusionInferencePipeline.from_config(
        {"model": dict(SMALL, name="smallthinker_dn"),
         "schedule": {"name": "cosine", "timesteps": 1000},
         "predictor": "v"}, params={"params": params})
    pipe.input_config = DiffusionInputConfig(
        sample_data_key="sample", sample_data_shape=(RES, RES, CH),
        conditions=[ConditionalInputConfig(
            encoder=SeededContextEncoder(null_ctx))])
    assert pipe.model.serve_rows_apart
    assert pipe.get_sampler("ddim", 3.0).tally_shape == {
        "picks": (4, 16), "fitted": (4,)}
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(pipeline=pipe, telemetry=tel,
                             config=SchedulerConfig())
    cond = np.random.default_rng(2).standard_normal(
        (1, TOK, FEAT)).astype(np.float32)

    def request(nfe):
        return SampleRequest(num_samples=1, resolution=RES, channels=CH,
                             diffusion_steps=nfe, sampler="ddim",
                             guidance_scale=3.0, seed=11 + nfe,
                             conditioning=cond)
    first, other = [f.result(timeout=600) for f in
                    [sched.submit(request(nfe)) for nfe in (3, 2)]]
    again = sched.submit(request(3)).result(timeout=600)
    sched.close(drain=True)
    assert sched.batch_buckets == (1,)
    assert [r.rounds for r in (first, other, again)] == [1, 1, 1]
    assert tel.counter("serving/rounds").value \
        == tel.counter("serving/rows_real").value == 3
    assert tel.counter("serving/rows_padded").value == 0
    # served twice, the second time alone: equal to the last bit
    np.testing.assert_array_equal(first.samples, again.samples)
    # a wider round of this model is launches of the one-row program: no
    # padded slot is evaluated, no wider program is compiled, and each row
    # is what it is alone, to the last bit
    from flaxdiff_tpu.serving.request import ServingFuture
    eng = sched.engine
    rounds = {k for k in eng._programs if "chunk" in str(k)}
    rows = [eng.prepare(request(nfe), ServingFuture(), 0.0, 0.0)
            for nfe in (3, 2, 3)]
    live, ended = rows, []
    while live:
        ended += eng.advance(live, 4, 8)[0]
        live = [r for r in live if r.remaining > 0]
    assert {k for k in eng._programs if "chunk" in str(k)} == rounds
    assert [r.rounds for r in rows] == [1, 1, 1] and ended == rows
    wide = np.asarray(eng.finalize(ended, 4)[0])
    np.testing.assert_array_equal(wide[0], first.samples)
    np.testing.assert_array_equal(wide[1], other.samples)
    np.testing.assert_array_equal(wide[2], first.samples)
    forward = jax.jit(lambda p, *a: ref.forward(p, SMALL, *a))
    want = sample.serve(
        lambda p, cfg, *a: forward(p, *a), SMALL, params,
        {"seed": 14, "nfe": 3, "guidance": 3.0, "shape": (1, RES, RES, CH),
         "cond": cond, "uncond": null_ctx}, 1000, predictor="v")
    np.testing.assert_allclose(first.samples, want, atol=5e-4)
    evals = (4 + 3 + 4) * 2
    assert tel.counter("attn/pairs_read").value \
        == evals * (2 * 253 + 2 * 198)
    assert tel.counter("attn/pairs_causal").value == evals * 4 * 253
    assert tel.counter("moe/picks_routed").value \
        == tel.counter("moe/picks_held").value \
        == tel.counter("moe/picks_fitted").value == evals * TOKENS * 3 * 4
