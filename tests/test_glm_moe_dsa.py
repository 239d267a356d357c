"""The `glm_moe_dsa` denoiser trunk (models/glm_moe_dsa.py): latent
attention over keys a learned indexer selects (ops/dsa.py), shared
between layers, beside bias-corrected sigmoid routing; against the plain
reference (benchmark/reference/glm_moe_dsa.py) at small sizes on the
CPU."""
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
    code imports it as, WITHOUT `benchmark/` on `sys.path` (its `tests`
    package would shadow this directory)."""
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
from flaxdiff_tpu.models.glm_moe_dsa import (GlmMoeDsaBlock,  # noqa: E402
                                             published_indexer_type)
from flaxdiff_tpu.ops import dsa  # noqa: E402

ROPE = {"rope_theta": 8000000, "rope_type": "default"}
SMALL = dict(
    hidden_size=64, head_dim=12, qk_nope_head_dim=12, qk_rope_head_dim=4,
    qk_head_dim=16, v_head_dim=16, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=24,
    index_n_heads=16, index_head_dim=8, index_topk=12,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=4,
    router_experts=16, first_expert=4, num_experts_per_tok=3,
    num_hidden_layers=5, mlp_layer_types=("dense",) + ("sparse",) * 4,
    indexer_types=("full", "shared", "shared", "shared", "full"),
    first_layer=2, rope_parameters=ROPE, routed_scaling_factor=2.5,
    rms_norm_eps=1e-5, dtype="float32", patch_size=2, output_channels=2)
# one block of SMALL, as the trunk builds it
BLOCK = dict(
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=24,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    index_n_heads=16, index_head_dim=8, index_topk=12,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=4,
    n_shared_experts=1, num_experts_per_tok=3, router_experts=16,
    first_expert=4, norm_topk_prob=True, routed_scaling_factor=2.5,
    attention_bias=False, rms_norm_eps=1e-5, rope_theta=8e6,
    dtype=jnp.float32)
RES, CH, TOK, FEAT = 8, 2, 5, 12
TOKENS = 1 + TOK + (RES // 2) ** 2          # 22: index_topk 12 binds


def _seeded(model, key=7):
    from harness import weights
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, RES, RES, CH)), jnp.zeros((1,)),
                             jnp.zeros((1, TOK, FEAT)))["params"],
        jax.random.PRNGKey(0))
    params = jax.jit(lambda k: weights.fill_params(shapes, k))(
        jax.random.PRNGKey(key))
    # the seeded correction bias is 0.02 a leaf: widen it until it
    # changes which experts are picked
    return jax.tree_util.tree_map_with_path(
        lambda path, v: v * 10 if "router_bias" in jax.tree_util.keystr(path)
        else v, params)


def _inputs(batch=2, key=3):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(ks[0], (batch, RES, RES, CH)),
            jnp.linspace(20.0, 900.0, batch),
            jax.random.normal(ks[1], (batch, TOK, FEAT)))


@pytest.fixture(scope="module")
def small():
    model = build_model("glm_moe_dsa_dn", **SMALL)
    return model, _seeded(model)


# -- the model against the plain reference ---------------------------------

@pytest.mark.parametrize("top_k", [12, 64])     # binds / never binds
def test_forward_and_gradient_equal_the_plain_reference(top_k):
    from reference import glm_moe_dsa as ref
    cfg = dict(SMALL, index_topk=top_k)
    model = build_model("glm_moe_dsa_dn", **cfg)
    params = _seeded(model)
    x, t, text = _inputs()
    got, tally = jax.jit(lambda p: model.apply(
        {"params": p}, x, t, text, return_tally=True))(params)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.forward(p, cfg, x, t, text))(params)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # what one evaluation counts, by name
    assert set(tally) == set(model.tally_shapes) == {"picks", "fitted",
                                                     "keys"}
    assert tally["picks"].shape == (2, 4, 4) and tally["keys"].shape == (2, 5)
    # a quarter of the picks land here, half of them fit a pass: all do
    np.testing.assert_array_equal(tally["fitted"],
                                  tally["picks"].sum(axis=-1))
    assert int(tally["picks"].sum()) <= 2 * 4 * TOKENS * 3
    # `dsa/keys_selected` is the closed form: every causal pair of the
    # first top_k queries, top_k a query beyond
    pairs = dsa.selected_pairs(TOKENS, top_k)
    assert pairs == (12 * 13 // 2 + 10 * 12 if top_k == 12
                     else TOKENS * (TOKENS + 1) // 2)
    assert tally["keys"].tolist() == [[pairs] * 5] * 2
    added = model.tally_counters(
        jax.tree_util.tree_map(lambda a: np.asarray(a[0]), tally), 1,
        (RES, RES, CH), TOK)
    assert added["dsa/keys_selected"] == 5 * pairs
    assert added["dsa/keys_visible"] == 5 * TOKENS * (TOKENS + 1) // 2
    assert added["moe/picks_routed"] == TOKENS * 3 * 4
    assert added["moe/picks_fitted"] == added["moe/picks_held"] \
        == int(tally["picks"][0].sum()) > 0
    # a gradient, through the selection (piecewise constant) and the router
    loss = lambda f: lambda p: jnp.mean(f(p) ** 2)
    g_got = jax.jit(jax.grad(loss(lambda p: model.apply(
        {"params": p}, x, t, text))))(params)
    with jax.default_matmul_precision("highest"):
        g_want = jax.jit(jax.grad(loss(lambda p: ref.forward(
            p, cfg, x, t, text))))(params)
    for name in ("to_q_b", "to_k_b", "to_v_b", "experts_up", "router"):
        a = g_got["layer_4"][name]["kernel"]
        b = g_want["layer_4"][name]["kernel"]
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()),
                                   rtol=2e-3)


def test_an_idle_selector_is_plain_causal_attention(small):
    """A row no longer than `index_topk`: the selection keeps every
    causal pair, and the model equals itself with the core handed the
    causal mask and no indexer at all."""
    from flaxdiff_tpu.ops import attention as att
    model = build_model("glm_moe_dsa_dn", **dict(SMALL, index_topk=TOKENS))
    _, params = small
    x, t, text = _inputs()
    got, tally = jax.jit(lambda p: model.apply(
        {"params": p}, x, t, text, return_tally=True))(params)
    assert tally["keys"].tolist() == [[TOKENS * (TOKENS + 1) // 2] * 5] * 2
    seen = []
    real = att._xla_attention

    def causal_instead(q, k, v, **kw):
        seen.append(kw.pop("key_mask"))
        return real(q, k, v, **dict(kw, causal=True))

    att._xla_attention = causal_instead
    try:
        want = jax.jit(lambda p: model.apply({"params": p}, x, t, text))(
            params)
    finally:
        att._xla_attention = real
    assert len(seen) == 5 and all(m is not None for m in seen)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)


def test_a_shared_layer_reads_its_full_layers_selection_and_holds_no_indexer(
        small):
    model, params = small
    for i, kind in enumerate(SMALL["indexer_types"]):
        idx = {k for k in params[f"layer_{i}"] if k.startswith("idx_")}
        assert idx == ({"idx_q", "idx_k", "idx_k_norm", "idx_w"}
                       if kind == "full" else set()), (i, idx)
    for i, kind in enumerate(SMALL["mlp_layer_types"]):
        assert ("router" in params[f"layer_{i}"]) == (kind == "sparse")
        assert ("mlp_gate" in params[f"layer_{i}"]) == (kind == "dense")
    x = jax.random.normal(jax.random.PRNGKey(5), (2, TOKENS, 64))
    shared = GlmMoeDsaBlock(mlp_type="sparse", indexer_type="shared", **BLOCK)
    p = {"params": params["layer_1"]}
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((TOKENS, TOKENS), bool)),
                              (2, TOKENS, TOKENS))
    # its output moves with the selection it is handed, which it hands on
    sparse = causal & (jnp.arange(TOKENS)[None, :] % 2 == 0) \
        | jnp.eye(TOKENS, dtype=bool)
    y_all, keep, picks = jax.jit(shared.apply)(p, x, causal)
    y_some, kept, _ = jax.jit(shared.apply)(p, x, sparse)
    assert keep is not None and bool((kept == sparse).all())
    assert picks[0].shape == (2, 4) and picks[1].shape == (2,)
    assert float(jnp.abs(y_all - y_some).max()) > 1e-3
    with pytest.raises(ValueError, match="none came before"):
        shared.apply(p, x, None)
    # a full layer makes its own, whatever it is handed
    full = GlmMoeDsaBlock(mlp_type="sparse", indexer_type="full", **BLOCK)
    p4 = {"params": params["layer_4"]}
    _, own, _ = jax.jit(full.apply)(p4, x, causal)
    _, again, _ = jax.jit(full.apply)(p4, x, sparse)
    assert bool((own == again).all())
    assert int(own[0].sum()) == dsa.selected_pairs(TOKENS, 12)


def test_the_lists_are_held_to_the_published_rule():
    # the published 78 entries: three leading, then a period of four
    want = ["full"] * 3 + (["shared"] * 3 + ["full"]) * 18 + ["shared"] * 3
    assert [published_indexer_type(i, 3, 4) for i in range(78)] == want
    build_model("glm_moe_dsa_dn", **SMALL)
    for over, match in [
            (dict(first_layer=3), "mlp_layer_types"),
            (dict(first_k_dense_replace=2), "mlp_layer_types"),
            (dict(index_topk_freq=3), "indexer_types"),
            (dict(index_skip_topk_offset=2), "indexer_types"),
            (dict(indexer_types=("full",) * 5), "indexer_types"),
            (dict(num_hidden_layers=4), "mlp_layer_types"),
            (dict(first_layer=3, mlp_layer_types=("sparse",) * 5,
                  indexer_types=("shared", "shared", "shared", "full",
                                 "shared")), "select for itself"),
            (dict(topk_method="greedy"), "not built"),
            (dict(v_head_dim=8), "one head size"),
            (dict(first_expert=14), "outside the router"),
            (dict(rope_parameters={"rope_type": "yarn", "rope_theta": 1.0}),
             "rope_theta")]:
        with pytest.raises(ValueError, match=match):
            build_model("glm_moe_dsa_dn", **dict(SMALL, **over))


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference():
    """16 experts in 4 shares of 4: the routed parts of the four shares,
    with the shared expert and the attention counted once, equal the
    uncut reference's layer."""
    from reference import glm_moe_dsa as ref
    uncut = build_model("glm_moe_dsa_dn", **dict(
        SMALL, n_routed_experts=16, first_expert=0))
    layer = _seeded(uncut)["layer_4"]               # sparse + full
    x = jax.random.normal(jax.random.PRNGKey(5), (2, TOKENS, 64))
    cfg = dict(SMALL, n_routed_experts=16, first_expert=0)
    stacks = ("experts_gate", "experts_up", "experts_down")
    with jax.default_matmul_precision("highest"):
        want, keep = jax.jit(lambda p: ref._layer(
            cfg, p, x, None, True, True))(layer)
        # the same layer with no routed expert held: attention + shared
        none = dict(layer, **{k: {"kernel": layer[k]["kernel"][:0]}
                              for k in stacks})
        base, _ = jax.jit(lambda p: ref._layer(
            dict(cfg, n_routed_experts=0), p, x, None, True, True))(none)
    total, picks = 0.0, []
    for share in range(4):
        block = GlmMoeDsaBlock(mlp_type="sparse", indexer_type="full",
                               **dict(BLOCK, first_expert=4 * share))
        held = dict(layer, **{
            k: {"kernel": layer[k]["kernel"][4 * share:4 * share + 4]}
            for k in stacks})
        y, kept, (n, _) = jax.jit(block.apply)({"params": held}, x)
        assert bool((kept == keep).all())
        total = total + (y - base)
        picks.append(n)
    np.testing.assert_allclose(total + base, want, atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(want - base).max()) > 1e-2
    # every pick lands on exactly one share
    assert int(sum(p.sum() for p in picks)) == 2 * TOKENS * 3


# -- the selection -----------------------------------------------------------

def test_select_keeps_exactly_the_top_k_of_the_causal_scores():
    scores = jax.random.normal(jax.random.PRNGKey(0), (2, 70, 70))
    keep = np.asarray(jax.jit(lambda s: dsa.select(s, 16))(scores))
    assert keep.sum() == 2 * dsa.selected_pairs(70, 16)
    s = np.asarray(scores)
    for b, t in ((0, 3), (0, 15), (0, 16), (1, 40), (1, 69)):
        want = np.zeros(70, bool)
        want[np.argsort(-s[b, t, :t + 1], kind="stable")[:16]] = True
        assert (keep[b, t] == want).all(), (b, t)
    # the search is the k-th largest, negative, zero and tied scores among
    # them; `lax.top_k` is the witness
    x = jnp.concatenate([jax.random.normal(jax.random.PRNGKey(1), (6, 500)),
                         jnp.zeros((6, 40)), -jnp.zeros((6, 3)),
                         jnp.full((6, 5), -jnp.inf)], axis=1)
    for k in (1, 7, 250, 300, 548):
        got = dsa.kth_largest(dsa._ordered(x), k)
        want = dsa._ordered(jax.lax.top_k(x, k)[0][:, -1])
        assert bool((got == want).all()), k
    # ties at the k-th score are all kept
    tied = jnp.zeros((1, 9, 9))
    assert int(dsa.select(tied, 4).sum()) == 45


def test_index_scores_in_blocks_equal_the_scores_whole(monkeypatch):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 37, 3, 8))
    k = jax.random.normal(ks[1], (2, 37, 8))
    w = jax.random.normal(ks[2], (2, 37, 3))
    want = jnp.einsum("bthd,bsd->bhts", q, k)
    want = jnp.einsum("bhts,bth->bts", jax.nn.relu(want), w) / 24 ** 0.5
    np.testing.assert_allclose(dsa.index_scores(q, k, w), want, atol=1e-5)
    monkeypatch.setattr(dsa, "SCORE_BLOCK", 16)
    np.testing.assert_allclose(jax.jit(dsa.index_scores)(q, k, w), want,
                               atol=1e-5)


# -- serving: rows apart, the named tally, the counters -----------------------

def test_a_served_request_equals_the_references_trajectory_and_is_counted(
        small):
    from flaxdiff_tpu.inputs import (ConditionalInputConfig,
                                     DiffusionInputConfig)
    from flaxdiff_tpu.serving import (SampleRequest, SchedulerConfig,
                                      ServingScheduler)
    from flaxdiff_tpu.telemetry import Telemetry
    from harness.serving import SeededContextEncoder
    from reference import glm_moe_dsa as ref, sample

    model, params = small
    null_ctx = 0.5 * np.random.default_rng(1).standard_normal(
        (1, TOK, FEAT)).astype(np.float32)
    pipe = DiffusionInferencePipeline.from_config(
        {"model": dict(SMALL, name="glm_moe_dsa_dn"),
         "schedule": {"name": "cosine", "timesteps": 1000},
         "predictor": "v"}, params={"params": params})
    pipe.input_config = DiffusionInputConfig(
        sample_data_key="sample", sample_data_shape=(RES, RES, CH),
        conditions=[ConditionalInputConfig(
            encoder=SeededContextEncoder(null_ctx))])
    assert pipe.model.serve_rows_apart
    assert pipe.get_sampler("ddim", 3.0).tally_shape == {
        "picks": (4, 4), "fitted": (4,), "keys": (5,)}
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(pipeline=pipe, telemetry=tel,
                             config=SchedulerConfig())
    conds = [np.random.default_rng(2 + i).standard_normal(
        (1, TOK, FEAT)).astype(np.float32) for i in range(3)]
    reqs = [SampleRequest(num_samples=1, resolution=RES, channels=CH,
                          diffusion_steps=nfe, sampler="ddim",
                          guidance_scale=3.0, seed=11 + nfe,
                          conditioning=c)
            for nfe, c in zip((2, 3, 4), conds)]
    results = [f.result(timeout=600) for f in [sched.submit(r) for r in reqs]]
    sched.close(drain=True)
    # rows apart: a round of one row, a request's turns back to back
    assert sched.batch_buckets == (1,)
    assert [r.rounds for r in results] == [1, 1, 1]
    assert tel.counter("serving/rounds").value \
        == tel.counter("serving/rows_real").value == 3
    assert tel.counter("serving/rows_padded").value == 0
    forward = jax.jit(lambda p, *a: ref.forward(p, SMALL, *a))
    for req, res, cond in zip(reqs, results, conds):
        want = sample.serve(
            lambda p, cfg, *a: forward(p, *a), SMALL, params,
            {"seed": req.seed, "nfe": req.diffusion_steps, "guidance": 3.0,
             "shape": (1, RES, RES, CH), "cond": cond, "uncond": null_ctx},
            1000, predictor="v")
        np.testing.assert_allclose(res.samples, want, atol=5e-4)
    evals = sum((r.diffusion_steps + 1) * 2 for r in reqs)
    assert tel.counter("dsa/keys_selected").value \
        == evals * 5 * dsa.selected_pairs(TOKENS, 12)
    assert tel.counter("dsa/keys_visible").value \
        == evals * 5 * TOKENS * (TOKENS + 1) // 2
    assert tel.counter("moe/picks_routed").value == evals * TOKENS * 3 * 4
    held = tel.counter("moe/picks_held").value
    assert 0 < tel.counter("moe/picks_hottest").value <= held \
        < tel.counter("moe/picks_routed").value
    assert 0.9 * held < tel.counter("moe/picks_fitted").value <= held


def test_rows_apart_is_the_vmap_an_entry_at_a_time():
    from flaxdiff_tpu.samplers.common import rows_apart
    calls = []

    def fn(p, x, c):
        calls.append(x.shape)
        return x * p["w"] + c["text"].sum(), {"n": jnp.sum(x > 0)}

    p = {"w": jnp.float32(2.0)}
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 4))
    c = {"text": jnp.arange(3.0)}
    want = jax.vmap(lambda x, c: fn(p, x, c))(x, c)
    calls.clear()
    got = jax.jit(jax.vmap(lambda x, c: rows_apart(fn)(p, x, c)))(x, c)
    assert set(calls) == {(2, 4)}       # traced for ONE entry's shape
    jax.tree_util.tree_map(np.testing.assert_allclose, got, want)
    # an argument the vmap does not batch is every entry's
    got = jax.vmap(lambda x: rows_apart(fn)(p, x, {"text": jnp.float32(1)}))(x)
    np.testing.assert_allclose(got[0], x * 2 + 1)
