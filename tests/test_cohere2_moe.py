"""The `cohere2_moe` denoiser trunk (models/cohere2_moe.py), its kernels'
new paths (ops/moe.py; the masked grouped-query flash forward) and the
held-pick counters, against the plain reference
(benchmark/reference/cohere2_moe.py) at small sizes on the CPU."""
import functools
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _benchmark_package(name):
    """`benchmark/<name>` as the top-level package the benchmark's own
    code imports it as, WITHOUT `benchmark/` on `sys.path`: its `tests`
    package would shadow this directory for `tests.test_serving`."""
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
from flaxdiff_tpu.ops import moe  # noqa: E402
from flaxdiff_tpu.ops.attention import (_xla_attention, attend,  # noqa: E402
                                        dot_product_attention_bhld)
from flaxdiff_tpu.ops.flash_attention import flash_attention  # noqa: E402

SMALL = dict(
    hidden_size=64, head_dim=16, num_attention_heads=8,
    num_key_value_heads=2, intermediate_size=48, num_hidden_layers=4,
    layer_types=("sliding_attention",) * 3 + ("full_attention",),
    sliding_window=40, num_experts=4, router_experts=16, first_expert=4,
    num_experts_per_tok=3, num_shared_experts=2, rope_theta=50000,
    layer_norm_eps=1e-5, norm_topk_prob=True, dtype="float32",
    patch_size=2, output_channels=2)
RES, CH, TOK, FEAT = 8, 2, 5, 12


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


@pytest.fixture(scope="module")
def small():
    model = build_model("cohere2_moe_dn", **SMALL)
    return model, _seeded(model)


def _ref_cfg(**over):
    return dict(SMALL, **over)


# -- the model against the plain reference ---------------------------------

@pytest.mark.parametrize("window", [40, 6])     # never binds / binds
def test_forward_equals_the_plain_reference(window):
    from reference import cohere2_moe as ref
    model = build_model("cohere2_moe_dn", **dict(SMALL, sliding_window=window))
    params = _seeded(model)
    x, t, text = _inputs()
    got, tally = jax.jit(lambda p: model.apply(
        {"params": p}, x, t, text, return_tally=True))(params)
    picks = tally["picks"]
    assert set(tally) == set(model.tally_shapes) == {"picks", "fitted"}
    # a quarter of the picks land here, half of them fit a pass: all do
    np.testing.assert_array_equal(tally["fitted"], picks.sum(axis=-1))
    added = model.tally_counters(
        jax.tree_util.tree_map(lambda a: np.asarray(a[0]), tally), 1,
        (RES, RES, CH), TOK)
    assert added["moe/picks_fitted"] == added["moe/picks_held"] \
        == int(picks[0].sum()) > 0
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.forward(
            p, _ref_cfg(sliding_window=window), x, t, text))(params)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # every token makes 3 picks a layer over 16 experts; 4 are held here
    tokens = 1 + TOK + (RES // 2) ** 2
    assert picks.shape == (2, 4, 4)
    assert int(picks.sum()) <= 2 * 4 * tokens * 3
    assert model.routed_picks((RES, RES, CH), TOK) == tokens * 3 * 4


def test_reference_stages_fold_to_its_forward_and_share_one_layer():
    from reference import cohere2_moe as ref
    model = build_model("cohere2_moe_dn", **SMALL)
    params = _seeded(model)
    x, t, text = _inputs()
    stages = ref.stages(_ref_cfg(), x.shape)
    assert [n for n, _, _ in stages] == [
        "embed", "layer_0", "layer_1", "layer_2", "layer_3", "head"]
    assert len({apply for n, _, apply in stages if n.startswith("layer")}) \
        == 1
    assert set(params) == {n for _, needs, _ in stages for n in needs}


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference():
    """16 experts in 4 shares of 4: the routed parts of the four shares
    plus everything else counted once equal the uncut reference's layer."""
    from reference import cohere2_moe as ref
    from flaxdiff_tpu.models.cohere2_moe import Cohere2MoEBlock
    uncut = build_model("cohere2_moe_dn", **dict(
        SMALL, num_experts=16, first_expert=0, num_hidden_layers=1,
        layer_types=("sliding_attention",)))
    layer = _seeded(uncut)["layer_0"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 22, 64))
    cfg = _ref_cfg(num_experts=16, first_expert=0)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref._layer(cfg, p, x, jnp.asarray(True)))(
            layer)
        # the same layer with no routed expert held: x + attention + shared
        none = dict(layer, **{k: {"kernel": layer[k]["kernel"][:0]} for k in
                              ("experts_gate", "experts_up", "experts_down")})
        base = jax.jit(lambda p: ref._layer(
            dict(cfg, num_experts=0), p, x, jnp.asarray(True)))(none)
    total = 0.0
    picks = []
    for share in range(4):
        block = Cohere2MoEBlock(
            head_dim=16, num_attention_heads=8, num_key_value_heads=2,
            intermediate_size=48, num_experts=4, num_experts_per_tok=3,
            num_shared_experts=2, router_experts=16, first_expert=4 * share,
            norm_topk_prob=True, attention_bias=False, layer_norm_eps=1e-5,
            rope_theta=50000.0, window=40, dtype=jnp.float32)
        held = dict(layer, **{
            k: {"kernel": layer[k]["kernel"][4 * share:4 * share + 4]}
            for k in ("experts_gate", "experts_up", "experts_down")})
        y, n, _ = jax.jit(block.apply)({"params": held}, x)
        total = total + (y - base)
        picks.append(n)
    np.testing.assert_allclose(total + base, want, atol=2e-5, rtol=2e-5)
    # every pick lands on exactly one share
    assert int(sum(p.sum() for p in picks)) == 2 * 22 * 3


# -- routed experts ---------------------------------------------------------

def _experts(n=40, d=32, f=48, e=4, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    return (jax.random.normal(ks[0], (n, d)),
            jax.random.normal(ks[1], (e, d, f)) / 6,
            jax.random.normal(ks[2], (e, d, f)) / 6,
            jax.random.normal(ks[3], (e, f, d)) / 7)


def _dense(x, local, w, wg, wu, wd):
    y = jnp.zeros_like(x)
    for e in range(wg.shape[0]):
        g = x @ wg[e]
        out = (g * jax.nn.sigmoid(g) * (x @ wu[e])) @ wd[e]
        y = y + out * jnp.sum(jnp.where(local == e, w, 0.0), axis=1)[:, None]
    return y


def _imbalanced(n, e):
    """Expert 1 gets no pick, expert 2 half of them; some picks are of
    experts held elsewhere (`e`)."""
    rng = np.random.default_rng(0)
    local = rng.choice([0, 3, e], size=(n, 2))
    local[:, 0] = np.where(np.arange(n) % 2 == 0, 2, local[:, 0])
    local[::2, 1] = 2
    return jnp.asarray(local, jnp.int32)


@functools.lru_cache(maxsize=None)
def _experts_summed_exactly(n=40, d=32, f=48, e=4):
    """Experts whose every sum is exact in float32 in any order (small
    whole numbers into gate and up; a down matrix with one +-1 a
    column), so that two runs differ only where one is WRONG: the CPU's
    `dot` orders a contraction by its operands' shapes, the MXU does
    not."""
    rng = np.random.default_rng(7)
    wd = np.zeros((e, f, d), np.float32)
    for i in range(e):
        wd[i, (np.arange(d) * 5 + i) % f, np.arange(d)] = rng.choice(
            [-1.0, 1.0], d)
    return (jnp.asarray(rng.integers(-2, 3, (n, d)), jnp.float32),
            jnp.asarray(rng.integers(-1, 2, (e, d, f)), jnp.float32),
            jnp.asarray(rng.integers(-1, 2, (e, d, f)), jnp.float32),
            jnp.asarray(wd))


_ffn_xla = jax.jit(moe._expert_ffn_xla, static_argnums=5)


@functools.lru_cache(maxsize=None)
def _imbalanced_product(act, tile_n, exact=False):
    """An imbalanced layout (expert 2's group is five row tiles of 8,
    expert 3's a single one, expert 1's none) through both kernels in
    interpret mode at one column tile, under `jit`: (got [M, 32], the
    rows that groups own, x[src], tile_group, padded)."""
    x, wg, wu, wd = _experts_summed_exactly() if exact else _experts()
    local = np.asarray(_imbalanced(40, 4))
    third = local == 3          # all but five of expert 3's picks to 0
    local = jnp.asarray(np.where(
        third & (np.cumsum(third).reshape(local.shape) > 5), 0, local))
    rows, src, padded, tile_group, num_tiles = moe.dispatch(
        local, moe.held_order(local, 4), 4, 8)
    ffn = jax.jit(functools.partial(
        moe._expert_ffn_pallas, tile_m=8, tile_n=tile_n, interpret=True,
        act=act))
    return (np.asarray(ffn(x[src], wg, wu, wd, tile_group, num_tiles)),
            int(padded.sum()), x[src], np.asarray(tile_group), padded)


def test_dispatch_lays_an_imbalance_out_by_expert_in_token_order():
    local = _imbalanced(40, 4)
    counts = [int((local == e).sum()) for e in range(4)]
    assert counts[1] == 0 and counts[2] >= sum(counts) // 2
    picks = moe.held_order(local, 4)
    rows, src, padded, tile_group, num_tiles = moe.dispatch(
        local, picks, 4, 8)
    assert [int(p) for p in padded] == [-(-c // 8) * 8 for c in counts]
    assert int(num_tiles) == int(padded.sum()) // 8
    # the served picks in token order, each on a row of its own token's
    # in its expert's group
    live = np.asarray(picks) < 80
    assert live.sum() == sum(counts) and (np.diff(picks[live]) > 0).all()
    assert (np.asarray(src)[np.asarray(rows)[live]]
            == np.asarray(picks)[live] // 2).all()
    assert (np.asarray(tile_group)[np.asarray(rows)[live] // 8]
            == np.asarray(local).reshape(-1)[np.asarray(picks)[live]]).all()
    assert len(set(np.asarray(rows)[live].tolist())) == live.sum()


# column tiles of the two products (gate/up: n = 48, down: n = 32):
# narrower than n and dividing it, n itself (48 is cut to down's 32),
# and the rule's own (`column_tile`: all of so small an n)
@pytest.mark.parametrize("tile_n", [8, 16, 48, None])
@pytest.mark.parametrize("act", ["silu", "relu"])
def test_the_grouped_product_in_interpret_mode_under_an_imbalance(act,
                                                                  tile_n):
    _, wg, wu, wd = _experts()
    got, live, xs, group, padded = _imbalanced_product(act, tile_n)
    tiles = np.bincount(group[:live // 8], minlength=4)
    assert list(tiles) == [3, 0, 5, 1]
    xs = np.asarray(xs)
    for row in range(live):     # per-expert dot, row by row
        e = int(group[row // 8])
        g = xs[row] @ np.asarray(wg[e])
        g = np.maximum(g, 0) if act == "relu" else g / (1 + np.exp(-g))
        want = (g * (xs[row] @ np.asarray(wu[e]))) @ np.asarray(wd[e])
        np.testing.assert_allclose(got[row], want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        got[:live], _ffn_xla(xs, wg, wu, wd, padded, act)[:live],
        atol=2e-5, rtol=2e-5)
    # a column tile says which step writes a column, not what its sum
    # is: to the last bit between tile widths and against XLA's form
    got, _, xs, _, _ = _imbalanced_product(act, tile_n, exact=True)
    assert np.abs(got[:live]).max() > 8
    np.testing.assert_array_equal(
        got[:live], _imbalanced_product(act, 16, exact=True)[0][:live])
    np.testing.assert_array_equal(
        got[:live], _ffn_xla(xs, *_experts_summed_exactly()[1:], padded,
                             act)[:live])


# a layer's (model width, expert width) and the column tiles of its
# gate/up and down products at bfloat16
COLUMN_TILES = {
    "command_a_plus": (4096, 4096, 256, 256),
    "smallthinker": (2560, 768, 768, 2560),
    "glm": (6144, 2048, 256, 512),
    "a_small_preset": (64, 96, 96, 64),
}


@pytest.mark.parametrize("layer", list(COLUMN_TILES))
def test_the_column_tile_is_a_rule_of_the_shapes(layer):
    d, f, gate_up, down = COLUMN_TILES[layer]
    assert moe.column_tile(d, f, 2, 2) == gate_up
    assert moe.column_tile(f, d, 1, 2) == down
    for k, n, matrices, tile_n in ((d, f, 2, gate_up), (f, d, 1, down)):
        assert n % tile_n == 0
        # the step's blocks, double-buffered, inside what the call asks
        assert 2 * 2 * matrices * k * tile_n < moe._gmm_vmem(
            k, tile_n, matrices, 2) <= moe.VMEM_LIMIT
        # and no narrower than the floor wherever n is cut at all
        assert tile_n == n or tile_n >= moe.TILE_N


def _everywhere(n, e):
    """Every pick is of an expert held here."""
    return jnp.asarray(np.random.default_rng(1).integers(0, e, (n, 2)),
                       jnp.int32)


def _elsewhere(n, e):
    """Three picks in eight land here, none on expert 1."""
    return jnp.asarray(np.random.default_rng(2).choice(
        [0, 2, 3] + [e] * 5, size=(n, 2)), jnp.int32)


# name: (local [40, 2], the layer's experts over all chips, passes)
PASSES = {
    "every_pick_fits": (lambda: _elsewhere(40, 4), 16, 1),
    "two_passes": (lambda: _everywhere(40, 4), 16, 2),
    "three_passes": (lambda: _everywhere(40, 4), 24, 3),
    "no_pick_lands": (lambda: jnp.full((40, 2), 4, jnp.int32), 16, 0),
    "the_whole_layer_is_here": (lambda: _imbalanced(40, 4), 4, 1),
}


@pytest.mark.parametrize("case", list(PASSES))
def test_routed_experts_drop_no_token_and_pool_a_vmap_over_rows(case):
    make, total, passes = PASSES[case]
    x, wg, wu, wd = _experts()
    local = make()
    w = jax.random.uniform(jax.random.PRNGKey(9), (40, 2))
    held = int((local < 4).sum())
    count = moe.capacity(80, 4, total)
    assert -(-held // count) == passes
    want = jax.jit(_dense)(x, local, w, wg, wu, wd)

    def routed(x, local, w, wg, wu, wd):
        return moe.routed_experts(x, local, w, wg, wu, wd, total)
    got, fitted = jax.jit(routed)(x, local, w, wg, wu, wd)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # what the first pass served: every held pick, or a pass's capacity
    assert fitted.shape == (40,) and int(fitted.sum()) == min(held, count)
    assert int(fitted.sum()) == {"every_pick_fits": held, "two_passes": count,
                                 "three_passes": count, "no_pick_lands": 0,
                                 "the_whole_layer_is_here": held}[case]
    if passes == 0:
        assert not np.asarray(got).any()
    by_row = jax.vmap(routed, in_axes=(0, 0, 0, None, None, None))
    halves = (x.reshape(4, 10, -1), local.reshape(4, 10, 2),
              w.reshape(4, 10, 2), wg, wu, wd)
    rows, fitted_rows = jax.jit(by_row)(*halves)
    np.testing.assert_allclose(rows.reshape(40, -1), want, atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_array_equal(fitted_rows.reshape(-1), fitted)
    # pooled: ONE grouped product for the four rows, not four, in ONE
    # loop over passes (of at most one where `held == total`: a pass
    # then holds every pick); the one-pass form has none
    pooled = str(jax.make_jaxpr(by_row)(*halves))
    alone = str(jax.make_jaxpr(routed)(x, local, w, wg, wu, wd))
    assert pooled.count("ragged_dot") == alone.count("ragged_dot") > 0
    one_pass = str(jax.make_jaxpr(functools.partial(
        moe._routed, total=total, one_pass=True))(x, local, w, wg, wu, wd))
    assert alone.count("while[") == pooled.count("while[") \
        == one_pass.count("while[") + 1
    assert (count == 80) == (case == "the_whole_layer_is_here")
    grads = jax.jit(jax.grad(lambda *a: (routed(
        a[0], local, w, *a[1:])[0] ** 2).sum(), argnums=(0, 1, 2, 3)))(
        x, wg, wu, wd)
    wants = jax.jit(jax.grad(
        lambda *a: (_dense(a[0], local, w, *a[1:]) ** 2).sum(),
        argnums=(0, 1, 2, 3)))(x, wg, wu, wd)
    # the backward is the one-pass form's, whatever the passes
    today = jax.jit(jax.grad(lambda *a: (moe._routed(
        a[0], local, w, *a[1:], total, one_pass=True)[0] ** 2).sum(),
        argnums=(0, 1, 2, 3)))(x, wg, wu, wd)
    for g, want_g, same in zip(grads, wants, today):
        np.testing.assert_allclose(g, want_g, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(g, same, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("case", ["every_pick_fits", "three_passes",
                                  "no_pick_lands"])
def test_the_combine_kernel_in_interpret_mode_pass_by_pass(case,
                                                            monkeypatch):
    """`fdt_moe_combine` against the XLA composition on every pass of a
    call, at tiles of 8, to the last bit (both add a token's products
    one by one in the order of its k); rows no group owns hold NaN, and
    a served row that is not finite spoils its own token and no other."""
    make, total, passes = PASSES[case]
    x, _, _, _ = _experts(d=128)
    local = make()
    w = jax.random.uniform(jax.random.PRNGKey(9), (40, 2))
    order = moe.held_order(local, 4)
    count = moe.capacity(80, 4, total)
    acc = x.astype(jnp.float32)
    monkeypatch.setattr(moe, "TILE_M", 8)
    spoilt = set()
    for p in range(max(passes, 1)):
        picks = jnp.pad(order, (0, count), constant_values=80)[
            p * count:(p + 1) * count]
        rows, src, _, _, _ = moe.dispatch(local, picks, 4, 8)
        served = np.asarray(picks)[np.asarray(picks) < 80]
        owned = np.zeros(src.shape[0], bool)
        owned[np.asarray(rows)[np.asarray(picks) < 80]] = True
        ys = jnp.where(owned[:, None], jax.random.normal(
            jax.random.PRNGKey(p), (src.shape[0], 128)), jnp.nan)
        if len(served):     # one served row overflows in one column
            ys = ys.at[rows[len(served) // 2], 5].set(jnp.inf)
            spoilt.add(int(served[len(served) // 2]) // 2)
        for dtype in (jnp.float32, jnp.bfloat16):
            want = moe._combine_xla(acc, ys.astype(dtype), picks, rows, w)
            got = moe._combine_pallas(acc, ys.astype(dtype), picks, rows, w,
                                      interpret=True)
            np.testing.assert_array_equal(got, want)
        acc = want
        bad = ~np.isfinite(np.asarray(acc)).all(axis=1)
        assert set(np.flatnonzero(bad).tolist()) == spoilt
    assert passes == 0 or np.nanmax(np.abs(np.asarray(acc - x))) > 0.1


def _rows_of_requests(case):
    """Four requests of ten tokens each (d = 128): one spread evenly,
    one that picks held experts only, one that picks none, one mixed."""
    x, wg, wu, wd = _experts(d=128)
    rng = np.random.default_rng(3)
    local = np.stack([rng.choice([0, 1, 2, 3] + [4] * 12, size=(10, 2)),
                      rng.integers(0, 4, (10, 2)),
                      np.full((10, 2), 4),
                      rng.choice([1, 3] + [4] * 6, size=(10, 2))])
    if case == "overflow":      # every pick of every request lands here
        local = rng.integers(0, 4, (4, 10, 2))
    w = jax.random.uniform(jax.random.PRNGKey(9), (4, 10, 2))
    return (x.reshape(4, 10, 128), jnp.asarray(local, jnp.int32), w,
            wg, wu, wd)


@pytest.mark.parametrize("form", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("case", ["fits", "overflow"])
def test_a_request_pooled_with_others_equals_the_request_alone(
        case, form, monkeypatch):
    """docs/SERVING.md's determinism contract in the routed layer: the
    rows of a round are pooled into one call, and a row comes out as it
    does alone, to the last bit, whatever the others hold, whatever the
    passes (`overflow`: the pooled call takes two, a row alone one or
    two), and a row that is not finite leaves the others as they were."""
    x, local, w, wg, wu, wd = _rows_of_requests(case)
    if form == "pallas_interpret":
        monkeypatch.setattr(moe, "TILE_M", 8)
        monkeypatch.setattr(moe, "_combine", functools.partial(
            moe._combine_pallas, interpret=True))
        moe._pooled.cache_clear()
    held = int((local < 4).sum())
    assert -(-held // moe.capacity(80, 4, 16)) == {"fits": 1,
                                                   "overflow": 2}[case]

    def routed(x, local, w):
        return moe.routed_experts(x, local, w, wg, wu, wd, 16)
    pooled, fitted = jax.jit(jax.vmap(routed))(x, local, w)
    assert int(fitted.sum()) == min(held, moe.capacity(80, 4, 16))
    for r in range(4):
        alone, _ = jax.jit(routed)(x[r], local[r], w[r])
        np.testing.assert_array_equal(pooled[r], alone)
        np.testing.assert_allclose(
            alone, _dense(x[r], local[r], w[r], wg, wu, wd), atol=5e-5,
            rtol=5e-5)
    # request 1 overflows in one token: the others do not see it
    spoilt, _ = jax.jit(jax.vmap(routed))(
        x.at[1, 4, 7].set(jnp.inf), local, w)
    bad = ~np.isfinite(np.asarray(spoilt)).all(axis=-1)
    assert bad[1, 4] and bad.sum() == 1
    keep = np.ones((4, 10), bool)
    keep[1, 4] = False
    np.testing.assert_array_equal(np.asarray(spoilt)[keep],
                                  np.asarray(pooled)[keep])
    moe._pooled.cache_clear()


def test_route_scores_every_expert_and_normalises_over_the_picks():
    h = jax.random.normal(jax.random.PRNGKey(1), (9, 16))
    wr = jax.random.normal(jax.random.PRNGKey(2), (16, 12))
    idx, w = moe.route(h, wr, 3)
    scores = jax.nn.sigmoid(h @ wr)
    np.testing.assert_array_equal(np.sort(idx, axis=1),
                                  np.sort(np.argsort(-scores, axis=1)[:, :3],
                                          axis=1))
    np.testing.assert_allclose(w.sum(axis=1), 1.0, rtol=1e-6)
    local, counts = moe.held_picks(idx, 4, 4)
    assert int(counts.sum()) == int(((idx >= 4) & (idx < 8)).sum())
    assert set(np.unique(local)) <= {0, 1, 2, 3, 4}


# -- the masked grouped-query flash forward ---------------------------------

MASKS = {
    "causal": dict(l=40, h=4, kv=4, causal=True, window=None),
    "window_smaller": dict(l=50, h=4, kv=2, causal=True, window=8),
    "window_larger": dict(l=50, h=4, kv=2, causal=True, window=100),
    "tail_padding": dict(l=37, h=8, kv=2, causal=True, window=None),
    "16_queries_a_kv_head": dict(l=50, h=32, kv=2, causal=True, window=20),
    "grouped_unmasked": dict(l=50, h=4, kv=2, causal=False, window=None),
}


@pytest.mark.parametrize("case", sorted(MASKS))
def test_masked_grouped_flash_in_interpret_mode(case):
    c = MASKS[case]
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, c["l"], c["h"], 16))
    k = jax.random.normal(ks[1], (2, c["l"], c["kv"], 16))
    v = jax.random.normal(ks[2], (2, c["l"], c["kv"], 16))
    cot = jax.random.normal(ks[3], q.shape)

    def flash(q, k, v):
        return flash_attention(q, k, v, None, 16, 16, True, c["causal"],
                               c["window"])

    def xla(q, k, v):
        return _xla_attention(q, k, v, causal=c["causal"], window=c["window"])

    np.testing.assert_allclose(jax.jit(flash)(q, k, v),
                               jax.jit(xla)(q, k, v), atol=2e-5, rtol=2e-5)
    got = jax.jit(jax.grad(lambda *a: (flash(*a) * cot).sum(),
                           argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(lambda *a: (xla(*a) * cot).sum(),
                            argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-5)


def test_the_dispatchers_settle_heads_and_mask_in_one_place():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 30, 8, 16))
    k = jax.random.normal(ks[1], (2, 30, 2, 16))
    v = jax.random.normal(ks[2], (2, 30, 2, 16))
    # the plain composition over repeated key/value heads, masked by hand
    kr, vr = jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / 4.0
    i, j = jnp.arange(30)[:, None], jnp.arange(30)[None, :]
    keep = (j <= i) & (j > i - 7)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
        jnp.where(keep, logits, -jnp.inf), axis=-1), vr)
    got = attend(q, k, v, causal=True, window=7)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    bhld = dot_product_attention_bhld(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, window=7)
    np.testing.assert_allclose(bhld.transpose(0, 2, 1, 3), want, atol=2e-5,
                               rtol=2e-5)
    # a window the sequence never reaches is no window
    np.testing.assert_array_equal(attend(q, k, v, causal=True, window=30),
                                  attend(q, k, v, causal=True))
    with pytest.raises(ValueError, match="not a multiple"):
        attend(q, k[:, :, :1].repeat(3, axis=2), v[:, :, :1].repeat(3, axis=2))


def test_the_plain_flash_call_lowers_to_the_kernel_it_was():
    """With no mask and one head count the kernel takes none of the new
    static branches: no row iota, no skip test, the old block maps."""
    s = jax.ShapeDtypeStruct((2, 256, 4, 128), jnp.bfloat16)

    def lowered(causal):
        return jax.export.export(jax.jit(lambda q, k, v: flash_attention(
            q, k, v, None, None, None, False, causal, None)),
            platforms=["tpu"])(s, s, s).mlir_module()
    plain, masked = lowered(False), lowered(True)
    assert plain.count("tpu_custom_call") == masked.count(
        "tpu_custom_call") == 1
    assert plain != masked


# -- serving: a DDIM trajectory and the held-pick counters -------------------

def test_a_served_request_equals_the_references_trajectory_and_is_counted(
        small):
    from flaxdiff_tpu.inputs import (ConditionalInputConfig,
                                     DiffusionInputConfig)
    from flaxdiff_tpu.serving import (SampleRequest, SchedulerConfig,
                                      ServingScheduler)
    from flaxdiff_tpu.telemetry import Telemetry
    from harness.serving import SeededContextEncoder
    from reference import cohere2_moe as ref, sample

    model, params = small
    null_ctx = 0.5 * np.random.default_rng(1).standard_normal(
        (1, TOK, FEAT)).astype(np.float32)
    pipe = DiffusionInferencePipeline.from_config(
        {"model": dict(SMALL, name="cohere2_moe_dn"),
         "schedule": {"name": "cosine", "timesteps": 1000},
         "predictor": "v"}, params={"params": params})
    pipe.input_config = DiffusionInputConfig(
        sample_data_key="sample", sample_data_shape=(RES, RES, CH),
        conditions=[ConditionalInputConfig(
            encoder=SeededContextEncoder(null_ctx))])
    tel = Telemetry(enabled=False)
    sched = ServingScheduler(pipeline=pipe, telemetry=tel,
                             config=SchedulerConfig())
    cond = np.random.default_rng(2).standard_normal(
        (1, TOK, FEAT)).astype(np.float32)
    reqs = [SampleRequest(num_samples=1, resolution=RES, channels=CH,
                          diffusion_steps=nfe, sampler="ddim",
                          guidance_scale=3.0, seed=11 + nfe,
                          conditioning=cond) for nfe in (4, 6)]
    launches0 = tel.counter("serving/launches").value
    results = [f.result(timeout=600) for f in [sched.submit(r) for r in reqs]]
    sched.close(drain=True)
    # the reference's forward as ONE program (traced inside `serve`'s
    # own precision context), not a primitive at a time
    forward = jax.jit(lambda p, *a: ref.forward(p, _ref_cfg(), *a))
    for req, res in zip(reqs, results):
        want = sample.serve(
            lambda p, cfg, *a: forward(p, *a), _ref_cfg(), params,
            {"seed": req.seed, "nfe": req.diffusion_steps, "guidance": 3.0,
             "shape": (1, RES, RES, CH), "cond": cond, "uncond": null_ctx},
            1000, predictor="v")
        np.testing.assert_allclose(res.samples, want, atol=5e-4)
    tokens = 1 + TOK + (RES // 2) ** 2
    routed = sum((r.diffusion_steps + 1) * 2 for r in reqs) * tokens * 3 * 4
    held = tel.counter("moe/picks_held").value
    hottest = tel.counter("moe/picks_hottest").value
    assert tel.counter("moe/picks_routed").value == routed
    assert 0 < hottest <= held < routed
    # nearly every held pick fitted its layer's first pass (at these few
    # tokens a layer now and then takes a second: 8 picks of 1,670 here,
    # and the samples above are the reference's all the same)
    assert 0.9 * held < tel.counter("moe/picks_fitted").value <= held
    assert hottest >= held / 4          # the largest of 4 experts a layer
    # the picks left the device with the samples: a round is one launch,
    # a request's admission two, a finalisation one
    rounds = tel.counter("serving/rounds").value
    launches = tel.counter("serving/launches").value - launches0
    assert launches <= rounds + 2 * len(reqs) + len(reqs)


def test_a_model_without_experts_carries_no_tally():
    from flaxdiff_tpu.samplers import DDIMSampler
    from flaxdiff_tpu.samplers.common import DiffusionSampler
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    ds = DiffusionSampler(
        model_fn=lambda p, x, t, c: x * p, schedule=CosineNoiseSchedule(1000),
        transform=EpsilonPredictionTransform(), sampler=DDIMSampler())
    assert ds.tally_shape is None
    x = jnp.ones((2, 1, 4, 4, 1))
    out = ds.make_chunk_program(2)(
        jnp.float32(0.5), x, jnp.zeros((2, 2), jnp.uint32),
        jnp.tile(jnp.asarray([[500.0, 250.0], [250.0, 0.0]]), (2, 1, 1)),
        jnp.asarray([2, 1], jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.int32(2), None, None, ())
    assert len(out) == 3


# -- training ---------------------------------------------------------------

def test_a_trainer_step_of_the_small_preset_runs(mesh):
    from flaxdiff_tpu.predictors import VPredictionTransform
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig
    model = build_model("cohere2_moe_dn", **SMALL)

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, cond["text"])

    def init_fn(key):
        return model.init(key, jnp.zeros((1, RES, RES, CH)), jnp.zeros((1,)),
                          jnp.zeros((1, TOK, FEAT)))["params"]

    trainer = DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
        schedule=CosineNoiseSchedule(timesteps=1000),
        transform=VPredictionTransform(), mesh=mesh,
        config=TrainerConfig(log_every=2, normalize=False,
                             weighted_loss=False))
    rng = np.random.default_rng(0)

    def batches():
        while True:
            yield {"sample": rng.normal(size=(16, RES, RES, CH)).astype(
                np.float32),
                "cond": {"text": rng.normal(size=(16, TOK, FEAT)).astype(
                    np.float32)}}

    before = jax.tree_util.tree_map(np.asarray,
                                    trainer.get_params(use_ema=False))
    hist = trainer.fit(batches(), total_steps=3)
    assert np.isfinite(hist["final_loss"])
    after = trainer.get_params(use_ema=False)
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - b).max()), after, before)
    # the routed experts and the router learn: the gradient reaches them
    assert moved["layer_0"]["experts_gate"]["kernel"] > 0
    assert moved["layer_0"]["router"]["kernel"] > 0
