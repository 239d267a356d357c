"""Weights a subtree at a time, and the plain reference a stage at a
time: the same values as the whole tree gives, with no more than one
stage of float32 weights alive."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import models, serving, spec, weights
from reference import dit, sample

from .conftest import BENCH


@pytest.fixture(scope="module")
def toy():
    cfg = models.effective_config(spec.load_config(
        os.path.join(BENCH, "configs", "dit-xl-2-256.json")), True)
    cfg["model"].update(num_layers=6, dtype="float32")
    _, _, init_fn, shapes = models.build(cfg)
    return cfg, init_fn, shapes


def _equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb) and la
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_subtree_made_alone_equals_the_whole_trees_leaves(toy, dtype):
    _, _, shapes = toy
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), shapes)
    key = jax.random.PRNGKey(11)
    whole = jax.jit(lambda k: weights.fill_params(shapes, k))(key)
    for name in ("block_3", "cond"):
        alone = jax.jit(lambda k: weights.fill_params(
            shapes[name], k, prefix=f"['{name}']"))(key)
        _equal(alone, whole[name])
    maker = weights.Maker(shapes)
    made = maker.make(key)
    _equal(made, whole)
    # the blocks share one compiled program: the path hashes are operands
    assert maker.program("block_0")[0] is maker.program("block_5")[0]
    assert maker.program("block_0")[0] is not maker.program("cond")[0]
    wide = maker.make(key, ["block_2"], widen=True)
    _equal(wide["block_2"], jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), whole["block_2"]))
    assert maker.nbytes(["block_2"], widen=True) == sum(
        4 * x.size for x in jax.tree_util.tree_leaves(whole["block_2"]))


def test_a_stacked_expert_kernel_is_scaled_by_its_own_fan_in():
    shapes = {"moe": {"experts": {"kernel": jax.ShapeDtypeStruct(
        (64, 256, 32), jnp.float32)}},
        "dense": {"kernel": jax.ShapeDtypeStruct((256, 32), jnp.float32)}}
    made = weights.Maker(shapes).make(jax.random.PRNGKey(0))
    for leaf in jax.tree_util.tree_leaves(made):
        assert float(jnp.std(leaf)) == pytest.approx(1 / 16.0, rel=0.05)


def _requests(cfg, nfes):
    res, ch = cfg["input"]["resolution"], cfg["input"]["channels"]
    tok, feat = (cfg["conditioning"]["tokens"],
                 cfg["conditioning"]["features"])
    null = weights.null_context(tok, feat)
    return [{"seed": 100 + i, "nfe": nfe, "guidance": 3.0,
             "shape": (1, res, res, ch),
             "cond": weights.request_context(5, i, tok, feat),
             "uncond": null} for i, nfe in enumerate(nfes)]


def _live_bytes():
    return sum(int(a.nbytes) for a in jax.live_arrays())


def test_the_staged_reference_gives_the_whole_trees_samples(toy):
    """Requests of different lengths in lockstep through the stages,
    against each request alone through the whole tree; and what is alive
    while a stage is loaded."""
    cfg, init_fn, shapes = toy
    key = jax.random.PRNGKey(4)
    reqs = _requests(cfg, [3, 5, 2])
    timesteps, pred = cfg["schedule"]["timesteps"], cfg["predictor"]
    params = jax.jit(init_fn)(key)
    want = [np.asarray(sample.serve(dit.forward, cfg["model"], params, r,
                                    timesteps, None, pred)) for r in reqs]
    tree_bytes = sum(4 * x.size for x in jax.tree_util.tree_leaves(params))
    del params

    maker = weights.Maker(shapes)
    stages_of, make = serving.reference_stages(cfg, maker, key)
    names = [n for n, _, _ in stages_of((2, 8, 8, 2))]
    assert names == ["embed"] + [f"block_{i}" for i in range(6)] + ["head"]
    largest = max(maker.nbytes(needs, widen=True)
                  for _, needs, _ in stages_of((2, 8, 8, 2)))
    base, seen = _live_bytes(), []
    got = sample.serve_staged(stages_of, make, reqs, timesteps, pred,
                              probe=lambda name: seen.append(
                                  (name, _live_bytes() - base)))
    for g, w in zip(got, want):
        assert float(np.abs(w).max()) > 0.1
        np.testing.assert_allclose(np.asarray(g), w, atol=2e-5)
    # every stage of every evaluation was probed: 6 turns (the longest
    # request's 5 steps and its terminal) x 8 stages
    assert len(seen) == 6 * 8
    # one stage of float32 weights and the requests' carries and
    # trajectories (a few KB each at this size), never the tree
    carries = 64 * 1024
    assert largest + carries < tree_bytes / 2
    assert max(b for _, b in seen) <= largest + carries


def test_a_family_without_stages_is_one_stage_made_once(toy):
    from reference import unet
    cfg = models.effective_config(spec.load_config(
        os.path.join(BENCH, "configs", "unet-flaxdiff-128.json")), True)
    assert not hasattr(unet, "stages")
    _, _, _, shapes = models.build(cfg)
    maker = weights.Maker(shapes)
    stages_of, make = serving.reference_stages(cfg, maker,
                                               jax.random.PRNGKey(1))
    (name, needs, _), = stages_of((2, 8, 8, 3))
    assert name == "all" and set(needs) == set(shapes)
    assert make(needs)[0] is make(needs)[0]
