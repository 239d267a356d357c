"""The required-operations functions against a count of the plain
reference's own jaxpr, and the plain references against the program at
a small size in float32."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import flops, models, spec

from .conftest import BENCH


def _cfg(name, f32=True):
    cfg = models.effective_config(spec.load_config(
        os.path.join(BENCH, "configs", name + ".json")), True)
    if f32:
        cfg["model"]["dtype"] = "float32"
    return cfg


def _jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), (lb, _) = eqn.params["dimension_numbers"]
            lhs, out = eqn.invars[0].aval, eqn.outvars[0].aval
            k = np.prod([lhs.shape[i] for i in lc]) if lc else 1
            total += 2.0 * np.prod(out.shape) * k
        elif eqn.primitive.name == "conv_general_dilated":
            rhs, out = eqn.invars[1].aval, eqn.outvars[0].aval
            total += 2.0 * np.prod(out.shape) * np.prod(rhs.shape[:-1])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _jaxpr_flops(sub)
    return total


@pytest.mark.parametrize("name,module", [("unet-flaxdiff-128", "unet"),
                                         ("dit-xl-2-256", "dit")])
def test_required_ops_match_the_references_own_count(name, module):
    import importlib
    forward = importlib.import_module(f"reference.{module}").forward
    cfg = _cfg(name)
    _, _, init_fn, _ = models.build(cfg)
    params = jax.jit(init_fn)(jax.random.PRNGKey(0))
    res, ch = cfg["input"]["resolution"], cfg["input"]["channels"]
    tok, feat = (cfg["conditioning"]["tokens"],
                 cfg["conditioning"]["features"])
    jaxpr = jax.make_jaxpr(
        lambda p, x, t, c: forward(p, cfg["model"], x, t, c))(
        params, jnp.zeros((1, res, res, ch)), jnp.zeros((1,)),
        jnp.zeros((1, tok, feat)))
    counted = _jaxpr_flops(jaxpr.jaxpr)
    assert flops.forward_flops(cfg) == pytest.approx(counted, rel=0.01)


def test_dit_xl_2_required_ops_land_on_the_papers_figure():
    cfg = models.effective_config(spec.load_config(
        os.path.join(BENCH, "configs", "dit-xl-2-256.json")), False)
    # 2 x the paper's 118.6 GMACs
    assert flops.forward_flops(cfg) / 1e9 == pytest.approx(237.2, rel=0.02)
    assert cfg["required_gflop_per_image_fwd"] == 237


@pytest.mark.parametrize("name,module", [("unet-flaxdiff-128", "unet"),
                                         ("dit-xl-2-256", "dit")])
def test_reference_agrees_with_the_program_in_float32(name, module):
    import importlib
    forward = importlib.import_module(f"reference.{module}").forward
    cfg = _cfg(name)
    _, apply_fn, init_fn, shapes = models.build(cfg)
    params = jax.jit(init_fn)(jax.random.PRNGKey(3))
    # every leaf is seeded: no zero-initialised layer is left
    for leaf in jax.tree_util.tree_leaves(params):
        assert float(jnp.abs(leaf).max()) > 0
    res, ch = cfg["input"]["resolution"], cfg["input"]["channels"]
    k = jax.random.PRNGKey(1)
    x = jax.random.normal(k, (3, res, res, ch))
    t = jnp.array([3.0, 500.5, 999.0])
    text = jax.random.normal(k, (3, cfg["conditioning"]["tokens"],
                                 cfg["conditioning"]["features"]))
    with jax.default_matmul_precision("highest"):
        got = apply_fn(params, x, t, {"text": text})
    want = forward(params, cfg["model"], x, t, text)
    assert float(jnp.abs(want).max()) > 0.5       # not a vacuous zero
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_lower_precision_moves_the_reference():
    from reference import dit, nn
    cfg = _cfg("dit-xl-2-256")
    _, _, init_fn, _ = models.build(cfg)
    params = jax.jit(init_fn)(jax.random.PRNGKey(3))
    res, ch = cfg["input"]["resolution"], cfg["input"]["channels"]
    k = jax.random.PRNGKey(1)
    x = jax.random.normal(k, (2, res, res, ch))
    t = jnp.array([3.0, 999.0])
    text = jax.random.normal(k, (2, cfg["conditioning"]["tokens"],
                                 cfg["conditioning"]["features"]))
    want = dit.forward(params, cfg["model"], x, t, text)
    gaps = {}
    for prec in ("bf16", "fp8"):
        with nn.precision(prec):
            got = dit.forward(params, cfg["model"], x, t, text)
        gaps[prec] = float(jnp.abs(got - want).mean())
    assert 0 < gaps["bf16"] < gaps["fp8"]
