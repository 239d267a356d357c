"""Fused AdaLN / GEGLU / gate-residual kernels (ops/fused_adaln.py):
interpret-mode fwd AND bwd numerical parity vs the exact XLA
compositions, dispatch gating, and model-level bit-identity off-TPU.

Shapes are deliberately tiny — the interpret-mode compile dominates and
this file must stay a small slice of the tier-1 budget."""
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.ops import fused_adaln as fa

EPS = 1e-5


def _inputs(key, b=2, l=24, c=16, dtype=jnp.float32):
    ks = [jax.random.fold_in(key, i) for i in range(8)]
    x = jax.random.normal(ks[0], (b, l, c), dtype)
    mods = [jax.random.normal(k, (b, 1, c), dtype) * 0.2
            for k in ks[1:5]]
    g = [jax.random.normal(k, (b, l, c), dtype) for k in ks[5:7]]
    return x, mods, g


def _flax_ln(x):
    return nn.LayerNorm(epsilon=EPS, use_scale=False, use_bias=False,
                        dtype=jnp.float32).apply({}, x)


def test_ln_modulate2_fwd_matches_flax_composition():
    """Both fused views vs flax LayerNorm + modulate — the exact chain
    AdaLNZero/MMAdaLNZero run unfused."""
    x, (s1, b1, s2, b2), _ = _inputs(jax.random.PRNGKey(0))
    got = fa.fused_ln_modulate2(x, s1, b1, s2, b2, EPS,
                                interpret=True, force_pallas=True)
    norm = _flax_ln(x)
    for view, (s, b) in zip(got, ((s1, b1), (s2, b2))):
        np.testing.assert_allclose(view, norm * (1 + s) + b,
                                   rtol=2e-4, atol=2e-4)


def test_ln_modulate2_grads_match_xla():
    """dx/ds1/db1/ds2/db2 from the Pallas backward (saved mean/rstd)
    vs XLA autodiff of the composition."""
    x, (s1, b1, s2, b2), (g1, g2) = _inputs(jax.random.PRNGKey(1))

    def loss_fused(x, s1, b1, s2, b2):
        v1, v2 = fa.fused_ln_modulate2(x, s1, b1, s2, b2, EPS,
                                       interpret=True, force_pallas=True)
        return jnp.sum(v1 * g1) + jnp.sum(v2 * g2)

    def loss_ref(x, s1, b1, s2, b2):
        norm = _flax_ln(x)
        return (jnp.sum((norm * (1 + s1) + b1) * g1)
                + jnp.sum((norm * (1 + s2) + b2) * g2))

    got = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4))(x, s1, b1, s2, b2)
    want = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(x, s1, b1, s2, b2)
    for name, a, b in zip(("dx", "ds1", "db1", "ds2", "db2"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3,
                                   err_msg=name)


def test_ln_modulate_single_view_fwd_and_grads():
    x, (s, b, _, _), (g, _) = _inputs(jax.random.PRNGKey(2))
    out = fa.fused_ln_modulate(x, s, b, EPS, interpret=True,
                               force_pallas=True)
    np.testing.assert_allclose(out, _flax_ln(x) * (1 + s) + b,
                               rtol=2e-4, atol=2e-4)
    got = jax.grad(lambda *a: jnp.sum(fa.fused_ln_modulate(
        *a, EPS, interpret=True, force_pallas=True) * g),
        argnums=(0, 1, 2))(x, s, b)
    want = jax.grad(lambda x_, s_, b_: jnp.sum(
        (_flax_ln(x_) * (1 + s_) + b_) * g), argnums=(0, 1, 2))(x, s, b)
    for name, a, b_ in zip(("dx", "ds", "db"), got, want):
        np.testing.assert_allclose(a, b_, rtol=2e-3, atol=2e-3,
                                   err_msg=name)


def test_ln_modulate_multiblock_partial_tail(monkeypatch):
    """L spanning several row blocks with a padded tail: per-row stats
    and the backward partial sums must mask/slice it exactly."""
    monkeypatch.setattr(fa, "_BLOCK_BYTES", 8 * 16 * 4)  # 8-row blocks
    x, (s, b, _, _), (g, _) = _inputs(jax.random.PRNGKey(3), l=27)
    out = fa.fused_ln_modulate(x, s, b, EPS, interpret=True,
                               force_pallas=True)
    np.testing.assert_allclose(out, _flax_ln(x) * (1 + s) + b,
                               rtol=2e-4, atol=2e-4)
    got = jax.grad(lambda x_: jnp.sum(fa.fused_ln_modulate(
        x_, s, b, EPS, interpret=True, force_pallas=True) * g))(x)
    want = jax.grad(lambda x_: jnp.sum(
        (_flax_ln(x_) * (1 + s) + b) * g))(x)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_gate_residual_fwd_and_grads():
    x, (gate, _, _, _), (g, _) = _inputs(jax.random.PRNGKey(4))
    h = jax.random.normal(jax.random.PRNGKey(40), x.shape)
    out = fa.fused_gate_residual(x, gate, h, interpret=True,
                                 force_pallas=True)
    np.testing.assert_allclose(out, x + gate * h, rtol=1e-6, atol=1e-6)
    got = jax.grad(lambda *a: jnp.sum(fa.fused_gate_residual(
        *a, interpret=True, force_pallas=True) * g),
        argnums=(0, 1, 2))(x, gate, h)
    want = jax.grad(lambda x_, g_, h_: jnp.sum((x_ + g_ * h_) * g),
                    argnums=(0, 1, 2))(x, gate, h)
    for name, a, b_ in zip(("dx", "dgate", "dh"), got, want):
        np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-5,
                                   err_msg=name)


def test_geglu_fwd_and_grads():
    proj = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 2 * 16))
    g = jax.random.normal(jax.random.PRNGKey(50), (2, 24, 16))
    out = fa.fused_geglu(proj, interpret=True, force_pallas=True)
    np.testing.assert_allclose(out, fa._xla_geglu(proj),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda p: jnp.sum(fa.fused_geglu(
        p, interpret=True, force_pallas=True) * g))(proj)
    want = jax.grad(lambda p: jnp.sum(fa._xla_geglu(p) * g))(proj)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_geglu_matches_geglufeedforward_composition():
    """The exact GEGLUFeedForward chain: gate is the FIRST half."""
    proj = jax.random.normal(jax.random.PRNGKey(6), (1, 8, 2 * 8))
    gate, val = jnp.split(proj, 2, axis=-1)
    want = val * jax.nn.gelu(gate)
    got = fa.fused_geglu(proj, interpret=True, force_pallas=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_bwd_ab_switch_matches(monkeypatch):
    """FLAXDIFF_FUSED_ADALN_BWD=xla (recompute-through-autodiff) and the
    Pallas backward must agree — the in-context A/B is only meaningful
    if both sides compute the same gradient."""
    x, (s, b, _, _), (g, _) = _inputs(jax.random.PRNGKey(7))

    def grad_of(x_):
        return jax.grad(lambda xx: jnp.sum(fa.fused_ln_modulate(
            xx, s, b, EPS, interpret=True, force_pallas=True) * g))(x_)

    g_pallas = grad_of(x)
    monkeypatch.setenv("FLAXDIFF_FUSED_ADALN_BWD", "xla")
    g_xla = grad_of(x)
    np.testing.assert_allclose(np.asarray(g_pallas), np.asarray(g_xla),
                               rtol=2e-3, atol=2e-3)


def test_dispatch_gating(monkeypatch):
    """Off-TPU default = XLA composition (and fused_adaln_active()
    False, so model layers take their original code path); =interpret
    forces the kernels; =xla forces them off even with interpret set
    elsewhere."""
    assert not fa.fused_adaln_active()      # CPU test runner
    monkeypatch.setenv("FLAXDIFF_FUSED_ADALN", "interpret")
    assert fa.fused_adaln_active()
    monkeypatch.setenv("FLAXDIFF_FUSED_ADALN", "xla")
    assert not fa.fused_adaln_active()


def test_unsupported_modulator_shapes_fall_back():
    """Per-token [B, L, C] modulators (3-D conditioning through
    AdaLNParams) must route to the XLA composition, not the kernel."""
    x, _, _ = _inputs(jax.random.PRNGKey(8))
    s = jax.random.normal(jax.random.PRNGKey(80), x.shape) * 0.1
    b = jnp.zeros_like(s)
    out = fa.fused_ln_modulate(x, s, b, EPS, interpret=True)
    np.testing.assert_allclose(out, _flax_ln(x) * (1 + s) + b,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("model_kind", ["dit", "mmdit"])
def test_model_interpret_parity_and_cpu_bit_identity(model_kind,
                                                     monkeypatch):
    """Model-level acceptance: (a) off-TPU outputs with the flag ON are
    bit-identical to the flag-OFF (pre-fusion) path — fusion is
    TPU-only by default; (b) under the interpret hook the fused model
    matches the unfused one numerically. Params are randomized because
    the zero-init final projection would otherwise make the comparison
    vacuous (all-zero outputs)."""
    from flaxdiff_tpu.models.dit import SimpleDiT
    from flaxdiff_tpu.models.mmdit import SimpleMMDiT

    kw = dict(patch_size=4, emb_features=32, num_layers=1, num_heads=2)
    if model_kind == "dit":
        fused_m, unfused_m = (SimpleDiT(**kw),
                              SimpleDiT(fused_epilogues=False, **kw))
    else:
        fused_m, unfused_m = (SimpleMMDiT(**kw),
                              SimpleMMDiT(fused_epilogues=False, **kw))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 3))
    t = jnp.array([0.3, 0.7])
    txt = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 12))
    params = jax.jit(fused_m.init)(jax.random.PRNGKey(2), x, t, txt)

    @jax.jit
    def randomized(params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(jax.random.PRNGKey(3), len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            jax.random.normal(k, l.shape, l.dtype) * 0.05
            for l, k in zip(leaves, keys)])

    params = randomized(params)
    out_flag_on = jax.jit(fused_m.apply)(params, x, t, txt)
    out_flag_off = jax.jit(unfused_m.apply)(params, x, t, txt)
    assert float(jnp.max(jnp.abs(out_flag_off))) > 1e-4  # not vacuous
    # (a) same platform, no env: flag on == flag off BIT-IDENTICALLY
    np.testing.assert_array_equal(np.asarray(out_flag_on),
                                  np.asarray(out_flag_off))
    # (b) interpret hook: real kernels, numeric parity
    monkeypatch.setenv("FLAXDIFF_FUSED_ADALN", "interpret")
    # a NEW function object: the hook is read while tracing, and jit's
    # cache of `fused_m.apply` holds the trace made without it
    def hooked(*a):
        return fused_m.apply(*a)

    assert "pallas_call" in str(jax.make_jaxpr(hooked)(params, x, t, txt))
    out_fused = jax.jit(hooked)(params, x, t, txt)
    np.testing.assert_allclose(np.asarray(out_fused),
                               np.asarray(out_flag_off),
                               rtol=1e-3, atol=1e-4)


def test_bf16_dtype_promotion_matches_composition():
    """Fused outputs must carry the same dtype the unfused chain
    produces (f32 norm x bf16 modulators -> f32; bf16 gate residual
    stays bf16)."""
    x, (s, b, _, _), _ = _inputs(jax.random.PRNGKey(9),
                                 dtype=jnp.bfloat16)
    out = fa.fused_ln_modulate(x, s, b, EPS, interpret=True,
                               force_pallas=True)
    ref = _flax_ln(x) * (1 + s) + b
    assert out.dtype == ref.dtype
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32),
                               rtol=3e-2, atol=3e-2)
    h = jax.random.normal(jax.random.PRNGKey(90), x.shape, jnp.bfloat16)
    got = fa.fused_gate_residual(x, s, h, interpret=True,
                                 force_pallas=True)
    assert got.dtype == (x + s * h).dtype == jnp.bfloat16


# -- TPU lowering and mesh partitioning, checked from the CPU -----------------

def _tpu_custom_calls(fn, *args) -> int:
    """Cross-lower `fn` for a TPU (Pallas -> Mosaic MLIR; nothing is
    compiled, so no chip is needed) and count its custom calls. A block
    shape the Pallas TPU lowering refuses raises here, on the CPU."""
    exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    return exp.mlir_module().count("tpu_custom_call")


@pytest.mark.parametrize("op", ["ln_modulate", "ln_modulate2",
                                "gate_residual", "geglu"])
def test_epilogue_grads_lower_for_tpu(op):
    """jax.grad of every fused epilogue lowers for platforms=["tpu"] at
    a shape with MORE THAN ONE row block (L=256, C=384 -> two): the
    per-block partial-sum outputs are where a (1, 1, C) block of a
    [B, nblk, C] array was refused."""
    b, l, c = 2, 256, 384
    x = jax.ShapeDtypeStruct((b, l, c), jnp.bfloat16)
    m = jax.ShapeDtypeStruct((b, 1, c), jnp.bfloat16)
    total = lambda out: sum(jnp.sum(o.astype(jnp.float32))
                            for o in jax.tree_util.tree_leaves(out))
    if op == "ln_modulate":
        fn, args = (lambda x, s, z: total(fa.fused_ln_modulate(
            x, s, z, EPS, force_pallas=True))), (x, m, m)
    elif op == "ln_modulate2":
        fn, args = (lambda x, s1, b1, s2, b2: total(fa.fused_ln_modulate2(
            x, s1, b1, s2, b2, EPS, force_pallas=True))), (x, m, m, m, m)
    elif op == "gate_residual":
        fn, args = (lambda x, g, h: total(fa.fused_gate_residual(
            x, g, h, force_pallas=True))), (x, m, x)
    else:
        fn, args = (lambda p: total(fa.fused_geglu(
            p, force_pallas=True))), (
            jax.ShapeDtypeStruct((b, l, 2 * c), jnp.bfloat16),)
    grad = jax.grad(fn, argnums=tuple(range(len(args))))
    assert _tpu_custom_calls(grad, *args) >= 1


def test_simple_dit_grad_lowers_for_tpu(monkeypatch):
    """fused_epilogues defaults to True, so on a TPU every DiT-family
    gradient runs through these kernels: the whole model's grad must
    lower (tokens 256 x emb 384 puts two row blocks in each epilogue)."""
    from flaxdiff_tpu.models.dit import SimpleDiT
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    model = SimpleDiT(patch_size=4, emb_features=384, num_layers=1,
                      num_heads=6, dtype=jnp.bfloat16)
    x = jnp.zeros((2, 64, 64, 3))
    t = jnp.zeros((2,))
    txt = jnp.zeros((2, 5, 12))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x, t, txt)
    loss = lambda p: jnp.sum(model.apply(p, x, t, txt) ** 2)
    assert _tpu_custom_calls(jax.grad(loss), params) >= 4


def test_fused_ops_run_per_device_under_a_mesh(mesh, monkeypatch):
    """GSPMD cannot partition a pallas_call: in a multi-device program
    on a TPU, jax refuses to lower one outside a shard_map. Under an
    active multi-device mesh each fused op must sit inside a shard_map
    over the batch axes — every pallas_call sees batch/8 —
    with values and gradients (the replicated GroupNorm scale/bias
    included) equal to the single-device result."""
    from flaxdiff_tpu.ops.fused_norm import fused_groupnorm_silu
    from flaxdiff_tpu.parallel import use_mesh

    monkeypatch.setenv("FLAXDIFF_FUSED_ADALN", "interpret")
    monkeypatch.setenv("FLAXDIFF_FUSED_NORM", "interpret")
    b, l, c = 8, 16, 32
    k = jax.random.PRNGKey(5)
    x = jax.random.normal(k, (b, l, c))
    mod = jax.random.normal(jax.random.fold_in(k, 1), (b, 1, c)) * 0.2
    scale = jax.random.normal(jax.random.fold_in(k, 2), (c,)) + 1.0
    bias = jax.random.normal(jax.random.fold_in(k, 3), (c,))

    def make_grad():
        # a fresh function per trace: the active mesh is read while
        # tracing and is not part of jit's cache key
        def loss(x, mod, scale, bias):
            v1, v2 = fa.fused_ln_modulate2(x, mod, mod, mod, mod, EPS)
            y = fa.fused_gate_residual(v1, mod, fa.fused_ln_modulate(
                v2, mod, mod, EPS))
            y = fa.fused_geglu(jnp.concatenate([y, x], axis=-1))
            y = fused_groupnorm_silu(y, scale, bias, groups=4)
            return jnp.sum(y ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))

    want = jax.jit(make_grad())(x, mod, scale, bias)
    with use_mesh(mesh):
        got = jax.jit(make_grad())(x, mod, scale, bias)
        jaxpr = jax.make_jaxpr(make_grad())(x, mod, scale, bias)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)

    batches = []

    def walk(jp, sharded):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                assert sharded, "pallas_call outside shard_map"
                batches.append(eqn.invars[0].aval.shape[0])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, sharded
                             or eqn.primitive.name == "shard_map")

    walk(jaxpr.jaxpr, False)
    assert len(batches) >= 10 and set(batches) == {b // 8}


def test_sharded_programs_lower_for_a_multi_device_tpu(mesh, monkeypatch):
    """The real train step and the sampler's scan program, with params
    sharded over the 8-device mesh, cross-lowered for TPU with every
    default-path kernel on. In a multi-device program jax refuses a
    pallas_call that is not inside a shard_map ("Mosaic kernels cannot
    be automatically partitioned"): that is what a four-chip run hits,
    and this reproduces it without a chip — the same programs traced
    WITHOUT the active mesh (no shard_map) must raise."""
    import optax

    from flaxdiff_tpu.models.unet import Unet
    from flaxdiff_tpu.ops import attention as att
    from flaxdiff_tpu.ops import fused_norm
    from flaxdiff_tpu.parallel import use_mesh
    from flaxdiff_tpu.predictors import EpsilonPredictionTransform
    from flaxdiff_tpu.samplers import DDIMSampler, DiffusionSampler
    from flaxdiff_tpu.schedulers import CosineNoiseSchedule
    from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

    # 32 channels at the attention level: GEGLU's F = 128, one lane tile
    model = Unet(output_channels=3, emb_features=16,
                 feature_depths=(32, 64), norm_groups=4,
                 attention_configs=({"heads": 2, "dim_head": 8}, None),
                 num_res_blocks=1, dtype=jnp.bfloat16)
    ctx = (77, 16)

    def apply_fn(params, x, t, cond):
        return model.apply({"params": params}, x, t, cond["text"])

    def init_fn(key):
        return model.init(key, jnp.zeros((1, 16, 16, 3)), jnp.zeros((1,)),
                          jnp.zeros((1,) + ctx))["params"]

    schedule, transform = (CosineNoiseSchedule(timesteps=1000),
                           EpsilonPredictionTransform())
    trainer = DiffusionTrainer(      # initialised on the CPU paths
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
        schedule=schedule, transform=transform, mesh=mesh,
        config=TrainerConfig(normalize=False),
        null_cond={"text": np.zeros((1,) + ctx, np.float32)})
    batch = trainer.put_batch({
        "sample": np.zeros((16, 16, 16, 3), np.float32),
        "cond": {"text": np.zeros((16,) + ctx, np.float32)}})
    # from here on, dispatch as on a TPU
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(att, "_flash_on_tpu", lambda: True)
    monkeypatch.setattr(fused_norm, "_use_pallas",
                        lambda interpret, force: (True, False))

    with use_mesh(mesh):
        assert _tpu_custom_calls(trainer._step, trainer.state, batch) > 20

    def sampler_program():      # a fresh trace each time (see above)
        ds = DiffusionSampler(
            model_fn=lambda p, x, t, c: apply_fn(p, x, t, {"text": c}),
            schedule=schedule, transform=transform,
            sampler=DDIMSampler(), guidance_scale=2.0)
        return ds._get_program(2, (8, 16, 16, 3), None, 0.0)

    text = jnp.zeros((8,) + ctx)
    args = (trainer.get_params(use_ema=False), jnp.zeros((8, 16, 16, 3)),
            jax.random.PRNGKey(0), text, text)
    with use_mesh(mesh):
        assert _tpu_custom_calls(sampler_program(), *args) > 10
    with pytest.raises(NotImplementedError, match="shard_map"):
        _tpu_custom_calls(sampler_program(), *args)
