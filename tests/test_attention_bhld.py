"""BHLD attention layout (VERDICT r3 weak #2c: layout-copy elimination).

The BHLD path folds the head permutation into the q/k/v projection
matmuls and feeds the flash kernel its native [B*H, L, D] layout via
free reshapes — no transposes for XLA to materialize around the pallas
custom call. Parameters are layout-independent, so the SAME checkpoint
must produce the SAME function in either layout.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.models.attention import AttentionLayer


def _mk(bhld, heads=2, dim_head=8):
    return AttentionLayer(heads=heads, dim_head=dim_head, backend="xla",
                          bhld=bhld)


def test_param_trees_are_layout_independent():
    x = jnp.ones((2, 16, 12))
    p_ref = jax.jit(_mk(False).init)(jax.random.PRNGKey(0), x)["params"]
    p_bh = jax.jit(_mk(True).init)(jax.random.PRNGKey(0), x)["params"]
    flat_ref = jax.tree_util.tree_leaves_with_path(p_ref)
    flat_bh = jax.tree_util.tree_leaves_with_path(p_bh)
    assert [(jax.tree_util.keystr(p), l.shape) for p, l in flat_ref] == \
           [(jax.tree_util.keystr(p), l.shape) for p, l in flat_bh]


@pytest.mark.parametrize("cross", [False, True])
def test_same_params_same_function(cross):
    """One param tree, both layouts, identical outputs (self and cross,
    spatial and sequence inputs) to float tolerance."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 4, 4, 12)), jnp.float32)
    ctx = (jnp.asarray(rng.normal(size=(2, 7, 12)), jnp.float32)
           if cross else None)
    params = jax.jit(_mk(False).init)(
        jax.random.PRNGKey(1), x, ctx)["params"]
    out_ref = jax.jit(_mk(False).apply)({"params": params}, x, ctx)
    out_bh = jax.jit(_mk(True).apply)({"params": params}, x, ctx)
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_bh),
                               rtol=2e-5, atol=2e-6)


def test_same_params_same_gradients():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 16, 12)), jnp.float32)
    params = jax.jit(_mk(False).init)(jax.random.PRNGKey(2), x)["params"]

    def loss(p, bhld):
        return jnp.sum(_mk(bhld).apply({"params": p}, x) ** 2)

    grad = jax.jit(jax.grad(loss), static_argnums=1)
    g_ref = grad(params, False)
    g_bh = grad(params, True)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
        g_ref, g_bh)


def test_flash_bh_interpret_parity():
    """flash_attention_bh (the BHLD entry point) against the direct
    softmax oracle in interpret mode with the hardware lane layout."""
    import flaxdiff_tpu.ops.flash_attention as fa

    old = fa._FORCE_LANES
    fa._FORCE_LANES = fa.LANES
    try:
        rng = np.random.default_rng(2)
        bh, lq, lk, d = 4, 64, 48, 16
        q = jnp.asarray(rng.normal(size=(bh, lq, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(bh, lk, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(bh, lk, d)), jnp.float32)

        def loss(q, k, v):
            return fa.flash_attention_bh(q, k, v, None, None, None,
                                         True).sum()

        out = jax.jit(lambda *a: fa.flash_attention_bh(
            *a, None, None, None, True))(q, k, v)
        ref = jax.nn.softmax(
            (q @ k.transpose(0, 2, 1)) / d ** 0.5, axis=-1) @ v
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

        def oracle(q, k, v):
            return jnp.sum(jax.nn.softmax(
                (q @ k.transpose(0, 2, 1)) / d ** 0.5, axis=-1) @ v)

        g_ref = jax.jit(jax.grad(oracle, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)
    finally:
        fa._FORCE_LANES = old


def test_bhld_env_toggle(monkeypatch):
    """bhld=None reads FLAXDIFF_ATTN_BHLD (the A/B knob)."""
    x = jnp.ones((1, 16, 8))
    layer = AttentionLayer(heads=2, dim_head=4, backend="xla")
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]
    out_off = layer.apply({"params": params}, x)
    monkeypatch.setenv("FLAXDIFF_ATTN_BHLD", "1")
    out_on = layer.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(out_off), np.asarray(out_on),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("cross", [False, True])
def test_rope_attention_layouts_agree(cross):
    """RoPEAttention (the DiT family's attention) with one param tree in
    both layouts — RoPE is position-elementwise, so the rotation is
    layout-independent."""
    from flaxdiff_tpu.models.vit_common import RoPEAttention

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 16, 12)), jnp.float32)
    ctx = (jnp.asarray(rng.normal(size=(2, 9, 12)), jnp.float32)
           if cross else None)
    mk = lambda bhld: RoPEAttention(heads=2, dim_head=8, backend="xla",
                                    bhld=bhld)
    params = jax.jit(mk(False).init)(
        jax.random.PRNGKey(0), x, ctx)["params"]
    out_ref = jax.jit(mk(False).apply)({"params": params}, x, ctx)
    out_bh = jax.jit(mk(True).apply)({"params": params}, x, ctx)
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_bh),
                               rtol=2e-5, atol=2e-6)

    def loss(p, bhld):
        return jnp.sum(mk(bhld).apply({"params": p}, x, ctx) ** 2)

    grad = jax.jit(jax.grad(loss), static_argnums=1)
    g_ref = grad(params, False)
    g_bh = grad(params, True)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
        g_ref, g_bh)


def test_fresh_inits_are_layout_identical():
    """Same seed, both layouts, BOTH module families: bit-identical
    fresh params (the projections wrap the same init on the same
    flattened shape under the same param RNG path — a narrower init in
    one layout would silently confound from-scratch comparisons)."""
    from flaxdiff_tpu.models.vit_common import RoPEAttention

    x = jnp.ones((1, 16, 12))
    for mk in (lambda b: AttentionLayer(heads=2, dim_head=8,
                                        backend="xla", bhld=b),
               lambda b: RoPEAttention(heads=2, dim_head=8,
                                       backend="xla", bhld=b)):
        p_ref = jax.jit(mk(False).init)(jax.random.PRNGKey(5), x)["params"]
        p_bh = jax.jit(mk(True).init)(jax.random.PRNGKey(5), x)["params"]
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)),
            p_ref, p_bh)


def test_flash_interpret_dispatch_in_full_model(monkeypatch):
    """FLAXDIFF_FLASH_INTERPRET routes the REAL flash kernel (via the
    Pallas interpreter, hardware lane layout) through the normal
    dispatch inside a full model fwd+bwd — the in-context integration
    coverage that CPU CI otherwise lacks (an on-chip failure inside
    the train step is otherwise unattributable between the kernel and
    everything around it). Runs both layouts."""
    import flaxdiff_tpu.ops.flash_attention as fa
    from flaxdiff_tpu.models.attention import TransformerBlock

    monkeypatch.setenv("FLAXDIFF_FLASH_INTERPRET", "1")
    monkeypatch.setenv("FLAXDIFF_FLASH_BLOCK_Q", "512")
    monkeypatch.setenv("FLAXDIFF_FLASH_BLOCK_K", "1024")
    monkeypatch.setenv("FLAXDIFF_FLASH_NATIVE_D", "1")
    monkeypatch.setattr(fa, "_FORCE_LANES", fa.LANES)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 16, 16, 24)), jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(1, 7, 24)), jnp.float32)
    for bhld in (False, True):
        block = TransformerBlock(heads=2, dim_head=8, backend="flash",
                                 bhld=bhld)
        params = jax.jit(block.init)(
            jax.random.PRNGKey(0), x, ctx)["params"]

        def loss(p):
            return jnp.sum(block.apply({"params": p}, x, ctx) ** 2)

        val, grads = jax.jit(jax.value_and_grad(loss))(params)
        assert np.isfinite(float(val))
        assert all(np.isfinite(np.asarray(g)).all()
                   for g in jax.tree_util.tree_leaves(grads))


def test_bhld_multidevice_shard_mapped_flash(monkeypatch):
    """On a >1-device mesh the BHLD dispatcher must keep the native
    [B,H,L,D] shard_map path for batch/head-sharded flash (ADVICE r4:
    routing multi-device through the transposing BLHD dispatcher lost
    the layout win on production configs) — and match XLA numerically.
    Interpret mode runs the real kernel on the virtual CPU mesh."""
    import flaxdiff_tpu.ops.flash_attention as fa
    from flaxdiff_tpu.ops.attention import (_xla_attention_bhld,
                                            dot_product_attention_bhld)
    from flaxdiff_tpu.parallel import create_mesh, use_mesh

    monkeypatch.setenv("FLAXDIFF_FLASH_INTERPRET", "1")
    monkeypatch.setattr(fa, "_FORCE_LANES", fa.LANES)
    mesh = create_mesh(axes={"data": -1})
    n = mesh.devices.size
    assert n > 1, "virtual mesh fixture must expose >1 device"

    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(n, 2, 128, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(n, 2, 128, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n, 2, 128, 8)), jnp.float32)
    want = _xla_attention_bhld(q, k, v)
    with use_mesh(mesh):
        got = dot_product_attention_bhld(q, k, v, backend="flash")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    # gradients flow through the shard-mapped custom_vjp path
    def loss(q):
        with use_mesh(mesh):
            return jnp.sum(dot_product_attention_bhld(
                q, k, v, backend="flash") ** 2)

    def loss_ref(q):
        return jnp.sum(_xla_attention_bhld(q, k, v) ** 2)

    g = jax.grad(loss)(q)
    g_ref = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=5e-4, rtol=5e-4)

    # a shape that doesn't tile the mesh still answers correctly via
    # the BLHD fallback route
    q3 = jnp.asarray(rng.normal(size=(3, 2, 128, 8)), jnp.float32)
    with use_mesh(mesh):
        got3 = dot_product_attention_bhld(q3, q3, q3, backend="flash")
    np.testing.assert_allclose(
        np.asarray(got3), np.asarray(_xla_attention_bhld(q3, q3, q3)),
        atol=2e-5, rtol=2e-5)


def test_bhld_ring_backend_matches_xla():
    """BHLD dispatcher + backend='ring' under a seq mesh: the
    sequence-parallel route goes through the BLHD dispatcher (one
    transpose each way) and must stay numerically exact."""
    from flaxdiff_tpu.ops.attention import (_xla_attention_bhld,
                                            dot_product_attention_bhld)
    from flaxdiff_tpu.parallel import create_mesh, use_mesh

    mesh = create_mesh(axes={"data": 2, "seq": 4})
    rng = np.random.default_rng(11)
    # [B, H, L, D]; L divisible by the seq axis, B by the data axis
    q = jnp.asarray(rng.normal(size=(2, 2, 32, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, 32, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, 32, 16)), jnp.float32)
    want = _xla_attention_bhld(q, k, v)
    with use_mesh(mesh):
        got = dot_product_attention_bhld(q, k, v, backend="ring")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    with use_mesh(mesh):
        got_u = dot_product_attention_bhld(q, k, v, backend="ulysses")
    np.testing.assert_allclose(np.asarray(got_u), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
