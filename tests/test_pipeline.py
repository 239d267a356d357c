"""Pipeline parallelism: GPipe-over-`pipe`-axis numerical parity.

The reference has no pipeline parallelism (single-host pmap loop);
these tests pin the new axis against plain sequential block
application — forward AND gradients, with data x pipe mesh
composition and varying microbatch counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flaxdiff_tpu.models.dit import DiTBlock
from flaxdiff_tpu.parallel import create_mesh
from flaxdiff_tpu.parallel.pipeline import (
    pipeline_blocks,
    stack_block_params,
)

FEAT, HEADS, TOKENS = 16, 2, 8
N_BLOCKS = 8


@pytest.fixture(scope="module")
def blocks():
    block = DiTBlock(features=FEAT, num_heads=HEADS, dtype=None)
    key = jax.random.PRNGKey(0)
    x0 = jnp.zeros((1, TOKENS, FEAT))
    c0 = jnp.zeros((1, FEAT))
    init = jax.jit(block.init)
    params = [init(jax.random.fold_in(key, i), x0, c0)["params"]
              for i in range(N_BLOCKS)]
    stacked = stack_block_params(params)

    def block_fn(p, h, c):
        return block.apply({"params": p}, h, c)

    return block_fn, stacked


def _sequential(block_fn, stacked, x, cond):
    def body(h, p):
        return block_fn(p, h, cond), None
    out, _ = jax.lax.scan(body, x, stacked)
    return out


def _data(batch, seed=1):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (batch, TOKENS, FEAT))
    cond = jax.random.normal(jax.random.fold_in(key, 1), (batch, FEAT))
    return x, cond


@pytest.mark.parametrize("axes,mb", [
    ({"data": 2, "pipe": 4}, 4),
    ({"data": 2, "pipe": 4}, 8),   # more microbatches than stages
    ({"pipe": 8}, 8),              # pipe-only mesh
    ({"data": 4, "pipe": 2}, 2),
])
def test_pipeline_matches_sequential_fwd_and_grad(blocks, axes, mb):
    block_fn, stacked = blocks
    mesh = create_mesh(axes=axes)
    x, cond = _data(batch=16)

    def pipe_loss(params, x, cond):
        out = pipeline_blocks(block_fn, params, x, cond, mesh,
                              num_microbatches=mb)
        return jnp.sum(out ** 2), out

    def seq_loss(params, x, cond):
        out = _sequential(block_fn, params, x, cond)
        return jnp.sum(out ** 2), out

    (pl, pout), pgrad = jax.jit(
        jax.value_and_grad(pipe_loss, argnums=(0, 1, 2), has_aux=True)
    )(stacked, x, cond)
    (sl, sout), sgrad = jax.jit(
        jax.value_and_grad(seq_loss, argnums=(0, 1, 2), has_aux=True)
    )(stacked, x, cond)

    np.testing.assert_allclose(pout, sout, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(pl, sl, rtol=2e-5)
    np.testing.assert_allclose(pgrad[1], sgrad[1], rtol=2e-4, atol=2e-4)
    # cond rides the most novel AD route (per-stage local reads across
    # the tick schedule) — pin its gradient too
    np.testing.assert_allclose(pgrad[2], sgrad[2], rtol=2e-4, atol=2e-4)
    for (pa, pleaf), (_, sleaf) in zip(
            jax.tree_util.tree_leaves_with_path(pgrad[0]),
            jax.tree_util.tree_leaves_with_path(sgrad[0])):
        np.testing.assert_allclose(
            pleaf, sleaf, rtol=2e-4, atol=2e-4,
            err_msg=jax.tree_util.keystr(pa))


def test_pipeline_no_remat_matches(blocks):
    block_fn, stacked = blocks
    mesh = create_mesh(axes={"data": 2, "pipe": 4})
    x, cond = _data(batch=8, seed=3)
    # each side one compiled program: remat changes what the backward
    # keeps, and XLA may fuse the two forwards differently
    with_remat = jax.jit(lambda *a: pipeline_blocks(
        block_fn, *a, mesh, remat=True))(stacked, x, cond)
    without = jax.jit(lambda *a: pipeline_blocks(
        block_fn, *a, mesh, remat=False))(stacked, x, cond)
    np.testing.assert_allclose(with_remat, without, rtol=1e-6)


@pytest.mark.parametrize("order", ["raster", "hilbert"])
def test_pipelined_dit_matches_plain_apply(order):
    """Full-model integration: a normally-initialized SimpleDiT applied
    through pipelined_dit_apply must reproduce dit.apply exactly —
    embed/cond/final replicated, trunk pipelined, existing checkpoints
    reusable without re-init."""
    from flaxdiff_tpu.models.dit import SimpleDiT
    from flaxdiff_tpu.parallel.pipeline import pipelined_dit_apply

    dit = SimpleDiT(output_channels=3, patch_size=4, emb_features=FEAT,
                    num_layers=4, num_heads=HEADS,
                    use_hilbert=(order == "hilbert"))
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (8, 16, 16, 3))
    t = jax.random.uniform(jax.random.fold_in(key, 1), (8,))
    txt = jax.random.normal(jax.random.fold_in(key, 2), (8, 4, FEAT))
    params = jax.jit(dit.init)(
        jax.random.fold_in(key, 3), x, t, txt)["params"]

    want = jax.jit(dit.apply)({"params": params}, x, t, txt)
    mesh = create_mesh(axes={"data": 2, "pipe": 4})
    got = jax.jit(lambda p, x_, t_, c_: pipelined_dit_apply(
        dit, p, x_, t_, c_, mesh))(params, x, t, txt)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_pipeline_rejects_bad_divisibility(blocks):
    block_fn, stacked = blocks
    mesh = create_mesh(axes={"data": 2, "pipe": 4})
    x, cond = _data(batch=6)
    with pytest.raises(ValueError, match="microbatches"):
        pipeline_blocks(block_fn, stacked, x, cond, mesh,
                        num_microbatches=4)
    three = jax.tree_util.tree_map(lambda leaf: leaf[:3], stacked)
    x, cond = _data(batch=8)
    with pytest.raises(ValueError, match="stages"):
        pipeline_blocks(block_fn, three, x, cond, mesh)
