"""Tensor parallelism: Megatron partition rules + head-sharded attention.

The reference has no TP of any kind (SURVEY §2 parallelism audit). Here
TP is a mesh decision: a >1 `tensor` axis makes `fsdp_sharding_tree`
emit column/row-parallel specs for attention and MLP projections, GSPMD
inserts the all-reduce at the row-parallel contraction, and a DiT must
train with numerics matching a replicated run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from flaxdiff_tpu.models.dit import SimpleDiT
from flaxdiff_tpu.parallel import create_mesh
from flaxdiff_tpu.parallel.partition import infer_tp_spec
from flaxdiff_tpu.predictors import EpsilonPredictionTransform
from flaxdiff_tpu.schedulers import CosineNoiseSchedule
from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig


@pytest.fixture(scope="module")
def tp_mesh():
    return create_mesh(axes={"data": 2, "fsdp": 2, "tensor": 2})


class TestInferTPSpec:
    def test_qkv_densegeneral_shards_heads(self, tp_mesh):
        spec = infer_tp_spec("blk/attn/to_q/kernel", (64, 8, 16), tp_mesh)
        assert spec[1] == "tensor"
        spec = infer_tp_spec("blk/attn/to_q/bias", (8, 16), tp_mesh)
        assert spec == P("tensor", None)

    def test_out_proj_shards_input_heads(self, tp_mesh):
        spec = infer_tp_spec("blk/attn/to_out/kernel", (8, 16, 64), tp_mesh)
        assert spec[0] == "tensor"
        # row-parallel bias replicated (added after the reduction)
        assert infer_tp_spec("blk/attn/to_out/bias", (64,), tp_mesh) == P()

    def test_mlp_column_row(self, tp_mesh):
        assert infer_tp_spec("blk/mlp_in/kernel", (64, 256), tp_mesh)[1] \
            == "tensor"
        assert infer_tp_spec("blk/mlp_out/kernel", (256, 64), tp_mesh)[0] \
            == "tensor"

    def test_2d_tp_plus_fsdp(self, tp_mesh):
        spec = infer_tp_spec("blk/mlp_in/kernel", (64, 256), tp_mesh,
                             min_size_2d=0)
        assert spec == P("fsdp", "tensor")
        # below the 2-D threshold: tensor axis only
        assert infer_tp_spec("blk/mlp_in/kernel", (64, 256), tp_mesh) \
            == P(None, "tensor")

    def test_non_matching_and_indivisible_fall_through(self, tp_mesh):
        assert infer_tp_spec("conv/kernel", (3, 3, 64, 64), tp_mesh) is None
        # heads=3 doesn't divide tensor=2
        assert infer_tp_spec("a/to_q/kernel", (64, 3, 16), tp_mesh) is None

    def test_no_tensor_axis_is_none(self, mesh):
        assert infer_tp_spec("a/to_q/kernel", (64, 8, 16), mesh) is None

    def test_conv_projection_rank_guard(self, tp_mesh):
        # a conv-variant proj_in ([kh, kw, cin, cout], rank 4) must not be
        # head-sharded by the Dense rules
        assert infer_tp_spec("t/proj_in/kernel", (3, 3, 64, 64),
                             tp_mesh) is None


def _make_dit_trainer(mesh, seed=0):
    model = SimpleDiT(output_channels=3, patch_size=4, emb_features=32,
                      num_layers=2, num_heads=4, backend="xla")

    def apply_fn(params, x, t, cond):
        text = cond["text"] if cond is not None else None
        return model.apply({"params": params}, x, t, text)

    def init_fn(key):
        return model.init(key, jnp.zeros((1, 16, 16, 3)), jnp.zeros((1,)),
                          jnp.zeros((1, 4, 32)))["params"]

    return DiffusionTrainer(
        apply_fn=apply_fn, init_fn=init_fn, tx=optax.adam(1e-3),
        schedule=CosineNoiseSchedule(timesteps=1000),
        transform=EpsilonPredictionTransform(),
        mesh=mesh,
        config=TrainerConfig(uncond_prob=0.0, normalize=False,
                             weighted_loss=False, log_every=2, seed=seed),
        null_cond={"text": jnp.zeros((1, 4, 32))})


def _batches(n, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    return [{
        "sample": rng.normal(size=(batch, 16, 16, 3)).astype(np.float32) * 0.3,
        "cond": {"text": rng.normal(size=(batch, 4, 32)).astype(np.float32)},
    } for _ in range(n)]


@pytest.fixture(scope="module")
def tp_trainer(tp_mesh):
    """The TP trainer whose shardings one test reads and which another
    fits: built (and its state initialised) once."""
    return _make_dit_trainer(tp_mesh)


class TestTensorParallelTraining:
    def test_dit_params_are_head_sharded(self, tp_trainer):
        tr = tp_trainer
        flat = {"/".join(str(getattr(p, "key", p)) for p in path): leaf
                for path, leaf in
                jax.tree_util.tree_leaves_with_path(tr.state.params)}
        qkv = [v for k, v in flat.items() if k.endswith("to_q/kernel")]
        assert qkv, f"no to_q kernels found in {list(flat)[:8]}"
        for leaf in qkv:
            assert "tensor" in str(leaf.sharding.spec), leaf.sharding.spec
        mlp_out = [v for k, v in flat.items() if k.endswith("mlp_out/kernel")]
        for leaf in mlp_out:
            assert str(leaf.sharding.spec).startswith("PartitionSpec('tensor'")

    def test_tp_training_matches_replicated(self, tp_mesh):
        """The TP program must compute the same function: identical loss
        trajectory to a single-axis run with identical data and seeds.

        Needs partitionable threefry: jax 0.4.37 defaults
        `jax_threefry_partitionable` to False, under which the values
        `jax.random` produces INSIDE a jitted program depend on the
        output sharding — the tensor-sharded `to_out`/`mlp_out` kernels
        draw different init bits on the TP mesh than on the replicated
        one (measured: max |Δparam| 0.53 at init, 1.7% step-1 loss
        drift — two different models, not a numerics bug). With the
        flag on, draws are sharding-invariant: both meshes start from
        identical weights and the trajectories agree to reduction-order
        rounding (measured max rel diff 1.2e-7, bar 2e-4)."""
        prev = jax.config.jax_threefry_partitionable
        jax.config.update("jax_threefry_partitionable", True)
        try:
            tp = _make_dit_trainer(tp_mesh)
            rep = _make_dit_trainer(create_mesh(axes={"data": -1}))
            losses_tp, losses_rep = [], []
            for b in _batches(4):
                losses_tp.append(float(tp.train_step(tp.put_batch(b))))
                losses_rep.append(float(rep.train_step(rep.put_batch(b))))
        finally:
            jax.config.update("jax_threefry_partitionable", prev)
        np.testing.assert_allclose(losses_tp, losses_rep, rtol=2e-4,
                                   atol=1e-5)

    def test_tp_loss_decreases(self, tp_trainer):
        tr = tp_trainer
        hist = tr.fit(iter(_batches(40)), total_steps=40)
        assert np.isfinite(hist["final_loss"])
        assert hist["final_loss"] < hist["loss"][0]


class TestShardMappedFlash:
    def test_flash_specs(self, tp_mesh):
        from flaxdiff_tpu.ops.attention import _flash_specs
        assert _flash_specs(tp_mesh, n_batch=8, n_heads=4) == \
            (("data", "fsdp"), "tensor")
        # heads don't divide the tensor axis
        assert _flash_specs(tp_mesh, n_batch=8, n_heads=3) is None
        # batch doesn't divide data*fsdp
        assert _flash_specs(tp_mesh, n_batch=2, n_heads=4) is None
        seq_mesh = create_mesh(axes={"data": 2, "seq": 4})
        assert _flash_specs(seq_mesh, n_batch=8, n_heads=4) is None

    def test_shard_mapped_flash_matches_xla(self, tp_mesh, rng):
        from flaxdiff_tpu.ops.attention import (_shard_mapped_flash,
                                                _xla_attention)
        B, L, H, D = 4, 32, 4, 8
        q = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
        scale = 1.0 / (D ** 0.5)
        out = _shard_mapped_flash(q, k, v, scale, tp_mesh,
                                  ("data", "fsdp"), "tensor",
                                  interpret=True)
        ref = _xla_attention(q, k, v, scale=scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_shard_mapped_flash_cross_attention(self, tp_mesh, rng):
        from flaxdiff_tpu.ops.attention import (_shard_mapped_flash,
                                                _xla_attention)
        B, Lq, Lk, H, D = 4, 32, 7, 4, 8
        q = jnp.asarray(rng.normal(size=(B, Lq, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, Lk, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, Lk, H, D)), jnp.float32)
        scale = 1.0 / (D ** 0.5)
        out = _shard_mapped_flash(q, k, v, scale, tp_mesh,
                                  ("data", "fsdp"), "tensor",
                                  interpret=True)
        ref = _xla_attention(q, k, v, scale=scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_gradients_flow_through_shard_map(self, tp_mesh, rng):
        from flaxdiff_tpu.ops.attention import (_shard_mapped_flash,
                                                _xla_attention)
        B, L, H, D = 4, 16, 2, 8
        q = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
        scale = 1.0 / (D ** 0.5)

        def loss_sm(q):
            return jnp.sum(_shard_mapped_flash(
                q, k, v, scale, tp_mesh, ("data", "fsdp"), None,
                interpret=True) ** 2)

        def loss_ref(q):
            return jnp.sum(_xla_attention(q, k, v, scale=scale) ** 2)

        g_sm = jax.grad(loss_sm)(q)
        g_ref = jax.grad(loss_ref)(q)
        np.testing.assert_allclose(np.asarray(g_sm), np.asarray(g_ref),
                                   rtol=5e-4, atol=5e-4)
