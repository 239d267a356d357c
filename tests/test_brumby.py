"""The `brumby` denoiser trunk (models/brumby.py), gated power retention
(ops/power_retention.py: the pairwise form) against the two state forms
written here as its witnesses (chunked, and the token-by-token
recurrence), and what the two trunks share (models/trunk.py), against
the plain reference (benchmark/reference/brumby.py) at small sizes on
the CPU."""
import importlib.util
import json
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
from flaxdiff_tpu.ops import power_retention as pr  # noqa: E402

# the published 40 query / 8 key-value heads shrunk to 10 / 2: a group of 5
SMALL = dict(hidden_size=64, head_dim=16, num_attention_heads=10,
             num_key_value_heads=2, intermediate_size=96,
             num_hidden_layers=2, dtype="float32", patch_size=2,
             output_channels=2)
RES, CH, TOK, FEAT = 8, 2, 5, 12
GATES = {"seeded": None, "open": 6.0}   # the gate's bias leaves set to +6


def _seeded(model, key=7, gate_bias=None):
    from harness import weights
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, RES, RES, CH)), jnp.zeros((1,)),
                             jnp.zeros((1, TOK, FEAT)))["params"],
        jax.random.PRNGKey(0))
    params = jax.jit(lambda k: weights.fill_params(shapes, k))(
        jax.random.PRNGKey(key))
    if gate_bias is not None:
        for name in params:
            if name.startswith("layer_"):
                params[name]["to_gate"]["bias"] = jnp.full_like(
                    params[name]["to_gate"]["bias"], gate_bias)
    return params


def _inputs(batch=2, key=3):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(ks[0], (batch, RES, RES, CH)),
            jnp.linspace(20.0, 900.0, batch),
            jax.random.normal(ks[1], (batch, TOK, FEAT)))


def _qkvg(b=2, t=50, h=10, kv=2, d=16, bias=0.0, dtype=jnp.float32, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    q = jax.random.normal(ks[0], (b, t, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, kv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, kv, d)).astype(dtype)
    log_g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (b, t, kv)) + bias)
    return q, k, v, log_g


def _phi(u):
    """The symmetric square by its definition: u_i u_j for i <= j, times
    sqrt 2 where i < j (numpy, float64)."""
    i, j = np.triu_indices(u.shape[-1])
    return u[..., i] * u[..., j] * np.where(i == j, 1.0, np.sqrt(2.0))


def _recurrence(q, k, v, log_g, eps=1e-6):
    """S_t = g_t S_{t-1} + phi(k_t) v_t^T, z_t = g_t z_{t-1} + phi(k_t),
    o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps), a token at a time in
    float64."""
    q, k, v, log_g = (np.asarray(a, np.float64) for a in (q, k, v, log_g))
    b, t, h, d = q.shape
    kv = k.shape[2]
    out = np.zeros((b, t, h, d))
    for bi in range(b):
        for j in range(kv):
            s = np.zeros((d * (d + 1) // 2, d))
            z = np.zeros(d * (d + 1) // 2)
            for ti in range(t):
                g, pk = np.exp(log_g[bi, ti, j]), _phi(k[bi, ti, j] / d ** 0.25)
                s, z = g * s + np.outer(pk, v[bi, ti, j]), g * z + pk
                for i in range(j * h // kv, (j + 1) * h // kv):
                    pq = _phi(q[bi, ti, i] / d ** 0.25)
                    out[bi, ti, i] = pq @ s / (pq @ z + eps)
    return out


def symmetric_square(u):
    """phi(u) over the last axis [..., D] -> [..., D (D + 1) / 2]: row i
    of the upper triangle of u u^T, `u_i * u[i:]`, its off-diagonal
    entries times sqrt 2 (jax, static slices)."""
    d = u.shape[-1]
    root2 = np.sqrt(2.0).astype(np.float32)
    return jnp.concatenate(
        [u[..., i:i + 1] * u[..., i:] * np.where(
            np.arange(d - i) == 0, np.float32(1.0), root2)
         for i in range(d)], axis=-1)


def _chunked(q, k, v, log_g, chunk, eps=1e-6):
    """The chunked state form (degree 2), a witness of the pairwise form
    and the shape a state kernel would take (ROADMAP M6): a `lax.scan`
    over chunks carries `S` and `z` in float32; the pairs inside a chunk
    go the pairwise way (`pr._pairs`), earlier chunks arrive through the
    carry, and every exponent is a difference of `G` inside one chunk
    that is never positive."""
    b, t, h, d = q.shape
    n = -(-t // chunk)
    # a padded token has k = v = 0 and gate 1: it adds nothing to the
    # state and decays nothing; its own output is cut off
    pad = lambda x: jnp.pad(x, [(0, 0), (0, n * chunk - t)]
                            + [(0, 0)] * (x.ndim - 2))
    root = 1.0 / d ** 0.25      # phi(q root) . phi(k root) = (q . k)^2 / d
    feats = d * (d + 1) // 2

    def head(args):
        qh, kh, vh, lg = args   # [B, T, G, D], [B, T, D] x 2, [B, T]

        def step(carry, xs):
            s_prev, z_prev = carry          # [B, P, D], [B, P]
            qc, kc, vc, lc = xs             # one chunk: [B, C, ...]
            a = jnp.cumsum(lc, axis=1)      # G inside the chunk: <= 0
            end = a[:, -1]
            num, den = pr._pairs(qc, kc, vc, a, a, 0, 1.0 / d ** 0.5, 2)
            pq = symmetric_square(qc * root)
            reach = jnp.exp(a)[:, :, None]
            num += reach[..., None] * jnp.einsum("bcgp,bpd->bcgd", pq, s_prev)
            den += reach * jnp.einsum("bcgp,bp->bcg", pq, z_prev)
            pk = symmetric_square(kc * root) * jnp.exp(
                end[:, None] - a)[..., None]
            keep = jnp.exp(end)[:, None]
            s_next = keep[..., None] * s_prev + jnp.einsum(
                "bcp,bcd->bpd", pk, vc)
            return (s_next, keep * z_prev + pk.sum(1)), \
                num / (den + eps)[..., None]

        chunks = lambda x: x.reshape((b, n, chunk) + x.shape[2:]).swapaxes(
            0, 1)
        zero = (jnp.zeros((b, feats, d)), jnp.zeros((b, feats)))
        _, out = jax.lax.scan(step, zero, tuple(
            chunks(x) for x in (qh, kh, vh, lg)))
        return out.swapaxes(0, 1).reshape((b, n * chunk) + qh.shape[2:])

    out = jax.lax.map(head, pr._by_kv_head(*(pad(a) for a in
                                             (q, k, v, log_g))))
    return pr._from_kv_head(out[:, :, :t], b, t, h, d)


def _dense(q, k, v, log_g, degree=2, eps=1e-6):
    """The definition with every pair standing at once (numpy,
    float64)."""
    q, k, v, log_g = (np.asarray(a, np.float64) for a in (q, k, v, log_g))
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v, big_g = (np.repeat(a, group, axis=2) for a in (
        k, v, np.cumsum(log_g, axis=1)))
    s = np.einsum("bthd,bshd->bhts", q, k) / q.shape[-1] ** 0.5
    decay = np.exp(np.minimum(
        big_g.transpose(0, 2, 1)[..., :, None]
        - big_g.transpose(0, 2, 1)[..., None, :], 0.0))
    w = s ** degree * decay * np.tril(np.ones((t, t)))
    return np.einsum("bhts,bshd->bthd", w, v) / (
        w.sum(-1).transpose(0, 2, 1)[..., None] + eps)


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- the retention's forms ---------------------------------------------------

def test_phi_of_q_dot_phi_of_k_is_the_square_of_q_dot_k(highest):
    q, k, _, _ = _qkvg(t=7, d=16)
    got = jnp.einsum("bthp,bthp->bth", symmetric_square(q),
                     symmetric_square(jnp.repeat(k, 5, axis=2)))
    want = jnp.einsum("bthd,bthd->bth", q, jnp.repeat(k, 5, axis=2)) ** 2
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert symmetric_square(q).shape[-1] == 16 * 17 // 2
    np.testing.assert_allclose(symmetric_square(q),
                               _phi(np.asarray(q, np.float64)), rtol=1e-6)
    assert 128 * 129 // 2 == 8256       # a head of 128, as published


@pytest.mark.parametrize("gate", sorted(GATES))
@pytest.mark.parametrize("chunk", [8, 16, 25, 64])     # 50 tokens: 8 and
def test_pairwise_chunked_and_the_recurrence_agree(     # 16 do not divide
        highest, chunk, gate):
    q, k, v, log_g = _qkvg(bias=GATES[gate] or 0.0)
    want = _recurrence(q, k, v, log_g)
    np.testing.assert_allclose(pr.power_retention(q, k, v, log_g), want,
                               atol=5e-6, rtol=2e-5)
    np.testing.assert_allclose(_chunked(q, k, v, log_g, chunk), want,
                               atol=5e-6, rtol=2e-5)


def test_only_open_gates_let_a_far_pair_and_the_carried_state_show(highest):
    """With seeded gates (near 0.5) a key 16 tokens back weighs 2^-16:
    cutting it off changes nothing a test can see, so the cases above run
    with the gates' biases at +6 as well (g about 0.9975)."""
    for bias, seen in ((0.0, False), (6.0, True)):
        q, k, v, log_g = _qkvg(b=1, t=48, bias=bias)
        whole = pr._pairwise_xla(q, k, v, log_g)[:, 32:]
        # the same queries over the last 16 keys alone
        near = pr._pairwise_xla(
            q[:, 16:], k[:, 16:], v[:, 16:], log_g[:, 16:])[:, 16:]
        # ... which is only the truth if the first 32 weigh nothing
        far = float(np.abs(whole - near).max())
        assert (far > 1e-2) if seen else (far < 1e-3), (bias, far)


CASES = {   # (tokens, query heads, key/value heads, head size, degree)
    "group_of_5": (50, 10, 2, 16, 2),
    "one_head_count": (40, 4, 4, 8, 2),
    "one_kv_head": (33, 6, 1, 16, 2),
    "degree_4": (50, 10, 2, 16, 4),
}


@pytest.mark.parametrize("gate", sorted(GATES))
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_composition_is_the_definition(highest, case, gate):
    t, h, kv, d, degree = CASES[case]
    q, k, v, log_g = _qkvg(t=t, h=h, kv=kv, d=d, bias=GATES[gate] or 0.0)
    got = pr.power_retention(q, k, v, log_g, degree=degree)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(got, _dense(q, k, v, log_g, degree),
                               atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("tokens", [40, 41, 64])
def test_a_long_row_goes_in_query_blocks(highest, tokens, monkeypatch):
    """Beyond twice `PAIRWISE_BLOCK` a block of queries sees the keys up
    to its own end and no further: the ragged last block too."""
    monkeypatch.setattr(pr, "PAIRWISE_BLOCK", 16)
    q, k, v, log_g = _qkvg(t=tokens, bias=6.0)
    jaxpr = str(jax.make_jaxpr(pr.power_retention)(q, k, v, log_g))
    assert jaxpr.count("dot_general") == 2 * -(-tokens // 16)
    np.testing.assert_allclose(pr.power_retention(q, k, v, log_g),
                               _dense(q, k, v, log_g), atol=1e-5, rtol=1e-4)


def test_the_retention_differentiates(highest):
    """No cell trains it; the composition's gradient is jax's own."""
    q, k, v, log_g = _qkvg(b=1, t=12, h=4, kv=2, d=8, bias=2.0)
    loss = lambda *a: jnp.sum(jnp.sin(pr.power_retention(*a)))
    got = jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, log_g)
    step = 1e-3
    for i, g in enumerate(got):
        args = [q, k, v, log_g]
        bump = jax.random.normal(jax.random.PRNGKey(20 + i), args[i].shape)
        hi = loss(*args[:i], args[i] + step * bump, *args[i + 1:])
        lo = loss(*args[:i], args[i] - step * bump, *args[i + 1:])
        np.testing.assert_allclose(jnp.sum(g * bump), (hi - lo) / (2 * step),
                                   rtol=2e-2, atol=1e-3)


def test_a_token_moves_nothing_before_it_and_no_other_row(highest):
    q, k, v, log_g = _qkvg(b=2, t=30, bias=6.0)
    base = pr.power_retention(q, k, v, log_g)
    at = (0, 17)                            # row 0, token 17
    moved = pr.power_retention(
        q.at[at].add(1.0), k.at[at].add(1.0), v.at[at].add(1.0),
        log_g.at[at].add(-1.0))
    np.testing.assert_array_equal(moved[0, :17], base[0, :17])
    np.testing.assert_array_equal(moved[1], base[1])
    assert float(jnp.abs(moved[0, 17:] - base[0, 17:]).min(
        axis=(-2, -1)).min()) > 0           # every later token sees it


def test_the_retention_is_one_named_scope_and_refuses_what_it_cannot_do():
    q, k, v, log_g = _qkvg(t=40)
    text = jax.jit(pr.power_retention).lower(q, k, v, log_g).as_text(
        debug_info=True)
    assert "fdt_power_pairwise" in text
    with pytest.raises(ValueError, match="not a multiple"):
        pr.power_retention(q[:, :, :3], k, v, log_g)
    with pytest.raises(ValueError, match="odd"):
        pr.power_retention(q, k, v, log_g, degree=3)


# -- the model against the plain reference ---------------------------------

@pytest.mark.parametrize("gate", sorted(GATES))
def test_forward_equals_the_plain_reference(gate):
    from reference import brumby as ref
    model = build_model("brumby_dn", **SMALL)
    params = _seeded(model, gate_bias=GATES[gate])
    x, t, text = _inputs()
    got = jax.jit(model.apply)({"params": params}, x, t, text)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.forward(
            p, dict(SMALL, rms_norm_eps=1e-6, rope_theta=1000000), x, t,
            text))(params)
    assert got.shape == x.shape and float(jnp.abs(want).mean()) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_every_source_key_is_a_field_under_the_sources_name():
    from flaxdiff_tpu.models.brumby import BrumbyDenoiser
    with open(os.path.join(ROOT, "benchmark", "configs", "sources",
                           "brumby-14b-dn-384.json")) as f:
        row = json.load(f)
    fields = BrumbyDenoiser.__dataclass_fields__
    assert len(row["config"]) == 18 and set(row["config"]) <= set(fields)
    for key, value in row["config"].items():    # the published defaults
        assert fields[key].default == value, key
    model = build_model("brumby_dn", **row["config"])
    assert (model.num_attention_heads, model.num_key_value_heads,
            model.head_dim, model.intermediate_size) == (40, 8, 128, 17408)
    # 1 + 77 + 24 x 24 tokens of a 48 x 48 latent
    from flaxdiff_tpu.models.trunk import sequence_tokens
    assert sequence_tokens((48, 48, 4), model.patch_size, 77) == 654
    with pytest.raises(ValueError, match="only the published"):
        build_model("brumby_dn", **dict(SMALL, use_sliding_window=True))


def test_the_trunks_share_their_embedding_and_keep_their_leaf_paths():
    """`weights.fill_params` draws a leaf from the hash of its path: a
    renamed module of `Cohere2MoEDenoiser` would change
    `command-a-plus.generate-few`'s weights. Pinned as they were before
    `models/trunk.py` took the shared part."""
    from flaxdiff_tpu.models import brumby, cohere2_moe, trunk
    assert cohere2_moe.SequenceEmbed is brumby.SequenceEmbed \
        is trunk.SequenceEmbed
    model = build_model(
        "cohere2_moe_dn", hidden_size=64, head_dim=16,
        num_attention_heads=8, num_key_value_heads=2, intermediate_size=48,
        num_hidden_layers=1, layer_types=("sliding_attention",),
        num_experts=4, router_experts=16, num_experts_per_tok=3,
        num_shared_experts=2, dtype="float32", output_channels=2)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, RES, RES, CH)), jnp.zeros((1,)),
                             jnp.zeros((1, TOK, FEAT)))["params"],
        jax.random.PRNGKey(0))
    paths = sorted(jax.tree_util.keystr(p) for p, _ in
                   jax.tree_util.tree_leaves_with_path(shapes))
    embed = ["['embed']['patch_embed']['proj']['bias']",
             "['embed']['patch_embed']['proj']['kernel']",
             "['embed']['t_proj']['Dense_0']['bias']",
             "['embed']['t_proj']['Dense_0']['kernel']",
             "['embed']['t_proj']['Dense_1']['bias']",
             "['embed']['t_proj']['Dense_1']['kernel']",
             "['embed']['text_proj']['bias']",
             "['embed']['text_proj']['kernel']",
             "['final_norm']['scale']", "['final_proj']['bias']",
             "['final_proj']['kernel']"]
    layer = [f"['layer_0']['{n}']['kernel']" for n in (
        "experts_down", "experts_gate", "experts_up", "router",
        "shared_experts_down", "shared_experts_gate", "shared_experts_up",
        "to_k", "to_out", "to_q", "to_v")] + ["['layer_0']['norm']['scale']"]
    assert paths == sorted(embed + layer)
    # the new trunk's leaves beside them
    mine = build_model("brumby_dn", **dict(SMALL, num_hidden_layers=1))
    mine_paths = sorted(
        jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(
            jax.eval_shape(lambda k: mine.init(
                k, jnp.zeros((1, RES, RES, CH)), jnp.zeros((1,)),
                jnp.zeros((1, TOK, FEAT)))["params"], jax.random.PRNGKey(0))))
    assert mine_paths == sorted(embed + [
        f"['layer_0']['{n}']['kernel']" for n in (
            "mlp_down", "mlp_gate", "mlp_up", "to_gate", "to_k", "to_out",
            "to_q", "to_v")] + ["['layer_0']['to_gate']['bias']"] + [
        f"['layer_0']['{n}']['scale']" for n in (
            "k_norm", "mlp_norm", "norm", "q_norm")])


# -- serving: a guided DDIM trajectory through the scheduler -----------------

@pytest.mark.parametrize("gate", sorted(GATES))
def test_a_served_request_equals_the_references_trajectory(gate):
    from flaxdiff_tpu.inputs import (ConditionalInputConfig,
                                     DiffusionInputConfig)
    from flaxdiff_tpu.serving import (SampleRequest, SchedulerConfig,
                                      ServingScheduler)
    from flaxdiff_tpu.telemetry import Telemetry
    from harness.serving import SeededContextEncoder
    from reference import brumby as ref, sample

    params = _seeded(build_model("brumby_dn", **SMALL),
                     gate_bias=GATES[gate])
    null_ctx = 0.5 * np.random.default_rng(1).standard_normal(
        (1, TOK, FEAT)).astype(np.float32)
    pipe = DiffusionInferencePipeline.from_config(
        {"model": dict(SMALL, name="brumby_dn"),
         "schedule": {"name": "cosine", "timesteps": 1000},
         "predictor": "v"}, params={"params": params})
    pipe.input_config = DiffusionInputConfig(
        sample_data_key="sample", sample_data_shape=(RES, RES, CH),
        conditions=[ConditionalInputConfig(
            encoder=SeededContextEncoder(null_ctx))])
    sched = ServingScheduler(pipeline=pipe,
                             telemetry=Telemetry(enabled=False),
                             config=SchedulerConfig())
    cond = np.random.default_rng(2).standard_normal(
        (1, TOK, FEAT)).astype(np.float32)
    reqs = [SampleRequest(num_samples=1, resolution=RES, channels=CH,
                          diffusion_steps=nfe, sampler="ddim",
                          guidance_scale=3.0, seed=11 + nfe,
                          conditioning=cond) for nfe in (2, 4)]
    results = [f.result(timeout=600) for f in [sched.submit(r) for r in reqs]]
    sched.close(drain=True)
    cfg = dict(SMALL, rms_norm_eps=1e-6, rope_theta=1000000)
    # the reference's forward as ONE program (traced inside `serve`'s
    # own precision context), not a primitive at a time
    forward = jax.jit(lambda p, *a: ref.forward(p, cfg, *a))
    for req, res in zip(reqs, results):
        want = sample.serve(
            lambda p, _, *a: forward(p, *a), cfg, params,
            {"seed": req.seed, "nfe": req.diffusion_steps, "guidance": 3.0,
             "shape": (1, RES, RES, CH), "cond": cond, "uncond": null_ctx},
            1000, predictor="v")
        np.testing.assert_allclose(res.samples, want, atol=5e-4)
