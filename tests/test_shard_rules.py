"""Sharding & collective-traffic analyzer (ISSUE 14): true-positive
fixtures per rule, clean-pass assertions on the REAL meshed programs,
the comm byte model, budget tightening, and numerical parity of the
exact configurations the meshed builders trace.

The full-repo acceptance run (all rules, meshed inventory included,
exit 0) stays the ONE unified invocation in tests/test_tools.py; this
file proves each new rule detects what it claims to detect and that
the meshed programs the rules gate are also numerically correct on the
forced 8-device CPU host platform.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from flaxdiff_tpu.analysis import framework
from flaxdiff_tpu.analysis import graph_rules  # noqa: F401 — registers
from flaxdiff_tpu.analysis import shard_rules
from flaxdiff_tpu.analysis.framework import GRAPH_RULES
from flaxdiff_tpu.analysis.programs import (MESHED_PROGRAM_BUILDERS,
                                            TracedProgram,
                                            meshed_programs)
from flaxdiff_tpu.analysis.shard_rules import collective_summary
from flaxdiff_tpu.parallel import create_mesh
from flaxdiff_tpu.parallel.partition import (partition_coverage,
                                             fsdp_sharding_tree,
                                             with_named_constraint)


@pytest.fixture(scope="module")
def mesh2(devices):
    return create_mesh(axes={"data": 2}, devices=devices[:2])


# -- collective-inventory -----------------------------------------------------

def test_collective_summary_counts_and_bytes(mesh2):
    """psum of a [4,4] f32 over a 2-device axis: one dispatch, ring
    all-reduce sends 2*(n-1)/n*payload = 64 bytes/device; the axis size
    is harvested from the shard_map mesh when not passed."""
    def f(x):
        fn = shard_map(lambda s: jax.lax.psum(s, "data"), mesh=mesh2,
                       in_specs=P("data", None), out_specs=P(None, None))
        return fn(x)

    closed = jax.make_jaxpr(f)(jnp.zeros((4, 4), jnp.float32))
    s = collective_summary(closed)
    assert s["collectives"] == 1
    assert s["by_primitive"] == {"psum": 1}
    # local shard is [2,4] f32 = 32 bytes payload; 2*(1/2)*32 = 32
    assert s["comm_bytes_by_axis"] == {"data": 32}
    assert s["comm_bytes"] == 32


def test_collective_summary_scan_multiplies(mesh2):
    """A ppermute inside a scan body counts once per trip, exactly the
    ring-attention K/V rotation shape."""
    perm = [(0, 1), (1, 0)]

    def body(s):
        def step(c, _):
            return jax.lax.ppermute(c, "data", perm), ()
        out, _ = jax.lax.scan(step, s, None, length=5)
        return out

    def f(x):
        fn = shard_map(body, mesh=mesh2, in_specs=P("data", None),
                       out_specs=P("data", None))
        return fn(x)

    closed = jax.make_jaxpr(f)(jnp.zeros((4, 4), jnp.float32))
    s = collective_summary(closed)
    assert s["by_primitive"] == {"ppermute": 5}
    assert s["comm_bytes_by_axis"] == {"data": 5 * 32}


def test_collective_summary_cond_takes_max_branch(mesh2):
    """cond branches are alternatives: the model takes the costlier
    branch, never the sum (a refresh/reuse switch must not double)."""
    def body(s, flag):
        return jax.lax.cond(
            flag,
            lambda c: jax.lax.psum(c, "data"),
            lambda c: jax.lax.psum(c, "data") * 2.0
            + jax.lax.psum(c * 2.0, "data"),
            s)

    def f(x, flag):
        fn = shard_map(body, mesh=mesh2,
                       in_specs=(P("data", None), P()),
                       out_specs=P(None, None))
        return fn(x, flag)

    closed = jax.make_jaxpr(f)(jnp.zeros((4, 4), jnp.float32),
                               jnp.zeros((), bool))
    s = collective_summary(closed)
    assert s["by_primitive"]["psum"] == 2       # max branch, not 3
    assert s["comm_bytes"] == 64                # pbroadcast moves 0


def test_collective_budget_breach_is_a_finding(monkeypatch):
    [(name, prog)] = meshed_programs(["meshed_ring_attention"])
    monkeypatch.setitem(framework.COMM_BUDGET, "fix", 100)
    findings, stats = GRAPH_RULES["collective-inventory"].check(
        "fix", prog)
    assert len(findings) == 1
    assert "budget of 100" in findings[0].message
    assert stats["budget"] == 100
    # at its real pinned budget the same program passes
    findings, stats = GRAPH_RULES["collective-inventory"].check(
        name, prog)
    assert findings == []
    assert stats["comm_bytes"] == framework.COMM_BUDGET[name]


# -- partition-coverage -------------------------------------------------------

def test_partition_coverage_sources_and_spec_agreement(devices):
    mesh = create_mesh(axes={"fsdp": 4}, devices=devices[:4])
    params = {
        "ruled": jnp.zeros((6, 6)),          # explicit rule wins
        "big_odd": jnp.zeros((7, 9)),        # nothing divides: unmatched
        "tiny": jnp.zeros((3,)),             # deliberate replicate
        "shardable": jnp.zeros((8, 16)),     # FSDP inference
    }
    rules = [(r"^ruled$", P(None, None))]
    cov = partition_coverage(params, mesh, rules=rules, min_size=16)
    by_path = {a.path: a for a in cov}
    assert by_path["ruled"].source == "rule"
    assert by_path["big_odd"].source == "unmatched"
    assert by_path["tiny"].source == "replicated-small"
    assert by_path["shardable"].source == "fsdp"
    # the audit view must agree leaf-for-leaf with the executable one
    specs = fsdp_sharding_tree(params, mesh, rules=rules, min_size=16)
    for a in cov:
        assert a.spec == specs[a.path], a.path
    # a 1-sized shard axis replicates everything by construction:
    # nothing is "unmatched" on it
    mesh1 = create_mesh(axes={"data": 2}, devices=devices[:2])
    cov1 = partition_coverage(params, mesh1, min_size=16)
    assert all(a.source != "unmatched" for a in cov1)


def test_partition_coverage_rule_flags_unmatched(devices):
    mesh = create_mesh(axes={"fsdp": 4}, devices=devices[:4])
    cov = partition_coverage({"big_odd": jnp.zeros((7, 9))}, mesh,
                             min_size=16)
    closed = jax.make_jaxpr(lambda x: x)(jnp.zeros(()))
    prog = TracedProgram(closed, {"fsdp": 4}, partition=cov)
    findings, stats = GRAPH_RULES["partition-coverage"].check(
        "fix", prog)
    assert len(findings) == 1 and "big_odd" in findings[0].message
    assert stats["unmatched"] == 1
    # programs without a partition subject are out of scope, not clean
    findings, stats = GRAPH_RULES["partition-coverage"].check(
        "fix", TracedProgram(closed))
    assert findings == [] and stats == {}


# -- implicit-reshard ---------------------------------------------------------

def test_reshard_boundary_mismatch_detected(mesh2):
    def f(x):
        x = with_named_constraint(x, P("data", None), mesh2)
        fn = shard_map(lambda s: s * 2, mesh=mesh2,
                       in_specs=P(None, "data"),
                       out_specs=P(None, "data"))
        return fn(x)

    prog = TracedProgram(jax.make_jaxpr(f)(jnp.zeros((4, 4))),
                         {"data": 2})
    findings, stats = GRAPH_RULES["implicit-reshard"].check("fix", prog)
    assert len(findings) == 1
    assert "enters shard_map" in findings[0].message
    assert stats["reshards"] == 1


def test_reshard_elementwise_operand_mismatch_detected(mesh2):
    def f(x, y):
        a = with_named_constraint(x, P("data", None), mesh2)
        b = with_named_constraint(y, P(None, "data"), mesh2)
        return a + b

    prog = TracedProgram(
        jax.make_jaxpr(f)(jnp.zeros((4, 4)), jnp.zeros((4, 4))),
        {"data": 2})
    findings, stats = GRAPH_RULES["implicit-reshard"].check("fix", prog)
    assert len(findings) == 1 and "combines operands" in \
        findings[0].message


def test_reshard_explicit_constraint_is_planned_not_flagged(mesh2):
    """A sharding_constraint IS the plan: relaying out through one is
    never a finding, and tracking resumes at the declared layout."""
    def f(x):
        a = with_named_constraint(x, P("data", None), mesh2)
        b = with_named_constraint(a * 2, P(None, "data"), mesh2)
        fn = shard_map(lambda s: s + 1, mesh=mesh2,
                       in_specs=P(None, "data"),
                       out_specs=P(None, "data"))
        return fn(b)

    prog = TracedProgram(jax.make_jaxpr(f)(jnp.zeros((4, 4))),
                         {"data": 2})
    findings, stats = GRAPH_RULES["implicit-reshard"].check("fix", prog)
    assert findings == []
    assert stats["annotated_boundaries"] == 3


def test_reshard_matching_boundary_clean(mesh2):
    def f(x):
        x = with_named_constraint(x, P("data", None), mesh2)
        fn = shard_map(lambda s: s * 2, mesh=mesh2,
                       in_specs=P("data", None),
                       out_specs=P("data", None))
        return fn(x)

    prog = TracedProgram(jax.make_jaxpr(f)(jnp.zeros((4, 4))),
                         {"data": 2})
    findings, _ = GRAPH_RULES["implicit-reshard"].check("fix", prog)
    assert findings == []


# -- the real meshed programs (ISSUE 14 acceptance) ---------------------------

def test_meshed_inventory_builds_every_program(devices):
    progs = meshed_programs()
    assert [n for n, _ in progs] == sorted(MESHED_PROGRAM_BUILDERS)
    assert all(hasattr(p, "jaxpr") for _, p in progs)
    with pytest.raises(ValueError, match="unknown meshed program"):
        meshed_programs(["nope"])


@pytest.mark.parametrize("name", sorted(MESHED_PROGRAM_BUILDERS))
def test_meshed_real_programs_pass_sharding_rules(name):
    """Acceptance bar: zero partition-coverage and implicit-reshard
    findings, and comm within its pinned budget, on every REAL meshed
    program."""
    [(prog_name, prog)] = meshed_programs([name])
    for rid in ("collective-inventory", "partition-coverage",
                "implicit-reshard"):
        findings, _ = GRAPH_RULES[rid].check(prog_name, prog)
        assert findings == [], (rid, [f.message for f in findings])


def test_meshed_comm_models_match_the_algorithms():
    """The static comm model must reproduce what the algorithms say:
    ring = 2 ppermutes/hop x n hops on `seq`; its backward adds the
    dK/dV accumulator rotation; ulysses = exactly 2 all_to_all;
    pipeline = 1 ppermute/tick over M+S-1 ticks + the masked-psum
    collection."""
    progs = dict(meshed_programs())
    ring = collective_summary(progs["meshed_ring_attention"].closed,
                              {"data": 2, "seq": 4})
    assert ring["by_primitive"]["ppermute"] == 2 * 4     # K and V, 4 hops
    assert set(ring["comm_bytes_by_axis"]) == {"seq"}

    grad = collective_summary(
        progs["meshed_ring_attention_grad"].closed, {"data": 2, "seq": 4})
    assert grad["by_primitive"]["ppermute"] == 24        # K,V,dK,dV fwd+bwd
    assert grad["comm_bytes"] > ring["comm_bytes"]

    uly = collective_summary(progs["meshed_ulysses_attention"].closed,
                             {"data": 2, "seq": 4})
    assert uly["by_primitive"]["all_to_all"] == 2

    pipe = collective_summary(progs["meshed_pipeline"].closed,
                              {"data": 2, "pipe": 4})
    # 4 microbatches over 4 stages: M + S - 1 = 7 ticks
    assert pipe["by_primitive"]["ppermute"] == 7
    assert pipe["by_primitive"]["psum"] == 1
    assert set(pipe["comm_bytes_by_axis"]) == {"pipe"}

    # GSPMD-era programs carry no explicit collectives — documented
    # limitation; their sharding is gated by partition-coverage instead
    fsdp = collective_summary(progs["meshed_train_step_fsdp"].closed)
    assert fsdp["collectives"] == 0
    cov = progs["meshed_train_step_fsdp"].partition
    sources = {a.source for a in cov}
    assert "tensor-parallel" in sources and "fsdp" in sources
    assert "unmatched" not in sources


# -- numerical parity of the traced configurations (satellite) ----------------

def _reference_attention(q, k, v):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def test_traced_ring_config_matches_xla_reference(devices, rng):
    """The EXACT (shape, mesh) configuration meshed_ring_attention
    traces — [2,16,4,8] on data=2 x seq=4 — must also be numerically
    correct, outputs AND the grads whose backward ring the grad builder
    traces, vs the single-device XLA reference."""
    from flaxdiff_tpu.parallel.ring_attention import ring_self_attention
    mesh = create_mesh(axes={"data": 2, "seq": 4}, devices=devices[:8])
    q, k, v = (jnp.asarray(rng.normal(size=(2, 16, 4, 8)), jnp.float32)
               for _ in range(3))
    out = ring_self_attention(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference_attention(q, k, v)),
                               rtol=2e-5, atol=2e-5)

    def loss_ring(q, k, v):
        return jnp.sum(ring_self_attention(q, k, v, mesh) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=1e-4, atol=1e-4)


def test_traced_ulysses_config_matches_xla_reference(devices, rng):
    """Same parity bar for the Ulysses builder configuration: the two
    all_to_all re-shards the inventory counts are exact, not just
    counted."""
    from flaxdiff_tpu.parallel.ulysses import ulysses_self_attention
    mesh = create_mesh(axes={"data": 2, "seq": 4}, devices=devices[:8])
    q, k, v = (jnp.asarray(rng.normal(size=(2, 16, 4, 8)), jnp.float32)
               for _ in range(3))
    out = ulysses_self_attention(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference_attention(q, k, v)),
                               rtol=2e-5, atol=2e-5)


def test_traced_ring_chunked_matches_reference(devices, rng):
    """Chunked ring hops (chunk smaller than the visiting shard, so the
    online-softmax chunk scan truly accumulates) at the builder's mesh
    layout vs the XLA reference."""
    from flaxdiff_tpu.parallel import ring_attention as ra
    mesh = create_mesh(axes={"seq": 2}, devices=devices[:2])
    q, k, v = (jnp.asarray(rng.normal(size=(1, 64, 2, 8)), jnp.float32)
               for _ in range(3))
    spec = ra.seq_shard_spec(mesh)

    def ring8(q, k, v):
        body = (lambda a, b, c:
                ra.ring_attention_sharded(a, b, c, "seq", None, 8))
        fn = shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                       out_specs=spec, check_vma=False)
        return fn(q, k, v)

    np.testing.assert_allclose(np.asarray(ring8(q, k, v)),
                               np.asarray(_reference_attention(q, k, v)),
                               rtol=2e-5, atol=2e-5)


# -- budget tightening (satellite) --------------------------------------------

def test_tightened_budgets_semantics():
    """min(old, observed) for existing entries, drop-at-zero, never add
    files, never raise; comm gains pins for new nonzero programs."""
    from flaxdiff_tpu.analysis.framework import Finding, Report
    from flaxdiff_tpu.analysis.tighten import tightened_budgets
    findings = [Finding("host-sync", "a.py", 1, "x"),
                Finding("host-sync", "a.py", 2, "y"),
                Finding("host-sync", "rogue.py", 3, "z")]
    report = Report(
        findings=findings, failures=[], notes=[],
        graph_stats={
            "progA": {"bf16-upcast": {"elements": 100, "casts": 2},
                      "collective-inventory": {"comm_bytes": 500,
                                               "collectives": 3}},
            "progB": {"collective-inventory": {"comm_bytes": 0,
                                               "collectives": 0}},
        },
        rules_run=["host-sync", "bf16-upcast", "collective-inventory"])
    allow = {"host-sync": {"a.py": 5, "gone.py": 3},
             "silent-except": {}}
    upcast = {"progA": 400}
    comm = {"progA": 800}
    new_allow, new_up, new_comm, changes = tightened_budgets(
        report, allow, upcast, comm)
    assert new_allow["host-sync"] == {"a.py": 2}     # shrunk + dropped
    assert "rogue.py" not in new_allow["host-sync"]  # never added
    assert new_up == {"progA": 100}
    assert new_comm == {"progA": 500}                # zero-comm progB
    assert not any("rogue" in c for c in changes)    # not pinned

    # re-lint clean: the tightened allowlist produces zero failures AND
    # zero shrink notes on the same findings
    from flaxdiff_tpu.analysis.framework import apply_budgets
    failures, notes = apply_budgets(
        [f for f in findings if f.file == "a.py"], new_allow)
    assert failures == [] and notes == []

    # a scoped run leaves un-run rules' budgets byte-identical
    report2 = Report(findings=[], failures=[], notes=[], graph_stats={},
                     rules_run=["silent-except"])
    a2, u2, c2, ch2 = tightened_budgets(report2, allow, upcast, comm)
    assert a2["host-sync"] == allow["host-sync"]
    assert u2 == upcast and c2 == comm and ch2 == []


def test_tighten_cli_writes_relintable_module(tmp_path, capsys):
    """--tighten output is a loadable budgets module whose tables the
    framework re-lints clean (scoped to a fast pure-AST rule so the
    test stays cheap; the repo-wide tighten ran for real this PR)."""
    from flaxdiff_tpu.analysis.cli import main
    out = tmp_path / "budgets_new.py"
    assert main(["--tighten", "--tighten-out", str(out),
                 "--rules", "silent-except", "--no-graph"]) == 0
    text = out.read_text()
    ns: dict = {}
    exec(compile(text, str(out), "exec"), ns)  # noqa: S102 — own output
    assert ns["ALLOWLIST"]["silent-except"] == {}
    # rules that did not run keep their budgets byte-identical
    assert ns["ALLOWLIST"]["host-sync"] == framework.ALLOWLIST[
        "host-sync"]
    assert ns["UPCAST_BUDGET"] == framework.UPCAST_BUDGET
    assert ns["COMM_BUDGET"] == framework.COMM_BUDGET


# -- registry comm fields -----------------------------------------------------

def test_registry_rows_carry_static_comm_model(tmp_path, mesh2):
    """record_jitted attaches the collective inventory to the program
    row; rows stay byte-stable (sorted keys, int bytes)."""
    from flaxdiff_tpu.telemetry.programs import (ProgramRegistry,
                                                 read_registry)

    def f(x):
        fn = shard_map(lambda s: jax.lax.psum(s, "data"), mesh=mesh2,
                       in_specs=P("data", None),
                       out_specs=P(None, None))
        return fn(x)

    jitted = jax.jit(f)
    x = jnp.ones((4, 4), jnp.float32)
    path = tmp_path / "programs.jsonl"
    reg = ProgramRegistry(path=str(path), deep=False)
    row = reg.record_jitted("meshtest", "k0", jitted, (x,))
    assert row["collectives"] == 1
    assert row["comm_bytes_by_axis"] == {"data": 32}
    [persisted] = read_registry(str(path))
    assert persisted["comm_bytes_by_axis"] == {"data": 32}
    # plain single-device programs degrade to an explicit zero model
    row2 = reg.record_jitted("solo", "k1", jax.jit(lambda x: x * 2),
                             (x,))
    assert row2["collectives"] == 0
    assert row2["comm_bytes_by_axis"] == {}
    blob = json.dumps(row, sort_keys=True)
    assert json.loads(blob)["collectives"] == 1


# -- generated rule tables (parallel/planner.py; ISSUE 20) --------------------

def _arch_shapes():
    """Param shape trees (eval_shape — nothing materialized) for the
    three real architectures the generated tables must cover."""
    from flaxdiff_tpu.models.dit import SimpleDiT
    from flaxdiff_tpu.models.mmdit import SimpleMMDiT
    from flaxdiff_tpu.models.unet import Unet

    dit = SimpleDiT(output_channels=1, patch_size=2, emb_features=32,
                    num_layers=2, num_heads=2, backend="xla")
    mmdit = SimpleMMDiT(output_channels=1, patch_size=4,
                        emb_features=32, num_layers=2, num_heads=4,
                        backend="xla")
    unet = Unet(output_channels=1, emb_features=32,
                feature_depths=(8, 12), num_res_blocks=1,
                norm_groups=4)
    x = jnp.zeros((1, 16, 16, 1))
    t = jnp.zeros((1,))
    ctx = jnp.zeros((1, 3, 16))
    return [
        ("dit", jax.eval_shape(
            lambda: dit.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 16, 16, 1)), t, None))),
        ("mmdit", jax.eval_shape(
            lambda: mmdit.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 16, 16, 1)), t, ctx))),
        ("unet", jax.eval_shape(
            lambda: unet.init(jax.random.PRNGKey(0), x, t))),
    ]


@pytest.mark.parametrize("axes", [{"fsdp": 4}, {"fsdp": 2, "tensor": 2},
                                  {"data": 2, "tensor": 4}])
def test_generated_rules_cover_every_arch(devices, axes):
    """ISSUE 20: a planner-generated rule table must leave ZERO
    unmatched leaves on MM-DiT and UNet trees (not just the DiT it was
    smoke-tested on) — every leaf's coverage provenance is an explicit
    rule, and the executable sharding tree agrees with the audit."""
    from flaxdiff_tpu.parallel.planner import generate_rules

    n = 1
    for s in axes.values():
        n *= s
    mesh = create_mesh(axes=axes, devices=devices[:n])
    for name, shapes in _arch_shapes():
        rules = generate_rules(shapes, mesh, min_size=2 ** 8)
        cov = partition_coverage(shapes, mesh, rules=rules,
                                 min_size=2 ** 8)
        assert cov, name
        unmatched = [a.path for a in cov if a.source == "unmatched"]
        assert unmatched == [], (name, axes, unmatched)
        assert all(a.source == "rule" for a in cov), name
        # the audit view and the executable tree agree leaf-for-leaf
        specs = fsdp_sharding_tree(shapes, mesh, rules=rules,
                                   min_size=2 ** 8)
        from flaxdiff_tpu.parallel.partition import _path_str
        flat = {_path_str(p): s for p, s in
                jax.tree_util.tree_flatten_with_path(specs)[0]}
        for a in cov:
            assert a.spec == flat[a.path], (name, a.path)


def test_generated_rules_are_suffix_anchored(devices):
    """The same generated table must match a leaf at ANY tree depth —
    a TrainState wraps the params it was generated from under
    `params/...`, `ema_params/...` and the optimizer mu/nu trees, and
    the table must shard all of them identically (the planner's HBM
    estimate multiplies by those copies)."""
    from flaxdiff_tpu.parallel.planner import generate_rules

    mesh = create_mesh(axes={"fsdp": 4}, devices=devices[:4])
    [( _, shapes)] = [a for a in _arch_shapes() if a[0] == "dit"]
    rules = generate_rules(shapes, mesh, min_size=2 ** 8)
    wrapped = {"params": shapes, "ema_params": shapes,
               "opt": {"mu": shapes, "nu": shapes}}
    cov = partition_coverage(wrapped, mesh, rules=rules,
                             min_size=2 ** 8)
    assert all(a.source == "rule" for a in cov)
    by_path = {a.path: a.spec for a in cov}
    for path, spec in by_path.items():
        if path.startswith("params/"):
            leaf = path[len("params/"):]
            assert by_path[f"ema_params/{leaf}"] == spec, path
            assert by_path[f"opt/mu/{leaf}"] == spec, path
