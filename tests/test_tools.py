"""Dev-tooling coverage: trace analyzer, the SFC demo, the graph-hygiene
analyzer and the run-comparison and diagnosis scripts."""
import gzip
import json

import pytest


def _write_trace(path, events):
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)


DEVICE_EVENTS = [
    {"ph": "M", "name": "process_name", "pid": 3,
     "args": {"name": "/device:TPU:0"}},
    {"ph": "M", "name": "process_name", "pid": 9,
     "args": {"name": "/host:CPU"}},
    {"ph": "X", "pid": 3, "name": "attn1.2", "dur": 4000},
    {"ph": "X", "pid": 3, "name": "attn1.3", "dur": 2000},
    {"ph": "X", "pid": 3, "name": "fusion.7", "dur": 1000},
    {"ph": "X", "pid": 3, "name": "jit_train_step(123)", "dur": 99999},
    {"ph": "X", "pid": 9, "name": "host_only_thing", "dur": 5000},
]


def test_analyze_trace_aggregates_device_ops(tmp_path, capsys):
    from scripts.analyze_trace import main
    d = tmp_path / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    _write_trace(d / "vm.trace.json.gz", DEVICE_EVENTS)
    assert main([str(tmp_path), "--top", "5"]) == 0
    out = capsys.readouterr().out
    assert "/device:TPU:0" in out
    assert "7.00 ms" in out   # total: 6 ms attn + 1 ms fusion
    # the attn FAMILY row aggregates attn1.2 + attn1.3 into 6.00 ms —
    # a falsifiable check that family() strips the SSA counter
    attn_rows = [ln for ln in out.splitlines()
                 if ln.startswith("attn")]
    assert len(attn_rows) == 1 and "6.00" in attn_rows[0], attn_rows
    assert "jit_train_step" not in out and "host_only_thing" not in out


def test_analyze_trace_skips_corrupt_and_host_only(tmp_path, capsys):
    """Newest capture truncated, next host-only, oldest good: the good
    one must be chosen (a run killed mid-capture leaves exactly this)."""
    from scripts.analyze_trace import main
    base = tmp_path / "plugins" / "profile"
    good = base / "2020_01_01"
    hostonly = base / "2021_01_01"
    corrupt = base / "2022_01_01"
    for d in (good, hostonly, corrupt):
        d.mkdir(parents=True)
    _write_trace(good / "vm.trace.json.gz", DEVICE_EVENTS)
    _write_trace(hostonly / "vm.trace.json.gz", [
        {"ph": "M", "name": "process_name", "pid": 9,
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 9, "name": "x", "dur": 1}])
    with gzip.open(hostonly / "vm.trace.json.gz", "rb") as f:
        blob = f.read(40)
    (corrupt / "vm.trace.json.gz").write_bytes(blob)  # truncated gz
    assert main([str(tmp_path)]) == 0
    assert "2020_01_01" in capsys.readouterr().out


def test_analyze_trace_reports_host_only(tmp_path):
    from scripts.analyze_trace import main
    d = tmp_path / "p"
    d.mkdir()
    _write_trace(d / "vm.trace.json.gz", [
        {"ph": "M", "name": "process_name", "pid": 9,
         "args": {"name": "/host:CPU"}}])
    with pytest.raises(SystemExit, match="no device timeline"):
        main([str(d)])


def test_sfc_demo_renders(tmp_path):
    """The SFC visualization demo (reference demo_hilbert_curve.py
    analogue) renders and its round-trip check passes."""
    from scripts.demo_sfc import main
    out = tmp_path / "sfc.png"
    assert main(["--grid", "8", "--out", str(out)]) == 0
    assert out.stat().st_size > 10_000


# -- graph-hygiene analyzer (scripts/lint.py; ISSUE 9) ------------------------
#
# Per-rule true-positive fixtures live in tests/test_analysis.py; here
# the tier-1 gate is ONE unified-CLI invocation over the whole repo —
# every AST rule (silent-except, metric-name, host-sync, lane-slice)
# AND the jaxpr analyzers over the real traced hot programs.

def test_repo_lint_clean_unified(capsys):
    """ISSUE 9 + ISSUE 14 acceptance: `scripts/lint.py` exits 0 on the
    repo with an EMPTY silent-except allowlist, the jaxpr analyzers
    report zero RNG-reuse / callback findings on the real train-step
    and sampler chunk programs, and the sharding rules report zero
    partition-coverage / implicit-reshard findings with pinned
    collective budgets on every MESHED parallel program."""
    from scripts.lint import main
    assert main(["--json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["ok"] is True
    assert not any(f["over_budget"] for f in data["findings"])
    # the silent-except debt is GONE — nothing grandfathered
    assert not any(f["rule"] == "silent-except"
                   for f in data["findings"])
    graph = data["graph"]
    for prog in ("train_step", "train_step_monitored", "chunk_ddim",
                 "chunk_euler_ancestral"):
        assert graph[prog]["rng-key-reuse"]["reused"] == 0, prog
        assert graph[prog]["callback-leak"]["callbacks"] == 0, prog
    # the meshed inventory traced, its comm models are pinned, and no
    # sharding finding survived (coverage + reshard findings would have
    # flipped ok above; assert the stats landed so a silently-skipped
    # meshed trace can't fake a pass)
    for prog in ("meshed_ring_attention", "meshed_ring_attention_grad",
                 "meshed_ulysses_attention", "meshed_pipeline"):
        ci = graph[prog]["collective-inventory"]
        assert ci["collectives"] > 0 and "budget" in ci, prog
        assert graph[prog]["implicit-reshard"]["reshards"] == 0, prog
    cov = graph["meshed_train_step_fsdp"]["partition-coverage"]
    assert cov["leaves"] > 0 and cov.get("unmatched", 0) == 0
    assert not any(f["rule"] in ("partition-coverage",
                                 "implicit-reshard")
                   for f in data["findings"])
    # ISSUE 18/19: the SLO engine, flight recorder and device
    # profiler are host bookkeeping by contract — their host-sync
    # budgets are pinned at ZERO and the clean run above proves they
    # hold (devprof's one pipeline drain lives in the TRAINER, behind
    # its counted seam, never inside the profiler module)
    from flaxdiff_tpu.analysis.budgets import ALLOWLIST
    for pinned in ("flaxdiff_tpu/telemetry/slo.py",
                   "flaxdiff_tpu/telemetry/flightrec.py",
                   "flaxdiff_tpu/telemetry/devprof.py",
                   # ISSUE 20: the planner is a static search — its one
                   # sync lives behind the blessed _block_until_ready
                   # seam for injected probe fns, never inline
                   "flaxdiff_tpu/parallel/planner.py"):
        assert ALLOWLIST["host-sync"][pinned] == 0, pinned


def test_lint_json_output_is_stable(capsys):
    """--json is for machines: two runs on an unchanged tree must be
    byte-identical (sorted findings, no timestamps, no abs paths) —
    including the graph section's collective inventories (ISSUE 14:
    the static comm model is a pinned artifact, not a measurement)."""
    from scripts.lint import main
    assert main(["--json", "--no-graph"]) == 0
    first = capsys.readouterr().out
    assert main(["--json", "--no-graph"]) == 0
    assert capsys.readouterr().out == first
    json.loads(first)       # and it parses
    # graph included (program builders are lru-cached, so the second
    # full run only re-walks the jaxprs): still byte-identical
    assert main(["--json"]) == 0
    g1 = capsys.readouterr().out
    assert main(["--json"]) == 0
    assert capsys.readouterr().out == g1
    graph = json.loads(g1)["graph"]
    ci = graph["meshed_ring_attention"]["collective-inventory"]
    assert ci["comm_bytes_by_axis"] == {"seq": 4096}


# -- evidence diff CLI (scripts/compare_runs.py; ISSUE 13) --------------------

def _telemetry_fixture(tmp_path, name, latency_p50, compile_ms,
                       platform="cpu", comm_bytes=4096):
    """A minimal telemetry dir: one metrics snapshot + a programs.jsonl
    row (static comm model included), values parameterized so the pair
    can regress on demand."""
    d = tmp_path / name
    d.mkdir()
    rows = [
        {"type": "metrics", "serving/latency_ms/p50": latency_p50,
         "serving/latency_ms/p99": latency_p50 * 3.0,
         "serving/latency_ms/count": 8.0,
         "goodput/fraction": 0.9},
        {"type": "request_trace", "outcome": "ok", "trace_id": "r0",
         "queue_ms": 1.0, "compile_ms": compile_ms, "device_ms": 4.0,
         "latency_ms": 5.0 + compile_ms},
    ]
    with open(d / "telemetry.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    prog = {"type": "program", "kind": "chunk", "key": "('chunk', 2, 2)",
            "compile_ms": compile_ms, "flops_jaxpr": 1e9,
            "flops_cost": None, "bytes_cost": None,
            "hbm_peak_bytes": None,
            "collectives": 8,
            "comm_bytes_by_axis": {"seq": comm_bytes},
            "fingerprint": {"platform": platform,
                            "device_kind": platform, "jax": "0"}}
    with open(d / "programs.jsonl", "w") as f:
        f.write(json.dumps(prog) + "\n")
    return str(d)


def test_compare_runs_clean_pair_and_byte_stable_json(tmp_path, capsys):
    """Contract: equal evidence compares clean (exit 0) and the --json
    report is byte-identical across invocations."""
    from scripts.compare_runs import main
    a = _telemetry_fixture(tmp_path, "a", 10.0, 100.0)
    b = _telemetry_fixture(tmp_path, "b", 10.5, 102.0)  # within 10%
    assert main([a, b, "--json"]) == 0
    first = capsys.readouterr().out
    assert main([a, b, "--json"]) == 0
    assert capsys.readouterr().out == first
    doc = json.loads(first)
    assert doc["ok"] is True and doc["fingerprint"]["match"] is True
    assert doc["programs"]["compared"] == 1


def test_compare_runs_regression_exit_code(tmp_path, capsys):
    """A latency regression above threshold exits 1 and names the
    metric; improvements never fail."""
    from scripts.compare_runs import main
    a = _telemetry_fixture(tmp_path, "base", 10.0, 100.0)
    worse = _telemetry_fixture(tmp_path, "worse", 20.0, 250.0)
    assert main([a, worse]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "serving/latency_ms/p50" in out
    # same movement, generous per-stage thresholds -> clean
    assert main([a, worse, "--threshold", "3.0"]) == 0
    capsys.readouterr()
    # improvement direction: candidate FASTER is never a regression
    assert main([worse, a]) == 0


def test_compare_runs_comm_model_is_neutral(tmp_path, capsys):
    """ISSUE 14 acceptance: `comm_bytes_by_axis` / `collectives` rows
    round-trip through the evidence diff as INFORMATIONAL — a comm-model
    change means the program changed shape (the lint budgets gate that),
    never a run regression — while real latency regressions in the same
    pair still fail."""
    from scripts.compare_runs import main
    a = _telemetry_fixture(tmp_path, "a", 10.0, 100.0, comm_bytes=4096)
    b = _telemetry_fixture(tmp_path, "b", 10.0, 100.0,
                           comm_bytes=999999)
    assert main([a, b, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    rows = {r["metric"]: r for r in doc["programs"]["rows"]}
    assert rows["comm_bytes_by_axis/seq"]["direction"] == "info"
    assert rows["comm_bytes_by_axis/seq"]["regressed"] is False
    assert rows["collectives"]["direction"] == "info"
    # the neutrality is scoped: a latency regression alongside the comm
    # drift still fails the comparison
    worse = _telemetry_fixture(tmp_path, "worse", 30.0, 100.0,
                               comm_bytes=999999)
    assert main([a, worse]) == 1


def test_compare_runs_plan_field_directions():
    """ISSUE 20 contract: planner decision fields diff with the right
    signs — search bookkeeping (candidate/prune/probe counts, cache
    hits, the HBM estimate/budget of the CHOSEN plan) is informational,
    while the chosen plan's measured/predicted milliseconds regress
    like any latency."""
    from scripts.compare_runs import direction
    for path in ("plan_probe_ms", "plan_predicted_ms"):
        assert direction(path) == 1, path
    for path in ("plan_candidates", "plan_pruned_unmatched",
                 "plan_pruned_hbm", "plan_pruned_comm", "plan_probes",
                 "plan_cache_hit", "plan_hbm_estimate_bytes",
                 "plan_hbm_budget_bytes", "comm_bytes_by_axis/fsdp"):
        assert direction(path) == 0, path


def test_compare_runs_fingerprint_mismatch(tmp_path, capsys):
    """Different hardware is a different experiment: exit 2, unless
    explicitly overridden."""
    from scripts.compare_runs import main
    a = _telemetry_fixture(tmp_path, "cpu_run", 10.0, 100.0,
                           platform="cpu")
    b = _telemetry_fixture(tmp_path, "tpu_run", 10.0, 100.0,
                           platform="TPU v4")
    assert main([a, b]) == 2
    capsys.readouterr()
    assert main([a, b, "--allow-fingerprint-mismatch"]) == 0


def test_compare_runs_bench_files(tmp_path, capsys):
    """BENCH-file mode: per-stage numeric diff + the --evidence stamp
    feeding the fingerprint check."""
    from scripts.compare_runs import main
    base = {"value": 100.0, "platform": "cpu",
            "evidence": {"platform": "cpu", "jax": "0.4.37"},
            "stages": {"serve": {"status": "ok",
                                 "warm": {"latency_ms": {"p50": 6.0}}},
                       "broken": {"status": "failed: x"}}}
    cand = json.loads(json.dumps(base))
    cand["stages"]["serve"]["warm"]["latency_ms"]["p50"] = 30.0
    pa, pb = tmp_path / "A.json", tmp_path / "B.json"
    pa.write_text(json.dumps(base))
    pb.write_text(json.dumps(cand))
    assert main([str(pa), str(pb)]) == 1
    assert "serve" in capsys.readouterr().out
    # per-stage override rescues a stage known to be noisy
    assert main([str(pa), str(pb), "--stage-threshold",
                 "serve=5.0"]) == 0


def test_legacy_shims_still_gate(tmp_path, capsys):
    """The old standalone gates are thin shims over the unified rules:
    same flags, same verdicts."""
    bad = tmp_path / "offender.py"
    bad.write_text("try:\n"
                   "    risky()\n"
                   "except Exception:\n"
                   "    pass\n")
    from scripts.check_bare_except import main as bare_main
    assert bare_main(["--root", str(bad)]) == 1
    assert "offender.py:3" in capsys.readouterr().err

    code = tmp_path / "emitter.py"
    code.write_text("def f(reg):\n"
                    "    reg.counter('secret/undocumented').inc()\n"
                    "    reg.gauge('train/loss').set(1.0)\n")
    docs = tmp_path / "docs.md"
    docs.write_text("| `train/loss` | gauge | documented |\n")
    from scripts.check_metric_names import main as metric_main
    assert metric_main(["--root", str(code), "--docs", str(docs)]) == 1
    err = capsys.readouterr().err
    assert "secret/undocumented" in err and "train/loss" not in err
