"""One span layer on the profiler's clock (ISSUE 24).

`tracing.span` opens every span of the program as a
`jax.profiler.TraceAnnotation` named `fdt.<name>`; `Telemetry.span` and
`StepPhaseTimer.phase` are its only callers. These tests capture a
`jax.profiler` trace around a tiny scheduler run and a short `fit` and
read the spans back: names, nesting, attributes, threads. They also
walk the source: every span name at a call site is in the closed list,
every `pl.pallas_call` and every sampler program carries a name.
"""
import ast
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from flaxdiff_tpu import profiling
from flaxdiff_tpu.predictors import EpsilonPredictionTransform
from flaxdiff_tpu.schedulers import CosineNoiseSchedule
from flaxdiff_tpu.serving import (SampleRequest, SchedulerConfig,
                                  ServingScheduler)
from flaxdiff_tpu.telemetry import StepPhaseTimer, Telemetry, tracing
from flaxdiff_tpu.trainer import DiffusionTrainer, TrainerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "flaxdiff_tpu")


# -- reading a capture --------------------------------------------------------

class capture:
    """`with capture(dir) as c:` ... `c.spans()` -> the `fdt.*` events
    as dicts {name, thread, start, end, stats}, sorted by start."""

    def __init__(self, d):
        self.dir = str(d)

    def __enter__(self):
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()

    def spans(self):
        from jax.profiler import ProfileData
        pb = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                    "*.xplane.pb"))[-1]
        out = []
        for plane in ProfileData.from_file(pb).planes:
            for k, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(tracing.SPAN_PREFIX):
                        out.append({
                            "name": e.name[len(tracing.SPAN_PREFIX):],
                            "thread": f"{plane.name}/{line.name}#{k}",
                            "start": e.start_ns,
                            "end": e.start_ns + e.duration_ns,
                            "stats": {str(a): b for a, b in e.stats}})
        return sorted(out, key=lambda s: (s["start"], -s["end"]))


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _parent(spans, child):
    """The innermost span of the same thread that contains `child`."""
    best = None
    for s in spans:
        if s is not child and s["thread"] == child["thread"] \
                and s["start"] <= child["start"] \
                and s["end"] >= child["end"]:
            if best is None or s["end"] - s["start"] \
                    <= best["end"] - best["start"]:
                best = s
    return best


# -- the primitive ------------------------------------------------------------

def test_span_records_only_inside_a_profiler_session(tmp_path):
    tel = Telemetry(enabled=False)
    assert tel.recorder is None
    with tel.span("serve.stack"):       # no session: nothing anywhere
        pass
    with capture(tmp_path) as cap:
        with tel.span("serve.round", cat="serving",
                      args={"round": 7, "bucket": 8, "note": 1.5}):
            with tracing.span("serve.stack"):
                pass
    spans = cap.spans()
    assert [s["name"] for s in spans] == ["serve.round", "serve.stack"]
    # int and str args ride as stats; others stay out of the TraceMe
    assert spans[0]["stats"] == {"round": 7, "bucket": 8}
    assert _parent(spans, spans[1]) is spans[0]


def test_span_with_a_recorder_writes_each_sink_once(tmp_path):
    rec = tracing.TraceRecorder(str(tmp_path / "trace.json"))
    tel = Telemetry(recorder=rec)
    with capture(tmp_path / "cap") as cap:
        with tel.span("serve.launch", cat="serving",
                      args={"kind": "chunk"}):
            pass
    doc = json.load(open(rec.save()))
    mine = [e for e in doc["traceEvents"]
            if e.get("name") == "serve.launch"]
    assert len(mine) == 1 and mine[0]["args"] == {"kind": "chunk"}
    got = _named(cap.spans(), "serve.launch")
    assert len(got) == 1 and got[0]["stats"] == {"kind": "chunk"}


def test_span_closes_both_sinks_on_an_exception(tmp_path):
    rec = tracing.TraceRecorder(str(tmp_path / "trace.json"))
    tel = Telemetry(recorder=rec)
    with capture(tmp_path / "cap") as cap:
        with pytest.raises(ValueError):
            with tel.span("train.rollback"):
                raise ValueError("boom")
    ev = [e for e in json.load(open(rec.save()))["traceEvents"]
          if e.get("name") == "train.rollback"]
    assert len(ev) == 1 and ev[0]["args"]["error"] is True
    assert len(_named(cap.spans(), "train.rollback")) == 1


def test_phase_opens_a_fit_span_and_books_self_time(tmp_path):
    """A phase inside a phase (`elastic` inside `log_step`) books its
    time to itself alone, so the phases of a step still sum to its
    wall-clock; each is a span `fit.<name>` of the capture."""
    now = [0.0]
    timer = StepPhaseTimer(clock=lambda: now[0])
    with capture(tmp_path) as cap:
        timer.begin_step(1)
        with timer.phase("host"):
            now[0] += 1.0
        with timer.phase("log_step"):
            now[0] += 2.0
            with timer.phase("elastic"):
                now[0] += 4.0
            now[0] += 8.0
        now[0] += 16.0
        out = timer.end_step()
    assert out["host"] == 1.0 and out["elastic"] == 4.0
    assert out["log_step"] == 10.0 and out["other"] == 16.0
    assert out["wall"] == 31.0 == sum(
        v for k, v in out.items() if k not in ("wall", "step"))
    spans = cap.spans()
    assert [s["name"] for s in spans] == ["fit.host", "fit.log_step",
                                          "fit.elastic"]
    assert _parent(spans, spans[2]) is spans[1]


def test_traced_steps_is_range_with_a_step_annotation(tmp_path):
    with capture(tmp_path) as cap:
        seen = []
        for i in tracing.traced_steps(5):
            seen.append(i)
            if i == 2:
                break           # closes the open turn
        with tracing.span("fit.host"):
            pass
    assert seen == [0, 1, 2]
    spans = cap.spans()
    steps = _named(spans, "fit.step")
    assert [s["stats"]["step_num"] for s in steps] == [1, 2, 3]
    assert _parent(spans, _named(spans, "fit.host")[0]) is None


def test_profiling_annotate_is_gone():
    assert not hasattr(profiling, "annotate")


# -- the scheduler ------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_pipe():
    from flaxdiff_tpu.inference import (DiffusionInferencePipeline,
                                        build_model)
    config = {
        "model": {"name": "simple_dit", "emb_features": 32,
                  "num_heads": 4, "num_layers": 2, "patch_size": 4,
                  "output_channels": 1},
        "schedule": {"name": "cosine", "timesteps": 100},
        "predictor": "epsilon",
    }
    model = build_model("simple_dit", emb_features=32, num_heads=4,
                        num_layers=2, patch_size=4, output_channels=1)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)),
                        jnp.zeros((1,)), None)
    return DiffusionInferencePipeline.from_config(config, params=params)


def _requests():
    return [SampleRequest(resolution=8, channels=1, diffusion_steps=n,
                          sampler="ddim", seed=seed, use_ema=False)
            for n, seed in ((3, 1), (5, 2), (4, 3))]


@pytest.fixture(scope="module")
def serving_capture(tiny_pipe, tmp_path_factory):
    """One traced replay: a recorder-carrying hub, the scheduler started
    inside the capture with nothing queued (so it waits), one batch in
    flight at most (so it backpressures)."""
    d = tmp_path_factory.mktemp("serve")
    tel = Telemetry.create(str(d / "tel"))
    sched = ServingScheduler(
        pipeline=tiny_pipe, telemetry=tel, autostart=False,
        config=SchedulerConfig(round_steps=2, batch_buckets=(2,),
                               max_inflight=0))
    sched.prewarm(_requests()[:1])
    with capture(d / "cap") as cap:
        sched.start()
        time.sleep(0.05)
        futs = [sched.submit(r) for r in _requests()]
        outs = [f.result(timeout=300) for f in futs]
        sched.close()
    rounds = int(tel.counter("serving/rounds").value)
    programs = {p.__name__ for p in sched.engine._programs.values()}
    tel.close()
    return {"spans": cap.spans(), "outs": outs, "rounds": rounds,
            "programs": programs,
            "trace_json": json.load(open(d / "tel" / "trace.json")),
            "rows": [json.loads(x) for x in
                     open(d / "tel" / "telemetry.jsonl")]}


SERVE_DISPATCH = ("serve.wait", "serve.admit", "serve.round",
                  "serve.stack", "serve.launch", "serve.unstack",
                  "serve.handoff", "serve.backpressure")
SERVE_COMPLETE = ("serve.fetch", "serve.resolve")


@pytest.mark.parametrize("name", SERVE_DISPATCH + SERVE_COMPLETE)
def test_serving_capture_holds_the_span(serving_capture, name):
    assert _named(serving_capture["spans"], name), name


def test_serving_spans_sit_on_two_threads(serving_capture):
    spans = serving_capture["spans"]
    disp = {s["thread"] for s in spans if s["name"] in SERVE_DISPATCH}
    comp = {s["thread"] for s in spans if s["name"] in SERVE_COMPLETE}
    assert len(disp) == 1 and len(comp) == 1 and disp != comp


def test_serving_spans_nest_as_stated(serving_capture):
    spans = serving_capture["spans"]
    for s in spans:
        want = tracing.SPANS[s["name"]].parent
        got = _parent(spans, s)
        if want is None:
            assert got is None, (s["name"], got and got["name"])
        else:
            assert got is not None and got["name"] in want, s["name"]
    # a round is stack, launch, unstack in that order; a hand-off is
    # stack then launch
    for r in _named(spans, "serve.round"):
        kids = [s["name"] for s in spans if _parent(spans, s) is r]
        assert kids == ["serve.stack", "serve.launch", "serve.unstack"]
    for f in _named(spans, "serve.handoff"):
        kids = [s["name"] for s in spans if _parent(spans, s) is f]
        assert kids == ["serve.stack", "serve.launch"]


def test_serving_spans_carry_their_attributes(serving_capture):
    spans = serving_capture["spans"]
    rounds = _named(spans, "serve.round")
    assert len(rounds) == serving_capture["rounds"]
    assert [r["stats"]["round"] for r in rounds] \
        == list(range(1, len(rounds) + 1))
    for r in rounds:
        assert r["stats"]["bucket"] == 2
        assert 1 <= r["stats"]["rows"] <= 2
    # `steps` is the round's own length, not the compiled size: a round
    # ends where its first row ends (the 4-step request's last is 1)
    assert {r["stats"]["steps"] for r in rounds} == {1, 2}
    # rows x steps over the rounds is every turn the requests asked
    # for, their terminal denoises among them: nfe + 1 each
    assert sum(r["stats"]["rows"] * r["stats"]["steps"] for r in rounds) \
        == sum(q.diffusion_steps + 1 for q in _requests())
    kinds = {s["stats"]["kind"] for s in _named(spans, "serve.launch")}
    assert kinds == {"chunk", "handoff"}
    for name in ("serve.handoff", "serve.fetch", "serve.resolve"):
        assert all(s["stats"]["rows"] >= 1 for s in _named(spans, name))
    assert all(s["stats"]["bucket"] == 2
               for s in _named(spans, "serve.handoff"))
    # the span that timed the terminal program went with the program
    assert not _named(spans, "serve.finalize")
    # every request's result came back, three in all
    assert sum(s["stats"]["rows"]
               for s in _named(spans, "serve.resolve")) == 3


def test_round_is_the_join_key_and_each_sink_holds_it_once(
        serving_capture):
    """`trace.json` shows a round once (the scheduler's own span, not a
    second one from the request tracer), and the `request_trace` rows'
    `round_detail` carries the same round numbers as the spans."""
    n = serving_capture["rounds"]
    events = serving_capture["trace_json"]["traceEvents"]
    mine = [e for e in events if e.get("name") == "serve.round"]
    assert len(mine) == n
    assert sorted(e["args"]["round"] for e in mine) \
        == list(range(1, n + 1))
    fin = [e for e in events if e.get("name") == "serve.handoff"]
    assert len(fin) == len(_named(serving_capture["spans"],
                                  "serve.handoff"))
    traces = [r for r in serving_capture["rows"]
              if r.get("type") == "request_trace"]
    assert len(traces) == 3
    joined = {d["round"] for t in traces for d in t["round_detail"]}
    assert joined == set(range(1, n + 1))
    for t in traces:
        assert all("key" in d and "n_act" in d for d in t["round_detail"])


def test_serving_programs_carry_stable_names(serving_capture):
    assert serving_capture["programs"] == {
        "sampler_init", "sampler_noise", "sampler_chunk",
        "sampler_handoff"}


# -- fit ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def fit_capture(mesh, tmp_path_factory):
    import flax.linen as nn

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, t, cond=None):
            return nn.Conv(x.shape[-1], (3, 3))(x)

    model = Tiny()
    d = tmp_path_factory.mktemp("fit")
    tel = Telemetry.create(str(d / "tel"))
    trainer = DiffusionTrainer(
        apply_fn=lambda p, x, t, c: model.apply({"params": p}, x, t, None),
        init_fn=lambda key: model.init(
            key, jnp.zeros((1, 8, 8, 1)), jnp.zeros((1,)))["params"],
        tx=optax.adam(1e-3), schedule=CosineNoiseSchedule(timesteps=100),
        transform=EpsilonPredictionTransform(), mesh=mesh,
        config=TrainerConfig(normalize=False, log_every=2),
        telemetry=tel)
    rng = np.random.default_rng(0)

    def data():
        while True:
            yield {"sample": rng.normal(size=(8, 8, 8, 1))
                   .astype(np.float32)}

    trainer.fit(data(), total_steps=2)          # compile outside
    trainer.best_loss = float("inf")            # the copy fires again
    with capture(d / "cap") as cap:
        trainer.fit(data(), total_steps=4)
    rows = [json.loads(x) for x in open(d / "tel" / "telemetry.jsonl")]
    tel.close()
    return {"spans": cap.spans(), "rows": rows}


FIT_SPANS = ("fit.step", "fit.host", "fit.data_wait", "fit.device",
             "fit.log_step", "fit.loss_fetch", "fit.best_state_copy",
             "data.first_batch")


@pytest.mark.parametrize("name", FIT_SPANS)
def test_fit_capture_holds_the_span(fit_capture, name):
    assert _named(fit_capture["spans"], name), name


def test_fit_spans_nest_count_and_share_a_thread(fit_capture):
    spans = [s for s in fit_capture["spans"]
             if s["name"] in FIT_SPANS]
    assert len({s["thread"] for s in spans}) == 1
    steps = _named(spans, "fit.step")
    assert [s["stats"]["step_num"] for s in steps] == [1, 2, 3, 4]
    assert len(_named(spans, "fit.host")) == 4
    assert len(_named(spans, "fit.data_wait")) == 3    # not the last turn
    assert len(_named(spans, "fit.log_step")) == 2     # log_every=2
    for s in spans:
        want = tracing.SPANS[s["name"]].parent
        got = _parent(spans, s)
        assert (got["name"] if got else None) == want, s["name"]


def test_log_step_is_a_phase_of_the_step_rows(fit_capture):
    rows = [r for r in fit_capture["rows"]
            if r.get("type") == "step_phases"]
    logged = [r for r in rows if "log_step" in r]
    assert logged and all(r["log_step"] > 0 for r in logged)
    for r in rows:
        parts = sum(v for k, v in r.items() if k not in
                    ("type", "step", "wall", "_time", "epoch"))
        assert parts == pytest.approx(r["wall"], rel=1e-3, abs=1e-5)


# -- the source: names at call sites, kernels, programs -----------------------

def _py_files():
    out = [os.path.join(ROOT, "train.py")]
    for base, _, files in os.walk(PKG):
        out += [os.path.join(base, f) for f in files if f.endswith(".py")]
    return out


def _span_literals():
    """(file, line, name) of every `.span("...")` / `span("...")` and
    `.phase("...")` call with a literal first argument."""
    found = []
    for path in _py_files():
        tree = ast.parse(open(path, encoding="utf-8").read())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            f = node.func
            attr = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None)
            a0 = node.args[0]
            if attr in ("span", "phase") and isinstance(a0, ast.Constant) \
                    and isinstance(a0.value, str):
                name = a0.value if attr == "span" else "fit." + a0.value
                found.append((os.path.relpath(path, ROOT), node.lineno,
                              name))
    return found


def test_every_span_name_at_a_call_site_is_in_the_closed_list():
    found = _span_literals()
    assert len(found) >= 40
    unknown = [f for f in found if f[2] not in tracing.SPANS]
    assert not unknown, unknown
    used = {f[2] for f in found} | {"fit.step"}     # traced_steps' own
    assert set(tracing.SPANS) == used, set(tracing.SPANS) ^ used


def test_only_the_primitive_opens_profiler_annotations():
    """`TraceAnnotation` / `StepTraceAnnotation` appear in tracing.py
    alone: every `fdt.*` span goes through the one primitive."""
    for path in _py_files():
        if path.endswith(os.path.join("telemetry", "tracing.py")):
            continue
        tree = ast.parse(open(path, encoding="utf-8").read())
        used = [n for n in ast.walk(tree)
                if getattr(n, "attr", getattr(n, "id", "")) in
                ("TraceAnnotation", "StepTraceAnnotation")
                or (isinstance(n, ast.alias) and "TraceAnnotation" in n.name)]
        assert not used, path


def _pallas_calls():
    out = []
    for path in sorted(glob.glob(os.path.join(PKG, "ops", "*.py"))):
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "pallas_call":
                kw = {k.arg: k.value for k in node.keywords}
                out.append((os.path.basename(path), node.lineno,
                            kw.get("name")))
    return out


def test_every_pallas_call_carries_a_distinct_name():
    calls = _pallas_calls()
    assert len(calls) == 16
    names = []
    for path, line, name in calls:
        # a constant, or a choice between two (the flash forward's name
        # where a window binds: static, `a if binds else b`)
        choice = [name.body, name.orelse] if isinstance(name, ast.IfExp) \
            else [name]
        prefix = {"flash_attention.py": "fdt_flash_",
                  "fused_norm.py": "fdt_gn_silu_",
                  "fused_adaln.py": "fdt_adaln_",
                  "moe.py": "fdt_moe_"}[path]
        for one in choice:
            assert isinstance(one, ast.Constant) \
                and isinstance(one.value, str), (path, line)
            assert one.value.startswith(prefix), (path, line, one.value)
            names.append(one.value)
    assert len(set(names)) == len(names) == 17
    # the grouped product's two are the ones `kernel.moe_gmm_*` read by
    # name; the combine does no counted operation and is not among them
    assert sorted(n for n in names if n.startswith("fdt_moe_gmm")) == [
        "fdt_moe_gmm_down", "fdt_moe_gmm_gate_up"]
    assert "fdt_moe_combine" in names
    assert {"fdt_flash_fwd", "fdt_flash_fwd_window", "fdt_flash_bwd_dq",
            "fdt_flash_bwd_dkv"} <= set(names)


@pytest.mark.parametrize("path,want", [
    (("samplers", "common.py"),
     ["sampler_chunk", "sampler_chunk_cached", "sampler_chunk_spatial",
      "sampler_init", "sampler_model", "sampler_noise", "sampler_scan"]),
    # the engine's own: the round wrapper takes its chunk program's
    # name (`run`), the hand-off keeps the `sampler_` prefix so that
    # `sampler.step_device_ms` sees every program of a turn
    (("serving", "engine.py"), ["run", "sampler_handoff"]),
], ids=["samplers", "engine"])
def test_every_sampler_program_is_jitted_under_its_own_name(path, want):
    path = os.path.join(PKG, *path)
    tree = ast.parse(open(path).read())
    defs = {n.name for n in ast.walk(tree)
            if isinstance(n, ast.FunctionDef)}
    jitted = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "jit":
            arg = node.args[0]
            assert isinstance(arg, ast.Name), node.lineno
            jitted.append(arg.id)
    assert sorted(jitted) == want
    assert set(jitted) <= defs


def test_kernel_names_reach_the_tpu_custom_call():
    """Cross-lowered for a TPU (nothing compiled, no chip): the Mosaic
    custom calls of flash attention's gradient carry the three names
    the trace's device events are matched by."""
    from flaxdiff_tpu.ops.flash_attention import flash_attention
    q = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.bfloat16)
    loss = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v).astype(jnp.float32))
    exp = jax.export.export(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                            platforms=["tpu"])(q, q, q)
    text = exp.mlir_module()
    for name in ("fdt_flash_fwd", "fdt_flash_bwd_dq", "fdt_flash_bwd_dkv"):
        assert f'kernel_name = "{name}"' in text, name
