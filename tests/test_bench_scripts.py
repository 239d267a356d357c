"""CPU smoke tests for the hardware bench scripts.

These scripts exist to run on the chip (scripts/bench_sweep256.py,
scripts/bench_sampler_trace.py) — CI proves the harnesses execute end to
end and emit the JSON shape the evidence pipeline expects.
"""
import json

import numpy as np


def test_sweep256_records_every_batch(tmp_path, capsys):
    from scripts.bench_sweep256 import main
    out = tmp_path / "sweep.jsonl"
    assert main(["--image_size", "16", "--depths", "8,16",
                 "--batches", "8,16", "--timed_steps", "2",
                 "--attn_backend", "xla", "--out", str(out)]) == 0
    rec = json.loads(out.read_text().strip().splitlines()[-1])
    assert rec["platform"] == "cpu"
    # every attempted batch present with a number or a cause
    for b in ("8", "16"):
        cell = rec["per_batch"][b]
        assert ("imgs_per_sec_per_chip" in cell) or ("error" in cell)
    assert "best" in rec and np.isfinite(
        rec["best"]["imgs_per_sec_per_chip"])


def test_sampler_trace_harness(tmp_path):
    from scripts.bench_sampler_trace import main
    out = tmp_path / "ddim.jsonl"
    assert main(["--image_size", "16", "--steps", "2", "--repeats", "1",
                 "--depths", "8,16", "--emb", "16",
                 "--trace", str(tmp_path / "tr"), "--out", str(out)]) == 0
    rec = json.loads(out.read_text().strip().splitlines()[-1])
    assert "uncond" in rec["configs"] and "cfg3" in rec["configs"]
    for cfg in rec["configs"].values():
        assert np.isfinite(cfg["latency_ms"])


def test_sfc_demo_renders(tmp_path):
    """The SFC visualization demo (reference demo_hilbert_curve.py
    analogue) renders and its round-trip check passes."""
    from scripts.demo_sfc import main
    out = tmp_path / "sfc.png"
    assert main(["--grid", "8", "--out", str(out)]) == 0
    assert out.stat().st_size > 10_000
