"""chip_smoke.py (the on-chip bring-up proof) as far as a CPU can check
it: the rehearsal mode end to end, the refusal to run without a chip,
and the compile-cache placement rule the smoke and train.py share
(flaxdiff_tpu/utils.py)."""
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(tmp_path, *args, timeout):
    # the suite runs with the persistent cache off (conftest.py); the
    # smoke's own process gets it back, placed in the test's directory
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    return subprocess.run([sys.executable, SMOKE, *args],
                          capture_output=True, text=True, env=env,
                          cwd=tmp_path, timeout=timeout)


@pytest.mark.slow     # ~2-3 min: kept out of the tier-1 `-m 'not slow'` window
def test_rehearsal_runs_every_phase(tmp_path):
    """`--rehearse`: tiny shapes, the Pallas interpreter, every phase
    (with the 8 virtual devices the suite forces, the FSDP phase too),
    the strict two-key JSON object as the last stdout line and the full
    report, marked as a rehearsal, on the line before it."""
    proc = _run_smoke(tmp_path, "--rehearse", timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    # the driver's contract: exactly these keys, nothing else
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert lines[-2].startswith("report ")
    final = json.loads(lines[-2][len("report "):])
    assert final["ok"] is True and final["rehearsal"] is True
    assert final["device"] == last["device"]
    assert final["device"]["platform"] == "cpu"
    assert final["device"]["count"] == jax.device_count()
    want = {"device", "kernels", "train", "sample", "serve"}
    if jax.device_count() > 1:
        want.add("fsdp")
    assert set(final["phases"]) == want
    assert all(p["s"] >= 0 and p["compile_s"] >= 0
               for p in final["phases"].values())
    # the cache was placed from outside, and the smoke left it there
    assert final["compile_cache_dir"] == str(tmp_path / "jax_cache")
    assert os.listdir(tmp_path / "jax_cache")
    assert "pass" in proc.stdout and "FAIL" not in proc.stdout
    report = json.loads(
        (tmp_path / "chiprun_out" / "chip_smoke.json").read_text())
    assert report == final


def test_without_a_chip_fails_in_phase_zero(tmp_path):
    """No flag, no TPU: non-zero exit in phase 0, no result line, and
    nothing compiled (the placed cache directory was never written)."""
    proc = _run_smoke(tmp_path, timeout=300)
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout
    assert "=== phase" not in proc.stdout and '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
    assert not (tmp_path / "jax_cache").exists()
    assert not (tmp_path / "chiprun_out").exists()


@pytest.fixture()
def cache_config():
    """Restore jax's three cache settings after a helper call."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_cache_helper_sets_no_directory_when_env_places_it(
        monkeypatch, tmp_path, cache_config):
    from flaxdiff_tpu.utils import configure_compilation_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    # not even an explicit --compilation_cache_dir overrides the env
    assert configure_compilation_cache(str(tmp_path / "flag")) \
        == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    # only the thresholds are set
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_cache_helper_defaults_to_a_fixed_path_in_the_checkout(
        monkeypatch, tmp_path, cache_config):
    from flaxdiff_tpu.utils import configure_compilation_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert configure_compilation_cache() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    # train.py --compilation_cache_dir: an explicit override, env unset
    assert configure_compilation_cache(str(tmp_path / "flag")) \
        == str(tmp_path / "flag")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "flag")
