"""Test harness: force an 8-device virtual CPU platform before jax init.

Multi-chip sharding logic is validated on a virtual CPU mesh
(xla_force_host_platform_device_count) since real multi-chip hardware is
unavailable in CI.
"""
import contextlib
import os
import signal
import sys
import threading

# Force CPU regardless of any preset platform: tests must be hermetic,
# fast, and runnable in CI without accelerators (and must never take the
# chip from a process that is measuring on it).
os.environ["JAX_PLATFORMS"] = "cpu"
# The tests assert what a program computes, never how fast this CPU runs
# it, and most of the suite's time is XLA compiling: with LLVM at -O0 and
# without its expensive passes (what `jax_disable_most_optimizations`
# sets) the suite's durations fell by a third (PR 42). The HLO passes
# (fusion, SPMD partitioning, layout) are as they were. A flag already in
# the environment wins; the two-process tests hand these to their workers.
CHEAP_COMPILE_FLAGS = ("--xla_backend_optimization_level=0 "
                       "--xla_llvm_disable_expensive_passes=true")
flags = os.environ.get("XLA_FLAGS", "")
for flag in ("--xla_force_host_platform_device_count=8",
             *CHEAP_COMPILE_FLAGS.split()):
    if flag.split("=")[0].lstrip("-") not in flags:
        flags += " " + flag
os.environ["XLA_FLAGS"] = flags.strip()

# No persistent compile cache inside the test process: train.main and
# chip_smoke.py turn it on (<checkout>/.jax_cache), and entries surviving
# from an earlier run would make every "cold compile" a test times warm.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


# The most one test may take, and the most one module- or session-scoped
# fixture may take to set up. The longest test of the suite is a third of
# it; it is here so that a hang costs one test, by name, and not the run
# its clock.
TEST_LIMIT_S = 300.0


@contextlib.contextmanager
def limited(what, limit=TEST_LIMIT_S):
    """Fail what runs inside, by the name `what`, once `limit` seconds
    are up: a SIGALRM timer on the main thread, where xdist workers run
    tests and set fixtures up (a no-op where the platform has no
    `setitimer`). A timer armed around this one gets back what it had
    left when this one started."""
    if (not hasattr(signal, "setitimer")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def overrun(signum, frame):
        pytest.fail(f"{what} ran over its {limit:g} s limit", pytrace=False)

    previous = signal.signal(signal.SIGALRM, overrun)
    outer = signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *outer)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(autouse=True)
def time_limit(request):
    """Fail an overrunning test with its node id: its body and its
    function-scoped fixtures. A test of the fixture passes its own limit
    as the fixture's parameter."""
    limit = getattr(request, "param", TEST_LIMIT_S)
    with limited(request.node.nodeid, limit):
        yield limit


@pytest.hookimpl(hookwrapper=True)
def pytest_fixture_setup(fixturedef, request):
    """The module- and session-scoped fixtures are set up before
    `time_limit` is armed, and hold the suite's longest bodies (a
    `train.main`, a fit, a restore): each gets the same limit, and fails
    the test that asked for it with the fixture's name."""
    if fixturedef.scope == "function":
        yield
        return
    with limited(f"fixture `{fixturedef.argname}` of "
                 f"{request.node.nodeid or 'the session'}"):
        yield


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh(devices):
    from flaxdiff_tpu.parallel import create_mesh
    return create_mesh(axes={"data": 2, "fsdp": 4})


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def make_av_file():
    """Factory: synthesize a cv2 mp4 + sidecar sine wav (the av module's
    no-ffmpeg path). Shared by the AV pipeline and CLI video tests."""
    def _make(path, size=64, dur=3, fps=25, tone=440, sidecar_sr=22050):
        import cv2
        from scipy.io import wavfile
        path = str(path)
        w = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*"mp4v"), fps,
                            (size, size))
        assert w.isOpened()
        r = np.random.default_rng(0)
        for i in range(int(dur * fps)):
            frame = np.full((size, size, 3), (i * 7) % 255, np.uint8)
            frame[: size // 4] = r.integers(0, 255, (size // 4, size, 3),
                                            dtype=np.uint8)
            w.write(frame)
        w.release()
        t = np.arange(int(dur * sidecar_sr), dtype=np.float32) / sidecar_sr
        audio = (0.5 * np.sin(2 * np.pi * tone * t) * 32767).astype(np.int16)
        wavfile.write(path.rsplit(".", 1)[0] + ".wav", sidecar_sr, audio)
        return path
    return _make
