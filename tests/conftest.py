"""Test harness: force an 8-device virtual CPU platform before jax init.

Multi-chip sharding logic is validated on a virtual CPU mesh
(xla_force_host_platform_device_count) since real multi-chip hardware is
unavailable in CI.
"""
import os
import sys

# Force CPU regardless of any preset platform: tests must be hermetic,
# fast, and runnable in CI without accelerators (and must never take the
# chip from a process that is measuring on it).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# No persistent compile cache inside the test process: train.main and
# chip_smoke.py turn it on (<checkout>/.jax_cache), and entries surviving
# from an earlier run would make every "cold compile" a test times warm.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


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
