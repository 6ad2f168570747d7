"""Test harness configuration.

Mirrors the reference's distributed-test trick (tests/conftest.py + LT_DEVICES,
reference tests/test_algos/test_algos.py:48-53): tests run on the host CPU platform
with 8 virtual XLA devices, so multi-chip mesh semantics (psum gradient reduction,
data-axis sharding) execute on a true multi-device mesh without TPU hardware.
"""

import os
import sys
import types

# must happen before jax initializes any backend
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

# Force tensorboard's TF *stub*: `tensorboard.compat.tf` falls back to the stub
# iff `tensorboard.compat.notf` is importable. Without this, the learning-gate
# tests' EventAccumulator lazily imports the REAL tensorflow into a process that
# already loaded torch — which segfaults (absl/protobuf symbol clash) and takes
# the whole pytest process down at ~51% of the suite.
sys.modules.setdefault("tensorboard.compat.notf", types.ModuleType("tensorboard.compat.notf"))

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache (same policy as cli._setup_xla_env): the fused
# Dreamer train programs take 30-60 s to compile; with the cache, repeat suite runs
# skip every compile that already happened. Keyed by program, so shape changes in a
# test invalidate only that test's entries.
from sheeprl_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import signal  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _per_test_timeout(request):
    """Per-test wall-clock budget (reference tests/conftest.py:73-78 uses
    pytest-timeout markers; that plugin is not in this image, so SIGALRM plays the
    same role). Override per test with @pytest.mark.timeout(seconds)."""
    marker = request.node.get_closest_marker("timeout")
    seconds = int(marker.args[0]) if marker and marker.args else 300

    def _raise(signum, frame):
        raise TimeoutError(f"test exceeded its {seconds}s budget")

    old = signal.signal(signal.SIGALRM, _raise)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(autouse=True)
def chdir_tmp(tmp_path, monkeypatch):
    """Isolate each test's logs/ and memmap dirs in a tmpdir."""
    monkeypatch.chdir(tmp_path)
    yield


@pytest.fixture()
def standard_args():
    return [
        "dry_run=True",
        "env.sync_env=True",
        "env.capture_video=False",
        "fabric.accelerator=cpu",
        "metric.log_level=0",
        "checkpoint.save_last=False",
        "buffer.memmap=False",
        "env.num_envs=2",
    ]
