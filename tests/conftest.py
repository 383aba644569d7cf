"""Test configuration: 8 virtual CPU devices + x64.

The reference can only test multi-rank behavior on real clusters via SLURM
(SURVEY §4: "no mock backend"); this framework tests its full multi-device
sharding on a virtual CPU mesh, and f64 correctness gates run on the CPU
backend (TPU has no native f64 — SURVEY §7 hard parts).
"""

import os
import subprocess

import numpy as np
import pytest

import jax

# Must run before any backend initialization (conftest imports precede
# tests; importing the package does not initialize a backend).
from distributedfft_tpu.parallel.mesh import force_cpu_devices  # noqa: E402

force_cpu_devices(8)
jax.config.update("jax_enable_x64", True)
# In-process CLI tests place the persistent compile cache
# (mesh.use_compile_cache); tests compile fresh and write no cache.
jax.config.update("jax_enable_compilation_cache", False)

# Build the native planner once so its tests run instead of skipping on a
# fresh checkout; a missing/failed toolchain degrades back to skip. The
# flock serializes concurrent pytest processes racing the same build dir.
_NATIVE = os.path.join(os.path.dirname(__file__), os.pardir, "native")
try:
    import fcntl
    with open(os.path.join(_NATIVE, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        # Always invoke make: a no-op when fresh, and a stale .so (missing a
        # newer symbol) would otherwise silently disable the native path.
        subprocess.run(["make", "-C", _NATIVE], capture_output=True,
                       timeout=120, check=False)
except (OSError, ImportError, subprocess.TimeoutExpired):
    pass


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture()
def rng():
    return np.random.default_rng(1234)
