"""Where the persistent compilation cache lands (``mesh.use_compile_cache``):
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
Each case runs in a fresh CPU process: JAX reads the cache directory once
per process."""

import glob
import os
import subprocess
import sys
import uuid

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT_CACHE = os.path.join(REPO, ".jax_cache")

_PROBE = """
import jax, jax.numpy as jnp
from distributedfft_tpu.parallel.mesh import use_compile_cache
print(use_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
def {name}(x):
    return jnp.cos(x) * 3
jax.jit({name}).lower(jnp.ones(8)).compile()
"""


def _entries(d, name):
    return glob.glob(os.path.join(d, f"jit_{name}-*"))


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_lands_where_configured(tmp_path, from_env):
    name = f"cache_probe_{uuid.uuid4().hex[:12]}"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    want = str(tmp_path / "cache") if from_env else CHECKOUT_CACHE
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    try:
        r = subprocess.run([sys.executable, "-c", _PROBE.format(name=name)],
                           capture_output=True, text=True, timeout=120,
                           cwd=str(tmp_path), env=env)
        assert r.returncode == 0, r.stderr[-800:]
        assert r.stdout.strip().splitlines()[-1] == want
        assert _entries(want, name), os.listdir(want)
        if from_env:
            assert not _entries(CHECKOUT_CACHE, name)
    finally:
        for f in _entries(CHECKOUT_CACHE, name):
            os.remove(f)


_SCOPES = """
import sys
from distributedfft_tpu.parallel.mesh import force_cpu_devices
force_cpu_devices(4)
import jax
import distributedfft_tpu as dfft
from distributedfft_tpu.analysis import hloscan
from distributedfft_tpu.obs import profile
from distributedfft_tpu.parallel.mesh import use_compile_cache
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

def text():
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16), dfft.SlabPartition(4),
                            dfft.Config(use_wisdom=False))
    aval = hloscan._input_aval(plan, "{direction}")
    x = jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                             sharding=plan.input_sharding
                             if "{direction}" == "forward"
                             else plan.output_sharding)
    return hloscan._builder(plan, "{direction}").lower(x).compile().as_text()

with profile.scopes_off():
    assert ".ax" not in text()
jax.clear_caches()
assert ".ax" in text(), "the cache served the unscoped build"
print("ok")
"""


@pytest.mark.parametrize("direction", ["forward", "inverse"])
def test_cache_keeps_each_builds_scopes(tmp_path, direction):
    """A program that differs from a cached one only in its metadata (an
    older build without the per-axis scopes) compiles anew instead of
    loading the cached executable with the old scopes."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    r = subprocess.run(
        [sys.executable, "-c", _SCOPES.format(direction=direction)],
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path),
        env=env)
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-1500:]
