"""The benchmark's own tests: CPU only, four virtual devices.

They run outside tier-1 (which collects ``tests/`` only):
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402

from distributedfft_tpu.parallel.mesh import force_cpu_devices  # noqa: E402

force_cpu_devices(4)
jax.config.update("jax_enable_compilation_cache", False)
