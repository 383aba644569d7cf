"""Device-mesh construction — the TPU analog of the reference's MPI
communicator bookkeeping.

The reference derives rank layout from ``MPI_Comm_rank``/``MPI_Comm_split``
(``src/mpicufft.cpp:46-51``; pencil sub-communicators
``src/pencil/mpicufft_pencil.cpp:112-123``). Here the same roles are played by
``jax.sharding.Mesh`` axes: a slab plan uses a 1D mesh ``('p',)``; a pencil
plan a 2D mesh ``('p1', 'p2')`` where each axis is the analog of one
sub-communicator (collectives over one named axis == communication within
one MPI sub-communicator).
"""

from __future__ import annotations

import math
import os
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

SLAB_AXIS = "p"
PENCIL_AXES = ("p1", "p2")

# One process-wide mutex serializing COLLECTIVE launches across threads.
# XLA's in-process cross-device rendezvous assumes one program at a time
# per local device set: two threads interleaving all-to-alls on the same
# mesh (a resident solver stepping while the serving thread executes a
# volume plan — possible since mesh workers host both) park participants
# of different run_ids at the same rendezvous and deadlock. Reentrant so
# a guarded caller can call guarded helpers. Single-threaded device use
# never contends; holders pay one uncontended acquire.
DEVICE_LOCK = threading.RLock()


def force_cpu_devices(n: int) -> None:
    """Select the CPU platform with ``n`` virtual devices (call before
    the first device query: the CPU backend reads the count once)."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


# The checkout root: the package lives one level below it.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.
    ``$JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    left alone; otherwise the cache goes to ``<checkout>/.jax_cache``
    (git-ignored). A fixed path matters: the path is part of the key, so
    a cache that moves never hits. Call before the first compile.

    Entries are keyed on the programs' metadata too. The stage scopes
    (``obs/profile.py``) live there, and JAX's key leaves metadata out
    by default, so a program whose scopes changed would load an older
    build's executable and profile under that build's scopes."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _topology_mesh(shape: Tuple[int, ...]):
    """ICI/DCN-aware device ordering via ``mesh_utils.create_device_mesh``
    when the mesh spans every device (the multi-host pod case, where naive
    ``jax.devices()`` order would put mesh neighbors on different hosts and
    push transpose traffic onto DCN). None when unavailable or partial."""
    try:
        from jax.experimental import mesh_utils
        return mesh_utils.create_device_mesh(shape)
    except Exception as e:  # noqa: BLE001 — any failure degrades, visibly
        import sys
        print(f"warning: topology-aware device mesh unavailable ({e!r}); "
              "falling back to enumeration order — on a multi-host pod this "
              "can route transpose traffic over DCN", file=sys.stderr)
        return None


def make_slab_mesh(p: Optional[int] = None, devices: Optional[Sequence] = None) -> Mesh:
    """1D mesh over ``p`` devices (reference world == slab ranks)."""
    if devices is None:
        devices = jax.devices()
        if p is None or p == len(devices):
            dm = _topology_mesh((len(devices),))
            if dm is not None:
                return Mesh(dm, (SLAB_AXIS,))
    if p is None:
        p = len(devices)
    if p > len(devices):
        raise ValueError(f"requested {p} slab ranks but only {len(devices)} devices")
    return Mesh(np.asarray(devices[:p]).reshape(p), (SLAB_AXIS,))


def make_pencil_mesh(p1: int, p2: int, devices: Optional[Sequence] = None) -> Mesh:
    """2D mesh; axis ``p1`` is the column sub-communicator (second transpose),
    ``p2`` the row sub-communicator (first transpose), matching the
    reference's ``comm1``/``comm2`` split (``src/pencil/mpicufft_pencil.cpp:112-123``)."""
    need = p1 * p2
    if devices is None:
        devices = jax.devices()
        if need == len(devices):
            dm = _topology_mesh((p1, p2))
            if dm is not None:
                return Mesh(dm, PENCIL_AXES)
    if need > len(devices):
        raise ValueError(f"requested {p1}x{p2} pencil grid but only {len(devices)} devices")
    return Mesh(np.asarray(devices[:need]).reshape(p1, p2), PENCIL_AXES)


def best_pencil_grid(n: int) -> Tuple[int, int]:
    """Most-square factorization of ``n`` into (p1, p2), the usual default
    when a job spec gives only a rank count."""
    best = (1, n)
    for p1 in range(1, int(math.isqrt(n)) + 1):
        if n % p1 == 0:
            best = (p1, n // p1)
    return best
