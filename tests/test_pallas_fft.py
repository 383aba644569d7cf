"""Pallas kernel FFT backend (ops/pallas_fft.py) vs numpy ground truth.

Runs in Pallas interpret mode on the CPU test mesh (compiled Mosaic kernels
need real TPU hardware); covers direct, four-step with the fused twiddle
epilogue, prime fallback, the real-input R2C fast path, norm modes, the f64
fallback route, and an end-to-end slab plan with
``Config(fft_backend="pallas")``.
"""

import numpy as np
import pytest

import distributedfft_tpu as dfft
from distributedfft_tpu.ops import fft as lf
from distributedfft_tpu.ops import pallas_fft
from distributedfft_tpu.params import FFTNorm

def _rel(a, b):
    return np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)


# direct (8, 96), odd direct (12, 13-prime), four-step fused twiddle (1024 ->
# 32x32), non-square four-step (640 -> 20x32).
NS = [8, 12, 13, 96, 640, 1024]


@pytest.mark.parametrize("n", NS)
def test_fft_ifft_vs_numpy(n, rng):
    x = (rng.standard_normal((3, n)) + 1j * rng.standard_normal((3, n))
         ).astype(np.complex64)
    got = np.asarray(pallas_fft.fft(x, axis=-1))
    assert _rel(got, np.fft.fft(x, axis=-1)) < 5e-4
    goti = np.asarray(pallas_fft.ifft(x, axis=-1))
    # FFTNorm.NONE inverse is unnormalized (cuFFT convention).
    assert _rel(goti, n * np.fft.ifft(x, axis=-1)) < 5e-4


@pytest.mark.parametrize("n", NS)
def test_rfft_irfft_vs_numpy(n, rng):
    x = rng.standard_normal((4, n)).astype(np.float32)
    got = np.asarray(pallas_fft.rfft(x, axis=-1))
    ref = np.fft.rfft(x, axis=-1)
    assert got.shape == ref.shape
    assert _rel(got, ref) < 5e-4
    back = np.asarray(pallas_fft.irfft(got, n=n, axis=-1,
                                       norm=FFTNorm.BACKWARD))
    assert _rel(back, x) < 5e-4


def test_four_step_recursion_unfused_branch(rng):
    """n=1042 -> (2, 521): n2 > DIRECT_MAX takes the unfused
    recurse-then-twiddle branch (prime 521 inner stage <= _N_MAX)."""
    n = 1042
    x = rng.standard_normal((2, n)).astype(np.float32)
    got = np.asarray(pallas_fft.rfft(x, axis=-1))
    assert _rel(got, np.fft.rfft(x, axis=-1)) < 2e-3


def test_axis_and_ortho(rng):
    x = rng.standard_normal((5, 32, 7)).astype(np.float32)
    got = np.asarray(pallas_fft.rfft(x, axis=1, norm=FFTNorm.ORTHO))
    assert _rel(got, np.fft.rfft(x, axis=1, norm="ortho")) < 5e-4
    c = x.astype(np.complex64)
    got2 = np.asarray(pallas_fft.ifft(c, axis=0, norm=FFTNorm.ORTHO))
    assert _rel(got2, np.fft.ifft(c, axis=0, norm="ortho")) < 5e-4


def test_f64_falls_back_to_matmul_path(rng):
    """f64 data bypasses the f32-only kernels but must stay correct."""
    x = rng.standard_normal((4, 64)).astype(np.float64)
    got = np.asarray(pallas_fft.rfft(x, axis=-1))
    assert got.dtype == np.complex128
    assert _rel(got, np.fft.rfft(x, axis=-1)) < 1e-11


def test_backend_dispatch_matches_xla(rng):
    x = rng.standard_normal((4, 64)).astype(np.float32)
    a = np.asarray(lf.rfft(x, axis=-1, backend="pallas"))
    b = np.asarray(lf.rfft(x, axis=-1, backend="xla"))
    assert _rel(a, b) < 5e-4


def test_rfftn3d_roundtrip(rng):
    x = rng.standard_normal((8, 8, 8)).astype(np.float32)
    got = np.asarray(pallas_fft.rfftn_3d(x))
    assert _rel(got, np.fft.rfftn(x)) < 5e-4
    back = np.asarray(pallas_fft.irfftn_3d(got, (8, 8, 8)))
    assert _rel(back, x * 8 ** 3) < 5e-4


def test_fused_twiddle_stage_matches_unfused(rng):
    """The fused kernel epilogue must agree with explicit matmul+twiddle."""
    from distributedfft_tpu.ops import mxu_fft as mx
    n1, n2 = 8, 16
    a = (rng.standard_normal((3, n1, n2))
         + 1j * rng.standard_normal((3, n1, n2))).astype(np.complex64)
    fused = np.asarray(pallas_fft._stage(a, mx._dft_np(n2, False, False),
                                         twiddle=(n1, n2, False)))
    unfused = (np.asarray(pallas_fft._stage(a, mx._dft_np(n2, False, False)))
               * mx._twiddle_np(n1, n2, False, False))
    assert _rel(fused, unfused) < 5e-4


def test_slab_plan_with_pallas_backend(devices, rng):
    g = dfft.GlobalSize(16, 16, 16)
    cfg = dfft.Config(fft_backend="pallas")
    mesh = dfft.make_slab_mesh(4, devices)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(4), cfg, mesh=mesh)
    x = rng.standard_normal(g.shape).astype(np.float32)
    out = plan.crop_spectral(plan.exec_r2c(plan.pad_input(x)))
    assert _rel(out, np.fft.rfftn(x)) < 2e-3
    back = plan.crop_real(plan.exec_c2r(plan.exec_r2c(plan.pad_input(x))))
    assert _rel(back, x * g.nx * g.ny * g.nz) < 2e-3
