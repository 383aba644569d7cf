"""Plain float64 reference of the transforms a cell times, on the host.

It imports nothing of the program and takes nothing that the program
made except the answers under test: ``scipy.fft`` (the pocketfft that
numpy also ships) and BLAS in float64, with cuFFT's unnormalised
convention (``FFTNorm.NONE``): R2C is ``rfftn``, C2R is ``irfftn`` times
the element count. Both are computed for a few planes along axis 0 only:
a DFT along axis 0 for those indices as one matrix product, then the two
other axes plane by plane, so a 1024^3 cube takes seconds, not minutes.
"""

from __future__ import annotations

import os
from typing import Sequence

import numpy as np
import scipy.fft as sfft

_WORKERS = os.cpu_count() or 1


def rel_l2(out: np.ndarray, ref: np.ndarray) -> float:
    """||out - ref|| / ||ref||, in float64."""
    if out.shape != ref.shape:
        return float("inf")
    d = out.astype(ref.dtype) - ref
    num = float(np.vdot(d, d).real)
    if not np.isfinite(num):
        return float("inf")
    return float(np.sqrt(num / np.vdot(ref, ref).real))


def _dft0(x: np.ndarray, planes: Sequence[int], sign: float,
          block: int = 1 << 16) -> np.ndarray:
    """Rows ``planes`` of the unnormalised DFT of ``x`` along axis 0
    (``exp(sign 2 pi i k n / n0)``), in complex128, by column blocks."""
    n0 = x.shape[0]
    k = len(planes)
    w = np.exp(sign * 2j * np.pi * np.asarray(planes, dtype=np.float64)
               [:, None] * np.arange(n0)[None, :] / n0)
    flat = x.reshape(n0, -1)
    y = np.empty((k, flat.shape[1]), dtype=np.complex128)
    if np.iscomplexobj(x):
        for i in range(0, flat.shape[1], block):
            y[:, i:i + block] = w @ flat[:, i:i + block].astype(np.complex128)
    else:
        # One real matrix, contiguous: a strided operand (``w.real``)
        # sends numpy's matmul down its own loop instead of BLAS, ~100x
        # slower.
        wri = np.ascontiguousarray(np.concatenate([w.real, w.imag]))
        for i in range(0, flat.shape[1], block):
            p = wri @ flat[:, i:i + block].astype(np.float64)
            y[:, i:i + block] = p[:k] + 1j * p[k:]
    return y.reshape((k,) + x.shape[1:])


def rfftn_planes(x: np.ndarray, planes: Sequence[int]) -> np.ndarray:
    """Planes ``planes`` (along axis 0) of ``rfftn(x)`` of a real cube."""
    y = _dft0(x, planes, -1.0)
    y = sfft.fft(y, axis=-1, overwrite_x=True,
                 workers=_WORKERS)[..., :x.shape[-1] // 2 + 1]
    return sfft.fft(y, axis=-2, overwrite_x=True, workers=_WORKERS)


def irfftn_planes(c: np.ndarray, planes: Sequence[int], n_last: int
                  ) -> np.ndarray:
    """Planes ``planes`` (along axis 0) of the unnormalised C2R of a
    half spectrum ``c`` to a real cube whose last axis is ``n_last``."""
    y = _dft0(c, planes, 1.0)
    y = sfft.ifft(y, axis=-2, norm="forward", overwrite_x=True,
                  workers=_WORKERS)
    return sfft.irfft(y, n=n_last, axis=-1, norm="forward",
                      workers=_WORKERS)
