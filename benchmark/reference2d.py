"""Plain float64 reference of the batched 2D transforms a cell times, on
the host.

It imports nothing of the program and takes nothing that the program
made except the answers under test: ``scipy.fft`` (the pocketfft that
numpy also ships) in float64, with cuFFT's unnormalised convention
(``FFTNorm.NONE``): R2C is ``rfft2`` over the last two axes of each
image, C2R is ``irfft2`` times the image's element count ``nx * ny``.
The batch axis carries no transform, so a few images along axis 0 are
computed alone.
"""

from __future__ import annotations

import os
from typing import Sequence

import numpy as np
import scipy.fft as sfft

_WORKERS = os.cpu_count() or 1


def rfft2_images(x: np.ndarray, images: Sequence[int]) -> np.ndarray:
    """``rfft2`` of the images ``images`` (along axis 0) of a real stack
    ``x`` of shape ``(batch, nx, ny)``, in complex128."""
    return sfft.rfft2(np.asarray(x)[list(images)].astype(np.float64),
                      axes=(1, 2), workers=_WORKERS)


def irfft2_images(c: np.ndarray, images: Sequence[int], ny: int
                  ) -> np.ndarray:
    """The unnormalised C2R of the images ``images`` of a half-spectrum
    stack ``c`` of shape ``(batch, nx, ny // 2 + 1)``, to real images of
    ``ny`` columns, in float64."""
    return sfft.irfft2(np.asarray(c)[list(images)].astype(np.complex128),
                       s=(c.shape[1], ny), axes=(1, 2), norm="forward",
                       workers=_WORKERS)
