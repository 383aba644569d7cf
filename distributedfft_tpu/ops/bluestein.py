"""Bluestein (chirp-z) FFT backend — arbitrary axis sizes on the fast path.

Every fast path in this repo assumes 5-smooth (2^a * 3^b * 5^c) axis
sizes: XLA's FFT expansion degrades off powers of small primes, and the
MXU matmul backend's four-step split returns ``(1, n)`` for a prime
length — a dense O(n^2) contraction (``mxu_fft._split``: "acceptable:
benchmark sizes are smooth"). This module removes that cliff with the
chirp-z identity

    X[k] = c*_k * ( (x * c) circ-conv b )[k],   c_j = exp(-i*pi*j^2/n),
                                                b_j = conj(c_j) = c*_j,

which evaluates a length-``n`` DFT (any ``n``: prime, 251, whatever) as
one pointwise chirp multiply, a circular convolution at the padded CHIRP
LENGTH ``m = chirp_length(n)`` (the next power of two >= 2n-1), and a
final chirp multiply. The convolution runs as FFT(m) -> pointwise ->
IFFT(m); the kernel spectrum ``FFT(b)`` is a host-precomputed constant
(``functools.lru_cache``, closed over as a jit constant like the DFT
matrices of ``ops/mxu_fft.py``), so each chirp-z pass costs exactly two
smooth power-of-two transforms plus O(m) elementwise work — O(n log n)
for every n, at a bounded overhead over a natively smooth axis
(``evalkit/roofline.bluestein_axis_report`` quotes the factor honestly).

Registered as ``Config(fft_backend="bluestein")`` (``ops/fft.py``
dispatch): smooth axes delegate to the XLA expansion untouched
(bit-identical to ``"xla"`` there), non-smooth axes take the chirp path.
``fft_backend="auto"`` races it against the other backends — for a
non-smooth shape that is the race between the chirp-z transform and the
O(n^2) direct fallbacks; for smooth shapes it is skipped (identical to
xla by construction, racing it would double-count one candidate).

Everything here is ``jnp`` elementwise ops + smooth FFTs, so the chirp
path is differentiable end to end (the solver suite's ``jit(grad)``
gates cover it) and composes under ``shard_map`` exactly like the other
local backends: plans stay oblivious — the exchange renderings, wire
encodings and guards wrap it unchanged.

The quadratic chirp exponent is reduced mod 2n before the trig
(``j^2 mod 2n``), the same exact-angle trick as ``mxu_fft._dft_np`` —
f64 sin/cos lose ~n*eps for angles of order n^2 otherwise.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence, Tuple

import numpy as np

import jax.numpy as jnp

from ..params import FFTNorm

# The smoothness radix set of every fast path in the repo (XLA FFT /
# mxu four-step benchmark sizes are 2^a*3^b*5^c).
SMOOTH_RADICES = (2, 3, 5)


def is_smooth(n: int, radices: Tuple[int, ...] = SMOOTH_RADICES) -> bool:
    """True when ``n`` factors entirely over ``radices`` (5-smooth by
    default) — the sizes the non-chirp fast paths handle natively."""
    if n < 1:
        return False
    for p in radices:
        while n % p == 0:
            n //= p
    return n == 1


def chirp_length(n: int) -> int:
    """The chirp-z working length for a length-``n`` axis: the smallest
    power of two >= 2n-1 (the circular convolution must hold the full
    linear-convolution support so no wraparound aliases the first n
    outputs)."""
    if n < 1:
        raise ValueError(f"axis length must be positive, got {n}")
    return 1 << (max(2 * n - 1, 1) - 1).bit_length()


def good_size(n: int, radices: Tuple[int, ...] = SMOOTH_RADICES) -> int:
    """The smallest 5-smooth integer >= ``n`` — the zero-padding target
    for workloads that may legally round an axis up (spectral
    convolution pads to linear-conv length anyway; an exact-length FFT
    cannot use this and takes the chirp path instead)."""
    if n < 1:
        raise ValueError(f"axis length must be positive, got {n}")
    m = n
    while not is_smooth(m, radices):
        m += 1
    return m


# ---------------------------------------------------------------------------
# host-side chirp constants (jit constants, like mxu_fft's DFT matrices)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _chirp_np(n: int, inverse: bool, double: bool) -> np.ndarray:
    """c_j = exp(-+ i*pi*j^2/n), j in [0, n) (sign flipped for the
    inverse transform). Exponent reduced mod 2n for exact trig."""
    dt = np.complex128 if double else np.complex64
    j = np.arange(n, dtype=np.int64)
    sign = 1j if inverse else -1j
    return np.exp(sign * np.pi * ((j * j) % (2 * n)) / n).astype(dt)


@functools.lru_cache(maxsize=None)
def _kernel_spectrum_np(n: int, inverse: bool, double: bool) -> np.ndarray:
    """FFT(m) of the symmetric chirp kernel b_j = conj(c_j) laid out for
    circular convolution: b at [0, n) and mirrored into the tail
    [m-n+1, m) so index k-j wraps to b_{|k-j|}."""
    m = chirp_length(n)
    c = _chirp_np(n, inverse, True)  # build in f64, cast after the FFT
    b = np.zeros(m, dtype=np.complex128)
    b[:n] = np.conj(c)
    b[m - n + 1:] = np.conj(c[1:][::-1])
    dt = np.complex128 if double else np.complex64
    return np.fft.fft(b).astype(dt)


def _is_double(dtype) -> bool:
    return jnp.dtype(dtype) in (jnp.dtype(np.complex128),
                                jnp.dtype(np.float64))


# ---------------------------------------------------------------------------
# core transform along the LAST axis
# ---------------------------------------------------------------------------


def _fft_last(x, inverse: bool):
    """Unnormalized DFT along the last axis of a complex array: smooth
    lengths delegate to the XLA expansion (bit-identical to the "xla"
    backend), everything else runs the chirp-z identity."""
    n = x.shape[-1]
    if is_smooth(n):
        return jnp.fft.ifft(x, norm="forward") if inverse \
            else jnp.fft.fft(x, norm="backward")
    dbl = _is_double(x.dtype)
    m = chirp_length(n)
    c = jnp.asarray(_chirp_np(n, inverse, dbl))
    bf = jnp.asarray(_kernel_spectrum_np(n, inverse, dbl))
    a = jnp.fft.fft(x * c, n=m, norm="backward")
    y = jnp.fft.ifft(a * bf, norm="backward")[..., :n]
    return y * c


# ---------------------------------------------------------------------------
# norm scaling (same FFTNorm semantics as ops/mxu_fft.py)
# ---------------------------------------------------------------------------


def _fwd_scale(n: int, norm: FFTNorm) -> float:
    return 1.0 / math.sqrt(n) if norm is FFTNorm.ORTHO else 1.0


def _inv_scale(n: int, norm: FFTNorm) -> float:
    if norm is FFTNorm.ORTHO:
        return 1.0 / math.sqrt(n)
    if norm is FFTNorm.BACKWARD:
        return 1.0 / n
    return 1.0  # NONE: unnormalized inverse (cuFFT convention)


def _scaled(y, s: float):
    return y if s == 1.0 else y * jnp.asarray(s, dtype=y.dtype).real


def _hermitian_extend(c, n: int):
    """Rebuild the full length-n spectrum from its n//2+1 half (C2R)."""
    tail = jnp.conj(c[..., 1:(n + 1) // 2])[..., ::-1]
    return jnp.concatenate([c, tail], axis=-1)


def _fit_axis(c, axis: int, n: int):
    """Crop or zero-pad ``axis`` to extent n (jnp.fft's ``n=`` semantics)."""
    cur = c.shape[axis]
    if cur > n:
        from jax import lax
        c = lax.slice_in_dim(c, 0, n, axis=axis)
    elif cur < n:
        widths = [(0, 0)] * c.ndim
        widths[axis % c.ndim] = (0, n - cur)
        c = jnp.pad(c, widths)
    return c


# ---------------------------------------------------------------------------
# public API (mirrors ops/mxu_fft.py signatures, dispatched by ops/fft.py)
# ---------------------------------------------------------------------------


def fft(x, axis: int, norm: FFTNorm = FFTNorm.NONE):
    cdt = np.complex128 if _is_double(x.dtype) else np.complex64
    x = jnp.moveaxis(x.astype(cdt), axis, -1)
    y = _scaled(_fft_last(x, False), _fwd_scale(x.shape[-1], norm))
    return jnp.moveaxis(y, -1, axis)


def ifft(x, axis: int, norm: FFTNorm = FFTNorm.NONE):
    cdt = np.complex128 if _is_double(x.dtype) else np.complex64
    x = jnp.moveaxis(x.astype(cdt), axis, -1)
    y = _scaled(_fft_last(x, True), _inv_scale(x.shape[-1], norm))
    return jnp.moveaxis(y, -1, axis)


def rfft(x, axis: int, norm: FFTNorm = FFTNorm.NONE):
    """Forward R2C: smooth axes delegate to the native rfft; a chirp axis
    runs the full complex transform and crops the half spectrum (an odd
    non-smooth length has no real-matmul shortcut worth special-casing)."""
    n = x.shape[axis]
    if is_smooth(n):
        y = jnp.moveaxis(x, axis, -1)
        y = jnp.fft.rfft(y, norm="ortho" if norm is FFTNorm.ORTHO
                         else "backward")
        return jnp.moveaxis(y, -1, axis)
    cdt = np.complex128 if _is_double(x.dtype) else np.complex64
    c = jnp.moveaxis(x.astype(cdt), axis, -1)
    y = _scaled(_fft_last(c, False), _fwd_scale(n, norm))[..., :n // 2 + 1]
    return jnp.moveaxis(y, -1, axis)


def irfft(x, n: int, axis: int, norm: FFTNorm = FFTNorm.NONE):
    if is_smooth(n):
        inorm = {FFTNorm.NONE: "forward", FFTNorm.ORTHO: "ortho",
                 FFTNorm.BACKWARD: "backward"}[norm]
        from .fft import xla_irfft
        return xla_irfft(x, n, axis, inorm)
    cdt = np.complex128 if _is_double(x.dtype) else np.complex64
    c = jnp.moveaxis(x.astype(cdt), axis, -1)
    c = _fit_axis(c, -1, n // 2 + 1)
    y = jnp.real(_fft_last(_hermitian_extend(c, n), True))
    return jnp.moveaxis(_scaled(y, _inv_scale(n, norm)), -1, axis)


# The n-dimensional wrappers delegate WHOLESALE to the exact jnp.fft
# calls the "xla" backend makes whenever every transformed axis is
# smooth — per-axis composition of the same transforms is numerically
# equivalent but not bit-identical to the fused rfftn/irfftn ops, and
# the backend's contract is "bit-identical to xla off the chirp path"
# (what lets the 'auto' race skip it on smooth shapes).


def fftn(x, axes: Sequence[int], norm: FFTNorm = FFTNorm.NONE):
    if all(is_smooth(x.shape[a]) for a in axes):
        return jnp.fft.fftn(x, axes=tuple(axes),
                            norm="ortho" if norm is FFTNorm.ORTHO
                            else "backward")
    for a in axes:
        x = fft(x, axis=a, norm=norm)
    return x


def ifftn(x, axes: Sequence[int], norm: FFTNorm = FFTNorm.NONE):
    if all(is_smooth(x.shape[a]) for a in axes):
        inorm = {FFTNorm.NONE: "forward", FFTNorm.ORTHO: "ortho",
                 FFTNorm.BACKWARD: "backward"}[norm]
        return jnp.fft.ifftn(x, axes=tuple(axes), norm=inorm)
    for a in axes:
        x = ifft(x, axis=a, norm=norm)
    return x


def rfftn_3d(x, norm: FFTNorm = FFTNorm.NONE):
    if all(is_smooth(n) for n in x.shape[-3:]):
        return jnp.fft.rfftn(x, axes=(-3, -2, -1),
                             norm="ortho" if norm is FFTNorm.ORTHO
                             else "backward")
    c = rfft(x, axis=-1, norm=norm)
    c = fft(c, axis=-2, norm=norm)
    return fft(c, axis=-3, norm=norm)


def irfftn_3d(x, shape_3d: Tuple[int, int, int], norm: FFTNorm = FFTNorm.NONE):
    if all(is_smooth(n) for n in shape_3d[-3:]):
        inorm = {FFTNorm.NONE: "forward", FFTNorm.ORTHO: "ortho",
                 FFTNorm.BACKWARD: "backward"}[norm]
        from .fft import irfftn_3d as xla_irfftn_3d
        return xla_irfftn_3d(x, shape_3d, norm)
    c = ifft(_fit_axis(x, -3, shape_3d[-3]), axis=-3, norm=norm)
    c = ifft(_fit_axis(c, -2, shape_3d[-2]), axis=-2, norm=norm)
    return irfft(c, n=shape_3d[-1], axis=-1, norm=norm)
