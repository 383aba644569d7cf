"""Local (per-shard) FFT layer — the TPU analog of the reference's L0 shim.

The reference maps ``T in {float, double}`` to cuFFT types and exec function
pointers (``include/cufft.hpp:23-61``). Here the same role is played by
``jnp.fft`` lowered by XLA to its native FFT implementation; the dtype policy
maps precision to (real, complex) jnp dtypes, and the normalization policy
maps the cuFFT "unnormalized both ways" convention onto numpy norm strings.

All functions are shape-polymorphic, jit-safe wrappers; batching comes from
the untouched axes (cuFFT "batched plan" ≙ XLA treating other axes as batch).

Every entry point takes ``backend``: ``"xla"`` lowers to XLA's FFT
expansion; ``"matmul"`` dispatches to the MXU matmul four-step backend
(``ops/mxu_fft.py``) — the TPU-first alternative that keeps the FLOPs on the
systolic array, and what a plan whose Config names no backend resolves to on
a TPU for float32 and 5-smooth extents, at ``Precision.HIGHEST``
(``utils/wisdom.platform_default``; elsewhere that default is ``"xla"``);
``"matmul-r2"`` is the same backend with radix-2 DIF
splitting of the C2C stages down to MXU-depth matmuls (measured slower on
v5e at 256^3 — see ``mxu_fft.MXUSettings.radix2`` — raced for completeness);
``"pallas"`` runs the same four-step with hand-written Pallas kernels
fusing the twiddle epilogue into the DFT matmul (``ops/pallas_fft.py``);
``"bluestein"`` is the arbitrary-size backend (``ops/bluestein.py``):
5-smooth axes delegate to the XLA expansion bit-identically, while prime /
non-smooth lengths run the chirp-z identity at O(n log n) instead of
falling off every fast path (the matmul four-step degrades to a dense
O(n^2) contraction there). Selected plan-wide via ``Config.fft_backend``.

The one-axis entry points (``rfft``, ``irfft``, ``fft``, ``ifft``) trace
their work, for every backend, under the per-axis sub-scope of the open
plan stage (``obs/profile.axis_scope``: ``dfft/<family>/<node>.ax<k>``);
the multi-axis ones stay at the stage scope. On a TPU a plan's float32
R2C along the last axis on the "xla" backend runs as one complex FFT per
pair of rows (``pairs_rows``, ``_paired_rfft``), and so does its C2R
(``_paired_irfft``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import metrics as obs_metrics
from ..obs.profile import axis_scope
from ..params import FFTNorm

BACKENDS = ("xla", "matmul", "matmul-r2", "pallas", "bluestein")


def _mxu():
    from . import mxu_fft
    return mxu_fft


def _pallas():
    from . import pallas_fft
    return pallas_fft


def _bluestein():
    from . import bluestein
    return bluestein


def validate_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown fft backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    return backend


def _impl(backend: str):
    """Non-XLA implementation module for ``backend``, or None for "xla"."""
    b = validate_backend(backend)
    if b in ("matmul", "matmul-r2"):
        return _mxu()
    if b == "pallas":
        return _pallas()
    if b == "bluestein":
        return _bluestein()
    return None


def _settings_ctx(backend: str, settings):
    """Context scoping per-call ``MXUSettings`` around a non-XLA dispatch
    (the settings are read at TRACE time inside the backend; the ContextVar
    scope makes the read thread/task-safe). ``"matmul-r2"`` is the matmul
    backend with ``radix2`` forced on — overriding whatever the caller's
    settings say, since the backend string is the more specific request.
    The pallas backend reads only ``precision`` (via ``mxu_fft._prec_for``)
    but is scoped identically so a plan's precision choice reaches it."""
    mx = _mxu()
    if backend == "matmul-r2":
        settings = dataclasses.replace(settings or mx.current_settings(),
                                       radix2=True)
    if settings is None:
        return contextlib.nullcontext()
    return mx.use_settings(settings)


def dtypes_for(double_prec: bool) -> Tuple[jnp.dtype, jnp.dtype]:
    """(real, complex) dtypes; f64/c128 requires ``jax_enable_x64`` and is
    intended for CPU-backend correctness gates (TPU has no native f64)."""
    if double_prec:
        return jnp.dtype(jnp.float64), jnp.dtype(jnp.complex128)
    return jnp.dtype(jnp.float32), jnp.dtype(jnp.complex64)


def _fwd_norm(norm: FFTNorm) -> str:
    # cuFFT forward is unnormalized == numpy "backward" forward.
    return "ortho" if norm is FFTNorm.ORTHO else "backward"


def _inv_norm(norm: FFTNorm) -> str:
    # cuFFT inverse is also unnormalized; numpy's norm="forward" puts the
    # full 1/N on the forward side, making the inverse unnormalized.
    if norm is FFTNorm.NONE:
        return "forward"
    if norm is FFTNorm.ORTHO:
        return "ortho"
    return "backward"


# Where pairing won on a v5e (PERF.md, the pairing sweeps). The R2C:
# from an extent of 2048 up to 2^25 points a call. Past that both forms
# ran alike or the pairs lost (16 x 4096^2: 52.8 ms either way; 256 x
# 1024^2: 59.2 against 52.9), and below 2048 the results were mixed.
PAIRED_MIN_EXTENT = 2048
PAIRED_MAX_POINTS = 1 << 25
# The C2R: from an extent of 1024 and from 2^22 up to 2^26 points a call,
# where every point of the sweep won (0.53 to 0.82 of the unpaired
# time); at 2^20 and 2^21 points both forms ran alike.
PAIRED_C2R_MIN_EXTENT = 1024
PAIRED_C2R_MIN_POINTS = 1 << 22
PAIRED_C2R_MAX_POINTS = 1 << 26


def pairs_rows(platform: str, double_prec: bool, backend: str, n: int,
               rows: int, inverse: bool = False) -> bool:
    """Whether a plan's R2C (``inverse``: its C2R) along the last axis,
    of extent ``n`` over ``rows`` local rows a call, runs as one complex
    FFT per pair of rows (``rfft(..., paired=True)``, ``_paired_rfft``;
    ``irfft(..., paired=True)``, ``_paired_irfft``): on a TPU, in
    float32, on the xla backend, with ``n`` and ``rows`` even, and ``n``
    and ``n * rows`` inside the direction's window above. XLA's TPU R2C
    carries a full complex spectrum of every real row from its first
    stage on: one 4096^2 image took 2.63 ms, and 1.55 paired. Its C2R
    interleaves the halves of each real row along the lanes: 1.88 ms,
    and 1.53 paired."""
    if inverse:
        fits = (n >= PAIRED_C2R_MIN_EXTENT
                and PAIRED_C2R_MIN_POINTS <= n * rows <= PAIRED_C2R_MAX_POINTS)
    else:
        fits = n >= PAIRED_MIN_EXTENT and n * rows <= PAIRED_MAX_POINTS
    return (platform == "tpu" and not double_prec and backend == "xla"
            and n % 2 == 0 and rows % 2 == 0 and fits)


def _pairable(x, axis: int, n: int, dtype) -> bool:
    """Whether ``x`` of ``dtype`` can pair its rows along ``axis``, the
    last, to an even extent ``n``: the leading axes hold an even count."""
    return (x.dtype == dtype and axis % x.ndim == x.ndim - 1 and n % 2 == 0
            and math.prod(x.shape[:-1]) % 2 == 0)


def rfft(x, axis: int, norm: FFTNorm = FFTNorm.NONE, backend: str = "xla",
         settings=None, paired: bool = False):
    """Forward R2C along one axis (cuFFT ``execR2C`` analog, 1D case).

    ``settings`` (all entry points): optional ``mxu_fft.MXUSettings``
    scoped around the dispatch — the per-plan alternative to the
    deprecated ``set_*`` process globals. Ignored by the "xla" backend.

    ``paired``: the plan's ``r2c_paired`` (``pairs_rows``). On the "xla"
    backend a float32 ``x`` whose last axis is ``axis``, with an even
    extent and an even number of rows, then takes ``_paired_rfft``,
    counted as ``local_fft.r2c_paired``; any other ``x`` keeps
    ``jnp.fft.rfft``."""
    with axis_scope(axis, x.ndim):
        m = _impl(backend)
        if m is not None:
            with _settings_ctx(backend, settings):
                return m.rfft(x, axis=axis, norm=norm)
        paired = paired and _pairable(x, axis, x.shape[-1], jnp.float32)
        if paired:
            obs_metrics.inc("local_fft.r2c_paired")
        return _xla_rfft(x, axis, _fwd_norm(norm), paired)


@functools.partial(jax.custom_jvp, nondiff_argnums=(1, 2, 3))
def _xla_rfft(x, axis: int, norm: str, paired: bool = False):
    """``jnp.fft.rfft`` (or ``_paired_rfft``) whose tangent map is
    spelled as slice(fft(x)). JAX's own transpose of rfft traces a naive
    rfft against an unsharded dummy, so inside ``shard_map`` its output
    type lacks the cotangent's varying mesh axes and the backward pass
    is refused; slice, fft and the real-to-complex cast transpose with
    the types intact."""
    if paired:
        return _paired_rfft(x, norm)
    return jnp.fft.rfft(x, axis=axis, norm=norm)


@_xla_rfft.defjvp
def _xla_rfft_jvp(axis, norm, paired, primals, tangents):
    x, = primals
    t, = tangents
    m = x.shape[axis] // 2 + 1
    full = jnp.fft.fft(t.astype(jnp.result_type(t, 1j)), axis=axis,
                       norm=norm)
    return _xla_rfft(x, axis, norm, paired), jax.lax.slice_in_dim(
        full, 0, m, axis=axis)


def _paired_rfft(x, norm: str):
    """``jnp.fft.rfft`` along the last axis as one complex FFT per pair
    of rows. With the leading axes flattened to R rows of even length n,
    row j and row j + R/2 make z = a + i b; Z = fft(z) and
    Zr[k] = Z[(n - k) mod n] give, for k = 0..n/2,
    A[k] = (Z[k] + conj(Zr[k]))/2 and B[k] = (Z[k] - conj(Zr[k]))/(2i),
    the spectra of a and b. The split is spelled on the real and
    imaginary planes, so the DC and Nyquist bins come out exactly real;
    Zr is a constant-index gather, not a reverse op (see
    ``_reversed_last``)."""
    lead, n = x.shape[:-1], x.shape[-1]
    h = math.prod(lead) // 2
    x = x.reshape(2, h, n)
    z = jnp.fft.fft(jax.lax.complex(x[0], x[1]), axis=-1, norm=norm)
    m = n // 2 + 1
    zr = jnp.take(z, (n - np.arange(m)) % n, axis=-1)
    z = z[:, :m]
    p, q = jnp.real(z), jnp.imag(z)
    s, t = jnp.real(zr), jnp.imag(zr)
    a = jax.lax.complex((p + s) * 0.5, (q - t) * 0.5)
    b = jax.lax.complex((q + t) * 0.5, (s - p) * 0.5)
    return jnp.stack([a, b]).reshape(lead + (m,))


def irfft(x, n: int, axis: int, norm: FFTNorm = FFTNorm.NONE,
          backend: str = "xla", settings=None, paired: bool = False):
    """Inverse C2R along one axis; ``n`` is the real output extent (needed
    because the halved axis length ``n//2+1`` is ambiguous).

    ``paired``: the plan's ``c2r_paired`` (``pairs_rows``). On the "xla"
    backend a complex64 ``x`` whose last axis is ``axis``, with an even
    ``n`` and an even number of rows, then takes ``_paired_irfft``,
    counted as ``local_fft.c2r_paired``; any other ``x`` keeps
    ``xla_irfft``."""
    with axis_scope(axis, x.ndim):
        m = _impl(backend)
        if m is not None:
            with _settings_ctx(backend, settings):
                return m.irfft(x, n=n, axis=axis, norm=norm)
        if paired and _pairable(x, axis, n, jnp.complex64):
            obs_metrics.inc("local_fft.c2r_paired")
            return _paired_irfft(x, n, _inv_norm(norm))
        return xla_irfft(x, n, axis, _inv_norm(norm))


def _paired_irfft(x, n: int, norm: str):
    """``jnp.fft.irfft`` along the last axis, of even length ``n``, as
    one complex inverse FFT per pair of rows. With the leading axes
    flattened to R rows of half spectra, row j (A) and row j + R/2 (B)
    make Z[k] = A[k] + i B[k] for k <= n/2 and
    Z[k] = conj(A[n - k]) + i conj(B[n - k]) above, so ifft(Z) = a + i b
    holds both real rows whole: no lane interleave. Z is spelled on the
    real and imaginary planes of A and B apart, each (R/2, n/2 + 1), and
    not on one (2, R/2, n/2 + 1) array: a TPU's layouts put such a pair
    axis of 2 in the sublanes, at a quarter of their width. Its tail
    reads the planes through a constant-index gather, not a reverse op
    (see ``_reversed_last``). Like numpy, the spectral axis is cropped or
    zero-padded to n/2 + 1, and the imaginary parts of the DC and
    Nyquist bins are ignored."""
    lead, m = x.shape[:-1], n // 2
    h = math.prod(lead) // 2
    x = _fit_last(x, m + 1).reshape((2 * h, m + 1))
    ar, ai = jnp.real(x[:h]), jnp.imag(x[:h])
    br, bi = jnp.real(x[h:]), jnp.imag(x[h:])
    inner = np.ones(m + 1, np.float32)
    inner[[0, m]] = 0.0
    tail = np.arange(m - 1, 0, -1)
    re = jnp.concatenate([ar - bi * inner, jnp.take(ar + bi, tail, axis=-1)],
                         axis=-1)
    im = jnp.concatenate([ai * inner + br, jnp.take(br - ai, tail, axis=-1)],
                         axis=-1)
    z = jnp.fft.ifft(jax.lax.complex(re, im), axis=-1, norm=norm)
    return jnp.concatenate([jnp.real(z), jnp.imag(z)]).reshape(lead + (n,))


def _fit_last(x, k: int):
    """Crop or zero-pad the last axis to ``k`` (numpy's irfft rule)."""
    if x.shape[-1] > k:
        return x[..., :k]
    if x.shape[-1] < k:
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, k - x.shape[-1])])
    return x


def _reversed_last(x):
    """``x[..., ::-1]`` as a gather. On a TPU v5e (JAX 0.9.0) a reverse
    op reading the output of an FFT in the same program returns wrong
    values at 256^3-class shapes; the gather does not (chip runs,
    PR 21)."""
    return jnp.take(x, np.arange(x.shape[-1] - 1, -1, -1), axis=-1)


def xla_irfft(x, n: int, axis: int = -1, norm: str = "backward"):
    """Inverse C2R along one axis, equal to ``jnp.fft.irfft``. XLA's own
    IRFFT builds the Hermitian half with a reverse op, and on a TPU v5e
    (JAX 0.9.0) that goes wrong after inverse FFTs along other axes in
    the same program (``irfftn`` at 256^3; chip runs, PR 21). So the
    real inverse of even length n is one complex inverse of length n/2:
    with a = X[k], b = conj(X[n/2-k]), Z[k] = (a + b)/2 + i (a - b)
    e^{2 pi i k/n}/2 holds the DFTs of the even and odd samples, and
    ifft(Z) interleaves them. An odd n takes the Hermitian-extended
    full-length inverse. Like numpy, the spectral axis is cropped or
    zero-padded to n//2 + 1 and the imaginary parts of the DC and
    Nyquist bins are ignored."""
    x = jnp.moveaxis(x, axis, -1)
    m = n // 2
    x = _fit_last(x, m + 1)
    scale = {"backward": 1.0, "forward": float(n),
             "ortho": math.sqrt(n)}[norm]
    if n % 2:
        tail = jnp.conj(_reversed_last(x[..., 1:]))
        y = jnp.real(jnp.fft.ifft(jnp.concatenate([x, tail], axis=-1)))
        return jnp.moveaxis(y * scale, -1, axis)
    k = np.arange(m + 1)
    x = jnp.where((k == 0) | (k == m), jnp.real(x).astype(x.dtype), x)
    a = x[..., :m]
    b = jnp.conj(_reversed_last(x[..., 1:]))
    w = jnp.exp(2j * np.pi * np.arange(m) / n).astype(x.dtype)
    z = jnp.fft.ifft((a + b) + 1j * (a - b) * w) * (0.5 * scale)
    y = jnp.stack([jnp.real(z), jnp.imag(z)], axis=-1)
    return jnp.moveaxis(y.reshape(x.shape[:-1] + (n,)), -1, axis)


def fft(x, axis: int, norm: FFTNorm = FFTNorm.NONE, backend: str = "xla",
        settings=None):
    """Forward C2C along one axis (cuFFT ``execC2C(..., CUFFT_FORWARD)``)."""
    with axis_scope(axis, x.ndim):
        m = _impl(backend)
        if m is not None:
            with _settings_ctx(backend, settings):
                return m.fft(x, axis=axis, norm=norm)
        return jnp.fft.fft(x, axis=axis, norm=_fwd_norm(norm))


def ifft(x, axis: int, norm: FFTNorm = FFTNorm.NONE, backend: str = "xla",
         settings=None):
    """Inverse C2C along one axis (cuFFT ``execC2C(..., CUFFT_INVERSE)``)."""
    with axis_scope(axis, x.ndim):
        m = _impl(backend)
        if m is not None:
            with _settings_ctx(backend, settings):
                return m.ifft(x, axis=axis, norm=norm)
        return jnp.fft.ifft(x, axis=axis, norm=_inv_norm(norm))


def fftn(x, axes: Sequence[int], norm: FFTNorm = FFTNorm.NONE,
         backend: str = "xla", settings=None):
    m = _impl(backend)
    if m is not None:
        with _settings_ctx(backend, settings):
            return m.fftn(x, axes=axes, norm=norm)
    return jnp.fft.fftn(x, axes=tuple(axes), norm=_fwd_norm(norm))


def ifftn(x, axes: Sequence[int], norm: FFTNorm = FFTNorm.NONE,
          backend: str = "xla", settings=None):
    m = _impl(backend)
    if m is not None:
        with _settings_ctx(backend, settings):
            return m.ifftn(x, axes=axes, norm=norm)
    return jnp.fft.ifftn(x, axes=tuple(axes), norm=_inv_norm(norm))


def rfftn_3d(x, norm: FFTNorm = FFTNorm.NONE, backend: str = "xla",
             settings=None):
    """Single-device full 3D R2C over the trailing three axes — the analog of
    the reference's ``cufftMakePlan3d`` single-process fallback
    (``src/mpicufft.cpp:65``, ``src/slab/default/mpicufft_slab.cpp:142-145``).
    The halved axis is z (the last), matching cuFFT's layout."""
    m = _impl(backend)
    if m is not None:
        with _settings_ctx(backend, settings):
            return m.rfftn_3d(x, norm=norm)
    return jnp.fft.rfftn(x, axes=(-3, -2, -1), norm=_fwd_norm(norm))


def irfftn_3d(x, shape_3d: Tuple[int, int, int], norm: FFTNorm = FFTNorm.NONE,
              backend: str = "xla", settings=None):
    m = _impl(backend)
    if m is not None:
        with _settings_ctx(backend, settings):
            return m.irfftn_3d(x, shape_3d=shape_3d, norm=norm)
    inorm = _inv_norm(norm)
    x = jnp.fft.ifft(x, n=shape_3d[0], axis=-3, norm=inorm)
    x = jnp.fft.ifft(x, n=shape_3d[1], axis=-2, norm=inorm)
    return xla_irfft(x, shape_3d[2], -1, inorm)
