"""MXU matmul-based FFT backend (four-step Cooley-Tukey).

TPU has no FFT hardware unit: XLA expands ``fft`` HLOs into a chain of
small matmuls and relayouts that is bound by HBM traffic, not by the
128x128 MXU systolic array where virtually all of the chip's FLOPs live.
This backend writes the DFT as dense matrix products so the transform runs
on the MXU, each along its axis in place (one ``dot_general``; no swap of
the axis to the end):

* **Direct**: for ``n <= direct_max`` the transform along an axis is one
  contraction with the ``n x n`` DFT matrix ``F[j,k] = w^(jk)``,
  ``w = exp(-2*pi*i/n)``. The real transforms (R2C, C2R) run direct up
  to ``_REAL_DIRECT * direct_max``: one pass over HBM, where the
  four-step pays three.
* **Four-step** (Bailey): for larger ``n = n1*n2`` (``_split_for``: n2 the
  largest divisor <= ``direct_max``, n1 the short factor), input index
  ``t = a*n2 + b``, output ``k = c + n1*d``:

      X[c + n1*d] = sum_b W_n2^(b*d) * [ W_n^(b*c) * sum_a x[a*n2+b] * W_n1^(a*c) ]

  The short DFT over a and the twiddle are elementwise work on the n1
  contiguous blocks of the axis (``_fourstep``); each long DFT over b is
  one MXU contraction; interleaving d and c is the one relayout. The R2C
  computes only the outputs k <= n/2 and the C2R reads only the n/2+1
  stored ones (``_rfft_axis``, ``_irfft_axis``), half the long-stage work.

Precision: ``MXUSettings.precision`` applies to float32 operands. The
platform default (``utils/wisdom.platform_default``) runs ``HIGHEST`` —
float32 products with float32 accumulation — and picks ``direct_max`` per
extent from ``DIRECT_MAX_BY_EXTENT``; an explicitly named matmul backend
keeps the process default, ``HIGH`` (three bf16 passes). float64 always
runs ``HIGHEST``.

Role in the framework: ``Config.fft_backend = "matmul"``, and what a
Config that names no backend resolves to on a TPU for float32 and
5-smooth extents; the alternative to the XLA-FFT local layer
(``ops/fft.py``). This is the TPU-first analog of the reference's cuFFT
plan choice (the reference's L0 shim, ``include/cufft.hpp:23-61``,
hard-wires cuFFT — on TPU the equivalent "vendor transform" is a compiler
expansion, so the framework supplies its own MXU-shaped implementation).

Normalization follows the cuFFT "unnormalized both ways" convention mapped
through ``FFTNorm`` exactly like ``ops/fft.py``.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import math
from typing import Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp
from jax import lax

from ..params import FFTNorm

# Largest length transformed by a single direct DFT matmul. 128 lanes x
# 4 sublane-tiles keeps each operand tile comfortably inside VMEM while the
# contraction depth (= n) stays a multiple of the MXU's 128-deep pipeline.
DIRECT_MAX = 512

# The platform default's direct-matmul threshold, by the largest extent a
# plan transforms (``utils/wisdom.platform_default``): rows of (largest
# extent up to, mxu_direct_max), first fit. Past the last row XLA's own
# FFT was faster and the platform default keeps ``"xla"``. From one v5e
# at HIGHEST (PERF.md section 6), complex axes: 512 direct (26.4 ms against
# 31.8 for 2x256); 1024 as 4x256 (34.2 against 36.4 for 2x512, 40.2
# direct, 40.5 XLA); 2048 as 4x512 (43.9 against 52.0 for 8x256; XLA
# 42.2, but the R2C's direct contraction wins 41.8 to 51.4); 4096 as
# 8x512 took 54.9 against XLA's 40.6.
DIRECT_MAX_BY_EXTENT: Tuple[Tuple[int, int], ...] = (
    (512, 512),
    (1024, 256),
    (2048, 512),
)


def direct_max_for(n: int) -> Optional[int]:
    """``mxu_direct_max`` for a plan whose largest extent is ``n``, or
    None past the table (the platform default keeps XLA's FFT)."""
    for upto, dm in DIRECT_MAX_BY_EXTENT:
        if n <= upto:
            return dm
    return None


@dataclasses.dataclass(frozen=True)
class MXUSettings:
    """Per-call backend knobs, read at TRACE time.

    Replaces the four former module globals (precision / radix2 /
    karatsuba / fourstep_einsum) so two plans with different settings can
    coexist in one process: every public entry point accepts
    ``settings=``, scoped through a ``contextvars.ContextVar`` for the
    duration of the (trace-time) call, so concurrent traces in other
    threads/contexts are unaffected. ``Config.mxu_settings()`` builds one
    from plan configuration; the ``set_*`` module functions survive as
    deprecated shims that mutate the process-default instance.

    * ``precision`` — MXU precision for SINGLE-precision DFT matmuls.
      Raw bf16 (DEFAULT) leaves 5.4e-4 max rel error at 256^3 (v5e,
      f32 vs f64 truth); three-pass bf16 emulation (HIGH) reaches
      8.2e-7 at half the MXU passes of HIGHEST (3.0e-8), float32
      products. HIGH is this class's default; the platform default of a
      Config that names no backend sets HIGHEST. f64 inputs always use
      HIGHEST.
    * ``radix2`` — DIF splitting of C2C stages down to depth-128
      matmuls (see the analysis above ``_fft_radix2``).
    * ``karatsuba`` — 3-matmul complex multiply (see ``_matmul_F``).
    * ``fourstep_einsum`` — relayout-free four-step (see
      ``_fourstep_einsum``).
    * ``direct_max`` — largest length transformed by one direct DFT
      matmul before the four-step split kicks in (default the module
      ``DIRECT_MAX``). Lowering it forces a four-step factorization of
      lengths that would otherwise run direct — the knob behind the
      512-direct vs 256x2-four-step efficiency comparison.
    """

    precision: lax.Precision = lax.Precision.HIGH
    radix2: bool = False
    karatsuba: bool = False
    fourstep_einsum: bool = False
    direct_max: int = DIRECT_MAX

    @classmethod
    def make(cls, precision=None, radix2: bool = False,
             karatsuba: bool = False, fourstep_einsum: bool = False,
             direct_max: Optional[int] = None) -> "MXUSettings":
        """Build from loosely-typed values (precision may be a string
        name in any case, a ``lax.Precision``, or None for the HIGH
        default)."""
        p = lax.Precision.HIGH if precision is None else as_precision(
            precision)
        return cls(p, bool(radix2), bool(karatsuba), bool(fourstep_einsum),
                   DIRECT_MAX if direct_max is None else int(direct_max))


def as_precision(p) -> lax.Precision:
    """Coerce a ``lax.Precision`` or its string name (any case) — string
    values come from ``Config.mxu_precision``, which validates
    case-insensitively, so the coercion must be too."""
    return p if isinstance(p, lax.Precision) else lax.Precision(
        str(p).lower())


# Process-default settings, mutated only by the deprecated ``set_*`` shims.
_DEFAULTS = MXUSettings()

# Active per-call override; None -> fall through to _DEFAULTS. A ContextVar
# (not a bare global) so a trace running in another thread or asyncio task
# never observes a neighbour's scoped settings.
_ACTIVE: contextvars.ContextVar[Optional[MXUSettings]] = \
    contextvars.ContextVar("mxu_settings", default=None)


def current_settings() -> MXUSettings:
    """Settings in effect for the current context (scoped override if one
    is active, else the process defaults)."""
    return _ACTIVE.get() or _DEFAULTS


def default_settings() -> MXUSettings:
    """The process-default settings (what the deprecated ``set_*`` shims
    mutate), ignoring any active scoped override — the base
    ``Config.mxu_settings()`` resolves unset knobs against."""
    return _DEFAULTS


@contextlib.contextmanager
def use_settings(settings: Optional[MXUSettings]):
    """Scope ``settings`` as the active MXUSettings for this context.
    ``None`` is a no-op (keeps whatever is already in effect)."""
    if settings is None:
        yield
        return
    token = _ACTIVE.set(settings)
    try:
        yield
    finally:
        _ACTIVE.reset(token)


def _set_default(**kw) -> None:
    global _DEFAULTS
    _DEFAULTS = dataclasses.replace(_DEFAULTS, **kw)


def set_precision(p) -> None:
    """DEPRECATED shim: set the process-DEFAULT MXU precision for
    single-precision DFT matmuls (``lax.Precision`` or its string name).
    Prefer ``Config(mxu_precision=...)`` / an explicit ``MXUSettings`` —
    this global default is read at TRACE time and is not thread-scoped.
    Already-compiled programs keep the precision they were traced with."""
    _set_default(precision=as_precision(p))


def _prec_for(dtype):
    return (lax.Precision.HIGHEST if _is_double(dtype)
            else current_settings().precision)


# ---------------------------------------------------------------------------
# DFT / twiddle constants (host-side, cached; closed over as jit constants)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _dft_np(n: int, inverse: bool, double: bool) -> np.ndarray:
    """Dense DFT matrix F[j,k] = exp(-+ 2*pi*i*j*k/n) (numpy, cached)."""
    dt = np.complex128 if double else np.complex64
    j = np.arange(n)
    sign = 2j if inverse else -2j
    # W^(jk) = W^(jk mod n): reduce the exponent first so sin/cos see small
    # exact angles (f64 trig loses ~n*eps for angles of order n).
    return np.exp(sign * np.pi * (np.outer(j, j) % n) / n).astype(dt)


@functools.lru_cache(maxsize=None)
def _twiddle_np(n1: int, n2: int, inverse: bool, double: bool) -> np.ndarray:
    """Four-step twiddle T[r,k2] = exp(-+ 2*pi*i*r*k2/(n1*n2))."""
    dt = np.complex128 if double else np.complex64
    n = n1 * n2
    sign = 2j if inverse else -2j
    return np.exp(sign * np.pi * np.outer(np.arange(n1), np.arange(n2)) / n
                  ).astype(dt)


def _is_double(dtype) -> bool:
    return jnp.dtype(dtype) in (jnp.dtype(np.complex128),
                                jnp.dtype(np.float64))


@functools.lru_cache(maxsize=None)
def _split(n: int) -> Tuple[int, int]:
    """Balanced factorization n = n1*n2 with n1 <= n2, n1 maximal.

    Returns (1, n) for primes — the caller then falls back to a direct
    matmul of the full length (acceptable: benchmark sizes are smooth).
    """
    r = int(math.isqrt(n))
    for n1 in range(r, 1, -1):
        if n % n1 == 0:
            return n1, n // n1
    return 1, n


@functools.lru_cache(maxsize=None)
def _split_wide(n: int, direct_max: int) -> Tuple[int, int]:
    """MXU-deep factorization n = n1*n2 with n2 the LARGEST divisor of
    ``n`` not exceeding ``direct_max`` (and n1 = n/n2). Returns (1, n)
    when no such divisor > 1 exists (primes)."""
    for n2 in range(min(int(direct_max), n - 1), 1, -1):
        if n % n2 == 0:
            return n // n2, n2
    return 1, n


@functools.lru_cache(maxsize=None)
def _split_for(n: int, direct_max: int) -> Tuple[int, int]:
    """The factorization the four-step dispatch actually uses for an
    axis of length ``n > direct_max`` — the large-axis extension of the
    direct table (ISSUE 10 tentpole c).

    The balanced split minimizes MACs (n1+n2 smallest) but starves the
    MXU at intermediate lengths: 2048 under the default ``DIRECT_MAX``
    factors as 32x64, two contractions well below the systolic array's
    128-deep pipeline — exactly the regime where the measured all-direct
    1024^3 result (652 vs 228 GFLOPS, a pre-PR-12 single-chip row, not
    measured on the current code) showed depth beating flop count ~3x. So when a factorization with BOTH
    factors on the direct-DFT matmul path exists, prefer the one whose
    dominant factor is as DEEP as possible: n2 = the largest divisor
    <= direct_max (2048 -> 4x512, 4096 -> 8x512 at the default table;
    2048 -> 2x1024 under the raced direct_max=1024), so the contraction
    carrying ~all the volume runs at full direct depth and 2048/4096
    axes stop falling off the MXU. When the deep co-factor n1 would
    itself exceed ``direct_max`` (n > direct_max^2, or divisor
    structure forbids it), fall back to the balanced split and let the
    recursion handle the large factor."""
    n1, n2 = _split_wide(n, direct_max)
    if 1 < n1 <= direct_max:
        return n1, n2
    return _split(n)


# ---------------------------------------------------------------------------
# Core transform along the LAST axis
# ---------------------------------------------------------------------------


# Complex matmul strategy for the C2C stages. XLA decomposes a complex dot
# into 4 real matmuls (ArFr - AiFi, ArFi + AiFr); the Karatsuba-style
# 3-multiplication form (t1=ArFr, t2=AiFi, t3=(Ar+Ai)(Fr+Fi); Re=t1-t2,
# Im=t3-t1-t2) trades one matmul for two extra additions. Measured on v5e
# at 256^3 it is a net LOSS (~1.9-2.2 ms roundtrip vs ~1.5 ms): at these
# sizes the stages are close to HBM-bound, so trimming MXU passes while
# adding elementwise operand traffic costs more than it saves. Off by
# default (``MXUSettings.karatsuba``); the toggle stays as a benchmarkable
# axis for larger / more compute-bound shapes.


def set_karatsuba(on: bool) -> None:
    """DEPRECATED shim: set the process-DEFAULT 3-matmul complex-multiply
    form (prefer ``Config(mxu_karatsuba=...)``)."""
    _set_default(karatsuba=bool(on))


# Radix-2 splitting of the C2C stages. A direct depth-n DFT matmul costs
# O(n) MXU passes per output row-block; decimation-in-frequency recursion
#
#   X[2k]   = DFT_{n/2}(x1 + x2)                 (x1 = first half, x2 = second)
#   X[2k+1] = DFT_{n/2}((x1 - x2) * w^j),  w = exp(-+2*pi*i/n)
#
# halves the matmul depth per level at the cost of one VPU butterfly and an
# even/odd output interleave. Recursing down to depth _R2_BASE = 128 — the
# MXU's native contraction depth, below which passes waste systolic rows —
# turns the depth-256 stages of a 256^3 transform into two depth-128
# matmuls plus cheap elementwise work: ~2x fewer MXU passes on the stages
# that dominate the roundtrip. Measured on v5e at 256^3 f32 it is a net
# LOSS (2.64 ms roundtrip vs 1.52 ms direct, same session): the interleave
# store is a full-array relayout per stage that XLA does NOT fold away, and
# like the Karatsuba toggle above, trading MXU passes for extra HBM traffic
# loses on an op that is already bandwidth-balanced. Kept as a raced
# backend ("matmul-r2") because the trade-off flips where compute dominates
# (deeper axes / cheaper memory systems); both input halves are contiguous
# (DIF, not DIT), so no strided gather on the input side.
_R2_BASE = 128


def set_radix2(on: bool) -> None:
    """DEPRECATED shim: set the process-DEFAULT radix-2 DIF splitting of
    C2C stages (prefer backend "matmul-r2" / an explicit MXUSettings)."""
    _set_default(radix2=bool(on))


@contextlib.contextmanager
def radix2(on: bool = True):
    """Scoped radix-2 override: the current settings with ``radix2=on``,
    context-local (thread/task-safe), restored on exit."""
    with use_settings(dataclasses.replace(current_settings(),
                                          radix2=bool(on))):
        yield


@functools.lru_cache(maxsize=None)
def _r2_twiddle_np(n: int, inverse: bool, double: bool) -> np.ndarray:
    """Radix-2 DIF twiddle w^j = exp(-+2*pi*i*j/n), j in [0, n/2)."""
    dt = np.complex128 if double else np.complex64
    sign = 2j if inverse else -2j
    return np.exp(sign * np.pi * np.arange(n // 2) / n).astype(dt)


def _fft_radix2(x, inverse: bool):
    """DIF radix-2 split of an even-length last-axis DFT: two half-length
    DFTs (recursively down to ``_R2_BASE``) + butterfly + interleave."""
    n = x.shape[-1]
    h = n // 2
    dbl = _is_double(x.dtype)
    x1 = x[..., :h]
    x2 = x[..., h:]
    even = _fft_last(x1 + x2, inverse)
    odd = _fft_last((x1 - x2) * jnp.asarray(_r2_twiddle_np(n, inverse, dbl)),
                    inverse)
    # X[2k] = even[k], X[2k+1] = odd[k]
    return jnp.stack([even, odd], axis=-1).reshape(x.shape[:-1] + (n,))


def _tdot(x, axis: int, M):
    """``y[.., k, ..] = sum_j x[.., j, ..] M[j, k]`` along ``axis``, the
    output axis left in place (one dot_general; XLA folds the move into
    its operand layouts)."""
    y = jnp.tensordot(x, M, axes=[[axis], [0]], precision=_prec_for(x.dtype))
    return jnp.moveaxis(y, -1, axis)


def _matmul_F(x, F_np: np.ndarray, axis: int = -1):
    """x @ F along ``axis`` for complex x and a constant complex DFT
    matrix."""
    axis %= x.ndim
    if not current_settings().karatsuba:
        return _tdot(x, axis, jnp.asarray(F_np))
    rdt = np.float64 if _is_double(x.dtype) else np.float32
    Fr = jnp.asarray(np.ascontiguousarray(F_np.real.astype(rdt)))
    Fi = jnp.asarray(np.ascontiguousarray(F_np.imag.astype(rdt)))
    Fs = jnp.asarray((F_np.real + F_np.imag).astype(rdt))
    ar, ai = jnp.real(x), jnp.imag(x)
    t1 = _tdot(ar, axis, Fr)
    t2 = _tdot(ai, axis, Fi)
    t3 = _tdot(ar + ai, axis, Fs)
    return lax.complex(t1 - t2, t3 - t1 - t2)


def _rmatmul_F(x_real, F_np: np.ndarray, axis: int = -1):
    """x @ F along ``axis`` for REAL x: two real matmuls instead of a
    complex one (halves the MXU work of the R2C stages)."""
    axis %= x_real.ndim
    re = _tdot(x_real, axis, jnp.asarray(np.ascontiguousarray(F_np.real)))
    im = _tdot(x_real, axis, jnp.asarray(np.ascontiguousarray(F_np.imag)))
    return lax.complex(re, im)


# Four-step layout alternative. ``_fourstep`` (the default) keeps the
# transform axis in place and runs the long DFTs as one contraction; the
# einsum formulation below takes the axis to the end and contracts both
# reshaped factor axes with dot_general, the twiddle between them. It was
# measured slower than the swapaxes pipeline ``_fourstep`` replaced
# (v5e, batched-2D 2048^2 x 64 roundtrip: einsum 167.3 ms vs swapaxes
# 137.2 ms, an older row) and stays a benchmarkable toggle
# (``MXUSettings.fourstep_einsum``; the same math, equal to 1e-14 in f64
# on CPU). Applies when both factors are direct-sized.


def set_fourstep_einsum(on: bool) -> None:
    """DEPRECATED shim: set the process-DEFAULT einsum (relayout-free)
    four-step formulation (prefer ``Config(mxu_fourstep_einsum=...)``)."""
    _set_default(fourstep_einsum=bool(on))


@contextlib.contextmanager
def fourstep_einsum(on: bool = True):
    """Scoped fourstep-einsum override, context-local (same pattern as
    ``radix2``)."""
    with use_settings(dataclasses.replace(current_settings(),
                                          fourstep_einsum=bool(on))):
        yield


def _fourstep_einsum(x4, inverse: bool, n1: int, n2: int, dbl: bool):
    """Four-step stages as direct contractions of a [..., s, r] factor
    array (x[..., s*n1 + r]); returns [..., k1, k2] (X[k1*n2 + k2])."""
    prec = _prec_for(x4.dtype)
    if jnp.iscomplexobj(x4):
        b = jnp.einsum("...sr,sk->...kr", x4,
                       jnp.asarray(_dft_np(n2, inverse, dbl)), precision=prec)
    else:  # real first stage: two real contractions (R2C fast path)
        F2 = _dft_np(n2, inverse, dbl)
        br = jnp.einsum("...sr,sk->...kr", x4,
                        jnp.asarray(np.ascontiguousarray(F2.real)),
                        precision=prec)
        bi = jnp.einsum("...sr,sk->...kr", x4,
                        jnp.asarray(np.ascontiguousarray(F2.imag)),
                        precision=prec)
        b = lax.complex(br, bi)
    # Twiddle transposed to the [k2, r] layout of b.
    c = b * jnp.asarray(np.ascontiguousarray(
        _twiddle_np(n1, n2, inverse, dbl).T))
    d = jnp.einsum("...kr,rj->...jk", c,
                   jnp.asarray(_dft_np(n1, inverse, dbl)), precision=prec)
    return d.reshape(d.shape[:-2] + (n1 * n2,))


# The short stage of the four-step (``_fourstep``) runs as an unrolled
# sum of scaled slices on the vector units up to this radix (every output
# slice reads the n1 input slices; the DFT weights of radix 2 and 4 are
# +-1 and +-i); a longer short stage recurses through the matmul path.
_UNROLL_MAX = 8

# The real transforms (R2C, C2R) take the direct contraction up to this
# multiple of ``direct_max``. A direct real transform of length n does
# about n real MACs an element and reads and writes the array once; the
# four-step does less arithmetic but pays three HBM passes. Measured on
# one v5e at HIGHEST (PERF.md section 6): direct R2C/C2R at 1024 beat the
# 4x256 four-step by 8 and 21 ms, and direct R2C at 2048 beat 4x512.
_REAL_DIRECT = 4


def _bcast(v: np.ndarray, axis: int, ndim: int):
    """A constant laid along ``axis`` (and the axes after it, one per
    dimension of ``v``) of an ``ndim`` array."""
    return jnp.asarray(v.reshape(v.shape + (1,) * (ndim - axis - v.ndim)))


def _short_dft(slices, W: np.ndarray, k: int):
    """``sum_j W[j, k] * slices[j]`` with the unit weights spelled as
    adds and subtracts."""
    acc = None
    for j, v in enumerate(slices):
        w = complex(W[j, k])
        if w == 1:
            t = v
        elif w == -1:
            t = -v
        else:
            t = v * w
        acc = t if acc is None else acc + t
    return acc


def _fft_axis(x, axis: int, inverse: bool):
    """Unnormalized DFT along ``axis`` of a complex array, left in place."""
    n = x.shape[axis]
    dbl = _is_double(x.dtype)
    st = current_settings()
    last = axis == x.ndim - 1
    if st.radix2 and n > _R2_BASE and n % 2 == 0:
        if last:
            return _fft_radix2(x, inverse)
        return jnp.moveaxis(_fft_radix2(jnp.moveaxis(x, axis, -1), inverse),
                            -1, axis)
    if n <= st.direct_max:
        return _matmul_F(x, _dft_np(n, inverse, dbl), axis)
    n1, n2 = _split_for(n, st.direct_max)
    if n1 == 1:  # prime length: direct full-size matmul
        return _matmul_F(x, _dft_np(n, inverse, dbl), axis)
    if st.fourstep_einsum and n1 <= st.direct_max and n2 <= st.direct_max:
        xl = x if last else jnp.moveaxis(x, axis, -1)
        y = _fourstep_einsum(xl.reshape(xl.shape[:-1] + (n2, n1)),
                             inverse, n1, n2, dbl)
        return y if last else jnp.moveaxis(y, -1, axis)
    return _fourstep(x, axis, n1, n2, inverse)


def _fourstep(x, axis: int, n1: int, n2: int, inverse: bool):
    """Four-step DFT of length n = n1*n2 along ``axis`` with the short
    factor n1 outside: input t = a*n2 + b, output k = c + n1*d.

    The short DFT over a (n1, unrolled up to ``_UNROLL_MAX``) and the
    twiddle W_n^(b c) are elementwise work on the n1 contiguous blocks of
    the axis, stacked as [.., b, c, ..]; the n1 long DFTs over b (n2) are
    then one contraction on the MXU whose output [.., d, c, ..] is the
    result, reshaped. No swap of the transform axis to the end is needed,
    and one contraction needs fewer HBM bytes and temporaries than n1
    (XLA's cost model and buffer assignment for a v5e)."""
    s, ax = x.shape, axis
    dbl = _is_double(x.dtype)
    W1 = _dft_np(n1, inverse, dbl)
    T = _twiddle_np(n1, n2, inverse, dbl)                  # T[c, b]
    if n1 <= _UNROLL_MAX:
        xa = [lax.slice_in_dim(x, a * n2, (a + 1) * n2, axis=ax)
              for a in range(n1)]
        y = jnp.stack([_short_dft(xa, W1, c) for c in range(n1)],
                      axis=ax + 1) * _bcast(T.T, ax, x.ndim + 1)
        z = _fft_axis(y, ax, inverse)                      # [.., d, c, ..]
    else:
        xr = x.reshape(s[:ax] + (n1, n2) + s[ax + 1:])
        y = _fft_axis(xr, ax, inverse)                     # [.., c, b, ..]
        y = y * _bcast(T, ax, xr.ndim)
        z = jnp.swapaxes(_fft_axis(y, ax + 1, inverse), ax, ax + 1)
    return z.reshape(s)


def _fft_last(x, inverse: bool):
    """Unnormalized DFT along the last axis of a complex array."""
    return _fft_axis(x, x.ndim - 1, inverse)


def _rfft_axis(x, axis: int):
    """Unnormalized R2C DFT along ``axis`` of a real array; output length
    n//2+1 there (the reference's R2C halving, ``params.hpp:30``).

    Direct up to ``_REAL_DIRECT * direct_max``; longer axes run
    ``_fourstep``'s decomposition with each long DFT cut to the outputs
    k <= n/2 (d <= (n//2)//n1): about half the long-stage work, where
    transforming the full length and slicing would throw half of it
    away."""
    n = x.shape[axis]
    n_out = n // 2 + 1
    dbl = _is_double(x.dtype)
    st = current_settings()
    n1, n2 = _split_for(n, st.direct_max)
    if n <= _REAL_DIRECT * st.direct_max or n1 == 1:
        return _rmatmul_F(x, _dft_np(n, False, dbl)[:, :n_out], axis)
    if n1 > _UNROLL_MAX:
        cdt = np.complex128 if dbl else np.complex64
        return lax.slice_in_dim(_fft_axis(x.astype(cdt), axis, False),
                                0, n_out, axis=axis)
    W1 = _dft_np(n1, False, dbl)
    T = _twiddle_np(n1, n2, False, dbl)
    F2 = _dft_np(n2, False, dbl)[:, :(n // 2) // n1 + 1]
    xa = [lax.slice_in_dim(x, a * n2, (a + 1) * n2, axis=axis)
          for a in range(n1)]
    y = jnp.stack([_short_dft(xa, W1, c) for c in range(n1)],
                  axis=axis + 1) * _bcast(T.T, axis, x.ndim + 1)
    z = _matmul_F(y, F2, axis)                             # [.., d, c, ..]
    z = z.reshape(x.shape[:axis] + (F2.shape[1] * n1,) + x.shape[axis + 1:])
    return lax.slice_in_dim(z, 0, n_out, axis=axis)


def _rfft_last(x):
    """``_rfft_axis`` along the last axis."""
    return _rfft_axis(x, x.ndim - 1)


def _irfft_axis(c, n: int, axis: int):
    """Unnormalized C2R along ``axis`` of a half spectrum already fitted
    to n//2+1 entries: real output of length n.

    Up to ``_REAL_DIRECT * direct_max``: two real contractions with the folded
    half-spectrum matrices (``_c2r_np``). Longer axes: the real output is
    Re(IDFT(X~)) with X~[k] = a_k X[k] for k <= n/2 and 0 above (a_k = 2
    but at DC and Nyquist), so ``_fourstep``'s inverse runs each long
    DFT over the d <= (n//2)//n1 entries of its residue class c only,
    keeps the real part of the short stage, and writes the n1 contiguous
    output blocks: half the work of rebuilding the Hermitian spectrum,
    and no reversal."""
    dbl = _is_double(c.dtype)
    st = current_settings()
    n1, n2 = _split_for(n, st.direct_max)
    direct = n <= _REAL_DIRECT * st.direct_max or n1 == 1
    if direct or n1 > _UNROLL_MAX:
        if direct:
            CR, CI = _c2r_np(n, dbl)
            return (_tdot(jnp.real(c), axis, jnp.asarray(CR))
                    - _tdot(jnp.imag(c), axis, jnp.asarray(CI)))
        return jnp.real(_fft_axis(_hermitian_extend(c, n, axis), axis, True))
    m = n // 2
    D = m // n1 + 1
    k = np.arange(m + 1)
    wk = np.where((k == 0) | ((k == m) & (n % 2 == 0)), 1.0, 2.0)
    c = c * _bcast(wk.astype(np.float64 if dbl else np.float32), axis,
                   c.ndim)
    c = _fit_axis(c, axis, D * n1)
    cr = c.reshape(c.shape[:axis] + (D, n1) + c.shape[axis + 1:])
    F2 = _dft_np(n2, True, dbl)[:D]
    W1 = _dft_np(n1, True, dbl)
    T = _twiddle_np(n1, n2, True, dbl)
    v = _matmul_F(cr, F2, axis) * _bcast(T.T, axis, cr.ndim)  # [.., b, j, ..]
    vs = [lax.index_in_dim(v, j, axis + 1, keepdims=False) for j in range(n1)]
    return jnp.concatenate([jnp.real(_short_dft(vs, W1, a))
                            for a in range(n1)], axis=axis)


@functools.lru_cache(maxsize=None)
def _c2r_np(n: int, double: bool) -> Tuple[np.ndarray, np.ndarray]:
    """Half-spectrum inverse-DFT matrices (CR, CI) with conjugate symmetry
    folded in: for Hermitian input of length n//2+1,
    ``y = Re(c) @ CR - Im(c) @ CI`` equals ``Re(idft(hermitian_extend(c)))``
    with a quarter of the MXU work of the full complex matmul (2 real
    matmuls of n//2+1 depth vs 4 of n)."""
    dt = np.float64 if double else np.float32
    n_out = n // 2 + 1
    jk = np.outer(np.arange(n_out), np.arange(n)) % n  # reduce for exact trig
    ang = 2.0 * np.pi * jk / n
    a = np.full((n_out, 1), 2.0)
    a[0] = 1.0
    if n % 2 == 0:
        a[n // 2] = 1.0
    return (a * np.cos(ang)).astype(dt), (a * np.sin(ang)).astype(dt)


def _hermitian_extend(c, n: int, axis: int = -1):
    """Rebuild the full length-n spectrum from its n//2+1 half (C2R input)
    along ``axis``. The tail is reversed with a gather: a reverse op
    reading an FFT's output went wrong on a TPU v5e (``ops/fft.
    _reversed_last``)."""
    tail = jnp.conj(lax.slice_in_dim(c, 1, (n + 1) // 2, axis=axis))
    tail = jnp.take(tail, np.arange(tail.shape[axis] - 1, -1, -1), axis=axis)
    return jnp.concatenate([c, tail], axis=axis)


def _fit_axis(c, axis: int, n: int):
    """Crop or zero-pad axis to extent n (jnp.fft's ``s=``/``n=`` semantics,
    applied before transforming along that axis)."""
    cur = c.shape[axis]
    if cur > n:
        c = lax.slice_in_dim(c, 0, n, axis=axis)
    elif cur < n:
        widths = [(0, 0)] * c.ndim
        widths[axis % c.ndim] = (0, n - cur)
        c = jnp.pad(c, widths)
    return c


# ---------------------------------------------------------------------------
# Norm scaling (same FFTNorm semantics as ops/fft.py)
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


# ---------------------------------------------------------------------------
# Public API (mirrors ops/fft.py signatures)
# ---------------------------------------------------------------------------


def fft(x, axis: int, norm: FFTNorm = FFTNorm.NONE):
    cdt = np.complex128 if _is_double(x.dtype) else np.complex64
    axis %= x.ndim
    y = _fft_axis(x.astype(cdt), axis, False)
    return _scaled(y, _fwd_scale(x.shape[axis], norm))


def ifft(x, axis: int, norm: FFTNorm = FFTNorm.NONE):
    cdt = np.complex128 if _is_double(x.dtype) else np.complex64
    axis %= x.ndim
    y = _fft_axis(x.astype(cdt), axis, True)
    return _scaled(y, _inv_scale(x.shape[axis], norm))


def rfft(x, axis: int, norm: FFTNorm = FFTNorm.NONE):
    axis %= x.ndim
    return _scaled(_rfft_axis(x, axis), _fwd_scale(x.shape[axis], norm))


def irfft(x, n: int, axis: int, norm: FFTNorm = FFTNorm.NONE):
    cdt = np.complex128 if _is_double(x.dtype) else np.complex64
    axis %= x.ndim
    # jnp.fft.irfft contract: the spectral axis is cropped/zero-padded to
    # n//2+1 before inversion.
    c = _fit_axis(x.astype(cdt), axis, n // 2 + 1)
    return _scaled(_irfft_axis(c, n, axis), _inv_scale(n, norm))


def fftn(x, axes: Sequence[int], norm: FFTNorm = FFTNorm.NONE):
    for a in axes:
        x = fft(x, axis=a, norm=norm)
    return x


def ifftn(x, axes: Sequence[int], norm: FFTNorm = FFTNorm.NONE):
    for a in axes:
        x = ifft(x, axis=a, norm=norm)
    return x


def rfftn_3d(x, norm: FFTNorm = FFTNorm.NONE):
    c = rfft(x, axis=-1, norm=norm)
    c = fft(c, axis=-2, norm=norm)
    return fft(c, axis=-3, norm=norm)


# ---------------------------------------------------------------------------
# All-real-planes 3D transform: the same DFT matmuls with the complex
# arithmetic written out on separate (re, im) f32 planes, so the compiled
# program contains NO complex dtypes anywhere — input, output, and every
# intermediate are real. Since XLA lowers complex dots to exactly these
# real matmuls anyway, this formulation measures the same hardware work.
# Direct sizes only (every axis <= DIRECT_MAX); reachable as the chain
# timer's ``matmul-planes`` backend label (testing/chaintimer.py; ROADMAP
# C3 decides whether it stays).
# ---------------------------------------------------------------------------


_RP_EINSUM = ("ak,ayz->kyz", "ak,xaz->xkz", "ak,xya->xyk")


def _rp_stage(ar, ai, F_np: np.ndarray, axis: int):
    """One DFT stage along ``axis`` of split-plane data. ``ai=None`` means
    real input (the R2C first stage's two-matmul fast path)."""
    eq = _RP_EINSUM[axis]
    prec = _prec_for(ar.dtype)
    Fr = jnp.asarray(np.ascontiguousarray(F_np.real.astype(np.float32)))
    Fi = jnp.asarray(np.ascontiguousarray(F_np.imag.astype(np.float32)))

    def e(M, a):
        return jnp.einsum(eq, M, a, precision=prec)

    if ai is None:
        return e(Fr, ar), e(Fi, ar)
    return e(Fr, ar) - e(Fi, ai), e(Fr, ai) + e(Fi, ar)


def rfftn_3d_planes(x):
    """Unnormalized forward R2C over the trailing 3 axes of a REAL 3D f32
    array, returned as (re, im) f32 planes of shape (X, Y, Z//2+1)."""
    X, Y, Z = x.shape
    for n in (X, Y, Z):
        if n > DIRECT_MAX:
            raise ValueError(f"rfftn_3d_planes is direct-size only "
                             f"(axis {n} > {DIRECT_MAX})")
    ar, ai = _rp_stage(x.astype(jnp.float32), None,
                       _dft_np(Z, False, False)[:, :Z // 2 + 1], 2)
    ar, ai = _rp_stage(ar, ai, _dft_np(Y, False, False), 1)
    return _rp_stage(ar, ai, _dft_np(X, False, False), 0)


def irfftn_3d_planes(cr, ci, shape_3d):
    """Unnormalized inverse of ``rfftn_3d_planes``: (re, im) spectral planes
    of shape (X, Y, Z//2+1) -> real f32 (X, Y, Z)."""
    X, Y, Z = shape_3d
    for n in (X, Y, Z):
        if n > DIRECT_MAX:
            raise ValueError(f"irfftn_3d_planes is direct-size only "
                             f"(axis {n} > {DIRECT_MAX})")
    er, ei = _rp_stage(cr, ci, _dft_np(X, True, False), 0)
    er, ei = _rp_stage(er, ei, _dft_np(Y, True, False), 1)
    CR, CI = _c2r_np(Z, False)
    prec = _prec_for(er.dtype)
    return (jnp.einsum(_RP_EINSUM[2], jnp.asarray(CR), er, precision=prec)
            - jnp.einsum(_RP_EINSUM[2], jnp.asarray(CI), ei, precision=prec))


def irfftn_3d(x, shape_3d: Tuple[int, int, int], norm: FFTNorm = FFTNorm.NONE):
    c = ifft(_fit_axis(x, -3, shape_3d[-3]), axis=-3, norm=norm)
    c = ifft(_fit_axis(c, -2, shape_3d[-2]), axis=-2, norm=norm)
    return irfft(c, n=shape_3d[-1], axis=-1, norm=norm)
