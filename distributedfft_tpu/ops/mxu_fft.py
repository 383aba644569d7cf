"""MXU matmul-based FFT backend (four-step Cooley-Tukey).

TPU has no FFT hardware unit: XLA expands ``fft`` HLOs into scalar/vector
code that runs on the VPU, leaving the 128x128 MXU systolic array — where
virtually all of the chip's FLOPs live — idle. This backend reformulates the
DFT as dense matrix multiplication so the transform runs on the MXU:

* **Direct**: for ``n <= DIRECT_MAX`` the transform along an axis is one
  batched matmul with the ``n x n`` DFT matrix ``F[j,k] = w^(jk)``,
  ``w = exp(-2*pi*i/n)``.
* **Four-step** (Bailey): for larger ``n = n1*n2``, decompose index
  ``n = s*n1 + r`` (r in [0,n1)), ``k = k1*n2 + k2``:

      X[k1*n2+k2] = sum_r W_n1^(r*k1) * [ W_n^(r*k2) * sum_s x[s*n1+r] * W_n2^(s*k2) ]

  i.e. reshape -> DFT matmul (n2) -> twiddle multiply -> DFT matmul (n1) ->
  reshape, recursing when a factor still exceeds ``DIRECT_MAX``. The matmul
  count is O(n * (n1+n2)) flops — more than O(n log n), but on the MXU's
  dense-matmul throughput rather than the VPU's. The factor choice is the
  MXU-deep split (``_split_for``): whenever both factors can stay on the
  direct path, the dominant factor is the largest divisor <= ``direct_max``
  (2048 -> 4x512, 4096 -> 8x512) rather than the balanced pair (32x64,
  64x64), so intermediate lengths keep the systolic array's full
  contraction depth — the large-axis extension of the direct table, driven
  by a pre-PR-12 single-chip 1024^3 measurement (652 vs 228 GFLOPS/chip;
  not measured on the current code).

The matmul is the hot op of this backend; it lowers to plain XLA
``dot_general`` so the compiler fuses the twiddle multiplies into the
surrounding elementwise graph.

Role in the framework: selected by ``Config.fft_backend = "matmul"`` as a
drop-in alternative to the XLA-FFT local layer (``ops/fft.py``); this is the
TPU-first analog of the reference's cuFFT plan choice (the reference's L0
shim, ``include/cufft.hpp:23-61``, hard-wires cuFFT — on TPU the equivalent
"vendor transform" is a compiler expansion, so the framework supplies its own
MXU-shaped implementation and lets benchmarks pick the winner, preserving
the reference's comparative spirit).

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
      8.2e-7 — O(f32 eps) — at half the MXU passes of HIGHEST (3.0e-8),
      so HIGH is the default. f64 inputs always use HIGHEST.
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


def _matmul_F(x, F_np: np.ndarray):
    """x @ F for complex x and a constant complex DFT matrix."""
    prec = _prec_for(x.dtype)
    if not current_settings().karatsuba:
        return jnp.matmul(x, jnp.asarray(F_np), precision=prec)
    rdt = np.float64 if _is_double(x.dtype) else np.float32
    Fr = jnp.asarray(np.ascontiguousarray(F_np.real.astype(rdt)))
    Fi = jnp.asarray(np.ascontiguousarray(F_np.imag.astype(rdt)))
    Fs = jnp.asarray((F_np.real + F_np.imag).astype(rdt))
    ar, ai = jnp.real(x), jnp.imag(x)
    t1 = jnp.matmul(ar, Fr, precision=prec)
    t2 = jnp.matmul(ai, Fi, precision=prec)
    t3 = jnp.matmul(ar + ai, Fs, precision=prec)
    return lax.complex(t1 - t2, t3 - t1 - t2)


def _rmatmul_F(x_real, F_np: np.ndarray):
    """x @ F for REAL x: two real matmuls instead of a complex one (halves
    the MXU work for the R2C first stage and the four-step first stage)."""
    prec = _prec_for(x_real.dtype)
    re = jnp.matmul(x_real, jnp.asarray(np.ascontiguousarray(F_np.real)),
                    precision=prec)
    im = jnp.matmul(x_real, jnp.asarray(np.ascontiguousarray(F_np.imag)),
                    precision=prec)
    return lax.complex(re, im)


# Four-step layout strategy. The original formulation materializes three
# jnp.swapaxes relayouts of the full array per four-step level (pack to
# [r,s], re-pack between the stages, unpack at the end); the einsum
# formulation contracts the reshaped factor axes directly (dot_general
# with non-trailing contracting dims), letting XLA pick operand layouts.
# Measured on v5e (batched-2D 2048^2 x 64 roundtrip, same session):
# einsum 167.3 ms vs swapaxes 137.2 ms — XLA's layout assignment for the
# non-trailing contraction is WORSE than the explicit relayout pipeline,
# so the swapaxes path stays the default and the einsum variant remains a
# benchmarkable toggle (``MXUSettings.fourstep_einsum``; exact same math,
# bit-identical in f64 on CPU). Applies when both factors are direct-sized
# (n <= DIRECT_MAX^2 = 256k — every practical axis).


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


def _fft_last(x, inverse: bool):
    """Unnormalized DFT along the last axis of a complex array."""
    n = x.shape[-1]
    dbl = _is_double(x.dtype)
    st = current_settings()
    if st.radix2 and n > _R2_BASE and n % 2 == 0:
        return _fft_radix2(x, inverse)
    if n <= st.direct_max:
        return _matmul_F(x, _dft_np(n, inverse, dbl))
    n1, n2 = _split_for(n, st.direct_max)
    if n1 == 1:  # prime length: direct full-size matmul
        return _matmul_F(x, _dft_np(n, inverse, dbl))
    if st.fourstep_einsum and n1 <= st.direct_max and n2 <= st.direct_max:
        return _fourstep_einsum(x.reshape(x.shape[:-1] + (n2, n1)),
                                inverse, n1, n2, dbl)
    # x[..., s*n1 + r] -> A[..., r, s]
    a = jnp.swapaxes(x.reshape(x.shape[:-1] + (n2, n1)), -1, -2)
    b = _fft_last(a, inverse)                       # DFT over s -> (r, k2)
    c = b * jnp.asarray(_twiddle_np(n1, n2, inverse, dbl))
    d = _fft_last(jnp.swapaxes(c, -1, -2), inverse)  # DFT over r -> (k2, k1)
    return jnp.swapaxes(d, -1, -2).reshape(x.shape[:-1] + (n,))


def _rfft_last(x):
    """Unnormalized R2C DFT along the last axis of a real array; output
    length n//2+1 (the reference's R2C halving, ``params.hpp:30``)."""
    n = x.shape[-1]
    n_out = n // 2 + 1
    dbl = _is_double(x.dtype)
    st = current_settings()
    if n <= st.direct_max:
        return _rmatmul_F(x, _dft_np(n, False, dbl)[:, :n_out])
    n1, n2 = _split_for(n, st.direct_max)
    if n1 == 1:
        return _rmatmul_F(x, _dft_np(n, False, dbl)[:, :n_out])
    if st.fourstep_einsum and n1 <= st.direct_max and n2 <= st.direct_max:
        full = _fourstep_einsum(x.reshape(x.shape[:-1] + (n2, n1)),
                                False, n1, n2, dbl)
        return full[..., :n_out]
    a = jnp.swapaxes(x.reshape(x.shape[:-1] + (n2, n1)), -1, -2)
    # First stage on real data: real matmul pair.
    if n2 <= st.direct_max:
        b = _rmatmul_F(a, _dft_np(n2, False, dbl))
    else:
        cdt = np.complex128 if dbl else np.complex64
        b = _fft_last(a.astype(cdt), False)
    c = b * jnp.asarray(_twiddle_np(n1, n2, False, dbl))
    d = _fft_last(jnp.swapaxes(c, -1, -2), False)
    full = jnp.swapaxes(d, -1, -2).reshape(x.shape[:-1] + (n,))
    return full[..., :n_out]


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


def _hermitian_extend(c, n: int):
    """Rebuild the full length-n spectrum from its n//2+1 half (C2R input)."""
    tail = jnp.conj(c[..., 1:(n + 1) // 2])[..., ::-1]
    return jnp.concatenate([c, tail], axis=-1)


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
    x = jnp.moveaxis(x.astype(cdt), axis, -1)
    y = _scaled(_fft_last(x, False), _fwd_scale(x.shape[-1], norm))
    return jnp.moveaxis(y, -1, axis)


def ifft(x, axis: int, norm: FFTNorm = FFTNorm.NONE):
    cdt = np.complex128 if _is_double(x.dtype) else np.complex64
    x = jnp.moveaxis(x.astype(cdt), axis, -1)
    y = _scaled(_fft_last(x, True), _inv_scale(x.shape[-1], norm))
    return jnp.moveaxis(y, -1, axis)


def rfft(x, axis: int, norm: FFTNorm = FFTNorm.NONE):
    x = jnp.moveaxis(x, axis, -1)
    y = _scaled(_rfft_last(x), _fwd_scale(x.shape[-1], norm))
    return jnp.moveaxis(y, -1, axis)


def irfft(x, n: int, axis: int, norm: FFTNorm = FFTNorm.NONE):
    cdt = np.complex128 if _is_double(x.dtype) else np.complex64
    c = jnp.moveaxis(x.astype(cdt), axis, -1)
    # jnp.fft.irfft contract: the spectral axis is cropped/zero-padded to
    # n//2+1 before inversion.
    c = _fit_axis(c, -1, n // 2 + 1)
    if n <= current_settings().direct_max:
        dbl = _is_double(c.dtype)
        CR, CI = _c2r_np(n, dbl)
        prec = _prec_for(c.dtype)
        y = (jnp.matmul(jnp.real(c), jnp.asarray(CR), precision=prec)
             - jnp.matmul(jnp.imag(c), jnp.asarray(CI), precision=prec))
    else:
        full = _hermitian_extend(c, n)
        y = jnp.real(_fft_last(full, True))
    return jnp.moveaxis(_scaled(y, _inv_scale(n, norm)), -1, axis)


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
# Direct sizes only (every axis <= DIRECT_MAX); reachable as bench.py's
# ``DFFT_BENCH_BACKEND=matmul-planes`` (ROADMAP C3 decides whether it
# stays).
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
