"""Pallas TPU kernel FFT backend (fused multi-stage DFT kernels).

The ``"matmul"`` backend (``ops/mxu_fft.py``) expresses each DFT stage as
XLA ``dot_general`` calls plus elementwise epilogues, trusting the compiler
to fuse and schedule them. This backend hand-writes the hot ops as Pallas
kernels instead, at two granularities:

* **fused 3D path** (``_rfftn3d_fused`` / ``_irfftn3d_fused``): at direct
  sizes (every axis <= ``mxu_fft.DIRECT_MAX``) one kernel computes TWO
  transform stages per HBM pass — z-R2C + y-C2C forward, y-C2C + z-C2R
  inverse — with the inter-stage intermediate resident in VMEM only;
* **per-axis four-step path** (everything else): one kernel = one four-step
  stage, the complex matmul and the twiddle epilogue in a single
  VMEM-resident pass; a real-input variant halves the MXU work of the R2C
  first stage.

MEASURED VERDICT (v5e, 256^3 f32 roundtrip, chained-iteration harness;
round-2 numbers): **matmul@HIGH 1.48-1.51 ms, pallas fused 3.17 ms,
matmul@HIGHEST 2.61 ms** — the matmul backend stays the default, and the
gap is structural, not a tuning artifact:

* the fused zy kernel alone (one HBM pass) measures 0.91 ms where XLA's two
  SEPARATE giant dot_generals + marshalling measure 0.61 ms: Mosaic's
  per-row left-multiply matmuls (needed to keep the kernel transpose-free)
  run at ~2/3 the throughput of XLA's one wide contraction, which costs
  more than the saved intermediate round-trip (~0.17 ms of HBM traffic at
  820 GB/s) recovers;
* ``pallas_call`` is a custom-call boundary: XLA cannot fuse the chain
  carrier or the next stage's operand prep into it, so the composed
  pipeline pays ~0.8 ms of extra HBM passes that the pure-jnp backend's
  end-to-end fusion avoids entirely.

For THIS op — dense matmuls with elementwise epilogues and no data-dependent
access — XLA's own scheduling is already near-optimal, and the productive
TPU-first wins are in backend-level policy (bf16x3 HIGH precision, the
half-spectrum C2R constants, the four-step factorization), not in replacing
dot_general with Mosaic. The backend remains supported, raced honestly by
``testing/autotune.py`` on every platform, and is the right substrate for
ops XLA genuinely schedules badly (double-buffered collective-compute
overlap), but it is NOT the default.

Mosaic constraints encoded here (all discovered on hardware):
``precision=HIGH`` does not lower inside kernels — the HIGH policy is
emulated with an explicit bf16 hi/lo split (``_dot2``); block shapes pad to
(8, 128) tiles, so VMEM budgeting must use padded extents (a 129-wide
half-spectrum block occupies 256 lanes) and the ~16 MB scoped-vmem limit is
a hard compile error when exceeded.

Row-twiddle contract of the per-axis path: for a stage input reshaped to
``(..., n1, n2)`` the flattened matmul row index is ``b*n1 + r``, so the
twiddle row is ``row % n1`` — the kernel receives the twiddle pre-tiled to
the row-block height (a multiple of ``n1``), keeping the epilogue a plain
elementwise multiply with no gather.

Selected via ``Config.fft_backend = "pallas"``. Off-TPU (the CPU test mesh)
the kernels run in Pallas interpret mode; f64 inputs fall back to the
``matmul`` backend's jnp path on TPU (no native f64 there — correctness
gates for double precision run on CPU, SURVEY §7 hard parts).

Public API mirrors ``ops/mxu_fft.py`` (same signatures, same FFTNorm
semantics); the four-step recursion and constant caches are shared with it.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ..params import FFTNorm
from . import mxu_fft as mx

# Row-block height per grid step (padded up to a multiple of the twiddle
# period n1 when a twiddle is fused). 256 f32 rows x <=512 lanes keeps
# x/y/F/T blocks ~4.5 MB total, comfortably inside ~16 MB VMEM.
_ROW_BLOCK = 256

# Largest contraction length the kernel accepts with the full DFT matrix
# resident in VMEM. Above this (huge prime axis lengths), fall back to the
# jnp matmul path.
_N_MAX = 1024

# MXU precision follows the matmul backend's policy (HIGH three-pass bf16
# for f32 — measured 8.2e-7 fwd rel err at 256^3 — HIGHEST only for f64,
# which this kernel routes to the fallback anyway). See
# mxu_fft.MXUSettings.precision (read via mxu_fft._prec_for, so a plan's
# context-scoped settings reach this kernel too).
def _prec():
    return mx._prec_for(jnp.float32)


def _interpret() -> bool:
    """Compile on TPU; interpret elsewhere (the CPU test mesh)."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Kernels. Complex arrays travel as (real, imag) f32 pairs: Mosaic has no
# native complex tiles, and split planes let each product hit the MXU as a
# plain f32 matmul.
# ---------------------------------------------------------------------------


def _split_bf16(a):
    """bf16 hi + residual lo planes of an f32 value (HIGH emulation)."""
    ah = a.astype(jnp.bfloat16)
    return ah, (a - ah.astype(jnp.float32)).astype(jnp.bfloat16)


def _planes(a):
    """Precision-dependent operand prep, done ONCE per value so constants
    and reused intermediates are not re-split per product.

    Mosaic rejects ``precision=HIGH`` inside kernels (only DEFAULT/HIGHEST
    lower), so the HIGH policy — three-pass bf16 emulation, the measured
    accuracy/speed sweet spot (mxu_fft.MXUSettings.precision) — is emulated by
    splitting each operand into bf16 hi + residual lo here and taking the
    three significant cross products in ``_dot2``, exactly what XLA emits
    for HIGH outside Pallas."""
    if _prec() == lax.Precision.HIGH:
        return _split_bf16(a)
    return (a, None)


def _dot2(ap, bp):
    """Matmul of two ``_planes`` operands at the backend's precision."""
    ah, al = ap
    bh, bl = bp
    if al is None:
        return jnp.dot(ah, bh, precision=_prec(),
                       preferred_element_type=jnp.float32)

    def d(u, v):
        return jnp.dot(u, v, preferred_element_type=jnp.float32)

    return d(ah, bh) + d(ah, bl) + d(al, bh)


def _c2r_kernel(xr_ref, xi_ref, cr_ref, ci_ref, y_ref):
    """Half-spectrum inverse: y = Re(c) @ CR - Im(c) @ CI with conjugate
    symmetry folded into the constant matrices (mxu_fft._c2r_np) — half the
    MXU work of inverting the Hermitian-extended full spectrum."""
    y_ref[:] = (_dot2(_planes(xr_ref[:]), _planes(cr_ref[:]))
                - _dot2(_planes(xi_ref[:]), _planes(ci_ref[:])))


def _cmatmul_kernel(xr_ref, xi_ref, fr_ref, fi_ref, yr_ref, yi_ref):
    xr, xi = _planes(xr_ref[:]), _planes(xi_ref[:])
    fr, fi = _planes(fr_ref[:]), _planes(fi_ref[:])
    yr_ref[:] = _dot2(xr, fr) - _dot2(xi, fi)
    yi_ref[:] = _dot2(xr, fi) + _dot2(xi, fr)


def _cmatmul_tw_kernel(xr_ref, xi_ref, fr_ref, fi_ref, tr_ref, ti_ref,
                       yr_ref, yi_ref):
    xr, xi = _planes(xr_ref[:]), _planes(xi_ref[:])
    fr, fi = _planes(fr_ref[:]), _planes(fi_ref[:])
    yr = _dot2(xr, fr) - _dot2(xi, fi)
    yi = _dot2(xr, fi) + _dot2(xi, fr)
    tr, ti = tr_ref[:], ti_ref[:]
    yr_ref[:] = yr * tr - yi * ti      # twiddle epilogue, fused in VMEM
    yi_ref[:] = yr * ti + yi * tr


def _rmatmul_kernel(x_ref, fr_ref, fi_ref, yr_ref, yi_ref):
    x = _planes(x_ref[:])
    yr_ref[:] = _dot2(x, _planes(fr_ref[:]))
    yi_ref[:] = _dot2(x, _planes(fi_ref[:]))


def _rmatmul_tw_kernel(x_ref, fr_ref, fi_ref, tr_ref, ti_ref,
                       yr_ref, yi_ref):
    x = _planes(x_ref[:])
    yr = _dot2(x, _planes(fr_ref[:]))
    yi = _dot2(x, _planes(fi_ref[:]))
    tr, ti = tr_ref[:], ti_ref[:]
    yr_ref[:] = yr * tr - yi * ti
    yi_ref[:] = yr * ti + yi * tr


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _row_block(period: int) -> int:
    """Row-block height: a multiple of the twiddle period covering >= 256
    rows when possible (period 1 = no twiddle alignment constraint)."""
    if period >= _ROW_BLOCK:
        return period
    return period * (_ROW_BLOCK // period)


@functools.lru_cache(maxsize=None)
def _tiled_twiddle(n1: int, n2: int, inverse: bool, tb: int) -> Tuple[np.ndarray, np.ndarray]:
    """Four-step twiddle tiled up to the row-block height (f32 planes)."""
    t = mx._twiddle_np(n1, n2, inverse, False)
    t = np.tile(t, (tb // n1, 1))
    return (np.ascontiguousarray(t.real.astype(np.float32)),
            np.ascontiguousarray(t.imag.astype(np.float32)))


def _f32_planes(F: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    return (np.ascontiguousarray(F.real.astype(np.float32)),
            np.ascontiguousarray(F.imag.astype(np.float32)))


def _spec(block, index_map):
    """``pl.BlockSpec`` whose block indices stay int32 under
    ``jax_enable_x64`` (Mosaic refuses the i64 constants it would
    otherwise get)."""
    return pl.BlockSpec(
        block, lambda *g: tuple(jnp.int32(v) for v in index_map(*g)))


def _vma(x) -> frozenset:
    """The value's varying-across-mesh-axes set (empty outside
    shard_map)."""
    return jax.typeof(x).vma


def _lift_vma(args, vma):
    """Under shard_map every kernel operand must carry the same
    varying-across-mesh-axes set; lift replicated constants to match the
    per-shard data."""
    if not vma:
        return args

    def one(a):
        missing = vma - _vma(a)
        return lax.pvary(a, tuple(missing)) if missing else a

    return [one(a) for a in args]


def _call_stage(x2, F_np: np.ndarray, twiddle: "Tuple[int, int, bool] | None"):
    """One DFT stage on 2D data: ``y = (x2 @ F) [* T]``.

    x2: (M, n) complex64 or float32 (real-input fast path); F_np: (n, k);
    twiddle: (n1, n2, inverse) with rows of x2 cycling through n1.
    Returns (M, k) complex64.
    """
    m, n = x2.shape
    k = F_np.shape[1]
    real_in = not jnp.issubdtype(x2.dtype, jnp.complexfloating)

    if _interpret() and _vma(x2):
        # Pallas's HLO interpreter cannot yet thread shard_map's vma through
        # its internal grid loop carries; off-TPU, inside shard_map, compute
        # the stage with the equivalent jnp ops (the compiled Mosaic path on
        # real TPU takes the kernel below).
        F = jnp.asarray(F_np.astype(np.complex64))
        y = (mx._rmatmul_F(x2.astype(jnp.float32), F_np.astype(np.complex64))
             if real_in else jnp.matmul(x2.astype(jnp.complex64), F,
                                        precision=_prec()))
        if twiddle is not None:
            n1, n2, inv = twiddle
            tr, ti = _tiled_twiddle(n1, n2, inv, _row_block(n1))
            t = lax.complex(jnp.asarray(tr), jnp.asarray(ti))
            reps = (m + t.shape[0] - 1) // t.shape[0]
            y = y * jnp.tile(t, (reps, 1))[:m]
        return y

    period = twiddle[0] if twiddle is not None else 1
    tb = _row_block(period)
    m_pad = tb * ((m + tb - 1) // tb)
    if m_pad != m:
        x2 = jnp.pad(x2, [(0, m_pad - m), (0, 0)])
    grid = (m_pad // tb,)

    fr, fi = _f32_planes(F_np)
    row_spec = _spec((tb, n), lambda i: (i, 0))
    const_spec = _spec((n, k), lambda i: (0, 0))
    tw_spec = _spec((tb, k), lambda i: (0, 0))
    out_spec = _spec((tb, k), lambda i: (i, 0))
    # Propagate the input's varying-across-mesh-axes set so the kernel works
    # under shard_map's vma checking (per-shard data varies over the mesh).
    vma = _vma(x2)
    out_shape = [jax.ShapeDtypeStruct((m_pad, k), jnp.float32, vma=vma)] * 2

    flops_c = (2 if real_in else 4) * 2 * m_pad * n * k
    cost = pl.CostEstimate(flops=flops_c, transcendentals=0,
                           bytes_accessed=4 * (m_pad * (n + k) * 2 + n * k * 2))

    if real_in:
        args = [x2.astype(jnp.float32), fr, fi]
        specs = [row_spec, const_spec, const_spec]
        kern = _rmatmul_kernel if twiddle is None else _rmatmul_tw_kernel
    else:
        xc = x2.astype(jnp.complex64)
        args = [jnp.real(xc), jnp.imag(xc), fr, fi]
        specs = [row_spec, row_spec, const_spec, const_spec]
        kern = _cmatmul_kernel if twiddle is None else _cmatmul_tw_kernel
    if twiddle is not None:
        n1, n2, inv = twiddle
        tr, ti = _tiled_twiddle(n1, n2, inv, tb)
        args += [jnp.asarray(tr), jnp.asarray(ti)]
        specs += [tw_spec, tw_spec]
    args = _lift_vma(args, vma)

    yr, yi = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=specs,
        out_specs=[out_spec, out_spec],
        out_shape=out_shape,
        cost_estimate=cost,
        interpret=_interpret(),
    )(*args)
    y = lax.complex(yr, yi)
    return y[:m] if m_pad != m else y


def _stage(x, F_np: np.ndarray, twiddle=None):
    """DFT stage along the LAST axis of an nd array (rows = flattened rest)."""
    lead = x.shape[:-1]
    y2 = _call_stage(x.reshape((-1, x.shape[-1])), F_np, twiddle)
    return y2.reshape(lead + (F_np.shape[1],))


def _c2r_stage(c, n: int):
    """Half-spectrum C2R along the last axis (length n//2+1 -> n, real)."""
    lead = c.shape[:-1]
    c2 = c.reshape((-1, c.shape[-1])).astype(jnp.complex64)
    m, n_in = c2.shape
    CR, CI = mx._c2r_np(n, False)
    xr, xi = jnp.real(c2), jnp.imag(c2)

    if _interpret() and _vma(c2):
        y2 = (jnp.matmul(xr, jnp.asarray(CR), precision=_prec())
              - jnp.matmul(xi, jnp.asarray(CI), precision=_prec()))
        return y2.reshape(lead + (n,))

    tb = _row_block(1)
    m_pad = tb * ((m + tb - 1) // tb)
    if m_pad != m:
        xr = jnp.pad(xr, [(0, m_pad - m), (0, 0)])
        xi = jnp.pad(xi, [(0, m_pad - m), (0, 0)])
    vma = _vma(c2)
    row_spec = _spec((tb, n_in), lambda i: (i, 0))
    const_spec = _spec((n_in, n), lambda i: (0, 0))
    out_spec = _spec((tb, n), lambda i: (i, 0))
    args = _lift_vma([xr, xi, jnp.asarray(CR), jnp.asarray(CI)], vma)
    y2 = pl.pallas_call(
        _c2r_kernel,
        grid=(m_pad // tb,),
        in_specs=[row_spec, row_spec, const_spec, const_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, n), jnp.float32, vma=vma),
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * m_pad * n_in * n, transcendentals=0,
            bytes_accessed=4 * (m_pad * (2 * n_in + n) + 2 * n_in * n)),
        interpret=_interpret(),
    )(*args)
    return (y2[:m] if m_pad != m else y2).reshape(lead + (n,))


def _use_fallback(x) -> bool:
    """jnp-matmul fallback: f64 data (kernel is f32-only; f64 gates run
    via the matmul backend on CPU)."""
    return mx._is_double(x.dtype)


# ---------------------------------------------------------------------------
# Fused 3D path: two kernels per direction instead of three axis stages.
#
# At direct sizes (every axis <= mxu_fft.DIRECT_MAX) the per-axis path's cost
# is not the MXU work but the marshalling between stages: each axis transform
# materializes a moveaxis copy plus split real/imag planes in HBM, which XLA
# fuses away for the jnp-matmul backend but a per-axis pallas_call cannot.
# Round 1 measured the consequence: pallas 5.16 ms vs matmul@HIGH 1.51 ms at
# 256^3 (VERDICT "weak" #6). The fused path removes that traffic instead of
# racing it: one kernel computes z-R2C AND y-C2C per x-block entirely in
# VMEM, a second contracts x — two HBM passes per direction, intermediates
# never leave the core. Two structural tricks keep the kernels transpose-free:
#
# * the DFT matrix is symmetric (F[j,k] = w^(jk) = F[k,j]), so the y/x
#   transforms are LEFT-multiplies by the same constant the right-multiply
#   would use: out[k, z] = sum_y F[k, y] c[y, z] — output lands directly in
#   (k, z) order, no in-kernel transpose, and the operand never moves;
# * the C2R half-spectrum matrices (mxu_fft._c2r_np) fold conjugate symmetry
#   into the constants, so the inverse's z stage is two real matmuls fused
#   after the y-inverse in the same kernel pass.
#
# This is the TPU rendering of the reference's opt1 idea taken further: where
# opt1 bakes the transpose into the cuFFT plan's output striding
# (include/mpicufft_slab_opt1.hpp:46-54), here BOTH the layout change and the
# next transform happen inside the producer kernel.
# ---------------------------------------------------------------------------


def _zy_fwd_kernel(x_ref, fzr_ref, fzi_ref, fyr_ref, fyi_ref, yr_ref, yi_ref):
    """z-R2C over the whole block as one wide matmul pair, then per-row
    y-C2C left-multiplies; the (B, Y, Zo) intermediate lives only in VMEM."""
    B, Y, Z = x_ref.shape
    Zo = fzr_ref.shape[1]
    fzr, fzi = _planes(fzr_ref[:]), _planes(fzi_ref[:])
    fyr, fyi = _planes(fyr_ref[:]), _planes(fyi_ref[:])
    xz = _planes(x_ref[:].reshape(B * Y, Z))
    cr = _dot2(xz, fzr).reshape(B, Y, Zo)
    ci = _dot2(xz, fzi).reshape(B, Y, Zo)
    for b in range(B):
        crb, cib = _planes(cr[b]), _planes(ci[b])
        yr_ref[b] = _dot2(fyr, crb) - _dot2(fyi, cib)   # (Ky, Zo)
        yi_ref[b] = _dot2(fyr, cib) + _dot2(fyi, crb)


def _x_c2c_kernel(xr_ref, xi_ref, fr_ref, fi_ref, yr_ref, yi_ref):
    """C2C along axis 0 (x) as a left-multiply, per ky-column of the tile."""
    fr, fi = _planes(fr_ref[:]), _planes(fi_ref[:])
    for t in range(xr_ref.shape[1]):
        ar, ai = _planes(xr_ref[:, t]), _planes(xi_ref[:, t])   # (X, Zo)
        yr_ref[:, t] = _dot2(fr, ar) - _dot2(fi, ai)
        yi_ref[:, t] = _dot2(fr, ai) + _dot2(fi, ar)


def _yz_inv_kernel(xr_ref, xi_ref, fyr_ref, fyi_ref, czr_ref, czi_ref, y_ref,
                   er_s, ei_s):
    """Per x-row y-C2C inverse (left-multiply) into VMEM scratch, then the
    half-spectrum C2R over the whole block as one wide matmul pair."""
    B, Y, Zo = xr_ref.shape
    Z = czr_ref.shape[1]
    fyr, fyi = _planes(fyr_ref[:]), _planes(fyi_ref[:])
    czr, czi = _planes(czr_ref[:]), _planes(czi_ref[:])
    for b in range(B):
        ar, ai = _planes(xr_ref[b]), _planes(xi_ref[b])   # (Ky, Zo)
        er_s[b] = _dot2(fyr, ar) - _dot2(fyi, ai)         # (Y, Zo)
        ei_s[b] = _dot2(fyr, ai) + _dot2(fyi, ar)
    er = _planes(er_s[:].reshape(B * Y, Zo))
    ei = _planes(ei_s[:].reshape(B * Y, Zo))
    y_ref[:] = (_dot2(er, czr) - _dot2(ei, czi)).reshape(B, Y, Z)


# Per-grid-step VMEM budget for the sliced block operands. Mosaic
# double-buffers revolving blocks and keeps the constants resident on top,
# and the ~16 MB scoped-vmem limit is hard (measured: an 18.3 MB working
# set is a compile error, not a slowdown), so the budget is conservative.
_VMEM_BLOCK_BUDGET = 5 << 20

# Mosaic tile geometry: the last block dim pads to 128 lanes, the
# second-to-last to 8 sublanes — VMEM accounting must use PADDED extents
# (a 129-wide half-spectrum block occupies 256 lanes, 2x its logical size).
_SUBLANE = 8


def _lane_pad(n: int) -> int:
    return -(-n // 128) * 128


def _block_rows(per_row_bytes: int) -> int:
    """x-rows per grid step: a power of two <= 8 within the VMEM budget."""
    b = min(8, max(1, _VMEM_BLOCK_BUDGET // max(per_row_bytes, 1)))
    return 1 << (b.bit_length() - 1)


def _x_tile(X: int, Zo: int) -> int:
    """ky-tile for the x-contraction kernel (multiple of 8), or 0 when even
    the smallest legal tile blows the VMEM budget — the caller then
    contracts x with a plain dot_general instead (XLA contracts axis 0
    natively, no marshalling, and supports precision=HIGH outside Mosaic)."""
    per_t = 16 * X * _lane_pad(Zo)   # 4 f32 planes (in r/i + out r/i)
    tk = (_VMEM_BLOCK_BUDGET // max(per_t, 1)) // _SUBLANE * _SUBLANE
    return min(tk, 16) if tk >= _SUBLANE else 0


def _pad_axis_to(x, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def fused3d_applicable(shape3, dtype) -> bool:
    """The fused two-kernel path handles 3D arrays whose axes all take a
    single direct DFT matmul; larger axes go through the four-step
    per-axis path."""
    return (not mx._is_double(dtype)
            and len(shape3) == 3
            and all(2 <= n <= mx.DIRECT_MAX for n in shape3))


def _const_planes(*mats) -> list:
    out = []
    for m in mats:
        r, i = _f32_planes(m)
        out += [jnp.asarray(r), jnp.asarray(i)]
    return out


def _x_transform(yr, yi, inverse: bool, vma):
    """C2C along axis 0 of split-plane (X, Ky, Zo) data: the Pallas
    left-multiply kernel when a legal tile fits VMEM, else one dot_general
    (XLA contracts axis 0 in place; no moveaxis copies either way)."""
    X, Ky, Zo = yr.shape
    fx = mx._dft_np(X, inverse, False)
    tk = _x_tile(X, Zo)
    if tk == 0:
        z = jnp.einsum("xk,xyz->kyz", jnp.asarray(fx),
                       lax.complex(yr, yi), precision=_prec())
        return jnp.real(z), jnp.imag(z)
    yr, _ = _pad_axis_to(yr, 1, tk)
    yi, _ = _pad_axis_to(yi, 1, tk)
    Kp = yr.shape[1]
    args = _lift_vma([yr, yi] + _const_planes(fx), vma)
    zr, zi = pl.pallas_call(
        _x_c2c_kernel,
        grid=(Kp // tk,),
        in_specs=[_spec((X, tk, Zo), lambda i: (0, i, 0)),
                  _spec((X, tk, Zo), lambda i: (0, i, 0)),
                  _spec((X, X), lambda i: (0, 0)),
                  _spec((X, X), lambda i: (0, 0))],
        out_specs=[_spec((X, tk, Zo), lambda i: (0, i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((X, Kp, Zo), jnp.float32, vma=vma)] * 2,
        cost_estimate=pl.CostEstimate(
            flops=4 * X * X * Kp * Zo * 2, transcendentals=0,
            bytes_accessed=4 * X * Kp * Zo * 4),
        interpret=_interpret(),
    )(*args)
    return zr[:, :Ky], zi[:, :Ky]


def _rfftn3d_fused(x):
    """(X, Y, Z) f32 -> (X, Y, Z//2+1) c64, unnormalized forward."""
    X, Y, Z = x.shape
    Zo = Z // 2 + 1
    vma = _vma(x)

    # Pass 1: fused z-R2C + y-C2C, grid over x blocks. The per-row working
    # set is the input plane, the two output planes, AND the two in-kernel
    # cr/ci intermediate planes the z-stage materializes before the y-stage
    # consumes them.
    B = _block_rows(Y * _lane_pad(Z) * 4 + 4 * Y * _lane_pad(Zo) * 4)
    x, _ = _pad_axis_to(x.astype(jnp.float32), 0, B)
    Xp = x.shape[0]
    fz = mx._dft_np(Z, False, False)[:, :Zo]
    fy = mx._dft_np(Y, False, False)        # symmetric: left-multiply = DFT
    consts = _const_planes(fz, fy)
    args = _lift_vma([x] + consts, vma)
    yr, yi = pl.pallas_call(
        _zy_fwd_kernel,
        grid=(Xp // B,),
        in_specs=[_spec((B, Y, Z), lambda i: (i, 0, 0)),
                  _spec((Z, Zo), lambda i: (0, 0)),
                  _spec((Z, Zo), lambda i: (0, 0)),
                  _spec((Y, Y), lambda i: (0, 0)),
                  _spec((Y, Y), lambda i: (0, 0))],
        out_specs=[_spec((B, Y, Zo), lambda i: (i, 0, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((Xp, Y, Zo), jnp.float32, vma=vma)] * 2,
        cost_estimate=pl.CostEstimate(
            flops=2 * Xp * Y * Z * Zo * 2 + 4 * Xp * Y * Y * Zo * 2,
            transcendentals=0,
            bytes_accessed=4 * Xp * Y * (Z + 2 * Zo)),
        interpret=_interpret(),
    )(*args)
    # Pass 2: x-C2C contraction.
    zr, zi = _x_transform(yr[:X], yi[:X], False, vma)
    return lax.complex(zr, zi)


def _irfftn3d_fused(c, shape_3d):
    """(X, Y, Z//2+1)-croppable c64 -> (X, Y, Z) f32, unnormalized inverse."""
    X, Y, Z = shape_3d
    Zo = Z // 2 + 1
    c = c.astype(jnp.complex64)
    for ax, n in ((-3, X), (-2, Y), (-1, Zo)):
        c = mx._fit_axis(c, ax, n)
    vma = _vma(c)

    # Pass 1: x-C2C inverse contraction.
    er, ei = _x_transform(jnp.real(c), jnp.imag(c), True, vma)

    # Pass 2: fused y-C2C inverse + z-C2R, grid over x blocks (the scratch
    # planes for the y-stage intermediate count against the same budget).
    B = _block_rows(4 * Y * _lane_pad(Zo) * 4 + Y * _lane_pad(Z) * 4)
    er, _ = _pad_axis_to(er, 0, B)
    ei, _ = _pad_axis_to(ei, 0, B)
    Xp = er.shape[0]
    fy = mx._dft_np(Y, True, False)
    CR, CI = mx._c2r_np(Z, False)
    args = _lift_vma([er, ei] + _const_planes(fy) +
                     [jnp.asarray(CR), jnp.asarray(CI)], vma)
    y = pl.pallas_call(
        _yz_inv_kernel,
        grid=(Xp // B,),
        in_specs=[_spec((B, Y, Zo), lambda i: (i, 0, 0)),
                  _spec((B, Y, Zo), lambda i: (i, 0, 0)),
                  _spec((Y, Y), lambda i: (0, 0)),
                  _spec((Y, Y), lambda i: (0, 0)),
                  _spec((Zo, Z), lambda i: (0, 0)),
                  _spec((Zo, Z), lambda i: (0, 0))],
        out_specs=_spec((B, Y, Z), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Xp, Y, Z), jnp.float32, vma=vma),
        scratch_shapes=[pltpu.VMEM((B, Y, Zo), jnp.float32)] * 2,
        cost_estimate=pl.CostEstimate(
            flops=4 * Xp * Y * Y * Zo * 2 + 2 * Xp * Y * Zo * Z * 2,
            transcendentals=0,
            bytes_accessed=4 * Xp * Y * (2 * Zo + Z)),
        interpret=_interpret(),
    )(*args)
    return y[:X]


# ---------------------------------------------------------------------------
# Four-step recursion (structure shared with mxu_fft, stages fused here)
# ---------------------------------------------------------------------------


def _fft_last(x, inverse: bool):
    n = x.shape[-1]
    if _use_fallback(x):
        return mx._fft_last(x, inverse)
    if n <= mx.DIRECT_MAX:
        return _stage(x, mx._dft_np(n, inverse, False))
    n1, n2 = mx._split_for(n, mx.DIRECT_MAX)
    if n1 == 1:  # prime length
        if n <= _N_MAX:
            return _stage(x, mx._dft_np(n, inverse, False))
        return mx._fft_last(x, inverse)
    a = jnp.swapaxes(x.reshape(x.shape[:-1] + (n2, n1)), -1, -2)  # (.., n1, n2)
    if n2 <= mx.DIRECT_MAX:
        # Fused: DFT over s and the twiddle epilogue in one kernel pass.
        c = _stage(a, mx._dft_np(n2, inverse, False), twiddle=(n1, n2, inverse))
    else:
        c = _fft_last(a, inverse) * jnp.asarray(
            mx._twiddle_np(n1, n2, inverse, False))
    d = _fft_last(jnp.swapaxes(c, -1, -2), inverse)
    return jnp.swapaxes(d, -1, -2).reshape(x.shape[:-1] + (n,))


def _rfft_last(x):
    n = x.shape[-1]
    n_out = n // 2 + 1
    if _use_fallback(x):
        return mx._rfft_last(x)
    if n <= mx.DIRECT_MAX:
        return _stage(x, mx._dft_np(n, False, False)[:, :n_out])
    n1, n2 = mx._split_for(n, mx.DIRECT_MAX)
    if n1 == 1:
        if n <= _N_MAX:
            return _stage(x, mx._dft_np(n, False, False)[:, :n_out])
        return mx._rfft_last(x)
    a = jnp.swapaxes(x.reshape(x.shape[:-1] + (n2, n1)), -1, -2)
    if n2 <= mx.DIRECT_MAX:
        # Real-input fused stage: two MXU matmuls + twiddle epilogue.
        c = _stage(a, mx._dft_np(n2, False, False), twiddle=(n1, n2, False))
    else:
        c = _fft_last(a.astype(jnp.complex64), False) * jnp.asarray(
            mx._twiddle_np(n1, n2, False, False))
    d = _fft_last(jnp.swapaxes(c, -1, -2), False)
    full = jnp.swapaxes(d, -1, -2).reshape(x.shape[:-1] + (n,))
    return full[..., :n_out]


# ---------------------------------------------------------------------------
# Fused wire kernels (ISSUE 10, the overlap engine's HBM lever).
#
# The ring renderings encode each TRAVELLING block to the bf16 planar wire
# immediately before its ppermute and decode + FFT it on arrival
# (parallel/transpose.ring_transpose). Composed from jnp ops, that boundary
# costs extra HBM round-trips on TPU whenever a pallas_call sits nearby:
# the custom-call boundary stops XLA from fusing the split/cast/stack into
# the neighboring kernels (the exact structural limit the module verdict
# above documents), so the payload is materialized once in f32 planes and
# again in bf16. These kernels collapse the boundary:
#
# * ``wire_encode_fused``  — planar split + bf16 cast + pack in ONE kernel
#   pass (the send side; there is structurally no per-block FFT to fuse
#   with here — the last pre-exchange FFT always runs along the split
#   axis, so it cannot commute past the per-peer chunking);
# * ``decode_fft_fused``   — bf16 unpack + the first pipelined per-block
#   DFT matmul stage in ONE kernel (the receive side): the planes convert
#   to f32 inside VMEM and feed the MXU contraction directly, so the
#   decoded f32 image never lands in HBM;
# * ``wire_decode_fused``  — unpack-only variant for blocks with no
#   pipelined FFT (every pencil/batched2d ring block, slab ZY_Then_X).
#
# Numerics contract: the jnp fallbacks (off-TPU, f64, oversized axes,
# interpret-mode shard_map) are EXACTLY the unfused compositions, and the
# kernel paths agree with them to the wire's documented bf16 bound (the
# fused DFT runs at the backend's HIGH three-pass emulation; the bf16 wire
# quantization dominates — tests/test_overlap.py pins the bound). The
# encode/decode formulas mirror ``parallel/transpose.wire_encode``/
# ``wire_decode`` and must stay in sync with them.
# ---------------------------------------------------------------------------


def _enc_pack_kernel(xr_ref, xi_ref, yr_ref, yi_ref):
    """Planar split + bf16 cast ("encode + pack") in one VMEM pass."""
    yr_ref[:] = xr_ref[:].astype(jnp.bfloat16)
    yi_ref[:] = xi_ref[:].astype(jnp.bfloat16)


def _dec_unpack_kernel(pr_ref, pi_ref, yr_ref, yi_ref):
    """bf16 planes -> f32 planes (decode/unpack) in one VMEM pass."""
    yr_ref[:] = pr_ref[:].astype(jnp.float32)
    yi_ref[:] = pi_ref[:].astype(jnp.float32)


def _dec_cmatmul_kernel(pr_ref, pi_ref, fr_ref, fi_ref, yr_ref, yi_ref):
    """Fused decode + complex DFT matmul: the bf16 wire planes convert to
    f32 inside VMEM and feed the MXU contraction directly."""
    xr = _planes(pr_ref[:].astype(jnp.float32))
    xi = _planes(pi_ref[:].astype(jnp.float32))
    fr, fi = _planes(fr_ref[:]), _planes(fi_ref[:])
    yr_ref[:] = _dot2(xr, fr) - _dot2(xi, fi)
    yi_ref[:] = _dot2(xr, fi) + _dot2(xi, fr)


def _wire_planes_encode_jnp(x):
    """The unfused encode (== transpose.wire_encode's formula)."""
    return jnp.stack([jnp.real(x), jnp.imag(x)]).astype(jnp.bfloat16)


def _wire_planes_decode_jnp(y, dtype):
    """The unfused decode (== transpose.wire_decode's formula)."""
    f = (jnp.float64 if mx._is_double(dtype) else jnp.float32)
    z = y.astype(f)
    return lax.complex(z[0], z[1])


def _wire_kernel_usable(x) -> bool:
    """Whether the fused wire kernels can run on this value: f32-family
    data, and not the interpret-mode shard_map corner (same contract as
    ``_call_stage``'s fallback)."""
    return (not mx._is_double(x.dtype)
            and not (_interpret() and _vma(x)))


def _plane_pass(kern, planes, out_dtype):
    """Run an elementwise two-plane kernel over (M, n)-reshaped planes
    with the shared row-block grid."""
    shape = planes[0].shape
    p2 = [p.reshape((-1, shape[-1])) for p in planes]
    m, n = p2[0].shape
    tb = _row_block(1)
    m_pad = tb * ((m + tb - 1) // tb)
    if m_pad != m:
        p2 = [jnp.pad(p, [(0, m_pad - m), (0, 0)]) for p in p2]
    vma = _vma(planes[0])
    spec = _spec((tb, n), lambda i: (i, 0))
    args = _lift_vma(p2, vma)
    yr, yi = pl.pallas_call(
        kern,
        grid=(m_pad // tb,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((m_pad, n), out_dtype, vma=vma)] * 2,
        interpret=_interpret(),
    )(*args)
    if m_pad != m:
        yr, yi = yr[:m], yi[:m]
    return yr.reshape(shape), yi.reshape(shape)


def fused_ring_hooks(config, snd=None):
    """``(encode_fn, arrive_fn)`` for a ring exchange whose arriving
    blocks run NO pipelined per-block FFTs (every pencil and batched-2D
    ring block): the one-pass encode-pack and the unpack-only arrival.
    ``(None, None)`` — the plain wire layer — when the fused wire is
    inactive for this transpose (``Config.fused_wire_for``; ``snd``
    defaults to the config's first-transpose send method). Slab's
    pipelined arrivals build their decode+FFT hook via
    ``SlabFFTPlan._ring_hooks`` instead; both share this module's
    kernels and the Config predicate, so the activation condition lives
    in exactly one place."""
    active = (config.fused_wire_for(snd) if snd is not None
              else config.fused_wire_active())
    if not active:
        return None, None
    from ..parallel.transpose import wire_complex_dtype
    cdt = wire_complex_dtype(config.double_prec)
    return wire_encode_fused, (lambda b: wire_decode_fused(b, cdt))


def wire_encode_fused(x):
    """Complex array -> planar (real, imag) bf16 pair along a new leading
    axis, as ONE kernel pass (the ring's per-travelling-block encode +
    pack). Fallback (off-TPU / f64 / interpret shard_map): the exact
    unfused formula — bit-identical to ``transpose.wire_encode``."""
    if not (jnp.iscomplexobj(x) and _wire_kernel_usable(x)):
        return _wire_planes_encode_jnp(x)
    yr, yi = _plane_pass(_enc_pack_kernel,
                         [jnp.real(x.astype(jnp.complex64)),
                          jnp.imag(x.astype(jnp.complex64))],
                         jnp.bfloat16)
    return jnp.stack([yr, yi])


def wire_decode_fused(y, dtype):
    """Planar bf16 pair -> complex ``dtype`` as ONE kernel pass (the
    unpack-only arrival path of ring blocks with no pipelined FFT).
    Fallback: the exact unfused formula (== ``transpose.wire_decode``)."""
    if mx._is_double(dtype) or not _wire_kernel_usable(y):
        return _wire_planes_decode_jnp(y, dtype)
    zr, zi = _plane_pass(_dec_unpack_kernel, [y[0], y[1]], jnp.float32)
    return lax.complex(zr, zi)


def decode_fft_fused(y, dtype, axis: int, *, inverse: bool = False,
                     norm: FFTNorm = FFTNorm.NONE, settings=None):
    """Fused wire decode + per-block DFT along ``axis`` of the decoded
    block: the bf16 planes feed the MXU contraction inside VMEM, so the
    decoded f32 image never round-trips HBM. The DFT is the direct
    matmul (the fusion IS the matmul — regardless of the plan's
    ``fft_backend``); axes past ``_N_MAX`` and every fallback condition
    run the exact unfused composition ``mxu_fft.(i)fft(decode(y))``
    under the same settings."""
    with mx.use_settings(settings):
        n = y.shape[1:][axis]
        # The f64 guard keys on the TARGET dtype, not the payload (the
        # bf16 planes are never 'double'): a double_prec plan's arrived
        # blocks must restore complex128 via the unfused composition,
        # not silently drop to the f32 kernel.
        if (mx._is_double(dtype) or not _wire_kernel_usable(y)
                or n > _N_MAX):
            c = _wire_planes_decode_jnp(y, dtype)
            return (mx.ifft if inverse else mx.fft)(c, axis=axis, norm=norm)
        # Planes to (M, n) rows with the DFT axis last (the same relayout
        # the unfused lf.fft pays), then one fused kernel.
        pr = jnp.moveaxis(y[0], axis, -1)
        pi = jnp.moveaxis(y[1], axis, -1)
        shape = pr.shape
        pr2, pi2 = pr.reshape((-1, n)), pi.reshape((-1, n))
        m = pr2.shape[0]
        tb = _row_block(1)
        m_pad = tb * ((m + tb - 1) // tb)
        if m_pad != m:
            pr2 = jnp.pad(pr2, [(0, m_pad - m), (0, 0)])
            pi2 = jnp.pad(pi2, [(0, m_pad - m), (0, 0)])
        fr, fi = _f32_planes(mx._dft_np(n, inverse, False))
        vma = _vma(y)
        row_spec = _spec((tb, n), lambda i: (i, 0))
        const_spec = _spec((n, n), lambda i: (0, 0))
        args = _lift_vma([pr2, pi2, jnp.asarray(fr), jnp.asarray(fi)], vma)
        yr, yi = pl.pallas_call(
            _dec_cmatmul_kernel,
            grid=(m_pad // tb,),
            in_specs=[row_spec, row_spec, const_spec, const_spec],
            out_specs=[row_spec, row_spec],
            out_shape=[jax.ShapeDtypeStruct((m_pad, n), jnp.float32, vma=vma)] * 2,
            cost_estimate=pl.CostEstimate(
                flops=4 * 2 * m_pad * n * n, transcendentals=0,
                bytes_accessed=2 * m_pad * n * 2 + 4 * (m_pad + n) * n * 2),
            interpret=_interpret(),
        )(*args)
        if m_pad != m:
            yr, yi = yr[:m], yi[:m]
        out = lax.complex(yr, yi).reshape(shape)
        scale = (mx._inv_scale(n, norm) if inverse
                 else mx._fwd_scale(n, norm))
        return jnp.moveaxis(mx._scaled(out, scale), -1, axis)


# ---------------------------------------------------------------------------
# Public API (mirrors ops/mxu_fft.py; same FFTNorm semantics)
# ---------------------------------------------------------------------------


def fft(x, axis: int, norm: FFTNorm = FFTNorm.NONE):
    x = jnp.moveaxis(x, axis, -1)
    if not mx._is_double(x.dtype):
        x = x.astype(jnp.complex64)
    y = mx._scaled(_fft_last(x, False), mx._fwd_scale(x.shape[-1], norm))
    return jnp.moveaxis(y, -1, axis)


def ifft(x, axis: int, norm: FFTNorm = FFTNorm.NONE):
    x = jnp.moveaxis(x, axis, -1)
    if not mx._is_double(x.dtype):
        x = x.astype(jnp.complex64)
    y = mx._scaled(_fft_last(x, True), mx._inv_scale(x.shape[-1], norm))
    return jnp.moveaxis(y, -1, axis)


def rfft(x, axis: int, norm: FFTNorm = FFTNorm.NONE):
    x = jnp.moveaxis(x, axis, -1)
    y = mx._scaled(_rfft_last(x), mx._fwd_scale(x.shape[-1], norm))
    return jnp.moveaxis(y, -1, axis)


def irfft(x, n: int, axis: int, norm: FFTNorm = FFTNorm.NONE):
    c = jnp.moveaxis(x, axis, -1)
    if not mx._is_double(c.dtype):
        c = c.astype(jnp.complex64)
    c = mx._fit_axis(c, -1, n // 2 + 1)
    if _use_fallback(c) or n > mx.DIRECT_MAX:
        full = mx._hermitian_extend(c, n)
        y = jnp.real(_fft_last(full, True))
    else:
        y = _c2r_stage(c, n)
    return jnp.moveaxis(mx._scaled(y, mx._inv_scale(n, norm)), -1, axis)


def fftn(x, axes: Sequence[int], norm: FFTNorm = FFTNorm.NONE):
    for a in axes:
        x = fft(x, axis=a, norm=norm)
    return x


def ifftn(x, axes: Sequence[int], norm: FFTNorm = FFTNorm.NONE):
    for a in axes:
        x = ifft(x, axis=a, norm=norm)
    return x


def _fused3d_usable(x, shape3) -> bool:
    # Under shard_map in interpret mode (the CPU test mesh) the per-axis
    # path's jnp fallback applies; everywhere else the fused path rules at
    # direct sizes.
    return (fused3d_applicable(shape3, x.dtype)
            and not (_interpret() and _vma(x)))


def rfftn_3d(x, norm: FFTNorm = FFTNorm.NONE):
    if x.ndim == 3 and _fused3d_usable(x, x.shape):
        s = 1.0
        for n in x.shape:
            s *= mx._fwd_scale(n, norm)
        return mx._scaled(_rfftn3d_fused(x), s)
    c = rfft(x, axis=-1, norm=norm)
    c = fft(c, axis=-2, norm=norm)
    return fft(c, axis=-3, norm=norm)


def irfftn_3d(x, shape_3d: Tuple[int, int, int], norm: FFTNorm = FFTNorm.NONE):
    if x.ndim == 3 and _fused3d_usable(x, shape_3d):
        s = 1.0
        for n in shape_3d:
            s *= mx._inv_scale(n, norm)
        return mx._scaled(_irfftn3d_fused(x, tuple(shape_3d)), s)
    c = ifft(mx._fit_axis(x, -3, shape_3d[-3]), axis=-3, norm=norm)
    c = ifft(mx._fit_axis(c, -2, shape_3d[-2]), axis=-2, norm=norm)
    return irfft(c, n=shape_3d[-1], axis=-1, norm=norm)
