"""MXU-utilization roofline for the matmul FFT backend (VERDICT r3 item 5).

The artifact CSVs quote FFT-NOMINAL GFLOPS (2.5·N·log2 N — the rate a
textbook FFT would need, BASELINE.md §Derived), which is the right number
for cross-framework comparison but the wrong denominator for "is the chip
busy": the matmul backend executes O(n) MXU MACs per element per axis, not
O(log n). This module counts the MACs the backend ACTUALLY issues — by
mirroring the dispatch logic of ``ops/mxu_fft.py`` (direct vs four-step vs
radix-2, R2C/C2R real-matmul fast paths, XLA's 4-real-matmul complex dot
decomposition) — and converts each measured row into achieved MXU TFLOPS
and fraction of the chip's effective peak.

Peak model: ``PEAKS`` holds each chip's published peaks, keyed by the
``device_kind`` JAX reports; a kind not in the table has no ideal. The
backend's default precision is ``HIGH`` = 3-pass bf16 emulation of f32
(``MXUSettings.precision`` docstring), so its effective peak is a third
of the bf16 peak; ``HIGHEST`` is 6-pass (a sixth).

Reference anchor: the reference derives GPU efficiency from cuFFT's nominal
flops only (``/root/reference/eval/complete/scalability.py``); a
hardware-true denominator is an extension.

DEFAULT-SETTINGS ASSUMPTION: the MAC model mirrors ``ops/mxu_fft.py`` at
its default ``MXUSettings`` only. Two non-default toggles change the MACs
actually issued — ``karatsuba=True`` lowers each complex dot to 3 real
matmuls plus extra adds, and ``fourstep_einsum=True`` makes ``_rfft_last``
skip the real-matmul fast path — and neither is recorded in the measured
CSV, so ``_BACKENDS`` maps only default-settings backend labels and any
row measured under those toggles must not be fed to ``roofline_rows``
(it would be silently miscounted, not skipped).
"""

from __future__ import annotations

import math
import os
import re
from typing import Dict, Optional, Tuple

from ..ops.bluestein import chirp_length, is_smooth
from ..ops.mxu_fft import DIRECT_MAX, _R2_BASE, _split_for

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 16 GB of HBM at 819 GB/s.
V5E_KIND = "TPU v5 lite"
PEAKS: Dict[str, Dict[str, float]] = {
    V5E_KIND: {"bf16_tflops": 197.0, "hbm_gbps": 819.0, "hbm_gb": 16.0},
}

# MXU passes per f32-emulating matmul at each lax.Precision.
_PREC_PASSES = {"default": 1, "high": 3, "highest": 6}


def effective_peak_tflops(kind: str, precision: str = "high"
                          ) -> Optional[float]:
    """Effective matmul peak of a ``kind`` chip for f32 data at the given
    precision; None for a kind with no published peak in ``PEAKS``."""
    peak = PEAKS.get(kind)
    if peak is None:
        return None
    return peak["bf16_tflops"] / _PREC_PASSES[precision]


# ---------------------------------------------------------------------------
# Per-element MAC counts, mirroring ops/mxu_fft.py dispatch
# ---------------------------------------------------------------------------


def macs_c2c_axis(n: int, direct_max: int = DIRECT_MAX, *,
                  radix2: bool = False, complex_mults: int = 4) -> float:
    """MXU MACs per element for one C2C pass along an axis of length ``n``
    (``_fft_last``): direct = one complex matmul lowered to
    ``complex_mults`` real depth-n matmuls; four-step recurses on both
    factors; radix-2 DIF halves the depth per level down to ``_R2_BASE``
    = 128 (butterflies/twiddles are VPU work, not MXU).

    ``complex_mults``: the textbook complex-dot lowering is 4 real
    matmuls (ArFr - AiFi, ArFi + AiFr); a 3-multiplication Karatsuba-form
    lowering also exists. Measured rows that exceed 100% of peak under
    the 4-matmul model (128^3) prove the compiler's actual lowering is
    cheaper than 4 — so the two models BRACKET the hardware count, and
    the roofline reports both."""
    if radix2 and n > _R2_BASE and n % 2 == 0:
        return macs_c2c_axis(n // 2, direct_max, radix2=radix2,
                             complex_mults=complex_mults)
    if n <= direct_max:
        return float(complex_mults) * n
    # The four-step factor choice mirrors _fft_last's _split_for
    # dispatch: the MXU-deep split (dominant factor = largest divisor
    # <= direct_max) when both factors stay direct, balanced otherwise.
    n1, n2 = _split_for(n, direct_max)
    if n1 == 1:
        return float(complex_mults) * n
    return (macs_c2c_axis(n2, direct_max, radix2=radix2,
                          complex_mults=complex_mults)
            + macs_c2c_axis(n1, direct_max, radix2=radix2,
                            complex_mults=complex_mults))


def macs_r2c_axis(n: int, direct_max: int = DIRECT_MAX, *,
                  complex_mults: int = 4) -> float:
    """MACs per INPUT element for the R2C first pass (``_rfft_last``):
    direct = 2 real n->n_out matmuls (2·n_out MACs/element); four-step =
    real depth-n2 pair + complex depth-n1 on the FULL volume (the crop to
    n_out happens after the transform)."""
    n_out = n // 2 + 1
    if n <= direct_max:
        return 2.0 * n_out
    n1, n2 = _split_for(n, direct_max)
    if n1 == 1:
        return 2.0 * n_out
    return 2.0 * n2 + macs_c2c_axis(n1, direct_max,
                                    complex_mults=complex_mults)


def macs_c2r_axis(n: int, direct_max: int = DIRECT_MAX, *,
                  radix2: bool = False, complex_mults: int = 4) -> float:
    """MACs per OUTPUT element for the C2R last pass (``irfft``): direct =
    2 real depth-n_out matmuls with conjugate symmetry folded in
    (``_c2r_np``); beyond direct_max the code Hermitian-extends and runs a
    full complex inverse (``_fft_last`` cost on the full length — which
    honors the radix-2 setting, so the model must too)."""
    n_out = n // 2 + 1
    if n <= direct_max:
        return 2.0 * n_out
    return macs_c2c_axis(n, direct_max, radix2=radix2,
                         complex_mults=complex_mults)


# ---------------------------------------------------------------------------
# Bluestein (chirp-z) honesty: non-smooth axes
# ---------------------------------------------------------------------------
#
# The nominal 2.5·N·log2 N FLOP model (BASELINE.md §Derived, quoted by the
# CSVs and `flops_roundtrip_3d`) silently assumes every axis is 5-smooth.
# A Bluestein-padded axis actually executes TWO smooth transforms at the
# padded chirp length m = chirp_length(n) (>= 2n-1, next power of two)
# plus O(m) chirp multiplies per pass, and the matmul backend off the
# chirp path executes a dense O(n^2) contraction — so for non-smooth axes
# the honest model must say so instead of quoting the smooth-size number.


def nominal_flops_axis(n: int) -> float:
    """Textbook per-element flops of ONE smooth-length-n axis pass
    (2.5·log2 n per element, the CSVs' nominal convention)."""
    return 2.5 * math.log2(float(n))


def bluestein_flops_axis(n: int) -> float:
    """Per-element flops one chirp-z pass of a non-smooth length-n axis
    actually needs: two length-m smooth FFTs amortized over n elements
    (the kernel spectrum is precomputed) plus the three O(1)-per-element
    chirp/pointwise multiplies (6 real flops each as complex mults)."""
    m = chirp_length(n)
    return 2.0 * 2.5 * m * math.log2(float(m)) / float(n) + 3.0 * 6.0


def bluestein_axis_report(n: int) -> Tuple[int, float]:
    """(padded chirp length m, flop overhead factor vs a natively smooth
    axis of the same length) — the pair dfft-explain quotes so a
    prime-size plan's roofline is honest rather than silently wrong.
    Smooth lengths report (n, 1.0): the backend delegates them."""
    if is_smooth(n):
        return n, 1.0
    return chirp_length(n), bluestein_flops_axis(n) / nominal_flops_axis(n)


def nonsmooth_axes(shape) -> list:
    """The distinct non-5-smooth axis lengths of a shape (sorted)."""
    return sorted({int(n) for n in shape if not is_smooth(int(n))})


# ---------------------------------------------------------------------------
# Whole-workload MXU flops (2 flops per MAC)
# ---------------------------------------------------------------------------


def mxu_flops_roundtrip_3d(n: int, direct_max: int = DIRECT_MAX,
                           radix2: bool = False,
                           complex_mults: int = 4) -> float:
    """MXU flops the matmul backend executes for one R2C+C2R roundtrip of
    an ``n^3`` f32 cube (``rfftn_3d`` then ``irfftn_3d``): z R2C pass on
    the full cube, two C2C passes each way on the halved volume, z C2R
    pass back to the full cube. Radix-2 applies to the C2C stages only
    (``_rfft_last`` never takes the radix-2 branch)."""
    n_out = n // 2 + 1
    v_half = n * n * n_out
    macs = (n ** 3 * macs_r2c_axis(n, direct_max,
                                   complex_mults=complex_mults)
            + 4 * v_half * macs_c2c_axis(n, direct_max, radix2=radix2,
                                         complex_mults=complex_mults)
            + n ** 3 * macs_c2r_axis(n, direct_max, radix2=radix2,
                                     complex_mults=complex_mults))
    return 2.0 * macs


def mxu_flops_batched2d(batch: int, m: int, direct_max: int = DIRECT_MAX,
                        complex_mults: int = 4,
                        radix2: bool = False) -> float:
    """MXU flops for one batched-2D R2C+C2R roundtrip of ``batch`` m x m
    planes (``Batched2DFFTPlan``): per plane, an R2C pass over m rows, one
    C2C pass each way on the halved volume, and a C2R pass back."""
    m_out = m // 2 + 1
    v_half = m * m_out
    macs_plane = (m * m * macs_r2c_axis(m, direct_max,
                                        complex_mults=complex_mults)
                  + 2 * v_half * macs_c2c_axis(m, direct_max, radix2=radix2,
                                               complex_mults=complex_mults)
                  + m * m * macs_c2r_axis(m, direct_max, radix2=radix2,
                                          complex_mults=complex_mults))
    return 2.0 * batch * macs_plane


# ---------------------------------------------------------------------------
# roofline_fraction: the tracked per-row gate (ISSUE 10 / ROADMAP item 3)
# ---------------------------------------------------------------------------
#
# ``roofline_fraction = ideal_ms / measured_ms``: the fraction of the
# model's 100%-of-effective-peak time a measured row achieved. The model
# is the SAME per-plan expectation dfft-explain prints — the exact MXU MAC
# count for the matmul-family backends, the nominal 2.5·N·log2 N flops for
# everything else — against the effective peak of the ``kind`` the caller
# names, divided by the mesh size for distributed rows (per-chip share of
# the transform work; the exchange is deliberately NOT in the
# denominator, so communication time shows up as lost fraction). A CPU
# row scored against ``V5E_KIND`` is a TRACKING number the caller chose
# explicitly, not a utilization claim.


def _parse_size(shape):
    """Normalize a workload size to ``("cube", n)`` / ``("b2d", (b, m))``
    or None: accepts an int (cube edge), a ``"256^3"`` / ``"4096^2x64"``
    string (the bench row-key forms; a trailing ``:inverse``-style mode
    tag is ignored), or a shape tuple — (n, n, n) cubes and (b, m, m)
    batched planes."""
    if isinstance(shape, str):
        s = shape.split(":")[0]
        m = re.fullmatch(r"(\d+)(\^3)?", s)
        if m:
            return "cube", int(m.group(1))
        m = re.fullmatch(r"(\d+)\^2x(\d+)", s)
        if m:
            return "b2d", (int(m.group(2)), int(m.group(1)))
        return None
    if isinstance(shape, int):
        return "cube", int(shape)
    t = tuple(int(v) for v in shape)
    if len(t) == 3 and t[0] == t[1] == t[2]:
        return "cube", t[0]
    if len(t) == 3 and t[1] == t[2]:
        return "b2d", (t[0], t[1])
    return None


def _backend_model(backend: str):
    """(counts_on_mxu, precision, radix2) for a bench/Config backend
    label — bare names ("matmul") and CSV forms ("matmul@high") both
    resolve; non-matmul backends fall to the nominal model."""
    base = str(backend).split()[0]
    name, _, prec = base.partition("@")
    if name in ("matmul", "matmul-planes"):
        return True, (prec or "high"), False
    if name == "matmul-r2":
        return True, (prec or "high"), True
    return False, "high", False


def ideal_time_ms(shape, backend: str, *, kind: str, devices: int = 1,
                  mode: str = "roundtrip",
                  direct_max: "Optional[int]" = None) -> Optional[float]:
    """The per-plan expectation: the time ``mode`` of this workload would
    take at 100% of a ``kind`` chip's effective MXU peak — exact MACs
    (4mm bound) for the matmul family, nominal FFT flops for other
    backends. None when the shape is outside the model (non-cube/
    non-square-batched) or the kind has no published peak. ``devices``
    divides the work (per-chip share); ``direct_max`` overrides the plan
    threshold (the ``direct(N)`` bench plan note)."""
    parsed = _parse_size(shape)
    mxu, precision, r2 = _backend_model(backend)
    peak = effective_peak_tflops(kind, precision)
    if parsed is None or devices < 1 or peak is None:
        return None
    form, dims = parsed
    dmax = DIRECT_MAX if direct_max is None else int(direct_max)
    if form == "cube":
        n = dims
        if mxu:
            flops = mxu_flops_roundtrip_3d(n, dmax, radix2=r2)
        else:
            from ..testing.workloads import flops_roundtrip_3d
            flops = flops_roundtrip_3d(n)
    else:
        b, m = dims
        if mxu:
            flops = mxu_flops_batched2d(b, m, dmax, radix2=r2)
        else:
            from ..testing.workloads import flops_batched2d
            flops = flops_batched2d(b, m, m)
    if mode != "roundtrip":  # forward / inverse / forward-chunked
        flops /= 2.0
    return flops / (peak * 1e12) / float(devices) * 1e3


def _mesh_devices(mesh) -> int:
    """Device count of a mesh-ish argument: None (single chip), an int,
    or a ``jax.sharding.Mesh``."""
    if mesh is None:
        return 1
    if isinstance(mesh, int):
        return max(1, mesh)
    devs = getattr(mesh, "devices", None)
    return int(devs.size) if devs is not None else 1


def roofline_row(measured_ms: float, shape, backend: str, mesh=None, *,
                 kind: str, mode: str = "roundtrip",
                 direct_max: "Optional[int]" = None) -> Optional[dict]:
    """The tracked roofline record for one measured row (what bench.py
    writes under BENCH_DETAILS.json's ``"roofline"`` block): the model's
    ideal time, the achieved ``roofline_fraction``, and which model
    produced it. None when unmodelable (bad shape / degenerate time)."""
    if not measured_ms or measured_ms <= 0:
        return None
    devices = _mesh_devices(mesh)
    ideal = ideal_time_ms(shape, backend, kind=kind, devices=devices,
                          mode=mode, direct_max=direct_max)
    if ideal is None:
        return None
    mxu, precision, _ = _backend_model(backend)
    # Significant digits, not fixed decimals: CPU tracking rows sit many
    # orders below the v5e model and must never round to a 0.0 the gate
    # would reject.
    return {
        "ideal_ms": float(f"{ideal:.4g}"),
        "roofline_fraction": float(f"{ideal / measured_ms:.4g}"),
        "model": (f"mxu-4mm@{precision}" if mxu else "nominal@high"),
        "kind": kind,
        "mode": mode,
        "devices": devices,
    }


def roofline_fraction(measured_ms: float, shape, backend: str,
                      mesh=None, *, kind: str, mode: str = "roundtrip",
                      direct_max: "Optional[int]" = None
                      ) -> Optional[float]:
    """``ideal_time_ms / measured_ms`` — the honest, tracked fraction of
    the per-plan roofline a measurement achieved (ROADMAP item 3's gate:
    every perf PR must move this number, and the CI roofline job fails a
    >10% regression against the committed BENCH_DETAILS.json)."""
    row = roofline_row(measured_ms, shape, backend, mesh, kind=kind,
                       mode=mode, direct_max=direct_max)
    return None if row is None else row["roofline_fraction"]


_BENCH_DETAILS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "..", "..", "BENCH_DETAILS.json")


def tracked_fractions(path: Optional[str] = None) -> dict:
    """The committed ``"roofline"`` rows of BENCH_DETAILS.json (row key ->
    record), or {} when the artifact/block is absent — what dfft-explain
    quotes as the tracked fraction and the CI job regresses against."""
    import json
    try:
        with open(path or _BENCH_DETAILS, encoding="utf-8") as f:
            data = json.load(f)
        rows = data.get("roofline", {}).get("rows", {})
        return rows if isinstance(rows, dict) else {}
    except (OSError, ValueError):
        return {}


# ---------------------------------------------------------------------------
# Roofline table from a measurement CSV
# ---------------------------------------------------------------------------

# backend label -> (counts_on_mxu, precision, radix2). The all-real-planes
# formulation issues the identical matmuls on split (re, im) planes, so it
# shares the matmul count; XLA's native FFT is not a matmul pipeline and
# pallas kernels schedule their own MXU passes — no honest count for
# either, so they are skipped rather than guessed.
_BACKENDS = {
    "matmul@high": ("high", False),
    "matmul@highest": ("highest", False),
    "matmul-r2@high": ("high", True),
    "matmul-planes": ("high", False),
}

# Plan suffix on the backend column (e.g. "matmul@high direct(1024)",
# "matmul@high four-step(16x32)", "matmul@high ck=1"): the execution-plan
# variant the row was measured under. The MAC model takes the plan as a
# ``direct_max`` threshold, so every suffix maps to one:
#   direct(N)        -> direct_max=N (the whole axis is one contraction);
#   four-step(AxB)   -> direct_max=max(A,B) (forces the four-step branch;
#                       the factors themselves are <= max(A,B) so they run
#                       direct, exactly as measured);
#   ck=N / chunked   -> batch/stage chunking re-orders work without
#                       changing the MACs issued -> no override.
_SUFFIX_DIRECT = re.compile(r"direct\((\d+)\)")
_SUFFIX_FOURSTEP = re.compile(r"four-step\((\d+)x(\d+)\)")


def _parse_backend(label: str):
    """Split a CSV backend label into (base, direct_max override or None).
    Returns ``None`` for labels whose MACs the model cannot count."""
    parts = label.split()
    if not parts or parts[0] not in _BACKENDS:
        return None
    base = parts[0]
    dmax = None
    for tok in parts[1:]:
        m = _SUFFIX_DIRECT.fullmatch(tok)
        if m:
            dmax = int(m.group(1))
            continue
        m = _SUFFIX_FOURSTEP.fullmatch(tok)
        if m:
            dmax = max(int(m.group(1)), int(m.group(2)))
            continue
        if tok.startswith("ck=") or tok == "chunked":
            continue
        return None  # unknown suffix: skip the row rather than miscount
    return base, dmax


def roofline_rows(csv_path: str, kind: str) -> list:
    """Parse a measurement CSV (columns ``size``, ``transform``,
    ``backend``, ``per_iter_ms``, ``gflops``) taken on ``kind`` chips and
    return roofline dicts for every row whose backend has an exact MXU
    MAC count. A kind with no published peak raises: a table without a
    denominator says nothing."""
    if kind not in PEAKS:
        raise ValueError(f"no peak for {kind!r}; known: {sorted(PEAKS)}")
    out = []
    with open(csv_path) as f:
        header = f.readline().strip().split(",")
        idx = {k: i for i, k in enumerate(header)}
        for line in f:
            parts = line.rstrip("\n").split(",")
            if len(parts) < 5:
                continue
            size, transform = parts[idx["size"]], parts[idx["transform"]]
            backend = parts[idx["backend"]]
            per_ms = float(parts[idx["per_iter_ms"]])
            nominal = float(parts[idx["gflops"]])
            parsed = _parse_backend(backend)
            if parsed is None or "roundtrip" not in transform:
                continue
            base, dmax_override = parsed
            precision, r2 = _BACKENDS[base]
            dmax = DIRECT_MAX if dmax_override is None else dmax_override
            m_cube = re.fullmatch(r"(\d+)\^3", size)
            m_b2d = re.fullmatch(r"(\d+)\^2x(\d+)", size)
            if m_cube:
                n = int(m_cube.group(1))
                f4 = mxu_flops_roundtrip_3d(n, dmax, radix2=r2)
                f3 = mxu_flops_roundtrip_3d(n, dmax, radix2=r2,
                                            complex_mults=3)
            elif m_b2d:
                m, b = int(m_b2d.group(1)), int(m_b2d.group(2))
                f4 = mxu_flops_batched2d(b, m, dmax, radix2=r2)
                f3 = mxu_flops_batched2d(b, m, dmax, complex_mults=3,
                                         radix2=r2)
            else:
                continue
            peak = effective_peak_tflops(kind, precision)
            t4 = f4 / (per_ms * 1e-3) / 1e12
            t3 = f3 / (per_ms * 1e-3) / 1e12
            out.append({
                "size": size, "backend": backend,
                "per_iter_ms": per_ms, "nominal_gflops": nominal,
                "mxu_tflops_4mm": round(t4, 1),
                "mxu_tflops_3mm": round(t3, 1),
                "peak_tflops": round(peak, 1),
                "util_4mm": round(t4 / peak, 3),
                "util_3mm": round(t3 / peak, 3),
            })
    return out


def render_markdown(rows, kind: str, path: Optional[str] = None) -> str:
    eff = {p: effective_peak_tflops(kind, p) for p in ("high", "highest")}
    lines = [
        f"# MXU-utilization roofline ({kind}, one chip)",
        "",
        "Measured roundtrip rows, with the MXU flops the matmul backend",
        "ACTUALLY executes (counted by `evalkit/roofline.py`, mirroring",
        "`ops/mxu_fft.py` dispatch) against the chip's effective peak",
        f"({PEAKS[kind]['bf16_tflops']:g} bf16 TFLOPS; `HIGH` = 3-pass bf16",
        f"f32 emulation -> {eff['high']:.1f} TFLOPS effective, `HIGHEST` =",
        f"6-pass -> {eff['highest']:.1f}).",
        "",
        "| size | backend | ms/iter | nominal GFLOPS | MXU TFLOPS "
        "(3mm-4mm) | eff. peak | utilization (3mm-4mm) |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r['size']} | {r['backend']} | {r['per_iter_ms']:.4f} | "
            f"{r['nominal_gflops']:.1f} | "
            f"{r['mxu_tflops_3mm']:.1f}-{r['mxu_tflops_4mm']:.1f} | "
            f"{r['peak_tflops']:.1f} | "
            f"{100 * r['util_3mm']:.1f}-{100 * r['util_4mm']:.1f}% |")
    lines += [
        "",
        "The two bounds bracket XLA's complex-dot lowering: `4mm` = the",
        "textbook 4-real-matmul decomposition, `3mm` = the 3-multiplication",
        "Karatsuba form; the hardware truth lies between the columns (R2C/",
        "C2R passes are exact in both — they are explicit real-matmul pairs",
        "in `ops/mxu_fft.py`).",
    ]
    text = "\n".join(lines) + "\n"
    if path:
        with open(path, "w") as f:
            f.write(text)
    return text


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        "dfft-roofline", description="Render the MXU roofline table from "
        "a measurement CSV taken on one kind of chip.")
    ap.add_argument("--csv", required=True)
    ap.add_argument("--device-kind", required=True,
                    help=f"the chips' jax device_kind, one of "
                         f"{sorted(PEAKS)}")
    ap.add_argument("--out", default=None,
                    help="write markdown here (default: print)")
    a = ap.parse_args(argv)
    rows = roofline_rows(a.csv, a.device_kind)
    text = render_markdown(rows, a.device_kind, a.out)
    if not a.out:
        print(text, end="")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
