"""``--selftest``: one guarded forward+inverse roundtrip before the run.

The reference validates offline (testcases 1/3/4) and benchmarks blind; a
misconfigured production run — wrong wisdom cell, broken backend on a new
jax, a lossy wire on data it cannot represent — burns its whole timed loop
before anyone notices. ``--selftest`` (on each of the four
CLIs) runs ONE roundtrip of the plan's actual
shape/rendering first and prints a PASS/FAIL line:

* **Parseval** — the forward output's energy against the guard invariant
  (``guards.GuardSpec`` of the plan family, checked host-side here so the
  selftest works at any ``Config.guards`` mode, "off" included);
* **roundtrip** — max rel error of forward∘inverse against the scaled
  input (cuFFT-unnormalized scale, exactly testcase 3's identity),
  computed on device with one scalar readback so it runs at north-star
  sizes;
* **reference** — max rel error of the forward output against the
  UNSHARDED host ``np.fft`` path (testcase 1's coordinator-rank analog);
  skipped above ``--selftest-ref-max`` total elements (default 2^21) or
  in multi-controller runs, where no host holds the global array.

FAIL aborts the CLI with exit code 1 — a run whose selftest failed would
time (or worse, publish) wrong answers. Tolerances follow the guard
derivation: dtype eps scaled by log2(N), widened under a compressed wire
to the documented per-crossing bound times the pipeline's crossings.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .. import obs
from . import guards

# Elements above which the host np.fft reference sub-check is skipped
# (dense host transform; the device-side checks carry the load at scale).
DEFAULT_REF_MAX = 1 << 21


def _roundtrip_tol(config, crossings: int) -> float:
    """Max rel error a healthy roundtrip may show: backend rounding
    (1e-4 matches the autotune accuracy budget the backends are gated
    on; 1e-12 f64) plus the compressed wire's documented per-crossing
    bound over every crossing of the forward+inverse pipeline."""
    tol = 1e-12 if config.double_prec else 1e-4
    if config.wire_dtype != "native":
        tol += 2e-2 * max(2, crossings)
    return tol


def _crossings(plan, dims: int) -> int:
    """Wire crossings of one roundtrip (forward + inverse exchanges)."""
    from ..models.pencil import PencilFFTPlan
    if getattr(plan, "fft3d", False):
        return 0
    if isinstance(plan, PencilFFTPlan):
        return 2 * max(0, dims - 1)
    return 2


def run_selftest(plan, dims: Optional[int] = None, seed: int = 0,
                 ref_max: int = DEFAULT_REF_MAX) -> dict:
    """Run the guarded roundtrip; prints the PASS/FAIL line and returns
    ``{"ok", "parseval", "parseval_tol", "roundtrip", "roundtrip_tol",
    "reference" (optional), "checks"}``."""
    import jax
    import jax.numpy as jnp

    from ..models.batched2d import Batched2DFFTPlan
    from ..models.pencil import PencilFFTPlan
    from ..testing import testcases as tc
    from ..testing.microbench import max_rel_err

    obs.metrics.inc("selftest.runs")
    cfg = plan.config
    if dims is None:
        dims = 2 if isinstance(plan, Batched2DFFTPlan) else 3
    with obs.span("selftest", plan=type(plan).__name__,
                  shape=list(plan.global_size.shape), dims=dims):
        rdt = np.float64 if cfg.double_prec else np.float32
        cdt = np.complex128 if cfg.double_prec else np.complex64
        complex_in = getattr(plan, "transform", "r2c") == "c2c"
        rng = np.random.default_rng(seed)
        xh = rng.random(plan.input_shape).astype(rdt)
        if complex_in:
            xh = (xh + 1j * rng.random(plan.input_shape)).astype(cdt)
        x = plan.pad_input(jnp.asarray(xh))
        fwd, inv = tc._fused_fns(plan, dims)
        spec = fwd(x)
        y = inv(spec)

        checks = {}
        # Parseval: the guard invariant, computed host-side (eager jnp on
        # the global arrays) so it applies at every Config.guards mode.
        gspec = plan._guard_spec("forward", dims)
        in_e = float(guards._energy(
            guards._slice_logical(x, gspec.in_logical), None, 0))
        out_e = float(guards._energy(
            guards._slice_logical(spec, gspec.out_logical),
            gspec.halved_axis, gspec.halved_n))
        expected = gspec.scale * in_e
        parseval = abs(out_e - expected) / max(abs(expected), guards._TINY)
        ptol = guards.parseval_tolerance(
            cfg.double_prec, cfg.wire_dtype,
            int(np.prod(gspec.in_logical)))
        checks["parseval"] = (parseval, ptol)

        # Roundtrip vs the scaled input (testcase 3's identity), on the
        # logical region only.
        scale = tc._roundtrip_scale(plan, dims)
        yl = guards._slice_logical(y, plan.input_shape)
        xl = guards._slice_logical(x, plan.input_shape)
        roundtrip = max_rel_err(yl, xl * scale)
        rtol = _roundtrip_tol(cfg, _crossings(plan, dims))
        checks["roundtrip"] = (roundtrip, rtol)

        # Unsharded host reference (skipped at scale / multi-controller;
        # the non-batched C2C reference is the plain full fftn, so partial
        # pencil C2C depths skip this sub-check too).
        ref = None
        if plan.global_size.n_total <= ref_max and jax.process_count() == 1:
            if complex_in and not isinstance(plan, Batched2DFFTPlan):
                if dims == 3:
                    ref = np.fft.fftn(np.asarray(xh, np.complex128))
            else:
                ref = tc.reference_spectrum(plan, xh.astype(np.float64),
                                            dims)
        reference = None
        if ref is not None:
            got = (plan.crop_spectral(spec, dims)
                   if isinstance(plan, PencilFFTPlan)
                   else plan.crop_spectral(spec))
            denom = float(np.abs(ref).max()) or 1.0
            reference = float(np.abs(got - ref.astype(got.dtype)).max()
                              / denom)
            checks["reference"] = (reference, rtol)

        ok = all(v <= tol for v, tol in checks.values())
        detail = "  ".join(f"{k} {v:.3e} (tol {tol:.0e})"
                           for k, (v, tol) in checks.items())
        fp = guards.fingerprint(plan, "roundtrip")
        line = (f"selftest: {'PASS' if ok else 'FAIL'}  {detail}  "
                f"[{fp['plan']} {fp['shape']} {fp['comm']}/{fp['send']}"
                f"/opt{fp['opt']}/{fp['wire']} backend={fp['backend']}]")
        print(line, flush=True)
        if not ok:
            obs.metrics.inc("selftest.failures")
            obs.notice(line, name="selftest.failure", **{
                k: float(v) for k, (v, _) in checks.items()})
        return {"ok": ok, "parseval": parseval, "parseval_tol": ptol,
                "roundtrip": roundtrip, "roundtrip_tol": rtol,
                "reference": reference, "checks": {
                    k: {"value": float(v), "tol": float(t)}
                    for k, (v, t) in checks.items()}}
