"""The reference's five testcases, re-implemented as library functions.

The reference builds testcases 0-4 into each executable and runs them under
``mpirun`` (SURVEY §4); they are the judge-visible behavior of the test
harness. Semantics preserved (slab: ``tests/src/slab/random_dist_default.cu``;
pencil analogs under ``tests/src/pencil/``):

* 0 — perf: random input, loop ``exec_r2c``. No check.
* 1 — distributed vs reference: a single-host full 3D transform is the
  ground truth (the reference uses an extra coordinator rank with a
  ``cufftMakePlan3d`` plan, ``random_dist_default.cu:227-459``; in the
  single-controller JAX model the host plays the coordinator); prints
  ``Result <sum|diff|>`` like the reference's cublas-asum residual.
* 2 — perf of the inverse on random spectral input.
* 3 — round-trip: forward then inverse vs input * Nx*Ny*Nz (cuFFT
  unnormalized semantics); prints ``Result (avg)`` / ``Result (max)``.
* 4 — analytic Laplacian: u = sin(2πx/Nx)sin(2πy/Ny)sin(2πz/Nz); forward,
  multiply by -(k1²+k2²+k3²)/sqrt(N), inverse; compare to the closed form
  -3·sqrt(N)·u (``random_dist_default.cu:626-758``, the testcase every
  ``jobs/**/validation.json`` runs). This is the FFT-diagonalized Poisson
  operator of BASELINE config #5.

Per-iteration phase timings go through the reference-schema ``Timer``
(phases fenced with ``block_until_ready``); warmup iterations are not
gathered, matching the reference's warmup counter.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

from .. import params as pm
from ..models.batched2d import Batched2DFFTPlan
from ..models.pencil import PencilFFTPlan
from ..models.slab import SlabFFTPlan
from ..utils.timer import Timer, benchmark_filename
from . import sharded


def make_plan(kind: str, global_size: pm.GlobalSize, partition, config,
              sequence=None, mesh=None, transform: str = "r2c",
              dims: int = 3):
    """``transform`` must match the program the caller will actually run
    (the comm autotuner races THIS plan — a c2c run tuned on an r2c plan
    would time transposes moving roughly half the bytes). ``dims`` is the
    pencil partial-transform depth hint for wisdom resolution (exec-time
    choice; ignored by the other kinds)."""
    if kind == "slab":
        return SlabFFTPlan(global_size, partition, config, mesh=mesh,
                           sequence=sequence or pm.SlabSequence.ZY_THEN_X,
                           transform=transform)
    if kind == "batched2d":
        # Size-slot convention of the batched plan's global_size property:
        # (batch, nx, ny). Comm only matters for the x decomposition.
        g = global_size
        return Batched2DFFTPlan(g.nx, g.ny, g.nz, partition, config,
                                mesh=mesh, shard="x", transform=transform)
    if kind == "pencil":
        return PencilFFTPlan(global_size, partition, config, mesh=mesh,
                             transform=transform, dims=dims)
    raise ValueError(f"unknown plan kind {kind!r}")


def make_timer(plan, write_csv: bool = True) -> Timer:
    cfg = plan.config
    filename = None
    if write_csv:
        grid = ((plan.p1, plan.p2) if isinstance(plan, PencilFFTPlan)
                and not plan.fft3d else None)
        filename = benchmark_filename(cfg.benchmark_dir, plan.variant_name,
                                      cfg, plan.global_size,
                                      plan.partition.num_ranks,
                                      pencil_grid=grid)
    import jax
    return Timer(plan.section_descriptions, plan.partition.num_ranks, filename,
                 process_index=jax.process_index(),
                 num_processes=jax.process_count())


def reference_spectrum(plan, x: np.ndarray, dims: int = 3) -> np.ndarray:
    """Single-host ground truth in the plan's own spectral layout."""
    if isinstance(plan, Batched2DFFTPlan):
        # Batched 2D: transform over (x, y) = axes (1, 2), batch untouched.
        if plan.transform == "c2c":
            return np.fft.fft(np.fft.fft(x, axis=2), axis=1)
        return np.fft.fft(np.fft.rfft(x, axis=2), axis=1)
    if isinstance(plan, SlabFFTPlan) and plan.sequence is pm.SlabSequence.Y_THEN_ZX:
        r = np.fft.rfft(x, axis=1)
        r = np.fft.fft(r, axis=2)
        return np.fft.fft(r, axis=0)
    r = np.fft.rfft(x, axis=2)
    if dims >= 2:
        r = np.fft.fft(r, axis=1)
    if dims >= 3:
        r = np.fft.fft(r, axis=0)
    return r


def _stages(plan, direction: str, dims: int = 3):
    """Stage list for any plan kind; pencil takes the partial-dim depth
    (reference --fft-dim), slab and batched2d ignore it (slab is always
    full 3D, batched2d always a 2D transform — its callers pass dims=2
    so the roundtrip scale comes out nx*ny)."""
    if isinstance(plan, PencilFFTPlan):
        return (plan.forward_stages(dims) if direction == "fwd"
                else plan.inverse_stages(dims))
    return plan.forward_stages() if direction == "fwd" else plan.inverse_stages()


def random_real_input(plan, seed: int = 0) -> np.ndarray:
    """Random uniform input like the reference's cuRAND generation
    (``tests/include/tests_base.hpp:30-43``), in the plan's precision."""
    rdt, _ = _dtypes(plan)
    rng = np.random.default_rng(seed)
    return rng.random(plan.input_shape, dtype=np.float64).astype(rdt)


def _dtypes(plan):
    from ..ops.fft import dtypes_for
    return dtypes_for(plan.config.double_prec)


FUSED_DESC = "Run complete (fused)"


def _fused_fns(plan, dims: int = 3):
    """(forward, inverse) closures over the plan's FUSED production programs
    (one jitted call each) — the path ``exec_r2c``/``exec_c2r`` users run.
    The staged path that feeds the per-phase timers is separately jitted
    stages with fences between them (extra dispatch, no cross-stage
    overlap), so its "Run complete" overstates the production runtime; the
    reference times its actual hot path (mpicufft_slab.cpp:772-821)."""
    if isinstance(plan, Batched2DFFTPlan):
        # exec_forward/exec_inverse carry both r2c and c2c modes.
        return plan.exec_forward, plan.exec_inverse
    if getattr(plan, "transform", "r2c") == "c2c":
        if isinstance(plan, PencilFFTPlan):
            return (lambda v: plan.exec_c2c(v, dims),
                    lambda c: plan.exec_c2c_inv(c, dims))
        return plan.exec_c2c, plan.exec_c2c_inv
    if isinstance(plan, PencilFFTPlan):
        return (lambda v: plan.exec_r2c(v, dims),
                lambda c: plan.exec_c2r(c, dims))
    return plan.exec_r2c, plan.exec_c2r


def _run_staged(plan, stages, timer: Timer, x, warmup: int, iterations: int,
                run_desc: str = "Run complete", fused_fn=None):
    """Timed loop over staged execution; gathers CSV rows after warmup
    (reference warmup-counter behavior). Returns (last output, list of
    per-iteration 'Run complete' ms, list of fused ms).

    When ``fused_fn`` is given, each iteration additionally runs the fused
    production program once and stores its cumulative mark under
    ``FUSED_DESC`` — so phase CSVs carry the staged attribution AND the
    real (fused) runtime, recoverable as FUSED_DESC − "Run complete"."""
    out = None
    times, fused_times = [], []
    for it in range(warmup + iterations):
        timer.start()
        y = x
        for desc, fn in stages:
            y = fn(y)
            if desc is not None:
                jax.block_until_ready(y)
                timer.stop_store(desc)
        jax.block_until_ready(y)
        ms = timer.stop_store(run_desc)
        fused_ms = None
        if fused_fn is not None:
            jax.block_until_ready(fused_fn(x))
            fused_ms = timer.stop_store(FUSED_DESC) - ms
        if it >= warmup:
            times.append(ms)
            if fused_ms is not None:
                fused_times.append(fused_ms)
            timer.gather()
        out = y
    return out, times, fused_times


def testcase0(plan, iterations: int = 1, warmup: int = 0, seed: int = 0,
              write_csv: bool = True, dims: int = 3) -> Dict:
    """Forward perf (reference testcase 0)."""
    if jax.process_count() > 1:
        # Multi-controller run: each process fills only its own block, like
        # each reference rank's local cuRAND generate
        # (tests/src/slab/random_dist_default.cu:174-190).
        from ..parallel.multihost import plan_local_input
        x = plan_local_input(plan, seed)
    else:
        x = plan.pad_input(jnp.asarray(random_real_input(plan, seed)))
    timer = make_timer(plan, write_csv)
    stages = _stages(plan, "fwd", dims)
    fwd, _ = _fused_fns(plan, dims)
    _, times, fused = _run_staged(plan, stages, timer, x, warmup, iterations,
                                  fused_fn=fwd)
    return {"times_ms": times, "mean_ms": float(np.mean(times)),
            "fused_times_ms": fused, "fused_mean_ms": float(np.mean(fused))}


def testcase1(plan, seed: int = 0, write_csv: bool = True,
              dims: int = 3, truth: str = "host") -> Dict:
    """Distributed vs reference spectrum (testcase 1); prints the asum
    residual as ``Result <sum>``.

    ``truth="host"`` (default, reference parity): dense random input, the
    ground truth is a full ``np.fft`` on the host (the coordinator-rank
    analog, ``random_dist_default.cu:227-459``) — which bounds the
    checkable size by host memory/time. ``truth="analytic"`` removes that
    bound: the input is the separable sine field and the truth is its
    closed-form spectrum, BOTH generated on device
    (``sharded.sine_spectrum_ref``), so the distributed-vs-truth check
    runs at north-star sizes (sparser spectrum, but any transpose/
    wavenumber-mapping error still lands on the residual). Either way the
    residual reduction runs ON DEVICE with a scalar readback — the
    reference's GPU ``difference`` kernel + cublas asum
    (``random_dist_default.cu:365-371``). ``residual_max`` over
    ``ref_max`` is the relative error."""
    if truth not in ("host", "analytic"):
        raise ValueError(f"truth must be 'host' or 'analytic', got {truth!r}")
    timer = make_timer(plan, write_csv)
    if truth == "analytic":
        x = sharded.sine_input(plan)
        refdev = sharded.sine_spectrum_ref(plan, dims)
    else:
        _, cdt = _dtypes(plan)
        xh = random_real_input(plan, seed)
        x = plan.pad_input(jnp.asarray(xh))
        ref = reference_spectrum(plan, xh.astype(np.float64), dims).astype(cdt)
        refdev = (plan.pad_spectral(jnp.asarray(ref), dims)
                  if isinstance(plan, PencilFFTPlan)
                  else plan.pad_spectral(jnp.asarray(ref)))
    out, _, _ = _run_staged(plan, _stages(plan, "fwd", dims), timer, x, 0, 1)
    resid, rmax = sharded.residuals(plan, out, refdev, "spectral", dims)
    print(f"Result {resid}")
    return {"residual_sum": resid, "residual_max": rmax,
            "ref_max": float(jnp.max(jnp.abs(refdev)))}


def testcase2(plan, iterations: int = 1, warmup: int = 0, seed: int = 0,
              write_csv: bool = True, dims: int = 3) -> Dict:
    """Inverse perf on random spectral input (testcase 2)."""
    if jax.process_count() > 1:
        from ..parallel.multihost import plan_local_spectral
        c = plan_local_spectral(plan, seed, dims=dims)
    else:
        _, cdt = _dtypes(plan)
        rng = np.random.default_rng(seed)
        c = (rng.random(plan.output_shape)
             + 1j * rng.random(plan.output_shape))
        c = jnp.asarray(c.astype(cdt))
        c = (plan.pad_spectral(c, dims) if isinstance(plan, PencilFFTPlan)
             else plan.pad_spectral(c))
    timer = make_timer(plan, write_csv)
    stages = _stages(plan, "inv", dims)
    _, inv = _fused_fns(plan, dims)
    _, times, fused = _run_staged(plan, stages, timer, c, warmup, iterations,
                                  fused_fn=inv)
    return {"times_ms": times, "mean_ms": float(np.mean(times)),
            "fused_times_ms": fused, "fused_mean_ms": float(np.mean(fused))}


def testcase3(plan, iterations: int = 1, warmup: int = 0, seed: int = 0,
              write_csv: bool = True, dims: int = 3) -> Dict:
    """Round-trip forward+inverse vs scaled input (testcase 3). With
    cuFFT-style unnormalized transforms the comparison scale is Nx*Ny*Nz
    (``random_dist_default.cu:529-623``)."""
    g = plan.global_size
    xh = random_real_input(plan, seed)
    x = plan.pad_input(jnp.asarray(xh))
    timer = make_timer(plan, write_csv)
    fwd, inv = _stages(plan, "fwd", dims), _stages(plan, "inv", dims)
    # On-device masked residual vs the (zero-padded) device input — two
    # scalar readbacks per iteration, like the reference's differenceInv +
    # MPI_Allreduce of avg & max (random_dist_default.cu:529-623).
    rfn = sharded.residual_fn(plan, "real", dims,
                              ref_scale=_roundtrip_scale(plan, dims))
    ffwd, finv = _fused_fns(plan, dims)
    avg = mx = 0.0
    fused_times = []
    for it in range(warmup + iterations):
        timer.start()
        y = x
        for desc, fn in fwd:
            y = fn(y)
        for desc, fn in inv:
            y = fn(y)
        jax.block_until_ready(y)
        ms = timer.stop_store("Run complete")
        jax.block_until_ready(finv(ffwd(x)))
        fused_ms = timer.stop_store(FUSED_DESC) - ms
        if it >= warmup:
            fused_times.append(fused_ms)
            timer.gather()
        s, m = rfn(y, x)
        avg = float(s) / g.n_total
        mx = float(m)
    print(f"Result (avg): {avg}")
    print(f"Result (max): {mx}")
    return {"avg_error": avg, "max_error": mx,
            "ref_max": _roundtrip_scale(plan, dims) * float(np.abs(xh).max()),
            "fused_mean_ms": float(np.mean(fused_times))}


def _roundtrip_scale(plan, dims: int = 3) -> float:
    if plan.config.norm is not pm.FFTNorm.NONE:
        return 1.0
    g = plan.global_size
    return float({1: g.nz, 2: g.nz * g.ny, 3: g.n_total}[dims])


def testcase4(plan, iterations: int = 1, warmup: int = 0,
              write_csv: bool = True) -> Dict:
    """Analytic Laplacian / spectral Poisson validation (testcase 4).

    Wavenumber convention matches the reference's ``derivativeCoefficients``
    kernel (``random_dist_default.cu:71-119``): integer frequencies folded to
    [-N/2, N/2), Nyquist zeroed, scale -(k1²+k2²+k3²)/sqrt(N)."""
    g = plan.global_size
    # Everything on device, built from O(N) 1D vectors (testing/sharded.py):
    # input field, Laplacian symbol, and masked residual vs -3·sqrt(N)·u.
    # No dense host cube and no array readback, so this testcase runs at
    # north-star sizes on the CPU mesh and unmodified on the real TPU.
    x = sharded.sine_input(plan)
    apply_scale = sharded.laplacian_scale_fn(plan)
    rfn = sharded.residual_fn(plan, "real",
                              ref_scale=-3.0 * float(np.sqrt(g.n_total)))

    timer = make_timer(plan, write_csv)
    fwd, inv = plan.forward_stages(), plan.inverse_stages()
    ffwd, finv = _fused_fns(plan)
    avg = mx = 0.0
    fused_times = []
    for it in range(warmup + iterations):
        timer.start()
        y = x
        for desc, fn in fwd:
            y = fn(y)
        y = apply_scale(y)
        for desc, fn in inv:
            y = fn(y)
        jax.block_until_ready(y)
        ms = timer.stop_store("Run complete")
        jax.block_until_ready(finv(apply_scale(ffwd(x))))
        fused_ms = timer.stop_store(FUSED_DESC) - ms
        if it >= warmup:
            fused_times.append(fused_ms)
            timer.gather()
        s, m = rfn(y, x)
        avg = float(s) / g.n_total
        mx = float(m)
    print(f"Result (avg): {avg}")
    print(f"Result (max): {mx}")
    return {"avg_error": avg, "max_error": mx,
            "fused_mean_ms": float(np.mean(fused_times))}
