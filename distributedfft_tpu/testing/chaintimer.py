"""Shared chained-roundtrip timing harness (testing/autotune.py's races).

Methodology: K roundtrips chained through ``lax.fori_loop`` inside ONE
jitted program reduced to a scalar (a scalar readback is the completion
fence); per-iteration time is the median over ``repeats`` pairs of
(t_K - t_1) so the constant dispatch/readback overhead cancels. K must be
big enough that (K-1) iterations of work dominate the run-to-run noise of
that constant.
A nonpositive median means the work was swamped anyway; callers must treat
that as a degenerate measurement, not a timing.
"""

from __future__ import annotations

import time
from typing import Tuple

import numpy as np

from ..params import FFTNorm


def roundtrip_chain(k: int, shape, backend: str, settings=None):
    """Jitted scalar-fenced chain of ``k`` R2C+C2R roundtrips of ``shape``
    (dtype follows the input array: f32 or f64).

    ``settings`` is an optional ``mxu_fft.MXUSettings`` threaded into every
    local transform — how autotune races precision variants without
    touching the process defaults.

    ``backend="matmul-planes"`` uses the all-real-planes formulation
    (``mxu_fft.rfftn_3d_planes``): the identical DFT matmuls with no
    complex dtype anywhere in the program (see mxu_fft)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..ops import fft as lf
    from ..ops import mxu_fft as mx

    scale = 1.0 / float(np.prod(shape))

    if backend == "matmul-planes":
        def body(i, v):
            with mx.use_settings(settings):
                cr, ci = mx.rfftn_3d_planes(v)
                return mx.irfftn_3d_planes(cr, ci, tuple(shape)) * scale
    else:
        def body(i, v):
            c = lf.rfftn_3d(v, norm=FFTNorm.NONE, backend=backend,
                            settings=settings)
            r = lf.irfftn_3d(c, tuple(shape), norm=FFTNorm.NONE,
                             backend=backend, settings=settings)
            # FFTNorm.NONE leaves both directions unnormalized (cuFFT
            # convention); rescaling keeps the chained value bounded.
            return r * scale

    return jax.jit(lambda x: jnp.sum(jnp.abs(lax.fori_loop(0, k, body, x))))


def _accum_forward_chain(k: int, shape, fwd, rdt):
    """Shared forward-direction chaining body: on-device input, scalar
    accumulator folded into the next iteration's input as ``+ acc*1e-30``
    (numerically negligible, but a real data dependency so XLA cannot
    hoist or parallelize iterations). Single source of truth for the
    chaining contract — ``directional_chain`` and
    ``chunked_forward_chain`` must stay timing-comparable."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    scale = 1.0 / float(np.prod(shape))
    tiny = 1e-30

    def run(seed):
        u = jax.random.uniform(jax.random.key(seed), tuple(shape), rdt)

        def body(i, acc):
            c = fwd(u + acc * tiny)
            return acc + jnp.real(c)[0, 0, 0] * scale
        return lax.fori_loop(0, k, body, jnp.zeros((), rdt))

    return jax.jit(run)


def directional_chain(k: int, shape, backend: str, direction: str,
                      settings=None, dtype=None):
    """Jitted scalar-fenced chain of ``k`` SINGLE-DIRECTION transforms
    (``direction`` in {"forward", "inverse", "roundtrip"}) with the input
    generated ON DEVICE — no host transfer, so north-star sizes (1024^3 is
    a 4 GiB cube) are timed without staging it.

    Chaining trick for the one-way directions: the loop carry is a scalar
    accumulator folded into the next iteration's input as ``+ acc*1e-30``
    — numerically negligible (operands are O(1)..O(N^3), the perturbation
    stays ~1e-20) but a real data dependency, so XLA cannot hoist or
    parallelize the iterations. For "inverse" the spectral input is built
    by ONE forward transform outside the loop; like input generation, it
    runs once per call and cancels in the (t_K - t_1) pair difference.

    Returns a jitted ``fn(seed) -> scalar``; call with an int (the rng
    seed). Callers time it exactly like ``roundtrip_chain``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..ops import fft as lf

    if direction not in ("forward", "inverse", "roundtrip"):
        raise ValueError(f"direction must be forward/inverse/roundtrip, "
                         f"got {direction!r}")
    rdt = jnp.float32 if dtype is None else jnp.dtype(dtype)
    scale = 1.0 / float(np.prod(shape))
    tiny = 1e-30

    if direction == "forward":
        return _accum_forward_chain(
            k, shape,
            lambda v: lf.rfftn_3d(v, norm=FFTNorm.NONE, backend=backend,
                                  settings=settings), rdt)

    def run(seed):
        u = jax.random.uniform(jax.random.key(seed), tuple(shape), rdt)
        if direction == "inverse":
            c0 = lf.rfftn_3d(u, norm=FFTNorm.NONE, backend=backend,
                             settings=settings)
            def body(i, acc):
                y = lf.irfftn_3d(c0 + acc * tiny, tuple(shape),
                                 norm=FFTNorm.NONE, backend=backend,
                                 settings=settings)
                return acc + y[0, 0, 0] * scale
            return lax.fori_loop(0, k, body, jnp.zeros((), rdt))

        def body(i, v):
            c = lf.rfftn_3d(v, norm=FFTNorm.NONE, backend=backend,
                            settings=settings)
            return lf.irfftn_3d(c, tuple(shape), norm=FFTNorm.NONE,
                                backend=backend, settings=settings) * scale
        return jnp.sum(jnp.abs(lax.fori_loop(0, k, body, u)))

    return jax.jit(run)


def chunked_forward_chain(k: int, n: int, chunk: int = 8,
                          backend: str = "matmul"):
    """Forward chain over a CHUNKED single-device plan pipeline
    (``Config.fft3d_chunk``): the z and y stages run in ``chunk``
    sequential slices so the program's live intermediates fit a 16 GB
    chip at 1024^3 (``eval/benchmarks/tpu_v5e/MEMORY_1024.md`` — the
    all-at-once forward's intermediates do not). Same on-device-input +
    scalar-accumulator chaining contract as ``directional_chain``."""
    import jax.numpy as jnp

    from ..models.slab import SlabFFTPlan
    from ..params import Config, GlobalSize, SlabPartition

    plan = SlabFFTPlan(GlobalSize(n, n, n), SlabPartition(1),
                       Config(fft_backend=backend, fft3d_chunk=chunk))
    return _accum_forward_chain(k, (n, n, n), plan.forward_fn(),
                                jnp.float32)


STAGES = ("rfft_z", "fft_y", "fft_x", "ifft_x", "ifft_y", "irfft_z")


def stage_chain(k: int, shape, backend: str, stage: str, settings=None):
    """Jitted scalar-fenced chain of ``k`` SINGLE-AXIS transforms — one
    stage of the 3D R2C/C2R pipeline in isolation, on exactly the shapes
    the full pipeline feeds it. The per-stage attribution tool behind the
    512^3 efficiency breakdown (VERDICT r2 weak#2): chain each of the six
    stages, compare their sum against the fused roundtrip.

    ``stage``: ``rfft_z`` times the real->halved-complex first stage on a
    real cube; the complex stages (``fft_y``/``fft_x``/inverses) operate
    on the halved cube built by ONE on-device forward outside the loop
    (cancels in the pair difference); ``irfft_z`` times the final
    halved-complex->real stage. Same accumulator-perturbation chaining as
    ``directional_chain``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..ops import fft as lf

    if stage not in STAGES:
        raise ValueError(f"stage must be one of {STAGES}, got {stage!r}")
    nz = shape[-1]
    scale = 1.0 / float(np.prod(shape))
    tiny = 1e-30

    def run(seed):
        u = jax.random.uniform(jax.random.key(seed), tuple(shape),
                               jnp.float32)
        if stage == "rfft_z":
            def body(i, acc):
                c = lf.rfft(u + acc * tiny, axis=-1, backend=backend,
                            settings=settings)
                return acc + jnp.real(c)[0, 0, 0] * scale
            return lax.fori_loop(0, k, body, jnp.zeros((), jnp.float32))
        c0 = lf.rfft(u, axis=-1, backend=backend, settings=settings)
        if stage == "irfft_z":
            def body(i, acc):
                y = lf.irfft(c0 + acc * tiny, n=nz, axis=-1,
                             backend=backend, settings=settings)
                return acc + y[0, 0, 0] * scale
            return lax.fori_loop(0, k, body, jnp.zeros((), jnp.float32))
        axis = -2 if stage in ("fft_y", "ifft_y") else -3
        fwd = stage.startswith("fft")

        def body(i, acc):
            op = lf.fft if fwd else lf.ifft
            y = op(c0 + acc * tiny, axis=axis, backend=backend,
                   settings=settings)
            return acc + jnp.real(y)[0, 0, 0] * scale
        return lax.fori_loop(0, k, body, jnp.zeros((), jnp.float32))

    return jax.jit(run)


def timed_best(fn, x, inner: int) -> float:
    """Best-of-``inner`` wall-clock of one scalar-fenced call."""
    best = float("inf")
    for _ in range(inner):
        t0 = time.perf_counter()
        float(fn(x))
        best = min(best, time.perf_counter() - t0)
    return best


def median_pair_diff_ms(fn1, fnK, x, k: int, repeats: int,
                        inner: int) -> Tuple[float, float]:
    """(per-iteration ms from the median (t_K - t_1) pair, last t_1 seconds).

    Callers compile+warm both fns first. The returned t_1 lets a caller
    build a degenerate fallback (such as subtracting a null readback)."""
    if k < 2:
        raise ValueError(f"k must be >= 2 (got {k})")
    pairs = [(timed_best(fnK, x, inner), timed_best(fn1, x, inner))
             for _ in range(repeats)]
    diffs = sorted(tk - t1 for tk, t1 in pairs)
    per_ms = diffs[len(diffs) // 2] / (k - 1) * 1e3
    return per_ms, pairs[-1][1]
