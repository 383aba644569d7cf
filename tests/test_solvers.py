"""The spectral application suite (solvers/, ISSUE 9): Navier-Stokes vs a
numpy reference and its invariants, jit(grad) through multi-step solves on
the 8-device mesh, DCT/DST vs scipy goldens, spectral convolution vs
direct references (non-periodic padding included), Bluestein prime-size
transforms vs np.fft on all three plan families, and the
guards x compressed-wire composition of a solver path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import distributedfft_tpu as dfft
from distributedfft_tpu.models.batched2d import Batched2DFFTPlan
from distributedfft_tpu.solvers import (
    NavierStokes2D,
    NavierStokes3D,
    PoissonSolver,
    make_convolver,
    make_solver,
    r2r,
    taylor_green_2d,
    taylor_green_3d,
)
from distributedfft_tpu.solvers.convolve import conv_shape

scipy_fft = pytest.importorskip("scipy.fft")
scipy_signal = pytest.importorskip("scipy.signal")


def _cfg(**kw):
    return dfft.Config(double_prec=True, use_wisdom=False, **kw)


# ---------------------------------------------------------------------------
# Navier-Stokes
# ---------------------------------------------------------------------------


def _np_ns2d_steps(w0, steps, dt, nu):
    """Tiny numpy mirror of the NavierStokes2D discretization (rfft2,
    2/3-rule mask, RK4) on an n x n periodic box of side 2π."""
    n = w0.shape[-1]
    kx = (np.fft.fftfreq(n) * n)[:, None]
    ky = np.arange(n // 2 + 1)[None, :]
    k2 = kx ** 2 + ky ** 2
    inv_k2 = np.where(k2 > 0, 1.0 / np.where(k2 > 0, k2, 1.0), 0.0)
    cut = n // 3
    mask = ((np.abs(kx) <= cut) * (ky <= cut)).astype(float)

    def rhs(wh):
        psi = wh * inv_k2
        u = np.fft.irfft2(1j * ky * psi, s=(n, n))
        v = np.fft.irfft2(-1j * kx * psi, s=(n, n))
        wx = np.fft.irfft2(1j * kx * wh, s=(n, n))
        wy = np.fft.irfft2(1j * ky * wh, s=(n, n))
        return -mask * np.fft.rfft2(u * wx + v * wy) - nu * k2 * wh

    wh = mask * np.fft.rfft2(w0)
    for _ in range(steps):
        k1 = rhs(wh)
        k2_ = rhs(wh + 0.5 * dt * k1)
        k3 = rhs(wh + 0.5 * dt * k2_)
        k4 = rhs(wh + dt * k3)
        wh = wh + (dt / 6.0) * (k1 + 2 * k2_ + 2 * k3 + k4)
    return np.fft.irfft2(wh, s=(n, n))


def test_ns2d_matches_numpy_reference(devices, rng):
    """3 RK4 steps of a random vorticity field through the distributed
    batched-2D pipeline == the numpy pseudo-spectral mirror, to f64
    roundoff (both batch planes)."""
    n, nu, dt = 24, 0.02, 1e-2
    plan = Batched2DFFTPlan(2, n, n, dfft.SlabPartition(8), _cfg(),
                            shard="x")
    ns = NavierStokes2D(plan, nu)
    w0 = rng.random((2, n, n))
    got = np.asarray(ns.run(w0, 3, dt))[:, :n, :n]
    for b in range(2):
        ref = _np_ns2d_steps(w0[b], 3, dt, nu)
        np.testing.assert_allclose(got[b], ref, atol=1e-13)


def test_ns2d_taylor_green_exact_decay(devices):
    """Taylor-Green vorticity kills the advection term identically, so
    ω(t) = ω(0)·e^{-2νt} exactly — a closed-form gate on the viscous
    half of the stepper."""
    n, nu, dt, steps = 32, 0.05, 1e-2, 5
    plan = Batched2DFFTPlan(1, n, n, dfft.SlabPartition(8), _cfg(),
                            shard="x")
    ns = NavierStokes2D(plan, nu)
    w0 = taylor_green_2d(n, batch=1)
    wT = np.asarray(ns.run(w0, steps, dt))[:, :n, :n]
    np.testing.assert_allclose(wT, w0 * np.exp(-2 * nu * dt * steps),
                               atol=1e-12)


def test_ns_energy_enstrophy_sanity_under_dealiasing(devices, rng):
    """Inviscid (ν=0) runs under the 2/3 truncation conserve energy and
    enstrophy up to RK4 time error: relative drift over 5 small steps
    stays tiny, and viscosity strictly dissipates both."""
    n = 24
    plan = Batched2DFFTPlan(1, n, n, dfft.SlabPartition(8), _cfg(),
                            shard="x")
    ns = NavierStokes2D(plan, 0.0)
    wh0 = ns.to_spectral(jnp.asarray(rng.random((1, n, n)) - 0.5))
    d0 = {k: float(v[0]) for k, v in ns.diagnostics(wh0).items()}
    step = jax.jit(ns.step_fn(2e-3))
    wh = wh0
    for _ in range(5):
        wh = step(wh)
    dT = {k: float(v[0]) for k, v in ns.diagnostics(wh).items()}
    assert abs(dT["energy"] - d0["energy"]) <= 1e-9 * max(d0["energy"], 1)
    assert abs(dT["enstrophy"] - d0["enstrophy"]) \
        <= 1e-7 * max(d0["enstrophy"], 1)
    # Viscous run: both strictly decay.
    nsv = NavierStokes2D(plan, 0.1)
    whv = wh0
    stepv = jax.jit(nsv.step_fn(2e-3))
    for _ in range(5):
        whv = stepv(whv)
    dV = {k: float(v[0]) for k, v in nsv.diagnostics(whv).items()}
    assert dV["energy"] < d0["energy"]
    assert dV["enstrophy"] < d0["enstrophy"]


def test_ns3d_taylor_green_conserves_energy_inviscid(devices):
    """3D rotational form on the slab family: inviscid Taylor-Green
    energy is conserved through 3 RK4 steps (the Leray projection and
    dealiasing keep the truncated system conservative)."""
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                            _cfg(fft_backend="matmul"))
    ns = NavierStokes3D(plan, 0.0)
    ch0 = ns.to_spectral(jnp.asarray(taylor_green_3d(16)))
    e0 = float(ns.diagnostics(ch0)["energy"])
    step = jax.jit(ns.step_fn(5e-3))
    ch = ch0
    for _ in range(3):
        ch = step(ch)
    eT = float(ns.diagnostics(ch)["energy"])
    assert e0 == pytest.approx(0.125, rel=1e-6)  # TG closed form |u|²/2
    assert eT == pytest.approx(e0, rel=1e-8)


def test_ns2d_jit_grad_multistep(devices, rng):
    """jit(grad) through a 4-step NS solve on the 8-device mesh
    (batched-2D family) matches central finite differences."""
    n = 16
    plan = Batched2DFFTPlan(2, n, n, dfft.SlabPartition(8),
                            _cfg(fft_backend="matmul"), shard="x")
    ns = NavierStokes2D(plan, 0.01)
    sfn = ns.solve_fn(4, 1e-2)

    def loss(w):
        return jnp.sum(sfn(w) ** 2)

    w0 = rng.random((2, n, n))
    got = np.asarray(jax.jit(jax.grad(loss))(jnp.asarray(w0)))
    assert np.all(np.isfinite(got))
    eps = 1e-6
    for idx in ((0, 3, 5), (1, 7, 2)):
        wp, wm = w0.copy(), w0.copy()
        wp[idx] += eps
        wm[idx] -= eps
        fd = (float(loss(jnp.asarray(wp))) - float(loss(jnp.asarray(wm)))) \
            / (2 * eps)
        assert got[idx] == pytest.approx(fd, rel=1e-6, abs=1e-10), idx


def test_ns3d_jit_grad_multistep_slab(devices):
    """jit(grad) through a 4-step 3D NS solve (slab family, two
    transposes per transform) matches finite differences — the second
    plan family of the acceptance gate."""
    g = dfft.GlobalSize(8, 8, 8)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                            _cfg(fft_backend="matmul"))
    ns = NavierStokes3D(plan, 0.02)
    sfn = ns.solve_fn(4, 5e-3)

    def loss(u):
        return jnp.sum(sfn(u) ** 2)

    u0 = taylor_green_3d(8)
    got = np.asarray(jax.jit(jax.grad(loss))(jnp.asarray(u0)))
    assert np.all(np.isfinite(got))
    eps = 1e-6
    up, um = u0.copy(), u0.copy()
    up[0, 1, 2, 3] += eps
    um[0, 1, 2, 3] -= eps
    fd = (float(loss(jnp.asarray(up))) - float(loss(jnp.asarray(um)))) \
        / (2 * eps)
    assert got[0, 1, 2, 3] == pytest.approx(fd, rel=1e-6)


def test_ns3d_runs_on_pencil(devices):
    """The 3D stepper is plan-family agnostic: one step on the pencil
    grid equals the slab result."""
    g = dfft.GlobalSize(16, 16, 16)
    u0 = taylor_green_3d(16)
    outs = []
    for plan in (dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                                  _cfg(fft_backend="matmul")),
                 dfft.PencilFFTPlan(g, dfft.PencilPartition(2, 4),
                                    _cfg(fft_backend="matmul"))):
        ns = NavierStokes3D(plan, 1e-2)
        outs.append(np.asarray(ns.run(u0, 1, 1e-3)))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-12)


def test_make_solver_dispatch(devices):
    g = dfft.GlobalSize(16, 16, 16)
    plan3 = dfft.SlabFFTPlan(g, dfft.SlabPartition(8), _cfg())
    plan2 = Batched2DFFTPlan(1, 16, 16, dfft.SlabPartition(8), _cfg())
    assert isinstance(make_solver("poisson", plan3), PoissonSolver)
    assert isinstance(make_solver("navier_stokes", plan3, viscosity=1e-3),
                      NavierStokes3D)
    assert isinstance(make_solver("navier-stokes", plan2, viscosity=1e-3),
                      NavierStokes2D)
    conv = make_solver("convolve", plan2, kernel=np.ones((3, 3)),
                       image_shape=(14, 14))
    assert conv.plan is plan2
    with pytest.raises(ValueError, match="unknown solver kind"):
        make_solver("heat", plan3)
    with pytest.raises(TypeError, match="viscosity"):
        make_solver("ns", plan3)


# ---------------------------------------------------------------------------
# Poisson boundary conditions (the R2R upgrade)
# ---------------------------------------------------------------------------


def test_poisson_dirichlet_box(devices):
    """Dirichlet walls on the staggered grid: u = Πsin(πx_i/L) is a
    single DST-II mode per axis, recovered exactly from f = ∇²u."""
    n, L = 16, 1.3
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(2 * n, 2 * n, 2 * n),
                            dfft.SlabPartition(8), _cfg())
    s = make_solver("poisson", plan, lengths=(L,) * 3, bc="dirichlet")
    assert s.interior_shape == (n, n, n)
    x = (np.arange(n) + 0.5) * (L / n)
    sx = np.sin(np.pi * x / L)
    u_true = sx[:, None, None] * sx[None, :, None] * sx[None, None, :]
    f = -3.0 * (np.pi / L) ** 2 * u_true
    np.testing.assert_allclose(np.asarray(s.solve(f)), u_true, atol=1e-12)


def test_poisson_neumann_box(devices):
    """Neumann walls: the DCT-II (even) extension, u = Πcos(πx_i/L)."""
    n, L = 16, 2.0
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(2 * n, 2 * n, 2 * n),
                            dfft.SlabPartition(8), _cfg())
    s = PoissonSolver(plan, lengths=(L,) * 3, bc="neumann")
    x = (np.arange(n) + 0.5) * (L / n)
    cx = np.cos(np.pi * x / L)
    u_true = cx[:, None, None] * cx[None, :, None] * cx[None, None, :]
    f = -3.0 * (np.pi / L) ** 2 * u_true
    np.testing.assert_allclose(np.asarray(s.solve(f)), u_true, atol=1e-12)


def test_poisson_mixed_bc_batched2d(devices):
    """Per-axis bc mixing on the batched-2D family: Dirichlet x,
    periodic y, every batch plane solved independently."""
    nb, nx, ny, L = 2, 16, 16, 1.0
    plan = Batched2DFFTPlan(nb, 2 * nx, ny, dfft.SlabPartition(8), _cfg(),
                            shard="x")
    s = PoissonSolver(plan, lengths=(1.0, L, 2 * np.pi),
                      bc=("periodic", "dirichlet", "periodic"))
    assert s.interior_shape == (nb, nx, ny)
    x = (np.arange(nx) + 0.5) * (L / nx)
    iy = np.arange(ny) * (2 * np.pi / ny)
    u_true = (np.sin(np.pi * x / L)[None, :, None]
              * np.sin(iy)[None, None, :] * np.ones((nb, 1, 1)))
    f = -((np.pi / L) ** 2 + 1.0) * u_true
    np.testing.assert_allclose(np.asarray(s.solve(f)), u_true, atol=1e-12)


def test_poisson_periodic_batched2d(devices):
    """The generalized solver on the batched-2D family (periodic): each
    plane is an independent 2D solve with the 1/(nx·ny) normalization —
    not the 3D volume's."""
    n = 32
    plan = Batched2DFFTPlan(3, n, n, dfft.SlabPartition(8), _cfg(),
                            shard="x")
    s = PoissonSolver(plan, lengths=(1.0, 2 * np.pi, 2 * np.pi))
    i = np.arange(n) * (2 * np.pi / n)
    u = (np.sin(i)[None, :, None] * np.sin(i)[None, None, :]
         * np.ones((3, 1, 1)))
    got = plan.crop_real(s.solve(-2.0 * u))
    np.testing.assert_allclose(got, u, atol=1e-12)


def test_poisson_bc_validation(devices):
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16),
                            dfft.SlabPartition(8), _cfg())
    with pytest.raises(ValueError, match="unknown bc"):
        PoissonSolver(plan, bc="robin")
    with pytest.raises(ValueError, match="integer"):
        PoissonSolver(plan, bc="dirichlet", mode="integer")
    odd = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 19),
                           dfft.SlabPartition(8), _cfg())
    with pytest.raises(ValueError, match="EXTENDED extent"):
        PoissonSolver(odd, bc="dirichlet")


# ---------------------------------------------------------------------------
# DCT / DST vs scipy goldens
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["dct", "dst"])
@pytest.mark.parametrize("type", [1, 2, 3])
def test_r2r_matches_scipy(rng, kind, type):
    x = rng.random((3, 11))
    ours = getattr(r2r, kind)
    ref = getattr(scipy_fft, kind)
    np.testing.assert_allclose(np.asarray(ours(x, type=type)),
                               ref(x, type=type, axis=-1), atol=1e-12)
    if type != 1:
        np.testing.assert_allclose(
            np.asarray(ours(x, type=type, norm="ortho")),
            ref(x, type=type, norm="ortho", axis=-1), atol=1e-12)
    inv = getattr(r2r, "i" + kind)
    iref = getattr(scipy_fft, "i" + kind)
    np.testing.assert_allclose(np.asarray(inv(x, type=type)),
                               iref(x, type=type, axis=-1), atol=1e-12)


def test_r2r_axes_backends_and_n(rng):
    """Axis selection, dctn/dstn separability, prime lengths through the
    bluestein backend, and the matmul backend agree with scipy."""
    x = rng.random((7, 13))
    np.testing.assert_allclose(np.asarray(r2r.dct(x, axis=0)),
                               scipy_fft.dct(x, axis=0), atol=1e-12)
    np.testing.assert_allclose(np.asarray(r2r.dctn(x)), scipy_fft.dctn(x),
                               atol=1e-11)
    np.testing.assert_allclose(np.asarray(r2r.dstn(x)), scipy_fft.dstn(x),
                               atol=1e-11)
    xp = rng.random((2, 127))
    np.testing.assert_allclose(
        np.asarray(r2r.dct(xp, backend="bluestein")), scipy_fft.dct(xp),
        atol=1e-10)
    np.testing.assert_allclose(
        np.asarray(r2r.dst(xp[:, :16], backend="matmul")),
        scipy_fft.dst(xp[:, :16]), atol=1e-11)
    # Round trip through the R2C machinery is the identity.
    np.testing.assert_allclose(np.asarray(r2r.idct(r2r.dct(x))), x,
                               atol=1e-12)


# ---------------------------------------------------------------------------
# Spectral convolution / correlation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["full", "same", "valid"])
def test_convolve_batched_images_vs_scipy(devices, rng, mode):
    """Image-batch convolution through the batched-2D stacked execution
    == direct scipy.signal.convolve2d per plane, every crop mode. The
    padded transform extent proves the non-periodic (linear) padding:
    no circular wraparound contaminates any output sample."""
    img = rng.random((3, 20, 17))
    ker = rng.random((5, 4))
    cv = make_convolver(ker, (20, 17), batch=3, mode=mode,
                        partition=dfft.SlabPartition(8), config=_cfg(),
                        family="batched2d")
    assert tuple(cv.plan.input_shape[1:]) == conv_shape((20, 17), (5, 4))
    got = np.asarray(cv(img))
    ref = np.stack([scipy_signal.convolve2d(img[i], ker, mode=mode)
                    for i in range(3)])
    np.testing.assert_allclose(got, ref, atol=1e-12)


def test_convolve_1d_matches_np_convolve(devices, rng):
    """Degenerate single-row case against np.convolve itself — the
    most-direct golden the ISSUE names."""
    x = rng.random(21)
    k = rng.random(6)
    cv = make_convolver(k[None, :], (1, 21), batch=1, mode="full",
                        partition=dfft.SlabPartition(1), config=_cfg())
    got = np.asarray(cv(x[None, None, :]))[0, 0]
    np.testing.assert_allclose(got, np.convolve(x, k), atol=1e-12)


def test_correlate_matches_scipy(devices, rng):
    img = rng.random((2, 12, 15))
    ker = rng.random((4, 5))
    for mode in ("full", "same", "valid"):
        cv = make_convolver(ker, (12, 15), batch=2, mode=mode,
                            correlate=True,
                            partition=dfft.SlabPartition(8), config=_cfg())
        got = np.asarray(cv(img))
        ref = np.stack([scipy_signal.correlate2d(img[i], ker, mode=mode)
                        for i in range(2)])
        np.testing.assert_allclose(got, ref, atol=1e-12, err_msg=mode)


def test_convolve_volume_slab_and_pencil(devices, rng):
    """3D volume convolution on the distributed 3D families vs
    scipy.signal.convolve (direct)."""
    vol = rng.random((12, 10, 9))
    k3 = rng.random((3, 3, 3))
    ref = scipy_signal.convolve(vol, k3, mode="same", method="direct")
    for family, part in (("slab", dfft.SlabPartition(8)),
                         ("pencil", dfft.PencilPartition(2, 4))):
        cv = make_convolver(k3, (12, 10, 9), family=family, mode="same",
                            partition=part, config=_cfg())
        np.testing.assert_allclose(np.asarray(cv(vol)), ref, atol=1e-12,
                                   err_msg=family)


def test_convolve_exact_pad_bluestein(devices, rng):
    """pad='exact' keeps the transform at the exact n+k-1 support (no
    smooth rounding) — only viable because the bluestein backend keeps
    arbitrary lengths on the fast path."""
    img = rng.random((2, 20, 17))
    ker = rng.random((5, 4))
    cv = make_convolver(ker, (20, 17), batch=2, mode="valid", pad="exact",
                        partition=dfft.SlabPartition(8),
                        config=_cfg(fft_backend="bluestein"))
    assert tuple(cv.plan.input_shape[1:]) == (24, 20)  # exact support
    got = np.asarray(cv(img))
    ref = np.stack([scipy_signal.convolve2d(img[i], ker, mode="valid")
                    for i in range(2)])
    np.testing.assert_allclose(got, ref, atol=1e-12)


def test_convolve_grad(devices, rng):
    """grad flows through conv_fn (matmul backend: fully jittable)."""
    vol = rng.random((8, 8, 8))
    k3 = rng.random((3, 3, 3))
    cv = make_convolver(k3, (8, 8, 8), family="slab", mode="same",
                        partition=dfft.SlabPartition(8),
                        config=_cfg(fft_backend="matmul"))
    fn = cv.conv_fn()
    g = np.asarray(jax.jit(jax.grad(lambda x: jnp.sum(fn(x) ** 2)))(
        jnp.asarray(vol)))
    assert g.shape == vol.shape and np.all(np.isfinite(g)) \
        and np.any(g != 0)


# ---------------------------------------------------------------------------
# Bluestein prime sizes on the plan families
# ---------------------------------------------------------------------------


def test_bluestein_prime_axis_ops(rng):
    """Op-level: the chirp path at the primes the ISSUE names (127, 251)
    matches np.fft, both transforms directions."""
    from distributedfft_tpu.ops import fft as lf
    for p in (127, 251):
        x = rng.random((2, p)) + 1j * rng.random((2, p))
        np.testing.assert_allclose(
            np.asarray(lf.fft(x, axis=-1, backend="bluestein")),
            np.fft.fft(x), atol=1e-10)
        xr = rng.random((2, p))
        np.testing.assert_allclose(
            np.asarray(lf.rfft(xr, axis=-1, backend="bluestein")),
            np.fft.rfft(xr), atol=1e-10)


def test_bluestein_smooth_axis_is_xla_identical(devices, rng):
    """On 5-smooth axes the bluestein backend delegates — bit-identical
    to the xla backend, so 'auto' racing skips it there
    (autotune_local_fft candidate rule)."""
    from distributedfft_tpu.ops import fft as lf
    from distributedfft_tpu.testing.autotune import autotune_local_fft
    x = rng.random((8, 12, 30))
    a = np.asarray(jax.jit(lambda v: lf.rfftn_3d(v, backend="bluestein"))(x))
    b = np.asarray(jax.jit(lambda v: lf.rfftn_3d(v, backend="xla"))(x))
    assert np.array_equal(a, b)
    import unittest.mock as mock
    with mock.patch(
            "distributedfft_tpu.testing.autotune._measure",
            side_effect=AssertionError("must not measure")):
        try:
            autotune_local_fft((8, 8, 8), backends=["bluestein"], k=2)
        except AssertionError:
            pytest.fail("bluestein raced on an all-smooth shape")


@pytest.mark.parametrize("make_plan", [
    lambda cfg: dfft.SlabFFTPlan(dfft.GlobalSize(19, 17, 13),
                                 dfft.SlabPartition(8), cfg),
    lambda cfg: dfft.PencilFFTPlan(dfft.GlobalSize(19, 17, 13),
                                   dfft.PencilPartition(2, 4), cfg),
])
def test_bluestein_all_prime_3d_slab_pencil(devices, rng, make_plan):
    """A fully prime (19 x 17 x 13) 3D R2C transform and its inverse
    match np.fft through the distributed slab and pencil pipelines with
    fft_backend='bluestein'."""
    plan = make_plan(_cfg(fft_backend="bluestein"))
    x = rng.random((19, 17, 13))
    got = plan.crop_spectral(plan.exec_r2c(x))
    np.testing.assert_allclose(got, np.fft.rfftn(x), atol=1e-10)
    back = np.asarray(plan.exec_c2r(plan.pad_spectral(
        jnp.asarray(np.fft.rfftn(x)))))[:19, :17, :13]
    np.testing.assert_allclose(back, x * x.size, atol=1e-9)  # NONE norm


def test_bluestein_prime_batched2d(devices, rng):
    """Prime-size planes through the batched-2D shard='x' exchange."""
    plan = Batched2DFFTPlan(2, 127, 31, dfft.SlabPartition(8),
                            _cfg(fft_backend="bluestein"), shard="x")
    img = rng.random((2, 127, 31))
    got = plan.crop_spectral(plan.exec_forward(img))
    np.testing.assert_allclose(got, np.fft.rfftn(img, axes=(1, 2)),
                               atol=1e-9)


def test_bluestein_prime_127_axis_slab(devices, rng):
    """A 127 (prime) decomposed axis — padded to 128 lanes over the mesh
    while the transform itself stays length 127 via chirp-z."""
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(127, 8, 8),
                            dfft.SlabPartition(8),
                            _cfg(fft_backend="bluestein"))
    x = rng.random((127, 8, 8))
    got = plan.crop_spectral(plan.exec_r2c(x))
    np.testing.assert_allclose(got, np.fft.rfftn(x), atol=1e-9)


def test_bluestein_helpers():
    from distributedfft_tpu.ops.bluestein import (chirp_length, good_size,
                                                  is_smooth)
    assert [is_smooth(n) for n in (1, 2, 30, 360, 7, 127)] == \
        [True, True, True, True, False, False]
    assert chirp_length(127) == 256 and chirp_length(251) == 512
    assert good_size(127) == 128 and good_size(97) == 100
    assert good_size(30) == 30


@pytest.mark.parametrize("n,m,factor", [
    (4095, 8192, 4.93453638913481),     # 3^2*5*7*13: chirp at 2^13
    (17, 64, 12.813931923651086),       # prime: chirp at 2^6
    (1024, 1024, 1.0),                  # smooth: delegated, no overhead
    (360, 360, 1.0),
])
def test_bluestein_axis_report(n, m, factor):
    """(chirp length, flop factor over a smooth axis): two length-m FFTs
    amortized over n elements plus three complex multiplies, over the
    nominal 2.5*log2(n) per element."""
    from distributedfft_tpu.ops.bluestein import bluestein_axis_report
    got_m, got_factor = bluestein_axis_report(n)
    assert got_m == m
    assert got_factor == pytest.approx(factor, rel=1e-12)
    if m != n:
        assert got_factor == pytest.approx(
            (5.0 * m * np.log2(m) / n + 18.0) / (2.5 * np.log2(n)))


def test_nonsmooth_axes():
    from distributedfft_tpu.ops.bluestein import nonsmooth_axes
    assert nonsmooth_axes((4095, 17, 1024, 17)) == [17, 4095]
    assert nonsmooth_axes((1024, 1024, 1024)) == []
    assert nonsmooth_axes((4096, 4096)) == []


# ---------------------------------------------------------------------------
# guards + compressed wire composition through a solver path
# ---------------------------------------------------------------------------


def test_solver_guards_check_with_bf16_wire(devices, rng):
    """One solver path (Poisson on the slab exchange) composed with
    guards='check' AND the compressed bf16 wire: the guarded pipeline
    runs through the exec envelope, the result stays within the
    documented wire tolerance of the native-wire solve, and no guard
    violation fires on the clean run."""
    from distributedfft_tpu import obs
    g = dfft.GlobalSize(32, 32, 32)
    f = rng.random(g.shape).astype(np.float32)
    f -= f.mean()

    def solve(wire, guards):
        plan = dfft.SlabFFTPlan(
            g, dfft.SlabPartition(8),
            dfft.Config(use_wisdom=False, wire_dtype=wire, guards=guards))
        return np.asarray(PoissonSolver(plan).solve(f))

    obs.metrics.reset()
    native = solve("native", "off")
    guarded = solve("bf16", "check")
    assert np.all(np.isfinite(guarded))
    scale = np.max(np.abs(native)) or 1.0
    assert np.max(np.abs(guarded - native)) / scale < 2e-2  # wire budget
    snap = obs.metrics.snapshot()["counters"]
    assert snap.get("guard.parseval_violations", 0) == 0
    assert snap.get("guard.wire_drift_violations", 0) == 0
