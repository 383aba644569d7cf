"""MXU matmul-FFT backend (ops/mxu_fft.py) vs numpy ground truth.

Covers direct (n <= DIRECT_MAX), four-step (composite n > DIRECT_MAX, incl.
recursion), prime-length fallback, all four 1D entry points, norm modes, and
end-to-end slab/pencil plans with ``Config(fft_backend="matmul")``.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import distributedfft_tpu as dfft
from distributedfft_tpu.ops import fft as lf
from distributedfft_tpu.ops import mxu_fft
from distributedfft_tpu.params import FFTNorm


def _rel(a, b):
    return np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)


# n exercising: small direct, odd direct, prime, composite four-step
# (640 = 2^7*5 -> split 20x32? balanced), pow2 four-step with recursion
# disabled (1024 -> 32x32).
NS = [8, 12, 13, 96, 640, 1024]


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("double", [False, True])
def test_fft_ifft_vs_numpy(n, double, rng):
    dt = np.complex128 if double else np.complex64
    tol = 1e-10 if double else 5e-4
    x = (rng.standard_normal((3, n)) + 1j * rng.standard_normal((3, n))
         ).astype(dt)
    got = np.asarray(mxu_fft.fft(x, axis=-1))
    assert _rel(got, np.fft.fft(x, axis=-1)) < tol
    goti = np.asarray(mxu_fft.ifft(x, axis=-1))
    # FFTNorm.NONE inverse is unnormalized (cuFFT convention): n * numpy ifft.
    assert _rel(goti, n * np.fft.ifft(x, axis=-1)) < tol


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("double", [False, True])
def test_rfft_irfft_vs_numpy(n, double, rng):
    rt = np.float64 if double else np.float32
    tol = 1e-10 if double else 5e-4
    x = rng.standard_normal((4, n)).astype(rt)
    got = np.asarray(mxu_fft.rfft(x, axis=-1))
    ref = np.fft.rfft(x, axis=-1)
    assert got.shape == ref.shape
    assert _rel(got, ref) < tol
    # Round trip with BACKWARD (1/n on inverse) recovers the input.
    back = np.asarray(mxu_fft.irfft(got, n=n, axis=-1, norm=FFTNorm.BACKWARD))
    assert _rel(back, x) < tol


def test_axis_and_ortho(rng):
    x = rng.standard_normal((5, 32, 7)).astype(np.float64)
    got = np.asarray(mxu_fft.rfft(x, axis=1, norm=FFTNorm.ORTHO))
    assert _rel(got, np.fft.rfft(x, axis=1, norm="ortho")) < 1e-11
    c = x.astype(np.complex128)
    got2 = np.asarray(mxu_fft.ifft(c, axis=0, norm=FFTNorm.ORTHO))
    assert _rel(got2, np.fft.ifft(c, axis=0, norm="ortho")) < 1e-11


def test_four_step_recursion(rng):
    """n=1042 splits to (2, 521) with 521 > DIRECT_MAX, forcing _fft_last to
    recurse (prime inner stage) and _rfft_last through its complex-promotion
    branch — the recursion paths no NS size reaches."""
    n = 1042
    assert mxu_fft._split(n) == (2, 521) and 521 > mxu_fft.DIRECT_MAX
    x = rng.standard_normal((2, n)).astype(np.float64)
    got = np.asarray(mxu_fft.rfft(x, axis=-1))
    assert _rel(got, np.fft.rfft(x, axis=-1)) < 1e-10
    c = (rng.standard_normal((2, n)) + 1j * rng.standard_normal((2, n)))
    gotc = np.asarray(mxu_fft.fft(c, axis=-1))
    assert _rel(gotc, np.fft.fft(c, axis=-1)) < 1e-10


def test_split_balanced():
    assert mxu_fft._split(1024) == (32, 32)
    assert mxu_fft._split(640) == (20, 32)
    n1, n2 = mxu_fft._split(6007)  # prime
    assert (n1, n2) == (1, 6007)


def test_backend_dispatch_matches_xla(rng):
    x = rng.standard_normal((4, 64)).astype(np.float64)
    a = np.asarray(lf.rfft(x, axis=-1, backend="matmul"))
    b = np.asarray(lf.rfft(x, axis=-1, backend="xla"))
    assert _rel(a, b) < 1e-11


def test_rfftn3d_matches_numpy(rng):
    x = rng.standard_normal((8, 8, 8)).astype(np.float64)
    got = np.asarray(mxu_fft.rfftn_3d(x))
    assert _rel(got, np.fft.rfftn(x)) < 1e-11
    back = np.asarray(mxu_fft.irfftn_3d(got, (8, 8, 8)))
    assert _rel(back, x * 8 ** 3) < 1e-11


@pytest.mark.parametrize("family", ["slab", "pencil"])
def test_plan_with_matmul_backend(family, devices, rng):
    g = dfft.GlobalSize(16, 16, 16)
    cfg = dfft.Config(double_prec=True, fft_backend="matmul")
    if family == "slab":
        mesh = dfft.make_slab_mesh(4, devices)
        plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(4), cfg, mesh=mesh)
    else:
        mesh = dfft.make_pencil_mesh(2, 2, devices[:4])
        plan = dfft.PencilFFTPlan(g, dfft.PencilPartition(2, 2), cfg,
                                  mesh=mesh)
    x = rng.standard_normal(g.shape).astype(np.float64)
    out = plan.crop_spectral(plan.exec_r2c(plan.pad_input(x)))
    assert _rel(out, np.fft.rfftn(x)) < 1e-10
    back = plan.crop_real(plan.exec_c2r(plan.exec_r2c(plan.pad_input(x))))
    assert _rel(back, x * g.nx * g.ny * g.nz) < 1e-10


def test_config_rejects_unknown_backend():
    with pytest.raises(ValueError):
        dfft.Config(fft_backend="cufft")


def test_karatsuba_toggle_matches_4matmul(rng):
    """The 3-matmul complex multiply must agree with the plain complex
    matmul path to f64 tightness (both run the same DFT)."""
    from distributedfft_tpu.ops import mxu_fft as mf
    x = (rng.standard_normal((8, 64)) + 1j * rng.standard_normal((8, 64))
         ).astype(np.complex128)
    try:
        mf.set_karatsuba(True)
        a = np.asarray(mf.fft(x, axis=-1))
        mf.set_karatsuba(False)
        b = np.asarray(mf.fft(x, axis=-1))
    finally:
        mf.set_karatsuba(False)  # module default
    assert _rel(a, b) < 1e-12
    assert _rel(a, np.fft.fft(x, axis=-1)) < 1e-12


def test_set_precision_accepts_names():
    from jax import lax
    from distributedfft_tpu.ops import mxu_fft as mf
    try:
        mf.set_precision("highest")
        assert mf.current_settings().precision == lax.Precision.HIGHEST
        mf.set_precision(lax.Precision.HIGH)
        assert mf.current_settings().precision == lax.Precision.HIGH
    finally:
        mf.set_precision(lax.Precision.HIGH)


class TestMXUSettings:
    """Per-plan backend knobs (VERDICT r2 weak#7): settings travel as
    Config/plan state through a context-scoped MXUSettings instead of the
    four former module globals, so differently-configured plans coexist."""

    def test_config_builds_settings(self):
        from jax import lax
        cfg = dfft.Config(fft_backend="matmul", mxu_precision="highest",
                          mxu_karatsuba=True)
        st = cfg.mxu_settings()
        assert st.precision == lax.Precision.HIGHEST
        assert st.karatsuba and not st.fourstep_einsum

    def test_config_default_settings_is_none(self):
        # None defers to the deprecated process defaults (back-compat).
        assert dfft.Config(fft_backend="matmul").mxu_settings() is None

    def test_config_rejects_bad_precision(self):
        with pytest.raises(ValueError, match="mxu_precision"):
            dfft.Config(mxu_precision="bf16")

    def test_two_plans_with_different_settings_coexist(self, rng):
        """The VERDICT 'done' criterion: trace two differently-configured
        plans in one process and observe both tracings honored (karatsuba
        changes the complex-multiply structure: 3 real dots per C2C stage
        vs 1 complex dot) with no global state mutated."""
        import jax

        from distributedfft_tpu.ops import mxu_fft as mf

        g = dfft.GlobalSize(8, 8, 8)
        part = dfft.SlabPartition(1)
        plain = dfft.SlabFFTPlan(g, part, dfft.Config(fft_backend="matmul"))
        kara = dfft.SlabFFTPlan(
            g, part, dfft.Config(fft_backend="matmul", mxu_karatsuba=True))
        x = rng.random(g.shape).astype(np.float32)
        jx_plain = str(jax.make_jaxpr(plain.forward_fn())(x))
        jx_kara = str(jax.make_jaxpr(kara.forward_fn())(x))
        assert jx_kara.count("dot_general") > jx_plain.count("dot_general")
        assert mf.current_settings() == mf.MXUSettings()  # nothing leaked
        # and both compute the same transform
        ref = np.fft.rfftn(x)
        assert _rel(np.asarray(plain.exec_r2c(x)), ref) < 1e-4
        assert _rel(np.asarray(kara.exec_r2c(x)), ref) < 1e-4

    def test_settings_kwarg_overrides_process_default(self, rng):
        """An explicit settings= beats the deprecated set_* default, and
        the scoped override never escapes the call."""
        import jax

        from distributedfft_tpu.ops import mxu_fft as mf

        # 1024 > DIRECT_MAX forces the four-step split (32*32), where the
        # einsum and swapaxes formulations trace differently.
        x = rng.random((4, 1024)).astype(np.float32)
        try:
            mf.set_fourstep_einsum(True)  # process default: einsum on
            st_off = mf.MXUSettings.make(fourstep_einsum=False)
            from distributedfft_tpu.ops import fft as lf
            jx_default = str(jax.make_jaxpr(
                lambda a: lf.fft(a, axis=-1, backend="matmul"))(
                    x.astype(np.complex64)))
            jx_off = str(jax.make_jaxpr(
                lambda a: lf.fft(a, axis=-1, backend="matmul",
                                 settings=st_off))(x.astype(np.complex64)))
            assert jx_default != jx_off
        finally:
            mf.set_fourstep_einsum(False)
        assert mf.current_settings() == mf.MXUSettings()


def test_plan_prime_dims_matmul_backend(devices, rng):
    """Prime global extents (7, 11, 13) under a sharded plan: every axis
    hits the direct DFT-matmul path and every mesh split needs padding."""
    g = dfft.GlobalSize(7, 11, 13)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(4),
                            dfft.Config(double_prec=True,
                                        fft_backend="matmul"),
                            mesh=dfft.make_slab_mesh(4, devices[:4]))
    x = rng.standard_normal(g.shape)
    out = plan.crop_spectral(plan.exec_r2c(plan.pad_input(x)))
    assert _rel(out, np.fft.rfftn(x)) < 1e-10
    back = plan.crop_real(plan.exec_c2r(plan.exec_r2c(plan.pad_input(x))))
    assert _rel(back, x * g.n_total) < 1e-10


def test_real_planes_3d_matches_numpy(rng):
    """All-real-planes formulation (bench's ``matmul-planes`` backend):
    same DFT matmuls, no complex dtype anywhere in the program."""
    import jax
    import jax.numpy as jnp

    for shape in [(16, 16, 16), (8, 12, 10), (4, 8, 9)]:
        x = rng.random(shape).astype(np.float32)
        cr, ci = jax.jit(mxu_fft.rfftn_3d_planes)(jnp.asarray(x))
        ref = np.fft.rfftn(x)
        err = max(np.abs(np.asarray(cr) - ref.real).max(),
                  np.abs(np.asarray(ci) - ref.imag).max())
        assert err / np.abs(ref).max() < 1e-5, shape
        y = jax.jit(lambda a, b, s=shape: mxu_fft.irfftn_3d_planes(a, b, s))(
            jnp.asarray(ref.real.astype(np.float32)),
            jnp.asarray(ref.imag.astype(np.float32)))
        assert np.abs(np.asarray(y) / np.prod(shape) - x).max() < 1e-4, shape


def test_real_planes_rejects_non_direct(rng):
    import jax.numpy as jnp

    with pytest.raises(ValueError, match="direct-size"):
        mxu_fft.rfftn_3d_planes(jnp.zeros((4, 4, 1024), np.float32))


def test_real_planes_chain_backend(rng):
    """chaintimer accepts backend='matmul-planes' and the chain agrees with
    the regular matmul chain on the same input."""
    import jax.numpy as jnp

    from distributedfft_tpu.testing import chaintimer

    x = jnp.asarray(rng.random((8, 8, 8)).astype(np.float32))
    a = float(chaintimer.roundtrip_chain(2, (8, 8, 8), "matmul")(x))
    b = float(chaintimer.roundtrip_chain(2, (8, 8, 8), "matmul-planes")(x))
    assert abs(a - b) / abs(a) < 1e-4


class TestRadix2:
    """Radix-2 DIF splitting (``set_radix2`` / backend "matmul-r2"):
    halves MXU matmul depth on C2C stages down to the 128-deep base case."""

    @pytest.mark.parametrize("n", [160, 256, 512])
    @pytest.mark.parametrize("double", [False, True])
    def test_fft_vs_numpy(self, n, double, rng):
        dt = np.complex128 if double else np.complex64
        tol = 1e-10 if double else 5e-4
        x = (rng.standard_normal((3, n)) + 1j * rng.standard_normal((3, n))
             ).astype(dt)
        with mxu_fft.radix2():
            got = np.asarray(mxu_fft.fft(x, axis=-1))
            goti = np.asarray(mxu_fft.ifft(x, axis=-1))
        assert _rel(got, np.fft.fft(x, axis=-1)) < tol
        assert _rel(goti, n * np.fft.ifft(x, axis=-1)) < tol

    def test_backend_shim_restores_flag(self, rng):
        """The "matmul-r2" backend scopes radix2=True only for the
        duration of the call (context-local MXUSettings override)."""
        assert mxu_fft.current_settings().radix2 is False
        x = rng.random((256, 4, 4)).astype(np.float32)
        c = lf.rfftn_3d(x, backend="matmul-r2")
        assert mxu_fft.current_settings().radix2 is False
        ref = np.fft.rfftn(x, axes=(0, 1, 2))
        assert _rel(np.asarray(c), ref) < 5e-4
        y = lf.irfftn_3d(c, x.shape, backend="matmul-r2")
        assert _rel(np.asarray(y) / x.size, x) < 5e-4

    def test_roundtrip_f64_tight(self, rng):
        """f64 radix-2 roundtrip at the north-star accuracy bar."""
        x = rng.standard_normal((256, 6, 6))
        c = lf.rfftn_3d(x, backend="matmul-r2")
        y = np.asarray(lf.irfftn_3d(c, x.shape, backend="matmul-r2")) / x.size
        assert np.abs(y - x).max() < 1e-10

    def test_odd_length_unaffected(self, rng):
        """Odd n can't split: radix-2 toggle must leave it identical to the
        direct path."""
        x = (rng.standard_normal((3, 81)) + 1j * rng.standard_normal((3, 81))
             ).astype(np.complex128)
        base = np.asarray(mxu_fft.fft(x, axis=-1))
        with mxu_fft.radix2():
            r2 = np.asarray(mxu_fft.fft(x, axis=-1))
        np.testing.assert_array_equal(base, r2)

    def test_autotune_races_r2(self, devices):
        """matmul-r2 shows up in the autotune candidate list with both
        precision variants."""
        from distributedfft_tpu.testing import autotune

        # 160 on the last axis: above _R2_BASE=128, so the r2 candidates
        # really trace the split path, not the shared direct fallback.
        cands = autotune.autotune_local_fft(
            (8, 8, 160), backends=("matmul", "matmul-r2"), k=3,
            repeats=1, inner=1)
        labels = {c.label for c in cands}
        assert {"matmul@high", "matmul@highest", "matmul-r2@high",
                "matmul-r2@highest"} <= labels
        # The test pins dispatch + accuracy, not wall-clock: on a loaded
        # host a k=3 chain of a 16^3-ish problem can legitimately measure
        # degenerate (median t_K - t_1 <= 0), which is not a failure of
        # the r2 path — but accuracy (computed before timing) must hold
        # even then.
        for c in cands:
            assert c.ok or (c.error and "degenerate" in c.error
                            and c.rel_err <= 1e-4), \
                (c.label, c.error, c.rel_err)

    def test_plan_backend_r2(self, devices, rng):
        """End-to-end sharded slab plan with Config(fft_backend='matmul-r2').
        x = 160 > _R2_BASE so the 1D-FFT(x) stage really takes the radix-2
        split, not the shared direct fallback."""
        g = dfft.GlobalSize(160, 16, 16)
        plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                                dfft.Config(double_prec=True,
                                            fft_backend="matmul-r2"))
        x = rng.standard_normal(g.shape)
        out = plan.crop_spectral(plan.exec_r2c(x))
        assert _rel(out, np.fft.rfftn(x)) < 1e-10


class TestFourStepEinsum:
    """Relayout-free four-step formulation (``set_fourstep_einsum``): same
    math as the swapaxes pipeline, contracted via dot_general. Measured
    slower on v5e (see the module comment) — kept as a raced toggle."""

    @pytest.mark.parametrize("n", [640, 1024, 2048])
    def test_c2c_matches_swap_path(self, n, rng):
        x = (rng.standard_normal((3, n)) + 1j * rng.standard_normal((3, n)))
        base = np.asarray(mxu_fft.fft(x, axis=-1))
        with mxu_fft.fourstep_einsum():
            via_einsum = np.asarray(mxu_fft.fft(x, axis=-1))
        # Same factor matrices and contraction math; tolerance instead of
        # bit-equality because the two dot_general lowerings may differ in
        # accumulation order across jaxlib versions.
        assert _rel(via_einsum, base) < 1e-14

    def test_r2c_vs_numpy(self, rng):
        x = rng.standard_normal((4, 640))
        with mxu_fft.fourstep_einsum():
            got = np.asarray(mxu_fft.rfft(x, axis=-1))
        ref = np.fft.rfft(x, axis=-1)
        assert _rel(got, ref) < 1e-10


@pytest.mark.parametrize("n", [1, 2, 9, 16, 30])
@pytest.mark.parametrize("norm", ["backward", "forward", "ortho"])
def test_xla_irfft_matches_numpy(rng, n, norm):
    """The xla backend's C2R (one half-length complex inverse, reversal
    as a gather; see ``xla_irfft``) equals numpy's irfft on any axis,
    with the spectral axis cropped or zero-padded like numpy and the
    DC/Nyquist imaginary parts ignored."""
    from distributedfft_tpu.ops.fft import xla_irfft

    for have in (n // 2 + 1, n // 2, n // 2 + 3):
        for axis in (0, 1, -1):
            shape = [3, 4, 5]
            shape[axis] = max(have, 1)
            c = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
            got = np.asarray(xla_irfft(jnp.asarray(c), n, axis, norm))
            np.testing.assert_allclose(
                got, np.fft.irfft(c, n=n, axis=axis, norm=norm), atol=1e-12)


@pytest.mark.parametrize("shape", [(4, 6, 8), (5, 7, 9), (2, 8, 16)])
def test_xla_irfftn_3d_matches_numpy(rng, shape):
    """The xla backend's 3D C2R (two complex inverses, then
    ``xla_irfft``) equals numpy's irfftn, also on a spectrum that is not
    Hermitian and one of the wrong length."""
    for have in (shape[2] // 2 + 1, shape[2] // 2):
        c = (rng.standard_normal(shape[:2] + (have,))
             + 1j * rng.standard_normal(shape[:2] + (have,)))
        got = np.asarray(lf.irfftn_3d(c, shape, norm=FFTNorm.BACKWARD))
        np.testing.assert_allclose(
            got, np.fft.irfftn(c, s=shape, axes=(0, 1, 2)), atol=1e-12)
