"""Differentiability of the distributed pipelines — a capability the
CUDA/MPI reference cannot express (hand-rolled MPI exchanges are opaque to
autodiff). The plans' ``forward_fn``/``inverse_fn`` expose the PURE
pipeline (no jit, no sharding annotations) so it composes under user
transforms: grad flows through the sharded local FFTs and the all_to_all
transposes. The matmul backend (pure einsum) is the differentiable
TPU-native local transform; XLA's FFT op may lack a vjp under shard_map.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import distributedfft_tpu as dfft


def _roundtrip_loss(plan, w):
    fwd, inv = plan.forward_fn(), plan.inverse_fn()
    n_total = plan.global_size.n_total

    def loss(x):
        return jnp.sum(jnp.asarray(w) * inv(fwd(x)) / n_total)

    return loss


@pytest.mark.parametrize("backend", ["matmul", "xla"])
def test_grad_through_sharded_slab_roundtrip(devices, rng, backend):
    """grad of a weighted-sum loss through the 8-device slab forward +
    inverse (crosses the all_to_all transpose both ways). The
    unnormalized roundtrip / N^3 is the identity, so dloss/dx = w.
    The xla case crosses the rfft transpose inside shard_map."""
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                            dfft.Config(double_prec=True,
                                        fft_backend=backend))
    w = rng.random(g.shape)
    got = np.asarray(jax.grad(_roundtrip_loss(plan, w))(rng.random(g.shape)))
    np.testing.assert_allclose(got, w, atol=1e-10)


def test_grad_through_pencil_roundtrip(devices, rng):
    """Same property through the 2D pencil mesh (two transposes each way),
    under an enclosing jax.jit as a user would run it."""
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.PencilFFTPlan(g, dfft.PencilPartition(2, 4),
                              dfft.Config(double_prec=True,
                                          fft_backend="matmul"))
    w = rng.random(g.shape)
    gradf = jax.jit(jax.grad(_roundtrip_loss(plan, w)))
    got = np.asarray(gradf(rng.random(g.shape)))
    np.testing.assert_allclose(got, w, atol=1e-10)


def test_grad_through_spectral_solve_matches_fd(devices, rng):
    """grad through a full distributed spectral solve (forward -> symbol
    multiply -> inverse, the Poisson structure) agrees with central finite
    differences at sampled coordinates."""
    g = dfft.GlobalSize(8, 8, 8)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                            dfft.Config(double_prec=True,
                                        fft_backend="matmul"))
    fwd, inv = plan.forward_fn(), plan.inverse_fn()
    w = jnp.asarray(rng.random(g.shape))
    sym = jnp.asarray(rng.random(plan.output_padded_shape) + 0.5)

    def loss(f):
        return jnp.sum(w * inv(fwd(f) * sym) / g.n_total)

    f0 = rng.random(g.shape)
    got = np.asarray(jax.grad(loss)(jnp.asarray(f0))).reshape(-1)

    def lossf(f):
        return float(loss(jnp.asarray(f)))

    eps = 1e-6
    for idx in (0, 17, 123, 511):
        fp = f0.copy().reshape(-1)
        fm = f0.copy().reshape(-1)
        fp[idx] += eps
        fm[idx] -= eps
        fd = (lossf(fp.reshape(g.shape)) - lossf(fm.reshape(g.shape))) \
            / (2 * eps)
        assert got[idx] == pytest.approx(fd, rel=1e-5, abs=1e-9), idx


def test_forward_fn_matches_exec(devices, rng):
    """The pure pipeline computes exactly what the jitted exec path does."""
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                            dfft.Config(double_prec=True))
    x = rng.random(g.shape)
    a = np.asarray(plan.exec_r2c(x))
    b = np.asarray(jax.jit(plan.forward_fn())(x))
    np.testing.assert_allclose(a, b, rtol=1e-12)


def test_single_process_forward_fn(rng):
    """fft3d fallback plans expose the pure pipeline too."""
    g = dfft.GlobalSize(8, 8, 8)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(1),
                            dfft.Config(double_prec=True,
                                        fft_backend="matmul"))
    w = rng.random(g.shape)
    got = np.asarray(jax.grad(_roundtrip_loss(plan, w))(rng.random(g.shape)))
    np.testing.assert_allclose(got, w, atol=1e-10)


@pytest.mark.parametrize("comm", ["ALL2ALL", "PEER2PEER"])
def test_grad_both_comm_methods(devices, rng, comm):
    """Both comm branches of the pure composition differentiate: the fused
    explicit-collective shard_map and the two-stage GSPMD path."""
    from distributedfft_tpu import CommMethod

    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                            dfft.Config(double_prec=True,
                                        fft_backend="matmul",
                                        comm_method=CommMethod[comm]))
    w = rng.random(g.shape)
    got = np.asarray(jax.grad(_roundtrip_loss(plan, w))(rng.random(g.shape)))
    np.testing.assert_allclose(got, w, atol=1e-10)


def test_forward_fn_pads_like_exec(devices, rng):
    """Non-mesh-divisible logical input is padded inside the traced
    pipeline (the exec_* preamble's differentiable analog)."""
    g = dfft.GlobalSize(20, 16, 16)  # 20 % 8 != 0 -> padded to 24
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                            dfft.Config(double_prec=True,
                                        fft_backend="matmul"))
    x = rng.random(g.shape)
    a = plan.crop_spectral(plan.exec_r2c(x))
    b = plan.crop_spectral(jax.jit(plan.forward_fn())(x))
    np.testing.assert_allclose(a, b, rtol=1e-12)
    # grad through the padded pipeline still matches the identity property
    w = rng.random(g.shape)
    fwd, inv = plan.forward_fn(), plan.inverse_fn()

    def loss(v):
        y = inv(fwd(v))[: g.nx] / g.n_total
        return jnp.sum(jnp.asarray(w) * y)

    got = np.asarray(jax.grad(loss)(rng.random(g.shape)))
    np.testing.assert_allclose(got, w, atol=1e-10)


def test_forward_fn_rejects_wrong_shape(devices):
    """A shape matching neither the logical nor the padded extent must
    raise (ADVICE r2: without this, shape-agnostic pipelines silently
    compute a transform inconsistent with the plan)."""
    g = dfft.GlobalSize(20, 16, 16)  # padded to 24 over 8 ranks
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                            dfft.Config(double_prec=True,
                                        fft_backend="matmul"))
    with pytest.raises(ValueError, match="neither the logical"):
        plan.forward_fn()(np.zeros((21, 16, 16)))


def test_forward_fn_is_cached(devices):
    """Repeated forward_fn() calls return the SAME callable, so a user's
    jit cache (keyed on function identity) does not retrace per call."""
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                            dfft.Config(double_prec=True))
    assert plan.forward_fn() is plan.forward_fn()
    assert plan.inverse_fn() is plan.inverse_fn()
    pplan = dfft.PencilFFTPlan(g, dfft.PencilPartition(2, 4),
                               dfft.Config(double_prec=True))
    assert pplan.forward_fn() is pplan.forward_fn()
    assert pplan.forward_fn(dims=2) is pplan.forward_fn(dims=2)
    assert pplan.forward_fn(dims=2) is not pplan.forward_fn(dims=3)


def test_grad_through_batched2d(devices, rng):
    """Batched-2D plan: grad through the batch-sharded pure pipeline, and
    through the shard='x' slab-style pipeline (one transpose each way)."""
    from distributedfft_tpu.models.batched2d import Batched2DFFTPlan

    for shard in ("batch", "x"):
        plan = Batched2DFFTPlan(8, 16, 16, dfft.SlabPartition(8),
                                dfft.Config(double_prec=True,
                                            fft_backend="matmul"),
                                shard=shard)
        fwd, inv = plan.forward_fn(), plan.inverse_fn()
        w = rng.random((8, 16, 16))

        def loss(x):
            return jnp.sum(jnp.asarray(w) * inv(fwd(x)) / (16 * 16))

        got = np.asarray(jax.grad(loss)(rng.random((8, 16, 16))))
        np.testing.assert_allclose(got, w, atol=1e-10, err_msg=shard)


def test_grad_through_poisson_solve_fn(devices, rng):
    """solver.solve_fn(): the flagship use case differentiates end to end
    (forward -> Laplacian symbol -> inverse) and matches the jitted solve
    numerically."""
    from distributedfft_tpu.solvers.poisson import PoissonSolver

    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                            dfft.Config(double_prec=True,
                                        fft_backend="matmul"))
    solver = PoissonSolver(plan, mode="integer")
    f = rng.random(g.shape)
    a = np.asarray(solver.solve(f))
    b = np.asarray(jax.jit(solver.solve_fn())(f))
    np.testing.assert_allclose(a, b, rtol=1e-12)

    w = jnp.asarray(rng.random(g.shape))
    sfn = solver.solve_fn()
    grad = jax.grad(lambda v: jnp.sum(w * sfn(v)))(jnp.asarray(f))
    # The solve operator S is linear and symmetric (real diagonal symbol in
    # Fourier space), so d/df sum(w * S f) = S w.
    ref = np.asarray(solver.solve(np.asarray(w)))
    np.testing.assert_allclose(np.asarray(grad), ref, atol=1e-12)


# ZY_Then_X (the default) is already covered by
# test_grad_through_sharded_slab_roundtrip; race only the other two.
@pytest.mark.parametrize("seq", ["Z_Then_YX", "Y_Then_ZX"])
def test_grad_all_slab_sequences(devices, rng, seq):
    """Every slab sequence's pure pipeline differentiates (each puts the
    halved axis and the transpose in a different place)."""
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                            dfft.Config(double_prec=True,
                                        fft_backend="matmul"), sequence=seq)
    w = rng.random(g.shape)
    got = np.asarray(jax.grad(_roundtrip_loss(plan, w))(rng.random(g.shape)))
    np.testing.assert_allclose(got, w, atol=1e-10)


def test_grad_c2c_transform(devices, rng):
    """C2C plans: holomorphic-style grad via real loss on complex input
    (jax requires the loss to be real; use |.|^2 of the roundtrip)."""
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8),
                            dfft.Config(double_prec=True,
                                        fft_backend="matmul"),
                            transform="c2c")
    fwd, inv = plan.forward_fn(), plan.inverse_fn()
    x0 = (rng.random(g.shape) + 1j * rng.random(g.shape))

    def loss(v):
        y = inv(fwd(v)) / g.n_total
        return jnp.sum(jnp.abs(y - jnp.asarray(x0)) ** 2).real

    # The roundtrip identity makes loss(v) = |v - x0|^2, whose jax grad
    # (conjugate-cotangent convention) is 2*conj(v - x0) — a NONZERO
    # expected gradient, so a silently-dead vjp cannot pass.
    v = jnp.asarray(rng.random(g.shape) + 1j * rng.random(g.shape))
    gr = jax.grad(loss)(v)
    np.testing.assert_allclose(np.asarray(gr),
                               np.asarray(2 * jnp.conj(v - jnp.asarray(x0))),
                               atol=1e-10)
