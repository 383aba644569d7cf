"""Shared chained-roundtrip timing harness (testing/chaintimer.py), used by
bench.py and the autotuner."""

import numpy as np
import pytest

import jax

from distributedfft_tpu.testing import chaintimer as ct


def test_chain_is_identity_scaled(rng):
    """One roundtrip through the chain reproduces sum|x| (the chain body is
    irfftn(rfftn(x))/N^3 == x up to float error)."""
    shape = (8, 8, 8)
    x = jax.device_put(rng.random(shape).astype(np.float32))
    for k in (1, 3):
        fn = ct.roundtrip_chain(k, shape, "xla")
        got = float(fn(x))
        assert got == pytest.approx(float(np.sum(np.abs(np.asarray(x)))),
                                    rel=1e-4)


def test_median_pair_diff_positive_on_real_work(rng):
    shape = (16, 16, 16)
    x = jax.device_put(rng.random(shape).astype(np.float32))
    fn1 = ct.roundtrip_chain(1, shape, "xla")
    fnK = ct.roundtrip_chain(33, shape, "xla")
    float(fn1(x))
    float(fnK(x))
    per_ms, t1 = ct.median_pair_diff_ms(fn1, fnK, x, 33, repeats=2, inner=2)
    assert per_ms > 0
    assert t1 > 0


def test_k_guard():
    with pytest.raises(ValueError, match="k must be >= 2"):
        ct.median_pair_diff_ms(None, None, None, 1, 1, 1)


class TestDirectionalChain:
    """On-device-input chains (forward / inverse / roundtrip) — how
    north-star sizes and the C2R-only rows are timed without host
    staging."""

    def test_forward_accumulates_serially(self):
        fn1 = ct.directional_chain(1, (16, 16, 16), "matmul", "forward")
        fn5 = ct.directional_chain(5, (16, 16, 16), "matmul", "forward")
        a, b = float(fn1(0)), float(fn5(0))
        # acc grows by ~the same mean-value term per iteration: 5x the
        # 1-chain value up to the 1e-30 perturbation
        assert abs(b - 5 * a) < 1e-3 * abs(b)

    def test_inverse_matches_input_mean(self):
        import numpy as np
        fn1 = ct.directional_chain(1, (16, 16, 16), "xla", "inverse")
        # irfftn(rfftn(u))[0,0,0]/N = u[0,0,0]; one iteration accumulates
        # that single value (bounded, seed-deterministic)
        v = float(fn1(3))
        assert np.isfinite(v) and 0.0 <= v <= 1.0

    def test_roundtrip_direction_matches_external_input_chain(self, rng):
        import jax
        import jax.numpy as jnp
        import numpy as np
        shape = (8, 8, 8)
        internal = float(ct.directional_chain(2, shape, "matmul",
                                              "roundtrip")(5))
        u = np.asarray(jax.jit(lambda: jax.random.uniform(
            jax.random.key(5), shape, jnp.float32))())
        external = float(ct.roundtrip_chain(2, shape, "matmul")(
            jax.device_put(u)))
        assert abs(internal - external) / abs(external) < 1e-5

    def test_bad_direction_rejected(self):
        import pytest as pt
        with pt.raises(ValueError, match="direction"):
            ct.directional_chain(2, (8, 8, 8), "xla", "sideways")


def test_stage_chain_all_stages_run():
    """Each per-axis stage chain compiles and accumulates serially (the
    512^3 per-stage breakdown tool)."""
    import numpy as np
    for stage in ct.STAGES:
        fn1 = ct.stage_chain(1, (8, 8, 8), "matmul", stage)
        fn3 = ct.stage_chain(3, (8, 8, 8), "matmul", stage)
        a, b = float(fn1(0)), float(fn3(0))
        assert np.isfinite(a) and np.isfinite(b), stage
        assert abs(b) >= abs(a) or a == b == 0.0, stage
    import pytest as pt
    with pt.raises(ValueError, match="stage"):
        ct.stage_chain(2, (8, 8, 8), "xla", "fft_w")


def test_direct_max_override_changes_factorization(rng):
    """MXUSettings.direct_max forces four-step on lengths that would run
    direct — the 512-direct vs four-step comparison knob — without
    changing results."""
    import jax
    import numpy as np
    from distributedfft_tpu.ops import fft as lf
    from distributedfft_tpu.ops.mxu_fft import MXUSettings
    x = rng.random((4, 256)).astype(np.float32)
    cx = x.astype(np.complex64)
    st = MXUSettings.make(direct_max=128)  # 256 -> 16x16 four-step
    j_direct = str(jax.make_jaxpr(
        lambda a: lf.fft(a, axis=-1, backend="matmul"))(cx))
    j_split = str(jax.make_jaxpr(
        lambda a: lf.fft(a, axis=-1, backend="matmul", settings=st))(cx))
    assert j_direct != j_split
    a = np.asarray(lf.fft(cx, axis=-1, backend="matmul"))
    b = np.asarray(lf.fft(cx, axis=-1, backend="matmul", settings=st))
    ref = np.fft.fft(x, axis=-1)
    denom = np.abs(ref).max()
    assert np.abs(a - ref).max() / denom < 1e-4
    assert np.abs(b - ref).max() / denom < 1e-4


def test_chunked_forward_chain_accumulates():
    """The chunked-plan forward chain (bench.py's last HBM rung at the
    north-star cube) follows the same serial-accumulator contract as
    directional_chain: k scales the accumulated scalar and the underlying
    chunked transform matches numpy."""
    import numpy as np
    a1 = float(ct.chunked_forward_chain(1, 32, chunk=4)(0))
    a5 = float(ct.chunked_forward_chain(5, 32, chunk=4)(0))
    assert np.isfinite(a1) and np.isfinite(a5)
    # The accumulator adds ~the same mean-derived term per iteration.
    assert abs(a5 - 5 * a1) < 5e-3 * max(1.0, abs(a1) * 5)
