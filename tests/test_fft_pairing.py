"""The R2C along the last axis as one complex FFT per pair of rows
(``ops/fft.rfft(..., paired=True)``, ``_paired_rfft``), and the C2R as
one complex inverse per pair of rows (``ops/fft.irfft(..., paired=True)``,
``_paired_irfft``).

A plan selects them on a TPU only (``plan.created``'s ``r2c_paired`` and
``c2r_paired``), so here the paired functions are called directly, and
plans are built with the platform they read steered to "tpu".
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from distributedfft_tpu import (Config, GlobalSize, PencilPartition,
                                SlabPartition, obs)
from distributedfft_tpu.models.batched2d import Batched2DFFTPlan
from distributedfft_tpu.models.pencil import PencilFFTPlan
from distributedfft_tpu.models.slab import SlabFFTPlan
from distributedfft_tpu.ops import fft as lf
from distributedfft_tpu.params import FFTNorm
from distributedfft_tpu.utils import wisdom

XLA = Config(fft_backend="xla")


def _rel_l2(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _paired(x, norm=FFTNorm.NONE):
    return np.asarray(lf.rfft(jnp.asarray(x), axis=-1, norm=norm,
                              paired=True))


def _text(fn, shape, dtype=jnp.float32):
    return jax.jit(fn).lower(jax.ShapeDtypeStruct(shape, dtype)).as_text()


@pytest.mark.parametrize("norm", list(FFTNorm), ids=lambda n: n.name)
@pytest.mark.parametrize("rows", [2, 6, 4096])
@pytest.mark.parametrize("n", [8, 64, 4096])
def test_matches_numpy(rng, n, rows, norm):
    """Agreement with float64 NumPy to float32 rounding."""
    x = rng.uniform(-1.0, 1.0, (rows, n)).astype(np.float32)
    ref = np.fft.rfft(x.astype(np.float64), axis=-1,
                      norm=lf._fwd_norm(norm))
    got = _paired(x, norm)
    assert got.dtype == np.complex64 and got.shape == ref.shape
    assert _rel_l2(got, ref) < 1e-6


@pytest.mark.parametrize("lead,n", [((2, 8), 16), ((1, 64), 64)])
def test_leading_shapes(rng, lead, n):
    """Row j pairs with row j + R/2 of the flattened leading axes; the
    answer keeps the leading shape."""
    x = rng.uniform(-1.0, 1.0, lead + (n,)).astype(np.float32)
    got = _paired(x)
    assert got.shape == lead + (n // 2 + 1,)
    assert _rel_l2(got, np.fft.rfft(x.astype(np.float64), axis=-1)) < 1e-6


@pytest.mark.parametrize("n", [8, 64, 4096])
def test_dc_and_nyquist_exactly_real(rng, n):
    x = rng.uniform(-1.0, 1.0, (6, n)).astype(np.float32)
    got = _paired(x, FFTNorm.ORTHO)
    assert not np.any(got[:, 0].imag) and not np.any(got[:, -1].imag)


def test_counts_each_traced_pairing(rng):
    obs.metrics.reset()
    x = jnp.asarray(rng.uniform(-1.0, 1.0, (4, 8)).astype(np.float32))
    lf.rfft(x, axis=-1, paired=True)
    lf.rfft(x, axis=-1)
    lf.rfft(x[:3], axis=-1, paired=True)  # odd row count: not paired
    assert obs.snapshot()["counters"].get("local_fft.r2c_paired") == 1


def test_lowered_program_has_no_reverse():
    """A reverse op reading an FFT's output is what the v5e got wrong
    (``_reversed_last``); the split reads Z through a gather."""
    txt = _text(lambda v: lf.rfft(v, axis=2, paired=True), (2, 8, 64))
    assert "stablehlo.gather" in txt
    assert "reverse" not in txt


@pytest.mark.parametrize("case", ["f64", "odd_extent", "odd_rows",
                                  "not_last", "matmul"])
def test_unpaired_inputs_lower_as_before(case):
    """Where pairing does not apply, ``paired=True`` lowers to the same
    program as ``paired=False``, which is ``jnp.fft.rfft`` for xla."""
    shape, dtype, axis, be = (4, 8, 16), jnp.float32, 2, "xla"
    if case == "f64":
        dtype = jnp.float64
    elif case == "odd_extent":
        shape = (4, 8, 15)
    elif case == "odd_rows":
        shape = (3, 5, 16)
    elif case == "not_last":
        axis = 1
    else:
        be = "matmul"
    paired = _text(lambda v: lf.rfft(v, axis=axis, backend=be, paired=True),
                   shape, dtype)
    assert paired == _text(lambda v: lf.rfft(v, axis=axis, backend=be),
                           shape, dtype)
    if be == "xla":
        assert paired == _text(lambda v: jnp.fft.rfft(
            v, axis=axis, norm="backward"), shape, dtype)


@pytest.fixture
def small_cut(monkeypatch):
    """Let plans of a few points pair, so that their programs run here."""
    monkeypatch.setattr(lf, "PAIRED_MIN_EXTENT", 2)
    monkeypatch.setattr(lf, "PAIRED_C2R_MIN_EXTENT", 2)
    monkeypatch.setattr(lf, "PAIRED_C2R_MIN_POINTS", 0)


def _tpu(monkeypatch, cls, *args, **kw):
    """A plan built as on a TPU, running here on the CPU."""
    with monkeypatch.context() as m:
        m.setattr(wisdom, "plan_platform", lambda mesh=None: "tpu")
        return cls(*args, **kw)


def _batched(monkeypatch, batch, nx, ny, config=XLA, **kw):
    return _tpu(monkeypatch, Batched2DFFTPlan, batch, nx, ny,
                SlabPartition(1), config, **kw)


def _decision_plan(monkeypatch, case):
    """A full-size batched plan (nothing traced) for one decision case."""
    if case == "tpu":
        return _batched(monkeypatch, 2, 2048, 2048)
    if case == "tpu_chunked":
        return _batched(monkeypatch, 48, 4096, 4096, batch_chunk=1)
    if case == "whole_stack":
        return _batched(monkeypatch, 48, 4096, 4096)
    if case == "small_extent":
        return _batched(monkeypatch, 8, 16, 16)
    if case == "odd_extent":
        return _batched(monkeypatch, 2, 2048, 2049)
    if case == "odd_rows":
        return _batched(monkeypatch, 1, 2049, 2048)
    if case == "float64":
        return _batched(monkeypatch, 2, 2048, 2048,
                        Config(fft_backend="xla", double_prec=True))
    if case == "cpu":
        return Batched2DFFTPlan(2, 2048, 2048, SlabPartition(1), XLA)
    if case == "matmul":
        return _batched(monkeypatch, 2, 2048, 2048,
                        Config(fft_backend="matmul"))
    if case == "extent_1024":
        return _batched(monkeypatch, 8, 1024, 1024)
    if case == "four_images":
        return _batched(monkeypatch, 4, 4096, 4096)
    if case == "small_call":
        return _batched(monkeypatch, 2, 256, 2048)
    return _batched(monkeypatch, 2, 2048, 2048, transform="c2c")


# (case, r2c_paired, c2r_paired): the two directions' windows differ in
# their least extent (2048, 1024) and in the points a call (at most
# 2^25; 2^22 to 2^26).
_DECISIONS = [
    ("tpu", True, True),
    ("tpu_chunked", True, True),
    ("whole_stack", False, False),
    ("small_extent", False, False),
    ("odd_extent", False, False),
    ("odd_rows", False, False),
    ("float64", False, False),
    ("cpu", False, False),
    ("matmul", False, False),
    ("c2c", False, False),
    ("extent_1024", False, True),
    ("four_images", False, True),
    ("small_call", True, False),
]


@pytest.mark.parametrize("case,want,_", _DECISIONS)
def test_batched_plan_decides_once(monkeypatch, case, want, _):
    """The batched plan pairs on a TPU, float32, xla, an even extent of
    at least ``PAIRED_MIN_EXTENT`` and an even local row count, with at
    most ``PAIRED_MAX_POINTS`` a call, and says so on ``plan.created``.
    Nothing is traced: the plans are built at full size."""
    obs.flightrec.clear()
    plan = _decision_plan(monkeypatch, case)
    assert plan.r2c_paired is want
    got = [r["attrs"]["r2c_paired"] for r in obs.flightrec.snapshot()
           if r["name"] == "plan.created"]
    assert got == [want]


@pytest.mark.parametrize("case,_,want", _DECISIONS)
def test_batched_plan_decides_c2r_once(monkeypatch, case, _, want):
    """``pairs_rows(..., inverse=True)`` decides the plan's
    ``c2r_paired`` on the same observed inputs, in the C2R's window: an
    even extent of at least ``PAIRED_C2R_MIN_EXTENT`` and from
    ``PAIRED_C2R_MIN_POINTS`` to ``PAIRED_C2R_MAX_POINTS`` a call; and
    ``plan.created`` carries it."""
    obs.flightrec.clear()
    plan = _decision_plan(monkeypatch, case)
    assert plan.c2r_paired is want
    got = [r["attrs"]["c2r_paired"] for r in obs.flightrec.snapshot()
           if r["name"] == "plan.created"]
    assert got == [want]


@pytest.mark.parametrize("batch_chunk", [None, 1])
def test_batched_plan_paired_answers(monkeypatch, rng, small_cut,
                                     batch_chunk):
    """The paired plan's forward matches float64 NumPy and its inverse
    returns the input, chunked (groups of 8) or whole."""
    plan = _batched(monkeypatch, 8, 16, 16, batch_chunk=batch_chunk)
    assert plan.r2c_paired
    obs.metrics.reset()
    x = rng.uniform(-1.0, 1.0, (8, 16, 16)).astype(np.float32)
    c = plan.exec_forward(x)
    assert obs.snapshot()["counters"].get("local_fft.r2c_paired", 0) >= 1
    assert _rel_l2(plan.crop_spectral(c),
                   np.fft.rfft2(x.astype(np.float64))) < 1e-6
    assert _rel_l2(plan.crop_real(plan.exec_inverse(c)), x * 256) < 1e-6


@pytest.mark.parametrize("family", ["slab", "slab_fft3d_chunked", "pencil"])
def test_3d_plans_pair_and_match(devices, monkeypatch, rng, small_cut,
                                 family):
    """Slab and pencil plans pair their R2C along z too, with the same
    answers as the CPU's plan."""
    g = GlobalSize(16, 16, 16)
    if family == "slab":
        mk = lambda: SlabFFTPlan(g, SlabPartition(8), XLA)  # noqa: E731
    elif family == "slab_fft3d_chunked":
        cfg = Config(fft_backend="xla", fft3d_chunk=2)
        mk = lambda: SlabFFTPlan(g, SlabPartition(1), cfg)  # noqa: E731
    else:
        mk = lambda: PencilFFTPlan(g, PencilPartition(2, 4), XLA)  # noqa: E731
    with monkeypatch.context() as m:
        m.setattr(wisdom, "plan_platform", lambda mesh=None: "tpu")
        paired = mk()
    base = mk()
    assert paired.r2c_paired and not base.r2c_paired
    x = rng.uniform(-1.0, 1.0, g.shape).astype(np.float32)
    got = paired.crop_spectral(paired.exec_r2c(x))
    np.testing.assert_allclose(got, base.crop_spectral(base.exec_r2c(x)),
                               rtol=1e-5, atol=2e-5)
    assert _rel_l2(got, np.fft.rfftn(x.astype(np.float64))) < 1e-6


@pytest.mark.parametrize("plan_kw,want", [
    ({"sequence": "Y_Then_ZX"}, False),   # the R2C runs along y
    ({}, True),
])
def test_slab_pairs_only_along_the_last_axis(devices, monkeypatch, small_cut,
                                            plan_kw, want):
    plan = _tpu(monkeypatch, SlabFFTPlan, GlobalSize(16, 16, 16),
                SlabPartition(8), XLA, **plan_kw)
    assert plan.r2c_paired is want


def test_slab_fft3d_unchunked_is_not_paired(monkeypatch, small_cut):
    """The one-device unchunked slab runs ``jnp.fft.rfftn`` over three
    axes, not ``rfft`` along the last."""
    plan = _tpu(monkeypatch, SlabFFTPlan, GlobalSize(16, 16, 16),
                SlabPartition(1), XLA)
    assert not plan.r2c_paired


def _shard2(fn):
    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=PartitionSpec("d"),
                                 out_specs=PartitionSpec("d")))


@pytest.mark.parametrize("mode", ["jvp", "grad"])
def test_autodiff_inside_shard_map(devices, rng, mode):
    """``jax.jvp`` and ``jax.grad`` through the paired path inside
    ``shard_map`` over 2 devices match the unpaired path."""
    x = jnp.asarray(rng.uniform(-1.0, 1.0, (4, 6, 16)).astype(np.float32))
    t = jnp.asarray(rng.uniform(-1.0, 1.0, (4, 6, 16)).astype(np.float32))
    w = jnp.asarray((rng.uniform(-1.0, 1.0, (4, 6, 9))
                     + 1j * rng.uniform(-1.0, 1.0, (4, 6, 9)))
                    .astype(np.complex64))
    out = {}
    for paired in (False, True):
        f = _shard2(lambda v, p=paired: lf.rfft(v, axis=2, paired=p))
        if mode == "jvp":
            out[paired] = jax.jvp(f, (x,), (t,))
        else:
            out[paired] = (jax.grad(
                lambda v: jnp.sum(jnp.real(f(v) * w)))(x),)
    for a, b in zip(out[True], out[False]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


# -- the C2R ---------------------------------------------------------------


def _spectrum(rng, shape):
    return (rng.uniform(-1.0, 1.0, shape)
            + 1j * rng.uniform(-1.0, 1.0, shape)).astype(np.complex64)


def _paired_inv(c, n, norm=FFTNorm.NONE):
    return np.asarray(lf.irfft(jnp.asarray(c), n=n, axis=-1, norm=norm,
                               paired=True))


@pytest.mark.parametrize("norm", list(FFTNorm), ids=lambda n: n.name)
@pytest.mark.parametrize("rows", [2, 6, 4096])
@pytest.mark.parametrize("n", [8, 64, 4096])
def test_c2r_matches_numpy(rng, n, rows, norm):
    """Agreement with float64 NumPy's ``irfft`` to float32 rounding."""
    c = _spectrum(rng, (rows, n // 2 + 1))
    ref = np.fft.irfft(c.astype(np.complex128), n=n, axis=-1,
                       norm=lf._inv_norm(norm))
    got = _paired_inv(c, n, norm)
    assert got.dtype == np.float32 and got.shape == ref.shape
    assert _rel_l2(got, ref) < 1e-6


@pytest.mark.parametrize("width", [3, 5, 9, 12, 20])
def test_c2r_crops_or_pads_the_spectral_axis(rng, width):
    """A spectral axis longer than n/2 + 1 is cropped, a shorter one
    zero-padded, as NumPy does (n = 16, so 9 is exact)."""
    c = _spectrum(rng, (4, width))
    ref = np.fft.irfft(c.astype(np.complex128), n=16, axis=-1)
    got = _paired_inv(c, 16, FFTNorm.BACKWARD)
    assert got.shape == (4, 16)
    assert _rel_l2(got, ref) < 1e-6


@pytest.mark.parametrize("n", [8, 64, 4096])
def test_c2r_ignores_dc_and_nyquist_imaginary_parts(rng, n):
    c = _spectrum(rng, (6, n // 2 + 1))
    real_ends = c.copy()
    real_ends[:, 0] = real_ends[:, 0].real
    real_ends[:, -1] = real_ends[:, -1].real
    got = _paired_inv(c, n)
    np.testing.assert_array_equal(got, _paired_inv(real_ends, n))
    ref = np.fft.irfft(c.astype(np.complex128), n=n, axis=-1, norm="forward")
    assert _rel_l2(got, ref) < 1e-6


@pytest.mark.parametrize("lead,n", [((2, 8), 16), ((1, 64), 64),
                                    ((4, 6), 4096)])
def test_paired_roundtrip(rng, lead, n):
    """Paired R2C then paired C2R returns n times the input (FFTNorm.NONE
    is unnormalised both ways), in the input's leading shape."""
    x = rng.uniform(-1.0, 1.0, lead + (n,)).astype(np.float32)
    c = lf.rfft(jnp.asarray(x), axis=-1, paired=True)
    got = np.asarray(lf.irfft(c, n=n, axis=-1, paired=True))
    assert got.shape == x.shape
    assert _rel_l2(got, x * n) < 1e-6


def test_c2r_counts_each_traced_pairing(rng):
    obs.metrics.reset()
    c = jnp.asarray(_spectrum(rng, (4, 5)))
    lf.irfft(c, n=8, axis=-1, paired=True)
    lf.irfft(c, n=8, axis=-1)
    lf.irfft(c[:3], n=8, axis=-1, paired=True)  # odd row count
    lf.irfft(c, n=9, axis=-1, paired=True)      # odd extent
    assert obs.snapshot()["counters"].get("local_fft.c2r_paired") == 1


def test_c2r_lowered_program_has_no_reverse():
    """Z's tail reads the spectrum through a gather, not a reverse op
    (``_reversed_last``), and the rows come back stacked along a leading
    axis: no lane interleave of real and imaginary parts."""
    txt = _text(lambda v: lf.irfft(v, n=64, axis=2, paired=True),
                (2, 8, 33), jnp.complex64)
    assert "stablehlo.gather" in txt
    assert "reverse" not in txt
    assert "tensor<2x8x32x2xf32>" not in txt


@pytest.mark.parametrize("case", ["c128", "odd_extent", "odd_rows",
                                  "not_last", "matmul"])
def test_c2r_unpaired_inputs_lower_as_before(case):
    """Where pairing does not apply, ``paired=True`` lowers to the same
    program as ``paired=False``, which is ``xla_irfft`` for xla."""
    shape, n, dtype, axis, be = (4, 8, 9), 16, jnp.complex64, 2, "xla"
    if case == "c128":
        dtype = jnp.complex128
    elif case == "odd_extent":
        n = 17
    elif case == "odd_rows":
        shape = (3, 5, 9)
    elif case == "not_last":
        shape, n, axis = (4, 9, 8), 16, 1
    else:
        be = "matmul"
    paired = _text(lambda v: lf.irfft(v, n=n, axis=axis, backend=be,
                                      paired=True), shape, dtype)
    assert paired == _text(lambda v: lf.irfft(v, n=n, axis=axis, backend=be),
                           shape, dtype)
    if be == "xla":
        assert paired == _text(lambda v: lf.xla_irfft(
            v, n, axis, "forward"), shape, dtype)


@pytest.mark.parametrize("batch_chunk", [None, 1])
def test_batched_plan_paired_c2r_answers(monkeypatch, rng, small_cut,
                                         batch_chunk):
    """The paired plan's inverse matches float64 NumPy, chunked or
    whole, and counts its paired C2R."""
    plan = _batched(monkeypatch, 8, 16, 16, batch_chunk=batch_chunk)
    assert plan.c2r_paired
    c = _spectrum(rng, (8, 16, 9))
    obs.metrics.reset()
    got = plan.crop_real(plan.exec_inverse(plan.pad_spectral(c)))
    assert obs.snapshot()["counters"].get("local_fft.c2r_paired", 0) >= 1
    ref = np.fft.irfft2(c.astype(np.complex128), s=(16, 16), norm="forward")
    assert _rel_l2(got, ref) < 1e-6


@pytest.mark.parametrize("family", ["slab", "slab_fft3d_chunked", "pencil"])
def test_3d_plans_pair_c2r_and_match(devices, monkeypatch, rng, small_cut,
                                     family):
    """Slab and pencil plans pair their C2R along z too, with the same
    answers as the CPU's plan and NumPy's."""
    g = GlobalSize(16, 16, 16)
    if family == "slab":
        mk = lambda: SlabFFTPlan(g, SlabPartition(8), XLA)  # noqa: E731
    elif family == "slab_fft3d_chunked":
        cfg = Config(fft_backend="xla", fft3d_chunk=2)
        mk = lambda: SlabFFTPlan(g, SlabPartition(1), cfg)  # noqa: E731
    else:
        mk = lambda: PencilFFTPlan(g, PencilPartition(2, 4), XLA)  # noqa: E731
    with monkeypatch.context() as m:
        m.setattr(wisdom, "plan_platform", lambda mesh=None: "tpu")
        paired = mk()
    base = mk()
    assert paired.c2r_paired and not base.c2r_paired
    x = rng.uniform(-1.0, 1.0, g.shape).astype(np.float32)
    c = base.exec_r2c(x)
    obs.metrics.reset()
    got = paired.crop_real(paired.exec_c2r(c))
    assert obs.snapshot()["counters"].get("local_fft.c2r_paired", 0) >= 1
    np.testing.assert_allclose(got, base.crop_real(base.exec_c2r(c)),
                               rtol=1e-5, atol=2e-3)
    assert _rel_l2(got, x * g.nx * g.ny * g.nz) < 1e-6


@pytest.mark.parametrize("mode", ["jvp", "grad"])
def test_c2r_autodiff_inside_shard_map(devices, rng, mode):
    """``jax.jvp`` and ``jax.grad`` through the paired C2R inside
    ``shard_map`` over 2 devices match the unpaired path."""
    c = jnp.asarray(_spectrum(rng, (4, 6, 9)))
    t = jnp.asarray(_spectrum(rng, (4, 6, 9)))
    w = jnp.asarray(rng.uniform(-1.0, 1.0, (4, 6, 16)).astype(np.float32))
    out = {}
    for paired in (False, True):
        f = _shard2(lambda v, p=paired: lf.irfft(v, n=16, axis=2, paired=p))
        if mode == "jvp":
            out[paired] = jax.jvp(f, (c,), (t,))
        else:
            out[paired] = (jax.grad(lambda v: jnp.sum(f(v) * w))(c),)
    for a, b in zip(out[True], out[False]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-4)
