"""The program's least-work model (``obs/profile.least_work``) against the
benchmark's (``benchmark/work.graph_work``), which it must equal: both
count the same declared plan graph, and the program keeps its own copy
so that it never imports the benchmark. The benchmark's module is only
read here, as the reference."""

import json
import math
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import distributedfft_tpu as dfft  # noqa: E402
from distributedfft_tpu.analysis import plangraph  # noqa: E402
from distributedfft_tpu.obs import profile  # noqa: E402

from benchmark import work as bwork  # noqa: E402

V5E = "TPU v5 lite"


def _slab(sequence, n=16, p=4, double=False):
    return dfft.SlabFFTPlan(dfft.GlobalSize(n, n, n), dfft.SlabPartition(p),
                            dfft.Config(double_prec=double),
                            sequence=sequence)


def _pencil(double=False):
    return dfft.PencilFFTPlan(dfft.GlobalSize(16, 16, 16),
                              dfft.PencilPartition(2, 2),
                              dfft.Config(double_prec=double))


def _batched(double=False, batch=8, n=16, p=4, shard="batch"):
    return dfft.Batched2DFFTPlan(batch, n, n, dfft.SlabPartition(p),
                                 dfft.Config(double_prec=double),
                                 shard=shard, transform="r2c")


PLANS = {
    "slab_zy_then_x": (lambda d: _slab("ZY_Then_X", double=d), 3, 4),
    "slab_z_then_yx": (lambda d: _slab("Z_Then_YX", double=d), 3, 4),
    "slab_y_then_zx": (lambda d: _slab("Y_Then_ZX", double=d), 3, 4),
    "pencil_2x2": (lambda d: _pencil(double=d), 3, 4),
    # Sharded along x, so the stack's exchange is counted too.
    "batched2d": (lambda d: _batched(double=d, shard="x"), 2, 4),
}


def _pair(plan, direction, dims, chips):
    graph = plangraph.graph_for(plan, direction, dims)
    shape = tuple(plan.global_size.shape)
    return (profile.least_work(graph, shape, chips),
            bwork.graph_work(graph, shape, chips))


@pytest.mark.parametrize("double", [False, True], ids=["f32", "f64"])
@pytest.mark.parametrize("direction", ["forward", "inverse"])
@pytest.mark.parametrize("name", sorted(PLANS))
def test_least_work_equals_the_benchmarks(devices, name, direction, double):
    make, dims, chips = PLANS[name]
    mine, ref = _pair(make(double), direction, dims, chips)
    assert (mine.flops, mine.hbm_bytes, mine.ici_bytes) \
        == (ref.fft_flops, ref.fft_hbm_bytes, ref.ici_bytes)
    assert mine.flops > 0 and mine.hbm_bytes > 0
    pk = bwork.peaks(V5E)
    assert mine.least_s(profile.PEAKS[V5E]) == ref.step_s(pk)


def _roundtrip(plan, dims, chips):
    works = [_pair(plan, d, dims, chips) for d in ("forward", "inverse")]
    for mine, ref in works:
        assert (mine.flops, mine.hbm_bytes, mine.ici_bytes) \
            == (ref.fft_flops, ref.fft_hbm_bytes, ref.ici_bytes)
    return works[0][0] + works[1][0]


def test_slab1024_over_4_least_time_closed_form(devices):
    """The ``slab1024x4.roundtrip`` cell's shape; the plan is built, never
    run, so nothing of 1024^3 is allocated."""
    w = _roundtrip(_slab("ZY_Then_X", n=1024, p=4), 3, 4)
    real = 4 * 1024 ** 3                   # 4,294,967,296 B
    spec = 8 * 1024 * 1024 * 513           # 4,303,355,904 B
    # Per direction: two local-FFT stages, one real side and three
    # spectral sides; each chip sends 3/4 of its quarter of the spectrum.
    assert w.hbm_bytes == 2 * (real + 3 * spec) / 4
    assert w.ici_bytes == 2 * spec / 4 * 3 / 4
    assert w.flops == 2 * 2.5 * 1024 ** 3 * 30 / 4
    # HBM bytes bound it: 8,602,517,504 B at 819 GB/s.
    assert w.least_s(profile.PEAKS[V5E]) == 8_602_517_504 / 819e9
    assert w.least_s(profile.PEAKS[V5E]) == pytest.approx(10.5037e-3,
                                                         rel=1e-5)


def test_batched2d_48x4096_on_one_least_time_closed_form(devices):
    """The ``batched2d4096.roundtrip`` cell's shape on one device: one
    collective-free 2D FFT node each way."""
    w = _roundtrip(_batched(batch=48, n=4096, p=1), 2, 1)
    n = 48 * 4096 * 4096
    real = 4 * n                           # 3,221,225,472 B
    spec = 8 * 48 * 4096 * 2049            # 3,222,798,336 B
    assert w.hbm_bytes == 2 * (real + spec)
    assert w.ici_bytes == 0
    assert w.flops == 2 * 2.5 * n * 2 * math.log2(4096)
    assert w.least_s(profile.PEAKS[V5E]) == 12_888_047_616 / 819e9
    assert w.least_s(profile.PEAKS[V5E]) == pytest.approx(15.7363e-3,
                                                         rel=1e-5)


def test_peaks_equal_the_benchmarks():
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        table = json.load(f)
    assert sorted(profile.PEAKS) == sorted(table)
    for kind, peaks in profile.PEAKS.items():
        assert peaks == {k: table[kind][k] for k in peaks}, kind
        assert set(peaks) == {"bf16_flop_per_s", "hbm_bytes_per_s",
                              "ici_bytes_per_s"}
