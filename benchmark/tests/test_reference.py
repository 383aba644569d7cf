"""The plane-wise float64 reference agrees with numpy's whole-cube FFT."""

import numpy as np
import pytest

from benchmark import load, reference

SHAPE = (16, 12, 10)
PLANES = [0, 3, 7, 15]


def test_rfftn_planes_match_numpy():
    x = np.random.default_rng(1).uniform(-1, 1, SHAPE).astype(np.float32)
    got = reference.rfftn_planes(x, PLANES)
    want = np.fft.rfftn(x.astype(np.float64))[PLANES]
    assert np.abs(got - want).max() < 1e-12 * np.abs(want).max()


def test_irfftn_planes_match_numpy_unnormalised():
    c = np.fft.rfftn(np.random.default_rng(2).uniform(-1, 1, SHAPE))
    got = reference.irfftn_planes(c.astype(np.complex64), PLANES, SHAPE[-1])
    want = np.fft.irfftn(c.astype(np.complex64).astype(np.complex128),
                         SHAPE, axes=(0, 1, 2)) * np.prod(SHAPE)
    assert np.abs(got - want[PLANES]).max() < 1e-9 * np.abs(want).max()


def test_rel_l2():
    a = np.ones((4, 4))
    assert reference.rel_l2(a, a) == 0
    assert reference.rel_l2(a * 1.5, a) == pytest.approx(0.5)
    assert reference.rel_l2(a[:2], a) == float("inf")


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, 2 ** 40 + 3])
def test_planes_are_drawn_from_any_seed(seed):
    p = load.sample_planes(seed, 1024, 16)
    assert p == load.sample_planes(seed, 1024, 16)
    assert len(set(p)) == 16 and all(0 <= i < 1024 for i in p)
    assert load.sample_planes(seed, 8, 16) == list(range(8))
