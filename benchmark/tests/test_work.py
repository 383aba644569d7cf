"""Least work of a step, checked against hand counts."""

import pytest

import distributedfft_tpu as dfft

from benchmark import harness, work

PK = work.peaks("TPU v5 lite")
R2C, C2R = harness.part("calls", "exec_r2c"), harness.part("calls", "exec_c2r")


def slab(n, p, **cfg):
    return dfft.SlabFFTPlan(dfft.GlobalSize(n, n, n), dfft.SlabPartition(p),
                            dfft.Config(**cfg))


def test_512_forward_by_hand():
    w = work.step_work(slab(512, 1), [R2C], 1)
    n = 512 ** 3
    assert w.fft_flops == pytest.approx(2.5 * n * 27)           # 9.06e9
    assert w.fft_hbm_bytes == 4 * n + 8 * 512 * 512 * 257      # in + out
    assert w.ici_bytes == 0
    # Bytes bound it: 1,075,838,976 B at 819 GB/s = 1.3136 ms; the flop
    # bound is 0.046 ms.
    assert w.local_fft_s(PK) == pytest.approx(1_075_838_976 / 819e9)
    assert w.local_fft_s(PK) == pytest.approx(1.31360e-3, rel=1e-5)
    assert w.step_s(PK) == w.local_fft_s(PK)


def test_1024_over_4_roundtrip_by_hand():
    w = work.step_work(slab(1024, 4), [R2C, C2R], 4)
    real = 4 * 1024 ** 3                 # 4,294,967,296 B
    spec = 8 * 1024 * 1024 * 513         # 4,303,355,904 B
    # Each direction: stage 1 reads and writes, stage 2 reads and writes;
    # one real side and three spectral sides, over four chips.
    per_dir = (real + 3 * spec) / 4
    assert w.fft_hbm_bytes == pytest.approx(2 * per_dir)        # 8.60e9
    assert w.local_fft_s(PK) == pytest.approx(8_602_517_504 / 819e9)
    assert w.local_fft_s(PK) == pytest.approx(10.5037e-3, rel=1e-4)
    # Each chip sends 3/4 of its 1/4 of the spectral payload, twice.
    assert w.ici_bytes == pytest.approx(2 * spec / 4 * 3 / 4)    # 1.61e9
    assert w.fft_flops == pytest.approx(2 * 2.5 * 1024 ** 3 * 30 / 4)
    assert w.step_s(PK) == w.local_fft_s(PK)


@pytest.mark.parametrize("p", [1, 4])
def test_least_time_is_the_same_for_every_backend(p):
    calls = [R2C, C2R]
    n = 512 if p == 1 else 1024
    got = {b: work.step_work(slab(n, p, fft_backend=b), calls, p)
           for b in ("xla", "matmul", "pallas")}
    assert got["xla"] == got["matmul"] == got["pallas"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
