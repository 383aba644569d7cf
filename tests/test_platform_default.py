"""The local-FFT backend a Config that names none resolves to.

``utils/wisdom.platform_default`` reads the platform (the mesh devices',
else JAX's default backend), the dtype and the transformed extents. The
platform is injected here through a stand-in mesh, so the TPU branch of
the rule runs on the CPU.
"""

import types

import numpy as np
import pytest

import distributedfft_tpu as dfft
from distributedfft_tpu import obs
from distributedfft_tpu.ops import mxu_fft
from distributedfft_tpu.params import FFTNorm
from distributedfft_tpu.utils import wisdom


class _Mesh:
    """What the rule and the wisdom key read of a mesh: its devices'
    platform and its axis sizes."""

    def __init__(self, platform):
        self.devices = np.array([types.SimpleNamespace(platform=platform)],
                                dtype=object)
        self.shape = {"p": 1}


CUBE = (1024, 1024, 1024)
DM = mxu_fft.direct_max_for(1024)

# name: (platform, Config fields, global shape, dims, expected fields)
CASES = {
    "tpu_f32_smooth": ("tpu", {}, CUBE, 3,
                       {"fft_backend": "matmul", "mxu_precision": "highest",
                        "mxu_direct_max": DM}),
    "tpu_largest_extent_keys_the_table": (
        "tpu", {}, (128, 256, 2048), 3,
        {"fft_backend": "matmul", "mxu_precision": "highest",
         "mxu_direct_max": mxu_fft.direct_max_for(2048)}),
    "tpu_extent_past_the_table": ("tpu", {}, (64, 4096, 4096), 2,
                                  {"fft_backend": "xla",
                                   "mxu_direct_max": None}),
    "tpu_batch_slot_not_an_extent": (
        "tpu", {}, (7, 512, 512), 2,
        {"fft_backend": "matmul",
         "mxu_direct_max": mxu_fft.direct_max_for(512)}),
    "tpu_explicit_precision_kept": (
        "tpu", {"mxu_precision": "high"}, CUBE, 3,
        {"fft_backend": "matmul", "mxu_precision": "high",
         "mxu_direct_max": DM}),
    "tpu_explicit_direct_max_kept": (
        "tpu", {"mxu_direct_max": 512}, CUBE, 3,
        {"fft_backend": "matmul", "mxu_precision": "highest",
         "mxu_direct_max": 512}),
    "cpu": ("cpu", {}, CUBE, 3,
            {"fft_backend": "xla", "mxu_precision": None,
             "mxu_direct_max": None}),
    "gpu": ("gpu", {}, CUBE, 3, {"fft_backend": "xla"}),
    "tpu_double_prec": ("tpu", {"double_prec": True}, CUBE, 3,
                        {"fft_backend": "xla", "mxu_precision": None}),
    "tpu_nonsmooth_axis": ("tpu", {}, (1024, 1024, 1023), 3,
                           {"fft_backend": "xla", "mxu_direct_max": None}),
    "tpu_explicit_xla": ("tpu", {"fft_backend": "xla"}, CUBE, 3,
                         {"fft_backend": "xla", "mxu_precision": None}),
    "tpu_explicit_pallas": ("tpu", {"fft_backend": "pallas"}, CUBE, 3,
                            {"fft_backend": "pallas", "mxu_precision": None,
                             "mxu_direct_max": None}),
    "tpu_explicit_matmul": ("tpu", {"fft_backend": "matmul"}, CUBE, 3,
                            {"fft_backend": "matmul", "mxu_precision": None,
                             "mxu_direct_max": None}),
}


def _counts():
    snap = obs.metrics.snapshot("cumulative")["counters"]
    return {k: v for k, v in snap.items()
            if k.startswith("plan.backend_default")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_platform_default_rule(case):
    """Each case: what construction resolves to, that ``peek_config``
    (``dfft-explain``) folds the same, and that the rule counts exactly
    when it chose the backend, labelled with what it chose."""
    platform, fields, shape, dims, want = CASES[case]
    kind = "batched2d" if dims == 2 else "slab"
    g = dfft.GlobalSize(*shape)
    cfg = dfft.Config(use_wisdom=False, **fields)
    before = _counts()
    got = wisdom.resolve_config(kind, g, dfft.SlabPartition(1), cfg,
                                mesh=_Mesh(platform), dims=dims)
    after = _counts()
    for k, v in want.items():
        assert getattr(got, k) == v, (k, getattr(got, k))
    peeked, prov = wisdom.peek_config(kind, g, dfft.SlabPartition(1), cfg,
                                      mesh=_Mesh(platform), dims=dims)
    assert peeked == got
    grew = {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}
    if "fft_backend" in fields:
        assert got.fft_backend == fields["fft_backend"]
        assert grew == {} and prov["backend_default"] is None
    else:
        label = obs.metrics.labeled(
            "plan.backend_default", backend=got.fft_backend,
            precision=got.mxu_precision, direct_max=got.mxu_direct_max)
        assert grew == {label: 1}
        assert prov["backend_default"].startswith(
            f"platform default: {platform}")


def test_auto_is_not_the_platform_rule():
    """``"auto"`` still goes to the wisdom race (here lookup-only), never
    to the platform rule."""
    cfg = dfft.Config(fft_backend="auto", use_wisdom=False)
    got, prov = wisdom.peek_config("slab", dfft.GlobalSize(*CUBE),
                                   dfft.SlabPartition(1), cfg,
                                   mesh=_Mesh("tpu"))
    assert prov["backend_default"] is None
    assert prov["slots"]["local_fft"]["status"] == "miss"
    assert got.fft_backend == "xla"


def test_default_plan_on_cpu_counts_xla_once(devices):
    """A plan built from ``Config()`` on the CPU is an xla plan, and the
    rule counts it once; the fields its ``plan.created`` event carries
    are the resolved precision and threshold (None for xla)."""
    from distributedfft_tpu.models.base import local_fft_fields
    label = obs.metrics.labeled("plan.backend_default", backend="xla",
                                precision=None, direct_max=None)
    n0 = obs.metrics.counter_total(label)
    mesh = dfft.make_slab_mesh(4, devices)
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(8, 8, 8), dfft.SlabPartition(4),
                            dfft.Config(), mesh=mesh)
    assert plan.config.fft_backend == "xla"
    assert obs.metrics.counter_total(label) == n0 + 1
    assert not wisdom.unresolved(plan.config)
    assert wisdom.unresolved(dfft.Config())
    assert local_fft_fields(plan.config, plan._mxu_st) == {
        "backend": "xla", "precision": None, "direct_max": None}
    mm = dfft.SlabFFTPlan(dfft.GlobalSize(8, 8, 8), dfft.SlabPartition(4),
                          dfft.Config(fft_backend="matmul",
                                      mxu_precision="highest",
                                      mxu_direct_max=4), mesh=mesh)
    assert local_fft_fields(mm.config, mm._mxu_st) == {
        "backend": "matmul", "precision": "highest", "direct_max": 4}


def _rel(a, b):
    return np.max(np.abs(a - b)) / np.max(np.abs(b))


TABLE = sorted({(n, mxu_fft.direct_max_for(n))
                for n in (128, 256, 512, 1024, 2048, 4096)
                if mxu_fft.direct_max_for(n) is not None})


@pytest.mark.parametrize("n,direct_max", TABLE)
def test_table_factor_matches_numpy(n, direct_max, rng):
    """Every (extent, direct_max) the platform default can choose, R2C and
    C2R, in float64 against numpy."""
    st = mxu_fft.MXUSettings.make(precision="highest", direct_max=direct_max)
    x = rng.standard_normal((3, n))
    with mxu_fft.use_settings(st):
        c = np.asarray(mxu_fft.rfft(x, axis=-1))
        assert _rel(c, np.fft.rfft(x, axis=-1)) < 1e-12
        spec = np.fft.rfft(x, axis=-1)
        back = np.asarray(mxu_fft.irfft(spec, n=n, axis=-1,
                                        norm=FFTNorm.BACKWARD))
        assert _rel(back, x) < 1e-12
