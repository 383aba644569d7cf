"""The single-device 3D plan in chunks (``models/base._chunked_3d``): the
z+y stages over chunks of x-planes and the x stage over blocks of
z-planes of the half spectrum, written back in place; the rule that
chooses the chunk count from the device's memory where the Config names
none (``fft3d_chunk_rule``); what the plan records of it; and the two
loops' stage scopes."""

import re

import numpy as np
import pytest

from distributedfft_tpu import (Config, GlobalSize, SlabFFTPlan,
                                SlabPartition, obs)
from distributedfft_tpu.analysis import hloscan
from distributedfft_tpu.models import base

V5E = base.HBM_LIMIT_BY_KIND["TPU v5 lite"]


def _rel_l2(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _plan(shape, chunk=None, **cfg):
    return SlabFFTPlan(GlobalSize(*shape), SlabPartition(1),
                       Config(fft3d_chunk=chunk, **cfg))


@pytest.mark.parametrize("direction", ["forward", "inverse"])
@pytest.mark.parametrize("chunk", [2, 4])
@pytest.mark.parametrize("backend", ["xla", "matmul"])
@pytest.mark.parametrize("shape", [(16, 16, 16), (16, 32, 64)],
                         ids=["cube", "16x32x64"])
def test_chunked_matches_float64(rng, shape, backend, chunk, direction):
    """Both loops, x stage included, against float64 NumPy, in float32."""
    plan = _plan(shape, chunk, fft_backend=backend)
    assert plan.fft3d_chunk == chunk
    x = rng.uniform(-1.0, 1.0, shape)
    ref = np.fft.rfftn(x)
    if direction == "forward":
        got = np.asarray(plan.exec_r2c(x.astype(np.float32)))
        assert got.dtype == np.complex64
        assert _rel_l2(got, ref) < 1e-5
    else:
        got = np.asarray(plan.exec_c2r(ref.astype(np.complex64)))
        assert got.dtype == np.float32 and got.shape == shape
        assert _rel_l2(got, x * x.size) < 1e-5


@pytest.mark.parametrize("shape,chunk", [((8, 12, 10), 4), ((4, 6, 2), 4),
                                         ((6, 4, 7), 3), ((8, 4, 6), 4)])
def test_planes_left_over_are_transformed(rng, shape, chunk):
    """Where the count does not divide the half spectrum's z extent (or
    exceeds it), the planes after the last whole block take one more
    step, and where it does, none is left: the answers match float64
    NumPy both ways."""
    plan = _plan(shape, chunk, fft_backend="xla", double_prec=True)
    x = rng.uniform(-1.0, 1.0, shape)
    c = np.asarray(plan.exec_r2c(x))
    np.testing.assert_allclose(c, np.fft.rfftn(x), rtol=0, atol=1e-11)
    np.testing.assert_allclose(np.asarray(plan.exec_c2r(c)), x * x.size,
                               rtol=0, atol=1e-11)


def test_rule_keeps_the_fused_program_without_a_limit():
    for kw in ({"backend": "matmul", "direct_max": 256},
               {"backend": "xla", "direct_max": None}):
        assert base.fft3d_chunk_rule((1024,) * 3, False, limit=None,
                                     **kw) is None


@pytest.mark.parametrize("shape,double", [((512,) * 3, False),
                                          ((256,) * 3, True),
                                          ((1024, 1024, 256), False)])
def test_rule_keeps_the_fused_program_where_it_fits(shape, double):
    assert base.fft3d_chunk_rule(shape, double, "matmul", 256, V5E) is None
    assert base.fft3d_chunk_rule(shape, double, "xla", None, V5E) is None


@pytest.mark.parametrize("backend,direct_max", [
    ("matmul", 256),    # the platform default at 1024: 4 x 256
    ("xla", None),
    ("matmul", 1024),   # one contraction per axis
])
def test_rule_chunks_the_v5e_cube(backend, direct_max):
    """1024^3 f32 on a v5e: the fused program does not fit, and every
    kind of local FFT takes the count that raced fastest."""
    assert base.fft3d_chunk_rule((1024,) * 3, False, backend, direct_max,
                                 V5E) == base.FFT3D_CHUNKS[0] == 128


def test_rule_reads_the_kind_of_local_fft():
    """With 16 GB to fill, the fused program of one direct contraction
    per axis fits where the four-step's and xla's do not."""
    limit = int(16e9 / base.FFT3D_FILL)
    assert base.fft3d_chunk_rule((1024,) * 3, False, "matmul", 1024,
                                 limit) is None
    assert base.fft3d_chunk_rule((1024,) * 3, False, "matmul", 256,
                                 limit) == 128
    assert base.fft3d_chunk_rule((1024,) * 3, False, "xla", None,
                                 limit) == 128


def test_rule_reads_the_dtype_and_the_limit():
    """Twice the bytes a point chunk where float32 does not; a larger
    limit never asks for more chunks."""
    shape = (1024, 512, 1024)
    assert base.fft3d_chunk_rule(shape, False, "matmul", 256, V5E) is None
    assert base.fft3d_chunk_rule(shape, True, "matmul", 256, V5E) in \
        base.FFT3D_CHUNKS
    counts = [base.fft3d_chunk_rule((1024,) * 3, False, "matmul", 256,
                                    int(gb * 1e9)) for gb in (15, 17, 20, 40)]
    assert counts == sorted(counts, key=lambda c: -(c or 0))
    assert counts[-1] is None


@pytest.mark.parametrize("nx,want", [(1000, 8), (1020, 4), (1025, None)])
def test_rule_takes_only_counts_that_divide_x(nx, want):
    """No count that divides 1000 or 1020 fits: the largest that divides
    it is taken; none divides 1025."""
    assert base.fft3d_chunk_rule((nx, 1024, 1024), False, "matmul", 256,
                                 V5E) == want


def test_cpu_reports_no_limit():
    assert base.device_bytes_limit() is None


def _created():
    return [r["attrs"] for r in obs.flightrec.snapshot()
            if r["name"] == "plan.created"]


def _auto_count():
    return obs.snapshot()["counters"].get("plan.fft3d_chunk_auto", 0)


@pytest.mark.parametrize("case,want", [
    ("memory", ("rule", "memory", 1)),
    ("config", (4, "config", 0)),
    ("config_fused", (None, "config", 0)),
    ("no_limit", (None, None, 0)),
    ("fits", (None, None, 0)),
    ("distributed", (None, None, 0)),
    ("c2c", (None, None, 0)),
])
def test_plan_records_its_chunk(monkeypatch, rng, devices, case, want):
    """``plan.created`` carries ``fft3d_chunk`` and ``fft3d_chunk_from``;
    ``plan.fft3d_chunk_auto`` counts each plan whose chunk the rule
    chose. The limit is steered small so a 16^3 cube does not fit."""
    shape = (16, 16, 16)
    limit = {"no_limit": None, "fits": 10 ** 9}.get(case, 80 * 1024)
    monkeypatch.setattr(base, "device_bytes_limit", lambda mesh=None: limit)
    obs.flightrec.clear()
    obs.metrics.reset()
    cfg = {"fft_backend": "xla"}
    if case == "config":
        cfg["fft3d_chunk"] = 4
    elif case == "config_fused":
        cfg["fft3d_chunk"] = 1
    part = SlabPartition(8 if case == "distributed" else 1)
    plan = SlabFFTPlan(GlobalSize(*shape), part, Config(**cfg),
                       transform="c2c" if case == "c2c" else "r2c")
    if want[0] == "rule":
        want = (base.fft3d_chunk_rule(shape, False, "xla", None, limit),
                ) + want[1:]
        assert want[0] in base.FFT3D_CHUNKS
    got = (plan.fft3d_chunk, plan.fft3d_chunk_from, _auto_count())
    assert got == want
    assert [(a["fft3d_chunk"], a["fft3d_chunk_from"])
            for a in _created()] == [want[:2]]
    if case == "memory":
        x = rng.uniform(-1.0, 1.0, shape).astype(np.float32)
        assert _rel_l2(np.asarray(plan.exec_r2c(x)),
                       np.fft.rfftn(x.astype(np.float64))) < 1e-5


def test_explicit_chunk_must_divide_x():
    with pytest.raises(ValueError, match="divide the x extent"):
        _plan((12, 16, 16), 8)


SCOPES = ("dfft/slab/chunk:1", "dfft/slab/chunk:2")


@pytest.mark.parametrize("direction", ["forward", "inverse"])
def test_chunk_loops_carry_their_scopes(direction):
    """Each loop's ops carry its chunk scope; the transforms inside keep
    their per-axis ``local_fft:1`` scopes; a fused plan has neither."""
    txt = hloscan.compiled_text(_plan((16, 16, 16), 4), direction)
    for s in SCOPES:
        assert s in txt
    assert set(re.findall(r"dfft/slab/local_fft:1\.ax\d", txt)) == {
        f"dfft/slab/local_fft:1.ax{a}" for a in range(3)}
    assert re.search(r"dfft/slab/chunk:2/[^\"]*dfft/slab/local_fft:1\.ax0",
                     txt)
    fused = hloscan.compiled_text(_plan((16, 16, 16)), direction)
    assert not any(s in fused for s in SCOPES)


@pytest.mark.parametrize("direction", ["forward", "inverse"])
def test_chunk_scopes_are_metadata_only(direction):
    from distributedfft_tpu.obs import profile
    on = hloscan.compiled_text(_plan((16, 16, 16), 4), direction)
    with profile.scopes_off():
        off = hloscan.compiled_text(_plan((16, 16, 16), 4), direction)
    assert SCOPES[1] in on and SCOPES[1] not in off
    assert hloscan.strip_metadata(on) == hloscan.strip_metadata(off)


@pytest.mark.parametrize("direction", ["forward", "inverse"])
def test_chunked_plan_passes_graph_verification(direction):
    """``dfft-verify``'s graph, contract, trace and scope passes and its
    jaxpr lints accept the chunk scopes, which no graph node declares."""
    from distributedfft_tpu.analysis import jaxprlint, plangraph
    plan = _plan((16, 16, 16), 4)
    assert plangraph.verify_graph(plan, direction, 3) == []
    assert jaxprlint.lint_plan(plan, direction, 3) == []
