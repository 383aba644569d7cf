"""The batched 2D cell (``batched2d4096.roundtrip``) at a size a test
run holds (CPU, 8 x 64 x 64): the call files' least work by hand, the 2D
float64 reference against numpy, ``correct`` through the harness for the
sound program and broken ones, the ``closed_last`` loop, and the chunk
loop's metric on a trace recorded on a v5e (``testdata/``)."""

import contextlib
import io
import os

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark import harness, load, reference2d, trace, work
from benchmark.harness import Context

CELL = "batched2d4096.roundtrip"
SHAPE = [8, 64, 64]
V5E = work.peaks("TPU v5 lite")
R2C = harness.part("calls", "batched_r2c")
C2R = harness.part("calls", "batched_c2r")
TRACE = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata",
                     "batched2d4096_roundtrip.xplane.pb")


@pytest.fixture(autouse=True)
def cpu_as_chip(monkeypatch):
    monkeypatch.setattr(harness, "check_devices", lambda devices, chips: None)
    monkeypatch.setattr(work, "peaks", lambda kind: V5E)


# The cell's own calls, and each call alone (C2R alone: its images
# against the float64 inverse of the step's input spectrum).
CALLS = {"roundtrip": ["batched_r2c", "batched_c2r"], "r2c": ["batched_r2c"],
         "c2r": ["batched_c2r"]}


def cell(calls="roundtrip"):
    c = harness.load_cell(CELL)
    c.config["global_size"] = list(SHAPE)
    c.traffic = {**c.traffic, "calls": CALLS[calls]}
    return c


def run(c, control="", trace_on=False, seed=2 ** 33 + 29):
    return harness.run(c, seed, 0.3, trace_on, control=control,
                       log=io.StringIO())


def test_work_by_hand():
    """Per direction: 2.5 n (log2 nx + log2 ny) flops over the n real
    elements, the real stack and the half spectrum each moved once."""
    plan = harness.part("plans", "batched2d").build(cell().config, {})
    n = 8 * 64 * 64
    real, spec = 4 * n, 8 * 8 * 64 * 33
    for call in (R2C, C2R):
        w = call.work(plan, 1)
        assert w.fft_flops == pytest.approx(2.5 * n * (6 + 6))
        assert w.fft_hbm_bytes == real + spec
        assert w.ici_bytes == 0
    step = work.step_work(plan, [R2C, C2R], 1)
    assert step.local_fft_s(V5E) == pytest.approx(2 * (real + spec) / 819e9)


def test_reference2d_matches_numpy():
    rng = np.random.default_rng(5)
    x = rng.uniform(-1.0, 1.0, SHAPE)
    images = [1, 6]
    c = np.fft.rfft2(x)
    np.testing.assert_allclose(reference2d.rfft2_images(x, images),
                               c[images], rtol=0, atol=1e-10)
    np.testing.assert_allclose(reference2d.irfft2_images(c, images, 64),
                               x[images] * 64 * 64, rtol=0, atol=1e-9)


def test_the_cell_loads_by_name():
    c = harness.load_cell(CELL)
    assert c.chips == 1 and c.traffic["loop"] == "closed_last"
    assert c.traffic["calls"] == ["batched_r2c", "batched_c2r"]
    assert {m["name"] for m in c.metrics} == {"step_ms", "peak_hbm_gb",
                                              "setup_s"}
    names = {m["name"] for m in c.per_layer}
    assert "batched2d.chunk_ms" in names and "local_fft.ax0_ms" not in names
    assert not any(n.startswith("exchange.") for n in names)
    plan = harness.part("plans", "batched2d").build(c.config, {})
    assert plan.input_shape == (48, 4096, 4096) and plan.batch_chunk == 1
    assert plan.mesh is None and plan.transform == "r2c"


@pytest.mark.parametrize("calls", sorted(CALLS))
def test_sound_run_is_correct(calls):
    r = run(cell(calls))
    assert r["correct"], r["checks"]
    assert r["attempted"] > 2 and r["compiles_in_window"] == 0
    assert set(r["checks"]) == {harness.part("calls", c).NAME
                                for c in CALLS[calls]}
    assert max(c["value"] for c in r["checks"].values()) < 1e-5


def bf16(o):
    """The answer rounded through bfloat16, as a single-pass bf16 matmul
    rounds its operands: the ``bf16_mxu`` control's error, which the CPU
    cannot show (it runs that control's matmul in float32)."""
    if jnp.iscomplexobj(o):
        return jnp.real(o).astype(jnp.bfloat16).astype(o.real.dtype) + 1j \
            * jnp.imag(o).astype(jnp.bfloat16).astype(o.real.dtype)
    return o.astype(jnp.bfloat16).astype(o.dtype)


def half(o):
    """The upper half of the stack never computed."""
    return o.at[o.shape[0] // 2:].set(0)


def altered(o):
    """One pixel of every other image altered where it is produced."""
    rms = jnp.sqrt(jnp.mean(jnp.abs(o) ** 2))
    return o.at[::2, 1, 1].add(100 * rms.astype(o.real.dtype))


@pytest.mark.parametrize("fault", [bf16, half, altered],
                         ids=["bf16", "half", "altered"])
@pytest.mark.parametrize("calls", sorted(CALLS))
def test_broken_answers_are_not_correct(monkeypatch, calls, fault):
    real = load.step_fn
    monkeypatch.setattr(load, "step_fn", lambda *a, **k: (
        lambda step: lambda x: [fault(o) for o in step(x)])(real(*a, **k)))
    r = run(cell(calls))
    assert not r["correct"], r["checks"]
    if fault is bf16:  # each call fails its own limit
        assert min(c["value"] for c in r["checks"].values()) > 1e-4


def test_last_step_is_compared_after_the_window():
    """``closed_last`` hands the last step to ``done`` once the window's
    clock has stopped: no reduction of the comparison runs in the
    window, and the harness keeps that step."""
    loop = harness.part("loops", "closed_last")
    calls = []

    def done(n, i, outs):
        calls.append((n, i, outs))

    stats = loop.window(lambda x: [x + 1, x + 2], [np.float32(1)], 0.05,
                        lambda name: contextlib.nullcontext(), done)
    assert stats["steps"] >= 1 and stats["window_s"] >= 0.05
    assert len(calls) == 1 and calls[0][0] == 0 and calls[0][2][-1] == 3
    with pytest.raises(ValueError):
        loop.validate({"clients": 1, "inputs": 1, "sample": 2})


def test_traced_run_reads_every_per_layer_metric():
    c = cell()
    r = run(c, trace_on=True, seed=11)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {m["name"] for m in c.per_layer}
    assert r["metrics"]["batched2d.chunk_ms"]["value"] > 0


def test_chunk_ms_reads_nothing_without_the_scope():
    tr = trace.Trace((0, 10), {"/device:TPU:0": [
        trace.Op("fusion.1", 0, 4, "batched2d/local_fft:1.ax2", "fusion"),
        trace.Op("while.2", 0, 9, "batched2d/local_fft:1", "while")]}, [])
    ctx = Context(1, 1e-8, 0.0, 0, [], None, {}, tr)
    assert harness.part("metrics", "batched2d.chunk_ms").read(ctx) is None


def test_chunk_ms_leaves_the_loop_op_out():
    tr = trace.Trace((0, 100), {"/device:TPU:0": [
        trace.Op("while.2", 0, 90, "batched2d/chunk:1", "while"),
        trace.Op("fusion.1", 0, 40, "batched2d/local_fft:1.ax2", "fusion"),
        trace.Op("dynamic-update-slice.3", 40, 46, "batched2d/chunk:1",
                 "dynamic-update-slice")]}, [])
    ctx = Context(2, 1e-7, 0.0, 0, [], None, {}, tr)
    assert harness.part("metrics", "batched2d.chunk_ms").read(ctx) \
        == pytest.approx(6e-6 / 2)


def recorded():
    tr = trace.load(TRACE)
    steps = sum(1 for s in tr.spans if s.name == "bench/step")
    return Context(steps, tr.window_s, 0.0, 0, [], None, {}, tr)


def test_chunk_ms_on_a_recorded_trace():
    """``batched2d4096.roundtrip`` recorded on one v5e (48 x 4096^2,
    chunks of one image, a 2 s window of 5 steps): the chunk loop's own
    ops read less than the two axes' transforms, and the axes, the chunk
    loop and the unscoped ops together are the device's busy time,
    nothing counted twice."""
    ctx = recorded()
    m = {k: harness.part("metrics", k).read(ctx) for k in (
        "batched2d.chunk_ms", "local_fft.ax1_ms", "local_fft.ax2_ms",
        "unscoped_ms")}
    # Per step: the chunk loop, the rows, the columns, no scope.
    assert list(m.values()) == pytest.approx([156.93, 65.61, 151.32, 47.95],
                                             abs=0.05)
    assert m["batched2d.chunk_ms"] < m["local_fft.ax1_ms"] \
        + m["local_fft.ax2_ms"]
    assert harness.part("metrics", "local_fft.ax0_ms").read(ctx) is None
    busy_ms = trace.busy_s(ctx.trace) * 1e3 / ctx.steps
    assert sum(m.values()) == pytest.approx(busy_ms, rel=1e-3)
