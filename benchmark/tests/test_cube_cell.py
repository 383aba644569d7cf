"""The one-chip cube cell (``cube1024.forward``) at a size a test run
holds (CPU, 16^3): the cell loads by name, ``correct`` through the
harness for the sound program, fused and in chunks, and for broken ones,
and the chunk loops' metric on hand-made traces and on a traced run."""

import io

import jax.numpy as jnp
import pytest

from benchmark import harness, load, trace, work
from benchmark.harness import Context

CELL = "cube1024.forward"
SHAPE = [16, 16, 16]
V5E = work.peaks("TPU v5 lite")


@pytest.fixture(autouse=True)
def cpu_as_chip(monkeypatch):
    monkeypatch.setattr(harness, "check_devices", lambda devices, chips: None)
    monkeypatch.setattr(work, "peaks", lambda kind: V5E)


def cell(chunk=None):
    """The cell at ``SHAPE``; ``chunk`` sets ``fft3d_chunk``, since the
    CPU reports no memory limit and so keeps the fused program."""
    c = harness.load_cell(CELL)
    c.config["global_size"] = list(SHAPE)
    if chunk:
        c.config["config"] = {**c.config["config"], "fft3d_chunk": chunk}
    return c


def run(c, trace_on=False, seed=2 ** 33 + 31):
    return harness.run(c, seed, 0.3, trace_on, log=io.StringIO())


def test_the_cell_loads_by_name():
    c = harness.load_cell(CELL)
    assert c.chips == 1 and c.traffic["loop"] == "closed_last"
    assert c.traffic["calls"] == ["exec_r2c"] and c.traffic["inputs"] == 1
    assert {m["name"] for m in c.metrics} == {"step_ms", "peak_hbm_gb",
                                              "setup_s"}
    names = {m["name"] for m in c.per_layer}
    assert {"fft3d.chunk_ms", "local_fft.ax0_ms", "step_roofline",
            "local_fft_roofline"} <= names
    assert "batched2d.chunk_ms" not in names
    assert not any(n.startswith("exchange.") for n in names)
    plan = harness.part("plans", "slab").build(c.config, {})
    assert plan.input_shape == (1024, 1024, 1024) and plan.fft3d
    assert plan.mesh is None and plan.transform == "r2c"


def test_work_by_hand():
    """One ``local_fft`` node: 2.5 n log2(16) flops over the three axes,
    the real cube and the half spectrum each moved once."""
    plan = harness.part("plans", "slab").build(cell().config, {})
    w = harness.part("calls", "exec_r2c").work(plan, 1)
    n = 16 ** 3
    assert w.fft_flops == pytest.approx(2.5 * n * 4 * 3)
    assert w.fft_hbm_bytes == 4 * n + 8 * 16 * 16 * 9
    assert w.ici_bytes == 0


@pytest.mark.parametrize("chunk", [None, 4])
def test_sound_run_is_correct(chunk):
    r = run(cell(chunk))
    assert r["correct"], r["checks"]
    assert r["attempted"] > 2 and r["compiles_in_window"] == 0
    assert set(r["checks"]) == {"r2c_rel_l2"}
    assert r["checks"]["r2c_rel_l2"]["value"] < 1e-5


def bf16(o):
    """The answer rounded through bfloat16, as a single-pass bf16 matmul
    rounds its operands (the CPU runs the ``bf16_mxu`` control's matmul
    in float32, so it cannot show that control's error)."""
    return jnp.real(o).astype(jnp.bfloat16).astype(jnp.float32) + 1j \
        * jnp.imag(o).astype(jnp.bfloat16).astype(jnp.float32)


def half(o):
    """The upper half of the x-planes never computed."""
    return o.at[o.shape[0] // 2:].set(0)


def altered(o):
    """One bin of every other x-plane altered where it is produced."""
    rms = jnp.sqrt(jnp.mean(jnp.abs(o) ** 2))
    return o.at[::2, 1, 1].add(100 * rms)


@pytest.mark.parametrize("fault", [bf16, half, altered],
                         ids=["bf16", "half", "altered"])
@pytest.mark.parametrize("chunk", [None, 4])
def test_broken_answers_are_not_correct(monkeypatch, chunk, fault):
    real = load.step_fn
    monkeypatch.setattr(load, "step_fn", lambda *a, **k: (
        lambda step: lambda x: [fault(o) for o in step(x)])(real(*a, **k)))
    r = run(cell(chunk))
    assert not r["correct"], r["checks"]
    if fault is bf16:
        assert r["checks"]["r2c_rel_l2"]["value"] > 1e-4


def test_traced_run_reads_every_per_layer_metric():
    c = cell(4)
    r = run(c, trace_on=True, seed=13)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {m["name"] for m in c.per_layer}
    assert r["metrics"]["fft3d.chunk_ms"]["value"] > 0


def test_fused_run_reads_no_chunk_time():
    """The fused program carries no chunk scope: the metric is left out
    of the line, as on a program that predates it."""
    r = run(cell(), trace_on=True, seed=17)
    assert r["correct"], r["checks"]
    assert "fft3d.chunk_ms" not in r["metrics"]
    assert r["metrics"]["local_fft_roofline"]["value"] > 0


def test_chunk_ms_leaves_the_loop_ops_out():
    tr = trace.Trace((0, 100), {"/device:TPU:0": [
        trace.Op("while.2", 0, 50, "slab/chunk:1", "while"),
        trace.Op("fusion.1", 0, 40, "slab/local_fft:1.ax2", "fusion"),
        trace.Op("dynamic-update-slice.3", 40, 46, "slab/chunk:1",
                 "dynamic-update-slice"),
        trace.Op("while.4", 50, 90, "slab/chunk:2", "while"),
        trace.Op("fusion.5", 50, 80, "slab/local_fft:1.ax0", "fusion"),
        trace.Op("custom-call.6", 90, 94, "slab/chunk:2", "custom-call"),
        trace.Op("fusion.7", 94, 96, "batched2d/chunk:1", "fusion")]}, [])
    ctx = Context(2, 1e-7, 0.0, 0, [], None, {}, tr)
    assert harness.part("metrics", "fft3d.chunk_ms").read(ctx) \
        == pytest.approx(10e-6 / 2)


def test_chunk_ms_reads_nothing_without_the_scope():
    tr = trace.Trace((0, 10), {"/device:TPU:0": [
        trace.Op("fusion.1", 0, 4, "slab/local_fft:1.ax2", "fusion")]}, [])
    ctx = Context(1, 1e-8, 0.0, 0, [], None, {}, tr)
    assert harness.part("metrics", "fft3d.chunk_ms").read(ctx) is None
