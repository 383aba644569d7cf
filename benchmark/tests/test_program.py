"""The per-layer metrics that read what the program reports of itself
(``benchmark/program.py``): the device time no stage scope owns and the
per-axis local-FFT time on traces recorded on a v5e (``testdata/``), and
the ``plan.exec`` spans of a traced CPU run at 32^3."""

import io
import os

import pytest

from benchmark import harness, trace, work
from benchmark.harness import Context

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata")
ONE = os.path.join(DATA, "slab512_forward.xplane.pb")
FOUR = os.path.join(DATA, "slab1024x4_roundtrip.xplane.pb")
AXES = os.path.join(DATA, "slab1024x4_roundtrip_axes.xplane.pb")


def context(path):
    """A metric's view of a recorded traced run: its steps are the
    ``bench/step`` spans of the window."""
    tr = trace.load(path)
    steps = sum(1 for s in tr.spans if s.name == "bench/step")
    return Context(steps, tr.window_s, 0.0, 0, [], None, {}, tr)


def read(name, ctx):
    return harness.part("metrics", name).read(ctx)


@pytest.mark.parametrize("path, ms", [(FOUR, 15.21), (ONE, 1.61)])
def test_unscoped_ms_on_recorded_traces(path, ms):
    """Recorded before the sub-scopes: four chips, the c64 boundary
    (``X64Combine``, ``X64SplitLow``/``High``) and the input's relayout
    copy; one chip, the input's relayout copy."""
    assert read("unscoped_ms", context(path)) == pytest.approx(ms, abs=0.05)


@pytest.mark.parametrize("path", [FOUR, ONE])
def test_axis_metrics_read_nothing_without_subscopes(path):
    ctx = context(path)
    assert all(read(f"local_fft.ax{k}_ms", ctx) is None for k in range(3))


def test_axis_metrics_on_a_recorded_trace_with_subscopes():
    """``slab1024x4.roundtrip`` recorded with the sub-scopes (5 s, 21
    steps, four chips): the three axes cover the local-FFT stages' time,
    and no op moved out of its stage."""
    ctx = context(AXES)
    axes = [read(f"local_fft.ax{k}_ms", ctx) for k in range(3)]
    # x, y, z per step and chip, forward and inverse (my recount).
    assert axes == pytest.approx([49.36, 55.44, 83.85], abs=0.05)
    devs = ctx.trace.devices.values()
    stage = sum(trace.scoped_ns(ops, "local_fft") for ops in devs) \
        / len(devs) * 1e-6 / ctx.steps
    assert sum(axes) >= 0.9 * stage
    assert sum(axes) <= stage * (1 + 1e-9)
    # The c64 boundary and the input copy, 15.37 ms, and the sampled
    # step's reduction programs.
    assert read("unscoped_ms", ctx) == pytest.approx(16.25, abs=0.05)


@pytest.fixture()
def cpu_as_chip(monkeypatch):
    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(harness, "check_devices", lambda devices, chips: None)
    monkeypatch.setattr(work, "peaks", lambda kind: v5e)


def test_plan_metrics_on_a_traced_cpu_run(cpu_as_chip):
    """The window's ``plan.exec`` spans: each call's envelope, within
    the benchmark's own time of the call; the envelope's own host time
    outside the jitted program's dispatch is part of it."""
    c = harness.load_cell("slab1024x4.roundtrip")
    c.config["global_size"] = [32] * 3
    r = harness.run(c, 2 ** 35 + 3, 0.3, True, log=io.StringIO())
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert r["correct"]
    assert 0 < m["plan.envelope_ms"] <= m["plan.exec_ms"] <= m["plan.call_ms"]
    assert all(m[f"local_fft.ax{k}_ms"] > 0 for k in range(3))
    assert m["unscoped_ms"] >= 0


def test_plan_metrics_read_nothing_without_exec_spans(monkeypatch):
    """An older program's ring holds no ``plan.exec`` span: None."""
    from distributedfft_tpu.obs import flightrec

    monkeypatch.setattr(flightrec, "snapshot", lambda: [
        {"ev": "span", "name": "plan.build", "seq": 1, "dur_ms": 3.0,
         "parent": None}])
    ctx = Context(2, 1.0, 0.0, 0, [0.001] * 4, None, {})
    assert read("plan.exec_ms", ctx) is None
    assert read("plan.envelope_ms", ctx) is None


def test_plan_metrics_take_the_window_calls(monkeypatch):
    """The last ``len(call_s)`` exec spans, each less its dispatch
    children (two where the fallback ladder retried)."""
    from distributedfft_tpu.obs import flightrec

    def span(name, seq, dur, parent=None):
        return {"ev": "span", "name": name, "seq": seq, "dur_ms": dur,
                "parent": parent}

    ring = [span("plan.dispatch", 2, 50.0, "plan.exec"),
            span("plan.exec", 1, 60.0),  # warm-up, before the window
            span("plan.dispatch", 4, 0.5, "plan.exec"),
            span("plan.exec", 3, 0.75),
            span("plan.dispatch", 6, 0.25, "plan.exec"),
            span("plan.dispatch", 7, 0.5, "plan.exec"),
            span("plan.exec", 5, 1.25)]
    monkeypatch.setattr(flightrec, "snapshot", lambda: ring)
    ctx = Context(1, 1.0, 0.0, 0, [0.001, 0.001], None, {})
    assert read("plan.exec_ms", ctx) == pytest.approx(1.0)
    assert read("plan.envelope_ms", ctx) == pytest.approx(0.375)
