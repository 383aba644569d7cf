"""``correct`` comes out false when the timed path is broken, and for the
lower-precision control, at a size a test run holds (CPU, 32^3, the
four-chip cell on four virtual devices). Each run skips only the
harness's look for a chip (and borrows the v5e's peaks); the rest of it
runs as on the chip."""

import io
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, load, work

N = 32
CELL = "slab1024x4.roundtrip"
V5E = work.peaks("TPU v5 lite")
# The cell's own calls, and each call alone: the call files serve later
# cells (PERF.md, Open questions) with the same semantics.
CALLS = {"roundtrip": ["exec_r2c", "exec_c2r"], "r2c": ["exec_r2c"],
         "c2r": ["exec_c2r"]}


@pytest.fixture(autouse=True)
def cpu_as_chip(monkeypatch):
    monkeypatch.setattr(harness, "check_devices", lambda devices, chips: None)
    monkeypatch.setattr(work, "peaks", lambda kind: V5E)


def cell(calls="roundtrip"):
    c = harness.load_cell(CELL)
    c.config["global_size"] = [N] * 3
    c.traffic = {**c.traffic, "calls": CALLS[calls]}
    return c


def run(c, control="", trace=False, seed=2 ** 33 + 17):
    return harness.run(c, seed, 0.3, trace, control=control,
                       log=io.StringIO())


def with_fault(monkeypatch, wrap):
    """The timed path broken underneath: ``wrap`` around every step."""
    real = load.step_fn
    monkeypatch.setattr(load, "step_fn",
                        lambda *a, **k: wrap(real(*a, **k)))


def per_output(f):
    """A fault that breaks every call's answer where it is produced."""
    return lambda step: (lambda x: [f(o) for o in step(x)])


def stale(step):
    """Each step after the first answers with the previous step's
    answers: the other input's, returned unchanged."""
    prev = []

    def s(x):
        outs = step(x)
        ret = prev[0] if prev else outs
        prev[:] = [outs]
        return ret
    return s


def half(o):
    """Half of the cube left out: the upper half of x never computed."""
    return o.at[o.shape[0] // 2:].set(0)


def altered(o):
    """One answer altered where it is produced (every 8th plane along x,
    so the seed-drawn planes of a call compared on planes hold one)."""
    rms = jnp.sqrt(jnp.mean(jnp.abs(o) ** 2))
    return o.at[::8, 1, 1].add(100 * rms.astype(o.real.dtype))


FAULTS = {"stale": stale, "half": per_output(half),
          "altered": per_output(altered)}


@pytest.mark.parametrize("calls", sorted(CALLS))
def test_sound_run_is_correct(calls):
    r = run(cell(calls))
    assert r["correct"], r["checks"]
    assert r["attempted"] > 2 and r["compiles_in_window"] == 0
    assert set(r["checks"]) == {harness.part("calls", c).NAME
                                for c in CALLS[calls]}
    assert max(c["value"] for c in r["checks"].values()) < 1e-5
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("calls", sorted(CALLS))
def test_fault_is_not_correct(monkeypatch, calls, fault):
    with_fault(monkeypatch, FAULTS[fault])
    r = run(cell(calls))
    assert not r["correct"], r["checks"]


def test_exchange_left_out_is_not_correct(monkeypatch):
    """The all-to-all replaced by a local reshuffle: every chip keeps its
    own blocks, as if each peer held the same data."""
    from distributedfft_tpu.parallel import transpose

    def local(x, axis_name, split_axis, concat_axis, realigned):
        p = jax.lax.axis_size(axis_name)
        return jnp.concatenate(jnp.split(x, p, axis=split_axis),
                               axis=concat_axis)

    monkeypatch.setattr(transpose, "_all_to_all_native", local)
    r = run(cell())
    assert not r["correct"], r["checks"]


def test_control_is_not_correct():
    """The CPU runs ``bf16_mxu``'s matmul in float32, so only the chip
    reads that control (PERF.md)."""
    r = run(cell(), control="bf16_wire")
    assert not r["correct"], r["checks"]
    assert min(c["value"] for c in r["checks"].values()) > 1e-4


def test_the_benchmark_cell_loads_by_name():
    c = harness.load_cell(CELL)
    assert c.chips == 4 and c.traffic["calls"] == CALLS["roundtrip"]
    assert {m["name"] for m in c.metrics} == {"step_ms", "peak_hbm_gb",
                                              "setup_s"}
    assert harness.part("plans", c.config["plan"]).build


def test_a_name_cannot_leave_its_directory():
    with pytest.raises(ValueError):
        harness.part("calls", "../harness")


def test_traced_run_reads_every_per_layer_metric():
    """``--trace 1`` on the CPU: the reduction falls back to the host's op
    lines, and each reader of the cell finds something to read."""
    c = cell()
    r = run(c, trace=True, seed=7)
    assert r["correct"]
    assert set(r["metrics"]) == {m["name"] for m in c.per_layer}
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert r["breakdown"]["device_ops"] and r["breakdown"]["idle_gaps"]


def test_no_tpu_exits_non_zero_without_a_result():
    root = os.path.dirname(harness.BENCH)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3", "--seconds", "1", "--trace", "0"], cwd=root,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == "", p.stderr[-2000:]
    assert "platform=cpu" in p.stderr
