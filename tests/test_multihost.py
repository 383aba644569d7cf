"""Multi-host runtime (parallel/multihost.py).

The reference can only exercise its multi-node path on a real SLURM cluster
(SURVEY §4); here the multi-controller path runs for real in the test suite:
two local processes, 4 CPU devices each, rendezvous over localhost — a
genuine 2-process 8-device mesh with cross-process collectives.
"""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

import distributedfft_tpu as dfft
from distributedfft_tpu.parallel import multihost as mh

def test_maybe_initialize_noop_single_process(monkeypatch):
    for var in (mh.ENV_COORD, mh.ENV_NPROCS, mh.ENV_PROCID,
                "JAX_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(var, raising=False)
    pid, cnt = mh.maybe_initialize()
    assert (pid, cnt) == (0, 1)
    assert mh.is_primary()


def test_process_local_slices_cover_global(devices):
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8), dfft.Config(),
                            mesh=dfft.make_slab_mesh(8, devices))
    slices = mh.process_local_slices(plan.input_sharding,
                                     plan.input_padded_shape)
    assert len(slices) == 8  # single process: every device is addressable
    starts = sorted((s[0].start or 0) for s in slices)
    assert starts == [i * 2 for i in range(8)]


def test_global_from_local_single_process(devices, rng):
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8), dfft.Config(),
                            mesh=dfft.make_slab_mesh(8, devices))
    local = rng.random(plan.input_padded_shape).astype(np.float32)
    arr = mh.global_from_local(plan.input_sharding, plan.input_padded_shape,
                               local)
    assert arr.shape == plan.input_padded_shape
    np.testing.assert_array_equal(np.asarray(arr), local)


def test_plan_local_input_shape(devices):
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8), dfft.Config(),
                            mesh=dfft.make_slab_mesh(8, devices))
    x = mh.plan_local_input(plan, seed=3)
    assert x.shape == plan.input_padded_shape
    c = mh.plan_local_spectral(plan, seed=3)
    assert c.shape == plan.output_padded_shape


_WORKER = textwrap.dedent("""
    import jax
    from distributedfft_tpu.parallel.mesh import force_cpu_devices
    force_cpu_devices(4)  # portable: pre-0.5 jax lacks jax_num_cpu_devices
    from distributedfft_tpu.parallel import multihost as mh
    pid, cnt = mh.maybe_initialize()
    assert cnt == 2, (pid, cnt)
    assert len(jax.devices()) == 8
    import distributedfft_tpu as dfft
    from distributedfft_tpu.testing import testcases as tc
    g = dfft.GlobalSize(32, 32, 32)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(8), dfft.Config())
    r0 = tc.testcase0(plan, iterations=1, warmup=0, write_csv=False)
    r2 = tc.testcase2(plan, iterations=1, warmup=0, write_csv=False)
    assert r0["mean_ms"] > 0 and r2["mean_ms"] > 0
    print(f"OK {pid}/{cnt}", flush=True)
    mh.shutdown()
""")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_two_procs(tmp_path, script_text):
    """Launch the worker script as a 2-process multi-controller job and
    return both outputs. Kills both processes on timeout — a regression
    that deadlocks a collective must not leave orphans holding the
    coordinator port."""
    script = tmp_path / "worker.py"
    script.write_text(script_text)
    port = _free_port()
    procs = []
    try:
        for i in range(2):
            env = dict(os.environ,
                       PYTHONPATH=os.path.dirname(os.path.dirname(__file__)),
                       DFFT_COORDINATOR=f"localhost:{port}",
                       DFFT_NUM_PROCESSES="2", DFFT_PROCESS_ID=str(i))
            env.pop("XLA_FLAGS", None)
            procs.append(subprocess.Popen([sys.executable, str(script)],
                                          env=env, stdout=subprocess.PIPE,
                                          stderr=subprocess.STDOUT,
                                          text=True))
        outs = [p.communicate(timeout=300)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
    return outs


def test_two_process_mesh_end_to_end(tmp_path):
    """Two controllers x 4 CPU devices: rendezvous, per-process input
    blocks, and the slab pipeline's all_to_all crossing processes."""
    outs = _run_two_procs(tmp_path, _WORKER)
    for i, out in enumerate(outs):
        assert f"OK {i}/2" in out


_TIMER_WORKER = textwrap.dedent("""
    import time
    import jax
    from distributedfft_tpu.parallel.mesh import force_cpu_devices
    force_cpu_devices(4)  # portable: pre-0.5 jax lacks jax_num_cpu_devices
    from distributedfft_tpu.parallel import multihost as mh
    pid, cnt = mh.maybe_initialize()
    assert cnt == 2, (pid, cnt)
    from distributedfft_tpu.utils.timer import Timer, read_timer_csv
    csv = CSV_PATH
    t = Timer(["phase A", "Run complete"], pcnt=8, filename=csv,
              process_index=pid, num_processes=cnt)
    t.start()
    time.sleep(0.05 * (pid + 1))   # deliberate per-process skew
    t.stop_store("phase A")
    t.stop_store("Run complete")
    t.gather()                     # collective: both processes reach it
    if pid == 0:
        row = read_timer_csv(csv)[0]["phase A"]
        assert len(row) == 8, row
        # ranks 0-3 carry process 0's measurement, ranks 4-7 process 1's;
        # the designed ~50 ms skew must be visible across the boundary and
        # invisible within each process's block.
        assert row[0] == row[3] and row[4] == row[7], row
        assert row[4] - row[0] > 20.0, row
    print(f"TIMER OK {pid}", flush=True)
    mh.shutdown()
""")


_TC1_ANALYTIC_WORKER = textwrap.dedent("""
    import jax
    from distributedfft_tpu.parallel.mesh import force_cpu_devices
    force_cpu_devices(4)  # portable: pre-0.5 jax lacks jax_num_cpu_devices
    jax.config.update("jax_enable_x64", True)  # double_prec plan below
    from distributedfft_tpu.parallel import multihost as mh
    pid, cnt = mh.maybe_initialize()
    assert cnt == 2, (pid, cnt)
    from distributedfft_tpu import (Config, GlobalSize, SlabFFTPlan,
                                    SlabPartition)
    from distributedfft_tpu.testing import testcases as tc
    plan = SlabFFTPlan(GlobalSize(16, 16, 16), SlabPartition(8),
                       Config(double_prec=True))
    r = tc.testcase1(plan, write_csv=False, truth="analytic")
    assert r["residual_sum"] < 1e-6, r
    print(f"TC1 OK {pid}", flush=True)
    mh.shutdown()
""")


def test_two_process_tc1_analytic(tmp_path):
    """Validation at pod scale: tc1 with the device-built analytic truth
    runs under multi-controller (no coordinator-rank host array exists) —
    the capability the reference's random_dist scheme cannot offer and
    the CLI gate now admits."""
    outs = _run_two_procs(tmp_path, _TC1_ANALYTIC_WORKER)
    for i, out in enumerate(outs):
        assert f"TC1 OK {i}" in out


def test_two_process_timer_gathers_per_process_columns(tmp_path):
    """VERDICT r2 item 6: under multi-controller runs the Timer CSV must
    carry each process's OWN durations in its ranks' columns (the
    reference Timer::gather MPI-gather analog), not process 0's value
    replicated — per-host skew is the thing the columns exist to expose."""
    csv = str(tmp_path / "bench" / "timer.csv")
    script = _TIMER_WORKER.replace("CSV_PATH", repr(csv))
    outs = _run_two_procs(tmp_path, script)
    for i, out in enumerate(outs):
        assert f"TIMER OK {i}" in out


_AUTOTUNE_WORKER = textwrap.dedent("""
    import jax
    from distributedfft_tpu.parallel.mesh import force_cpu_devices
    force_cpu_devices(4)  # portable: pre-0.5 jax lacks jax_num_cpu_devices
    from distributedfft_tpu.parallel import multihost as mh
    pid, cnt = mh.maybe_initialize()
    assert cnt == 2, (pid, cnt)
    import distributedfft_tpu as dfft
    from distributedfft_tpu.testing import autotune
    g = dfft.GlobalSize(16, 16, 16)
    cands = autotune.autotune_comm("slab", g, dfft.SlabPartition(8),
                                   iterations=1, warmup=0)
    win = cands[0]
    assert win.ok, autotune.describe_failures(cands)
    print(f"WINNER {pid} {win.label}", flush=True)
    mh.shutdown()
""")


def test_two_process_comm_autotune_agreement(tmp_path):
    """The comm-strategy autotuner's multi-controller agreement step: both
    processes must run the same unconditional broadcast (a divergent
    collective deadlocks) and emerge with the SAME winner, regardless of
    per-process timing noise."""
    outs = _run_two_procs(tmp_path, _AUTOTUNE_WORKER)
    winners = []
    for i, out in enumerate(outs):
        line = [ln for ln in out.splitlines() if ln.startswith(f"WINNER {i} ")]
        assert line, out
        winners.append(line[0].split(maxsplit=2)[2])
    assert winners[0] == winners[1], winners
