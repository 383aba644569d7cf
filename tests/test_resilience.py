"""Resilience layer (distributedfft_tpu/resilience/):

* in-graph guards catch injected wire faults (bit-flip / NaN / scale) on
  every exchange rendering x wire encoding, in ``check`` (counted) and
  ``enforce`` (structured ``GuardViolation``) modes — and never fire on
  clean runs;
* the zero-overhead pin: with ``guards="off"`` and ``$DFFT_FAULT_SPEC``
  unset, compiled HLO is byte-identical to a build that never saw a fault
  spec (the out-of-tree half of the pin — metadata-stripped op-graph
  identity against the ACTUAL pre-PR commit — was verified at development
  time for every rendering; in-tree, set-then-unset identity keeps it);
* the fallback ladder demotes exactly one rung per failure
  (ring -> opt1 -> default), records wisdom demotion stamps, and leaves
  default-rendering errors untouched;
* wisdom advisory-lock robustness: a killed holder never blocks the next
  writer (regression for the leftover-lock-file scenario), a HUNG holder
  is survived via acquisition timeout, and an old lock file is broken
  (age-based) under the stale-lock injector;
* coordinator connect backoff and autotune per-cell timeouts degrade
  instead of wedging;
* ``--selftest`` passes on healthy plans and fails (aborting the CLI)
  under an injected wire fault.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import jax

import distributedfft_tpu as dfft
from distributedfft_tpu import obs
from distributedfft_tpu import params as pm
from distributedfft_tpu.resilience import (GuardViolation, fallback, guards,
                                           inject, parse_fault_spec)
from distributedfft_tpu.utils import wisdom

G16 = dfft.GlobalSize(16, 16, 16)


@pytest.fixture(autouse=True)
def _resilience_hygiene(monkeypatch):
    """Every test starts with clean metrics and no fault/guard env."""
    for var in (inject.ENV_VAR, "DFFT_GUARDS", "DFFT_FALLBACK",
                "DFFT_WISDOM_LOCK_TIMEOUT_S", "DFFT_WISDOM_LOCK_STALE_S",
                "DFFT_AUTOTUNE_CELL_TIMEOUT_S", "DFFT_COORD_RETRIES",
                "DFFT_COORD_BACKOFF_S", "DFFT_DEMOTION_TTL_S"):
        monkeypatch.delenv(var, raising=False)
    obs.reset()
    yield
    obs.reset()


def _slab(cfg_kw, sequence="ZY_Then_X", guards_mode=None):
    kw = dict(cfg_kw)
    if guards_mode is not None:
        kw["guards"] = guards_mode
    return dfft.SlabFFTPlan(G16, dfft.SlabPartition(8), dfft.Config(**kw),
                            sequence=sequence)


def _input(plan, seed=0):
    return plan.pad_input(
        np.random.default_rng(seed).random(plan.input_shape)
        .astype(np.float32))


# ---------------------------------------------------------------------------
# grammar + tolerances
# ---------------------------------------------------------------------------

def test_fault_spec_grammar():
    s = parse_fault_spec("wire:scale:0.25@seed=7")
    assert (s.kind, s.mode, s.param, s.seed) == ("wire", "scale", 0.25, 7)
    assert parse_fault_spec(str(s)) == s
    assert parse_fault_spec("coordinator:down:2").param == 2
    assert parse_fault_spec("wisdom:stale-lock").mode == "stale-lock"
    # the serve straggler fault (ISSUE 8) parses like every other kind
    srv = parse_fault_spec("server:slow:25")
    assert (srv.kind, srv.mode, srv.param) == ("server", "slow", 25.0)
    assert parse_fault_spec("server:slow").param is None
    for bad in ("wire", "wire:frobnicate", "bogus:nan", "wire:nan@x=1",
                "wire:nan:oops:extra", "server:fast", "server"):
        with pytest.raises(ValueError):
            parse_fault_spec(bad)


def test_multi_fault_spec_grammar(monkeypatch):
    from distributedfft_tpu.resilience.inject import (active, active_specs,
                                                      parse_fault_specs)
    specs = parse_fault_specs("wire:bitflip,server:slow:40@seed=3")
    assert [s.kind for s in specs] == ["wire", "server"]
    assert specs[1].param == 40.0 and specs[1].seed == 3
    # strict: empty elements, malformed members, duplicate kinds all fail
    for bad in ("wire:nan,,", ",server:slow", "wire:nan,bogus:x",
                "wire:nan,wire:bitflip"):
        with pytest.raises(ValueError):
            parse_fault_specs(bad)
    monkeypatch.setenv(inject.ENV_VAR, "wire:nan,server:slow:5")
    assert [s.kind for s in active_specs()] == ["wire", "server"]
    assert active().kind == "wire"  # legacy first-spec accessor
    assert inject._spec_of("server").param == 5.0
    assert inject._spec_of("autotune") is None


def test_worker_fault_spec_grammar(monkeypatch):
    """ISSUE 13: the fleet's host-side worker faults parse under the
    same strict grammar — ``worker:crash[:K]`` / ``worker:hang[:MS]``
    with ``@seed=I`` selecting the victim worker INDEX — and compose
    with the wire/server kinds in one comma-separated spec."""
    from distributedfft_tpu.resilience.inject import parse_fault_specs
    s = parse_fault_spec("worker:crash:3@seed=1")
    assert (s.kind, s.mode, s.param, s.seed) == ("worker", "crash", 3, 1)
    assert parse_fault_spec(str(s)) == s  # round-trips
    assert parse_fault_spec("worker:crash").param is None  # K defaults 1
    h = parse_fault_spec("worker:hang:500")
    assert (h.mode, h.param) == ("hang", 500.0)
    # comma-composable with the existing kinds
    specs = parse_fault_specs("wire:bitflip,worker:crash:2@seed=1")
    assert [sp.kind for sp in specs] == ["wire", "worker"]
    for bad in ("worker", "worker:oops", "worker:crash:2:3",
                "worker:crash,worker:hang"):  # one fault per kind
        with pytest.raises(ValueError):
            (parse_fault_specs if "," in bad else parse_fault_spec)(bad)


def test_worker_fault_hooks_gate_on_victim_and_generation(monkeypatch):
    """The crash/hang hooks fire only in the victim worker index and
    only in its FIRST incarnation: a non-victim index, a respawned
    generation, and an unset spec are all exact no-ops (the replacement
    worker must come back clean — no crash loop)."""
    import time as _time

    # unset: no-ops
    assert inject.maybe_crash_worker(0, 0) is None
    assert inject.maybe_hang_worker(0, 0) is None

    monkeypatch.setenv(inject.ENV_VAR, "worker:hang:50@seed=1")
    t0 = _time.monotonic()
    inject.maybe_hang_worker(0, 0)   # wrong index: no sleep
    inject.maybe_hang_worker(1, 1)   # respawned generation: no sleep
    assert _time.monotonic() - t0 < 0.04
    inject.maybe_hang_worker(1, 0)   # the victim, generation 0: sleeps
    assert _time.monotonic() - t0 >= 0.05
    assert obs.metrics.counter_value("inject.worker_hangs") == 1

    # crash: gating paths must return without exiting the process
    # (the actual os._exit path is pinned end-to-end by the fleet
    # chaos test — it cannot run in-process by construction)
    monkeypatch.setenv(inject.ENV_VAR, "worker:crash:99@seed=1")
    inject._WORKER_REQS[0] = 0
    inject.maybe_crash_worker(0, 0)  # wrong index
    inject.maybe_crash_worker(1, 1)  # respawned generation
    assert inject._WORKER_REQS[0] == 0
    inject.maybe_crash_worker(1, 0)  # victim: counts toward K=99
    assert inject._WORKER_REQS[0] == 1
    inject._WORKER_REQS[0] = 0


def test_worker_devloss_spec_grammar_and_gating(monkeypatch):
    """ISSUE 20: ``worker:devloss[:D]`` parses under the same strict
    grammar (D = devices the victim's HOST loses, ``@seed=I`` the victim
    index), the kill hook gates exactly like crash (victim index,
    generation 0, ``$DFFT_DEVLOSS_AFTER``-th request), and the
    parent-side ``devloss_cut`` answers D only for the victim's
    RESPAWNED generations while the spec stays active — clearing the
    spec models host repair (a full-size replacement)."""
    s = parse_fault_spec("worker:devloss:4@seed=0")
    assert (s.kind, s.mode, s.param, s.seed) == ("worker", "devloss",
                                                 4.0, 0)
    assert parse_fault_spec(str(s)) == s       # round-trips
    assert parse_fault_spec("worker:devloss").param is None  # D defaults 1
    from distributedfft_tpu.resilience.inject import parse_fault_specs
    specs = parse_fault_specs("wire:nan,worker:devloss:2@seed=1")
    assert [sp.mode for sp in specs] == ["nan", "devloss"]
    with pytest.raises(ValueError):
        parse_fault_spec("worker:devloss:2:3")

    # unset spec: both hooks are exact no-ops
    assert inject.maybe_devloss_worker(0, 0) is None
    assert inject.devloss_cut(0, 1) == 0

    monkeypatch.setenv(inject.ENV_VAR, "worker:devloss:4@seed=1")
    monkeypatch.setenv("DFFT_DEVLOSS_AFTER", "99")  # never reaches exit
    inject._WORKER_REQS[0] = 0
    inject.maybe_devloss_worker(0, 0)   # wrong index: no count
    inject.maybe_devloss_worker(1, 1)   # respawned generation: no count
    assert inject._WORKER_REQS[0] == 0
    inject.maybe_devloss_worker(1, 0)   # the victim, generation 0
    assert inject._WORKER_REQS[0] == 1
    inject._WORKER_REQS[0] = 0
    # the parent-side cut: only the victim's replacements run short
    assert inject.devloss_cut(1, 1) == 4
    assert inject.devloss_cut(1, 2) == 4   # every generation while active
    assert inject.devloss_cut(1, 0) == 0   # the first incarnation is full
    assert inject.devloss_cut(0, 1) == 0   # non-victims are full
    monkeypatch.setenv(inject.ENV_VAR, "worker:devloss@seed=1")
    assert inject.devloss_cut(1, 1) == 1   # D defaults to one device
    # spec cleared = host repaired: the NEXT respawn is full-size again
    monkeypatch.delenv(inject.ENV_VAR)
    assert inject.devloss_cut(1, 1) == 0


def test_server_slow_injector(monkeypatch):
    monkeypatch.setenv(inject.ENV_VAR, "server:slow:60")
    t0 = time.perf_counter()
    inject.maybe_slow_server("test")
    assert time.perf_counter() - t0 >= 0.055
    assert obs.metrics.counter_value("inject.server_slow") == 1
    monkeypatch.delenv(inject.ENV_VAR)
    t0 = time.perf_counter()
    inject.maybe_slow_server("test")  # inactive: no sleep
    assert time.perf_counter() - t0 < 0.05


def test_guards_mode_resolution(monkeypatch):
    with pytest.raises(ValueError):
        dfft.Config(guards="sometimes")
    assert dfft.Config(guards="CHECK").guards == "check"
    assert dfft.Config().resolved_guards() == "off"
    monkeypatch.setenv("DFFT_GUARDS", "enforce")
    assert dfft.Config().resolved_guards() == "enforce"
    # explicit field beats the env
    assert dfft.Config(guards="off").resolved_guards() == "off"


def test_tolerance_derivation():
    f32 = guards.parseval_tolerance(False, "native", 16 ** 3)
    f64 = guards.parseval_tolerance(True, "native", 16 ** 3)
    bf = guards.parseval_tolerance(False, "bf16", 16 ** 3)
    assert f64 < f32 < bf
    assert guards.parseval_tolerance(False, "native", 1024 ** 3) > f32
    # every injected fault class sits far above the loosest tolerance
    assert bf < 0.2


# ---------------------------------------------------------------------------
# guards catch injected wire faults on every rendering x wire
# ---------------------------------------------------------------------------

RENDERINGS = [
    ("default", dict(comm_method=dfft.CommMethod.ALL2ALL), "ZY_Then_X"),
    ("opt1", dict(comm_method=dfft.CommMethod.ALL2ALL, opt=1), "ZY_Then_X"),
    ("ring", dict(send_method=dfft.SendMethod.RING), "Z_Then_YX"),
    ("gspmd", dict(comm_method=dfft.CommMethod.PEER2PEER), "ZY_Then_X"),
    ("default-bf16", dict(comm_method=dfft.CommMethod.ALL2ALL,
                          wire_dtype="bf16"), "ZY_Then_X"),
    ("ring-bf16", dict(send_method=dfft.SendMethod.RING,
                       wire_dtype="bf16"), "Z_Then_YX"),
    ("gspmd-bf16", dict(comm_method=dfft.CommMethod.PEER2PEER,
                        wire_dtype="bf16"), "ZY_Then_X"),
]


@pytest.mark.parametrize("name, kw, seq", RENDERINGS,
                         ids=[r[0] for r in RENDERINGS])
def test_guards_clean_then_injected(name, kw, seq, devices, monkeypatch):
    # Clean run in check mode: zero violations, result matches unguarded.
    ref = _slab(kw, seq)
    x = _input(ref)
    want = np.asarray(ref.exec_r2c(x))
    plan = _slab(kw, seq, guards_mode="check")
    got = np.asarray(plan.exec_r2c(x))
    np.testing.assert_array_equal(got, want)
    assert obs.metrics.counter_value("guard.parseval_violations") == 0
    # Injected NaN on the wire: check counts, enforce raises (structured).
    monkeypatch.setenv(inject.ENV_VAR, "wire:nan")
    hurt = _slab(kw, seq, guards_mode="enforce")
    with pytest.raises(GuardViolation) as ei:
        hurt.exec_r2c(x)
    fp = ei.value.fingerprint
    assert fp["shape"] == [16, 16, 16] and fp["direction"] == "forward"
    assert ei.value.check in ("parseval", "finite")
    assert obs.metrics.counter_value("inject.wire_faults") >= 1


@pytest.mark.parametrize("spec", ["wire:bitflip", "wire:scale:0.5"])
def test_guards_catch_bitflip_and_scale(spec, devices, monkeypatch):
    monkeypatch.setenv(inject.ENV_VAR, spec)
    plan = _slab(dict(comm_method=dfft.CommMethod.ALL2ALL),
                 guards_mode="check")
    plan.exec_r2c(_input(plan))
    assert obs.metrics.counter_value("guard.parseval_violations") == 1


def test_guards_inverse_nan_caught(devices, monkeypatch):
    """The C2R inverse's finiteness guard catches an injected NaN."""
    monkeypatch.setenv(inject.ENV_VAR, "wire:nan")
    plan = _slab(dict(comm_method=dfft.CommMethod.ALL2ALL),
                 guards_mode="enforce")
    c = plan.pad_spectral(
        (np.random.default_rng(1).random(plan.output_shape)
         + 1j * np.random.default_rng(2).random(plan.output_shape))
        .astype(np.complex64))
    with pytest.raises(GuardViolation) as ei:
        plan.exec_c2r(c)
    assert ei.value.check == "finite"


def test_c2c_inverse_parseval_guard(devices, monkeypatch):
    """C2C inverse keeps the full Parseval guard (exact for ANY input)."""
    plan = dfft.SlabFFTPlan(G16, dfft.SlabPartition(8),
                            dfft.Config(guards="check"), transform="c2c")
    rng = np.random.default_rng(0)
    c = (rng.random(G16.shape) + 1j * rng.random(G16.shape)
         ).astype(np.complex64)
    plan.exec_c2c_inv(plan.pad_spectral(c))
    assert obs.metrics.counter_value("guard.parseval_violations") == 0
    monkeypatch.setenv(inject.ENV_VAR, "wire:scale:0.5")
    hurt = dfft.SlabFFTPlan(G16, dfft.SlabPartition(8),
                            dfft.Config(guards="enforce"), transform="c2c")
    with pytest.raises(GuardViolation) as ei:
        hurt.exec_c2c_inv(hurt.pad_spectral(c))
    assert ei.value.check == "parseval"


def test_pencil_and_batched_guards(devices, monkeypatch):
    monkeypatch.setenv(inject.ENV_VAR, "wire:nan")
    pp = dfft.PencilFFTPlan(G16, dfft.PencilPartition(2, 4),
                            dfft.Config(guards="enforce"))
    with pytest.raises(GuardViolation):
        pp.exec_r2c(pp.pad_input(
            np.random.default_rng(0).random(G16.shape).astype(np.float32)))
    bp = dfft.Batched2DFFTPlan(8, 16, 16, dfft.SlabPartition(8),
                               dfft.Config(guards="enforce"), shard="x")
    with pytest.raises(GuardViolation):
        bp.exec_forward(bp.pad_input(
            np.random.default_rng(0).random((8, 16, 16))
            .astype(np.float32)))


def test_check_mode_wire_drift_demotes_to_native(devices):
    """A compressed wire whose measured drift exceeds the budget falls
    back to native for subsequent calls (check mode), with the demotion
    counted, noticed and stamp-free (no store configured)."""
    plan = _slab(dict(comm_method=dfft.CommMethod.ALL2ALL,
                      wire_dtype="bf16", wire_error_budget=1e-9),
                 guards_mode="check")
    x = _input(plan)
    plan.exec_r2c(x)  # bf16 drift >> 1e-9 -> violation -> demote
    assert obs.metrics.counter_value("guard.wire_drift_violations") == 1
    assert obs.metrics.counter_value("fallback.wire_demotions") == 1
    assert plan.config.wire_dtype == "native"
    # Subsequent calls run the native wire: bit-identical to a native plan.
    want = np.asarray(_slab(dict(comm_method=dfft.CommMethod.ALL2ALL))
                      .exec_r2c(x))
    np.testing.assert_array_equal(np.asarray(plan.exec_r2c(x)), want)


# ---------------------------------------------------------------------------
# zero-overhead pin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name, kw, seq", RENDERINGS[:4],
                         ids=[r[0] for r in RENDERINGS[:4]])
def test_hlo_byte_identical_when_off(name, kw, seq, devices, monkeypatch):
    """guards="off" + unset $DFFT_FAULT_SPEC compiles byte-identical HLO
    before, during-removal and after a fault-injected guarded build — so
    the default path carries zero resilience ops. (The cross-commit half
    of the pin — op-graph identity vs the actual pre-PR renderings — was
    verified at development time; this keeps it from regressing.)"""
    from distributedfft_tpu.analysis import hloscan

    def text():
        return hloscan.compiled_text(_slab(kw, seq), "forward")

    before = text()
    monkeypatch.setenv(inject.ENV_VAR, "wire:bitflip")
    guarded_plan = _slab(kw, seq, guards_mode="check")
    gtxt = hloscan.compiled_text(guarded_plan, "forward")
    assert gtxt != before  # the guarded+injected build is not vacuous
    monkeypatch.delenv(inject.ENV_VAR)
    after = text()
    # The op-graph fingerprint — the byte-identity currency dfft-verify's
    # pins use; source-location tables may differ (the guarded build
    # traced through other lines), the op graph may not.
    assert hloscan.op_graph_fingerprint(after) == \
        hloscan.op_graph_fingerprint(before)


def test_bitflip_changes_exactly_one_element(devices, monkeypatch):
    monkeypatch.setenv(inject.ENV_VAR, "wire:bitflip@seed=5")
    x = np.random.default_rng(0).random((4, 8)).astype(np.float32)
    y = np.asarray(jax.jit(lambda v: inject.taint_wire(v, "test"))(x))
    diff = np.nonzero((y != x).ravel())[0]
    assert list(diff) == [5]  # seed-keyed, exactly one element


# ---------------------------------------------------------------------------
# fallback ladder
# ---------------------------------------------------------------------------

def test_ladder_demotes_one_rung_per_failure(devices, monkeypatch):
    """ring fails -> opt1; opt1 fails -> default; result correct; each
    failure walked exactly one rung."""
    from distributedfft_tpu.models import slab as slab_mod
    from distributedfft_tpu.parallel import transpose as tr

    def ring_boom(*a, **kw):
        raise RuntimeError("simulated ring lowering failure")

    real_a2a = tr.all_to_all_transpose

    def opt1_boom(x, axis_name, split, concat, *, realigned=False,
                  wire="native"):
        if realigned:
            raise RuntimeError("simulated realigned-pack failure")
        return real_a2a(x, axis_name, split, concat, realigned=realigned,
                        wire=wire)

    monkeypatch.setattr(slab_mod, "ring_transpose", ring_boom)
    monkeypatch.setattr(slab_mod, "all_to_all_transpose", opt1_boom)
    plan = _slab(dict(send_method=dfft.SendMethod.RING), "ZY_Then_X")
    x = _input(plan)
    got = np.asarray(plan.exec_r2c(x))
    assert obs.metrics.counter_value("fallback.demotions") == 2
    assert obs.metrics.counter_value("fallback.send_demotions") == 1
    assert obs.metrics.counter_value("fallback.opt_demotions") == 1
    assert plan.config.send_method is dfft.SendMethod.SYNC
    assert plan.config.opt == 0
    want = np.asarray(_slab(dict(comm_method=dfft.CommMethod.ALL2ALL))
                      .exec_r2c(x))
    np.testing.assert_array_equal(got, want)


def test_default_rendering_errors_propagate(devices, monkeypatch):
    """A default-config plan has zero rungs: its errors are never
    retried or masked by the ladder."""
    from distributedfft_tpu.models import slab as slab_mod

    def boom(*a, **kw):
        raise RuntimeError("genuine failure")

    monkeypatch.setattr(slab_mod, "all_to_all_transpose", boom)
    plan = _slab(dict(comm_method=dfft.CommMethod.ALL2ALL))
    with pytest.raises(RuntimeError, match="genuine failure"):
        plan.exec_r2c(_input(plan))
    assert obs.metrics.counter_value("fallback.demotions") == 0


def test_ladder_disabled_by_env(devices, monkeypatch):
    monkeypatch.setenv("DFFT_FALLBACK", "off")
    from distributedfft_tpu.models import slab as slab_mod
    monkeypatch.setattr(slab_mod, "ring_transpose",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("ring failure")))
    plan = _slab(dict(send_method=dfft.SendMethod.RING), "Z_Then_YX")
    with pytest.raises(RuntimeError, match="ring failure"):
        plan.exec_r2c(_input(plan))
    assert obs.metrics.counter_value("fallback.demotions") == 0


def test_demotion_stamps_wisdom_and_reads_as_miss(tmp_path, devices,
                                                  monkeypatch):
    wpath = str(tmp_path / "w.json")
    from distributedfft_tpu.models import slab as slab_mod
    monkeypatch.setattr(slab_mod, "ring_transpose",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("ring failure")))
    plan = _slab(dict(send_method=dfft.SendMethod.RING,
                      wisdom_path=wpath), "Z_Then_YX")
    plan.exec_r2c(_input(plan))  # demotes, stamps
    assert obs.metrics.counter_value("wisdom.demotion_stamps") >= 1
    store = wisdom.WisdomStore(wpath)
    key = wisdom.plan_key("slab", G16.shape, False, dfft.SlabPartition(8),
                          pm.FFTNorm.NONE,
                          sequence=pm.SlabSequence.Z_THEN_YX)
    rec = store.lookup(key, "comm")
    assert rec and rec.get("demoted") and rec["demoted_rung"] == "send"
    # A stamped record reads as a miss: the store stops recommending it.
    folded, reason = wisdom._comm_hit_fold(dfft.Config(), rec, False, 2e-2)
    assert folded is None and "demoted" in reason


def test_demotion_stamp_ttl_expiry(monkeypatch):
    """ISSUE 8 satellite: a transient failure must not PERMANENTLY demote
    a cell — stamps age out after $DFFT_DEMOTION_TTL_S (default 24 h),
    after which the record reads as a hit again (with an obs notice)."""
    fresh = {"comm_method": "All2All", "opt": 0, "wire_dtype": "native",
             "demoted": True, "demoted_rung": "send",
             "demoted_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime())}
    old = dict(fresh, demoted_at="2020-01-01T00:00:00Z")
    base = dfft.Config()
    # fresh stamp, default TTL: still demoted
    assert wisdom.demotion_active(fresh)
    folded, reason = wisdom._comm_hit_fold(base, fresh, False, 2e-2)
    assert folded is None and "demoted" in reason
    # ancient stamp, default TTL: EXPIRED — reads as a hit again
    assert not wisdom.demotion_active(old)
    folded, reason = wisdom._comm_hit_fold(base, old, False, 2e-2)
    assert folded is not None and reason is None
    assert obs.metrics.counter_value("wisdom.demotion_expired") >= 1
    # the wire slot shares the expiry
    wrec = {"wire_dtype": "native", "demoted": True,
            "demoted_at": "2020-01-01T00:00:00Z"}
    folded, reason = wisdom._wire_hit_fold(base, wrec, 2e-2)
    assert folded is not None and reason is None
    # TTL <= 0 restores the permanent-stamp behavior
    monkeypatch.setenv(wisdom.DEMOTION_TTL_ENV, "0")
    assert wisdom.demotion_active(old)
    # a tiny TTL expires even a fresh stamp
    monkeypatch.setenv(wisdom.DEMOTION_TTL_ENV, "0.000001")
    time.sleep(0.01)
    assert not wisdom.demotion_active(fresh)
    # missing/unparseable demoted_at never expires (conservative)
    monkeypatch.setenv(wisdom.DEMOTION_TTL_ENV, "1")
    assert wisdom.demotion_active({"demoted": True})
    assert wisdom.demotion_active({"demoted": True, "demoted_at": "bogus"})
    # an unstamped record is never "demotion active"
    assert not wisdom.demotion_active({"comm_method": "All2All"})
    assert not wisdom.demotion_active(None)


def test_guard_violation_not_retried_by_ladder(devices, monkeypatch):
    """Enforce-mode GuardViolation propagates without walking the ladder
    (the guard's verdict IS the answer, not a rendering failure)."""
    monkeypatch.setenv(inject.ENV_VAR, "wire:nan")
    plan = _slab(dict(send_method=dfft.SendMethod.RING), "Z_Then_YX",
                 guards_mode="enforce")
    with pytest.raises(GuardViolation):
        plan.exec_r2c(_input(plan))
    assert obs.metrics.counter_value("fallback.demotions") == 0


# ---------------------------------------------------------------------------
# wisdom advisory lock: killed holders, hung holders, stale breaking
# ---------------------------------------------------------------------------

_HOLDER = textwrap.dedent("""
    import fcntl, sys, time
    lock = open(sys.argv[1], "a")
    fcntl.flock(lock, fcntl.LOCK_EX)
    print("HELD", flush=True)
    time.sleep(120)
""")


def _spawn_holder(lock_path):
    proc = subprocess.Popen([sys.executable, "-c", _HOLDER, lock_path],
                            stdout=subprocess.PIPE, text=True)
    assert proc.stdout.readline().strip() == "HELD"
    return proc


def test_killed_lock_holder_never_blocks_next_writer(tmp_path):
    """Regression (satellite): a holder killed mid-read-merge-replace
    leaves its .lock FILE behind; the next writer must proceed (the
    kernel released the flock with the fd — the leftover file is inert,
    not a lock)."""
    store = wisdom.WisdomStore(str(tmp_path / "w.json"))
    holder = _spawn_holder(store.path + ".lock")
    os.kill(holder.pid, signal.SIGKILL)
    holder.wait()
    assert os.path.exists(store.path + ".lock")  # the leftover file
    t0 = time.monotonic()
    assert store.record("k", "local_fft", {"fft_backend": "xla"})
    assert time.monotonic() - t0 < 5.0  # no lock wait
    assert store.lookup("k", "local_fft")["fft_backend"] == "xla"


def test_hung_lock_holder_survived_via_timeout(tmp_path, monkeypatch):
    """A holder that is alive but hung must not wedge the writer: the
    acquisition times out and the write lands unlocked (atomic)."""
    monkeypatch.setenv("DFFT_WISDOM_LOCK_TIMEOUT_S", "0.4")
    monkeypatch.setenv("DFFT_WISDOM_LOCK_STALE_S", "1000")
    store = wisdom.WisdomStore(str(tmp_path / "w.json"))
    holder = _spawn_holder(store.path + ".lock")
    try:
        t0 = time.monotonic()
        assert store.record("k", "local_fft", {"fft_backend": "xla"})
        assert 0.3 < time.monotonic() - t0 < 5.0
        assert obs.metrics.counter_value("wisdom.lock_timeouts") == 1
        assert store.lookup("k", "local_fft")["fft_backend"] == "xla"
    finally:
        holder.kill()
        holder.wait()


def test_stale_lock_broken_under_injection(tmp_path, monkeypatch):
    """$DFFT_FAULT_SPEC=wisdom:stale-lock simulates the hung holder; an
    OLD lock file is broken (age-based) and the write survives."""
    monkeypatch.setenv(inject.ENV_VAR, "wisdom:stale-lock")
    monkeypatch.setenv("DFFT_WISDOM_LOCK_TIMEOUT_S", "0.4")
    monkeypatch.setenv("DFFT_WISDOM_LOCK_STALE_S", "5")
    store = wisdom.WisdomStore(str(tmp_path / "w.json"))
    lock_path = store.path + ".lock"
    with open(lock_path, "w"):
        pass
    old = time.time() - 120
    os.utime(lock_path, (old, old))
    assert store.record("k", "local_fft", {"fft_backend": "xla"})
    assert obs.metrics.counter_value("wisdom.lock_breaks") == 1
    assert store.lookup("k", "local_fft")["fft_backend"] == "xla"


# ---------------------------------------------------------------------------
# coordinator backoff + autotune cell timeouts
# ---------------------------------------------------------------------------

def test_coordinator_backoff_retries_then_succeeds(monkeypatch):
    from distributedfft_tpu.parallel import multihost as mh
    calls = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    monkeypatch.setenv(inject.ENV_VAR, "coordinator:down:2")
    monkeypatch.setenv("DFFT_COORD_BACKOFF_S", "0.01")
    monkeypatch.setattr(mh, "_INITIALIZED", False)
    try:
        mh.maybe_initialize(coordinator_address="stub:1", num_processes=1,
                            process_id=0)
        assert len(calls) == 1  # attempts 0/1 injected-failed, 2 connected
        assert obs.metrics.counter_value(
            "inject.coordinator_failures") == 2
        assert obs.metrics.counter_value(
            "multihost.connect_retries") == 2
    finally:
        monkeypatch.setattr(mh, "_INITIALIZED", False)


def test_coordinator_down_fails_loudly_after_retries(monkeypatch):
    from distributedfft_tpu.parallel import multihost as mh
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: None)
    monkeypatch.setenv(inject.ENV_VAR, "coordinator:down")  # unbounded
    monkeypatch.setenv("DFFT_COORD_RETRIES", "3")
    monkeypatch.setenv("DFFT_COORD_BACKOFF_S", "0.01")
    monkeypatch.setattr(mh, "_INITIALIZED", False)
    with pytest.raises(inject.SimulatedFault):
        mh.maybe_initialize(coordinator_address="stub:1", num_processes=1,
                            process_id=0)
    assert mh._INITIALIZED is False


def test_autotune_cell_timeout_degrades_to_survivors(monkeypatch):
    """One hung candidate is abandoned on wall-clock; the survivors
    decide the race."""
    from distributedfft_tpu.testing import autotune as at
    # Generous enough for the xla cell's first-compile; far under the
    # injected 30 s hang.
    monkeypatch.setenv("DFFT_AUTOTUNE_CELL_TIMEOUT_S", "5")
    real = at._measure

    def hang_matmul(shape, backend, *a, **kw):
        if backend != "xla":
            time.sleep(30)
        return real(shape, backend, *a, **kw)

    monkeypatch.setattr(at, "_measure", hang_matmul)
    ranked = at.autotune_local_fft((8, 8, 8), k=8, repeats=2, inner=1,
                                   backends=("xla", "matmul"))
    # The hung candidates are abandoned on wall-clock; the xla survivor
    # ranks first and is never timed out (its timing may still read
    # degenerate on a noisy tiny shape — that is the chaintimer's own
    # gate, not the timeout's).
    assert ranked[0].backend == "xla"
    assert "CellTimeout" not in (ranked[0].error or "")
    hung = [c for c in ranked if c.backend == "matmul"]
    assert hung and all("CellTimeout" in (c.error or "") for c in hung)
    assert obs.metrics.counter_value("autotune.cell_timeouts") >= 1


def test_injected_cell_hang_times_out(monkeypatch):
    from distributedfft_tpu.testing import autotune as at
    monkeypatch.setenv(inject.ENV_VAR, "autotune:hang:30")
    monkeypatch.setenv("DFFT_AUTOTUNE_CELL_TIMEOUT_S", "0.3")
    ranked = at.autotune_local_fft((8, 8, 8), k=2, repeats=1, inner=1,
                                   backends=("xla",))
    assert not ranked[0].ok and "CellTimeout" in ranked[0].error
    assert obs.metrics.counter_value("inject.cell_hangs") >= 1


# ---------------------------------------------------------------------------
# selftest + CLI + explain surfaces
# ---------------------------------------------------------------------------

def test_selftest_passes_on_healthy_plan(devices, capsys):
    from distributedfft_tpu.resilience.selftest import run_selftest
    plan = _slab(dict(comm_method=dfft.CommMethod.ALL2ALL))
    r = run_selftest(plan)
    assert r["ok"] and r["reference"] is not None
    assert "selftest: PASS" in capsys.readouterr().out


def test_selftest_fails_under_injection(devices, capsys, monkeypatch):
    from distributedfft_tpu.resilience.selftest import run_selftest
    monkeypatch.setenv(inject.ENV_VAR, "wire:scale:0.5")
    plan = _slab(dict(comm_method=dfft.CommMethod.ALL2ALL))
    r = run_selftest(plan)
    assert not r["ok"]
    assert "selftest: FAIL" in capsys.readouterr().out
    assert obs.metrics.counter_value("selftest.failures") == 1


def test_cli_selftest_gate(devices, capsys, monkeypatch):
    from distributedfft_tpu.cli import slab as cli_slab
    argv = ["-nx", "16", "-ny", "16", "-nz", "16", "-p", "8", "-t", "3",
            "--selftest", "-comm", "All2All"]
    assert cli_slab.main(argv) == 0
    assert "selftest: PASS" in capsys.readouterr().out
    monkeypatch.setenv(inject.ENV_VAR, "wire:nan")
    assert cli_slab.main(argv) == 1
    out = capsys.readouterr()
    assert "selftest: FAIL" in out.out


def test_explain_reports_resilience_posture(devices, capsys):
    from distributedfft_tpu.obs import explain
    assert explain.main(["--kind", "slab", "-nx", "16", "-ny", "16",
                         "-nz", "16", "-p", "8", "-snd", "Ring",
                         "-wire", "bf16", "--guards", "check",
                         "--no-compile"]) == 0
    out = capsys.readouterr().out
    assert "resilience:" in out
    assert "guards: check (Config.guards)" in out
    assert "forward check: parseval, tolerance" in out
    assert "wire drift probe: budget" in out
    assert "fallback ladder: [send]" in out
    assert "demotion stamps: none" in out


def test_explain_reports_demotion_stamp(tmp_path, devices, capsys,
                                        monkeypatch):
    wpath = str(tmp_path / "w.json")
    from distributedfft_tpu.models import slab as slab_mod
    monkeypatch.setattr(slab_mod, "ring_transpose",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("ring failure")))
    plan = _slab(dict(send_method=dfft.SendMethod.RING,
                      wisdom_path=wpath), "Z_Then_YX")
    plan.exec_r2c(_input(plan))
    monkeypatch.undo()
    from distributedfft_tpu.obs import explain
    assert explain.main(["--kind", "slab", "-nx", "16", "-ny", "16",
                         "-nz", "16", "-p", "8", "-snd", "Ring",
                         "-s", "Z_Then_YX", "--wisdom", wpath,
                         "--no-compile"]) == 0
    out = capsys.readouterr().out
    assert "demotion stamp [comm]: rung send" in out


def test_obs_event_log_carries_injection_and_guard_events(tmp_path, devices,
                                                          monkeypatch):
    d = str(tmp_path / "obs")
    obs.enable(d)
    try:
        monkeypatch.setenv(inject.ENV_VAR, "wire:nan")
        plan = _slab(dict(comm_method=dfft.CommMethod.ALL2ALL),
                     guards_mode="check")
        plan.exec_r2c(_input(plan))
    finally:
        obs.reset_enablement()
    assert obs.validate_events_dir(d) > 0
    names = set()
    for fn in os.listdir(d):
        with open(os.path.join(d, fn)) as f:
            names |= {json.loads(ln)["name"] for ln in f if ln.strip()}
    assert "inject.wire_fault" in names
    assert "guard.violation" in names
