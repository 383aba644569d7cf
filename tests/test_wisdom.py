"""Persistent plan-wisdom store (utils/wisdom.py): hit/miss/record
round-trips, key sensitivity, corruption degradation, and the construction
contract — a wisdom hit must skip the timing race entirely (in-process via
a counting monkeypatch, and across processes via subprocesses sharing one
$DFFT_WISDOM store, the acceptance criterion's "autotune once, reuse
everywhere" shape)."""

import dataclasses as dc
import importlib
import json
import os
import subprocess
import sys
import textwrap

import pytest

import distributedfft_tpu as dfft
from distributedfft_tpu import params as pm
from distributedfft_tpu.utils import wisdom

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VALID_LOCAL = {"fft_backend": "xla", "mxu_precision": None,
               "mxu_direct_max": None}
VALID_COMM = {"comm_method": "All2All", "comm_method2": None, "opt": 1,
              "send_method": None, "streams_chunks": None}


def _no_ts(rec):
    """Drop the additive ``recorded_at`` provenance stamp ``record()``
    applies (tests/test_obs.py pins the stamp itself), so round-trip
    equality checks keep comparing the measured payload only."""
    rec = dict(rec or {})
    rec.pop("recorded_at", None)
    return rec


# ---------------------------------------------------------------------------
# store round-trip
# ---------------------------------------------------------------------------

def test_store_hit_miss_record_roundtrip(tmp_path):
    store = wisdom.WisdomStore(str(tmp_path / "w.json"))
    key = wisdom.local_key((8, 8, 8), False)
    assert store.lookup(key, "local_fft") is None  # miss on absent file
    assert store.record(key, "local_fft", VALID_LOCAL)
    assert _no_ts(store.lookup(key, "local_fft")) == VALID_LOCAL  # hit
    # A second slot under the same key merges, never clobbers.
    assert store.record(key, "comm", VALID_COMM)
    assert _no_ts(store.lookup(key, "local_fft")) == VALID_LOCAL
    assert _no_ts(store.lookup(key, "comm")) == VALID_COMM
    # Re-recording a slot overwrites just that slot.
    newer = dict(VALID_LOCAL, fft_backend="matmul")
    assert store.record(key, "local_fft", newer)
    assert _no_ts(store.lookup(key, "local_fft")) == newer
    assert _no_ts(store.lookup(key, "comm")) == VALID_COMM
    # On-disk format is the versioned schema.
    raw = json.loads((tmp_path / "w.json").read_text())
    assert raw["version"] == wisdom.WISDOM_VERSION
    assert key in raw["entries"]


def test_open_store_resolution(tmp_path, monkeypatch):
    monkeypatch.delenv(wisdom.ENV_VAR, raising=False)
    assert wisdom.open_store(None, True) is None       # nothing configured
    assert wisdom.open_store("/x/w.json", False) is None  # disabled wins
    p = str(tmp_path / "env.json")
    monkeypatch.setenv(wisdom.ENV_VAR, p)
    assert wisdom.open_store(None, True).path == p     # env default
    explicit = str(tmp_path / "cfg.json")
    assert wisdom.open_store(explicit, True).path == explicit  # path wins
    cfg = dfft.Config(wisdom_path=explicit)
    assert wisdom.store_for_config(cfg).path == explicit
    assert wisdom.store_for_config(dc.replace(cfg, use_wisdom=False)) is None


# ---------------------------------------------------------------------------
# keys: everything that can change a winner must change the key
# ---------------------------------------------------------------------------

def test_plan_key_sensitivity():
    base = dict(kind="slab", global_shape=(16, 16, 16), double_prec=False,
                partition=pm.SlabPartition(8), norm=pm.FFTNorm.NONE)

    def key(**over):
        kw = dict(base)
        kw.update(over)
        return wisdom.plan_key(kw.pop("kind"), kw.pop("global_shape"),
                               kw.pop("double_prec"), kw.pop("partition"),
                               kw.pop("norm"), **kw)

    k0 = key()
    assert key() == k0  # deterministic
    assert key(double_prec=True) != k0                      # dtype
    assert key(partition=pm.SlabPartition(4)) != k0         # mesh shape
    assert key(global_shape=(16, 16, 32)) != k0             # shape
    assert key(kind="pencil",
               partition=pm.PencilPartition(4, 2)) != k0    # decomposition
    assert key(norm=pm.FFTNorm.ORTHO) != k0                 # norm
    assert key(sequence="Z_Then_YX") != k0                  # slab sequence
    assert key(variant="x") != k0                           # batched shard
    assert key(transform="c2c") != k0
    assert key(dims=2) != k0                 # partial-transform depth
    # Pencil grids with equal rank counts stay distinct.
    assert (key(partition=pm.PencilPartition(4, 2))
            != key(partition=pm.PencilPartition(2, 4)))
    # local_key (bare single-device race) is its own namespace.
    assert wisdom.local_key((16, 16, 16), False) != k0
    assert (wisdom.local_key((16, 16, 16), False)
            != wisdom.local_key((16, 16, 16), True))


# ---------------------------------------------------------------------------
# degradation: corrupt / partial / stale stores are misses, never errors
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("payload", [
    "{not json at all",
    "",
    json.dumps([1, 2, 3]),
    json.dumps({"version": 999, "entries": {"k": {}}}),  # version mismatch
    json.dumps({"version": wisdom.WISDOM_VERSION, "entries": []}),
    json.dumps({"version": wisdom.WISDOM_VERSION}),      # missing entries
])
def test_corrupt_store_reads_empty_and_recovers(tmp_path, payload):
    p = tmp_path / "w.json"
    p.write_text(payload)
    store = wisdom.WisdomStore(str(p))
    assert store.load() == {"version": wisdom.WISDOM_VERSION, "entries": {}}
    key = wisdom.local_key((8, 8, 8), False)
    assert store.lookup(key, "local_fft") is None
    # Recording over the damaged file repairs it in place.
    assert store.record(key, "local_fft", VALID_LOCAL)
    assert _no_ts(store.lookup(key, "local_fft")) == VALID_LOCAL


def test_partial_entry_damage_is_per_key(tmp_path):
    p = tmp_path / "w.json"
    key_bad, key_good = "kb", "kg"
    p.write_text(json.dumps({
        "version": wisdom.WISDOM_VERSION,
        "entries": {key_bad: "not-a-dict",
                    key_good: {"local_fft": VALID_LOCAL}}}))
    store = wisdom.WisdomStore(str(p))
    assert store.lookup(key_bad, "local_fft") is None     # damaged: miss
    assert store.lookup(key_good, "local_fft") == VALID_LOCAL  # others live
    # Recording into the damaged key replaces it without touching the rest.
    assert store.record(key_bad, "comm", VALID_COMM)
    assert _no_ts(store.lookup(key_bad, "comm")) == VALID_COMM
    assert store.lookup(key_good, "local_fft") == VALID_LOCAL


def test_stale_record_fields_are_a_miss():
    # A backend this build doesn't know, or out-of-domain knobs, must read
    # as a miss (re-measure), not an error.
    assert not wisdom._valid_local_rec({"fft_backend": "cufft"})
    assert not wisdom._valid_local_rec({"fft_backend": "xla",
                                        "mxu_precision": "bogus"})
    assert not wisdom._valid_local_rec({"fft_backend": "xla",
                                        "mxu_direct_max": -3})
    assert wisdom._valid_local_rec(VALID_LOCAL)
    cfg = dfft.Config()
    with pytest.raises((KeyError, TypeError, ValueError)):
        wisdom._fold_comm_rec(cfg, {"comm_method": "CarrierPigeon"})
    with pytest.raises((KeyError, TypeError, ValueError)):
        wisdom._fold_comm_rec(cfg, dict(VALID_COMM, opt=7))
    out = wisdom._fold_comm_rec(cfg, VALID_COMM)
    assert out.comm_method is pm.CommMethod.ALL2ALL and out.opt == 1


def test_unreadable_store_degrades_on_write(tmp_path):
    # A store path whose directory cannot be created: record returns False,
    # lookup None — wisdom can cost a redundant measurement, never an error.
    blocker = tmp_path / "file"
    blocker.write_text("")
    store = wisdom.WisdomStore(str(blocker / "sub" / "w.json"))
    key = wisdom.local_key((8, 8, 8), False)
    assert store.lookup(key, "local_fft") is None
    assert store.record(key, "local_fft", VALID_LOCAL) is False


# ---------------------------------------------------------------------------
# version migration: v1 stores load without error (schema bumped to 2 when
# the RING variant joined the comm race)
# ---------------------------------------------------------------------------

def test_v1_store_migrates_not_errors(tmp_path):
    """A version-1 store loads as a migrated view: local_fft records are
    variant-agnostic and carry over verbatim; comm records were winners of
    a race that never saw the RING rendering, so they read as misses
    (re-raced once) instead of being trusted or erroring."""
    p = tmp_path / "w.json"
    p.write_text(json.dumps({
        "version": 1,
        "entries": {"k1": {"local_fft": VALID_LOCAL, "comm": VALID_COMM},
                    "k2": {"comm": VALID_COMM},
                    "k3": "damaged"}}))
    store = wisdom.WisdomStore(str(p))
    data = store.load()
    assert data["version"] == wisdom.WISDOM_VERSION
    assert store.lookup("k1", "local_fft") == VALID_LOCAL  # carried over
    assert store.lookup("k1", "comm") is None              # pre-ring: miss
    assert store.lookup("k2", "comm") is None
    # The next record persists the migrated store as v2 on disk.
    assert store.record("k4", "comm", VALID_COMM)
    raw = json.loads(p.read_text())
    assert raw["version"] == wisdom.WISDOM_VERSION
    assert raw["entries"]["k1"] == {"local_fft": VALID_LOCAL}
    assert "comm" not in raw["entries"].get("k1", {})
    assert _no_ts(raw["entries"]["k4"]["comm"]) == VALID_COMM


def test_ring_record_roundtrip():
    """A recorded RING winner folds back into a Config (send_method RING,
    no chunk count) and survives the multi-controller broadcast encoding."""
    from distributedfft_tpu.testing.autotune import CommCandidate
    cand = CommCandidate(pm.CommMethod.ALL2ALL, None, 0,
                         send=pm.SendMethod.RING)
    rec = wisdom.comm_record(cand)
    assert rec["send_method"] == "Ring" and rec["streams_chunks"] is None
    out = wisdom._fold_comm_rec(dfft.Config(), rec)
    assert out.send_method is pm.SendMethod.RING
    assert out.streams_chunks is None
    folded = dc.replace(dfft.Config(), send_method=pm.SendMethod.RING)
    back = wisdom._broadcast_comm_hit(folded, dfft.Config())
    assert back.send_method is pm.SendMethod.RING


# ---------------------------------------------------------------------------
# concurrency: N processes sharing one store cannot corrupt it or lose
# each other's records (atomic replace + advisory lock)
# ---------------------------------------------------------------------------

_WISDOM_PY = os.path.join(REPO, "distributedfft_tpu", "utils", "wisdom.py")

_WRITER = textwrap.dedent("""
    import importlib.util, os, sys
    spec = importlib.util.spec_from_file_location("w", sys.argv[1])
    w = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(w)
    store = w.WisdomStore(os.environ["DFFT_WISDOM"])
    wid = sys.argv[2]
    for i in range(8):
        assert store.record(f"key-{wid}-{i}", "local_fft",
                            {"fft_backend": "xla", "writer": wid})
    print("WROTE", flush=True)
""")


def test_concurrent_fresh_process_writers(tmp_path):
    """Four fresh processes hammer one $DFFT_WISDOM store concurrently;
    the advisory lock serializes the read-merge-replace window, so every
    record lands and the final file is valid versioned JSON (no torn or
    interleaved writes). The writer loads wisdom.py standalone — the lock
    contract must not depend on the package (or jax) being imported."""
    env = dict(os.environ)
    env["DFFT_WISDOM"] = str(tmp_path / "w.json")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _WRITER, _WISDOM_PY, str(wid)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for wid in range(4)]
    for pr in procs:
        out, err = pr.communicate(timeout=120)
        assert pr.returncode == 0 and "WROTE" in out, err[-800:]
    raw = json.loads((tmp_path / "w.json").read_text())
    assert raw["version"] == wisdom.WISDOM_VERSION
    assert len(raw["entries"]) == 32  # 4 writers x 8 keys, none lost
    store = wisdom.WisdomStore(env["DFFT_WISDOM"])
    for wid in range(4):
        for i in range(8):
            rec = store.lookup(f"key-{wid}-{i}", "local_fft")
            assert rec is not None and rec["writer"] == str(wid)


# ---------------------------------------------------------------------------
# construction-time resolution
# ---------------------------------------------------------------------------

def test_concrete_config_passes_through_untouched():
    # Concrete: every measured field and the backend named (a Config()
    # leaves the backend to the platform default, tests/
    # test_platform_default.py).
    cfg = dfft.Config(fft_backend="xla")
    out = wisdom.resolve_config("slab", dfft.GlobalSize(8, 8, 8),
                                pm.SlabPartition(1), cfg)
    assert out is cfg  # the zero-cost common case: no store I/O, no copy


def _counting_local_race(monkeypatch, backends=("xla",)):
    """Monkeypatch the local-FFT race with a counter (restricted to cheap
    backends so the test measures wiring, not every kernel). The chain
    timer is stubbed to a constant: at the tiny k these tests use, a real
    (t_K - t_1) pair on a noisy CPU timer occasionally comes out
    nonpositive, which the autotuner correctly reports as degenerate
    (ok=False) — and wisdom then correctly refuses to record an unmeasured
    winner. The tests verify wiring, not timing."""
    from distributedfft_tpu.testing import autotune as at
    from distributedfft_tpu.testing import chaintimer
    calls = []
    real = at.autotune_local_fft

    def counting(shape, *a, **kw):
        calls.append(shape)
        kw["backends"] = backends
        return real(shape, *a, **kw)

    monkeypatch.setattr(at, "autotune_local_fft", counting)
    monkeypatch.setattr(chaintimer, "median_pair_diff_ms",
                        lambda fn1, fnK, x, k, repeats, inner: (0.25, 1e-3))
    return calls


def test_plan_auto_races_once_then_hits(tmp_path, monkeypatch):
    monkeypatch.setenv("DFFT_WISDOM_K", "2")
    calls = _counting_local_race(monkeypatch)
    wpath = str(tmp_path / "w.json")
    cfg = dfft.Config(fft_backend="auto", wisdom_path=wpath)
    g = dfft.GlobalSize(8, 8, 8)
    p1 = dfft.SlabFFTPlan(g, pm.SlabPartition(1), cfg)
    assert len(calls) == 1  # miss: raced and recorded
    assert p1.config.fft_backend == "xla"
    # Second construction of the same plan config: wisdom hit, ZERO races.
    p2 = dfft.SlabFFTPlan(g, pm.SlabPartition(1), cfg)
    assert len(calls) == 1
    assert p2.config.fft_backend == p1.config.fft_backend
    # A different shape is a different key: races again.
    dfft.SlabFFTPlan(dfft.GlobalSize(8, 8, 16), pm.SlabPartition(1), cfg)
    assert len(calls) == 2
    # use_wisdom=False (--no-wisdom): no store, races per construction.
    off = dc.replace(cfg, use_wisdom=False)
    dfft.SlabFFTPlan(g, pm.SlabPartition(1), off)
    dfft.SlabFFTPlan(g, pm.SlabPartition(1), off)
    assert len(calls) == 4
    # ... and the store was never consulted nor written by the off runs:
    # the winner recorded earlier still reads back verbatim.
    rec = wisdom.WisdomStore(wpath).lookup(
        wisdom.plan_key("slab", g.shape, False, pm.SlabPartition(1),
                        pm.FFTNorm.NONE,
                        sequence=pm.SlabSequence.ZY_THEN_X), "local_fft")
    assert rec is not None and rec["fft_backend"] == "xla"


def test_comm_auto_races_once_then_hits(tmp_path, monkeypatch):
    from distributedfft_tpu.testing import autotune as at
    calls = []
    real = at.autotune_comm

    def counting(*a, **kw):
        calls.append(a[0])
        kw["iterations"], kw["warmup"] = 1, 0  # wiring test, not a bench
        return real(*a, **kw)

    monkeypatch.setattr(at, "autotune_comm", counting)
    wpath = str(tmp_path / "w.json")
    cfg = dfft.Config(comm_method="auto", wisdom_path=wpath)
    g = dfft.GlobalSize(16, 16, 16)
    p1 = dfft.SlabFFTPlan(g, pm.SlabPartition(8), cfg)
    assert len(calls) == 1
    assert isinstance(p1.config.comm_method, pm.CommMethod)
    p2 = dfft.SlabFFTPlan(g, pm.SlabPartition(8), cfg)
    assert len(calls) == 1  # hit: zero races
    assert p2.config.comm_method is p1.config.comm_method
    assert p2.config.opt == p1.config.opt
    assert p2.config.send_method is p1.config.send_method
    # Single-rank plans issue no collectives: defaults, no race, no store.
    p3 = dfft.SlabFFTPlan(dfft.GlobalSize(8, 8, 8), pm.SlabPartition(1), cfg)
    assert len(calls) == 1
    assert isinstance(p3.config.comm_method, pm.CommMethod)


def test_stale_stored_record_remeasures(tmp_path, monkeypatch):
    monkeypatch.setenv("DFFT_WISDOM_K", "2")
    calls = _counting_local_race(monkeypatch)
    wpath = str(tmp_path / "w.json")
    g = dfft.GlobalSize(8, 8, 8)
    key = wisdom.plan_key("slab", g.shape, False, pm.SlabPartition(1),
                          pm.FFTNorm.NONE,
                          sequence=pm.SlabSequence.ZY_THEN_X)
    store = wisdom.WisdomStore(wpath)
    store.record(key, "local_fft", {"fft_backend": "cufft"})  # not ours
    cfg = dfft.Config(fft_backend="auto", wisdom_path=wpath)
    plan = dfft.SlabFFTPlan(g, pm.SlabPartition(1), cfg)
    assert len(calls) == 1  # stale record = miss -> re-measured
    assert plan.config.fft_backend == "xla"
    assert store.lookup(key, "local_fft")["fft_backend"] == "xla"  # healed


def test_comm_auto_owns_send_axis(tmp_path):
    """params.py contract: comm 'auto' owns the whole comm x send x opt x
    chunks choice. A recorded winner whose send axis is SYNC (send_method
    None in the record) must override an explicit STREAMS send_method —
    folding the measured program, not an unmeasured comm x STREAMS mix."""
    wpath = str(tmp_path / "w.json")
    g = dfft.GlobalSize(16, 16, 16)
    key = wisdom.plan_key("slab", g.shape, False, pm.SlabPartition(8),
                          pm.FFTNorm.NONE,
                          sequence=pm.SlabSequence.ZY_THEN_X)
    wisdom.WisdomStore(wpath).record(key, "comm", VALID_COMM)
    cfg = dfft.Config(comm_method="auto",
                      send_method=pm.SendMethod.STREAMS, streams_chunks=8,
                      wisdom_path=wpath)
    plan = dfft.SlabFFTPlan(g, pm.SlabPartition(8), cfg)
    assert plan.config.comm_method is pm.CommMethod.ALL2ALL
    assert plan.config.send_method is pm.SendMethod.SYNC
    assert plan.config.streams_chunks is None


def test_comm_record_reflects_timed_base():
    """send=None candidates are timed on the BASE config's send method:
    a non-SYNC base (CLI --autotune-comm -snd Streams) must be recorded as
    the send method the measurement really used."""
    from distributedfft_tpu.testing.autotune import CommCandidate
    cand = CommCandidate(pm.CommMethod.ALL2ALL, None, 1)
    base = dfft.Config(send_method=pm.SendMethod.STREAMS, streams_chunks=8)
    rec = wisdom.comm_record(cand, base)
    assert rec["send_method"] == "Streams" and rec["streams_chunks"] == 8
    assert wisdom.comm_record(cand)["send_method"] is None  # SYNC base
    # An explicitly raced send axis always wins over the base.
    c2 = CommCandidate(pm.CommMethod.ALL2ALL, None, 0,
                       send=pm.SendMethod.STREAMS, chunks=4)
    assert wisdom.comm_record(c2, base)["streams_chunks"] == 4


def test_broadcast_comm_hit_roundtrip():
    """The multi-controller hit/miss agreement encoding: a folded Config
    survives the int-vector round-trip, and a miss stays a miss (so every
    process enters the collective race together)."""
    import dataclasses as dc
    base = dfft.Config()
    folded = dc.replace(base, comm_method=pm.CommMethod.PEER2PEER,
                        comm_method2=pm.CommMethod.ALL2ALL, opt=1,
                        send_method=pm.SendMethod.STREAMS, streams_chunks=4)
    out = wisdom._broadcast_comm_hit(folded, base)
    assert out.comm_method is pm.CommMethod.PEER2PEER
    assert out.comm_method2 is pm.CommMethod.ALL2ALL
    assert out.opt == 1
    assert out.send_method is pm.SendMethod.STREAMS
    assert out.streams_chunks == 4
    assert wisdom._broadcast_comm_hit(None, base) is None


def test_unresolved_auto_rejected_by_base_plan():
    with pytest.raises(ValueError, match="auto"):
        dfft.DistFFTPlan(dfft.GlobalSize(8, 8, 8), pm.SlabPartition(1),
                         dfft.Config(fft_backend="auto"))


# ---------------------------------------------------------------------------
# cross-process: autotune once, reuse everywhere (the acceptance shape)
# ---------------------------------------------------------------------------

_SEED = textwrap.dedent("""
    from distributedfft_tpu.testing import autotune as at
    from distributedfft_tpu.testing import chaintimer
    real = at.autotune_local_fft
    at.autotune_local_fft = (
        lambda shape, **kw: real(shape, **{**kw, "backends": ("xla",)}))
    # Constant timer: a real pair-diff at k=2 can be nonpositive on a noisy
    # CPU timer (degenerate -> ok=False -> nothing recorded), and this seed
    # must record.
    chaintimer.median_pair_diff_ms = (
        lambda fn1, fnK, x, k, repeats, inner: (0.25, 1e-3))
    import distributedfft_tpu as dfft
    from distributedfft_tpu import params as pm
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(8, 8, 8), pm.SlabPartition(1),
                            dfft.Config(fft_backend="auto"))
    assert plan.config.fft_backend == "xla", plan.config.fft_backend
    print("SEEDED", flush=True)
""")

_REUSE = textwrap.dedent("""
    from distributedfft_tpu.testing import autotune as at

    def boom(*a, **kw):
        raise AssertionError("timing race ran on a wisdom hit")

    at.autotune_local_fft = boom
    at.autotune_comm = boom
    import distributedfft_tpu as dfft
    from distributedfft_tpu import params as pm
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(8, 8, 8), pm.SlabPartition(1),
                            dfft.Config(fft_backend="auto"))
    assert plan.config.fft_backend == "xla", plan.config.fft_backend
    print("REUSED", flush=True)
""")


def test_fresh_process_auto_performs_zero_races(tmp_path):
    """Acceptance: a second construction of the same plan config in a FRESH
    process with fft_backend='auto' and $DFFT_WISDOM performs zero timing
    races (the reuse child replaces both autotuners with a bomb)."""
    env = dict(os.environ)
    env.update({"DFFT_WISDOM": str(tmp_path / "w.json"),
                "DFFT_WISDOM_K": "2", "JAX_PLATFORMS": "cpu"})

    def run(code):
        return subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              env=env, capture_output=True, text=True,
                              timeout=240)

    r1 = run(_SEED)
    assert r1.returncode == 0 and "SEEDED" in r1.stdout, r1.stderr[-800:]
    assert os.path.exists(env["DFFT_WISDOM"])
    r2 = run(_REUSE)
    assert r2.returncode == 0 and "REUSED" in r2.stdout, r2.stderr[-800:]


# ---------------------------------------------------------------------------
# CLI surface: all four executables accept --wisdom/--no-wisdom
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mod", ["slab", "pencil", "batched", "reference"])
def test_cli_accepts_wisdom_flags(mod):
    m = importlib.import_module(f"distributedfft_tpu.cli.{mod}")
    base = ["-nx", "8", "-ny", "8", "-nz", "8"]
    if mod == "pencil":
        base += ["-p1", "2", "-p2", "2"]
    args = m.build_parser().parse_args(base)
    assert args.wisdom is None and args.no_wisdom is False  # off by default
    args = m.build_parser().parse_args(
        base + ["--wisdom", "/tmp/w.json", "--no-wisdom"])
    assert args.wisdom == "/tmp/w.json" and args.no_wisdom is True
    from distributedfft_tpu.cli.common import wisdom_config_kwargs
    kw = wisdom_config_kwargs(args)
    assert kw == {"wisdom_path": "/tmp/w.json", "use_wisdom": False}
