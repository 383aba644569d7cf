"""Observability layer (distributedfft_tpu/obs/):

* span nesting + JSONL event-log schema round-trip (``validate_event`` is
  the same checker CI runs over the uploaded artifact);
* metrics registry: counters accumulate across a plan build and reset
  between plans; wisdom hit/miss/migration provenance is counted and
  surfaced as one-line notices;
* ``dfft-explain`` golden checks for slab / pencil / ring / bf16 configs
  on the 8-device CPU mesh (resolved rendering, wire bytes, HLO census —
  without executing the FFT);
* the zero-overhead pin: with ``$DFFT_OBS_DIR`` unset the obs layer adds
  ZERO HLO ops — compiled HLO with observability enabled is byte-identical
  to disabled for every exchange rendering, which transitively pins the
  disabled path to the pre-obs programs (spans are host-side only).
"""

import json
import re

import numpy as np
import pytest

import jax

import distributedfft_tpu as dfft
from distributedfft_tpu import obs
from distributedfft_tpu import params as pm
from distributedfft_tpu.obs import explain
from distributedfft_tpu.utils import wisdom


@pytest.fixture(autouse=True)
def _obs_hygiene():
    """Every test starts and ends with a clean registry and the
    pure-environment enablement (no leakage between tests)."""
    obs.reset()
    obs.reset_enablement()
    obs.disable_console()
    yield
    obs.reset()
    obs.reset_enablement()
    obs.disable_console()


# ---------------------------------------------------------------------------
# span tracing + event log
# ---------------------------------------------------------------------------

def test_span_nesting_and_jsonl_schema_roundtrip(tmp_path):
    d = str(tmp_path / "obs")
    obs.enable(d)
    with obs.span("outer", kind="test"):
        with obs.span("inner.a", i=1):
            pass
        with obs.span("inner.b"):
            obs.event("point", detail="x")
    obs.notice("a one-liner", name="wisdom.provenance", slot="comm")
    path = obs.event_log_path()
    assert path is not None and path.startswith(d)

    # Schema round-trip with the SAME validator CI uses.
    n = obs.validate_events_file(path)
    assert n == 5  # 3 spans + 1 event + 1 notice
    assert obs.validate_events_dir(d) == 5

    recs = [json.loads(ln) for ln in open(path)]
    by_name = {r["name"]: r for r in recs}
    # Nesting: children carry the parent name and depth 1; spans close
    # inner-first so the outer span is the LAST span record.
    assert by_name["outer"]["depth"] == 0
    assert by_name["outer"]["parent"] is None
    for child in ("inner.a", "inner.b"):
        assert by_name[child]["parent"] == "outer"
        assert by_name[child]["depth"] == 1
    spans = [r for r in recs if r["ev"] == "span"]
    assert spans[-1]["name"] == "outer"
    assert by_name["outer"]["dur_ms"] >= by_name["inner.a"]["dur_ms"]
    # Point events carry no duration; attrs round-trip.
    assert by_name["point"]["ev"] == "event"
    assert "dur_ms" not in by_name["point"]
    assert by_name["point"]["attrs"] == {"detail": "x"}
    assert by_name["point"]["parent"] == "inner.b"
    assert by_name["wisdom.provenance"]["attrs"]["msg"] == "a one-liner"
    # seq is assigned at OPEN time (spans are written at close, so file
    # order differs): unique, dense, and the outer span opened first.
    seqs = sorted(r["seq"] for r in recs)
    assert seqs == list(range(seqs[0], seqs[0] + len(recs)))
    assert by_name["outer"]["seq"] == min(seqs)


def test_span_disabled_feeds_ring_only(tmp_path, monkeypatch):
    """With the log off, spans/events do NO file I/O but still land in
    the always-on flight-recorder ring (ISSUE 12); with the recorder
    also off ($DFFT_FLIGHTREC=off) the span degrades to the shared
    no-op context — the fully-dropped path still exists."""
    obs.disable()
    obs.flightrec.clear()
    with obs.span("ring.only", k=1):
        pass
    obs.event("ring.event")
    obs.notice("ring notice")
    assert obs.event_log_path() is None  # no file surface
    names = [r["name"] for r in obs.flightrec.snapshot()]
    assert "ring.only" in names and "ring.event" in names
    # disable() beats the environment.
    import os
    os.environ[obs.ENV_VAR] = str(tmp_path)
    try:
        assert not obs.enabled()
    finally:
        del os.environ[obs.ENV_VAR]
    # Recorder off too -> the shared null context, zero allocation.
    monkeypatch.setenv("DFFT_FLIGHTREC", "off")
    s1, s2 = obs.span("a"), obs.span("b", k=1)
    assert s1 is s2
    with s1:
        pass
    obs.flightrec.clear()
    obs.event("fully.dropped")
    assert obs.flightrec.snapshot() == []


def test_validate_event_rejects_malformed():
    ok = {"ev": "span", "name": "x", "ts": 1.0, "pid": 1, "seq": 0,
          "depth": 0, "parent": None, "attrs": {}, "dur_ms": 0.1}
    obs.validate_event(ok)
    for bad in (
        "not a dict",
        {**ok, "ev": "bogus"},
        {**ok, "name": ""},
        {**ok, "ts": -1},
        {**ok, "depth": -2},
        {**ok, "parent": 7},
        {**ok, "attrs": []},
        {k: v for k, v in ok.items() if k != "dur_ms"},  # span needs dur
        {**ok, "ev": "event"},  # point event must NOT carry dur_ms
    ):
        with pytest.raises(ValueError):
            obs.validate_event(bad)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counters_reset_between_plans(devices):
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(g, pm.SlabPartition(8),
                            dfft.Config(comm_method=dfft.CommMethod.ALL2ALL))
    # Tracing the forward program walks the exchange builder once.
    plan._build_r2c().lower(
        jax.ShapeDtypeStruct(plan.input_padded_shape, np.float32))
    snap = obs.snapshot()
    assert snap["counters"].get("wire.exchanges_traced", 0) >= 1
    assert snap["gauges"].get("wire.bytes_per_transpose", 0) > 0
    obs.reset()
    snap = obs.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}


def test_wisdom_hit_miss_counters_and_notice(tmp_path, capsys):
    wpath = str(tmp_path / "w.json")
    g = dfft.GlobalSize(8, 8, 8)
    key = wisdom.plan_key("slab", g.shape, False, pm.SlabPartition(1),
                          pm.FFTNorm.NONE,
                          sequence=pm.SlabSequence.ZY_THEN_X)
    store = wisdom.WisdomStore(wpath)
    assert store.record(key, "local_fft",
                        {"fft_backend": "xla", "mxu_precision": None,
                         "mxu_direct_max": None})
    # recorded_at provenance stamp (what dfft-explain prints as "when").
    rec = store.lookup(key, "local_fft")
    assert re.fullmatch(r"\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2}Z",
                        rec["recorded_at"])
    obs.enable_console()
    cfg = dfft.Config(fft_backend="auto", wisdom_path=wpath)
    plan = dfft.SlabFFTPlan(g, pm.SlabPartition(1), cfg)
    assert plan.config.fft_backend == "xla"
    assert obs.metrics.counter_value("wisdom.hits") == 1
    assert obs.metrics.counter_value("wisdom.misses") == 0
    out = capsys.readouterr().out
    assert "wisdom[local_fft]: hit" in out  # the one-line provenance


def test_migration_counted_and_noticed_once(tmp_path, capsys):
    p = tmp_path / "w.json"
    p.write_text(json.dumps({"version": 1, "entries": {
        "k": {"local_fft": {"fft_backend": "xla"}}}}))
    obs.enable_console()
    store = wisdom.WisdomStore(str(p))
    store.load()
    store.load()  # second load of the same legacy store: no double count
    assert obs.metrics.counter_value("wisdom.migrations") == 1
    assert "migrated(v1→v5)" in capsys.readouterr().out


def test_hlo_census_feeds_gauges():
    from distributedfft_tpu.testing.microbench import async_collective_counts
    counts = async_collective_counts(
        "x = all-to-all(y) z = collective-permute(x) "
        "w = collective-permute(z) c = convert(w)")
    assert counts["all_to_all"] == 1 and counts["collective_permute"] == 2
    assert obs.metrics.gauge_value("hlo.all_to_all") == 1
    assert obs.metrics.gauge_value("hlo.collective_permute") == 2
    assert obs.metrics.gauge_value("hlo.convert") == 1


# ---------------------------------------------------------------------------
# dfft-explain golden checks (CPU mesh; no FFT is ever executed)
# ---------------------------------------------------------------------------

def _explain(argv, capsys) -> str:
    assert explain.main(argv) == 0
    return capsys.readouterr().out


def test_explain_slab_default(capsys, devices):
    out = _explain(["--kind", "slab", "-nx", "16", "-ny", "16", "-nz", "16",
                    "-p", "8", "-comm", "All2All"], capsys)
    assert "kind: slab  sequence: ZY_Then_X" in out
    assert "exchange: scatter y -> gather x" in out
    assert "explicit shard_map lax.all_to_all, default layout" in out
    assert "wire_nbytes" in out and "dtype: native" in out
    assert "all_to_all: 1" in out  # census: exactly one exchange
    assert "roofline" in out


def test_explain_slab_ring(capsys, devices):
    out = _explain(["--kind", "slab", "-nx", "16", "-ny", "16", "-nz", "16",
                    "-p", "8", "-snd", "Ring", "-s", "Z_Then_YX"], capsys)
    assert "ring — 7 distinct lax.ppermute steps" in out
    # Census proof the exchange is genuinely split (the tier-1 ring gate's
    # signature, >= P-1 distinct permutes).
    m = re.search(r"collective_permute: (\d+)", out)
    assert m and int(m.group(1)) >= 7


def test_explain_bf16_wire(capsys, devices):
    out = _explain(["--kind", "slab", "-nx", "16", "-ny", "16", "-nz", "16",
                    "-p", "8", "-comm", "Peer2Peer", "-wire", "bf16"],
                   capsys)
    assert "dtype: bf16" in out
    assert "native would be" in out  # halved wire bytes vs native
    assert "lossy" in out
    m = re.search(r"convert: (\d+)", out)
    assert m and int(m.group(1)) > 0  # encode/decode casts in the HLO


def test_explain_pencil(capsys, devices):
    out = _explain(["--kind", "pencil", "-nx", "16", "-ny", "16",
                    "-nz", "16", "-p1", "2", "-p2", "4"], capsys)
    assert "exchange 1 (p2 axis): scatter z -> gather y" in out
    assert "exchange 2 (p1 axis): scatter y -> gather x" in out
    assert "transpose 1:" in out and "transpose 2:" in out
    assert out.count("wire_nbytes") == 0 or "payload" in out


def test_explain_batched_shard_batch_no_collectives(capsys, devices):
    out = _explain(["--kind", "batched", "-nx", "16", "-ny", "16",
                    "-nz", "8", "--shard", "batch", "-p", "8"], capsys)
    assert "embarrassingly parallel batch sharding" in out
    assert "no exchange -> nothing on the wire" in out
    assert "all_to_all: 0" in out
    assert "batch_chunk: None  chunk_group: None" in out


def test_explain_batched_chunk_shows_chunk_group(capsys, devices):
    """The chunked plan's line names its chunk and the group of images
    its forward writes at once (None on the CPU; 8 on a TPU)."""
    out = _explain(["--kind", "batched", "-nx", "16", "-ny", "16",
                    "-nz", "8", "--shard", "batch", "-p", "8",
                    "--batch-chunk", "1"], capsys)
    assert "batch_chunk: 1  chunk_group: None" in out


def test_explain_wisdom_miss_never_races(tmp_path, capsys, devices,
                                         monkeypatch):
    """Explain reports a miss WITHOUT racing (the lookup-only contract):
    any call into the autotuners would execute FFTs."""
    from distributedfft_tpu.testing import autotune as at

    def boom(*a, **kw):
        raise AssertionError("explain must never race")

    monkeypatch.setattr(at, "autotune_local_fft", boom)
    monkeypatch.setattr(at, "autotune_comm", boom)
    monkeypatch.setattr(at, "autotune_wire", boom)
    wpath = str(tmp_path / "w.json")
    out = _explain(["--kind", "slab", "-nx", "16", "-ny", "16", "-nz", "16",
                    "-p", "8", "--fft-backend", "auto", "-comm", "auto",
                    "--wisdom", wpath, "--no-compile"], capsys)
    assert "local_fft: miss" in out
    assert "comm: miss" in out
    assert "a real run would race" in out
    import os
    assert not os.path.exists(wpath)  # lookup-only: nothing written


def test_explain_obs_flag_prints_snapshot_and_event_log(tmp_path, capsys,
                                                        devices):
    d = str(tmp_path / "obs")
    out = _explain(["--kind", "slab", "-nx", "16", "-ny", "16", "-nz", "16",
                    "-p", "8", "--no-compile", "--obs", "--obs-dir", d],
                   capsys)
    assert "obs metrics:" in out
    assert obs.validate_events_dir(d) > 0  # explain span landed in the log


# ---------------------------------------------------------------------------
# the zero-overhead pin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg_kw, sequence", [
    (dict(comm_method=dfft.CommMethod.ALL2ALL), "ZY_Then_X"),
    (dict(comm_method=dfft.CommMethod.ALL2ALL, opt=1), "ZY_Then_X"),
    (dict(send_method=dfft.SendMethod.RING), "Z_Then_YX"),
    (dict(comm_method=dfft.CommMethod.PEER2PEER, wire_dtype="bf16"),
     "ZY_Then_X"),
])
def test_obs_adds_zero_hlo_ops(tmp_path, devices, cfg_kw, sequence):
    """Compiled HLO with observability ENABLED has the same op graph as
    DISABLED for every exchange rendering: spans are host-side intervals,
    never ops, so the disabled path (the default) is transitively pinned
    to the pre-obs programs."""
    from distributedfft_tpu.analysis import hloscan

    g = dfft.GlobalSize(16, 16, 16)

    def compile_text():
        plan = dfft.SlabFFTPlan(g, pm.SlabPartition(8),
                                dfft.Config(**cfg_kw), sequence=sequence)
        return hloscan.compiled_text(plan, "forward")

    obs.disable()
    off = compile_text()
    obs.enable(str(tmp_path / "obs"))
    on = compile_text()
    # Source locations differ (the enabled path traces through other
    # lines); the op graph — what dfft-verify's pins compare — does not.
    assert hloscan.strip_metadata(on) == hloscan.strip_metadata(off)
    # And the enabled run really did trace (the comparison is not vacuous).
    assert obs.validate_events_dir(str(tmp_path / "obs")) > 0


# ---------------------------------------------------------------------------
# exec spans (resilience/fallback.execute) on the device trace's clock
# ---------------------------------------------------------------------------

def _exec_plan():
    from distributedfft_tpu.parallel.mesh import make_slab_mesh
    return dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16), pm.SlabPartition(4),
                            dfft.Config(use_wisdom=False),
                            mesh=make_slab_mesh(4))


@pytest.mark.parametrize("call, direction", [("exec_r2c", "forward"),
                                             ("exec_c2r", "inverse")])
def test_exec_leaves_one_exec_span_with_one_dispatch_child(call, direction,
                                                           devices):
    plan = _exec_plan()
    x = np.random.default_rng(0).random(plan.input_shape)
    if call == "exec_c2r":
        x = plan.exec_r2c(x)
    obs.flightrec.clear()
    jax.block_until_ready(getattr(plan, call)(x))
    spans = [r for r in obs.flightrec.snapshot() if r["ev"] == "span"]
    execs = [r for r in spans if r["name"] == "plan.exec"]
    kids = [r for r in spans if r["name"] == "plan.dispatch"]
    assert len(execs) == 1 and len(kids) == 1
    (e,), (d,) = execs, kids
    assert e["attrs"] == {"direction": direction, "family": "slab"}
    assert d["parent"] == "plan.exec" and d["depth"] == e["depth"] + 1
    assert d["seq"] > e["seq"] and 0 <= d["dur_ms"] <= e["dur_ms"]


def test_profiler_trace_has_exec_spans_without_obs_dir(tmp_path, devices,
                                                       monkeypatch):
    """The spans mirror into a ``jax.profiler`` trace with the JSONL log
    off: the program's host spans share the device trace's clock."""
    from jax.profiler import ProfileData

    monkeypatch.delenv(obs.ENV_VAR, raising=False)
    assert not obs.enabled()
    plan = _exec_plan()
    x = np.random.default_rng(0).random(plan.input_shape)
    jax.block_until_ready(plan.exec_r2c(x))  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(plan.exec_r2c(x))
    (pb,) = tmp_path.glob("**/*.xplane.pb")
    names = {e.name for p in ProfileData.from_file(str(pb)).planes
             for ln in p.lines for e in ln.events}
    assert {"dfft:plan.exec", "dfft:plan.dispatch"} <= names
    assert obs.event_log_path() is None


def test_off_path_stays_jax_free():
    """A process that never imported jax records spans into the ring,
    opens no profiler annotation and imports no jax (the package's own
    ``__init__`` imports jax, so the subprocess loads ``obs`` alone)."""
    import os
    import subprocess
    import sys

    pkg = os.path.dirname(os.path.dirname(obs.__file__))
    code = (
        "import sys, types\n"
        "pkg = types.ModuleType('distributedfft_tpu')\n"
        f"pkg.__path__ = [{pkg!r}]\n"
        "sys.modules['distributedfft_tpu'] = pkg\n"
        "from distributedfft_tpu import obs\n"
        "with obs.span('plan.exec', direction='forward'):\n"
        "    with obs.span('plan.dispatch'):\n"
        "        with obs.profile.axis_scope(0, 3):\n"
        "            pass\n"
        "assert [r['name'] for r in obs.flightrec.snapshot()] == "
        "['plan.dispatch', 'plan.exec']\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "print('ok')\n")
    env = {k: v for k, v in os.environ.items() if k != obs.ENV_VAR}
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr[-2000:]
