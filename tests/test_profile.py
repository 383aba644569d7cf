"""Stage-attributed device telemetry (``obs/profile.py``) — ISSUE 12:

* parser units on the COMMITTED trace fixture
  (``tests/data/stage_trace_fixture.json``): scope extraction from event
  names, string args and nested paths (innermost wins), flame-graph
  self-time attribution (a wrapper is charged only what its children do
  not cover), zero-duration and non-X events skipped;
* the scope-emission contract: ``stage_scope`` no-ops for falsy node
  ids and under ``disable_scopes()`` / ``$DFFT_NO_STAGE_SCOPES``;
* the ZERO-OVERHEAD pin (satellite 1): the metadata-stripped op-graph
  fingerprint of a scoped plan is byte-identical with scopes on vs off
  (a scope that introduces ops is a failure), and
  ``plangraph.check_graph_scopes`` proves the converse — no declared
  node is missing its scope in the compiled metadata;
* END-TO-END attribution (the acceptance criterion): for one explicit
  combo per family, a live ``stage_profile`` capture assigns device
  time to every declared plan-graph node and the attributed sum lands
  within 15% of the measured total.
"""

import json
import os

import pytest

import distributedfft_tpu as dfft
from distributedfft_tpu import params as pm
from distributedfft_tpu.analysis import hloscan, plangraph
from distributedfft_tpu.obs import profile

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "stage_trace_fixture.json")


# ---------------------------------------------------------------------------
# parser units (committed fixture; no jax, no execution)
# ---------------------------------------------------------------------------

def test_fixture_parse_and_aggregate():
    """The committed trace fixture aggregates to its documented numbers:
    nested ops resolved by self time, innermost scope wins, the unscoped
    wrapper's self time lands in the unattributed remainder."""
    planes = profile.load_trace(FIXTURE)
    assert [p["name"] for p in planes] == ["trace-events"]
    agg = profile.aggregate_trace(planes)
    assert agg["scopes"] == {"slab/exchange:1": 0.4,
                             "slab/local_fft:1": 0.3,
                             "slab/local_fft:2": 0.15,
                             "wire/encode": 0.1}
    assert agg["unattributed_ms"] == pytest.approx(0.05)
    assert agg["total_ms"] == pytest.approx(1.0)


def test_fixture_event_filtering():
    """Zero-duration and non-X-phase events never reach attribution."""
    events = profile.load_trace(FIXTURE)[0]["lines"][0]["events"]
    names = [e["name"] for e in events]
    assert "counter-event" not in names          # ph != "X"
    assert "zero-duration" in names              # parsed ...
    zero = [e for e in events if e["name"] == "zero-duration"][0]
    assert zero["dur_ps"] == 0                   # ... but self-time drops it


def test_extract_scope_innermost_wins():
    assert profile.extract_scope(
        ["dfft/slab/exchange:1/dfft/wire/encode"]) == "wire/encode"
    assert profile.extract_scope(["dfft/slab/local_fft:1"]) \
        == "slab/local_fft:1"
    assert profile.extract_scope(["no scope here", ""]) is None
    # The LONGEST matching string owns the verdict (a short duplicate
    # prefix must not shadow the full nested path).
    assert profile.extract_scope(
        ["dfft/slab/exchange:1",
         "dfft/slab/exchange:1/dfft/wire/decode"]) == "wire/decode"


def test_self_times_sibling_overlap_is_not_nested():
    """An event is a child only when CONTAINED; a sibling that merely
    starts before the previous one ends keeps its full self time."""
    evs = [{"name": "a", "scope": "f/a", "offset_ps": 0, "dur_ps": 100},
           {"name": "b", "scope": "f/b", "offset_ps": 100, "dur_ps": 100}]
    out = dict(profile._self_times(list(evs)))
    assert out == {"f/a": 100.0, "f/b": 100.0}


def test_parse_trace_events_accepts_bare_list():
    evs = profile.parse_trace_events(
        [{"ph": "X", "name": "dfft/slab/guard", "ts": 1, "dur": 2}])
    assert evs[0]["scope"] == "slab/guard"
    assert evs[0]["dur_ps"] == 2_000_000  # µs -> ps


# ---------------------------------------------------------------------------
# scope emission contract
# ---------------------------------------------------------------------------

def test_stage_scope_noops(monkeypatch):
    import contextlib
    assert isinstance(profile.stage_scope("slab", ""),
                      contextlib.nullcontext)  # undeclared exchange
    profile.disable_scopes()
    try:
        assert not profile.scopes_enabled()
        assert isinstance(profile.stage_scope("slab", "exchange:1"),
                          contextlib.nullcontext)
    finally:
        profile.enable_scopes()
    monkeypatch.setenv(profile.ENV_NO_SCOPES, "1")
    assert not profile.scopes_enabled()
    monkeypatch.delenv(profile.ENV_NO_SCOPES)
    assert profile.scopes_enabled()


def test_scoped_passes_falsy_node_through():
    fn = lambda x: x + 1  # noqa: E731
    assert profile.scoped("slab", "", fn) is fn
    assert profile.scoped("slab", "exchange:1", None) is None
    assert profile.scoped("slab", "exchange:1", fn)(1) == 2


# ---------------------------------------------------------------------------
# zero-overhead pin + scope conformance (satellite 1)
# ---------------------------------------------------------------------------

G32 = dfft.GlobalSize(32, 32, 32)


def _slab(**cfg_kw):
    return dfft.SlabFFTPlan(G32, pm.SlabPartition(8),
                            dfft.Config(use_wisdom=False, **cfg_kw))


def test_scope_zero_overhead_fingerprint(devices):
    """Scopes are metadata ONLY: the metadata-stripped op-graph
    fingerprint is byte-identical with stage scopes on vs off (the
    ``scope-zero-overhead`` pin ``dfft-verify`` runs per family)."""
    cfg = dict(comm_method=dfft.CommMethod.ALL2ALL)
    on = hloscan.plan_fingerprint(_slab(**cfg))
    profile.disable_scopes()
    try:
        off = hloscan.plan_fingerprint(_slab(**cfg))
    finally:
        profile.enable_scopes()
    assert on == off


def test_compiled_metadata_carries_every_declared_scope(devices):
    """The converse of the pin (``check_graph_scopes``): every declared
    node with an op region leaves its ``dfft/<family>/<node-id>`` scope
    in the compiled module metadata — and the check goes quiet both when
    scopes are disabled and for GSPMD combos (no explicit op region)."""
    plan = _slab(comm_method=dfft.CommMethod.ALL2ALL, wire_dtype="bf16")
    graph = plangraph.graph_for(plan, "forward", 3)
    txt = hloscan.compiled_text(plan, "forward", 3)
    assert plangraph.check_graph_scopes(graph, txt) == []
    # Expected scopes really are there (not vacuously passing).
    assert profile.scope_name("slab", "exchange:1") in txt
    assert profile.scope_name("wire", "encode") in txt
    # A stripped module would fail loudly for every scoped node.
    broken = plangraph.check_graph_scopes(graph,
                                          hloscan.strip_metadata(txt))
    assert broken and all("scope-conformance" in str(v) for v in broken)
    profile.disable_scopes()
    try:
        assert plangraph.check_graph_scopes(graph, "") == []
    finally:
        profile.enable_scopes()


# ---------------------------------------------------------------------------
# end-to-end attribution (acceptance criterion; one combo per family)
# ---------------------------------------------------------------------------

def _family_plan(family):
    if family == "slab":
        return _slab(comm_method=dfft.CommMethod.ALL2ALL,
                     wire_dtype="bf16"), 3
    if family == "pencil":
        return dfft.PencilFFTPlan(
            dfft.GlobalSize(16, 16, 16), pm.PencilPartition(2, 4),
            dfft.Config(comm_method=dfft.CommMethod.ALL2ALL,
                        use_wisdom=False)), 3
    from distributedfft_tpu.models.batched2d import Batched2DFFTPlan
    return Batched2DFFTPlan(
        16, 32, 32, pm.SlabPartition(8),
        dfft.Config(comm_method=dfft.CommMethod.ALL2ALL,
                    use_wisdom=False), shard="x"), 2


@pytest.mark.parametrize("family", ["slab", "pencil", "batched2d"])
def test_stage_profile_attributes_every_declared_node(family, devices):
    """Live capture on the CPU mesh: every declared plan-graph node gets
    a row, the workhorse nodes (exchange, local FFT) get NONZERO device
    time, and the attributed sum is within 15% of the measured total."""
    plan, dims = _family_plan(family)
    prof = profile.stage_profile(plan, "forward", dims, iters=2)
    graph = plangraph.graph_for(plan, "forward", dims)
    rows = {r["node"]: r for r in prof["stages"]}
    assert set(rows) == {n.id for n in graph.nodes}
    for node in graph.nodes:
        if profile.node_scope_key(graph, node) is None:
            continue
        row = rows[node.id]
        assert row["device_ms"] >= 0
        if node.kind in ("exchange", "local_fft"):
            assert row["device_ms"] > 0, (node.id, prof)
    # Acceptance: per-stage sum within 15% of the measured total.
    assert prof["attributed_ms"] >= 0.85 * prof["total_ms"], prof
    assert prof["exchange_ms"] > 0 and prof["compute_ms"] > 0
    assert prof["total_ms"] > 0 and prof["iters"] == 2


def test_profile_stages_failure_propagates(monkeypatch):
    """``--profile-stages`` is a requested output: a capture failure
    propagates (the CLI exits non-zero) instead of printing a note."""
    import argparse

    from distributedfft_tpu.cli import common

    def broken(*args, **kwargs):
        raise RuntimeError("no trace")

    monkeypatch.setattr(profile, "stage_profile", broken)
    with pytest.raises(RuntimeError, match="no trace"):
        common.print_stage_profile(None, argparse.Namespace(
            profile_stages=True))
    common.print_stage_profile(None, argparse.Namespace(
        profile_stages=False))  # not asked for: nothing runs


def test_aggregate_reads_device_op_line_and_inherits_scope():
    """A TPU device plane carries module- and op-level lines: only the op
    line counts. An unscoped child of a scoped op (a runtime marker) is
    charged to that op; ``window_ms`` bounds the per-line op sum."""
    def ev(name, scope, off, dur):
        return {"name": name, "scope": scope, "offset_ps": off * 10**9,
                "dur_ps": dur * 10**9}

    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [ev("jit_fwd", None, 0, 10)]},
        {"name": "XLA Ops", "events": [
            ev("fusion.1", "slab/local_fft:1", 0, 6),
            ev("end: fusion.1", None, 5, 1),
            ev("copy.2", None, 7, 2)]}]}]
    agg = profile.aggregate_trace(planes)
    assert agg["scopes"] == {"slab/local_fft:1": 6.0}
    assert agg["unattributed_ms"] == 2.0
    assert agg["total_ms"] == 8.0 and agg["window_ms"] == 9.0


# ---------------------------------------------------------------------------
# per-axis sub-scopes of the local FFT (obs/profile.axis_scope)
# ---------------------------------------------------------------------------

def _four_device_plan(family):
    from distributedfft_tpu.parallel.mesh import (make_pencil_mesh,
                                                  make_slab_mesh)
    g = dfft.GlobalSize(16, 16, 16)
    cfg = dfft.Config(use_wisdom=False)
    if family == "slab":
        return dfft.SlabFFTPlan(g, pm.SlabPartition(4), cfg,
                                mesh=make_slab_mesh(4))
    return dfft.PencilFFTPlan(g, pm.PencilPartition(2, 2), cfg,
                              mesh=make_pencil_mesh(2, 2))


def _axis_scopes(txt):
    import re
    return set(re.findall(r"dfft/(\w+/[\w:]+\.ax\d+)", txt))


@pytest.mark.parametrize("family, direction", [
    ("slab", "forward"), ("slab", "inverse"),
    ("pencil", "forward"), ("pencil", "inverse")])
def test_axis_subscopes_follow_the_declared_axes(family, direction, devices):
    """Each one-axis FFT of a local-FFT stage leaves
    ``dfft/<family>/<node>.ax<k>`` in the compiled metadata, with ``k``
    the global axis the plan graph declares for that node (the slab and
    pencil arrays keep the global x, y, z order), and no other."""
    plan = _four_device_plan(family)
    graph = plangraph.graph_for(plan, direction, 3)
    want = {f"{family}/{n.id}.ax{a}" for n in graph.nodes
            if n.kind == "local_fft" for a in n.axes}
    assert len(want) == 3
    txt = hloscan.compiled_text(plan, direction, 3)
    assert _axis_scopes(txt) == want


@pytest.mark.parametrize("family, direction", [
    ("slab", "forward"), ("slab", "inverse"), ("pencil", "forward")])
def test_axis_subscopes_are_metadata_only(family, direction, devices):
    """The zero-overhead pin with the sub-scopes on: the compiled program
    with metadata stripped is byte-identical with every scope off."""
    on = hloscan.compiled_text(_four_device_plan(family), direction, 3)
    with profile.scopes_off():
        off = hloscan.compiled_text(_four_device_plan(family), direction, 3)
    assert _axis_scopes(on) and not _axis_scopes(off)
    assert hloscan.strip_metadata(on) == hloscan.strip_metadata(off)


def test_axis_scope_noops_outside_a_stage():
    import contextlib
    assert isinstance(profile.axis_scope(0, 3), contextlib.nullcontext)
    with profile.stage_scope("slab", "local_fft:1"):
        assert not isinstance(profile.axis_scope(-1, 3),
                              contextlib.nullcontext)
        with profile.scopes_off():
            assert isinstance(profile.axis_scope(0, 3),
                              contextlib.nullcontext)
    assert isinstance(profile.axis_scope(0, 3), contextlib.nullcontext)


@pytest.mark.parametrize("scope, stage", [
    ("slab/local_fft:1.ax2", "slab/local_fft:1"),
    ("pencil/local_fft:3.ax0", "pencil/local_fft:3"),
    ("slab/exchange:1", "slab/exchange:1"),
    ("wire/encode", "wire/encode")])
def test_stage_of_folds_the_axis_suffix(scope, stage):
    assert profile.stage_of(scope) == stage


def test_stage_profile_folds_axis_subscopes_into_their_node(devices):
    """``dfft-explain --profile`` keeps one row per declared node: the
    time under ``<node>.ax<k>`` adds to the node's own."""
    plan = _slab(comm_method=dfft.CommMethod.ALL2ALL)
    capture = {"scopes": {"slab/local_fft:1": 0.5,
                          "slab/local_fft:1.ax1": 1.0,
                          "slab/local_fft:1.ax2": 2.0,
                          "slab/local_fft:2.ax0": 3.0,
                          "slab/exchange:1": 4.0},
               "unattributed_ms": 0.5, "total_ms": 11.0, "window_ms": 12.0,
               "iters": 1, "planes": []}
    prof = profile.stage_profile(plan, "forward", 3, capture=capture)
    rows = {r["node"]: r["device_ms"] for r in prof["stages"]}
    assert rows["local_fft:1"] == pytest.approx(3.5)
    assert rows["local_fft:2"] == pytest.approx(3.0)
    assert rows["exchange:1"] == pytest.approx(4.0)
    assert prof["other_scopes"] == {}
    assert prof["attributed_ms"] == pytest.approx(10.5)
    assert prof["unattributed_ms"] == pytest.approx(0.5)
