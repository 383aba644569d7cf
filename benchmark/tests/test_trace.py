"""The trace reduction on traces recorded on a v5e (``testdata/``):
``slab512_forward`` is a ``--trace 1`` run of ``slab512.forward`` (5 s,
92 steps, one chip); ``slab1024x4_roundtrip`` one of
``slab1024x4.roundtrip`` (four chips). Each number is checked against a
plain recount from JAX's own trace reader."""

import os

import pytest

from jax.profiler import ProfileData

from benchmark import trace

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata")
ONE = os.path.join(DATA, "slab512_forward.xplane.pb")
FOUR = os.path.join(DATA, "slab1024x4_roundtrip.xplane.pb")


def recount(path):
    """Window, and per device the merged op intervals inside it, straight
    from ``ProfileData``."""
    pd = ProfileData.from_file(path)
    win = None
    per = {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "bench/window":
                    win = (e.start_ns, e.end_ns)
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            iv = sorted((max(e.start_ns, win[0]), min(e.end_ns, win[1]))
                        for e in line.events if e.duration_ns > 0
                        and e.end_ns > win[0] and e.start_ns < win[1])
            merged = []
            for a, b in iv:
                if merged and a <= merged[-1][1]:
                    merged[-1][1] = max(merged[-1][1], b)
                else:
                    merged.append([a, b])
            per[plane.name] = sum(b - a for a, b in merged) * 1e-9
    return (win[1] - win[0]) * 1e-9, per


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.minus([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.minus([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert trace.length(trace.minus([(0, 4)], [])) == 4


@pytest.mark.parametrize("path", [ONE, FOUR])
def test_busy_and_idle_match_a_recount(path):
    if not os.path.exists(path):
        pytest.skip(f"{path} not recorded")
    tr = trace.load(path)
    window_s, per = recount(path)
    assert tr.window_s == pytest.approx(window_s)
    assert set(tr.devices) == set(per)
    assert trace.busy_s(tr) == pytest.approx(sum(per.values()) / len(per))
    idle = 1 - trace.busy_s(tr) / tr.window_s
    assert 0 < idle < 0.2


def test_one_chip_trace():
    tr = trace.load(ONE)
    (ops,) = tr.devices.values()
    assert not any(o.collective for o in ops)
    assert trace.collective_split(ops) == (0, 0)
    busy = trace.length(trace.busy(ops))
    # The local FFT's stage scope covers nearly all device time (PR 21's
    # smoke: 97%), and every op has its HLO name and opcode.
    assert trace.scoped_ns(ops, "local_fft") > 0.95 * busy
    assert all(o.opcode and "=" not in o.name for o in ops)
    assert tr.window_s == pytest.approx(5.039382644, rel=1e-6)
    assert trace.busy_s(tr) == pytest.approx(4.947804799, rel=1e-6)
    steps = [s for s in tr.spans if s.name == "bench/step"]
    assert len(steps) == 92


def test_four_chip_trace_collectives():
    if not os.path.exists(FOUR):
        pytest.skip(f"{FOUR} not recorded")
    tr = trace.load(FOUR)
    assert len(tr.devices) == 4
    for ops in tr.devices.values():
        coll, exposed = trace.collective_split(ops)
        assert coll > 0 and 0 <= exposed <= coll
        compute = trace.length(trace.busy([o for o in ops
                                           if not o.collective]))
        busy = trace.length(trace.busy(ops))
        # Busy time is compute plus the collective time nothing hides.
        assert busy == compute + exposed
        assert {o.opcode for o in ops if o.collective} <= {
            "all-to-all", "all-to-all-start", "all-to-all-done"}
