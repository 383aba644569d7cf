"""Communication microbenchmarks (``testing/microbench``) — the reference's
1D/2D/3D bandwidth-probe semantics (``tests_reference.hpp:53-96``), with
compiled-HLO evidence that each strategy measures a real collective."""

import numpy as np
import pytest

from distributedfft_tpu.testing import microbench as mb


@pytest.mark.parametrize("geometry", ["1d", "2d", "3d"])
@pytest.mark.parametrize("explicit", [True, False])
def test_geometry_strategy_matrix_measures_a_collective(devices, geometry,
                                                        explicit):
    """Every geometry x strategy cell must (a) produce a finite bandwidth
    and (b) contain a cross-device collective in its compiled HLO — the
    GSPMD 'reshard' path in particular must not be an XLA-elided no-op
    (it lowers to the same all-to-all as the explicit path)."""
    r = mb.transpose_bandwidth((16, 16, 16), 8, explicit=explicit,
                               iterations=2, warmup=1, geometry=geometry)
    assert r["geometry"] == geometry
    assert np.isfinite(r["gb_per_s"]) and r["gb_per_s"] > 0
    assert r["collective_ops"], (
        f"{geometry}/{'explicit' if explicit else 'gspmd'} compiled to no "
        f"collective — the probe measured nothing")


def test_pencil_axis_alias(devices):
    r = mb.transpose_bandwidth((16, 16, 16), 8, iterations=1, warmup=0,
                               pencil_axis=True)
    assert r["geometry"] == "2d"


def test_indivisible_extent_rejected(devices):
    with pytest.raises(ValueError, match="must divide the mesh"):
        mb.transpose_bandwidth((10, 10, 10), 8, geometry="1d")


def test_3d_geometry_needs_divisible_x(devices):
    with pytest.raises(ValueError, match="3d geometry"):
        mb.transpose_bandwidth((15, 16, 16), 8, geometry="3d")


def test_3d_geometry_rejects_degenerate_mesh(devices):
    """p=2 would give p1=1 — the 2d probe mislabeled as 3d."""
    with pytest.raises(ValueError, match="even device count > 2"):
        mb.transpose_bandwidth((16, 16, 16), 2, geometry="3d")


def test_wire_bandwidth_pure_exchange(devices):
    """The wire probe (all_to_all, split==concat axis) runs a real
    collective with no relayout and reports positive bandwidth + HLO
    evidence — the ceiling an exchange's bandwidth is read against."""
    r = mb.wire_bandwidth((64, 16, 16), 8, iterations=1, warmup=0)
    assert r["gb_per_s"] > 0
    assert "all-to-all" in r["collective_ops"]
    assert r["bytes"] == 64 * 16 * 16 * 4


def test_wire_bandwidth_rejects_indivisible(devices):
    with pytest.raises(ValueError, match="wire probe"):
        mb.wire_bandwidth((16, 16, 16), 8)


def _slab_prexpose_spec(n: int, p: int = 8):
    """(plan, pre-transpose spectral volume) — the fraction chain's
    operands, shared by the gate tests (and mirrored in the -t 4 CLI)."""
    import distributedfft_tpu as dfft

    g = dfft.GlobalSize(n, n, n)
    plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(p),
                            dfft.Config(comm_method=dfft.CommMethod.ALL2ALL))
    x = plan.pad_input(np.random.default_rng(0).random(g.shape)
                       .astype(np.float32))
    return plan, plan.forward_stages()[0][1](x)


def test_transpose_fraction_chain_is_a_gate(devices):
    """The chained interleaved-pair fraction (north-star gate): ceiling
    work is a per-iteration subset of pipeline work, so the median
    fraction lands in (0, 1] up to measurement noise, with a reported
    spread (VERDICT r2: a fraction >1 is not a gate)."""
    plan, spec = _slab_prexpose_spec(64)
    r = mb.transpose_fraction_chain(plan, spec, k=6, repeats=3)
    if r.get("degenerate"):
        pytest.skip("all repeats noise-swamped on this host")
    # Structural contract only: the <=1-in-expectation property is the
    # methodology's claim, demonstrated in bench artifacts; a hard bound
    # here would make CI flaky on loaded hosts (tails exist).
    assert 0.0 < r["fraction"] < 5.0
    lo, hi = r["fraction_spread"]
    assert lo <= r["fraction"] <= hi
    assert r["pipe_gb_per_s"] > 0 and r["raw_gb_per_s"] > 0
    # Two-phase variant race (round 4): the published value names its
    # rendering, and the selection-phase fractions ride along for
    # visibility without being gate values.
    assert r["variant"] in r["variants"]
    assert set(r["variants"]) <= {"opt0", "opt1"}
    for v in r["variants"].values():
        assert 0.0 < v["fraction"] < 5.0


def test_realigned_pack_shape_matches_transpose():
    """The merged-leading ceiling layout must equal the shape the
    realigned sender pack actually exchanges, for every (split, p)."""
    from distributedfft_tpu.parallel.transpose import realigned_pack_shape

    assert realigned_pack_shape((4, 16, 5), 1, 8) == (32, 2, 5)
    assert realigned_pack_shape((4, 7, 16), 2, 8) == (32, 7, 2)
    assert realigned_pack_shape((16, 3, 3), 0, 8) == (16, 3, 3)  # view
    with pytest.raises(ValueError, match="divisible"):
        realigned_pack_shape((4, 9, 5), 1, 8)


def test_transpose_fraction_chain_rejects_bad_divisibility(devices):
    # 32^3 over 8: local leading extent 4, not divisible by 8
    plan, spec = _slab_prexpose_spec(32)
    with pytest.raises(ValueError, match="divisible"):
        mb.transpose_fraction_chain(plan, spec, k=2, repeats=1)


def test_reference_cli_fraction_gate(devices, capsys):
    """dfft-reference -t 4: the north-star fraction gate as a CLI probe."""
    from distributedfft_tpu.cli import reference

    rc = reference.main(["-nx", "64", "-ny", "64", "-nz", "64", "-t", "4",
                         "-i", "3", "--emulate-devices", "8"])
    out = capsys.readouterr().out
    assert rc in (0, 1)  # 1 = degenerate on a hopelessly loaded host
    if rc == 0:
        assert "All2All fraction:" in out and "ceiling" in out


def test_async_collective_counts_text_contract():
    """The overlap detector counts op INSTANCES per form: the plain op
    must not swallow its async -start form (or vice versa), and
    async_total sums only the starts. ``convert`` counts the wire layer's
    encode/decode casts (tests/test_wire.py asserts the compressed-ring
    gate on it). Since the counter moved to ``analysis.hloscan`` (this
    function delegates) the census also carries the reduction collectives
    the no-exchange contracts pin; tests/test_analysis.py owns the full
    text contract."""
    txt = """
  %a = f32[8] all-to-all(x), replica_groups={}
  %b = f32[8] all-to-all-start(x)
  %c = f32[8] collective-permute(x), source_target_pairs={{0,1}}
  %d = f32[8] collective-permute(y), source_target_pairs={{1,0}}
  %e = f32[8] collective-permute-start(z)
  %f = bf16[8] convert(w)
"""
    counts = mb.async_collective_counts(txt)
    assert counts == {"all_to_all": 1, "all_to_all_start": 1,
                      "collective_permute": 2, "collective_permute_start": 1,
                      "all_reduce": 0, "all_reduce_start": 0,
                      "all_gather": 0, "all_gather_start": 0,
                      "reduce_scatter": 0, "reduce_scatter_start": 0,
                      "async_total": 2, "convert": 1}
