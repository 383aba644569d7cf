"""MXU roofline model (evalkit/roofline.py): the MAC counts must mirror
ops/mxu_fft.py dispatch exactly, peaks come only from the per-kind table,
and the table generator must translate a measurement CSV without
inventing or dropping rows."""

import pytest

from distributedfft_tpu.evalkit import roofline as rl

V5E = rl.V5E_KIND

# (size, backend label, direct_max the label implies, 3mm utilization)
_ROWS = [
    ("128^3", "matmul@high", None, 0.9),
    ("256^3", "matmul@high", None, 0.8),
    ("512^3", "matmul@high", None, 0.7),
    ("512^3", "matmul@high four-step(16x32)", 32, 0.5),
    ("1024^3", "matmul@high direct(1024)", 1024, 0.6),
    ("256^3", "matmul-r2@high", None, 0.3),
    ("2048^2x64", "matmul@high", None, 0.05),
    ("4096^2x64", "matmul@high ck=1", None, 0.04),
]


@pytest.fixture
def csv_path(tmp_path):
    """A synthetic measurement CSV in the committed schema: each
    matmul-family roundtrip row timed at a chosen 3mm utilization of the
    v5e effective peak, plus rows the table must skip (xla, forward)."""
    lines = ["size,transform,backend,per_iter_ms,gflops"]
    for size, label, dmax, util in _ROWS:
        base, _ = rl._parse_backend(label)
        prec, r2 = rl._BACKENDS[base]
        d = rl.DIRECT_MAX if dmax is None else dmax
        form, dims = rl._parse_size(size)
        f3 = (rl.mxu_flops_roundtrip_3d(dims, d, radix2=r2, complex_mults=3)
              if form == "cube" else
              rl.mxu_flops_batched2d(dims[0], dims[1], d, complex_mults=3,
                                     radix2=r2))
        ms = f3 / (util * rl.effective_peak_tflops(V5E, prec) * 1e12) * 1e3
        lines.append(f"{size},R2C+C2R roundtrip,{label},{ms:.6f},100.0")
    lines.append("256^3,R2C+C2R roundtrip,xla,5.0,100.0")
    lines.append("256^3,R2C forward,matmul@high,2.0,100.0")
    path = tmp_path / "rows.csv"
    path.write_text("\n".join(lines) + "\n")
    return str(path)


def test_axis_mac_counts_direct():
    # Direct C2C: one complex matmul = complex_mults real depth-n matmuls.
    assert rl.macs_c2c_axis(256) == 4 * 256
    assert rl.macs_c2c_axis(256, complex_mults=3) == 3 * 256
    # R2C/C2R direct: two real matmuls of depth n (resp. n_out).
    assert rl.macs_r2c_axis(256) == 2 * 129
    assert rl.macs_c2r_axis(256) == 2 * 129


def test_axis_mac_counts_fourstep_and_radix2():
    # 2048 > DIRECT_MAX=512 -> the MXU-deep dispatch (_split_for) factors
    # it 4x512 (dominant factor at full direct depth — the ISSUE 10
    # large-axis extension), not the balanced 32x64: the model mirrors
    # ops/mxu_fft.py's actual four-step choice.
    from distributedfft_tpu.ops.mxu_fft import _split_for
    assert _split_for(2048, 512) == (4, 512)
    assert _split_for(4096, 512) == (8, 512)
    assert rl.macs_c2c_axis(2048) == 4 * 512 + 4 * 4
    # R2C four-step: real pair on n2 + complex on n1 (full volume).
    assert rl.macs_r2c_axis(2048) == 2 * 512 + 4 * 4
    # C2R beyond direct: hermitian-extend + full complex inverse.
    assert rl.macs_c2r_axis(2048) == rl.macs_c2c_axis(2048)
    # Radix-2 DIF halves depth down to the 128 base case.
    assert rl.macs_c2c_axis(512, radix2=True) == 4 * 128


def test_roundtrip_flops_closed_form():
    n, n_out = 256, 129
    want_macs = (n ** 3 * 2 * n_out            # z R2C
                 + 4 * n * n * n_out * 4 * n   # 4 C2C passes, halved volume
                 + n ** 3 * 2 * n_out)         # z C2R
    assert rl.mxu_flops_roundtrip_3d(n) == 2 * want_macs


def test_effective_peak_model():
    assert rl.effective_peak_tflops(V5E, "default") == 197.0
    assert abs(rl.effective_peak_tflops(V5E, "high") - 197.0 / 3) < 1e-9
    assert abs(rl.effective_peak_tflops(V5E, "highest") - 197.0 / 6) < 1e-9
    assert rl.PEAKS[V5E]["hbm_gbps"] == 819.0


def test_unknown_kind_has_no_peak():
    """A kind missing from the table gets no ideal, never a default."""
    assert rl.effective_peak_tflops("cpu") is None
    assert rl.ideal_time_ms(256, "matmul", kind="cpu") is None
    assert rl.roofline_row(10.0, 256, "xla", kind="cpu") is None
    with pytest.raises(ValueError, match="no peak"):
        rl.roofline_rows("unused.csv", "cpu")


def test_table_from_csv(csv_path):
    rows = rl.roofline_rows(csv_path, V5E)
    # Every matmul-family ROUNDTRIP row translates; the xla row (no
    # honest MXU count) and the forward row are skipped.
    assert len(rows) == len(_ROWS)
    for r, (size, label, _, util) in zip(rows, _ROWS):
        assert (r["size"], r["backend"]) == (size, label)
        # 3mm (the cheapest known complex-dot lowering) is the physically
        # binding bound; 4mm over-counts by at most 4/3.
        assert r["util_3mm"] == pytest.approx(util, rel=1e-3)
        assert r["util_3mm"] < r["util_4mm"] < 4.0 / 3.0
    md = rl.render_markdown(rows, V5E)
    assert "512^3" in md and "utilization" in md and V5E in md


def test_cli_writes_the_rendered_table(csv_path, tmp_path):
    out = tmp_path / "ROOFLINE.md"
    assert rl.main(["--csv", csv_path, "--device-kind", V5E,
                    "--out", str(out)]) == 0
    assert out.read_text() == rl.render_markdown(
        rl.roofline_rows(csv_path, V5E), V5E)


def test_parse_backend_plan_suffixes():
    """The plan-suffix grammar of the CSV backend column: base label plus
    direct(N) / four-step(AxB) / ck=N tokens; anything else skips the row
    (None) rather than miscounting it."""
    assert rl._parse_backend("matmul@high") == ("matmul@high", None)
    assert rl._parse_backend("matmul@high direct(1024)") == ("matmul@high",
                                                             1024)
    assert rl._parse_backend("matmul@high four-step(16x32)") == (
        "matmul@high", 32)
    assert rl._parse_backend("matmul@high ck=1") == ("matmul@high", None)
    assert rl._parse_backend("") is None              # empty cell: skip
    assert rl._parse_backend("xla") is None           # no MXU count
    assert rl._parse_backend("matmul@high mystery") is None  # unknown suffix


def test_fourstep_suffix_macs_match_measured_plan():
    """A four-step(AxB) suffix -> direct_max=max(A,B) must reproduce the
    exact plan the row was measured under: B divides n and is the largest
    divisor <= B, so _split_for(n, B) == (A, B) for every annotated row —
    the mapping is exact under the MXU-deep dispatch too."""
    from distributedfft_tpu.ops.mxu_fft import _split_for
    assert _split_for(512, 32) == (16, 32)     # four-step(16x32)
    assert _split_for(2048, 64) == (32, 64)    # four-step(32x64), old CSV
    assert _split_for(4096, 64) == (64, 64)    # four-step(64x64), old CSV


def test_metric_size_rows_in_roofline(csv_path):
    """The BASELINE metric's own size must appear in the rendered table —
    the plan-suffix parsing exists so the 1024^3 row is not dropped."""
    rows = rl.roofline_rows(csv_path, V5E)
    assert any(r["size"] == "1024^3" for r in rows)
    assert any(r["size"] == "4096^2x64" for r in rows)


# ---------------------------------------------------------------------------
# roofline_fraction (ISSUE 10: the tracked per-row gate)
# ---------------------------------------------------------------------------

def test_ideal_time_and_fraction_cube():
    """fraction = ideal/measured with ideal from the exact MXU model: a
    measurement AT the model's time scores 1.0, half speed scores 0.5."""
    ideal = rl.ideal_time_ms("256^3", "matmul@high", kind=V5E)
    assert ideal is not None and ideal > 0
    assert rl.roofline_fraction(ideal, "256^3", "matmul", kind=V5E) == 1.0
    assert abs(rl.roofline_fraction(2 * ideal, 256, "matmul", kind=V5E) - 0.5) < 1e-3


def test_fraction_shape_forms_agree():
    """Every accepted size spelling — '256^3', '256', int, (n,n,n) tuple —
    resolves to the same model."""
    vals = {rl.ideal_time_ms(f, "matmul", kind=V5E)
            for f in ("256^3", "256", 256, (256, 256, 256))}
    assert len(vals) == 1


def test_fraction_modes_and_devices():
    """One-way modes halve the flops; a mesh divides the per-chip share
    (communication deliberately NOT modeled — it shows up as lost
    fraction)."""
    rt = rl.ideal_time_ms(256, "matmul", kind=V5E)
    assert abs(rl.ideal_time_ms(256, "matmul", kind=V5E, mode="forward")
               - rt / 2) < 1e-9
    assert abs(rl.ideal_time_ms(256, "matmul", kind=V5E, devices=8)
               - rt / 8) < 1e-9


def test_fraction_nominal_model_for_non_matmul():
    """xla/pallas/bluestein rows take the nominal 2.5·N·log2 N model (no
    honest MXU count) and say so in the record."""
    row = rl.roofline_row(10.0, "256^3", "xla", kind=V5E)
    assert row["model"].startswith("nominal")
    assert row["roofline_fraction"] > 0


def test_fraction_direct_plan_override():
    """The direct(N) bench plan note must reach the model: the all-direct
    1024 plan issues more MACs than the four-step default."""
    d = rl.ideal_time_ms(1024, "matmul", kind=V5E, direct_max=1024)
    f = rl.ideal_time_ms(1024, "matmul", kind=V5E)
    assert d > f


def test_fraction_unmodelable_returns_none():
    assert rl.roofline_fraction(1.0, "20x16x7", "matmul", kind=V5E) is None
    assert rl.roofline_fraction(0.0, "256^3", "matmul", kind=V5E) is None
    assert rl.roofline_row(-1.0, "256^3", "matmul", kind=V5E) is None
    assert rl._parse_size((20, 16, 7)) is None


def test_fraction_inverse_row_key():
    """Bench row keys like '256:inverse' parse (mode tag ignored by the
    size parser; bench passes the mode explicitly)."""
    assert rl._parse_size("256:inverse") == ("cube", 256)
    assert rl._parse_size("4096^2x64") == ("b2d", (64, 4096))


def test_committed_bench_details_roofline_block():
    """The committed BENCH_DETAILS.json must carry the tracked roofline
    block with a fraction per row (ISSUE 10 acceptance; the CI roofline
    job regresses against exactly these rows)."""
    rows = rl.tracked_fractions()
    assert rows, "BENCH_DETAILS.json has no roofline.rows block"
    for key, rec in rows.items():
        assert "roofline_fraction" in rec and rec["roofline_fraction"] > 0, key
        assert "ideal_ms" in rec and "model" in rec, key
