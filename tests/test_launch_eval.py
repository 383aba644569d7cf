"""Tests for the job launcher (L6) and eval reducer (L7)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import launch  # noqa: E402  (repo-root module, like the reference's launch.py)

from distributedfft_tpu.evalkit import evaluate  # noqa: E402
from distributedfft_tpu.utils.timer import Timer  # noqa: E402


class TestLauncher:
    def test_merge_flags_precedence(self):
        job = {"global_test_settings": {"-i": 5, "$-t": 4}}
        test = {"name": "Slab", "-comm": "All2All"}
        merged = launch.merge_flags(job, test, {"-i": "20", "-t": "0"})
        # plain keys overridden by CLI; $-escaped keys resist override
        assert merged["-i"] == "20"
        assert merged["-t"] == 4
        assert merged["-comm"] == "All2All"

    def test_size_flags(self):
        assert launch.size_flags(128) == ["-nx", "128", "-ny", "128", "-nz", "128"]
        assert launch.size_flags([128, 256, 512]) == [
            "-nx", "128", "-ny", "256", "-nz", "512"]

    def test_parse_param_string(self):
        got = launch.parse_param_string("-i 5 -c -b dir")
        assert got == {"-i": "5", "-c": True, "-b": "dir"}

    def test_exe_selection(self):
        assert launch.exe_for_test({"name": "Pencil"}) == "pencil"
        assert launch.exe_for_test({"name": "Reference"}) == "reference"
        assert launch.exe_for_test({"name": "Slab"}) == "slab"

    def test_dry_run_end_to_end(self, tmp_path, capsys):
        job = {"size": [16], "global_test_settings": {"-i": 1},
               "tests": [{"name": "Slab", "-comm": "All2All"}]}
        path = tmp_path / "job.json"
        path.write_text(json.dumps(job))
        rc = launch.main(["--jobs", str(path), "--dry-run",
                          "--emulate-devices", "8"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "distributedfft_tpu.cli.slab" in out
        assert "-nx 16 -ny 16 -nz 16" in out


def _write_fake_csvs(bench_dir, variant, combos, sizes, iters=3, seed=0,
                     p=8, time_scale=1.0):
    rng = np.random.default_rng(seed)
    descs = ["init", "first", "xpose", "last", "Run complete"]
    for (opt, comm, snd) in combos:
        for (nx, ny, nz) in sizes:
            fname = f"test_{opt}_{comm}_{snd}_{nx}_{ny}_{nz}_0_{p}.csv"
            t = Timer(descs, p, os.path.join(bench_dir, variant, fname))
            for _ in range(iters):
                t.start()
                base = (1.0 + rng.random()) * time_scale
                t._durations = {"first": base, "xpose": base * 2,
                                "last": base * 3, "Run complete": base * 3.1}
                t.gather()


class TestEvalKit:
    def test_reduce_outputs(self, tmp_path):
        bench = str(tmp_path / "bench")
        _write_fake_csvs(bench, "slab_default",
                         [(0, 0, 0), (0, 1, 0), (1, 1, 0)],
                         [(16, 16, 16), (16, 16, 32)])
        out = str(tmp_path / "eval")
        evaluate.reduce_prefix(bench, out)
        runs = open(os.path.join(out, "slab_default", "runs",
                                 "runs_0_8_0.csv")).read().splitlines()
        assert runs[0] == ",,16_16_16,16_16_32"
        assert runs[1].startswith("Peer2Peer,Sync,")
        assert runs[2].startswith("All2All,Sync,")
        results = open(os.path.join(out, "results_8.csv")).read().splitlines()
        # one triple per (variant, opt): 2 opts -> 6 data rows + title
        assert len(results) == 7
        assert results[1].startswith("Slab,2D-1D,Default,")
        assert results[4].startswith("Slab,2D-1D,Realigned,")
        # mean row between CI rows
        lo, m, hi = (float(results[i].split(",")[3]) for i in (1, 2, 3))
        assert lo <= m <= hi
        props = open(os.path.join(out, "proportions_8_0.csv")).read()
        assert "first," in props and "xpose," in props

    def test_phase_durations_from_cumulative_marks(self):
        blocks = [{"first": [2.0], "xpose": [5.0], "last": [6.0],
                   "Run complete": [6.1]}]
        d = evaluate._phase_durations(blocks)
        assert d["first"] == 2.0
        assert d["xpose"] == 3.0
        assert d["last"] == 1.0

    def test_reduce_with_plots_writes_pngs(self, tmp_path):
        """make_plots emits the comparison and proportions figures (the
        committed-artifact path); smoke-checks the plot code end-to-end.
        matplotlib is the optional 'plots' extra, so absent -> skip."""
        pytest.importorskip("matplotlib")
        bench = str(tmp_path / "bench")
        _write_fake_csvs(bench, "slab_default",
                         [(0, 0, 0), (0, 1, 0)],
                         [(16, 16, 16), (16, 16, 32)])
        out = str(tmp_path / "eval")
        evaluate.reduce_prefix(bench, out, make_plots=True)
        assert os.path.exists(os.path.join(out, "comparison_8.png"))
        assert os.path.exists(os.path.join(out, "proportions_8_0.png"))

    def test_scalability(self, tmp_path):
        """Perfect 1/P timing must reduce to efficiency ~1 across P."""
        bench = str(tmp_path / "bench")
        # Same seed -> identical base times, scaled exactly 1/P.
        _write_fake_csvs(bench, "slab_default", [(0, 0, 0)],
                         [(16, 16, 16)], seed=5, p=4, time_scale=1.0)
        _write_fake_csvs(bench, "slab_default", [(0, 0, 0)],
                         [(16, 16, 16)], seed=5, p=8, time_scale=0.5)
        out = str(tmp_path / "eval")
        evaluate.reduce_prefix(bench, out)
        rows = evaluate.scalability(out, "16_16_16")
        assert [(p, round(t, 6)) for _, _, p, t in rows] == \
            sorted((p, round(t, 6)) for _, _, p, t in rows)
        lines = open(os.path.join(out, "scalability_16_16_16.csv")
                     ).read().splitlines()
        assert lines[0] == "size,16_16_16"
        assert lines[1] == "variant,opt,cuda,P,best_ms,speedup,efficiency"
        recs = [l.split(",") for l in lines[2:]]
        assert [(r[3]) for r in recs] == ["4", "8"]
        effs = [float(r[6]) for r in recs]
        assert effs[0] == 1.0 and abs(effs[1] - 1.0) < 1e-9

    def test_scalability_stages_classification(self, tmp_path):
        """FFT vs Transpose phase classes sum from the raw Timer marks;
        ratios are relative to the series' smallest P."""
        bench = str(tmp_path / "bench")
        descs = ["init", "1D FFT Z-Direction",
                 "Transpose (Finished All2All)", "1D FFT X-Direction",
                 "Run complete"]
        for p, scale in ((4, 1.0), (8, 2.0)):
            vdir = os.path.join(bench, "slab_default")
            fname = f"test_0_1_0_16_16_16_0_{p}.csv"
            t = Timer(descs, p, os.path.join(vdir, fname))
            for _ in range(3):
                t.start()
                # cumulative timeline marks (the Timer stores the mark at
                # which each phase FINISHED): 2 ms FFT-Z, 3 ms transpose,
                # 6 ms FFT-X.
                t._durations = {
                    "1D FFT Z-Direction": 2.0 * scale,
                    "Transpose (Finished All2All)": 5.0 * scale,
                    "1D FFT X-Direction": 11.0 * scale,
                    "Run complete": 11.0 * scale}
                t.gather()
        rows = evaluate.scalability_stages(bench, "16_16_16",
                                           str(tmp_path / "stages.csv"))
        by_p = {p: (fft, xp) for _, _, p, _, fft, xp in rows}
        assert by_p[4] == (8.0, 3.0)  # 2+6 FFT, 3 transpose
        assert by_p[8] == (16.0, 6.0)
        lines = open(str(tmp_path / "stages.csv")).read().splitlines()
        assert lines[1] == ("variant,opt,cuda,P,total_ms,fft_ms,xpose_ms,"
                            "fft_vs_P0,xpose_vs_P0")
        rec8 = [l for l in lines if l.startswith("slab_default_default,0,0,8")]
        assert rec8 and rec8[0].endswith("2.000,2.000")

    def test_committed_stage_scalability_is_current(self):
        """The committed stage-decomposition CSV must match what the
        reducer produces from the committed raw Timer data (a 256^3
        strong-scaling series on the 8-device CPU mesh, every strategy
        of each series, so the per-P best-strategy pick is exercised)."""
        data = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "data")
        prefix = os.path.join(data, "scalability_256")
        committed = os.path.join(data, "scalability_stages_256_256_256.csv")
        with open(committed) as f:
            want = f.read()
        import tempfile
        with tempfile.NamedTemporaryFile("r", suffix=".csv") as tmp:
            evaluate.scalability_stages(prefix, "256_256_256", tmp.name)
            assert tmp.read() == want

    def test_numerical_results(self, tmp_path):
        log = tmp_path / "run.out"
        log.write_text(
            "+ python -m distributedfft_tpu.cli.slab -nx 16 -t 4\n"
            "Result (avg): 1e-12\nResult (max): 3e-12\n")
        out = str(tmp_path / "num.csv")
        n = evaluate.numerical_results(str(tmp_path), out)
        assert n == 2
        assert "Result (avg)" in open(out).read()


class TestProfileDir:
    def test_slab_cli_writes_profiler_trace(self, tmp_path, monkeypatch):
        """--profile-dir wraps the testcase in jax.profiler.trace (SURVEY §5
        tracing: the deep-dive complement to the Timer CSVs)."""
        from distributedfft_tpu.cli import slab as slab_cli

        monkeypatch.chdir(tmp_path)
        rc = slab_cli.main(["-nx", "16", "-ny", "16", "-nz", "16", "-p", "4",
                            "-t", "3", "-i", "1",
                            "--profile-dir", str(tmp_path / "trace")])
        assert rc == 0
        found = list((tmp_path / "trace").rglob("*.xplane.pb"))
        assert found, "no xplane trace written under --profile-dir"
