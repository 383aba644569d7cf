"""End-to-end test of the bench.py orchestrator — the scoreboard artifact
generator, exercised without a chip. DFFT_BENCH_FORCE_CPU routes every
child (probe, tpu, mesh) onto the CPU backend; DFFT_BENCH_SIZES / DFFT_BENCH_MESH_N
shrink the problem so the whole parent pipeline fits CI time."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run_bench(env_extra, timeout=420):
    env = dict(os.environ)
    env.update(env_extra)
    r = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, timeout=timeout, cwd=REPO, env=env)
    assert r.returncode == 0, r.stderr[-500:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.strip()]
    assert lines, "bench printed nothing"
    return json.loads(lines[-1])


def test_full_orchestration_on_cpu(tmp_path):
    """One full parent run: probe -> mesh metrics -> tpu child, all forced
    CPU. Must emit exactly one COMPACT JSON line with the driver contract
    keys (a truncated 2000-char tail capture must still parse) and a real
    measurement (no fallback: the 'tpu' child succeeds on CPU); the verbose
    record lands at DFFT_BENCH_DETAILS_PATH — redirected to tmp so this
    starved CPU run can NEVER overwrite the committed BENCH_DETAILS.json,
    which is the CI roofline gate's regression reference."""
    # fleet:1 starves the fleet child's budget so it SKIPS: spawning
    # 1+2+4 jax worker subprocesses (~25 s alone) would dominate this
    # test for a block it asserts nothing about — the CI roofline job
    # (fleet:120) and the committed BENCH_DETAILS.json cover it.
    details = tmp_path / "BENCH_DETAILS.json"
    d = _run_bench({"DFFT_BENCH_FORCE_CPU": "1",
                    "DFFT_BENCH_SIZES": "32",
                    "DFFT_BENCH_BATCHED": "2,16,1",
                    "DFFT_BENCH_MESH_N": "32",
                    "DFFT_BENCH_CHILD_TIMEOUT_S": "fleet:1",
                    "DFFT_BENCH_DETAILS_PATH": str(details)})
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in d, d
    assert d["unit"] == "ms"
    # Compact-line contract (VERDICT "Next #2"): the final line alone must
    # fit a 2000-char tail capture with room to spare.
    assert len(json.dumps(d)) < 2000, d
    assert d.get("details") == "BENCH_DETAILS.json", d
    with open(details) as f:
        full = json.load(f)
    # The probe and tpu child both run on CPU, so sizes must carry a real
    # (non-degenerate) measurement for 32.
    assert "tpu_sizes" in full, full
    rec = full["tpu_sizes"]["32"]
    assert "per_iter_ms" in rec, full
    # headline comes from the measured size (no CPU-FALLBACK), but carries
    # no vs_baseline because the baseline is a 256^3 number
    assert "32^3" in d["metric"] and "CPU-FALLBACK" not in d["metric"], d
    assert d["value"] == rec["per_iter_ms"], d
    assert d["vs_baseline"] is None
    # mesh geometry matrix ran (the raw wire probe legitimately cannot:
    # a 32^3 spectral volume fails its p^2 divisibility precondition)
    assert full.get("geometry_gb_per_s"), full
    # batched-2D row measured under its non-numeric key, and it did NOT
    # headline (the cube did)
    brec = full["tpu_sizes"]["16^2x2"]
    assert "per_iter_ms" in brec and brec.get("batch_chunk") == 1, full


def test_bench_sizes_tolerates_malformed_env(monkeypatch):
    """A typo'd DFFT_BENCH_SIZES must degrade to the default sweep, not
    crash the parent after the mesh metrics were gathered (ADVICE r2)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    for raw, want in [("", bench.SIZES), (",,", bench.SIZES),
                      ("abc", bench.SIZES), ("0,-4", bench.SIZES),
                      ("128, 256", (128, 256)), ("1024", (1024,)),
                      ("64,oops,256", (64, 256))]:
        monkeypatch.setenv("DFFT_BENCH_SIZES", raw)
        assert bench._bench_sizes() == want, raw
    monkeypatch.setenv("DFFT_BENCH_SIZES", "512,junk")
    assert bench._headline_size() == "512"
    monkeypatch.setenv("DFFT_BENCH_SIZES", "512,256")
    assert bench._headline_size() == "256"


def test_child_json_contract():
    """Each child prints its own one-line JSON even under the test hooks."""
    env = dict(os.environ)
    env.update({"DFFT_BENCH_FORCE_CPU": "1", "DFFT_BENCH_SIZES": "16",
                "DFFT_BENCH_BATCHED": "not,a,spec",
                "DFFT_BENCH_MESH_N": "16"})
    for child, extra in (("probe", []), ("tpu", ["60"])):
        r = subprocess.run([sys.executable, BENCH, "--child", child, *extra],
                           capture_output=True, text=True, timeout=180,
                           cwd=REPO, env=env)
        assert r.returncode == 0, (child, r.stderr[-300:])
        parsed = json.loads(r.stdout.strip().splitlines()[-1])
        assert isinstance(parsed, dict), child
        if child == "tpu":
            # Malformed DFFT_BENCH_BATCHED degrades to a diagnostic, and
            # the cube sweep's record survives it.
            assert "batched2d_error" in parsed, parsed
            assert "16" in parsed.get("sizes", {}), parsed


def test_direct_plan_override_is_evidence_gated():
    """The all-direct bench override applies exactly where it was measured
    (matmul at 1024), inherits deployed settings, and stays off elsewhere
    (code-review r5: no extrapolation to unmeasured sizes)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    from distributedfft_tpu.ops import mxu_fft

    st, note = bench._direct_plan_override("matmul", 1024)
    assert note == "direct(1024)" and st.direct_max == 1024
    # Every other knob inherits the deployed settings.
    cur = mxu_fft.current_settings()
    assert (st.precision, st.karatsuba, st.fourstep_einsum) == (
        cur.precision, cur.karatsuba, cur.fourstep_einsum)
    for backend, n in [("matmul", 512), ("matmul", 2048),
                       ("matmul-planes", 1024), ("matmul-r2", 1024),
                       ("xla", 1024)]:
        assert bench._direct_plan_override(backend, n) == (None, None), (
            backend, n)
