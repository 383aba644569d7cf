"""Batched distributed 2D FFT plan (BASELINE config #4 workload)."""

import re

import numpy as np
import pytest

from distributedfft_tpu import Config, SlabPartition
from distributedfft_tpu.models.batched2d import Batched2DFFTPlan


def ref2d(x):
    return np.fft.fft(np.fft.rfft(x, axis=2), axis=1)


@pytest.mark.parametrize("shard", ["batch", "x"])
def test_forward_roundtrip(devices, rng, shard):
    plan = Batched2DFFTPlan(16, 32, 32, SlabPartition(8), Config(),
                            shard=shard)
    x = rng.random((16, 32, 32))
    c = plan.exec_forward(x)
    np.testing.assert_allclose(plan.crop_spectral(c), ref2d(x), atol=1e-9)
    r = plan.crop_real(plan.exec_inverse(c))
    np.testing.assert_allclose(r, x * 32 * 32, atol=1e-8)


def test_uneven_batch(devices, rng):
    """batch=5 over 8 devices pads the batch axis."""
    plan = Batched2DFFTPlan(5, 12, 10, SlabPartition(8), Config(),
                            shard="batch")
    assert plan.input_padded_shape == (8, 12, 10)
    x = rng.random((5, 12, 10))
    np.testing.assert_allclose(plan.crop_spectral(plan.exec_forward(x)),
                               ref2d(x), atol=1e-9)


def test_uneven_image_x_shard(devices, rng):
    plan = Batched2DFFTPlan(3, 10, 9, SlabPartition(8), Config(), shard="x")
    x = rng.random((3, 10, 9))
    c = plan.exec_forward(x)
    np.testing.assert_allclose(plan.crop_spectral(c), ref2d(x), atol=1e-9)
    r = plan.crop_real(plan.exec_inverse(c))
    np.testing.assert_allclose(r, x * 10 * 9, atol=1e-8)


def test_c2c(devices, rng):
    plan = Batched2DFFTPlan(4, 16, 16, SlabPartition(8), Config(),
                            shard="x", transform="c2c")
    xc = rng.random((4, 16, 16)) + 1j * rng.random((4, 16, 16))
    np.testing.assert_allclose(plan.crop_spectral(plan.exec_forward(xc)),
                               np.fft.fft2(xc), atol=1e-9)


def test_single_device(rng):
    plan = Batched2DFFTPlan(4, 16, 16, SlabPartition(1))
    x = rng.random((4, 16, 16))
    np.testing.assert_allclose(np.asarray(plan.exec_forward(x)), ref2d(x),
                               atol=1e-9)


def test_validation(devices):
    with pytest.raises(ValueError, match="shard"):
        Batched2DFFTPlan(4, 16, 16, SlabPartition(8), shard="y")
    with pytest.raises(ValueError, match="positive"):
        Batched2DFFTPlan(0, 16, 16, SlabPartition(8))
    plan = Batched2DFFTPlan(4, 16, 16, SlabPartition(8))
    with pytest.raises(ValueError, match="expected"):
        plan.exec_forward(np.zeros((4, 8, 8)))


class TestBatchChunk:
    """batch_chunk: sequential lax.map over batch slices — caps peak
    intermediate memory and compiled-program size (the whole-stack R2C of
    4096^2 x 64 does not fit one v5e)."""

    def test_chunked_matches_unchunked(self, devices, rng):
        x = rng.random((8, 16, 16)).astype(np.float32)
        base = Batched2DFFTPlan(8, 16, 16, SlabPartition(1))
        ck = Batched2DFFTPlan(8, 16, 16, SlabPartition(1), batch_chunk=2)
        np.testing.assert_allclose(np.asarray(ck.exec_forward(x)),
                                   np.asarray(base.exec_forward(x)),
                                   rtol=1e-6)
        c = base.exec_forward(x)
        np.testing.assert_allclose(np.asarray(ck.exec_inverse(c)),
                                   np.asarray(base.exec_inverse(c)),
                                   rtol=1e-6)

    def test_chunked_sharded_batch(self, devices, rng):
        # 16 images over 8 devices -> local batch 2, chunk 1 per device.
        plan = Batched2DFFTPlan(16, 8, 8, SlabPartition(8),
                                batch_chunk=1)
        x = rng.random((16, 8, 8)).astype(np.float32)
        got = plan.crop_spectral(plan.exec_forward(plan.pad_input(x)))
        ref = np.fft.rfftn(x, axes=(1, 2))
        assert np.abs(got - ref).max() / np.abs(ref).max() < 1e-5

    def test_chunk_validation(self, devices):
        with pytest.raises(ValueError, match="divide"):
            Batched2DFFTPlan(8, 16, 16, SlabPartition(1), batch_chunk=3)
        with pytest.raises(ValueError, match="shard='batch'"):
            Batched2DFFTPlan(8, 16, 16, SlabPartition(8),
                             shard="x", batch_chunk=2)
        with pytest.raises(ValueError, match="positive"):
            Batched2DFFTPlan(8, 16, 16, SlabPartition(1), batch_chunk=-1)
        # 0 is the documented "whole stack fused" alias for None, not an
        # error (the CLI/bench '0 disables chunking' convention).
        plan = Batched2DFFTPlan(8, 16, 16, SlabPartition(1), batch_chunk=0)
        assert plan.batch_chunk is None


def _rel_l2(got, ref):
    got, ref = np.asarray(got), np.asarray(ref)
    return np.linalg.norm(got - ref) / np.linalg.norm(ref)


def _chunked_plan(batch_chunk):
    return Batched2DFFTPlan(8, 64, 64, SlabPartition(1), Config(),
                            batch_chunk=batch_chunk)


@pytest.mark.parametrize("batch_chunk", [1, 2])
def test_chunked_one_device_matches_float64(rng, batch_chunk):
    """The one-chip chunked stack (the benchmark's batched-2D cell, at
    8 x 64 x 64): the float32 R2C against numpy's float64 ``rfft2``, and
    the C2R of that float64 spectrum against its unnormalised
    ``irfft2``."""
    plan = _chunked_plan(batch_chunk)
    x = rng.uniform(-1.0, 1.0, (8, 64, 64))
    c = plan.exec_forward(x.astype(np.float32))
    assert c.dtype == np.complex64
    ref = np.fft.rfft2(x)
    assert _rel_l2(c, ref) < 1e-5
    r = plan.exec_inverse(ref.astype(np.complex64))
    assert _rel_l2(r, np.fft.irfft2(ref, s=(64, 64)) * 64 * 64) < 1e-5


CHUNK_SCOPE = "dfft/batched2d/chunk:1"


@pytest.mark.parametrize("direction", ["forward", "inverse"])
def test_chunk_loop_carries_its_scope(direction):
    """The chunk loop's ops carry ``dfft/batched2d/chunk:1``; the
    transform inside it keeps its per-axis ``local_fft:1`` scopes; a
    fused plan has no chunk scope."""
    from distributedfft_tpu.analysis import hloscan
    txt = hloscan.compiled_text(_chunked_plan(2), direction, 2)
    assert CHUNK_SCOPE in txt
    assert set(re.findall(r"dfft/batched2d/local_fft:1\.ax\d", txt)) == {
        "dfft/batched2d/local_fft:1.ax1", "dfft/batched2d/local_fft:1.ax2"}
    fused = hloscan.compiled_text(_chunked_plan(None), direction, 2)
    assert CHUNK_SCOPE not in fused


@pytest.mark.parametrize("direction", ["forward", "inverse"])
def test_chunk_scope_is_metadata_only(direction):
    """Scopes on and off compile the same op graph once the metadata is
    stripped."""
    from distributedfft_tpu.analysis import hloscan
    from distributedfft_tpu.obs import profile
    on = hloscan.compiled_text(_chunked_plan(1), direction, 2)
    with profile.scopes_off():
        off = hloscan.compiled_text(_chunked_plan(1), direction, 2)
    assert CHUNK_SCOPE in on and CHUNK_SCOPE not in off
    assert hloscan.strip_metadata(on) == hloscan.strip_metadata(off)


@pytest.mark.parametrize("direction", ["forward", "inverse"])
def test_chunked_plan_passes_graph_verification(direction):
    """``dfft-verify``'s graph, contract, trace and scope passes and its
    jaxpr lints accept the chunk scope, which no graph node declares."""
    from distributedfft_tpu.analysis import jaxprlint, plangraph
    plan = _chunked_plan(2)
    assert plangraph.verify_graph(plan, direction, 2) == []
    assert jaxprlint.lint_plan(plan, direction, 2) == []


def _tpu_plan(monkeypatch, *args, **kw):
    """A plan built as on a TPU (the platform it reads is steered to
    "tpu"), running here on the CPU: its forward jit writes in groups."""
    from distributedfft_tpu.utils import wisdom
    with monkeypatch.context() as m:
        m.setattr(wisdom, "plan_platform", lambda mesh=None: "tpu")
        return Batched2DFFTPlan(*args, **kw)


XLA = Config(fft_backend="xla")


def _constrains_layout(fn, shape, dtype):
    """Whether ``fn``'s program carries a layout constraint (the group
    buffers of ``_map_in_tile_groups``)."""
    import jax
    return "@LayoutConstraint" in jax.jit(fn).lower(
        jax.ShapeDtypeStruct(shape, dtype)).as_text()


class TestChunkGroups:
    """The chunked float32 forward on a TPU writes its answers into the
    stack in groups of 8 images (the described-v5e compile in
    ``test_tpu_compile.py`` shows the layouts); here the numbers are
    checked, and every consumer of the answer."""

    @pytest.mark.parametrize("guards", ["off", "check"])
    @pytest.mark.parametrize("transform", ["r2c", "c2c"])
    def test_chunked_matches_unchunked(self, monkeypatch, rng, transform,
                                       guards):
        x = rng.uniform(-1.0, 1.0, (16, 16, 12))
        if transform == "c2c":
            x = x + 1j * rng.uniform(-1.0, 1.0, x.shape)
        x = x.astype(np.complex64 if transform == "c2c" else np.float32)
        cfg = Config(fft_backend="xla", guards=guards)
        ck = _tpu_plan(monkeypatch, 16, 16, 12, SlabPartition(1), cfg,
                       transform=transform, batch_chunk=2)
        assert ck.chunk_group == 8
        assert _constrains_layout(ck._build(True), x.shape, x.dtype)
        whole = Batched2DFFTPlan(16, 16, 12, SlabPartition(1), cfg,
                                 transform=transform)
        got = ck.exec_forward(x)
        assert got.dtype == np.complex64
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(whole.exec_forward(x)),
                                   rtol=1e-6, atol=1e-5)

    def test_sharded_batch_matches_unchunked(self, devices, monkeypatch,
                                             rng):
        """``shard="batch"`` on a mesh: the groups inside ``shard_map``
        (8 images a device)."""
        x = rng.uniform(-1.0, 1.0, (64, 8, 8)).astype(np.float32)
        ck = _tpu_plan(monkeypatch, 64, 8, 8, SlabPartition(8), XLA,
                       batch_chunk=1)
        assert ck.chunk_group == 8
        assert _constrains_layout(ck._build(True), x.shape, x.dtype)
        whole = Batched2DFFTPlan(64, 8, 8, SlabPartition(8), XLA)
        np.testing.assert_allclose(
            ck.crop_spectral(ck.exec_forward(x)),
            whole.crop_spectral(whole.exec_forward(x)), rtol=1e-6,
            atol=1e-5)

    def test_answer_feeds_inverse_and_host(self, monkeypatch, rng):
        """``exec_forward``'s answer keeps the default layout and goes to
        ``exec_inverse``, ``crop_spectral`` and ``np.asarray``."""
        plan = _tpu_plan(monkeypatch, 8, 16, 16, SlabPartition(1), XLA,
                         batch_chunk=1)
        x = rng.uniform(-1.0, 1.0, (8, 16, 16)).astype(np.float32)
        c = plan.exec_forward(x)
        assert c.format.layout.major_to_minor == (0, 1, 2)
        ref = np.fft.rfft2(x.astype(np.float64))
        assert _rel_l2(plan.crop_spectral(c), ref) < 1e-6
        assert _rel_l2(np.asarray(c), ref) < 1e-6
        r = plan.exec_inverse(c)
        assert _rel_l2(plan.crop_real(r), x * 16 * 16) < 1e-6

    def test_user_spectrum_runs_inverse(self, monkeypatch, rng):
        """A spectrum a user builds (``jnp.fft``) runs through
        ``exec_inverse`` as the plan's own does."""
        import jax.numpy as jnp
        plan = _tpu_plan(monkeypatch, 8, 16, 16, SlabPartition(1), XLA,
                         batch_chunk=1)
        x = rng.uniform(-1.0, 1.0, (8, 16, 16)).astype(np.float32)
        own = plan.crop_real(plan.exec_inverse(plan.exec_forward(x)))
        theirs = plan.crop_real(plan.exec_inverse(
            jnp.fft.rfft2(jnp.asarray(x))))
        assert _rel_l2(theirs, x * 16 * 16) < 1e-6
        np.testing.assert_allclose(theirs, own, rtol=1e-5, atol=1e-4)

    def test_plan_created_names_the_chunk_group(self, monkeypatch):
        """``plan.created`` says whether the groups engaged: on a TPU,
        float32, with a chunk that divides 8 and a local batch that 8
        divides; not on the CPU, unchunked, in float64, or with another
        chunk or batch. The pure composition keeps ``lax.map``."""
        from distributedfft_tpu import obs
        obs.flightrec.clear()
        f64 = Config(fft_backend="xla", double_prec=True)
        cases = [
            (Batched2DFFTPlan(8, 16, 16, SlabPartition(1), XLA,
                              batch_chunk=1), None),
            (_tpu_plan(monkeypatch, 8, 16, 16, SlabPartition(1), XLA,
                       batch_chunk=1), 8),
            (_tpu_plan(monkeypatch, 8, 16, 16, SlabPartition(1), XLA),
             None),
            (_tpu_plan(monkeypatch, 8, 16, 16, SlabPartition(1), f64,
                       batch_chunk=1), None),
            (_tpu_plan(monkeypatch, 12, 16, 16, SlabPartition(1), XLA,
                       batch_chunk=3), None),
            (_tpu_plan(monkeypatch, 12, 16, 16, SlabPartition(1), XLA,
                       batch_chunk=1), None),
        ]
        assert [p.chunk_group for p, _ in cases] == [w for _, w in cases]
        got = [r["attrs"]["chunk_group"] for r in obs.flightrec.snapshot()
               if r["name"] == "plan.created"
               and r["attrs"].get("kind") == "batched2d"]
        assert got == [w for _, w in cases]
        assert not _constrains_layout(cases[1][0].forward_fn(),
                                      (8, 16, 16), np.float32)

    @pytest.mark.parametrize("platform,want", [("tpu", True),
                                               ("cpu", False)])
    def test_plan_created_and_explain_name_r2c_paired(
            self, monkeypatch, capsys, platform, want):
        """The benchmark cell's plan (48 images of 4096^2, chunk 1,
        float32, xla) pairs the rows of its R2C on a TPU
        (``ops/fft.pairs_rows``), and ``plan.created`` and
        ``dfft-explain`` say so; on the CPU it does not. Nothing runs."""
        from distributedfft_tpu import obs
        from distributedfft_tpu.obs import explain
        from distributedfft_tpu.utils import wisdom
        obs.flightrec.clear()
        monkeypatch.setattr(wisdom, "plan_platform",
                            lambda mesh=None: platform)
        plan = Batched2DFFTPlan(48, 4096, 4096, SlabPartition(1), XLA,
                                batch_chunk=1)
        assert plan.r2c_paired is want
        assert [r["attrs"]["r2c_paired"] for r in obs.flightrec.snapshot()
                if r["name"] == "plan.created"] == [want]
        assert explain.main(["--kind", "batched", "-nx", "4096", "-ny",
                             "4096", "-nz", "48", "--shard", "batch", "-p",
                             "1", "--batch-chunk", "1", "--fft-backend",
                             "xla"]) == 0
        assert f"r2c_paired: {want}" in capsys.readouterr().out

    @pytest.mark.parametrize("platform,want", [("tpu", True),
                                               ("cpu", False)])
    def test_plan_created_and_explain_name_c2r_paired(
            self, monkeypatch, capsys, platform, want):
        """The same cell's plan pairs the rows of its C2R on a TPU
        (``plan.c2r_paired``, ``ops/fft._paired_irfft``), and
        ``plan.created`` and ``dfft-explain`` say so; on the CPU it does
        not. Nothing runs."""
        from distributedfft_tpu import obs
        from distributedfft_tpu.obs import explain
        from distributedfft_tpu.utils import wisdom
        obs.flightrec.clear()
        monkeypatch.setattr(wisdom, "plan_platform",
                            lambda mesh=None: platform)
        plan = Batched2DFFTPlan(48, 4096, 4096, SlabPartition(1), XLA,
                                batch_chunk=1)
        assert plan.c2r_paired is want
        assert [r["attrs"]["c2r_paired"] for r in obs.flightrec.snapshot()
                if r["name"] == "plan.created"] == [want]
        assert explain.main(["--kind", "batched", "-nx", "4096", "-ny",
                             "4096", "-nz", "48", "--shard", "batch", "-p",
                             "1", "--batch-chunk", "1", "--fft-backend",
                             "xla"]) == 0
        assert f"c2r_paired: {want}" in capsys.readouterr().out


class TestHarnessWiring:
    """VERDICT r2 item 7: the batched plan flows through the same
    testcase/Timer/eval harness as the 3D engines (variant_name,
    section_descriptions, staged execution, CLI, job specs)."""

    def _plan(self, shard, **kw):
        return Batched2DFFTPlan(8, 24, 16, SlabPartition(8),
                                Config(double_prec=True), shard=shard, **kw)

    @pytest.mark.parametrize("shard", ["batch", "x"])
    def test_staged_matches_fused(self, devices, rng, shard):
        plan = self._plan(shard)
        x = plan.pad_input(rng.random((8, 24, 16)))
        y = x
        for _, fn in plan.forward_stages():
            y = fn(y)
        fused = plan.exec_forward(x)
        np.testing.assert_allclose(np.asarray(plan.crop_spectral(y)),
                                   np.asarray(plan.crop_spectral(fused)),
                                   atol=1e-10)
        z = y
        for _, fn in plan.inverse_stages():
            z = fn(z)
        np.testing.assert_allclose(np.asarray(plan.crop_real(z)),
                                   rng_scale := np.asarray(
                                       plan.crop_real(plan.exec_inverse(y))),
                                   atol=1e-8)
        assert rng_scale.shape == (8, 24, 16)

    @pytest.mark.parametrize("shard", ["batch", "x"])
    def test_stage_descs_subset_of_sections(self, devices, shard):
        plan = self._plan(shard)
        descs = {d for d, _ in plan.forward_stages()} | \
                {d for d, _ in plan.inverse_stages()}
        assert descs <= set(plan.section_descriptions)
        assert "Run complete" in plan.section_descriptions
        assert plan.variant_name == f"batched2d_{shard}"
        assert plan.global_size.shape == (8, 24, 16)

    @pytest.mark.parametrize("shard", ["batch", "x"])
    def test_testcases_0_to_3(self, devices, tmp_path, shard, monkeypatch):
        from distributedfft_tpu.testing import testcases as tc
        monkeypatch.chdir(tmp_path)
        plan = self._plan(shard)
        # shared Timer CSV exercised via write_csv=True (lands in tmp cwd)
        r0 = tc.testcase0(plan, iterations=2, warmup=1, dims=2)
        assert r0["mean_ms"] > 0 and r0["fused_mean_ms"] > 0
        r1 = tc.testcase1(plan, dims=2, write_csv=False)
        assert r1["residual_sum"] < 1e-6
        r2 = tc.testcase2(plan, iterations=1, dims=2, write_csv=False)
        assert r2["mean_ms"] > 0
        r3 = tc.testcase3(plan, iterations=1, dims=2, write_csv=False)
        assert r3["max_error"] < 1e-8  # f64 roundtrip vs nx*ny-scaled input
        # the CSV went under the batched variant dir with slab-schema name
        from distributedfft_tpu.utils.timer import read_timer_csv
        csvs = list((tmp_path / "benchmarks"
                     / f"batched2d_{shard}").glob("test_*.csv"))
        assert len(csvs) == 1
        blocks = read_timer_csv(str(csvs[0]))
        assert len(blocks) == 2  # testcase0's two gathered iterations
        assert "Run complete" in blocks[0]

    def test_cli_main_runs_testcase3(self, tmp_path, monkeypatch):
        from distributedfft_tpu.cli import batched
        monkeypatch.chdir(tmp_path)
        rc = batched.main(["-nx", "24", "-ny", "16", "-nz", "8",
                           "--shard", "batch", "-t", "3", "-d",
                           "--emulate-devices", "8"])
        assert rc == 0

    def test_cli_rejects_testcase4(self, tmp_path, monkeypatch):
        from distributedfft_tpu.cli import batched
        monkeypatch.chdir(tmp_path)
        rc = batched.main(["-nx", "8", "-ny", "8", "-nz", "4", "-t", "4",
                           "--emulate-devices", "8"])
        assert rc == 2


def test_x_shard_peer2peer_roundtrip(devices, rng):
    """PEER2PEER builds a genuinely different program (no explicit
    collective; GSPMD inserts it at the stage boundary) — it must still
    compute the same transform."""
    from distributedfft_tpu import CommMethod
    plan = Batched2DFFTPlan(4, 32, 32, SlabPartition(8),
                            Config(comm_method=CommMethod.PEER2PEER,
                                   double_prec=True), shard="x")
    x = rng.random((4, 32, 32))
    c = plan.exec_forward(x)
    np.testing.assert_allclose(plan.crop_spectral(c), ref2d(x), atol=1e-9)
    r = plan.crop_real(plan.exec_inverse(c))
    np.testing.assert_allclose(r, x * 32 * 32, atol=1e-8)


def test_autotune_comm_batched2d(devices):
    """The comm racer covers the batched plan's x decomposition (via
    testcases.make_plan kind='batched2d')."""
    from distributedfft_tpu import CommMethod, GlobalSize
    from distributedfft_tpu.testing import autotune as at
    ranked = at.autotune_comm("batched2d", GlobalSize(8, 64, 64),
                              SlabPartition(8), Config(),
                              iterations=1, warmup=0, dims=2)
    assert len(ranked) == 4  # {A2A, P2P} x opt{0,1}
    assert all(c.ok for c in ranked), at.describe_failures(ranked)
    assert {c.comm for c in ranked} == {CommMethod.ALL2ALL,
                                        CommMethod.PEER2PEER}
