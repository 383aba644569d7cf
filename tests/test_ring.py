"""Ring-pipelined transpose (``SendMethod.RING``) tests.

The ring rendering decomposes the global exchange into P-1 distinct
``lax.ppermute`` steps (``parallel/transpose.ring_transpose``) with the
non-gathered post-transpose FFTs pipelined per arriving peer block — the
overlap-capable answer to the measured STREAMS negative result (GSPMD
re-fuses chunked reshards into one collective, zero async ops —
``eval/benchmarks/cpumesh8/OVERLAP.md``). These tests pin (a) bit-exact
agreement of the bare ring with the tiled ``lax.all_to_all`` for every
split/concat role the plans use, (b) bit-level agreement of ring-assembled
plans with the default rendering across slab sequences x pencil dims x
uneven/padded extents x inverse paths, (c) ``jit(grad)`` through a ring
plan, and (d) the HLO regression counts: the realigned (opt1) transpose
emits exactly ONE ``all-to-all``, the ring emits >= P-1
``collective-permute`` ops with the per-block FFTs between them — so an
overlap regression (a re-fused exchange) fails tier-1 instead of silently
degrading.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import distributedfft_tpu as dfft
from distributedfft_tpu import params as pm
from distributedfft_tpu.parallel.mesh import make_slab_mesh
from distributedfft_tpu.parallel.transpose import (
    all_to_all_transpose,
    ring_transpose,
)
from distributedfft_tpu.analysis import contracts, hloscan
from distributedfft_tpu.testing.microbench import async_collective_counts

SEQS = ["ZY_Then_X", "Z_Then_YX", "Y_Then_ZX"]
RING = dfft.Config(send_method=pm.SendMethod.RING)


# ---------------------------------------------------------------------------
# the bare ring: bit-exact data movement
# ---------------------------------------------------------------------------

# Every (split, concat) role the plan families use: slab forward/inverse
# (1,0)/(0,1) and (2,0)/(0,2), pencil t1/t1b (2,1)/(1,2), t2/t2b (1,0)/(0,1),
# batched2d shard='x' (2,1)/(1,2).
@pytest.mark.parametrize("split,concat,shape,ispec,ospec", [
    (1, 0, (8, 16, 3), P("p", None, None), P(None, "p", None)),
    (0, 1, (8, 16, 3), P(None, "p", None), P("p", None, None)),
    (2, 0, (8, 2, 16), P("p", None, None), P(None, None, "p")),
    (0, 2, (8, 2, 16), P(None, None, "p"), P("p", None, None)),
    (2, 1, (4, 8, 16), P(None, "p", None), P(None, None, "p")),
    (1, 2, (4, 16, 8), P(None, None, "p"), P(None, "p", None)),
])
def test_ring_matches_all_to_all(devices, rng, split, concat, shape,
                                 ispec, ospec):
    """The bare ring is pure data movement: BIT-identical to the tiled
    ``lax.all_to_all`` rendering for every axis-role pair the plans use."""
    mesh = make_slab_mesh(8, devices)
    x = rng.random(shape)

    def run(body):
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=ispec,
                                     out_specs=ospec))(x)

    ref = run(lambda xl: all_to_all_transpose(xl, "p", split, concat))
    got = run(lambda xl: ring_transpose(xl, "p", split, concat))
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_ring_pipeline_fn_runs_per_block(devices, rng):
    """``pipeline_fn`` applies to every peer block exactly once, so a
    linear fn commutes with the exchange: ring(x, fn) == fn(a2a(x))."""
    mesh = make_slab_mesh(8, devices)
    x = rng.random((8, 16, 3))
    ispec, ospec = P("p", None, None), P(None, "p", None)
    got = jax.jit(jax.shard_map(
        lambda xl: ring_transpose(xl, "p", 1, 0,
                                  pipeline_fn=lambda b: 2.0 * b + 1.0),
        mesh=mesh, in_specs=ispec, out_specs=ospec))(x)
    ref = jax.jit(jax.shard_map(
        lambda xl: 2.0 * all_to_all_transpose(xl, "p", 1, 0) + 1.0,
        mesh=mesh, in_specs=ispec, out_specs=ospec))(x)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_ring_indivisible_extent_raises(devices):
    mesh = make_slab_mesh(8, devices)
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(jax.shard_map(
            lambda xl: ring_transpose(xl, "p", 1, 0),
            mesh=mesh, in_specs=P("p", None, None),
            out_specs=P(None, "p", None)))(np.zeros((8, 12, 3)))


# ---------------------------------------------------------------------------
# ring-assembled plans vs the default rendering
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq", SEQS)
def test_slab_ring_matches_default(devices, rng, seq):
    """Ring slab plans agree with the default (SYNC all_to_all) rendering
    to the bit for every sequence, forward and inverse — the transposed
    data is identical and the pipelined per-block FFTs are the same
    per-vector transforms the monolithic stage batches."""
    g = dfft.GlobalSize(16, 16, 16)
    x = rng.random(g.shape)
    base = dfft.SlabFFTPlan(g, pm.SlabPartition(8), dfft.Config(),
                            sequence=seq)
    ring = dfft.SlabFFTPlan(g, pm.SlabPartition(8), RING, sequence=seq)
    np.testing.assert_array_equal(np.asarray(ring.exec_r2c(x)),
                                  np.asarray(base.exec_r2c(x)))
    rb = np.asarray(base.exec_c2r(base.exec_r2c(x)))
    rr = np.asarray(ring.exec_c2r(ring.exec_r2c(x)))
    np.testing.assert_array_equal(rr, rb)
    np.testing.assert_allclose(ring.crop_real(rr) / g.n_total, x,
                               rtol=1e-10, atol=1e-10)


@pytest.mark.parametrize("seq", SEQS)
def test_slab_ring_uneven_extents(devices, rng, seq):
    """Uneven/padded extents (20 on the 8-way x axis; the R2C halved axis
    ``N/2+1`` is odd and padded wherever a sequence scatters it) against
    the host truth."""
    g = dfft.GlobalSize(20, 16, 16)
    plan = dfft.SlabFFTPlan(g, pm.SlabPartition(8), RING, sequence=seq)
    x = rng.random(g.shape)
    c = plan.crop_spectral(plan.exec_r2c(x))
    ax = {"ZY_Then_X": 2, "Z_Then_YX": 2, "Y_Then_ZX": 1}[seq]
    truth = np.fft.rfft(x, axis=ax)
    for a in (0, 1, 2):
        if a != ax:
            truth = np.fft.fft(truth, axis=a)
    np.testing.assert_allclose(c, truth, rtol=1e-9, atol=1e-9)
    r = plan.crop_real(plan.exec_c2r(plan.exec_r2c(x)))
    np.testing.assert_allclose(r / g.n_total, x, rtol=1e-10, atol=1e-10)


@pytest.mark.parametrize("seq", SEQS)
def test_slab_ring_c2c(devices, rng, seq):
    """C2C ring plans (the inverse pipelines the r2c-axis IFFT per block
    where it is not the gathered axis) vs the default rendering, to the
    1e-12 bit-level convention of the STREAMS tests."""
    g = dfft.GlobalSize(16, 16, 16)
    x = rng.random(g.shape) + 1j * rng.random(g.shape)
    base = dfft.SlabFFTPlan(g, pm.SlabPartition(8), dfft.Config(),
                            sequence=seq, transform="c2c")
    ring = dfft.SlabFFTPlan(g, pm.SlabPartition(8), RING, sequence=seq,
                            transform="c2c")
    np.testing.assert_array_equal(np.asarray(ring.exec_c2c(x)),
                                  np.asarray(base.exec_c2c(x)))
    rb = np.asarray(base.exec_c2c_inv(base.exec_c2c(x))) / g.n_total
    rr = np.asarray(ring.exec_c2c_inv(ring.exec_c2c(x))) / g.n_total
    np.testing.assert_allclose(rr, rb, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("dims", [1, 2, 3])
def test_pencil_ring_partial_dims(devices, rng, dims):
    """Pencil ring (both transposes rendered as rings via resolved_snd2)
    at every partial-transform depth, on an uneven global size whose
    halved z axis (nz_out = 9) pads to the p2 mesh extent — bit-identical
    to the default rendering, inverse paths included."""
    g = dfft.GlobalSize(20, 16, 16)
    x = rng.random(g.shape)
    base = dfft.PencilFFTPlan(g, pm.PencilPartition(2, 4), dfft.Config())
    ring = dfft.PencilFFTPlan(g, pm.PencilPartition(2, 4), RING)
    np.testing.assert_array_equal(np.asarray(ring.exec_r2c(x, dims=dims)),
                                  np.asarray(base.exec_r2c(x, dims=dims)))
    rb = base.exec_c2r(base.exec_r2c(x, dims=dims), dims=dims)
    rr = ring.exec_c2r(ring.exec_r2c(x, dims=dims), dims=dims)
    np.testing.assert_array_equal(np.asarray(rr), np.asarray(rb))


def test_pencil_ring_matches_truth(devices, rng):
    g = dfft.GlobalSize(20, 16, 16)
    x = rng.random(g.shape)
    plan = dfft.PencilFFTPlan(g, pm.PencilPartition(4, 2), RING)
    c = plan.crop_spectral(plan.exec_r2c(x))
    np.testing.assert_allclose(c, np.fft.rfftn(x), rtol=1e-10, atol=1e-10)
    r = plan.crop_real(plan.exec_c2r(plan.exec_r2c(x)))
    np.testing.assert_allclose(r / g.n_total, x, rtol=1e-10, atol=1e-10)


def test_batched2d_ring_matches_default(devices, rng):
    b, m = 8, 16
    base = dfft.Batched2DFFTPlan(b, m, m, pm.SlabPartition(8),
                                 dfft.Config(), shard="x")
    ring = dfft.Batched2DFFTPlan(b, m, m, pm.SlabPartition(8), RING,
                                 shard="x")
    x = rng.random((b, m, m))
    np.testing.assert_array_equal(
        np.asarray(ring.exec_forward(ring.pad_input(x))),
        np.asarray(base.exec_forward(base.pad_input(x))))
    rr = ring.crop_real(ring.exec_inverse(ring.exec_forward(
        ring.pad_input(x))))
    np.testing.assert_allclose(rr, x * m * m, rtol=1e-10, atol=1e-10)


def test_grad_through_ring_slab_roundtrip(devices, rng):
    """jit(grad) through a ring plan: ppermute and the per-block FFTs
    differentiate (the unnormalized roundtrip / N^3 is the identity, so
    dloss/dx = w — the test_autodiff contract)."""
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(
        g, pm.SlabPartition(8),
        dfft.Config(double_prec=True, fft_backend="matmul",
                    send_method=pm.SendMethod.RING),
        sequence="Z_Then_YX")
    fwd, inv = plan.forward_fn(), plan.inverse_fn()
    w = rng.random(g.shape)

    def loss(x):
        return jnp.sum(jnp.asarray(w) * inv(fwd(x)) / g.n_total)

    got = np.asarray(jax.jit(jax.grad(loss))(rng.random(g.shape)))
    np.testing.assert_allclose(got, w, atol=1e-10)


# ---------------------------------------------------------------------------
# HLO regression counts (the overlap detector as a tier-1 gate)
# ---------------------------------------------------------------------------

def test_hlo_opt1_single_all_to_all(devices):
    """The realigned (opt1) slab forward emits exactly ONE all-to-all (the
    pure exchange) and no collective-permutes — the monolithic rendering's
    signature, so a regression that splits or duplicates the exchange (or
    re-fuses a ring into it) is caught by count, not by timing drift.
    Pinned via the declarative contract (analysis/contracts.py: slab/a2a
    declares all_to_all == 1, collective_permute == 0 plus the payload
    reconciliation); the census double-check keeps the count visible
    here."""
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16), pm.SlabPartition(8),
                            dfft.Config(comm_method=pm.CommMethod.ALL2ALL,
                                        opt=1))
    assert contracts.verify_plan(plan, "forward") == []
    counts = async_collective_counts(hloscan.compiled_text(plan, "forward"))
    assert counts["all_to_all"] + counts["all_to_all_start"] == 1
    assert counts["collective_permute"] == 0
    assert counts["collective_permute_start"] == 0


@pytest.mark.parametrize("seq", SEQS)
def test_hlo_ring_p_minus_1_permutes(devices, seq):
    """A ring-assembled slab forward contains >= P-1 collective-permute
    ops and ZERO all-to-alls: the exchange is genuinely split into
    distinct steps XLA cannot re-fuse (the chunked STREAMS reshards WERE
    re-fused — OVERLAP.md), asserted on the 8-device CPU mesh so an
    overlap regression fails tier-1. The slab/ring contract declares
    exactly these rules (>= P-1 permutes, 0 all-to-alls, the (P-1)/P
    payload discount) — checked for both directions."""
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16), pm.SlabPartition(8),
                            RING, sequence=seq)
    contract = contracts.contract_for(plan, "forward")
    assert any(r.op == "collective_permute" and r.cmp == ">=" and
               r.value == 7 for r in contract.rules)  # P-1 on the 8-way mesh
    assert any(r.op == "all_to_all" and r.cmp == "==" and r.value == 0
               for r in contract.rules)
    assert contracts.verify_plan(plan, "forward", contract=contract) == []
    assert contracts.verify_plan(plan, "inverse") == []


def test_hlo_ring_pipelines_fft_between_permutes(devices):
    """Z_Then_YX pipelines the post-transpose y FFT per peer block: the
    lowered ring program carries one FFT op per block (>= P, vs the sync
    rendering's one batched op per stage), each consuming its own
    permute's output — the compute the scheduler can run while later ring
    steps are on the wire."""
    g = dfft.GlobalSize(16, 16, 16)
    ring = dfft.SlabFFTPlan(g, pm.SlabPartition(8), RING,
                            sequence="Z_Then_YX")
    sync = dfft.SlabFFTPlan(g, pm.SlabPartition(8), dfft.Config(),
                            sequence="Z_Then_YX")
    ring_txt = hloscan.lower_plan(ring, "forward").as_text()
    assert len(re.findall(r"collective_permute", ring_txt)) >= 7
    # Count compiled FFT ops: the staged module may share one private
    # function body between identical per-block calls.
    n_ring = len(re.findall(r" fft\(", hloscan.compiled_text(ring, "forward")))
    n_sync = len(re.findall(r" fft\(", hloscan.compiled_text(sync, "forward")))
    assert n_ring >= n_sync + 7  # one extra per non-local peer block


def test_hlo_pencil_ring_both_transposes(devices):
    """Pencil ring at dims=3: transpose 1 rings over p2 (3 permutes on a
    2x4 grid), transpose 2 over p1 (1 permute) — both all-to-alls gone.
    The pencil/ring contract sums the per-transpose ring steps."""
    plan = dfft.PencilFFTPlan(dfft.GlobalSize(16, 16, 16),
                              pm.PencilPartition(2, 4), RING)
    contract = contracts.contract_for(plan, "forward")
    assert any(r.op == "collective_permute" and r.cmp == ">=" and
               r.value == 4 for r in contract.rules)  # (p2-1) + (p1-1)
    assert contracts.verify_plan(plan, "forward", contract=contract) == []


# ---------------------------------------------------------------------------
# the race: autotune/wisdom include the ring variant
# ---------------------------------------------------------------------------

def test_autotune_comm_races_ring(devices):
    """race_send=True includes exactly one ring candidate (the ring is
    comm/opt-agnostic), it measures, and a ring winner folds into a Config
    whose send_method is RING."""
    from distributedfft_tpu.testing import autotune as at

    ranked = at.autotune_comm("slab", dfft.GlobalSize(16, 16, 16),
                              pm.SlabPartition(8), dfft.Config(),
                              iterations=1, warmup=0, race_send=True)
    rings = [c for c in ranked if c.send is pm.SendMethod.RING]
    assert len(rings) == 1
    assert rings[0].label.endswith("/ring")
    assert rings[0].ok, rings[0].error
    cfg = at.apply_best_comm([rings[0]], dfft.Config())
    assert cfg.send_method is pm.SendMethod.RING
    assert cfg.streams_chunks is None
