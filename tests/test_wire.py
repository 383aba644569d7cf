"""Compressed-wire transpose (``Config.wire_dtype``) tests.

The wire layer (``parallel/transpose`` ``wire_encode``/``wire_decode``)
selects how complex shards are encoded immediately before each global
exchange and decoded immediately after: ``native`` is the bit-identical
pass-through, ``bf16`` the opt-in lossy planar (real, imag) bf16 pair that
halves a complex64 exchange's wire bytes. These tests pin

* (a) NATIVE wire bit-identity: plans built with an explicit
  ``wire_dtype="native"`` agree to the bit with the pre-wire default plans
  for every rendering (all-to-all / opt1 / ring / GSPMD) x slab sequences
  x pencil dims 1-3 x uneven ``N/2+1`` extents x inverse paths, and their
  lowered HLO carries ZERO bf16 — the wire layer is structurally inert;
* (b) the bf16 wire's measured max-rel roundtrip error on the CPU mesh
  stays within the README-documented 2e-2 bound (typical: slab ~4e-3 at
  2 wire crossings, pencil ~1e-2 at 4);
* (c) ``jit(grad)`` traces through a compressed plan (convert/ppermute
  differentiate);
* (d) wisdom schema migration: legacy (v1-v3) stores migrate —
  ``local_fft`` carries over, ``comm`` re-races — and records round-trip
  at the current version (v4 since the RING_OVERLAP race axis);
* (e) the autotune wire axis: ``race_wire`` twins are error-gated and the
  winner folds; ``wire_dtype="auto"`` resolves through the store;
* (f) the microbench satellite: ``async_collective_counts`` counts the
  encode/decode ``convert`` ops, and the compressed ring plan still
  satisfies the >= P-1 collective-permute overlap gate (compression must
  not let GSPMD re-fuse the split exchange);
* (g) the Timer CSV filename wire code: native keeps the legacy name
  byte-for-byte, bf16 appends ``_w1`` so wire variants never share a CSV.
"""

import json

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
    wire_decode,
    wire_encode,
    wire_nbytes,
)
from distributedfft_tpu.analysis import contracts, hloscan, jaxprlint
from distributedfft_tpu.testing.microbench import async_collective_counts
from distributedfft_tpu.utils import wisdom
from distributedfft_tpu.utils.timer import benchmark_filename

SEQS = ["ZY_Then_X", "Z_Then_YX", "Y_Then_ZX"]
# The documented hard bound on the bf16 wire's max-rel roundtrip error
# (README "wire dtype" table; DEFAULT_WIRE_ERROR_BUDGET).
BF16_BOUND = 2e-2

RENDERINGS = {
    "a2a": dict(comm_method=pm.CommMethod.ALL2ALL),
    "opt1": dict(comm_method=pm.CommMethod.ALL2ALL, opt=1),
    "p2p": dict(comm_method=pm.CommMethod.PEER2PEER),
    "ring": dict(send_method=pm.SendMethod.RING),
}


def _cfg(rendering: str, wire: str) -> dfft.Config:
    return dfft.Config(wire_dtype=wire, **RENDERINGS[rendering])


def _rel_err(a, b) -> float:
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


# ---------------------------------------------------------------------------
# the bare wire: encode/decode and the transpose functions
# ---------------------------------------------------------------------------

def test_wire_encode_decode_roundtrip(rng):
    x = (rng.random((4, 6, 5)) + 1j * rng.random((4, 6, 5))).astype(
        np.complex64)
    y = wire_encode(x, "bf16")
    assert y.shape == (2,) + x.shape and y.dtype == jnp.bfloat16
    z = np.asarray(wire_decode(y, x.dtype, "bf16"))
    assert z.dtype == np.complex64
    assert _rel_err(z, x) < 6e-3  # one bf16 truncation
    # native and non-complex payloads pass through untouched.
    assert wire_encode(x, "native") is x
    r = jnp.asarray(rng.random((3, 3)).astype(np.float32))
    assert wire_encode(r, "bf16") is r


def test_wire_nbytes_halves_complex64():
    shape = (8, 16, 9)
    native = wire_nbytes(shape, np.complex64, "native")
    assert native == 8 * 16 * 9 * 8
    assert wire_nbytes(shape, np.complex64, "bf16") == native // 2
    # complex128 compresses 4x; real payloads never compress.
    assert wire_nbytes(shape, np.complex128, "bf16") == \
        wire_nbytes(shape, np.complex128, "native") // 4
    assert wire_nbytes(shape, np.float32, "bf16") == \
        wire_nbytes(shape, np.float32, "native")


@pytest.mark.parametrize("split,concat,shape,ispec,ospec", [
    (1, 0, (8, 16, 3), P("p", None, None), P(None, "p", None)),
    (0, 2, (8, 2, 16), P(None, None, "p"), P("p", None, None)),
])
@pytest.mark.parametrize("realigned", [False, True])
def test_bare_transpose_wires(devices, rng, split, concat, shape, ispec,
                              ospec, realigned):
    """Both all_to_all renderings and the ring: native wire bit-identical
    to the wire-less call, bf16 within one truncation's error."""
    mesh = make_slab_mesh(8, devices)
    x = (rng.random(shape) + 1j * rng.random(shape)).astype(np.complex64)

    def run(body):
        return np.asarray(jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=ispec, out_specs=ospec))(x))

    ref = run(lambda xl: all_to_all_transpose(xl, "p", split, concat,
                                              realigned=realigned))
    nat = run(lambda xl: all_to_all_transpose(
        xl, "p", split, concat, realigned=realigned, wire="native"))
    assert np.array_equal(nat, ref)
    bf = run(lambda xl: all_to_all_transpose(
        xl, "p", split, concat, realigned=realigned, wire="bf16"))
    assert _rel_err(bf, ref) < 6e-3
    if not realigned:
        rnat = run(lambda xl: ring_transpose(xl, "p", split, concat,
                                             wire="native"))
        assert np.array_equal(rnat, ref)
        rbf = run(lambda xl: ring_transpose(xl, "p", split, concat,
                                            wire="bf16"))
        assert _rel_err(rbf, ref) < 6e-3


# ---------------------------------------------------------------------------
# (a) native wire: bit-identical plans, bf16-free HLO
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rendering", sorted(RENDERINGS))
@pytest.mark.parametrize("seq", SEQS)
def test_slab_native_wire_bit_identical(devices, rng, seq, rendering):
    """Uneven extents (20 over the 8-way x axis; the odd halved N/2+1 axis
    padded wherever a sequence scatters it), forward and inverse."""
    g = dfft.GlobalSize(20, 16, 16)
    x = rng.random(g.shape)
    base = dfft.SlabFFTPlan(g, pm.SlabPartition(8),
                            dfft.Config(**RENDERINGS[rendering]),
                            sequence=seq)
    nat = dfft.SlabFFTPlan(g, pm.SlabPartition(8),
                           _cfg(rendering, "native"), sequence=seq)
    np.testing.assert_array_equal(np.asarray(nat.exec_r2c(x)),
                                  np.asarray(base.exec_r2c(x)))
    np.testing.assert_array_equal(
        np.asarray(nat.exec_c2r(nat.exec_r2c(x))),
        np.asarray(base.exec_c2r(base.exec_r2c(x))))


@pytest.mark.parametrize("rendering", sorted(RENDERINGS))
@pytest.mark.parametrize("dims", [1, 2, 3])
def test_pencil_native_wire_bit_identical(devices, rng, dims, rendering):
    g = dfft.GlobalSize(20, 16, 16)
    x = rng.random(g.shape)
    base = dfft.PencilFFTPlan(g, pm.PencilPartition(2, 4),
                              dfft.Config(**RENDERINGS[rendering]))
    nat = dfft.PencilFFTPlan(g, pm.PencilPartition(2, 4),
                             _cfg(rendering, "native"))
    np.testing.assert_array_equal(
        np.asarray(nat.exec_r2c(x, dims=dims)),
        np.asarray(base.exec_r2c(x, dims=dims)))
    np.testing.assert_array_equal(
        np.asarray(nat.exec_c2r(nat.exec_r2c(x, dims=dims), dims=dims)),
        np.asarray(base.exec_c2r(base.exec_r2c(x, dims=dims), dims=dims)))


@pytest.mark.parametrize("rendering", sorted(RENDERINGS))
def test_native_wire_hlo_carries_no_bf16(devices, rendering):
    """Structural pin of bit-identity: a native-wire plan's program
    contains no bf16 anywhere — the wire layer is inert, not merely
    numerically invisible. Pinned three ways through the analysis
    subsystem: the contract's forbidden-op rule on the COMPILED module,
    a direct scan of the STAGED module, and the jaxpr lint (zero bf16
    conversions traced)."""
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16),
                            pm.SlabPartition(8), _cfg(rendering, "native"))
    contract = contracts.contract_for(plan, "forward")
    assert any(r.kind == "forbid" and r.op == "bf16"
               for r in contract.rules)
    assert contracts.verify_plan(plan, "forward", contract=contract) == []
    assert not hloscan.contains_bf16(hloscan.staged_text(plan,
                                                         "forward")[1])
    assert jaxprlint.lint_plan(plan, "forward") == []


def test_batched2d_native_wire_bit_identical(devices, rng):
    b, m = 8, 16
    x = rng.random((b, m, m))
    for rendering in sorted(RENDERINGS):
        base = dfft.Batched2DFFTPlan(b, m, m, pm.SlabPartition(8),
                                     dfft.Config(**RENDERINGS[rendering]),
                                     shard="x")
        nat = dfft.Batched2DFFTPlan(b, m, m, pm.SlabPartition(8),
                                    _cfg(rendering, "native"), shard="x")
        np.testing.assert_array_equal(
            np.asarray(nat.exec_forward(nat.pad_input(x))),
            np.asarray(base.exec_forward(base.pad_input(x))))


# ---------------------------------------------------------------------------
# (b) bf16 wire: measured error within the documented bound
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rendering", sorted(RENDERINGS))
@pytest.mark.parametrize("seq", SEQS)
def test_slab_bf16_roundtrip_within_bound(devices, rng, seq, rendering):
    g = dfft.GlobalSize(20, 16, 16)
    x = rng.random(g.shape).astype(np.float32)
    plan = dfft.SlabFFTPlan(g, pm.SlabPartition(8), _cfg(rendering, "bf16"),
                            sequence=seq)
    r = plan.crop_real(plan.exec_c2r(plan.exec_r2c(x)))
    assert _rel_err(r / g.n_total, x) < BF16_BOUND


@pytest.mark.parametrize("rendering", sorted(RENDERINGS))
def test_pencil_bf16_roundtrip_within_bound(devices, rng, rendering):
    """Pencil crosses the wire FOUR times per roundtrip (two transposes
    each way) — still inside the documented bound."""
    g = dfft.GlobalSize(20, 16, 16)
    x = rng.random(g.shape).astype(np.float32)
    plan = dfft.PencilFFTPlan(g, pm.PencilPartition(2, 4),
                              _cfg(rendering, "bf16"))
    r = plan.crop_real(plan.exec_c2r(plan.exec_r2c(x)))
    assert _rel_err(r / g.n_total, x) < BF16_BOUND


def test_bf16_forward_vs_native_single_crossing(devices, rng):
    """One wire crossing (the forward transpose) costs ~one bf16
    truncation relative to the native spectrum."""
    g = dfft.GlobalSize(16, 16, 16)
    x = rng.random(g.shape).astype(np.float32)
    nat = dfft.SlabFFTPlan(g, pm.SlabPartition(8), _cfg("a2a", "native"))
    bf = dfft.SlabFFTPlan(g, pm.SlabPartition(8), _cfg("a2a", "bf16"))
    assert _rel_err(bf.exec_r2c(x), nat.exec_r2c(x)) < 6e-3


# ---------------------------------------------------------------------------
# (c) autodiff through a compressed plan
# ---------------------------------------------------------------------------

def test_grad_through_bf16_ring_roundtrip(devices, rng):
    """jit(grad) through a compressed ring plan: ppermute and the
    encode/decode converts differentiate. The bf16 wire rounds the
    tangents too, so the identity-roundtrip gradient matches w to wire
    precision, not to the bit."""
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(
        g, pm.SlabPartition(8),
        dfft.Config(double_prec=True, fft_backend="matmul",
                    send_method=pm.SendMethod.RING, wire_dtype="bf16"),
        sequence="Z_Then_YX")
    fwd, inv = plan.forward_fn(), plan.inverse_fn()
    w = rng.random(g.shape)

    def loss(x):
        return jnp.sum(jnp.asarray(w) * inv(fwd(x)) / g.n_total)

    got = np.asarray(jax.jit(jax.grad(loss))(rng.random(g.shape)))
    np.testing.assert_allclose(got, w, atol=5e-2)


# ---------------------------------------------------------------------------
# (d) wisdom schema migration round-trip (current version: 5)
# ---------------------------------------------------------------------------

def _legacy_store(tmp_path, version: int):
    key = wisdom.plan_key("slab", (16, 16, 16), False, pm.SlabPartition(8),
                          pm.FFTNorm.NONE)
    lrec = {"fft_backend": "xla", "mxu_precision": None,
            "mxu_direct_max": None}
    crec = {"comm_method": "All2All", "comm_method2": None, "opt": 1,
            "send_method": None, "streams_chunks": None}
    if version >= 3:
        # v3 grew the wire axis; v4 grew the RING_OVERLAP send race.
        # Neither ever saw the overlap depth/sub-block axes (v5).
        crec.update(wire_dtype="native", wire_raced=True)
    if version >= 4:
        crec.update(send_method="RingOverlap")
    path = tmp_path / f"wisdom_v{version}.json"
    path.write_text(json.dumps({
        "version": version,
        "entries": {key: {"local_fft": lrec, "comm": crec}}}))
    return wisdom.WisdomStore(str(path)), key


@pytest.mark.parametrize("version", [1, 2, 3, 4])
def test_legacy_store_migrates_to_current(tmp_path, version):
    """Legacy (v1-v4) stores load as a migrated current-version view:
    local_fft records carry over verbatim, comm records (raced without
    the wire axis for v1/v2, without the RING_OVERLAP axis for v3,
    without the overlap depth/sub-block axes for v4) read as misses;
    the next record persists the current version on disk."""
    store, key = _legacy_store(tmp_path, version)
    data = store.load()
    assert data["version"] == wisdom.WISDOM_VERSION == 5
    assert "comm" not in data["entries"][key]
    assert data["entries"][key]["local_fft"]["fft_backend"] == "xla"
    assert store.lookup(key, "comm") is None
    rec = {"comm_method": "All2All", "comm_method2": None, "opt": 1,
           "send_method": None, "streams_chunks": None,
           "wire_dtype": "bf16", "wire_raced": True}
    assert store.record(key, "comm", rec)
    raw = json.loads(open(store.path).read())
    assert raw["version"] == wisdom.WISDOM_VERSION
    assert raw["entries"][key]["comm"]["wire_dtype"] == "bf16"
    assert raw["entries"][key]["local_fft"]["fft_backend"] == "xla"
    # Round-trip: the persisted v3 record folds back with its wire axis.
    folded = wisdom._fold_comm_rec(dfft.Config(), store.lookup(key, "comm"))
    assert folded.wire_dtype == "bf16"
    assert folded.comm_method is pm.CommMethod.ALL2ALL and folded.opt == 1


def test_stale_wire_dtype_reads_as_miss():
    with pytest.raises(ValueError, match="wire_dtype"):
        wisdom._fold_comm_rec(dfft.Config(), {
            "comm_method": "All2All", "comm_method2": None, "opt": 0,
            "send_method": None, "streams_chunks": None,
            "wire_dtype": "fp8"})


# ---------------------------------------------------------------------------
# (e) autotune: the wire axis and "auto" resolution
# ---------------------------------------------------------------------------

def test_autotune_comm_races_wire_twins(devices):
    """race_wire=True twins every cell with an error-gated bf16 candidate;
    natives come first (the error reference) and every measured twin
    carries a finite wire_rel_err."""
    from distributedfft_tpu.testing import autotune as at

    ranked = at.autotune_comm("slab", dfft.GlobalSize(16, 16, 16),
                              pm.SlabPartition(8), dfft.Config(),
                              iterations=1, warmup=0, race_opt=False,
                              race_wire=True)
    wires = {c.wire for c in ranked}
    assert wires == {"native", "bf16"}
    n_nat = sum(1 for c in ranked if c.wire == "native")
    n_bf = sum(1 for c in ranked if c.wire == "bf16")
    assert n_nat == n_bf
    for c in ranked:
        if c.wire == "bf16" and c.ok:
            assert np.isfinite(c.wire_rel_err)
            assert c.label.endswith("/bf16")
    cfg = at.apply_best_comm(ranked, dfft.Config())
    assert cfg.wire_dtype in ("native", "bf16")


def test_autotune_wire_budget_gates_bf16(devices):
    """An impossible error budget rejects the compressed twin, so 'auto'
    degrades to the bit-identical native wire."""
    from distributedfft_tpu.testing import autotune as at

    ranked = at.autotune_wire("slab", dfft.GlobalSize(16, 16, 16),
                              pm.SlabPartition(8),
                              dfft.Config(comm_method=pm.CommMethod.ALL2ALL),
                              iterations=1, warmup=0, error_budget=1e-12)
    bf = next(c for c in ranked if c.wire == "bf16")
    assert not bf.ok and "over budget" in bf.error
    cfg = at.apply_best_comm(ranked, dfft.Config())
    assert cfg.wire_dtype == "native"


def test_autotune_wire_preserves_send_method2(devices):
    """The wire-only race measures the caller's FIXED rendering: an
    explicit pencil send_method2 must reach the timed candidate plans
    (and survive resolution) rather than being normalized away."""
    base = dfft.Config(comm_method=pm.CommMethod.ALL2ALL,
                       send_method2=pm.SendMethod.RING,
                       wire_dtype="auto", use_wisdom=False)
    plan = dfft.PencilFFTPlan(dfft.GlobalSize(16, 16, 16),
                              pm.PencilPartition(2, 4), base)
    assert plan.config.send_method2 is pm.SendMethod.RING
    assert plan.config.wire_dtype in ("native", "bf16")


def test_wire_auto_resolves_and_records(devices, tmp_path):
    """wire_dtype='auto' with an explicit comm method races once, records
    the 'wire' slot, and a second construction reuses the record (the
    store answers, no re-race)."""
    path = str(tmp_path / "w.json")
    cfg = dfft.Config(comm_method=pm.CommMethod.ALL2ALL, opt=1,
                      wire_dtype="auto", wisdom_path=path)
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16),
                            pm.SlabPartition(8), cfg)
    assert plan.config.wire_dtype in ("native", "bf16")
    assert plan.config.comm_method is pm.CommMethod.ALL2ALL
    assert plan.config.opt == 1
    raw = json.loads(open(path).read())
    assert raw["version"] == wisdom.WISDOM_VERSION
    (entry,) = [e for e in raw["entries"].values() if "wire" in e]
    assert entry["wire"]["wire_dtype"] == plan.config.wire_dtype
    # Hit path: poison the recorded winner to prove the store answers. A
    # bf16 record must carry a within-budget wire_rel_err or the fold-time
    # budget re-check (deliberately) reads it as a miss.
    target = next(k for k, e in raw["entries"].items() if "wire" in e)
    other = ("bf16" if plan.config.wire_dtype == "native" else "native")
    raw["entries"][target]["wire"] = {"wire_dtype": other,
                                      "wire_rel_err": 1e-3}
    open(path, "w").write(json.dumps(raw))
    plan2 = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16),
                             pm.SlabPartition(8), cfg)
    assert plan2.config.wire_dtype == other


def test_wire_hit_rechecks_tighter_budget(devices, tmp_path):
    """The budget is not part of the plan key, so a recorded bf16 winner
    must be re-validated against THE CALLER'S budget at fold time: a
    tighter --wire-error-budget turns the hit into a miss instead of
    silently reusing a lossy wire outside the user's tolerance."""
    path = str(tmp_path / "w.json")
    store = wisdom.WisdomStore(path)
    # The key must match what SlabFFTPlan's resolution builds — sequence
    # included (a sequence-less key is a different entry that would never
    # hit, silently turning this into a race test).
    key = wisdom.plan_key("slab", (16, 16, 16), False, pm.SlabPartition(8),
                          pm.FFTNorm.NONE,
                          sequence=pm.SlabSequence.ZY_THEN_X)
    store.record(key, "wire", {"wire_dtype": "bf16", "wire_rel_err": 4e-3})
    # A budget the record satisfies hits and folds the recorded bf16
    # as-is (no re-race, record untouched).
    loose = dfft.Config(comm_method=pm.CommMethod.ALL2ALL,
                        wire_dtype="auto", wire_error_budget=1e-2,
                        wisdom_path=path)
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16),
                            pm.SlabPartition(8), loose)
    assert plan.config.wire_dtype == "bf16"
    assert store.lookup(key, "wire")["wire_rel_err"] == 4e-3
    # An impossible budget turns the same record into a miss: the re-race
    # rejects the bf16 twin too, resolution lands on the bit-identical
    # native wire, and the re-raced (native) winner replaces the record.
    tight = dfft.Config(comm_method=pm.CommMethod.ALL2ALL,
                        wire_dtype="auto", wire_error_budget=1e-12,
                        wisdom_path=path)
    plan2 = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16),
                             pm.SlabPartition(8), tight)
    assert plan2.config.wire_dtype == "native"
    rec = store.lookup(key, "wire")
    assert rec["wire_dtype"] == "native"
    assert rec["wire_budget"] == 1e-12
    # And the other direction: a LOOSER budget must not stay pinned to a
    # native winner raced under the tight one — the hit reads as a miss
    # and the re-race (whose winner is time-dependent) re-records under
    # the caller's budget.
    plan3 = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16),
                             pm.SlabPartition(8), loose)
    assert plan3.config.wire_dtype in ("native", "bf16")
    assert store.lookup(key, "wire")["wire_budget"] == \
        loose.resolved_wire_budget()


def test_comm_hit_with_other_wire_reraces(devices, tmp_path):
    """A comm record whose winner was raced under a different wire
    encoding must not be folded with only its wire field rewritten (the
    ranking may not transfer): an explicit-wire caller re-races at its
    wire, and the new record carries it."""
    path = str(tmp_path / "w.json")
    store = wisdom.WisdomStore(path)
    key = wisdom.plan_key("slab", (16, 16, 16), False, pm.SlabPartition(8),
                          pm.FFTNorm.NONE,
                          sequence=pm.SlabSequence.ZY_THEN_X)
    store.record(key, "comm", {
        "comm_method": "Peer2Peer", "comm_method2": None, "opt": 0,
        "send_method": None, "streams_chunks": None,
        "wire_dtype": "bf16", "wire_raced": True, "wire_rel_err": 1e-3})
    cfg = dfft.Config(comm_method="auto", wire_dtype="native",
                      wisdom_path=path)
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16),
                            pm.SlabPartition(8), cfg)
    assert plan.config.wire_dtype == "native"
    rec = store.lookup(key, "comm")
    # Re-raced at the caller's wire (the bf16-raced record did not hit):
    # the fresh record carries native and no raced wire axis.
    assert rec["wire_dtype"] == "native"
    assert rec["wire_raced"] is False


def test_wire_auto_single_device_resolves_native(tmp_path):
    """No exchange -> no wire: 'auto' resolves to native without a race
    or a store touch."""
    path = str(tmp_path / "w.json")
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(8, 8, 8), pm.SlabPartition(1),
                            dfft.Config(wire_dtype="auto",
                                        wisdom_path=path))
    assert plan.config.wire_dtype == "native"
    import os
    assert not os.path.exists(path)


def test_unresolved_wire_auto_rejected_by_base_plan():
    """A Config still carrying wire 'auto' must never reach a plan body
    (the DistFFTPlan constructor guard extends to the wire axis)."""
    assert wisdom.unresolved(dfft.Config(wire_dtype="auto",
                                         fft_backend="xla"))
    # The local-FFT backend named: an unset one is unresolved too (the
    # platform default is settled at construction).
    assert not wisdom.unresolved(dfft.Config(wire_dtype="bf16",
                                             fft_backend="xla"))


# ---------------------------------------------------------------------------
# (f) HLO gates: compression must not break the ring's split exchange
# ---------------------------------------------------------------------------

def test_hlo_bf16_ring_keeps_p_minus_1_permutes(devices):
    """The satellite fix's assertion: the encode/decode converts fused
    into the collective operands did NOT let GSPMD re-fuse the ring — the
    compressed plan still shows >= P-1 distinct collective-permutes, zero
    all-to-alls, and a nonzero convert count attributing the wire casts."""
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16),
                            pm.SlabPartition(8), _cfg("ring", "bf16"),
                            sequence="Z_Then_YX")
    # The slab/ring contract (>= P-1 permutes, 0 all-to-alls, halved
    # payload) holds under compression; the convert count attributes the
    # wire casts.
    assert contracts.verify_plan(plan, "forward") == []
    counts = async_collective_counts(hloscan.compiled_text(plan, "forward"))
    assert counts["collective_permute"] + \
        counts["collective_permute_start"] >= 7  # P-1 on the 8-way mesh
    assert counts["all_to_all"] + counts["all_to_all_start"] == 0
    assert counts["convert"] > 0


def test_hlo_bf16_opt1_still_single_all_to_all(devices):
    """Compression composes with the realigned rendering without
    splitting or duplicating the exchange: still exactly ONE all-to-all,
    now over the bf16 planes."""
    plan = dfft.SlabFFTPlan(dfft.GlobalSize(16, 16, 16),
                            pm.SlabPartition(8), _cfg("opt1", "bf16"))
    assert contracts.verify_plan(plan, "forward") == []
    txt = hloscan.compiled_text(plan, "forward")
    counts = async_collective_counts(txt)
    assert counts["all_to_all"] + counts["all_to_all_start"] == 1
    assert hloscan.contains_bf16(txt)


# ---------------------------------------------------------------------------
# (g) Timer CSV filename wire code
# ---------------------------------------------------------------------------

def test_benchmark_filename_wire_code():
    g = dfft.GlobalSize(256, 256, 256)
    nat = benchmark_filename("b", "slab_default", dfft.Config(), g, 8)
    assert nat.endswith("_8.csv")  # legacy name, byte-for-byte
    bf = benchmark_filename("b", "slab_default",
                            dfft.Config(wire_dtype="bf16"), g, 8)
    assert bf.endswith("_8_w1.csv")
    assert bf != nat
    pbf = benchmark_filename("b", "pencil",
                             dfft.Config(wire_dtype="bf16"), g, 8,
                             pencil_grid=(2, 4))
    assert pbf.endswith("_2_4_w1.csv")


def test_benchmark_filename_rejects_unresolved_auto():
    with pytest.raises(KeyError):
        benchmark_filename("b", "slab_default",
                           dfft.Config(wire_dtype="auto"),
                           dfft.GlobalSize(8, 8, 8), 8)
