"""Overlap engine (ISSUE 10): ``SendMethod.RING_OVERLAP``, the fused
Pallas wire kernels, the MXU-deep four-step split, and the wisdom v4
race.

Gates, per the issue's test satellite:

* (a) RING_OVERLAP output is BIT-identical to RING across all three plan
  families x directions x uneven extents x the bf16 wire — the
  double-buffered schedule reorders the issue of the per-block ops but
  changes none of them;
* (b) ``jit(grad)`` flows through an overlapped plan;
* (c) HLO census: an overlapped program carries >= P-1 distinct
  ``collective-permute`` ops and ZERO ``all-to-all``s (counted sync +
  async-start combined — the TPU lowering rewrites each permute into a
  start/done pair, the CPU mesh lowers synchronously; the same combined
  count is the dfft-verify contract pin that stops GSPMD from
  serializing the overlap back);
* (d) fused-kernel numerics: the fused encode-pack / decode+FFT kernels
  agree with the unfused encode + FFT composition to the documented
  bounds (exact for encode/decode — same quantization — and within the
  wire error budget for the fused DFT stage);
* (e) the ``direct_max`` extension: the MXU-deep four-step split keeps
  both factors on the direct path and stays np.fft-exact at 2048/4096;
* (f) wisdom: schema v4 migration (v3 comm records re-race, others carry
  over), the comm "auto" race includes the RING_OVERLAP candidate, and
  the PR 5 demotion ladder applies to it unchanged.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import distributedfft_tpu as dfft
from distributedfft_tpu import params as pm
from distributedfft_tpu.analysis import contracts
from distributedfft_tpu.models.batched2d import Batched2DFFTPlan
from distributedfft_tpu.ops import mxu_fft, pallas_fft
from distributedfft_tpu.parallel.mesh import make_slab_mesh
from distributedfft_tpu.parallel.transpose import (
    all_to_all_transpose,
    ring_schedule,
    ring_transpose,
    wire_decode,
    wire_encode,
)
from distributedfft_tpu.testing.microbench import async_collective_counts
from distributedfft_tpu.utils import wisdom

SEQS = ["ZY_Then_X", "Z_Then_YX", "Y_Then_ZX"]
# Uneven x extent: every decomposed-axis padding path stays covered.
G = dfft.GlobalSize(20, 16, 16)


def _cfg(send, wire="native", **kw):
    return dfft.Config(send_method=send, wire_dtype=wire, use_wisdom=False,
                       **kw)


RING = pm.SendMethod.RING
OVL = pm.SendMethod.RING_OVERLAP


# ---------------------------------------------------------------------------
# (a) bit-identity vs RING: bare exchange + every family x direction x wire
# ---------------------------------------------------------------------------

def test_bare_overlap_ring_matches_ring_and_all_to_all(devices, rng):
    """The bare double-buffered ring is pure data movement: bit-identical
    to both the plain ring and the tiled all_to_all, for a pipelined fn
    too (the same per-block ops in a reordered issue schedule)."""
    mesh = make_slab_mesh(8, devices)
    x = rng.random((8, 16, 3))
    ispec, ospec = P("p", None, None), P(None, "p", None)

    def run(body):
        return np.asarray(jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=ispec, out_specs=ospec))(x))

    ref = run(lambda xl: all_to_all_transpose(xl, "p", 1, 0))
    plain = run(lambda xl: ring_transpose(xl, "p", 1, 0))
    ovl = run(lambda xl: ring_transpose(xl, "p", 1, 0, overlap=True))
    assert np.array_equal(ovl, ref) and np.array_equal(ovl, plain)

    def pipe(b):
        return b * 2.0 + 1.5

    a = run(lambda xl: ring_transpose(xl, "p", 1, 0, pipeline_fn=pipe))
    b = run(lambda xl: ring_transpose(xl, "p", 1, 0, pipeline_fn=pipe,
                                      overlap=True))
    assert np.array_equal(a, b)


@pytest.mark.parametrize("wire", ["native", "bf16"])
@pytest.mark.parametrize("seq", SEQS)
def test_slab_overlap_bit_identical_to_ring(devices, rng, seq, wire):
    ring = dfft.SlabFFTPlan(G, pm.SlabPartition(8), _cfg(RING, wire),
                            sequence=seq)
    ovl = dfft.SlabFFTPlan(G, pm.SlabPartition(8), _cfg(OVL, wire),
                           sequence=seq)
    x = rng.random(G.shape).astype(np.float32)
    a, b = np.asarray(ring.exec_r2c(x)), np.asarray(ovl.exec_r2c(x))
    assert np.array_equal(a, b)
    assert np.array_equal(np.asarray(ring.exec_c2r(a)),
                          np.asarray(ovl.exec_c2r(b)))


@pytest.mark.parametrize("wire", ["native", "bf16"])
@pytest.mark.parametrize("dims", [2, 3])
def test_pencil_overlap_bit_identical_to_ring(devices, rng, dims, wire):
    part = pm.PencilPartition(2, 4)
    ring = dfft.PencilFFTPlan(G, part, _cfg(RING, wire), dims=dims)
    ovl = dfft.PencilFFTPlan(G, part, _cfg(OVL, wire), dims=dims)
    x = rng.random(G.shape).astype(np.float32)
    a = np.asarray(ring.exec_r2c(x, dims=dims))
    b = np.asarray(ovl.exec_r2c(x, dims=dims))
    assert np.array_equal(a, b)
    assert np.array_equal(np.asarray(ring.exec_c2r(a, dims=dims)),
                          np.asarray(ovl.exec_c2r(b, dims=dims)))


@pytest.mark.parametrize("wire", ["native", "bf16"])
def test_batched2d_overlap_bit_identical_to_ring(devices, rng, wire):
    ring = Batched2DFFTPlan(8, 20, 16, pm.SlabPartition(8),
                            _cfg(RING, wire), shard="x")
    ovl = Batched2DFFTPlan(8, 20, 16, pm.SlabPartition(8),
                           _cfg(OVL, wire), shard="x")
    x = rng.random((8, 20, 16)).astype(np.float32)
    a = np.asarray(ring.exec_forward(x))
    b = np.asarray(ovl.exec_forward(x))
    assert np.array_equal(a, b)
    assert np.array_equal(np.asarray(ring.exec_inverse(a)),
                          np.asarray(ovl.exec_inverse(b)))


def test_overlap_c2c_inverse_matches_ring(devices, rng):
    """The c2c inverse (the one path RING reorders vs SYNC) still agrees
    bit-for-bit between the two ring schedules."""
    ring = dfft.SlabFFTPlan(G, pm.SlabPartition(8), _cfg(RING),
                            sequence="Z_Then_YX", transform="c2c")
    ovl = dfft.SlabFFTPlan(G, pm.SlabPartition(8), _cfg(OVL),
                           sequence="Z_Then_YX", transform="c2c")
    x = (rng.random(G.shape) + 1j * rng.random(G.shape)).astype(np.complex64)
    a, b = np.asarray(ring.exec_c2c(x)), np.asarray(ovl.exec_c2c(x))
    assert np.array_equal(a, b)
    assert np.array_equal(np.asarray(ring.exec_c2c_inv(a)),
                          np.asarray(ovl.exec_c2c_inv(b)))


# ---------------------------------------------------------------------------
# (b) jit(grad) through an overlapped plan
# ---------------------------------------------------------------------------

def test_grad_through_overlap_roundtrip(devices, rng):
    g = dfft.GlobalSize(16, 16, 16)
    plan = dfft.SlabFFTPlan(g, pm.SlabPartition(8), _cfg(OVL),
                            sequence="Z_Then_YX")
    fwd, inv = plan.forward_fn(), plan.inverse_fn()
    w = rng.random(g.shape)

    def loss(x):
        r = inv(fwd(x)) / g.n_total
        return jnp.sum(jnp.asarray(w) * r)

    got = np.asarray(jax.jit(jax.grad(loss))(rng.random(g.shape)))
    np.testing.assert_allclose(got, w, atol=5e-2)


# ---------------------------------------------------------------------------
# (c) HLO census: the overlap cannot be serialized back into a collective
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq", SEQS)
def test_hlo_overlap_p_minus_1_permutes_no_all_to_all(devices, seq):
    plan = dfft.SlabFFTPlan(G, pm.SlabPartition(8), _cfg(OVL),
                            sequence=seq)
    compiled = plan._build_r2c().lower(
        jax.ShapeDtypeStruct(plan.input_padded_shape, np.float32)).compile()
    c = async_collective_counts(compiled)
    # Sync + async-start forms summed: the TPU-style lowering rewrites
    # each permute into a collective-permute-start/done pair, the CPU
    # mesh lowers synchronously — the combined count is the portable pin.
    assert c["collective_permute"] + c["collective_permute_start"] >= 7
    assert c["all_to_all"] + c["all_to_all_start"] == 0


def test_hlo_overlap_bf16_keeps_permute_census(devices):
    """Compression must not collapse the split exchange (the wire gate's
    contract, extended to the overlap schedule)."""
    plan = dfft.SlabFFTPlan(G, pm.SlabPartition(8), _cfg(OVL, "bf16"),
                            sequence="Z_Then_YX")
    compiled = plan._build_r2c().lower(
        jax.ShapeDtypeStruct(plan.input_padded_shape, np.float32)).compile()
    c = async_collective_counts(compiled)
    assert c["collective_permute"] + c["collective_permute_start"] >= 7
    assert c["all_to_all"] + c["all_to_all_start"] == 0


@pytest.mark.parametrize("rendering,fused", [("ring_overlap", False),
                                             ("ring_overlap", True)])
def test_contract_registered_for_overlap(devices, rendering, fused):
    """dfft-verify's registry resolves the ring_overlap rendering (fused
    wire included) through the same census + payload contract as ring —
    the (P-1)/P discount included — and the live plan verifies clean."""
    cfg = _cfg(OVL, "bf16", fused_wire=fused)
    for plan, dims in (
            (dfft.SlabFFTPlan(G, pm.SlabPartition(8), cfg,
                              sequence="Z_Then_YX"), 3),
            (dfft.PencilFFTPlan(G, pm.PencilPartition(2, 4), cfg), 3),
            (Batched2DFFTPlan(8, 20, 16, pm.SlabPartition(8), cfg,
                              shard="x"), 2)):
        contract = contracts.contract_for(plan, "forward", dims)
        assert all(d.rendering == "ring_overlap" for d in contract.exchanges)
        assert contracts.verify_plan(plan, "forward", dims,
                                     contract=contract) == []


def test_ring_schedule_descriptor():
    sch = ring_schedule((256, 256, 129), np.complex64, "bf16", 8,
                        overlap=True)
    total = 256 * 256 * 129 * 4  # bf16 wire: 4 B per complex element
    assert sch["steps"] == 7 and sch["buffers"] == 2
    assert sch["block_wire_bytes"] == total // 64
    assert sch["bytes_in_flight"] == 2 * sch["block_wire_bytes"]
    assert sch["total_wire_bytes"] == total * 7 // 8  # (P-1)/P discount
    plain = ring_schedule((256, 256, 129), np.complex64, "bf16", 8)
    assert plain["buffers"] == 1
    assert plain["bytes_in_flight"] == plain["block_wire_bytes"]


# ---------------------------------------------------------------------------
# (d) fused wire kernels: numerics vs the unfused path
# ---------------------------------------------------------------------------

def test_fused_encode_decode_match_unfused_exactly(rng):
    """Encode-pack and decode-unpack are the same quantization as the
    plain wire layer: outside shard_map the kernels run (interpret mode
    on CPU) and must agree with wire_encode/wire_decode bit-for-bit."""
    x = (rng.random((4, 24, 16)) + 1j * rng.random((4, 24, 16))
         ).astype(np.complex64)
    xj = jnp.asarray(x)
    enc_ref = wire_encode(xj, "bf16")
    enc = pallas_fft.wire_encode_fused(xj)
    assert enc.dtype == jnp.bfloat16 and enc.shape == (2,) + x.shape
    assert np.array_equal(np.asarray(enc, np.float32),
                          np.asarray(enc_ref, np.float32))
    dec_ref = wire_decode(enc_ref, np.complex64, "bf16")
    dec = pallas_fft.wire_decode_fused(enc, np.complex64)
    assert np.array_equal(np.asarray(dec), np.asarray(dec_ref))


@pytest.mark.parametrize("inverse", [False, True])
def test_fused_decode_fft_within_documented_bound(rng, inverse):
    """decode+FFT fused agrees with the unfused decode -> matmul-DFT
    composition to the wire error budget (2e-2, README 'wire dtype'); in
    practice the fused stage differs only by the kernel's HIGH-emulation
    rounding, orders of magnitude below the bf16 wire quantization."""
    x = (rng.random((3, 32, 8)) + 1j * rng.random((3, 32, 8))
         ).astype(np.complex64)
    y = wire_encode(jnp.asarray(x), "bf16")
    for axis in (0, 1, 2):
        fused = np.asarray(pallas_fft.decode_fft_fused(
            y, np.complex64, axis, inverse=inverse))
        dec = wire_decode(y, np.complex64, "bf16")
        unfused = np.asarray((mxu_fft.ifft if inverse else mxu_fft.fft)(
            dec, axis=axis))
        denom = np.max(np.abs(unfused)) or 1.0
        assert np.max(np.abs(fused - unfused)) / denom <= 2e-2
        # And against the true transform of the decoded payload: the
        # fused stage must be a real DFT, not an approximation of one.
        ref = (np.fft.ifft(np.asarray(dec), axis=axis) * x.shape[axis]
               if inverse else np.fft.fft(np.asarray(dec), axis=axis))
        assert np.max(np.abs(fused - ref)) / (np.max(np.abs(ref)) or 1.0) \
            <= 1e-4


def test_fused_wire_plan_matches_unfused_within_budget(devices, rng):
    """End-to-end: a fused-wire overlapped plan agrees with the unfused
    overlapped plan within the wire error budget on every family (on the
    CPU mesh the kernels take their jnp fallbacks, so this also pins the
    fallback composition's correctness)."""
    fused = _cfg(OVL, "bf16", fused_wire=True)
    plain = _cfg(OVL, "bf16")
    x3 = rng.random(G.shape).astype(np.float32)
    for mk in (
        lambda c: dfft.SlabFFTPlan(G, pm.SlabPartition(8), c,
                                   sequence="Z_Then_YX"),
        lambda c: dfft.PencilFFTPlan(G, pm.PencilPartition(2, 4), c),
    ):
        a = np.asarray(mk(fused).exec_r2c(x3))
        b = np.asarray(mk(plain).exec_r2c(x3))
        assert np.max(np.abs(a - b)) / (np.max(np.abs(b)) or 1.0) <= 2e-2


def test_fused_decode_restores_double_precision_dtype(rng):
    """A double_prec plan's fused arrival must restore complex128 via
    the unfused composition (the f64 guard keys on the TARGET dtype —
    the bf16 planes themselves are never 'double')."""
    x = (rng.random((2, 8, 8)) + 1j * rng.random((2, 8, 8))
         ).astype(np.complex128)
    y = wire_encode(jnp.asarray(x), "bf16")
    out = pallas_fft.decode_fft_fused(y, np.complex128, 1)
    assert out.dtype == jnp.complex128
    assert pallas_fft.wire_decode_fused(y, np.complex128).dtype \
        == jnp.complex128


def test_fused_ring_hooks_shared_predicate():
    """The one shared hook builder: active exactly when fused_wire_for
    says so (per-transpose snd honored — a pencil snd2-only ring gets
    its hooks even though the first transpose is SYNC)."""
    cfg = dfft.Config(send_method=pm.SendMethod.SYNC, send_method2=OVL,
                      wire_dtype="bf16", fused_wire=True, use_wisdom=False)
    assert pallas_fft.fused_ring_hooks(cfg) == (None, None)  # snd1: SYNC
    enc, arr = pallas_fft.fused_ring_hooks(cfg, OVL)
    assert enc is pallas_fft.wire_encode_fused and arr is not None
    assert cfg.fused_wire_for(OVL) and not cfg.fused_wire_for(
        pm.SendMethod.SYNC)


def test_fused_wire_inert_off_ring_and_on_native():
    """fused_wire is inert off a ring rendering or off the bf16 wire —
    the Config predicate the assemblers share."""
    assert _cfg(OVL, "bf16", fused_wire=True).fused_wire_active()
    assert _cfg(RING, "bf16", fused_wire=True).fused_wire_active()
    assert not _cfg(OVL, "native", fused_wire=True).fused_wire_active()
    assert not dfft.Config(fused_wire=True,
                           wire_dtype="bf16").fused_wire_active()
    with pytest.raises(ValueError, match="fused_wire"):
        dfft.Config(fused_wire="yes")


# ---------------------------------------------------------------------------
# (e) direct_max extension: the MXU-deep split at 2048/4096
# ---------------------------------------------------------------------------

def test_wide_split_dispatch():
    assert mxu_fft._split_for(2048, 512) == (4, 512)
    assert mxu_fft._split_for(4096, 512) == (8, 512)
    assert mxu_fft._split_for(2048, 1024) == (2, 1024)
    # No direct-capable co-factor (n > direct_max^2 territory / awkward
    # divisors): fall back to the balanced recursion.
    assert mxu_fft._split_for(2 * 521, 512) == mxu_fft._split(2 * 521)
    # Primes keep the (1, n) direct-fallback contract.
    assert mxu_fft._split_for(521, 512) == (1, 521)


@pytest.mark.parametrize("n", [2048, 4096])
def test_direct_max_extension_exact_vs_numpy(rng, n):
    """2048/4096-point axes through the matmul backend stay np.fft-exact
    (f32 tolerance) under the MXU-deep factorization — both factors on
    the direct-DFT matmul path."""
    x = rng.random((2, n)).astype(np.float32)
    got = np.asarray(mxu_fft.rfft(jnp.asarray(x), axis=-1))
    ref = np.fft.rfft(x, axis=-1)
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 2e-5
    c = (rng.random((2, n)) + 1j * rng.random((2, n))).astype(np.complex64)
    got = np.asarray(mxu_fft.fft(jnp.asarray(c), axis=-1))
    ref = np.fft.fft(c, axis=-1)
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 2e-5
    # Roundtrip closes.
    back = np.asarray(mxu_fft.ifft(jnp.asarray(got), axis=-1)) / n
    assert np.max(np.abs(back - c)) / np.max(np.abs(c)) < 2e-5


def test_irfft_extension_exact_vs_numpy(rng):
    n = 2048
    c = np.fft.rfft(rng.random((2, n))).astype(np.complex64)
    got = np.asarray(mxu_fft.irfft(jnp.asarray(c), n=n, axis=-1)) / n
    ref = np.fft.irfft(c, n=n, axis=-1)
    assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 2e-4


# ---------------------------------------------------------------------------
# (f) wisdom v4: migration, the RING_OVERLAP candidate, demotion stamps
# ---------------------------------------------------------------------------

def test_v3_store_migrates_comm_rereaces(tmp_path):
    """A v3 store's comm record predates the RING_OVERLAP race axis and
    reads as a miss; local_fft and wire records carry over verbatim."""
    key = wisdom.plan_key("slab", (16, 16, 16), False, pm.SlabPartition(8),
                          pm.FFTNorm.NONE)
    path = tmp_path / "w3.json"
    path.write_text(json.dumps({"version": 3, "entries": {key: {
        "local_fft": {"fft_backend": "xla", "mxu_precision": None,
                      "mxu_direct_max": None},
        "wire": {"wire_dtype": "native"},
        "comm": {"comm_method": "All2All", "comm_method2": None, "opt": 1,
                 "send_method": "Ring", "streams_chunks": None,
                 "wire_dtype": "native", "wire_raced": True},
    }}}))
    store = wisdom.WisdomStore(str(path))
    data = store.load()
    assert data["version"] == wisdom.WISDOM_VERSION == 5
    assert store.lookup(key, "comm") is None
    assert store.lookup(key, "local_fft")["fft_backend"] == "xla"
    assert store.lookup(key, "wire")["wire_dtype"] == "native"


def test_comm_race_includes_ring_overlap_candidate(devices):
    """comm_method='auto' races RING_OVERLAP as one more candidate, and a
    recorded RingOverlap winner folds back into a Config."""
    from distributedfft_tpu.testing.autotune import autotune_comm
    ranked = autotune_comm("slab", dfft.GlobalSize(16, 16, 16),
                           pm.SlabPartition(8),
                           dfft.Config(use_wisdom=False),
                           iterations=1, warmup=0, race_opt=False,
                           race_send=True, streams_chunks=())
    labels = [c.label for c in ranked]
    assert any("/ring-ovl" in lb for lb in labels), labels
    assert any("/ring" in lb and "ovl" not in lb for lb in labels)
    ovl_cand = next(c for c in ranked if c.send is OVL)
    assert ovl_cand.ok, ovl_cand.error
    rec = wisdom.comm_record(ovl_cand, dfft.Config())
    assert rec["send_method"] == "RingOverlap"
    folded = wisdom._fold_comm_rec(dfft.Config(), rec)
    assert folded.send_method is OVL


def test_overlap_demotes_one_rung_like_ring():
    """The PR 5 fallback ladder applies unchanged: RING_OVERLAP demotes
    exactly one rung to the realigned SYNC exchange."""
    from distributedfft_tpu.resilience import fallback
    cfg, rung = fallback.next_rung(_cfg(OVL, "bf16"))
    assert rung == "send"
    assert cfg.send_method is pm.SendMethod.SYNC and cfg.opt == 1
    assert cfg.wire_dtype == "bf16"  # one axis per rung


def test_send_method_parse_and_encoding():
    assert pm.SendMethod.parse("RingOverlap") is OVL
    assert pm.SendMethod.parse("ring_overlap") is OVL
    assert pm.SendMethod.parse("overlap") is OVL
    assert OVL.is_ring and RING.is_ring
    assert not pm.SendMethod.SYNC.is_ring
    # The multihost broadcast encoding enumerates every SendMethod.
    assert OVL in wisdom._send_encoding()
