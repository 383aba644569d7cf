"""Parameter / configuration model for distributed FFT plans.

TPU-native re-design of the reference's parameter layer
(``include/params.hpp``): global sizes with the R2C halved axis
(``params.hpp:24-37``), slab / pencil partitions (``params.hpp:39-56``),
per-axis size/offset tables with remainder spread
(``src/slab/default/mpicufft_slab.cpp:112-128``), and the
communication-/send-method enums (``params.hpp:83-93``).

On TPU the comm/send matrix collapses into *how the XLA program is built*:

* ``CommMethod.ALL2ALL``  -> explicit ``shard_map`` + ``lax.all_to_all``
  (the device-collective analog of ``MPI_Alltoallv/w``).
* ``CommMethod.PEER2PEER`` -> GSPMD resharding: the pipeline is written as
  global-view ops with ``with_sharding_constraint`` between stages and XLA
  chooses the collective schedule (its latency-hiding scheduler plays the
  role of the reference's hand-rolled Isend/Irecv overlap engine).
* ``SendMethod.STREAMS`` -> the chunked/software-pipelined transpose: the
  local block is split into ``Config.streams_chunks`` pieces along an axis
  untouched by the exchange, and each piece runs its own
  FFT -> collective -> FFT chain — the intended role of the reference's
  Streams engine (per-peer packs on CUDA streams + callback thread +
  ``MPI_Isend``, ``src/slab/default/mpicufft_slab.cpp:343-448``).
  MEASURED RESULT (``eval/benchmarks/cpumesh8/OVERLAP.md``): under
  PEER2PEER, GSPMD re-fuses the piece reshards into ONE collective
  (HLO identical to SYNC), and even the explicit ALL2ALL rendering's K
  chunked collectives showed ZERO async collective ops — its measured
  1.2-1.4x win is a working-set effect, not overlap.
* ``SendMethod.RING`` -> the ring-pipelined transpose
  (``parallel/transpose.ring_transpose``): ``P-1`` distinct
  ``lax.ppermute`` steps XLA cannot re-fuse, with per-peer-block FFT
  compute pipelined between them — the overlap-capable rendering the
  STREAMS result motivated.
* ``SendMethod.RING_OVERLAP`` -> the same ring with the loop restructured
  as a DOUBLE-BUFFERED software pipeline: step t+1's permute is issued
  BEFORE block t's per-block FFT is traced (two revolving buffers), so a
  scheduler that respects program order can keep one transfer in flight
  under every block's compute. Same block math in a reordered schedule —
  bit-identical output to RING, pinned by tests/test_overlap.py.
  ``SYNC`` is the monolithic single-collective pipeline; ``MPI_TYPE``
  (zero-copy strided datatypes) has no analog under XLA -- packing is a
  fused transpose -- and is accepted as a benchmarking label alias of SYNC.

Everything here is pure Python (no devices required), mirroring the
reference's L1b layer which is header-only.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import List, Optional, Sequence, Tuple

from .utils import native_planner


# Valid Config.mxu_precision names (lax.Precision's string forms); a plain
# set so params.py stays importable without jax.
_MXU_PRECISIONS = frozenset({"default", "high", "highest"})

# Valid Config.guards modes (resilience/guards.py): "off" = the exact
# pre-guard programs (pinned byte-identical by tests/test_resilience.py),
# "check" = compute the in-graph energy/drift guards and REPORT violations
# (obs metrics + notice; a compressed wire additionally demotes itself to
# native for subsequent calls), "enforce" = raise a structured
# ``resilience.GuardViolation`` carrying the plan fingerprint.
GUARD_MODES = ("off", "check", "enforce")


def parse_guards(s: str) -> str:
    """Canonical guard-mode name (case-insensitive)."""
    key = str(s).strip().lower()
    if key in GUARD_MODES:
        return key
    raise ValueError(f"unknown guards mode: {s!r} (choose from {GUARD_MODES})")

# Marker for measurement-resolved Config fields: ``fft_backend=AUTO`` /
# ``comm_method=AUTO`` ask the plan constructors to consult the persistent
# wisdom store (``utils/wisdom.py``) and race-and-record on a miss. Plans
# never execute with an unresolved AUTO; resolution happens once, at
# construction.
AUTO = "auto"

# Valid Config.wire_dtype names: how the global-exchange payload is encoded
# on the wire. "native" is the bit-identical pass-through; "bf16" the
# planar (real, imag) bf16 pair (parallel/transpose.wire_encode) halving a
# complex64 exchange's bytes; "auto" races compressed vs native under
# Config.wire_error_budget at plan construction (wisdom-resolved, like the
# comm "auto").
_WIRE_DTYPES = ("native", "bf16", AUTO)

# Default Config.wire_error_budget: the max roundtrip rel error (vs the
# native path, relative to the output's max magnitude) the "auto" racer
# accepts from a compressed wire. bf16 carries an 8-bit mantissa
# (eps ~ 3.9e-3); a forward+inverse pipeline crosses the wire twice, so
# the measured roundtrip error sits at ~2-4e-3 per crossing — 2e-2 admits
# bf16 for ordinary f32 workloads while rejecting it wherever accumulation
# pushes past the percent level.
DEFAULT_WIRE_ERROR_BUDGET = 2e-2


def parse_wire_dtype(s: str) -> str:
    """Canonical wire-dtype name (case-insensitive; 'auto' = measured)."""
    key = str(s).strip().lower()
    if key in _WIRE_DTYPES:
        return key
    raise ValueError(
        f"unknown wire dtype: {s!r} (choose from {_WIRE_DTYPES})")


# Overlap depths the comm autotuner races for the revolving-buffer ring
# (and the issue-ahead window of the pipelined all_to_all): the
# schedule-verified candidate set — ``analysis/schedverify.py`` proves
# every member hazard-free per mesh size before a plan may trace it.
OVERLAP_DEPTHS = (2, 4, 8)


def parse_overlap_depth(s: "str | int") -> "str | int":
    """Canonical ``Config.overlap_depth`` value: ``"auto"`` (wisdom /
    race-resolved) or an int >= 2 (the revolving receive-buffer count;
    capped at the ring's step count at trace time)."""
    if isinstance(s, str) and s.strip().lower() == AUTO:
        return AUTO
    try:
        v = int(s)
    except (TypeError, ValueError):
        raise ValueError(
            f"overlap depth must be an int >= 2 or {AUTO!r}, got {s!r}")
    if v < 2:
        raise ValueError(f"overlap depth must be >= 2, got {v}")
    return v


def parse_comm_method(s: "str | CommMethod") -> "str | CommMethod":
    """``CommMethod.parse`` that additionally accepts ``"auto"`` (the
    wisdom-resolved marker, owning the whole comm x send x opt x chunk
    variant choice at plan construction)."""
    if isinstance(s, str) and s.strip().lower() == AUTO:
        return AUTO
    return CommMethod.parse(s)


class CommMethod(enum.Enum):
    """Global-redistribution strategy (reference ``params.hpp:83-85``)."""

    PEER2PEER = "Peer2Peer"  # GSPMD auto-resharding path
    ALL2ALL = "All2All"      # explicit shard_map + lax.all_to_all path

    @classmethod
    def parse(cls, s: "str | CommMethod") -> "CommMethod":
        if isinstance(s, CommMethod):
            return s
        key = str(s).strip().lower().replace("_", "").replace("-", "")
        if key in ("peer2peer", "p2p", "peer"):
            return cls.PEER2PEER
        if key in ("all2all", "a2a", "alltoall"):
            return cls.ALL2ALL
        raise ValueError(f"unknown comm method: {s!r}")


class SendMethod(enum.Enum):
    """Packing strategy (reference ``params.hpp:87-89``). ``STREAMS``
    selects the chunked/software-pipelined transpose (see module
    docstring); ``SYNC``/``MPI_TYPE`` are the monolithic pipeline.

    ``RING`` is an extension beyond the reference's 2x3 matrix: the
    transpose decomposed into ``P-1`` ``lax.ppermute`` ring steps
    (``parallel/transpose.ring_transpose``), one peer block per step,
    with the per-block post-transpose FFT stage pipelined between steps
    where the axis roles allow. Unlike STREAMS' chunked collectives —
    which GSPMD re-fuses under PEER2PEER and which stay K instances of
    one op under ALL2ALL — each ring step is a distinct
    ``collective-permute`` (async start/done pair on TPU) that XLA cannot
    re-fuse, so this is the rendering on which the overlap detector
    (HLO async-collective counts) actually fires. A ring is only
    expressible as an explicit ``shard_map`` program, so RING owns the
    exchange rendering regardless of ``comm_method`` (GSPMD delegation
    has no ppermute analog).

    ``RING_OVERLAP`` is RING's double-buffered schedule (the overlap
    engine of ISSUE 10): the per-block loop is restructured so step
    t+1's ``ppermute`` is issued before block t's per-block FFT, with
    two revolving buffers carrying the in-flight and the computing
    block. The per-block math is IDENTICAL to RING (bit-identical
    output, pinned), only the issue order changes — which is exactly
    what lets an asynchronous scheduler (TPU start/done pairs) hide
    each transfer under the previous block's compute instead of
    serializing permute -> FFT -> permute. Owns the rendering
    regardless of ``comm_method``, like RING."""

    SYNC = "Sync"
    STREAMS = "Streams"
    MPI_TYPE = "MPI_Type"
    RING = "Ring"
    RING_OVERLAP = "RingOverlap"

    @classmethod
    def parse(cls, s: "str | SendMethod") -> "SendMethod":
        if isinstance(s, SendMethod):
            return s
        key = str(s).strip().lower().replace("_", "").replace("-", "")
        if key == "sync":
            return cls.SYNC
        if key == "streams":
            return cls.STREAMS
        if key == "ring":
            return cls.RING
        if key in ("ringoverlap", "overlap", "ringovl"):
            return cls.RING_OVERLAP
        if key in ("mpitype", "mpit", "type"):
            return cls.MPI_TYPE
        raise ValueError(f"unknown send method: {s!r}")

    @property
    def is_ring(self) -> bool:
        """Both ppermute-ring renderings (RING and its double-buffered
        RING_OVERLAP schedule) — the predicate the plan assemblers and
        the contract/ladder layers share, so a new ring variant cannot
        be wired into one of them only."""
        return self in (SendMethod.RING, SendMethod.RING_OVERLAP)


class FFTNorm(enum.Enum):
    """Normalization policy.

    ``NONE`` reproduces cuFFT semantics (both directions unnormalized;
    the reference's round-trip test compares against the input scaled by
    ``Nx*Ny*Nz``, ``tests/src/slab/random_dist_default.cu:529-623``).
    ``BACKWARD`` is the numpy default (inverse carries 1/N).
    """

    NONE = "none"
    BACKWARD = "backward"
    ORTHO = "ortho"


@dataclasses.dataclass(frozen=True)
class GlobalSize:
    """Global 3D extent; ``nz_out`` is the R2C halved z extent
    (reference ``params.hpp:30``: ``Nz_out = Nz/2 + 1``)."""

    nx: int
    ny: int
    nz: int

    def __post_init__(self) -> None:
        for name in ("nx", "ny", "nz"):
            v = getattr(self, name)
            if not isinstance(v, int) or v <= 0:
                raise ValueError(f"{name} must be a positive int, got {v!r}")

    @property
    def nz_out(self) -> int:
        return self.nz // 2 + 1

    @property
    def ny_out(self) -> int:
        """Halved-y extent, used by the Y_Then_ZX slab sequence
        (reference ``src/slab/y_then_zx/mpicufft_slab_y_then_zx.cpp:95-103``)."""
        return self.ny // 2 + 1

    @property
    def shape(self) -> Tuple[int, int, int]:
        return (self.nx, self.ny, self.nz)

    @property
    def n_total(self) -> int:
        return self.nx * self.ny * self.nz


def block_sizes(n: int, p: int) -> List[int]:
    """Block distribution of ``n`` items over ``p`` parts with the remainder
    spread over the first ranks, exactly as the reference computes slab
    extents (``src/slab/default/mpicufft_slab.cpp:112-128``)."""
    return native_planner.block_sizes(n, p)


def block_starts(sizes: Sequence[int]) -> List[int]:
    """Exclusive prefix sum -> per-part start offsets
    (reference ``Partition_Dimensions::computeOffsets``, ``params.hpp:58-81``)."""
    return native_planner.block_starts(list(sizes))


def even_shard_sizes(n: int, n_pad: int, p: int) -> List[int]:
    """Logical per-rank extents under even padded sharding: each rank holds a
    ``n_pad/p`` block of the padded axis; ranks past the logical extent hold
    only pad and report 0. This is what the framework's NamedShardings
    actually materialize — distinct from the reference's remainder-spread
    ``block_sizes``."""
    return native_planner.even_shard_sizes(n, n_pad, p)


def padded_extent(n: int, p: int) -> int:
    """Smallest multiple of ``p`` >= ``n``.

    XLA collectives want equal splits; where the reference uses per-peer byte
    counts for uneven extents (e.g. the odd ``Nz/2+1`` axis), the TPU design
    pads the axis to ``p * ceil(n/p)`` and slices the result (SURVEY §7)."""
    return native_planner.padded_extent(n, p)


@dataclasses.dataclass(frozen=True)
class PartitionDims:
    """Per-axis local extents and offsets for one stage of a decomposition —
    the analog of the reference's ``Partition_Dimensions`` (``params.hpp:58-81``),
    holding sizes/starts for every rank rather than vectors per axis."""

    size_x: Tuple[int, ...]
    size_y: Tuple[int, ...]
    size_z: Tuple[int, ...]

    @property
    def start_x(self) -> List[int]:
        return block_starts(self.size_x)

    @property
    def start_y(self) -> List[int]:
        return block_starts(self.size_y)

    @property
    def start_z(self) -> List[int]:
        return block_starts(self.size_z)


class Partition:
    """Base partition type (reference ``params.hpp:39-43``)."""

    @property
    def num_ranks(self) -> int:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class SlabPartition(Partition):
    """1D decomposition over x (or the sequence-dependent first axis);
    reference ``Slab_Partition`` (``params.hpp:44-49``)."""

    p: int

    def __post_init__(self):
        if self.p <= 0:
            raise ValueError(f"slab partition count must be positive, got {self.p}")

    @property
    def num_ranks(self) -> int:
        return self.p


@dataclasses.dataclass(frozen=True)
class PencilPartition(Partition):
    """2D decomposition over (x, y) into a P1 x P2 grid; reference
    ``Pencil_Partition`` (``params.hpp:51-56``) with
    ``pidx = pidx_i * P2 + pidx_j`` (``src/pencil/mpicufft_pencil.cpp:83-85``)."""

    p1: int
    p2: int

    def __post_init__(self):
        if self.p1 <= 0 or self.p2 <= 0:
            raise ValueError(f"pencil grid must be positive, got {self.p1}x{self.p2}")

    @property
    def num_ranks(self) -> int:
        return self.p1 * self.p2


class SlabSequence(enum.Enum):
    """Which per-axis FFT sequence a slab plan runs (reference's three slab
    families, SURVEY §2.1)."""

    ZY_THEN_X = "ZY_Then_X"   # 2D FFT (y,z) -> transpose -> 1D FFT x  (default)
    Z_THEN_YX = "Z_Then_YX"   # 1D FFT z -> transpose -> 2D FFT (y,x)
    Y_THEN_ZX = "Y_Then_ZX"   # 1D R2C y -> transpose -> 2D FFT (z,x)

    @classmethod
    def parse(cls, s: "str | SlabSequence") -> "SlabSequence":
        if isinstance(s, SlabSequence):
            return s
        key = str(s).strip().lower().replace("-", "_")
        table = {
            "zy_then_x": cls.ZY_THEN_X, "default": cls.ZY_THEN_X, "2d_1d": cls.ZY_THEN_X,
            "z_then_yx": cls.Z_THEN_YX, "1d_2d": cls.Z_THEN_YX,
            "y_then_zx": cls.Y_THEN_ZX, "1d_2d_y": cls.Y_THEN_ZX,
        }
        if key in table:
            return table[key]
        raise ValueError(f"unknown slab sequence: {s!r}")


@dataclasses.dataclass(frozen=True)
class Config:
    """Plan-wide configuration — the analog of the reference's
    ``Configurations`` struct (``params.hpp:85-93``).

    ``comm_method2`` / ``send_method2`` apply to the pencil second transpose
    (reference CLI ``-comm2/-snd2``, ``tests/src/pencil/main.cpp:26-63``).
    ``opt`` selects the data-layout variant: 1 = the coordinate-transform
    ("realigned") layout where the pre-transpose FFT writes transposed
    coordinates (reference Opt1 classes); under XLA this is a hint that the
    transpose is fused into the producer, which the compiler does anyway, so
    opt only changes benchmark labeling and the internal einsum order.
    ``cuda_aware`` is accepted for CLI compatibility; device-resident
    collectives are always on for TPU.
    ``fft_backend`` selects the local-transform implementation. Left
    unset (None), plan construction picks it from what the plan can
    observe (``utils/wisdom.platform_default``): on a TPU, for float32
    and 5-smooth extents, ``"matmul"`` at ``mxu_precision="highest"``
    (float32 products, float32 accumulation) with the extent's
    ``mxu_direct_max`` from ``ops/mxu_fft.DIRECT_MAX_BY_EXTENT``;
    everywhere else (CPU, GPU, ``double_prec``, a non-smooth axis, an
    extent past the table) ``"xla"``. A built plan's ``config.fft_backend`` is always concrete.
    Named explicitly it is one of ``"xla"``
    (XLA's FFT expansion), ``"matmul"`` (MXU four-step DFT matmuls,
    ``ops/mxu_fft.py``), ``"matmul-r2"`` (same with radix-2 DIF splitting
    down to MXU-depth matmuls), ``"pallas"`` (Pallas kernels fusing the
    four-step twiddle into the DFT matmul, ``ops/pallas_fft.py``), or
    ``"bluestein"`` (``ops/bluestein.py``: chirp-z for arbitrary —
    prime, non-smooth — axis lengths at O(n log n); 5-smooth axes
    delegate to the XLA expansion bit-identically, so it costs nothing
    where the fast path already applies) — the TPU analog of the
    reference's cuFFT-plan choice at L0 (``include/cufft.hpp:23-61``).
    ``fft_backend="auto"`` defers the choice to measurement: plan
    construction consults the persistent wisdom store
    (``utils/wisdom.py``; path from ``wisdom_path`` -> ``$DFFT_WISDOM``),
    races the backends on a miss (the bluestein candidate joins exactly
    when the shape has a non-smooth axis — it would duplicate "xla"
    otherwise) and records the winner. ``comm_method=
    "auto"`` does the same for the whole comm x send x opt x streams-chunks
    variant, the RING and RING_OVERLAP ring renderings included (ignoring the explicit
    ``send_method``/``opt`` fields — the race owns them). ``use_wisdom=False`` (CLI ``--no-wisdom``) never
    touches disk; "auto" then races per process.

    ``streams_chunks`` sets how many pieces the ``SendMethod.STREAMS``
    pipelined transpose splits the local block into (None -> 4). Ignored
    unless the plan's (resolved) send method is STREAMS; clamped to the
    chunk axis extent at trace time. More chunks = more overlap windows
    but smaller (less bandwidth-efficient) exchanges.

    ``overlap_depth`` (``"auto"`` default) sets the revolving
    receive-buffer depth of the overlap schedules: RING_OVERLAP's ring
    issues up to ``depth - 1`` permutes ahead of the per-block FFTs
    (``"auto"`` -> 2, the shipped double-buffered pipeline, traced
    op-for-op as before), and the pipelined all_to_all uses the same
    value as its issue-ahead window. Capped at the exchange's step
    count (depth 8 on 8 ranks runs 7 buffers — and the descriptors say
    so). ``autotune_comm`` races depths ``OVERLAP_DEPTHS`` as wisdom
    candidates. ``overlap_subblocks`` (None -> 1) splits each
    travelling block into S sub-blocks: on a ring each peer block
    becomes S ppermute micro-steps (the first sub-block's FFT starts
    before the peer's full payload has arrived — the Streams-chunks
    idea inside the ring); under ALL2ALL + SYNC/MPI_TYPE a value > 1
    software-pipelines the monolithic collective into S chunked
    ``all_to_all``s (the ``a2a_pipe`` rendering), so opt0/opt1 get
    overlap without switching to the ring. Every depth/sub-block
    variant is bit-identical to its serial rendering;
    ``analysis/schedverify.py`` proves each shipped schedule
    hazard-free before a plan may trace it.

    ``wire_dtype`` selects the WIRE encoding of every global exchange
    (``parallel/transpose`` wire layer; CLI ``-wire``, env ``$DFFT_WIRE``):
    ``"native"`` keeps today's bit-identical payload; ``"bf16"`` packs the
    complex payload as a planar (real, imag) bf16 pair immediately before
    the collective and decodes immediately after — HALF the wire bytes of
    a complex64 exchange (quarter for complex128), an OPT-IN LOSSY choice
    (~2e-3 max rel error per crossing, measured/documented in README);
    ``"auto"`` races compressed vs native on the actual shape at plan
    construction, accepts bf16 only when its measured roundtrip error
    stays within ``wire_error_budget`` (None -> 2e-2), and records the
    winner in the wisdom store. The encoding composes with every exchange
    rendering — default/opt1 ``lax.all_to_all``, the GSPMD boundary, and
    the RING/RING_OVERLAP ppermute rings, which encode per travelling
    block so compression and overlap stack. Applies to both pencil
    transposes.

    ``fused_wire`` (opt-in, default False) renders the ring's per-block
    wire boundary with the fused Pallas kernels (``ops/pallas_fft``
    fused-wire section): the bf16 planar split + pack runs as ONE kernel
    pass on the send side, and the decode + the first pipelined per-block
    DFT stage fuse into one kernel on the receive side, so the travelling
    payload never round-trips HBM between the wire cast and the
    neighboring FFT matmul (``pallas_call`` is a custom-call boundary XLA
    cannot fuse across — the one case where the hand kernel wins; see the
    ``ops/pallas_fft.py`` module docstring). Only active on a ring
    rendering (RING / RING_OVERLAP) with ``wire_dtype="bf16"``; inert
    otherwise. Off-TPU the kernels fall back to the numerically
    equivalent jnp composition, and the fused decode+FFT stage computes
    its DFT as a matmul regardless of ``fft_backend`` (that IS the
    fusion) — numerics vs the unfused path are bounded by the wire's
    documented bf16 error (tests/test_overlap.py pins the bound).

    ``guards`` selects the in-graph numerical guards of the resilience
    layer (``resilience/guards.py``; CLI ``--guards``, env
    ``$DFFT_GUARDS``): ``None`` defers to the environment (unset = "off",
    the exact pre-guard programs); ``"check"`` adds a Parseval/energy-
    conservation residual (and, on a compressed wire, a drift probe
    against ``wire_error_budget``) to every jitted pipeline — one extra
    reduction, violations counted/noticed, a drifting wire demoted to
    native for subsequent calls; ``"enforce"`` raises a structured
    ``resilience.GuardViolation`` instead.

    ``fft3d_chunk`` bounds the SINGLE-DEVICE 3D path's peak memory: the
    z+y stages run as ``lax.map`` over that many chunks of x-planes, and
    the x stage over as many blocks of z-planes of the half spectrum,
    written back in place (``models/base._chunked_3d``), so the
    temporaries scale with a chunk instead of the whole cube (the fused
    1024^3 f32 R2C needs 17.3 GB on a 16 GB v5e; in 128 chunks 12.9).
    Must divide the x extent; 1 is the fused program. None (default):
    the plan decides once, when it is built, from the device's memory
    (``models/base.fft3d_chunk_rule``): fused where that fits or no limit
    is known (the CPU), else a count. R2C/C2R only; ignored by
    distributed plans (shard the cube instead).

    ``mxu_precision`` / ``mxu_karatsuba`` / ``mxu_fourstep_einsum`` /
    ``mxu_direct_max`` are the matmul-family backend knobs as PLAN state
    (read at trace time through a context-scoped ``mxu_fft.MXUSettings``,
    so two plans with different settings coexist in one process). Each
    knob is tri-state: None defers PER KNOB to the deprecated
    ``mxu_fft.set_*`` process defaults; an explicit value wins.
    ``mxu_precision`` is the single-precision DFT-matmul MXU precision:
    "default" (raw bf16), "high" (three bf16 passes; the process default
    of an explicitly named matmul backend), or "highest" (float32
    products; what the platform default resolves to); f64 always runs
    HIGHEST. ``mxu_direct_max`` is the direct-plan threshold: axes up to
    this length are one dense contraction, longer axes take the
    four-step factorization with the largest factor not above it on the
    MXU (``ops/mxu_fft._split_for``); the platform default sets it per
    extent, and ``autotune_local_fft`` races it past the default.
    """

    comm_method: CommMethod = CommMethod.ALL2ALL
    send_method: SendMethod = SendMethod.SYNC
    comm_method2: Optional[CommMethod] = None
    send_method2: Optional[SendMethod] = None
    opt: int = 0
    cuda_aware: bool = True
    warmup_rounds: int = 0
    iterations: int = 1
    double_prec: bool = False
    norm: FFTNorm = FFTNorm.NONE
    benchmark_dir: str = "benchmarks"
    fft_backend: Optional[str] = None
    mxu_precision: Optional[str] = None
    mxu_karatsuba: Optional[bool] = None
    mxu_fourstep_einsum: Optional[bool] = None
    mxu_direct_max: Optional[int] = None
    fft3d_chunk: Optional[int] = None
    streams_chunks: Optional[int] = None
    overlap_depth: "int | str" = AUTO
    overlap_subblocks: Optional[int] = None
    wire_dtype: str = "native"
    wire_error_budget: Optional[float] = None
    fused_wire: bool = False
    guards: Optional[str] = None
    wisdom_path: Optional[str] = None
    use_wisdom: bool = True

    def __post_init__(self):
        from .ops.fft import validate_backend  # lazy: ops.fft imports params
        if self.fft_backend not in (None, AUTO):
            validate_backend(self.fft_backend)
        if not (isinstance(self.comm_method, CommMethod)
                or self.comm_method == AUTO):
            raise ValueError(
                f"comm_method must be a CommMethod or {AUTO!r}, "
                f"got {self.comm_method!r}")
        if not (self.comm_method2 is None
                or isinstance(self.comm_method2, CommMethod)
                or self.comm_method2 == AUTO):
            raise ValueError(
                f"comm_method2 must be a CommMethod, {AUTO!r} or None, "
                f"got {self.comm_method2!r}")
        if self.mxu_precision is not None and \
                str(self.mxu_precision).lower() not in _MXU_PRECISIONS:
            raise ValueError(
                f"mxu_precision must be one of {sorted(_MXU_PRECISIONS)} "
                f"or None, got {self.mxu_precision!r}")
        if self.fft3d_chunk is not None and (
                not isinstance(self.fft3d_chunk, int) or self.fft3d_chunk < 1):
            raise ValueError(
                f"fft3d_chunk must be a positive int or None, "
                f"got {self.fft3d_chunk!r}")
        if self.mxu_direct_max is not None and (
                not isinstance(self.mxu_direct_max, int)
                or self.mxu_direct_max < 1):
            raise ValueError(
                f"mxu_direct_max must be a positive int or None, "
                f"got {self.mxu_direct_max!r}")
        if self.streams_chunks is not None and (
                not isinstance(self.streams_chunks, int)
                or self.streams_chunks < 1):
            # >= 1, not >= 2: the knob is documented as ignored unless the
            # send method is STREAMS, and chunks=1 degrades gracefully to
            # the monolithic exchange (chunk_slices clamps anyway).
            raise ValueError(
                f"streams_chunks must be a positive int or None, "
                f"got {self.streams_chunks!r}")
        # parse_overlap_depth canonicalizes (and rejects depths < 2) at
        # Config construction, like guards below — a typo'd depth fails
        # here, not at first trace.
        object.__setattr__(self, "overlap_depth",
                           parse_overlap_depth(self.overlap_depth))
        if self.overlap_subblocks is not None and (
                not isinstance(self.overlap_subblocks, int)
                or self.overlap_subblocks < 1):
            # >= 1, not >= 2: subblocks=1 degrades gracefully to the
            # monolithic per-peer block (ring_subblocks clamps anyway),
            # mirroring the streams_chunks contract above.
            raise ValueError(
                f"overlap_subblocks must be a positive int or None, "
                f"got {self.overlap_subblocks!r}")
        if self.wire_dtype not in _WIRE_DTYPES:
            raise ValueError(
                f"wire_dtype must be one of {_WIRE_DTYPES}, "
                f"got {self.wire_dtype!r} (parse_wire_dtype canonicalizes)")
        if self.wire_error_budget is not None and (
                not isinstance(self.wire_error_budget, (int, float))
                or not self.wire_error_budget > 0):
            raise ValueError(
                f"wire_error_budget must be a positive number or None, "
                f"got {self.wire_error_budget!r}")
        if not isinstance(self.fused_wire, bool):
            raise ValueError(
                f"fused_wire must be a bool, got {self.fused_wire!r}")
        if self.guards is not None:
            # Canonicalized here rather than at resolution so a typo'd
            # mode fails at Config construction, not at first exec.
            object.__setattr__(self, "guards", parse_guards(self.guards))

    def mxu_settings(self) -> Optional[object]:
        """The plan's ``mxu_fft.MXUSettings``, or None when every knob is
        None — None lets the deprecated ``set_*`` process defaults keep
        applying wholesale, preserving pre-Config behavior. When any knob
        is set, the OTHER knobs still fall back per-knob to the process
        defaults in effect at build time (a later ``set_*`` call does not
        reach an already-built plan)."""
        if (self.mxu_precision is None and self.mxu_karatsuba is None
                and self.mxu_fourstep_einsum is None
                and self.mxu_direct_max is None):
            return None
        import dataclasses as dc

        from .ops import mxu_fft as mx  # lazy: imports jax
        # PROCESS defaults, not current_settings(): a plan built inside an
        # ambient use_settings()/radix2() scope must not snapshot that
        # scope's overrides into its permanent state.
        base = mx.default_settings()
        kw = {}
        if self.mxu_precision is not None:
            kw["precision"] = mx.as_precision(self.mxu_precision)
        if self.mxu_karatsuba is not None:
            kw["karatsuba"] = self.mxu_karatsuba
        if self.mxu_fourstep_einsum is not None:
            kw["fourstep_einsum"] = self.mxu_fourstep_einsum
        if self.mxu_direct_max is not None:
            kw["direct_max"] = self.mxu_direct_max
        return dc.replace(base, **kw)

    def resolved_comm2(self) -> CommMethod:
        return self.comm_method2 if self.comm_method2 is not None else self.comm_method

    def resolved_snd2(self) -> SendMethod:
        return self.send_method2 if self.send_method2 is not None else self.send_method

    def resolved_streams_chunks(self) -> int:
        """Chunk count for the STREAMS pipelined transpose (None -> 4)."""
        return self.streams_chunks if self.streams_chunks is not None else 4

    def resolved_overlap_depth(self) -> int:
        """Revolving receive-buffer depth of the overlap schedules
        (RING_OVERLAP's ring, the pipelined all_to_all's issue-ahead
        window). ``"auto"`` -> 2, the shipped double-buffered pipeline —
        so every pre-depth program (and its fingerprint pin) is traced
        op-for-op unchanged unless a deeper schedule is explicitly
        chosen or wisdom-resolved. Capped at the exchange's step count
        at trace time (``ring_transpose``) and in every descriptor
        (``ring_schedule`` / ``schedverify.describe``)."""
        return 2 if self.overlap_depth == AUTO else int(self.overlap_depth)

    def resolved_overlap_subblocks(self) -> int:
        """Sub-blocks each travelling block is split into (None -> 1,
        the monolithic per-peer block). On a ring rendering this is the
        block-granularity axis (each peer block -> S ppermute
        micro-steps); under ALL2ALL + SYNC/MPI_TYPE a value > 1 selects
        the pipelined all_to_all rendering with S chunked collectives.
        Clamped to the split axis extent at trace time."""
        return (self.overlap_subblocks
                if self.overlap_subblocks is not None else 1)

    def fused_wire_for(self, snd: "SendMethod") -> bool:
        """The fused-wire predicate for an exchange rendered by ``snd``:
        opt-in ``fused_wire`` on a ring rendering (RING/RING_OVERLAP)
        with the compressed bf16 wire — inert everywhere else (read
        POST-resolution; an unresolved "auto" wire never reaches the
        assemblers). The ONE activation condition every family and the
        shared hook builder (``pallas_fft.fused_ring_hooks``) consult,
        so the three assemblers cannot drift."""
        return bool(self.fused_wire and snd.is_ring
                    and self.wire_dtype == "bf16")

    def fused_wire_active(self, second: bool = False) -> bool:
        """``fused_wire_for`` of this plan's own (first or second)
        transpose rendering."""
        return self.fused_wire_for(self.resolved_snd2() if second
                                   else self.send_method)

    def resolved_wire_budget(self) -> float:
        """Max rel error the 'auto' wire race accepts from a compressed
        wire (None -> DEFAULT_WIRE_ERROR_BUDGET)."""
        return (self.wire_error_budget if self.wire_error_budget is not None
                else DEFAULT_WIRE_ERROR_BUDGET)

    def resolved_guards(self) -> str:
        """Guard mode: the explicit ``guards`` field, else ``$DFFT_GUARDS``,
        else "off". Read once at plan construction (resilience/guards.py),
        so a mid-run env change cannot split a plan's directions across
        modes."""
        if self.guards is not None:
            return self.guards
        import os
        env = os.environ.get("DFFT_GUARDS", "").strip()
        return parse_guards(env) if env else "off"
