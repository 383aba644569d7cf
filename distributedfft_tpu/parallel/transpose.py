"""Global transpose (redistribution) engine.

This is the TPU-native replacement for the reference's entire L3 layer — the
2x3 (comm x send) matrix of pack / MPI / unpack strategies duplicated in every
decomposition class (``src/slab/default/mpicufft_slab.cpp:284-769``,
``src/pencil/mpicufft_pencil.cpp:678-1482``). On TPU the redistribution is a
single ``lax.all_to_all`` over a named mesh axis: XLA emits the device
collective (riding ICI), fuses the pack/unpack relayouts into neighbouring
ops, and its async scheduler overlaps compute with communication — the roles
of the reference's ``cudaMemcpy2D/3DAsync`` packing, ``MPI_Isend/Alltoallv``
and the Streams callback thread respectively.

Uneven extents (notably the R2C halved axis ``Nz/2+1``,
``params.hpp:30``) are handled by padding the split axis to a multiple of the
mesh-axis size and slicing afterwards, where the reference uses per-peer byte
counts (``src/slab/default/mpicufft_slab.cpp:217-228``). Padded lanes never
mix with real data because every FFT runs along a different axis; they are
sliced off at the plan boundary.
"""

from __future__ import annotations

import math

import numpy as np

import jax.numpy as jnp
from jax import lax

from .. import obs
from ..resilience import inject

# Wire dtypes of the global exchanges (the "wire layer"): how a complex
# shard is encoded immediately before a collective and decoded immediately
# after. NATIVE is the bit-identical pass-through (today's path); BF16
# packs the complex payload as a planar (real, imag) bf16 pair along a new
# leading axis, halving the wire bytes of a complex64 exchange (complex128:
# quarter). The split is PLANAR, not interleaved, so the per-peer pieces of
# the tiled collective stay contiguous slices of both planes and every
# exchange rendering (default / realigned / ring) works on the encoded
# array with its split/concat axes shifted by one. ``"auto"`` is a
# Config-level marker (params.AUTO semantics) resolved by measurement
# before any transpose runs; the functions here accept only the two
# concrete encodings.
WIRE_NATIVE = "native"
WIRE_BF16 = "bf16"
WIRE_DTYPES = (WIRE_NATIVE, WIRE_BF16)


def validate_wire(wire: str) -> str:
    if wire not in WIRE_DTYPES:
        raise ValueError(
            f"wire dtype must be one of {WIRE_DTYPES} (got {wire!r}; "
            f"'auto' must be resolved at plan construction)")
    return wire


def _wire_active(x, wire: str) -> bool:
    """Whether the wire layer transforms this payload: only complex arrays
    are compressed (every plan exchange carries post-FFT complex data; a
    real payload passes through native so the helpers stay total)."""
    validate_wire(wire)
    return wire != WIRE_NATIVE and jnp.iscomplexobj(x)


def wire_encode(x, wire: str = WIRE_BF16):
    """Complex array -> planar (real, imag) bf16 pair along a NEW leading
    axis (shape ``(2,) + x.shape``). Non-complex input and ``wire="native"``
    pass through unchanged. The emitted ops carry the ``dfft/wire/encode``
    stage scope (metadata only — ``obs/profile.py`` attribution)."""
    if not _wire_active(x, wire):
        return x
    with obs.profile.wire_scope("encode"):
        return jnp.stack([jnp.real(x), jnp.imag(x)]).astype(jnp.bfloat16)


def wire_decode(y, dtype, wire: str = WIRE_BF16):
    """Inverse of ``wire_encode``: planar pair -> complex array of
    ``dtype`` (the payload's pre-encode complex dtype; the bf16 wire lost
    the mantissa either way, so decoding restores only shape/dtype)."""
    validate_wire(wire)
    if wire == WIRE_NATIVE:
        return y
    with obs.profile.wire_scope("decode"):
        f = (jnp.float64 if np.dtype(dtype) == np.complex128
             else jnp.float32)
        z = y.astype(f)
        return lax.complex(z[0], z[1])


def wire_complex_dtype(double_prec: bool):
    """The complex dtype a GSPMD-boundary wire decode restores: the plan's
    configured precision. (The explicit shard_map renderings infer the
    payload dtype from the traced value instead; at a GSPMD stage boundary
    the decode stage only sees the bf16 planes, so the target dtype must
    be static — a plan fed f64 input without ``double_prec`` therefore
    continues in complex64 downstream of a compressed boundary, which is
    already far above the wire's bf16 precision.)"""
    return jnp.complex128 if double_prec else jnp.complex64


def wire_gspmd_stages(mesh, first, last, in_spec, out_spec, wire: str,
                      double_prec: bool):
    """The PEER2PEER (GSPMD) stage pair with the wire layer applied:
    ``(stage1, stage2, boundary_spec, axis_shift)``. Under a compressed
    wire, stage1 emits the planar bf16 encoding and stage2 decodes it, so
    the GSPMD-inserted boundary collective moves the compressed array —
    ``boundary_spec`` is then the encoded target layout (leading plane
    axis) and ``axis_shift`` is 1 (a chunked boundary's chunk axis shifts
    past the plane axis). ``wire="native"`` returns the plain stage pair,
    bit-identical to the pre-wire program. Shared by the slab and
    batched-2D engines (pencil's ``_compose`` mirrors this contract
    inline at its WBREAK/CHUNKED_BREAK markers — keep the three in
    sync)."""
    import jax
    from jax.sharding import PartitionSpec

    if wire == WIRE_NATIVE:
        # inject.taint_wire: the fault-injection hook on the boundary
        # payload — identity (zero added ops) without $DFFT_FAULT_SPEC.
        stage1 = jax.shard_map(
            lambda xl: inject.taint_wire(first(xl), "gspmd"),
            mesh=mesh, in_specs=in_spec, out_specs=in_spec)
        stage2 = jax.shard_map(last, mesh=mesh, in_specs=out_spec,
                               out_specs=out_spec)
        return stage1, stage2, out_spec, 0
    cdt = wire_complex_dtype(double_prec)
    enc1 = PartitionSpec(None, *in_spec)
    enc2 = PartitionSpec(None, *out_spec)
    stage1 = jax.shard_map(
        lambda xl: inject.taint_wire(wire_encode(first(xl), wire), "gspmd"),
        mesh=mesh, in_specs=in_spec, out_specs=enc1)
    stage2 = jax.shard_map(lambda yl: last(wire_decode(yl, cdt, wire)),
                           mesh=mesh, in_specs=enc2, out_specs=out_spec)
    return stage1, stage2, enc2, 1


def wire_itemsize(dtype, wire: str = WIRE_NATIVE) -> int:
    """Bytes ONE logical element of ``dtype`` occupies on the wire: the
    native itemsize, or 4 for a bf16-compressed complex element (two bf16
    planes). Non-complex payloads are never compressed."""
    validate_wire(wire)
    d = np.dtype(dtype)
    if wire == WIRE_NATIVE or d.kind != "c":
        return d.itemsize
    return 4  # 2 planes x 2 bytes (bf16)


def wire_nbytes(shape, dtype, wire: str = WIRE_NATIVE) -> int:
    """Wire bytes of a full exchange payload of ``shape``/``dtype`` under
    the given wire encoding — what the bench layer reports as
    ``wire_bytes_per_transpose`` (vs the logical ``nbytes`` that defines
    EFFECTIVE bandwidth)."""
    return math.prod(int(s) for s in shape) * wire_itemsize(dtype, wire)


def pad_axis_to(x, axis: int, target: int):
    """Zero-pad ``axis`` up to ``target`` extent (no-op when already there)."""
    cur = x.shape[axis]
    if cur == target:
        return x
    if cur > target:
        raise ValueError(f"axis {axis} extent {cur} exceeds pad target {target}")
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - cur)
    return jnp.pad(x, widths)


def slice_axis_to(x, axis: int, target: int):
    """Take the leading ``target`` entries along ``axis`` (no-op when equal)."""
    if x.shape[axis] == target:
        return x
    return lax.slice_in_dim(x, 0, target, axis=axis)


def chunk_slices(ext: int, k: int):
    """``(start, size)`` pairs splitting an axis of extent ``ext`` into
    ``min(k, ext)`` near-equal pieces (remainder spread over the leading
    pieces) — the static chunk table of the STREAMS pipelined transpose."""
    k = max(1, min(k, ext))
    q, r = divmod(ext, k)
    out, off = [], 0
    for i in range(k):
        sz = q + (1 if i < r else 0)
        out.append((off, sz))
        off += sz
    return out


def split_axis_chunks(x, axis: int, k: int):
    """Split ``x`` into ``min(k, extent)`` near-equal pieces along ``axis``
    (static slicing; uneven tail sizes allowed)."""
    return [lax.slice_in_dim(x, off, off + sz, axis=axis)
            for off, sz in chunk_slices(x.shape[axis], k)]


def concat_axis_chunks(pieces, axis: int):
    """Reassemble ``split_axis_chunks`` pieces (single piece passes through
    untouched — the split/join contract lives in one place)."""
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces,
                                                              axis=axis)


def chunked_reshard(x, target, axis: int, k: int):
    """Reshard the global array ``x`` to ``target`` (a NamedSharding) as
    ``k`` independent piece-reshards along ``axis`` — the PEER2PEER
    rendering of ``SendMethod.STREAMS``, intended as the TPU counterpart
    of the reference Streams engine's per-peer sends
    (``src/slab/default/mpicufft_slab.cpp:343-448``).

    MEASURED NEGATIVE RESULT (8-device CPU mesh, k=4 — see
    ``eval/benchmarks/cpumesh8/OVERLAP.md``): GSPMD re-fuses the K piece
    reshards into ONE collective — the compiled HLO is identical to the
    monolithic SYNC exchange, with ZERO async collective ops — so this
    rendering buys no pipelining; it is kept as the honest P2P+STREAMS
    no-op. For real comm/compute overlap use ``ring_transpose``
    (``SendMethod.RING``): its ``P-1`` distinct ``collective-permute``
    steps cannot be re-fused, and the overlap detector
    (``microbench.async_collective_counts``) fires on them.

    ``axis`` must be an axis whose sharding the stage boundary does NOT
    change (the exchange's free axis). When it is unsharded (slab free
    axis, batched-2D batch axis) the pieces are plain global slices.
    When it IS mesh-sharded — pencil: x over p1 at transpose 1, z over
    p2 at transpose 2, identically on both sides — global slices would
    cross shard boundaries and every piece-reshard would move data along
    the chunk axis that the monolithic reshard never touches. Instead
    the axis is reshaped shard-aligned into ``(mesh_extent, local)`` and
    the pieces split the LOCAL sub-axis, so each piece takes the same
    local rows of every shard and the K piece exchanges together move
    exactly the monolithic exchange's bytes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    spec = list(target.spec) + [None] * (x.ndim - len(target.spec))
    names = spec[axis]
    if names is None:
        pieces = [jax.lax.with_sharding_constraint(p, target)
                  for p in split_axis_chunks(x, axis, k)]
        return concat_axis_chunks(pieces, axis)
    if isinstance(names, str):
        names = (names,)
    mesh_ext = 1
    for n in names:
        mesh_ext *= target.mesh.shape[n]
    ext = x.shape[axis]
    if ext % mesh_ext:
        raise ValueError(
            f"chunk axis extent {ext} not divisible by its mesh extent "
            f"{mesh_ext} (padded distributed extents always are)")
    rs_shape = x.shape[:axis] + (mesh_ext, ext // mesh_ext) \
        + x.shape[axis + 1:]
    rs_spec = PartitionSpec(*(spec[:axis] + [spec[axis], None]
                              + spec[axis + 1:]))
    rs_target = NamedSharding(target.mesh, rs_spec)
    y = jnp.reshape(x, rs_shape)
    pieces = [jax.lax.with_sharding_constraint(p, rs_target)
              for p in split_axis_chunks(y, axis + 1, k)]
    return jnp.reshape(concat_axis_chunks(pieces, axis + 1), x.shape)


def ring_subblocks(concat_extent: int, subblocks: int) -> int:
    """Effective sub-block count of a ring exchange: the requested split
    clamped to the travelling block's concat-axis extent (``chunk_slices``
    semantics). The ONE clamp the transpose, the contract decls and the
    schedule descriptors all share, so the traced permute count and the
    declared census can never disagree."""
    return len(chunk_slices(max(1, int(concat_extent)), max(1, subblocks)))


def ring_transpose(x, axis_name: str, split_axis: int, concat_axis: int, *,
                   pipeline_fn=None, wire: str = WIRE_NATIVE,
                   overlap: bool = False, depth: int = 2, subblocks: int = 1,
                   encode_fn=None, arrive_fn=None):
    """Ring-pipelined rendering of the tiled ``lax.all_to_all`` exchange:
    the global transpose decomposed into ``P-1`` ``lax.ppermute`` steps
    (rotation offset t sends the block destined for peer ``r+t`` directly,
    so the total wire bytes equal the monolithic collective's), plus the
    zero-cost local block. Logical result is bit-identical to
    ``lax.all_to_all(..., split_axis, concat_axis, tiled=True)``.

    Why this rendering exists: the chunked STREAMS piece-reshards are
    re-fused by GSPMD into one collective (measured —
    ``eval/benchmarks/cpumesh8/OVERLAP.md``), and even the explicit chunked
    ``all_to_all``s stay K instances of the same op. Each ring step here is
    a DISTINCT ``collective-permute`` (async ``collective-permute-start``/
    ``done`` pair on TPU) carrying different data, which XLA can neither
    CSE nor re-fuse — so the exchange is genuinely split into ``P-1``
    independently schedulable transfers, the TPU analog of the reference
    Streams engine's per-peer ``MPI_Isend`` loop
    (``src/slab/default/mpicufft_slab.cpp:343-448``).

    ``pipeline_fn`` (optional) runs on each peer block AS IT ARRIVES —
    traced between ring steps, so step t+1's permute (whose operand is
    ready before the ring starts) can be in flight while block t computes;
    by the time the ring drains, all but the last block are already
    processed. It must be shape/dtype-preserving and must not mix data
    across ``concat_axis`` positions (received blocks are disjoint slices
    of the output along that axis) — per-axis FFTs along any axis other
    than ``concat_axis`` qualify; the gathered-axis FFT must wait for
    assembly.

    ``wire`` selects the wire encoding of each TRAVELLING block
    (``wire_encode`` before its ``ppermute``, ``wire_decode`` on arrival,
    before ``pipeline_fn``) — per-block, so compression and the ring's
    compute/communication overlap stack. The local block (step 0) never
    touches the wire and stays exact; the monolithic collective renderings
    by contrast compress their whole payload, resident chunk included —
    both satisfy the same per-element error bound, the ring merely keeps
    1/P of the data lossless for free.

    ``overlap`` selects the REVOLVING-BUFFER schedule
    (``SendMethod.RING_OVERLAP``): up to ``depth - 1`` permutes are
    issued ahead of each block's ``pipeline_fn`` with ``depth`` revolving
    receive buffers (capped at the step count, matching
    ``analysis/schedverify.revolving_schedule``'s effective-depth
    semantics). ``depth=2`` is the shipped double-buffered pipeline:
    step t+1's ``ppermute`` is issued before block t's ``pipeline_fn``
    is traced — op-for-op the pre-depth program, pinned by the plan
    fingerprints. Every per-block op — slice, encode, taint, permute,
    decode, pipeline — is IDENTICAL to the ``overlap=False`` schedule
    at every depth, only the issue order changes, so the output is
    bit-identical to RING while a scheduler that honors program order
    (the TPU async start/done lowering) can keep ``depth - 1`` wire
    transfers in flight under every block's compute instead of
    alternating permute -> FFT -> permute.

    ``subblocks`` adds the block-granularity axis (the Streams-chunks
    idea applied INSIDE the ring): each travelling peer block is split
    into ``ring_subblocks(concat_extent, subblocks)`` near-equal pieces
    along ``concat_axis``, each riding its own ``ppermute`` micro-step,
    so the first sub-block's ``pipeline_fn`` starts before the peer's
    full payload has arrived. The wire codec and the fused hooks apply
    per sub-block unchanged (both are elementwise / per-block by
    contract), and ``concat_axis`` is always a safe split axis because
    ``pipeline_fn`` must not mix data across it (see above) — so
    sub-blocking composes with every family's pipelined FFT stage.
    ``subblocks=1`` (default) traces the exact pre-split program.

    ``encode_fn``/``arrive_fn`` are the FUSED-WIRE hooks
    (``Config.fused_wire``; ``ops/pallas_fft`` fused-wire kernels):
    ``encode_fn`` replaces ``wire_encode`` on each travelling block
    (only consulted when the wire is active), and ``arrive_fn`` replaces
    the ``wire_decode`` + ``pipeline_fn`` pair on each ARRIVING block
    (the local block always takes plain ``pipeline_fn`` — it never
    touches the wire, so there is nothing to fuse with). Defaults
    (None) keep the plain wire layer.

    The ``split_axis`` extent must be divisible by the mesh axis size
    (plans pad). Must be called inside ``shard_map`` over ``axis_name``.
    """
    obs.metrics.inc("wire.exchanges_traced")
    obs.metrics.gauge("wire.bytes_per_transpose",
                      wire_nbytes(x.shape, x.dtype, wire))
    if depth < 1:
        raise ValueError(f"overlap depth must be >= 1, got {depth}")
    if overlap and depth < 2:
        raise ValueError(
            f"the revolving-buffer overlap schedule needs depth >= 2, "
            f"got {depth} (use overlap=False for the serial ring)")
    if subblocks < 1:
        raise ValueError(f"subblocks must be >= 1, got {subblocks}")
    p = lax.axis_size(axis_name)
    wired = _wire_active(x, wire)
    if pipeline_fn is None:
        def pipeline_fn(b):
            return b
    if p == 1:
        return pipeline_fn(x)
    s, c = split_axis, concat_axis
    ext = x.shape[s]
    if ext % p:
        raise ValueError(
            f"ring transpose needs split extent {ext} divisible by the "
            f"mesh axis size {p} (plans pad before the exchange)")
    ch = ext // p
    r = lax.axis_index(axis_name)
    # Sub-block split table along the CONCAT axis (safe by the
    # pipeline_fn contract above; ``ring_subblocks`` is the same clamp
    # the contract decls use). subblocks=1 -> a single full-block
    # "sub-block" with zero extra slice ops, so the pre-split program
    # is traced op-for-op.
    subs = chunk_slices(x.shape[c], max(1, subblocks))
    nsub = len(subs)

    def chunk(i):
        # Block destined for peer (r + i) mod p: a traced-offset slice, so
        # every device runs the same program on its own rotation.
        return lax.dynamic_slice_in_dim(x, ((r + i) % p) * ch, ch, axis=s)

    def send(t, j=0):
        """Encode + taint + permute of step t's travelling (sub-)block —
        the wire side of one ring micro-step, shared by both schedules
        so the per-block ops cannot diverge between them."""
        perm = [(src, (src + t) % p) for src in range(p)]
        b = chunk(t)
        if nsub > 1:
            off, sz = subs[j]
            b = lax.slice_in_dim(b, off, off + sz, axis=c)
        if wired:
            if encode_fn is None:
                b = wire_encode(b, wire)  # carries the wire/encode scope
            else:
                with obs.profile.wire_scope("encode"):
                    b = encode_fn(b)
        # Fault-injection hook on each TRAVELLING block (the local block
        # never touches the wire, mirroring the encoding contract above);
        # identity without $DFFT_FAULT_SPEC.
        b = inject.taint_wire(b, "ring")
        return lax.ppermute(b, axis_name, perm)

    def arrive(b):
        """Decode + per-block pipeline of one ARRIVED (sub-)block (the
        receive side of a ring micro-step); ``arrive_fn`` fuses the
        pair. The fused hook traces under the wire/decode scope (a
        family's arrive may nest its pipelined-FFT stage scope inside —
        innermost wins in attribution, so the fused DFT still lands on
        its local_fft node). Both apply per sub-block unchanged: the
        codec is elementwise and pipeline_fn never mixes data across
        the concat (= sub-block) axis."""
        if arrive_fn is not None:
            with obs.profile.wire_scope("decode"):
                return arrive_fn(b)
        if wired:
            b = wire_decode(b, x.dtype, wire)
        return pipeline_fn(b)

    # Step 0 is the local block (peer r -> itself, no wire). Step t sends
    # chunk r+t to peer r+t and receives peer (r-t)'s block for us. With
    # sub-blocks each peer step becomes ``nsub`` micro-steps, each
    # riding its own ppermute.
    steps = p - 1
    micro = steps * nsub

    def msend(m):
        # Micro-step m (1-based) = sub-block (m-1) % nsub of peer step
        # (m-1) // nsub + 1 — the same linearization
        # ``schedverify.revolving_schedule`` proves hazard-free.
        return send((m - 1) // nsub + 1, (m - 1) % nsub)

    # Issue-ahead window: ``depth`` revolving receive buffers -> up to
    # ``depth - 1`` permutes in flight ahead of the compute front (the
    # effective buffer count is additionally capped at the micro-step
    # count — schedverify's effective-depth semantics; a ring can never
    # hold more outstanding transfers than it has steps). The serial
    # RING is the zero-window degenerate of the same loop: issue
    # micro-step m, then arrive it immediately. At depth=2 / nsub=1 the
    # loop below traces op-for-op the shipped double-buffered
    # RING_OVERLAP order (pre-issue step 1's permute — its operand
    # carries no dependency on any compute — then inside the loop issue
    # t+1's permute before arriving block t), pinned by the plan
    # fingerprints; at every depth the per-block ops are those of the
    # serial ring in a reordered schedule — bit-identical output.
    w = min(depth - 1, micro) if overlap else 0
    queue = [msend(m) for m in range(1, w + 1)]
    blocks = [pipeline_fn(chunk(0))]
    landed = []
    for m in range(1, micro + 1):
        nxt = m + w
        if nxt <= micro:
            queue.append(msend(nxt))
        landed.append(arrive(queue.pop(0)))
    # Re-join each peer step's sub-blocks along the concat axis (the
    # axis they were split on; single sub-block passes through).
    for t in range(1, p):
        blocks.append(concat_axis_chunks(landed[(t - 1) * nsub:t * nsub],
                                         c))
    # Reassemble in PEER order along the concat axis (tiled all_to_all
    # semantics: the block from peer j lands at concat slot j). Block t
    # came from peer (r - t) mod p, so peer order is the arrival order
    # reversed then rotated by r+1: with V = flip(W), V[(j-r-1) mod p] =
    # W[(r-j) mod p] — i.e. roll(V, r+1)[j] is peer j's block.
    w = jnp.stack(blocks, axis=0)
    o = jnp.roll(jnp.flip(w, axis=0), r + 1, axis=0)
    o = jnp.moveaxis(o, 0, c)
    shp = list(o.shape)
    merged = shp.pop(c)
    shp[c] *= merged
    return o.reshape(tuple(shp))


def ring_schedule(payload_shape, dtype, wire: str, p: int,
                  overlap: bool = False, depth: int = 2,
                  subblocks: int = 1) -> dict:
    """Static description of a ring exchange's schedule over a GLOBAL
    padded payload of ``payload_shape`` (what ``dfft-explain`` prints for
    a resolved RING/RING_OVERLAP plan): ``steps`` peer steps per device
    (``permutes`` = ``steps * subblocks`` micro-steps once the
    block-granularity axis splits each peer block), ``buffers`` revolving
    receive buffers, the per-device travelling block's wire bytes (one
    P-th of the local shard) and the sub-block's (the unit in flight on
    each micro-step), the peak bytes in flight per device, and the total
    wire bytes across the mesh (the ``(P-1)/P`` ring discount: the local
    block never travels).

    ``buffers`` reports the EFFECTIVE buffer count: the requested
    ``depth`` capped at the micro-step count (``schedverify``'s
    effective-depth semantics — depth 8 on 8 ranks holds 7 buffers, and
    this descriptor says so; a descriptor claiming more buffers than the
    ring has steps would overstate the in-flight bytes). ``depth`` > 2
    describes the generalized D-way revolving pipeline (ROADMAP item 3's
    autotune axis); ``analysis/schedverify.py`` statically proves the
    generated schedule hazard-free at any depth/split before a plan may
    trace it."""
    if depth < 1:
        raise ValueError(f"buffer depth must be >= 1, got {depth}")
    if subblocks < 1:
        raise ValueError(f"subblocks must be >= 1, got {subblocks}")
    total = wire_nbytes(payload_shape, dtype, wire)
    block = total // (p * p) if p > 1 else total
    steps = max(0, p - 1)
    sub = max(1, subblocks)
    micro = steps * sub
    # Largest sub-block (chunk_slices spreads the remainder over the
    # leading pieces) — the honest peak unit in flight.
    sub_block = block if sub == 1 else -(-block // sub)
    buffers = (min(depth, micro) if micro else 0) if overlap else 1
    return {
        "steps": steps,
        "subblocks": sub,
        "permutes": micro,
        "buffers": buffers,
        "effective_depth": buffers if overlap else 1,
        "block_wire_bytes": block,
        "subblock_wire_bytes": sub_block,
        # Up to ``buffers`` sub-block-sized transfers live per device
        # (the in-flight window plus the computing block); the plain
        # ring holds one.
        "bytes_in_flight": sub_block * buffers,
        "total_wire_bytes": total * steps // p if p > 1 else 0,
    }


def realigned_pack_shape(shape, split_axis: int, p: int):
    """Shape the realigned sender pack exchanges (the merged-leading layout
    of ``all_to_all_transpose(..., realigned=True)``'s PURE collective) —
    applies uniformly to a local block or the global array. Single source
    of truth for ceiling probes that time that exact layout."""
    s = split_axis
    if shape[s] % p:
        raise ValueError(
            f"split extent {shape[s]} not divisible by mesh size {p}")
    if s == 0:
        return tuple(shape)
    return (p * shape[0],) + tuple(
        shape[i] // p if i == s else shape[i]
        for i in range(1, len(shape)))


def all_to_all_transpose(x, axis_name: str, split_axis: int, concat_axis: int,
                         *, realigned: bool = False,
                         wire: str = WIRE_NATIVE):
    """Redistribute inside ``shard_map``: scatter ``split_axis`` over the mesh
    axis and gather ``concat_axis`` from it — one global transpose, the
    analog of the reference's ``MPI_Alltoallv/w`` exchange.

    ``wire`` selects the wire encoding of the exchange payload
    (``WIRE_NATIVE`` = bit-identical pass-through; ``WIRE_BF16`` = planar
    (real, imag) bf16 pair, half the wire bytes of a complex64 payload).
    The encode happens immediately before the collective and the decode
    immediately after, on the planar array with the split/concat axes
    shifted past the new leading plane axis — so it composes with both the
    default and the realigned (opt1) rendering unchanged: the realigned
    pack merges the plane axis into its peer-major leading chunks and each
    peer's contiguous piece simply carries both planes of its block.

    ``realigned`` is the TPU rendering of the reference's "opt1" coordinate
    transform (``include/mpicufft_slab_opt1.hpp:46-54``): pack the block so
    the per-peer pieces are leading-axis contiguous *before* the collective,
    so the ``lax.all_to_all`` itself is PURE (``split_axis == concat_axis``,
    zero relayout inside the collective), then unpack on the receiving side.
    Logical result is bit-identical to the default rendering; the physical
    relayout moves across the collective, which is exactly the axis the
    reference's opt0/opt1 pair benchmarks.

    Why this rendering: XLA's native lowering of a ``split != concat``
    ``all_to_all`` materialises the strided per-peer slices with a chain of
    slice/transpose/copy ops (measured ~19 block-sized passes per exchange
    on the CPU backend — round-4 HLO count), while this rendering pays at
    most ONE explicit block transpose per side (and the side whose axis is
    already leading pays none — slab forward's receiver, slab inverse's
    sender are free views). Measured on the 8-device CPU mesh at 256^3 it
    moves the pipeline transpose pair from 0.59x to ~1.0x of the pure
    exchange ceiling (the north-star gate).
    """
    # Per-traced-exchange accounting (obs registry): shard-local payload
    # wire bytes, recorded once per trace, not per execution.
    obs.metrics.inc("wire.exchanges_traced")
    obs.metrics.gauge("wire.bytes_per_transpose",
                      wire_nbytes(x.shape, x.dtype, wire))
    # inject.taint_wire sits exactly at the wire_encode/wire_decode
    # boundary: the corrupted image is what travels (and what the
    # guards must catch). Identity without $DFFT_FAULT_SPEC.
    if _wire_active(x, wire):
        y = wire_encode(x, wire)
        y = inject.taint_wire(y, "all_to_all")
        y = _all_to_all_native(y, axis_name, split_axis + 1,
                               concat_axis + 1, realigned)
        return wire_decode(y, x.dtype, wire)
    return _all_to_all_native(inject.taint_wire(x, "all_to_all"),
                              axis_name, split_axis, concat_axis,
                              realigned)


def pipelined_all_to_all(x, axis_name: str, split_axis: int,
                         concat_axis: int, *, chunk_axis: int, chunks: int,
                         depth: int = 2, realigned: bool = False,
                         wire: str = WIRE_NATIVE):
    """Software-pipelined rendering of the monolithic ``all_to_all``
    exchange (``Config.overlap_subblocks`` > 1 under ``ALL2ALL`` +
    SYNC/MPI_TYPE): the payload is split into ``chunks`` near-equal
    pieces along ``chunk_axis`` — an axis the exchange does not touch —
    and chunk k+1's collective is ISSUED before chunk k is decoded, with
    an issue-ahead window of ``depth - 1`` collectives (the same
    revolving window as the depth-D ring), so opt0/opt1 get
    compute/communication overlap without switching to the ring
    rendering.

    Each chunk's exchange is the exact monolithic rendering (wire encode
    -> taint -> tiled/realigned ``lax.all_to_all`` -> decode) applied to
    a slice along an uninvolved axis, and slices along an uninvolved
    axis commute with ``all_to_all`` — so the concatenated result is
    BIT-IDENTICAL to ``all_to_all_transpose`` on the whole payload (the
    wire codec is elementwise; pinned by tests). ``chunk_axis`` must
    differ from ``split_axis``/``concat_axis``; ``chunks`` is clamped to
    the chunk-axis extent (``chunk_slices`` semantics — the census decl
    must use the same clamp).

    CPU-mesh caveat (mirrors STREAMS' measured result): the CPU
    backend's synchronous lowering runs the K collectives back-to-back,
    so this rendering only reorders ops there; the async start/done
    lowering on TPU is what turns the issue-ahead window into overlap.
    Unlike the GSPMD piece-reshards, the K explicit ``all_to_all`` ops
    carry different operands and are NOT re-fused into one collective
    (the streams precedent: K instances survive in the HLO — the census
    contract pins exactly ``chunks`` all-to-alls)."""
    if chunk_axis in (split_axis, concat_axis):
        raise ValueError(
            f"pipelined all_to_all needs a chunk axis the exchange does "
            f"not touch, got chunk_axis={chunk_axis} with "
            f"split={split_axis}/concat={concat_axis}")
    if depth < 1:
        raise ValueError(f"overlap depth must be >= 1, got {depth}")
    obs.metrics.inc("wire.exchanges_traced")
    obs.metrics.gauge("wire.bytes_per_transpose",
                      wire_nbytes(x.shape, x.dtype, wire))
    wired = _wire_active(x, wire)

    def issue(pc):
        if wired:
            y = wire_encode(pc, wire)
            y = inject.taint_wire(y, "a2a_pipe")
            return _all_to_all_native(y, axis_name, split_axis + 1,
                                      concat_axis + 1, realigned)
        return _all_to_all_native(inject.taint_wire(pc, "a2a_pipe"),
                                  axis_name, split_axis, concat_axis,
                                  realigned)

    def land(y):
        return wire_decode(y, x.dtype, wire) if wired else y

    pieces = split_axis_chunks(x, chunk_axis, chunks)
    k = len(pieces)
    w = min(depth - 1, k - 1)
    queue = [issue(pieces[i]) for i in range(w)]
    out = []
    for i in range(k):
        nxt = i + w
        if nxt < k:
            queue.append(issue(pieces[nxt]))
        out.append(land(queue.pop(0)))
    return concat_axis_chunks(out, chunk_axis)


def _all_to_all_native(x, axis_name: str, split_axis: int, concat_axis: int,
                       realigned: bool):
    """The exchange proper, on whatever array the wire layer hands it."""
    if not realigned:
        return lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
    p = lax.axis_size(axis_name)
    s, c = split_axis, concat_axis
    shp = x.shape
    if shp[s] % p:
        raise ValueError(
            f"realigned transpose needs split extent {shp[s]} divisible by "
            f"the mesh axis size {p} (plans pad before the exchange)")
    # Sender pack: split axis s into (p, shp[s]/p), bring the peer axis to
    # the front, merge it with the leading axis -> per-peer pieces are
    # contiguous leading chunks. For s == 0 this is a pure reshape (no data
    # movement); otherwise one block transpose.
    m = x.reshape(shp[:s] + (p, shp[s] // p) + shp[s + 1:])
    m = jnp.moveaxis(m, s, 0)
    m = m.reshape((m.shape[0] * m.shape[1],) + m.shape[2:])
    # Pure exchange: chunk d -> peer d, received chunk j <- peer j. Piece
    # ordering matches the tiled split/concat semantics of the default
    # rendering (chunk d of peer j's split axis lands at concat slot j).
    y = lax.all_to_all(m, axis_name, split_axis=0, concat_axis=0, tiled=True)
    # Receiver unpack: un-merge the peer axis, move it to the concat slot,
    # merge -> concatenation along c. For c == 0 this is a pure reshape.
    piece_lead = m.shape[0] // p
    r = y.reshape((p, piece_lead) + y.shape[1:])
    r = jnp.moveaxis(r, 0, c)
    out_shape = list(r.shape)
    merged = out_shape.pop(c)
    out_shape[c] *= merged
    return r.reshape(tuple(out_shape))
