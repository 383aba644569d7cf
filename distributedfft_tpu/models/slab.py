"""Slab (1D) decomposition engine — all three per-axis FFT sequences.

TPU-native re-design of the reference's slab family:

* ``ZY_Then_X`` (default): 2D FFT (y,z) -> transpose (x-split -> y-split) ->
  1D FFT x (``src/slab/default/mpicufft_slab.cpp``).
* ``Z_Then_YX``: 1D FFT z -> transpose (x-split -> z-split) -> 2D FFT (y,x);
  output distributed over the halved z axis
  (``src/slab/z_then_yx/mpicufft_slab_z_then_yx.cpp:96-104``).
* ``Y_Then_ZX``: 1D R2C y -> transpose (x-split -> y-split) -> 2D FFT (z,x);
  the halved axis is y (``src/slab/y_then_zx/mpicufft_slab_y_then_zx.cpp:95-103``).
  The reference implements this sequence forward-only; here the inverse comes
  for free from the shared pipeline builder and is provided as an extension.

Where the reference implements seven classes x a 2x3 comm/send matrix of
hand-scheduled pack/exchange/unpack variants, this engine expresses each
sequence as ONE jitted XLA program parameterized by axis roles, with two
communication strategies preserved for the reference's comparative spirit:

* ``CommMethod.ALL2ALL``  -> explicit ``shard_map`` + ``lax.all_to_all``.
* ``CommMethod.PEER2PEER`` -> GSPMD: global-view ops + sharding constraints;
  XLA chooses/schedules the collectives (its latency-hiding scheduler is the
  analog of the reference's Isend/Irecv + callback-thread overlap engine).

``config.opt == 1`` maps to the "realigned" layout (sender-contiguous
relayout before the collective), the analog of the reference's Opt1
coordinate-transform classes (``include/mpicufft_slab_opt1.hpp:46-54``).

Padded-shape contract
---------------------
XLA device meshes want extents divisible by the mesh axis, so every
*decomposed* axis of a distributed global array is zero-padded up to the next
multiple of P (``padded_extent``); undecomposed axes — including an odd
``N/2+1`` halved axis that stays local — are never padded. Where the
reference handles uneven extents with per-peer byte counts
(``src/slab/default/mpicufft_slab.cpp:217-228``), this engine pads:

* plan input  : real, ``input_padded_shape``  (x padded), sharded over x;
* plan output : complex, ``output_padded_shape`` (split axis padded),
  sharded over the split axis; pad lanes are exact zeros in forward output
  and are ignored by the inverse.

``pad_input`` / ``crop_real`` / ``pad_spectral`` / ``crop_spectral`` convert
between logical and padded forms. For mesh-divisible sizes (every benchmark
config) padded == logical and all of this is a no-op.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import obs
from .. import params as pm
from ..ops import fft as lf
from ..parallel.mesh import SLAB_AXIS, make_slab_mesh
from ..parallel.transpose import (all_to_all_transpose, chunked_reshard,
                                  concat_axis_chunks, pad_axis_to,
                                  pipelined_all_to_all, ring_subblocks,
                                  ring_transpose, slice_axis_to,
                                  split_axis_chunks, wire_gspmd_stages)
from ..utils import wisdom
from .base import (DistFFTPlan, _with_pad, local_fft_fields,
                   notice_axis_smoothness, r2c_pairing, c2r_pairing)


@dataclasses.dataclass(frozen=True)
class _SeqDef:
    """Axis roles for one slab sequence."""

    r2c_axis: int                 # axis of the real-to-complex transform
    pre_axes: Tuple[int, ...]     # C2C axes before the transpose
    split_axis: int               # axis scattered by the transpose
    post_axes: Tuple[int, ...]    # C2C axes after the transpose

    @property
    def halved(self) -> str:
        """The logical axis carrying the n//2+1 halving (the R2C axis)."""
        return "xyz"[self.r2c_axis]


_SEQS = {
    pm.SlabSequence.ZY_THEN_X: _SeqDef(2, (1,), 1, (0,)),
    pm.SlabSequence.Z_THEN_YX: _SeqDef(2, (), 2, (1, 0)),
    pm.SlabSequence.Y_THEN_ZX: _SeqDef(1, (), 1, (2, 0)),
}


class SlabFFTPlan(DistFFTPlan):
    """Distributed 3D R2C/C2R FFT with 1D (slab) decomposition over x."""

    def __init__(self, global_size: pm.GlobalSize, partition: pm.SlabPartition,
                 config: Optional[pm.Config] = None, mesh: Optional[Mesh] = None,
                 sequence: "pm.SlabSequence | str" = pm.SlabSequence.ZY_THEN_X,
                 transform: str = "r2c"):
        # Measurement-resolved Config fields (fft_backend="auto" /
        # comm_method="auto") are settled HERE, before anything reads the
        # config: wisdom hit -> reuse, miss -> bounded race-and-record
        # (utils/wisdom.py). Concrete configs pass through untouched.
        config = wisdom.resolve_config("slab", global_size, partition,
                                       config, mesh=mesh, sequence=sequence,
                                       transform=transform)
        if mesh is None and partition.p > 1:
            mesh = make_slab_mesh(partition.p)
        if mesh is not None and partition.p > 1:
            if SLAB_AXIS not in mesh.shape:
                raise ValueError(
                    f"slab mesh must have a {SLAB_AXIS!r} axis, got {mesh.axis_names}")
            if mesh.shape[SLAB_AXIS] != partition.p:
                raise ValueError(
                    f"mesh axis {SLAB_AXIS!r} has {mesh.shape[SLAB_AXIS]} devices "
                    f"but the partition asks for {partition.p}")
        super().__init__(global_size, partition, config, mesh)
        if transform not in ("r2c", "c2c"):
            raise ValueError(f"transform must be 'r2c' or 'c2c', got {transform!r}")
        self.transform = transform
        self.sequence = pm.SlabSequence.parse(sequence)
        self._seq = _SEQS[self.sequence]
        g, P = global_size, partition.p
        self._P = P
        if transform == "c2c":
            # No halved axis: complex-to-complex keeps the full extents
            # (BASELINE configs #1/#2 are 3D C2C transforms; the reference
            # core is R2C/C2R-only, so this is an extension).
            self._spec_shape = g.shape
        elif self._seq.halved == "z":
            self._spec_shape = (g.nx, g.ny, g.nz_out)
        else:
            self._spec_shape = (g.nx, g.ny_out, g.nz)
        self._split_ext = self._spec_shape[self._seq.split_axis]
        if self.fft3d:
            self._nx_pad = g.nx
            self._split_pad = self._split_ext
        else:
            self._nx_pad = pm.padded_extent(g.nx, P)
            self._split_pad = pm.padded_extent(self._split_ext, P)
            self._in_spec = PartitionSpec(SLAB_AXIS, None, None)
            out = [None, None, None]
            out[self._seq.split_axis] = SLAB_AXIS
            self._out_spec = PartitionSpec(*out)
        self._resolve_fft3d_chunk(transform)
        # Local rows of the R2C and the C2R along z (None: neither runs
        # along the last axis, or the single-device rfftn over three axes).
        ck = self.fft3d_chunk
        rows = None
        if transform == "r2c" and self._seq.r2c_axis == 2:
            if not self.fft3d:
                rows = self._nx_pad // P * g.ny
            elif ck:
                rows = g.nx // ck * g.ny
        self.r2c_paired = r2c_pairing(self.config, mesh, g.nz, rows)
        self.c2r_paired = c2r_pairing(self.config, mesh, g.nz, rows)
        notice_axis_smoothness("slab", g.shape, self.config)
        obs.event("plan.created", kind="slab", sequence=self.sequence.value,
                  transform=transform, shape=list(g.shape), ranks=P,
                  comm=self.config.comm_method.value,
                  send=self.config.send_method.value, opt=self.config.opt,
                  wire=self.config.wire_dtype, r2c_paired=self.r2c_paired,
                  c2r_paired=self.c2r_paired,
                  fft3d_chunk=ck, fft3d_chunk_from=self.fft3d_chunk_from,
                  **local_fft_fields(self.config, self._mxu_st))

    # -- shapes & size tables (reference getInSize/getOutSize family,
    #    include/mpicufft.hpp:66-79) --------------------------------------

    @property
    def output_shape(self) -> Tuple[int, int, int]:
        return self._spec_shape

    @property
    def input_padded_shape(self) -> Tuple[int, int, int]:
        g = self.global_size
        return (self._nx_pad, g.ny, g.nz)

    @property
    def output_padded_shape(self) -> Tuple[int, int, int]:
        s = list(self._spec_shape)
        s[self._seq.split_axis] = self._split_pad
        return tuple(s)

    def in_sizes(self, axis: str = "x") -> List[int]:
        if axis != "x":
            raise ValueError("slab input is decomposed over x only")
        return pm.even_shard_sizes(self.global_size.nx, self._nx_pad, self._P)

    def out_sizes(self, axis: Optional[str] = None) -> List[int]:
        """Per-rank extents of the decomposed output axis (y for ZY_Then_X /
        Y_Then_ZX, z for Z_Then_YX) — logical extents, excluding pad lanes."""
        expected = "xyz"[self._seq.split_axis]
        if axis is not None and axis != expected:
            raise ValueError(
                f"{self.sequence.value} output is decomposed over {expected}")
        return pm.even_shard_sizes(self._split_ext, self._split_pad, self._P)

    # -- logical <-> padded conversion helpers ----------------------------

    def pad_input(self, x):
        """Logical real input -> padded, device-placed input shard layout.
        Stays on device for jax arrays (no host round-trip)."""
        pad = self._nx_pad - self.global_size.nx
        if pad:
            x = jnp.pad(x, [(0, pad), (0, 0), (0, 0)])
        if self.mesh is not None:
            x = jax.device_put(x, self.input_sharding)
        return x

    def crop_real(self, r):
        """Padded inverse output -> logical (nx, ny, nz) host array."""
        return np.asarray(r)[: self.global_size.nx]

    def pad_spectral(self, c):
        pad = self._split_pad - self._split_ext
        if pad:
            widths = [(0, 0)] * 3
            widths[self._seq.split_axis] = (0, pad)
            c = jnp.pad(c, widths)
        if self.mesh is not None:
            c = jax.device_put(c, self.output_sharding)
        return c

    def crop_spectral(self, c):
        """Padded forward output -> logical spectral host array."""
        c = np.asarray(c)
        sl = [slice(None)] * 3
        sl[self._seq.split_axis] = slice(0, self._split_ext)
        return c[tuple(sl)]

    # -- execution (thin guarded wrappers over shared impl) ----------------

    def exec_r2c(self, x):
        if self.transform != "r2c":
            raise TypeError("this plan was built with transform='c2c'; "
                            "use exec_c2c/exec_c2c_inv")
        return self._exec_fwd(x)

    def exec_c2r(self, c):
        if self.transform != "r2c":
            raise TypeError("this plan was built with transform='c2c'; "
                            "use exec_c2c/exec_c2c_inv")
        return self._exec_inv(c)

    def exec_c2c(self, x):
        """Forward 3D C2C transform (transform='c2c' plans). Same pipeline
        as R2C with the first-axis transform complex."""
        if self.transform != "c2c":
            raise TypeError("this plan was built with transform='r2c'; "
                            "use exec_r2c/exec_c2r")
        return self._exec_fwd(x)

    def exec_c2c_inv(self, c):
        """Inverse 3D C2C transform (transform='c2c' plans)."""
        if self.transform != "c2c":
            raise TypeError("this plan was built with transform='r2c'; "
                            "use exec_r2c/exec_c2r")
        return self._exec_inv(c)

    def _exec_fwd(self, x):
        if tuple(x.shape) not in (self.input_shape, self.input_padded_shape):
            raise ValueError(
                f"forward exec expects global shape {self.input_shape} (or "
                f"padded {self.input_padded_shape}), got {tuple(x.shape)}")
        if not self.fft3d and tuple(x.shape) == self.input_shape \
                and self.input_shape != self.input_padded_shape:
            x = self.pad_input(x)
        from ..resilience import fallback
        return fallback.execute(self, "forward", x, self._get_r2c)

    def _exec_inv(self, c):
        if tuple(c.shape) not in (self.output_shape, self.output_padded_shape):
            raise ValueError(
                f"inverse exec expects global shape {self.output_shape} (or "
                f"padded {self.output_padded_shape}), got {tuple(c.shape)}")
        if not self.fft3d and tuple(c.shape) == self.output_shape \
                and self.output_shape != self.output_padded_shape:
            c = self.pad_spectral(c)
        from ..resilience import fallback
        return fallback.execute(self, "inverse", c, self._get_c2r)

    def _halved_axis_index(self) -> int:
        """Solver-protocol hook: the sequence's R2C axis carries the
        halving (y for Y_Then_ZX, z otherwise)."""
        return self._seq.r2c_axis

    # -- resilience hooks (guards + fallback ladder) -----------------------

    def _guard_spec(self, direction: str, dims: int = 3):
        """GuardSpec of the slab pipelines (``resilience/guards.py``):
        forward = Parseval with the sequence's R2C axis weighted (plain
        for c2c); inverse = Parseval for c2c (exact for any input),
        finiteness for C2R (arbitrary spectral input is not conjugate-
        symmetric — the transform projects it, so energy is not an
        invariant of that direction)."""
        from ..resilience.guards import GuardSpec
        g, norm = self.global_size, self.config.norm
        n = float(g.n_total)
        c2c = self.transform == "c2c"
        if direction == "forward":
            return GuardSpec(
                direction="forward", check="parseval",
                scale=1.0 if norm is pm.FFTNorm.ORTHO else n,
                in_logical=self.input_shape,
                out_logical=self._spec_shape,
                halved_axis=None if c2c else self._seq.r2c_axis,
                halved_n=0 if c2c else (g.nz if self._seq.halved == "z"
                                        else g.ny))
        if not c2c:
            return GuardSpec(direction="inverse", check="finite", scale=1.0,
                             in_logical=self.output_shape,
                             out_logical=self.input_shape)
        scale = {pm.FFTNorm.NONE: n, pm.FFTNorm.BACKWARD: 1.0 / n,
                 pm.FFTNorm.ORTHO: 1.0}[norm]
        return GuardSpec(direction="inverse", check="parseval", scale=scale,
                         in_logical=self.output_shape,
                         out_logical=self.input_shape)

    def _wisdom_key_args(self) -> dict:
        return {"kind": "slab", "sequence": self.sequence,
                "transform": self.transform, "dims": 3}

    # -- pipeline bodies ---------------------------------------------------
    # Three reusable local bodies per direction. The fused builders compose
    # them into one program; the GSPMD path drops the explicit transpose and
    # lets the stage boundary trigger the collective; forward_stages()/
    # inverse_stages() jit them individually for per-phase timing.

    def _streams_chunk_axis(self) -> int:
        """The axis the STREAMS pipelined transpose chunks along: the one
        axis involved in neither side of the exchange (slab transposes move
        ``split_axis`` <-> 0, leaving exactly one of {1, 2} free)."""
        return next(a for a in (1, 2) if a != self._seq.split_axis)

    def _a2a_pipe_chunks(self) -> int:
        """Resolved chunk count of the software-pipelined monolithic
        all-to-all (rendering ``a2a_pipe``: ALL2ALL + SYNC/MPI_TYPE with
        ``Config.overlap_subblocks`` > 1), clamped to the free-axis
        extent; 1 whenever another rendering owns the exchange."""
        cfg = self.config
        if (self.fft3d
                or cfg.comm_method is not pm.CommMethod.ALL2ALL
                or cfg.send_method not in (pm.SendMethod.SYNC,
                                           pm.SendMethod.MPI_TYPE)):
            return 1
        ca = self._streams_chunk_axis()
        return ring_subblocks(self.output_padded_shape[ca],
                              cfg.resolved_overlap_subblocks())

    def _xpose_bodies(self, realigned=None, chunks: Optional[int] = None,
                      wire: Optional[str] = None):
        """The pipeline's own transpose bodies ``(forward, inverse)`` for a
        given layout rendering (``realigned=None`` -> this plan's
        ``config.opt``). Single source of truth for what the slab exchange
        does — the fraction-gate microbench times exactly these, so the gate
        cannot drift from the shipped pipeline.

        ``chunks`` > 1 renders each transpose as that many independent
        per-piece collectives along the free axis (the exchange half of the
        STREAMS engine, without the interleaved FFTs — what the fraction
        chain races to see whether chunked exchanges alone pay or win).

        ``wire`` overrides this plan's wire encoding (``None`` -> the
        resolved ``config.wire_dtype``) — the bench layer's wire rows time
        exactly these bodies at each encoding."""
        if realigned is None:
            realigned = self.config.opt == 1
        if wire is None:
            wire = self.config.wire_dtype
        sa = self._seq.split_axis
        ca = self._streams_chunk_axis()

        def one(cl, split, concat):
            # Stage scope (obs/profile.py): the whole monolithic exchange
            # group — encode/collective/decode — attributes to the graph's
            # exchange:1 node (the wire layer nests its own sub-scopes).
            with obs.profile.stage_scope("slab", "exchange:1"):
                return all_to_all_transpose(cl, SLAB_AXIS, split, concat,
                                            realigned=realigned, wire=wire)

        if chunks is None and self._a2a_pipe_chunks() > 1:
            # ALL2ALL + SYNC/MPI_TYPE with a sub-block split: the
            # software-pipelined monolithic exchange (chunk k+1's
            # collective issued while chunk k decodes) along the one
            # free axis — opt0/opt1 overlap without switching to ring.
            pk = self._a2a_pipe_chunks()
            depth = self.config.resolved_overlap_depth()

            def piped(cl, split, concat):
                with obs.profile.stage_scope("slab", "exchange:1"):
                    return pipelined_all_to_all(
                        cl, SLAB_AXIS, split, concat, chunk_axis=ca,
                        chunks=pk, depth=depth, realigned=realigned,
                        wire=wire)

            return (lambda cl: piped(cl, sa, 0)), (lambda cl: piped(cl, 0, sa))

        if chunks is None or chunks <= 1:
            return (lambda cl: one(cl, sa, 0)), (lambda cl: one(cl, 0, sa))

        def chunked(cl, split, concat):
            return concat_axis_chunks(
                [one(p, split, concat)
                 for p in split_axis_chunks(cl, ca, chunks)], ca)

        return (lambda cl: chunked(cl, sa, 0)), (lambda cl: chunked(cl, 0, sa))

    def _fwd_parts(self):
        s, norm, g = self._seq, self.config.norm, self.global_size
        realigned = self.config.opt == 1
        be = self.config.fft_backend
        st = self._mxu_st
        split_pad, nx = self._split_pad, g.nx

        complex_mode = self.transform == "c2c"

        def first(xl):
            if complex_mode:
                c = lf.fft(xl, axis=s.r2c_axis, norm=norm, backend=be, settings=st)
            else:
                c = lf.rfft(xl, axis=s.r2c_axis, norm=norm, backend=be,
                            settings=st, paired=self.r2c_paired)
            for a in s.pre_axes:
                c = lf.fft(c, axis=a, norm=norm, backend=be, settings=st)
            return pad_axis_to(c, s.split_axis, split_pad)

        xpose = self._xpose_bodies(realigned)[0]

        def last(cl):
            # Drop the zero pad rows of x before transforming along it.
            c = slice_axis_to(cl, 0, nx)
            for a in s.post_axes:
                c = lf.fft(c, axis=a, norm=norm, backend=be, settings=st)
            return c

        # Stage scopes: the graph's local_fft:1 / local_fft:2 nodes
        # (metadata only — obs/profile.py attribution).
        return (obs.profile.scoped("slab", "local_fft:1", first), xpose,
                obs.profile.scoped("slab", "local_fft:2", last))

    def _inv_parts(self):
        s, norm, g = self._seq, self.config.norm, self.global_size
        realigned = self.config.opt == 1
        be = self.config.fft_backend
        st = self._mxu_st
        nx_pad, split_ext = self._nx_pad, self._split_ext
        real_n = g.nz if s.halved == "z" else g.ny
        complex_mode = self.transform == "c2c"

        def first(cl):
            c = cl
            for a in reversed(s.post_axes):
                c = lf.ifft(c, axis=a, norm=norm, backend=be, settings=st)
            return pad_axis_to(c, 0, nx_pad)

        xpose = self._xpose_bodies(realigned)[1]

        def last(cl):
            # Drop the pad lanes of the split axis before inverting along the
            # remaining axes.
            c = slice_axis_to(cl, s.split_axis, split_ext)
            for a in reversed(s.pre_axes):
                c = lf.ifft(c, axis=a, norm=norm, backend=be, settings=st)
            if complex_mode:
                return lf.ifft(c, axis=s.r2c_axis, norm=norm, backend=be, settings=st)
            return lf.irfft(c, n=real_n, axis=s.r2c_axis, norm=norm,
                            backend=be, settings=st, paired=self.c2r_paired)

        # Inverse graph numbering: its stage 1 (post-axis inverses) is
        # local_fft:1, its stage 2 (pre-axis + r2c inverses) local_fft:2.
        return (obs.profile.scoped("slab", "local_fft:1", first), xpose,
                obs.profile.scoped("slab", "local_fft:2", last))

    # -- STREAMS (chunked / software-pipelined) bodies ---------------------
    # The TPU rendering of the reference's Streams send engine (per-peer
    # packs on CUDA streams + callback thread + MPI_Isend,
    # src/slab/default/mpicufft_slab.cpp:343-448): split the local block
    # into K pieces along the one axis the exchange leaves free, and give
    # each piece its own transpose -> FFT chain. The K chains share no
    # data, so the scheduler may run piece i's collective concurrently
    # with piece i-1's FFT (async all-to-all-start/done pairs on TPU).
    # FFTs along the chunk axis itself cannot be chunked and run once on
    # the re-assembled block; separable DFT axes commute, so hoisting them
    # across the per-chunk transforms preserves the result exactly.

    def _streams_split(self):
        """(chunk_axis, chunks, per-chunk post axes, after-concat post
        axes) — the static plan of the STREAMS pipeline."""
        ca = self._streams_chunk_axis()
        k = self.config.resolved_streams_chunks()
        per_chunk = tuple(a for a in self._seq.post_axes if a != ca)
        after = tuple(a for a in self._seq.post_axes if a == ca)
        return ca, k, per_chunk, after

    def _streams_fwd_body(self):
        """Local forward body for ALL2ALL + STREAMS: first-stage FFTs, then
        K independent (transpose -> post-FFT) piece chains."""
        norm, g = self.config.norm, self.global_size
        be, st = self.config.fft_backend, self._mxu_st
        ca, k, per_chunk, after = self._streams_split()
        first = self._fwd_parts()[0]
        xpose = self._xpose_bodies()[0]
        nx = g.nx

        def body(xl):
            c = first(xl)
            outs = []
            for piece in split_axis_chunks(c, ca, k):
                y = xpose(piece)
                with obs.profile.stage_scope("slab", "local_fft:2"):
                    y = slice_axis_to(y, 0, nx)
                    for a in per_chunk:
                        y = lf.fft(y, axis=a, norm=norm, backend=be,
                                   settings=st)
                outs.append(y)
            with obs.profile.stage_scope("slab", "local_fft:2"):
                c = concat_axis_chunks(outs, ca)
                for a in after:
                    c = lf.fft(c, axis=a, norm=norm, backend=be, settings=st)
            return c

        return body

    def _streams_inv_body(self):
        """Local inverse body for ALL2ALL + STREAMS: mirror of
        ``_streams_fwd_body`` (chunk-axis inverse FFT first, then K
        independent (inverse-FFT -> transpose-back) piece chains, then the
        shared last stage)."""
        norm = self.config.norm
        be, st = self.config.fft_backend, self._mxu_st
        ca, k, per_chunk, after = self._streams_split()
        xpose_inv = self._xpose_bodies()[1]
        last = self._inv_parts()[2]
        nx_pad = self._nx_pad

        def body(cl):
            c = cl
            with obs.profile.stage_scope("slab", "local_fft:1"):
                for a in after:
                    c = lf.ifft(c, axis=a, norm=norm, backend=be,
                                settings=st)
            outs = []
            for piece in split_axis_chunks(c, ca, k):
                with obs.profile.stage_scope("slab", "local_fft:1"):
                    y = piece
                    for a in reversed(per_chunk):
                        y = lf.ifft(y, axis=a, norm=norm, backend=be,
                                    settings=st)
                    y = pad_axis_to(y, 0, nx_pad)
                outs.append(xpose_inv(y))
            return last(concat_axis_chunks(outs, ca))

        return body

    # -- RING / RING_OVERLAP (ppermute-pipelined) bodies -------------------
    # SendMethod.RING decomposes each transpose into P-1 DISTINCT
    # ``lax.ppermute`` steps (``parallel/transpose.ring_transpose``) and
    # runs the post-transpose FFT stages that do not touch the gathered
    # axis on each peer block AS IT ARRIVES — receiver-side pipelining,
    # the TPU analog of the reference Streams engine's per-peer
    # MPI_Isend/compute interleave. Unlike the STREAMS chunked collectives
    # (which GSPMD re-fuses — OVERLAP.md), the P-1 permutes carry
    # different data and cannot be merged, so the scheduler can genuinely
    # hide step t+1's wire time behind block t's FFT. The gathered-axis
    # FFT (always axis 0 on the slab forward) needs the assembled block
    # and runs after the ring drains, as does the shape-changing C2R
    # half-axis inverse.
    #
    # SendMethod.RING_OVERLAP runs the SAME per-block math on the
    # double-buffered schedule (ring_transpose(overlap=True): step t+1's
    # permute issued before block t's FFT — bit-identical output,
    # reordered issue), and Config.fused_wire swaps the per-block wire
    # boundary for the fused Pallas kernels (_ring_hooks below).

    def _ring_overlap(self, second: bool = False) -> bool:
        snd = (self.config.resolved_snd2() if second
               else self.config.send_method)
        return snd is pm.SendMethod.RING_OVERLAP

    def _ring_hooks(self, pipe_axes, inverse: bool = False):
        """``(encode_fn, arrive_fn, pipe)`` for a ring exchange whose
        arriving blocks run per-block FFTs over ``pipe_axes``: under the
        fused wire (``Config.fused_wire_active``) the encode is the
        one-pass Pallas pack and the arrival fuses the decode with the
        FIRST pipelined DFT stage (remaining axes run the plain pipe);
        otherwise ``(None, None, pipe)`` keeps the plain wire layer. The
        returned ``pipe`` is always the FULL per-block pipeline — the
        local block never touches the wire, so ring_transpose applies it
        unfused regardless."""
        pipe = self._ring_pipe(pipe_axes, inverse=inverse)
        if not self.config.fused_wire_active():
            return None, None, pipe
        from ..ops import pallas_fft as plf
        if not pipe_axes:
            # No pipelined per-block FFT: the shared unpack-only hooks
            # (the pencil/batched2d arrival).
            enc_fn, arr_fn = plf.fused_ring_hooks(self.config)
            return enc_fn, arr_fn, pipe
        from ..parallel.transpose import wire_complex_dtype
        cdt = wire_complex_dtype(self.config.double_prec)
        norm, st = self.config.norm, self._mxu_st
        first_ax, rest = pipe_axes[0], tuple(pipe_axes[1:])
        rest_pipe = self._ring_pipe(rest, inverse=inverse)

        def arrive(b):
            b = plf.decode_fft_fused(b, cdt, first_ax, inverse=inverse,
                                     norm=norm, settings=st)
            return rest_pipe(b) if rest_pipe is not None else b

        return plf.wire_encode_fused, arrive, pipe

    def _ring_pipe(self, axes, inverse: bool = False):
        """Shape-preserving per-block FFT pipeline over ``axes`` (None when
        empty — ring_transpose then skips the per-block stage)."""
        if not axes:
            return None
        norm, be, st = self.config.norm, self.config.fft_backend, self._mxu_st
        tf = lf.ifft if inverse else lf.fft

        def pipe(b):
            # The pipelined per-block FFTs belong to the graph's stage-2
            # local-FFT node even though they trace inside the ring
            # (innermost scope wins in attribution).
            with obs.profile.stage_scope("slab", "local_fft:2"):
                for a in axes:
                    b = tf(b, axis=a, norm=norm, backend=be, settings=st)
                return b

        return pipe

    def _ring_fwd_body(self):
        """Local forward body for SendMethod.RING: first-stage FFTs, then
        the ring-decomposed exchange with the non-gathered post-axis FFTs
        pipelined per arriving peer block (Z_Then_YX's y axis, Y_Then_ZX's
        z axis; ZY_Then_X's only post axis is the gathered x), then the
        gathered-axis FFT on the assembled block."""
        s, norm, g = self._seq, self.config.norm, self.global_size
        be, st = self.config.fft_backend, self._mxu_st
        first = self._fwd_parts()[0]
        enc_fn, arr_fn, pipe = self._ring_hooks(
            tuple(a for a in s.post_axes if a != 0))
        after = tuple(a for a in s.post_axes if a == 0)
        sa, nx = s.split_axis, g.nx
        wire = self.config.wire_dtype
        overlap = self._ring_overlap()
        depth = self.config.resolved_overlap_depth()
        subblocks = self.config.resolved_overlap_subblocks()

        def body(xl):
            with obs.profile.stage_scope("slab", "exchange:1"):
                y = ring_transpose(first(xl), SLAB_AXIS, sa, 0,
                                   pipeline_fn=pipe, wire=wire,
                                   overlap=overlap, depth=depth,
                                   subblocks=subblocks, encode_fn=enc_fn,
                                   arrive_fn=arr_fn)
            with obs.profile.stage_scope("slab", "local_fft:2"):
                y = slice_axis_to(y, 0, nx)
                for a in after:
                    y = lf.fft(y, axis=a, norm=norm, backend=be, settings=st)
            return y

        return body

    def _ring_inv_body(self):
        """Mirror of ``_ring_fwd_body``: the inverse exchange gathers the
        split axis, so the pipelined set is the last-stage C2C axes other
        than it (the C2C r2c-axis inverse where it is not the split axis);
        the shape-changing C2R transform always waits for assembly. Note
        the one rounding consequence in this PR: pipelining hoists that
        C2C r2c-axis IFFT ahead of the split-axis IFFT, so the c2c inverse
        agrees with the SYNC rendering to ~1e-15 RELATIVE rather than to
        the bit (every other path — bare ring, all forwards, r2c inverses
        — is bit-identical; tests/test_ring.py pins both levels)."""
        s, norm, g = self._seq, self.config.norm, self.global_size
        be, st = self.config.fft_backend, self._mxu_st
        first = self._inv_parts()[0]
        sa, split_ext = s.split_axis, self._split_ext
        real_n = g.nz if s.halved == "z" else g.ny
        complex_mode = self.transform == "c2c"
        pipe_axes = tuple(a for a in reversed(s.pre_axes) if a != sa)
        if complex_mode and s.r2c_axis != sa:
            pipe_axes = pipe_axes + (s.r2c_axis,)
        enc_fn, arr_fn, pipe = self._ring_hooks(pipe_axes, inverse=True)
        after = tuple(a for a in reversed(s.pre_axes) if a == sa)
        wire = self.config.wire_dtype
        overlap = self._ring_overlap()
        depth = self.config.resolved_overlap_depth()
        subblocks = self.config.resolved_overlap_subblocks()

        def body(cl):
            with obs.profile.stage_scope("slab", "exchange:1"):
                y = ring_transpose(first(cl), SLAB_AXIS, 0, sa,
                                   pipeline_fn=pipe, wire=wire,
                                   overlap=overlap, depth=depth,
                                   subblocks=subblocks, encode_fn=enc_fn,
                                   arrive_fn=arr_fn)
            with obs.profile.stage_scope("slab", "local_fft:2"):
                y = slice_axis_to(y, sa, split_ext)
                for a in after:
                    y = lf.ifft(y, axis=a, norm=norm, backend=be,
                                settings=st)
                if complex_mode:
                    if s.r2c_axis == sa:
                        y = lf.ifft(y, axis=s.r2c_axis, norm=norm,
                                    backend=be, settings=st)
                    return y
                return lf.irfft(y, n=real_n, axis=s.r2c_axis, norm=norm,
                                backend=be, settings=st,
                                paired=self.c2r_paired)

        return body

    # -- pipeline builders -------------------------------------------------

    def _build_r2c(self):
        with obs.span("plan.build", kind="slab", direction="forward",
                      sequence=self.sequence.value):
            if self.fft3d:
                return (self._fft3d_c2c(forward=True)
                        if self.transform == "c2c" else self._fft3d_r2c())
            return self._assemble(self._fwd_parts(), self._in_spec,
                                  self._out_spec, self.config.comm_method,
                                  forward=True)

    def _build_c2r(self):
        with obs.span("plan.build", kind="slab", direction="inverse",
                      sequence=self.sequence.value):
            if self.fft3d:
                return (self._fft3d_c2c(forward=False)
                        if self.transform == "c2c" else self._fft3d_c2r())
            return self._assemble(self._inv_parts(), self._out_spec,
                                  self._in_spec, self.config.comm_method,
                                  forward=False)

    def _assemble(self, parts, in_spec, out_spec, comm: pm.CommMethod,
                  forward: bool = True):
        """Compose (first, xpose, last) into one jitted program (the pure
        composition from ``_assemble_pure`` with in/out shardings). At
        guard modes check/enforce the program is the GUARDED pipeline
        ``x -> (y, stats)`` (``resilience/guards.py``: the Parseval/drift
        reductions traced into the same jit); at "off" it is byte-
        identical to the pre-guard program."""
        from ..resilience import guards
        pure = self._assemble_pure(parts, in_spec, out_spec, comm,
                                   forward=forward)
        mesh = self.mesh
        pure, guarded = guards.maybe_wrap(
            self, pure, "forward" if forward else "inverse")
        outsh = NamedSharding(mesh, out_spec)
        if guarded:
            outsh = (outsh, NamedSharding(mesh, PartitionSpec()))
        return jax.jit(pure, in_shardings=NamedSharding(mesh, in_spec),
                       out_shardings=outsh)

    def _assemble_pure(self, parts, in_spec, out_spec, comm: pm.CommMethod,
                       forward: bool = True):
        """Compose (first, xpose, last) into one pure callable.

        ALL2ALL: a single shard_map containing the explicit collective.
        PEER2PEER: two shard_map stages with the transpose omitted — the
        sharding change at the stage boundary makes XLA's SPMD partitioner
        insert and schedule the collective (its latency-hiding scheduler is
        the analog of the reference's Isend/Irecv + callback-thread overlap
        engine).

        ``SendMethod.STREAMS`` swaps in the chunked pipelined rendering:
        ALL2ALL uses the ``_streams_*_body`` per-piece chains — measured
        to genuinely emit K distinct ``all-to-all`` ops. PEER2PEER splits
        the stage boundary into per-piece reshards
        (``chunked_reshard``); MEASURED RESULT (8-device CPU mesh, k=4):
        GSPMD's partitioner re-fuses the piece reshards into ONE
        collective — identical HLO to SYNC — whether or not the stage-2
        FFT is interleaved per piece (it lowers constraint-of-slice as
        slice-of-reshard and CSEs the shared exchange). Under GSPMD
        delegation a chunked exchange cannot be forced; the explicit
        ALL2ALL rendering is the real chunked path, so a P2P+STREAMS
        config is an honest no-op rather than a mismeasured variant.

        ``SendMethod.RING`` / ``RING_OVERLAP`` render the exchange as the
        ``P-1``-step ``lax.ppermute`` ring (``_ring_fwd_body``/
        ``_ring_inv_body``; RING_OVERLAP on the double-buffered schedule).
        A ring is only expressible as an explicit shard_map program, so
        the ring renderings own the exchange regardless of ``comm``
        (params.py contract: GSPMD delegation has no ppermute analog)."""
        first, xpose, last = parts
        mesh = self.mesh
        if self.config.send_method.is_ring:
            body = self._ring_fwd_body() if forward else self._ring_inv_body()
            return jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                                 out_specs=out_spec)
        streams = self.config.send_method is pm.SendMethod.STREAMS
        if comm is pm.CommMethod.ALL2ALL:
            if streams:
                body = (self._streams_fwd_body() if forward
                        else self._streams_inv_body())
                return jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                                     out_specs=out_spec)
            return jax.shard_map(lambda xl: last(xpose(first(xl))), mesh=mesh,
                                 in_specs=in_spec, out_specs=out_spec)
        # PEER2PEER wire layer (wire_gspmd_stages): a compressed wire makes
        # stage1 emit the planar bf16 encoding and stage2 decode it, so
        # the GSPMD-inserted boundary collective moves the compressed
        # array; wire="native" is the unchanged pre-wire stage pair. Under
        # STREAMS the chunk axis shifts past the plane axis and the piece
        # reshards move the compressed planes (GSPMD re-fuses them either
        # way — the honest-no-op contract is unchanged, just half the
        # bytes).
        stage1, stage2, bspec, shift = wire_gspmd_stages(
            mesh, first, last, in_spec, out_spec, self.config.wire_dtype,
            self.config.double_prec)
        if not streams:
            return lambda x: stage2(stage1(x))
        ca, k, _, _ = self._streams_split()
        boundary = NamedSharding(mesh, bspec)
        ca = ca + shift

        def pure(x):
            with obs.profile.stage_scope("slab", "exchange:1"):
                y = chunked_reshard(stage1(x), boundary, ca, k)
            return stage2(y)

        return pure

    def forward_fn(self):
        """Pure forward pipeline (``DistFFTPlan.forward_fn`` contract).
        Cached per plan (a fresh closure per call would defeat the caller's
        jit cache); pads logical-shaped input like ``exec_r2c`` does, with
        a traced ``jnp.pad`` so the preamble stays differentiable."""
        if self._fwd_pure is None:
            if self.fft3d:
                pure = (self._fft3d_c2c(forward=True, jit=False)
                        if self.transform == "c2c"
                        else self._fft3d_r2c(jit=False))
            else:
                pure = self._assemble_pure(self._fwd_parts(), self._in_spec,
                                           self._out_spec,
                                           self.config.comm_method,
                                           forward=True)
            self._fwd_pure = _with_pad(pure, self.input_shape,
                                       self.input_padded_shape)
        return self._fwd_pure

    def inverse_fn(self):
        """Pure inverse pipeline (``DistFFTPlan.forward_fn`` contract)."""
        if self._inv_pure is None:
            if self.fft3d:
                pure = (self._fft3d_c2c(forward=False, jit=False)
                        if self.transform == "c2c"
                        else self._fft3d_c2r(jit=False))
            else:
                pure = self._assemble_pure(self._inv_parts(), self._out_spec,
                                           self._in_spec,
                                           self.config.comm_method,
                                           forward=False)
            self._inv_pure = _with_pad(pure, self.output_shape,
                                       self.output_padded_shape)
        return self._inv_pure

    # -- per-phase staged execution (benchmark timer support) --------------

    @property
    def variant_name(self) -> str:
        return {
            pm.SlabSequence.ZY_THEN_X: "slab_default",
            pm.SlabSequence.Z_THEN_YX: "slab_z_then_yx",
            pm.SlabSequence.Y_THEN_ZX: "slab_y_then_zx",
        }[self.sequence]

    @property
    def section_descriptions(self) -> List[str]:
        """Reference phase vocabulary for this sequence (slab default:
        include/mpicufft_slab.hpp:209-223; z_then_yx: :121-134; y_then_zx:
        :107-109). Phases that have no analog under XLA (pack/unpack/send
        bookkeeping) remain 0 in the CSV."""
        first, last = self._stage_descs()
        xpose = ["Transpose (First Send)", "Transpose (Packing)",
                 "Transpose (Start Local Transpose)", "Transpose (Start Receive)",
                 "Transpose (First Receive)", "Transpose (Finished Receive)",
                 "Transpose (Start All2All)", "Transpose (Finished All2All)",
                 "Transpose (Unpacking)"]
        # "Run complete (fused)" extends the reference vocabulary: the marker
        # after ONE extra call of the fused production program, so the CSV
        # carries both staged phase attribution and the true fused runtime
        # (fused = this mark minus the "Run complete" mark).
        if self.sequence is pm.SlabSequence.ZY_THEN_X:
            # The reference slab_default list carries an extra "2D FFT (Sync)"
            # marker before the 2D FFT row (mpicufft_slab.hpp:209-223).
            return ["init", "2D FFT (Sync)", first] + xpose + [
                last, "Run complete", "Run complete (fused)"]
        if self.sequence is pm.SlabSequence.Y_THEN_ZX:
            # y_then_zx has the short 9-entry list (mpicufft_slab_y_then_zx
            # .hpp:107-109): only P2P phases, no All2All markers.
            return ["init", first, "Transpose (First Send)",
                    "Transpose (Packing)", "Transpose (Start Local Transpose)",
                    "Transpose (Start Receive)", "Transpose (Finished Receive)",
                    last, "Run complete", "Run complete (fused)"]
        return ["init", first] + xpose + [last, "Run complete",
                                          "Run complete (fused)"]

    def _stage_descs(self) -> Tuple[str, str]:
        return {
            pm.SlabSequence.ZY_THEN_X: ("2D FFT Y-Z-Direction", "1D FFT X-Direction"),
            pm.SlabSequence.Z_THEN_YX: ("1D FFT Z-Direction", "2D FFT Y-X-Direction"),
            pm.SlabSequence.Y_THEN_ZX: ("1D FFT Y-Direction", "2D FFT Z-X-Direction"),
        }[self.sequence]

    def _xpose_desc(self) -> str:
        # y_then_zx's short reference list has no All2All markers (it is
        # hardcoded Peer2Peer there); keep its transpose time under the
        # receive marker for either comm method.
        if self.sequence is pm.SlabSequence.Y_THEN_ZX:
            return "Transpose (Finished Receive)"
        return ("Transpose (Finished All2All)"
                if self.config.comm_method is pm.CommMethod.ALL2ALL
                else "Transpose (Finished Receive)")

    def forward_stages(self):
        """[(phase desc, jitted stage fn)] for per-phase timed execution.
        Always uses the explicit collective (timing needs a materialization
        boundary); the fused exec path is unaffected."""
        if self.fft3d:
            return [(None, self._exec_fwd)]
        first, xpose, last = self._fwd_parts()
        d1, d2 = self._stage_descs()
        return self._jit_stages(
            [(d1, first, self._in_spec, self._in_spec),
             (self._xpose_desc(), xpose, self._in_spec, self._out_spec),
             (d2, last, self._out_spec, self._out_spec)])

    def inverse_stages(self):
        if self.fft3d:
            return [(None, self._exec_inv)]
        first, xpose, last = self._inv_parts()
        d1, d2 = self._stage_descs()
        return self._jit_stages(
            [(d2, first, self._out_spec, self._out_spec),
             (self._xpose_desc(), xpose, self._out_spec, self._in_spec),
             (d1, last, self._in_spec, self._in_spec)])




# ---------------------------------------------------------------------------
# contract declaration (analysis/contracts.py) — the exchange this family
# stages, declared next to the code that stages it so the verifier and the
# pipeline cannot drift apart.
# ---------------------------------------------------------------------------

def _contract_exchanges(plan, direction, dims=3):
    """Slab: one symmetric global exchange per direction (scatter the
    sequence's split axis, gather x), payload = the padded spectral
    volume. The single-device fallback stages none. The payload and
    rendering are direction-symmetric; only the ring sub-block split
    depends on ``direction`` (the concat axis — and hence the extent
    the split clamps to — flips with it)."""
    del dims
    if plan.fft3d:
        return ()
    from ..analysis import contracts as _c
    cfg = plan.config
    rendering = _c.rendering_name(cfg)
    # The exchanged block carries BOTH paddings: the split axis padded to
    # the mesh (output_padded_shape) AND x padded to nx_pad — the forward
    # `last` stage slices x back to nx only after the exchange.
    payload = list(plan.output_padded_shape)
    payload[0] = plan._nx_pad
    chunks = 1
    subblocks = 1
    if rendering == "streams":
        # chunk_slices clamps the piece count to the free-axis extent at
        # trace time; mirror it so the expected all-to-all count is exact.
        ca = plan._streams_chunk_axis()
        chunks = min(cfg.resolved_streams_chunks(), payload[ca])
    elif rendering == "a2a_pipe":
        chunks = plan._a2a_pipe_chunks()
    elif rendering in ("ring", "ring_overlap"):
        # The sub-block split slices arriving blocks along the concat
        # axis (forward gathers x = axis 0, inverse gathers the split
        # axis); ring_subblocks applies the same trace-time clamp as
        # ring_transpose, on the LOCAL (per-rank) extent.
        c = 0 if direction == "forward" else plan._seq.split_axis
        subblocks = ring_subblocks(payload[c] // plan._P,
                                   cfg.resolved_overlap_subblocks())
    return (_c.ExchangeDecl("transpose", tuple(payload),
                            plan._P, rendering, chunks,
                            subblocks=subblocks),)


def _declare_graph(plan, direction, dims=3):
    """Slab stage graph (analysis/plangraph.py): stage-1 local FFTs
    (the sequence's R2C axis + pre axes) -> one symmetric exchange
    (encode/decode around it under a compressed wire; fused Pallas
    kernels when ``Config.fused_wire`` is active) -> stage-2 local FFTs
    (post axes) -> guard (modes check/enforce). The single-device
    fallback is one fused local-FFT node."""
    from ..analysis import plangraph as _pg
    cfg = plan.config
    c2c = plan.transform == "c2c"
    cdt, rdt = _pg.payload_dtypes(cfg, plan.transform)
    fwd = direction == "forward"
    b = _pg.GraphBuilder("slab", direction, wire=cfg.wire_dtype,
                         guards=plan._guard_mode, complex_dtype=cdt)
    in_shape = plan.input_padded_shape if fwd else plan.output_padded_shape
    out_shape = plan.output_padded_shape if fwd else plan.input_padded_shape
    in_dtype, out_dtype = (rdt, cdt) if fwd else (cdt, rdt)
    b.node("input")
    b.payload(in_shape, in_dtype,
              plan.input_spec if fwd else plan.output_spec)
    if plan.fft3d:
        b.node("local_fft", axes=(2, 1, 0) if fwd else (0, 1, 2),
               label="fft3d")
        b.payload(out_shape, out_dtype, "")
    else:
        s = plan._seq
        (decl,) = _contract_exchanges(plan, direction, dims)
        if fwd:
            stage1 = (s.r2c_axis,) + s.pre_axes
            stage2 = s.post_axes
            pipe_axes = tuple(a for a in s.post_axes if a != 0)
        else:
            stage1 = tuple(reversed(s.post_axes))
            stage2 = tuple(reversed(s.pre_axes)) + (s.r2c_axis,)
            pipe_axes = tuple(a for a in reversed(s.pre_axes)
                              if a != s.split_axis)
            if c2c and s.r2c_axis != s.split_axis:
                pipe_axes += (s.r2c_axis,)
        b.node("local_fft", axes=stage1, label="stage 1")
        depth = _pg.shipped_schedule_depth(decl.rendering, cfg)
        fused = cfg.fused_wire_active()
        spec_after = plan.output_spec if fwd else plan.input_spec
        b.exchange(decl.label, decl.payload_shape, decl.axis_size,
                   decl.rendering, chunks=decl.chunks,
                   subblocks=decl.subblocks,
                   schedule_depth=depth, decoded_spec=spec_after,
                   fused_encode=fused,
                   decode_fuses=(("decode", "fft") if pipe_axes
                                 else ("decode",)) if fused else None)
        b.node("local_fft", axes=stage2, label="stage 2")
        b.payload(out_shape, out_dtype, spec_after)
    if plan._guard_mode != "off":
        b.node("guard")
    b.node("output")
    return b.graph()


def _register_contracts():
    from ..analysis import contracts as _c
    from ..analysis import plangraph as _pg
    _c.register_family("slab", "SlabFFTPlan", _contract_exchanges)
    _pg.register_graph_family("slab", _declare_graph)


_register_contracts()
