"""Batched distributed 2D FFT — the convolution-workload plan.

BASELINE config #4 ("Batched 2D FFT 4096^2 x 64, 1D mesh") stresses an axis
the reference never tested (SURVEY §7 hard parts: "plan the planner API to
allow batch dims from day 1"). Arrays are ``(batch, nx, ny)``; the transform
runs over (x, y) with ``batch`` as a pure batch dimension (cuFFT "batched
plan" analog — the reference reaches batching only through cufftMakePlanMany
batch counts, e.g. ``src/slab/default/mpicufft_slab.cpp:154-167``).

Two decompositions over a 1D mesh:

* ``shard="batch"`` — embarrassingly parallel: the batch axis is sharded,
  each device transforms its images locally, zero collectives. The right
  choice whenever ``batch >= P``.
* ``shard="x"`` — slab-style: x sharded, 1D FFT y -> all_to_all transpose
  -> 1D FFT x, for batches too small to fill the mesh or images too large
  for one device.

Same padded-shape contract and comm-method mapping as the 3D engines.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint
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
from .base import (_with_pad, jit_stages, local_fft_fields,
                   notice_axis_smoothness, r2c_pairing, c2r_pairing)

# A TPU's float32 tile is 8 sublanes by 128 lanes. The chunk loop's
# group buffers (``_map_in_tile_groups``) keep their images outermost, the
# spectrum's rows in the sublanes and x in the lanes, so that each image
# fills whole tiles.
_SUBLANES = 8
_BATCH_MAJOR = Layout(major_to_minor=(0, 2, 1))


class Batched2DFFTPlan:
    """Distributed batched 2D R2C/C2R (or C2C) FFT over a 1D mesh."""

    def __init__(self, batch: int, nx: int, ny: int,
                 partition: pm.SlabPartition,
                 config: Optional[pm.Config] = None,
                 mesh: Optional[Mesh] = None,
                 shard: str = "batch", transform: str = "r2c",
                 batch_chunk: Optional[int] = None):
        """``batch_chunk``: transform the (per-device) batch in sequential
        chunks of THIS SIZE in a loop instead of one fused program
        (``batch_chunk=1`` = per-plane slices, the most chunked form;
        ``None``/0 = whole stack fused). Caps the peak intermediate
        footprint and the compiled program size. On one v5e at
        4096^2 x 48 (float32, xla local FFT, input and spectrum live
        through the C2R) a roundtrip took 424 ms at ``batch_chunk=1``,
        495 at 2 and 556 at 4; the fused R2C does not fit (PERF.md,
        cell ``batched2d4096.roundtrip``). Only meaningful when
        the batch axis is a pure batch dimension (``shard='batch'`` or the
        single-process fallback); must divide the (local, padded) batch.

        On a TPU the float32 forward with a chunk that divides 8 and a
        local batch that 8 divides writes its answers into the stack in
        groups of 8 images (``chunk_group``), so that every write fills
        whole (8,128) tiles; see ``_chunked``. That took the roundtrip
        above from 423.4 ms to 331-333 (PERF.md)."""
        if shard not in ("batch", "x"):
            raise ValueError(f"shard must be 'batch' or 'x', got {shard!r}")
        if transform not in ("r2c", "c2c"):
            raise ValueError(f"transform must be 'r2c' or 'c2c', got {transform!r}")
        if batch <= 0 or nx <= 0 or ny <= 0:
            raise ValueError("batch/nx/ny must be positive")
        if batch_chunk == 0:
            batch_chunk = None  # documented alias: 0 = whole stack fused
        # Wisdom resolution of "auto" Config fields (see SlabFFTPlan);
        # shard='batch' issues no collectives, so its comm "auto" resolves
        # to the defaults without a race.
        config = wisdom.resolve_config(
            "batched2d", pm.GlobalSize(batch, nx, ny), partition, config,
            mesh=mesh, transform=transform, dims=2, variant=shard)
        if mesh is None and partition.p > 1:
            mesh = make_slab_mesh(partition.p)
        if mesh is not None and partition.p > 1 \
                and mesh.shape.get(SLAB_AXIS) != partition.p:
            raise ValueError(
                f"mesh axis {SLAB_AXIS!r} must have {partition.p} devices")
        self.batch, self.nx, self.ny = batch, nx, ny
        self.partition = partition
        self.config = config or pm.Config()
        # Settings snapshot at construction (see DistFFTPlan.__init__).
        self._mxu_st = self.config.mxu_settings()
        # Resilience state (DistFFTPlan contract — this plan sits outside
        # that hierarchy but honors the same guard/fallback envelope).
        from ..resilience import guards as _guards
        self._guard_mode = _guards.resolved_mode(self.config)
        self._guard_state = {}
        self.mesh = mesh
        self.shard = shard
        self.transform = transform
        self.fft3d = mesh is None or partition.p == 1
        P = partition.p
        self._P = P
        self._ny_spec = ny if transform == "c2c" else ny // 2 + 1
        if self.fft3d:
            self._batch_pad, self._nx_pad, self._nys_pad = batch, nx, self._ny_spec
            self._in_spec = self._out_spec = PartitionSpec()
        elif shard == "batch":
            self._batch_pad = pm.padded_extent(batch, P)
            self._nx_pad, self._nys_pad = nx, self._ny_spec
            self._in_spec = PartitionSpec(SLAB_AXIS, None, None)
            self._out_spec = PartitionSpec(SLAB_AXIS, None, None)
        else:
            self._batch_pad = batch
            self._nx_pad = pm.padded_extent(nx, P)
            self._nys_pad = pm.padded_extent(self._ny_spec, P)
            self._in_spec = PartitionSpec(None, SLAB_AXIS, None)
            self._out_spec = PartitionSpec(None, None, SLAB_AXIS)
        self.batch_chunk = batch_chunk
        local_b = self._batch_pad if self.fft3d else self._batch_pad // P
        if batch_chunk is not None:
            if batch_chunk <= 0:
                raise ValueError("batch_chunk must be positive")
            if not (self.fft3d or shard == "batch"):
                raise ValueError("batch_chunk requires shard='batch' (or "
                                 "the single-process fallback): with "
                                 "shard='x' the batch axis is not chunkable "
                                 "independently of the collectives")
            if local_b % batch_chunk:
                raise ValueError(
                    f"batch_chunk {batch_chunk} must divide the local "
                    f"padded batch {local_b}")
        self._fwd = None
        self._inv = None
        self._fwd_unguarded = None  # staged path under guard modes
        self._inv_unguarded = None
        self._fwd_pure = None
        self._inv_pure = None
        self.chunk_group = None
        if (batch_chunk and _SUBLANES % batch_chunk == 0
                and batch_chunk < local_b and local_b % _SUBLANES == 0
                and not self.config.double_prec
                and wisdom.plan_platform(mesh) == "tpu"):
            self.chunk_group = _SUBLANES
        rows = ((batch_chunk or local_b) * nx if self.fft3d or shard == "batch"
                else batch * (self._nx_pad // P))
        self.r2c_paired = transform == "r2c" and r2c_pairing(
            self.config, mesh, ny, rows)
        self.c2r_paired = transform == "r2c" and c2r_pairing(
            self.config, mesh, ny, rows)
        notice_axis_smoothness("batched2d", (nx, ny), self.config)
        obs.event("plan.created", kind="batched2d", shard=shard,
                  transform=transform, shape=[batch, nx, ny], ranks=P,
                  batch_chunk=batch_chunk, chunk_group=self.chunk_group,
                  r2c_paired=self.r2c_paired, c2r_paired=self.c2r_paired,
                  comm=self.config.comm_method.value,
                  send=self.config.send_method.value, opt=self.config.opt,
                  wire=self.config.wire_dtype,
                  **local_fft_fields(self.config, self._mxu_st))

    # -- shapes ------------------------------------------------------------

    @property
    def input_shape(self) -> Tuple[int, int, int]:
        return (self.batch, self.nx, self.ny)

    @property
    def input_padded_shape(self) -> Tuple[int, int, int]:
        # batch-sharded pads batch; x-sharded pads x; single-device neither.
        return (self._batch_pad, self._nx_pad, self.ny)

    @property
    def output_shape(self) -> Tuple[int, int, int]:
        return (self.batch, self.nx, self._ny_spec)

    @property
    def output_padded_shape(self) -> Tuple[int, int, int]:
        if self.fft3d or self.shard == "batch":
            return (self._batch_pad, self.nx, self._ny_spec)
        return (self.batch, self.nx, self._nys_pad)

    @property
    def input_sharding(self) -> Optional[NamedSharding]:
        return None if self.mesh is None else NamedSharding(self.mesh, self._in_spec)

    @property
    def output_sharding(self) -> Optional[NamedSharding]:
        return None if self.mesh is None else NamedSharding(self.mesh, self._out_spec)

    # -- pad/crop ----------------------------------------------------------

    def pad_input(self, x):
        tgt = self.input_padded_shape
        pads = [(0, tgt[i] - s) for i, s in enumerate(x.shape)]
        if any(p[1] for p in pads):
            x = jnp.pad(x, pads)
        if self.mesh is not None:
            x = jax.device_put(x, self.input_sharding)
        return x

    def pad_spectral(self, c):
        """Logical spectral array -> padded, device-placed output layout
        (same helper pair as the 3D plans)."""
        tgt = self.output_padded_shape
        pads = [(0, t - s) for t, s in zip(tgt, c.shape)]
        if any(p[1] for p in pads):
            c = jnp.pad(c, pads)
        if self.mesh is not None:
            c = jax.device_put(c, self.output_sharding)
        return c

    def crop_spectral(self, c) -> np.ndarray:
        return np.asarray(c)[: self.batch, : self.nx, : self._ny_spec]

    def crop_real(self, r) -> np.ndarray:
        return np.asarray(r)[: self.batch, : self.nx, : self.ny]

    # -- execution ---------------------------------------------------------

    def exec_forward(self, x):
        """Batched 2D forward transform over (x, y)."""
        if tuple(x.shape) not in (self.input_shape, self.input_padded_shape):
            raise ValueError(
                f"expected {self.input_shape} (or padded "
                f"{self.input_padded_shape}), got {tuple(x.shape)}")
        if tuple(x.shape) == self.input_shape \
                and self.input_shape != self.input_padded_shape:
            x = self.pad_input(x)
        from ..resilience import fallback
        return fallback.execute(self, "forward", x, self._get_fwd, dims=2)

    def exec_inverse(self, c):
        """Batched 2D inverse transform."""
        if tuple(c.shape) not in (self.output_shape, self.output_padded_shape):
            raise ValueError(
                f"expected {self.output_shape} (or padded "
                f"{self.output_padded_shape}), got {tuple(c.shape)}")
        if tuple(c.shape) == self.output_shape \
                and self.output_shape != self.output_padded_shape:
            c = self.pad_spectral(c)
        from ..resilience import fallback
        return fallback.execute(self, "inverse", c, self._get_inv, dims=2)

    def _get_fwd(self):
        if self._fwd is None:
            self._fwd = self._build(forward=True)
        return self._fwd

    def _get_inv(self):
        if self._inv is None:
            self._inv = self._build(forward=False)
        return self._inv

    # -- solver protocol (models/base.py contract; this plan sits outside
    #    the DistFFTPlan hierarchy but honors the identical surface) -------

    @property
    def transform_axes(self) -> Tuple[int, ...]:
        """The 2D transform covers (x, y); axis 0 is a pure batch
        dimension the solver suite broadcasts its symbols over."""
        return (1, 2)

    @property
    def transform_size(self) -> int:
        """N of the per-plane 2D transform (nx*ny; the batch axis carries
        no normalization — ``DistFFTPlan.transform_size`` contract)."""
        return self.nx * self.ny

    @property
    def spectral_halved_axis(self) -> Optional[int]:
        return None if self.transform == "c2c" else 2

    def exec_fwd(self, x):
        """Solver-protocol forward (``DistFFTPlan.exec_fwd`` contract)."""
        return self.exec_forward(x)

    def exec_inv(self, c):
        return self.exec_inverse(c)

    # -- resilience hooks (guards + fallback ladder) -----------------------

    def _guard_spec(self, direction: str, dims: int = 2):
        """GuardSpec of the batched-2D pipelines (slab contract): the
        transform covers (x, y) of every plane, so the Parseval volume is
        ``nx * ny`` and the R2C halved axis is the last slot."""
        from ..resilience.guards import GuardSpec
        norm = self.config.norm
        n = float(self.nx * self.ny)
        c2c = self.transform == "c2c"
        if direction == "forward":
            return GuardSpec(
                direction="forward", check="parseval",
                scale=1.0 if norm is pm.FFTNorm.ORTHO else n,
                in_logical=self.input_shape,
                out_logical=self.output_shape,
                halved_axis=None if c2c else 2,
                halved_n=0 if c2c else self.ny)
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
        return {"kind": "batched2d", "variant": self.shard,
                "transform": self.transform, "dims": 2}

    # -- builders ----------------------------------------------------------

    def _fft2(self, x, forward: bool):
        norm, be = self.config.norm, self.config.fft_backend
        st = self._mxu_st
        if forward:
            if self.transform == "c2c":
                c = lf.fft(x, axis=2, norm=norm, backend=be, settings=st)
            else:
                c = lf.rfft(x, axis=2, norm=norm, backend=be, settings=st,
                            paired=self.r2c_paired)
            return lf.fft(c, axis=1, norm=norm, backend=be, settings=st)
        c = lf.ifft(x, axis=1, norm=norm, backend=be, settings=st)
        if self.transform == "c2c":
            return lf.ifft(c, axis=2, norm=norm, backend=be, settings=st)
        return lf.irfft(c, n=self.ny, axis=2, norm=norm, backend=be,
                        settings=st, paired=self.c2r_paired)

    def _chunked(self, base, group: Optional[int] = None):
        """Wrap a whole-(local-)batch transform in a sequential ``lax.map``
        over ``batch_chunk``-sized slices (see __init__). The loop sits
        under the stage scope ``dfft/batched2d/chunk:1``, which no graph
        node declares: the transform's ops keep their innermost
        ``local_fft:1.ax<k>`` scopes, so only the per-chunk slices, the
        writes of each answer into the stack and the loop itself carry
        it (metadata only).

        ``group`` (``chunk_group``: the forward on a TPU, ``_build_pure``)
        maps over groups of that many images instead, with a complex64
        answer, see ``_map_in_tile_groups``. Why: the TPU compiler lays
        the stack out as the program's result, with the batch in the
        sublanes (a 48 x 4096^2 R2C's stack was
        ``f32[48,1,4096,2049]{2,0,3,1}``: 2049 is no multiple of 128, 48
        is one of 8), so writing one image touched one sublane of every
        (8,128) tile, 1.11 ms an image-plane on a v5e where its bytes
        need 0.082 (PERF.md). A batch-major result would avoid that, but
        a layout other than the default at a program's boundary does not
        survive JAX's persistent compile cache: a consumer loaded from it
        expects the default layout and refuses the buffer."""
        ck = self.batch_chunk
        if not ck:
            return base

        def fn(x):
            if x.shape[0] <= ck:
                return base(x)
            with obs.profile.stage_scope("batched2d", "chunk:1"):
                if group:
                    ans = jax.eval_shape(base, x[:ck])
                    if ans.dtype == jnp.complex64:
                        return _map_in_tile_groups(base, x, ck, group, ans)
                xs = x.reshape((x.shape[0] // ck, ck) + x.shape[1:])
                ys = jax.lax.map(base, xs)
                return ys.reshape((x.shape[0],) + ys.shape[2:])

        return fn

    def _build(self, forward: bool, guard: bool = True):
        with obs.span("plan.build", kind="batched2d", shard=self.shard,
                      direction="forward" if forward else "inverse"):
            from ..resilience import guards
            direction = "forward" if forward else "inverse"
            pure, in_spec, out_spec = self._build_pure(
                forward, self.chunk_group if forward else None)
            guarded = False
            if guard:
                pure, guarded = guards.maybe_wrap(self, pure, direction,
                                                  dims=2)
            if self.mesh is None:
                return jax.jit(pure)
            outsh = NamedSharding(self.mesh, out_spec)
            if guarded:
                outsh = (outsh, NamedSharding(self.mesh, PartitionSpec()))
            return jax.jit(pure,
                           in_shardings=NamedSharding(self.mesh, in_spec),
                           out_shardings=outsh)

    def _build_pure(self, forward: bool, group: Optional[int] = None):
        """(pure_fn, in_spec, out_spec) — the specs travel with the
        composition so the jit wrapper cannot drift from the shard_map.
        ``group``: see ``_chunked``; the jit that ``_build`` makes passes
        it, the pure composition (``forward_fn``) does not."""
        if self.fft3d or self.shard == "batch":
            # Stage scope (obs/profile.py): the collective-free graph's
            # one local_fft node covers the whole per-plane 2D transform.
            fn = self._chunked(obs.profile.scoped(
                "batched2d", "local_fft:1",
                lambda x: self._fft2(x, forward)), group)
            if self.mesh is None:
                return fn, PartitionSpec(), PartitionSpec()
            return (jax.shard_map(fn, mesh=self.mesh, in_specs=self._in_spec,
                                  out_specs=self._out_spec),
                    self._in_spec, self._out_spec)
        return self._build_slab_pure(forward)

    def forward_fn(self):
        """Pure forward pipeline (``DistFFTPlan.forward_fn`` contract: no
        jit, no sharding annotations — composes under user grad/jit).
        Cached; pads logical-shaped input inside the trace."""
        if self._fwd_pure is None:
            self._fwd_pure = _with_pad(self._build_pure(True)[0],
                                       self.input_shape,
                                       self.input_padded_shape)
        return self._fwd_pure

    def inverse_fn(self):
        """Pure inverse pipeline (see ``forward_fn``)."""
        if self._inv_pure is None:
            self._inv_pure = _with_pad(self._build_pure(False)[0],
                                       self.output_shape,
                                       self.output_padded_shape)
        return self._inv_pure

    def _slab_parts(self, forward: bool):
        """(first, xpose, last) stage closures of the shard='x' pipeline —
        composed fused by ``_build_slab_pure``, jitted individually by
        ``forward_stages``/``inverse_stages`` for per-phase timing."""
        norm, be = self.config.norm, self.config.fft_backend
        st = self._mxu_st
        realigned = self.config.opt == 1
        wire = self.config.wire_dtype
        nys_pad, nx_pad = self._nys_pad, self._nx_pad
        nx, ny, nys = self.nx, self.ny, self._ny_spec
        complex_mode = self.transform == "c2c"

        if forward:
            def first(xl):  # (B, nxb, ny)
                if complex_mode:
                    c = lf.fft(xl, axis=2, norm=norm, backend=be, settings=st)
                else:
                    c = lf.rfft(xl, axis=2, norm=norm, backend=be,
                                settings=st, paired=self.r2c_paired)
                return pad_axis_to(c, 2, nys_pad)

            def xpose(c):
                return all_to_all_transpose(c, SLAB_AXIS, 2, 1,
                                            realigned=realigned, wire=wire)

            def last(c):
                c = slice_axis_to(c, 1, nx)
                return lf.fft(c, axis=1, norm=norm, backend=be, settings=st)
        else:
            def first(cl):  # (B, nx, nysb)
                c = lf.ifft(cl, axis=1, norm=norm, backend=be, settings=st)
                return pad_axis_to(c, 1, nx_pad)

            def xpose(c):
                return all_to_all_transpose(c, SLAB_AXIS, 1, 2,
                                            realigned=realigned, wire=wire)

            def last(c):
                c = slice_axis_to(c, 2, nys)
                if complex_mode:
                    return lf.ifft(c, axis=2, norm=norm, backend=be,
                                   settings=st)
                return lf.irfft(c, n=ny, axis=2, norm=norm, backend=be,
                                settings=st, paired=self.c2r_paired)
        # Stage scopes (obs/profile.py): the shard='x' graph's nodes.
        sc = obs.profile.scoped
        return (sc("batched2d", "local_fft:1", first),
                sc("batched2d", "exchange:1", xpose),
                sc("batched2d", "local_fft:2", last))

    def _build_slab_pure(self, forward: bool):
        """shard='x': 1D FFT y -> transpose (x-split -> y-split) -> 1D FFT x,
        the 2D restriction of the slab ZY_Then_X pipeline.

        Comm-method mapping follows ``SlabFFTPlan._assemble_pure``: ALL2ALL
        is one shard_map with the explicit collective; PEER2PEER omits it —
        two shard_map stages whose boundary sharding change makes XLA's
        SPMD partitioner insert and schedule the collective. (Without this
        split the sweep's comm axis would compare two runs of the same
        program.)

        ``SendMethod.STREAMS`` chunks along the batch axis (the one axis
        the 2D transform and the transpose both leave untouched) into K
        independent exchange->FFT piece chains, exactly like the slab
        engine's pipelined rendering.

        ``SendMethod.RING`` / ``RING_OVERLAP`` render the exchange as the
        ``P-1``-step ``lax.ppermute`` ring (``ring_transpose``;
        RING_OVERLAP on the double-buffered schedule) — owning the
        rendering regardless of ``comm_method``, the slab contract. The
        post-transpose FFT runs along the gathered axis, so no per-block
        compute is pipelined; ``last`` runs on the assembled block (the
        fused wire therefore uses the unpack-only arrival kernel)."""
        first, xpose, last = self._slab_parts(forward)
        mesh = self.mesh
        if forward:
            in_spec, out_spec = self._in_spec, self._out_spec
        else:
            in_spec, out_spec = self._out_spec, self._in_spec
        wire = self.config.wire_dtype
        if self.config.send_method.is_ring:
            split, concat = (2, 1) if forward else (1, 2)
            overlap = self.config.send_method is pm.SendMethod.RING_OVERLAP
            depth = self.config.resolved_overlap_depth()
            subblocks = self.config.resolved_overlap_subblocks()
            from ..ops import pallas_fft as plf
            enc_fn, arr_fn = plf.fused_ring_hooks(self.config)

            def rbody(v):
                with obs.profile.stage_scope("batched2d", "exchange:1"):
                    y = ring_transpose(first(v), SLAB_AXIS, split,
                                       concat, wire=wire,
                                       overlap=overlap, depth=depth,
                                       subblocks=subblocks,
                                       encode_fn=enc_fn,
                                       arrive_fn=arr_fn)
                return last(y)

            return (jax.shard_map(rbody, mesh=mesh, in_specs=in_spec,
                                  out_specs=out_spec),
                    in_spec, out_spec)
        streams = self.config.send_method is pm.SendMethod.STREAMS
        k = self.config.resolved_streams_chunks()
        if self.config.comm_method is pm.CommMethod.ALL2ALL:
            if streams:
                def body(v):
                    c = first(v)
                    return concat_axis_chunks(
                        [last(xpose(p))
                         for p in split_axis_chunks(c, 0, k)], 0)
            elif self.config.resolved_overlap_subblocks() > 1:
                # a2a_pipe: the software-pipelined monolithic exchange,
                # chunked along the untouched batch axis (chunk k+1's
                # collective issued while chunk k decodes).
                split, concat = (2, 1) if forward else (1, 2)
                realigned = self.config.opt == 1
                pk = self.config.resolved_overlap_subblocks()
                depth = self.config.resolved_overlap_depth()

                def body(v):
                    with obs.profile.stage_scope("batched2d", "exchange:1"):
                        y = pipelined_all_to_all(
                            first(v), SLAB_AXIS, split, concat,
                            chunk_axis=0, chunks=pk, depth=depth,
                            realigned=realigned, wire=wire)
                    return last(y)
            else:
                def body(v):
                    return last(xpose(first(v)))
            return (jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                                  out_specs=out_spec),
                    in_spec, out_spec)
        # PEER2PEER wire layer (wire_gspmd_stages, the slab contract): a
        # compressed wire makes stage1 emit the planar bf16 encoding and
        # stage2 decode it, so the GSPMD boundary collective moves half
        # the bytes; "native" is the unchanged pre-wire stage pair. The
        # STREAMS batch chunk axis (0) shifts past the plane axis.
        stage1, stage2, bspec, shift = wire_gspmd_stages(
            mesh, first, last, in_spec, out_spec, wire,
            self.config.double_prec)
        if streams:
            boundary = NamedSharding(mesh, bspec)
            ca = shift

            def pure(v):
                with obs.profile.stage_scope("batched2d", "exchange:1"):
                    y = chunked_reshard(stage1(v), boundary, ca, k)
                return stage2(y)

            return pure, in_spec, out_spec
        return (lambda v: stage2(stage1(v)), in_spec, out_spec)

    # -- per-phase staged execution (benchmark timer support; same hooks
    #    as the 3D engines so testcases/Timer/eval reach this plan) -------

    @property
    def global_size(self) -> pm.GlobalSize:
        """(batch, nx, ny) mapped onto the 3-slot size schema of the Timer
        CSV filenames and testcases. The halved spectral axis is ny (the
        last slot), so ``nz_out`` equals ``self._ny_spec`` for r2c — the
        batched plan is structurally the 3D schema with batch riding the
        first slot and no transform along it."""
        return pm.GlobalSize(self.batch, self.nx, self.ny)

    @property
    def variant_name(self) -> str:
        """Chunked runs get their own variant directory: the reference
        filename schema has no chunk slot, and mixing chunked/unchunked
        blocks in one CSV would read as iterations of one config."""
        base = f"batched2d_{self.shard}"
        return f"{base}_ck{self.batch_chunk}" if self.batch_chunk else base

    @property
    def section_descriptions(self):
        """Phase vocabulary: the slab transpose marker set for shard='x'
        (same CSV columns the eval layer already reduces), one fused-2D
        marker for the collective-free batch sharding."""
        if self.fft3d or self.shard == "batch":
            return ["init", "2D FFT X-Y-Direction", "Run complete",
                    "Run complete (fused)"]
        xpose = ["Transpose (First Send)", "Transpose (Packing)",
                 "Transpose (Start Local Transpose)",
                 "Transpose (Start Receive)", "Transpose (First Receive)",
                 "Transpose (Finished Receive)", "Transpose (Start All2All)",
                 "Transpose (Finished All2All)", "Transpose (Unpacking)"]
        return ["init", "1D FFT Y-Direction"] + xpose + [
            "1D FFT X-Direction", "Run complete", "Run complete (fused)"]

    def _xpose_desc(self) -> str:
        return ("Transpose (Finished All2All)"
                if self.config.comm_method is pm.CommMethod.ALL2ALL
                else "Transpose (Finished Receive)")

    def _jit_stages(self, specs):
        return jit_stages(self.mesh, specs)

    def forward_stages(self):
        """[(phase desc, jitted stage fn)] for per-phase timed execution
        (slab contract). Batch sharding has no collective, so its staged
        path IS the fused program under one descriptive marker — built
        UNGUARDED when guards are on (the staged loop threads raw arrays
        between phases; the guard tuple belongs to the exec envelope)."""
        if self.fft3d or self.shard == "batch":
            if self._guard_mode != "off":
                if self._fwd_unguarded is None:
                    self._fwd_unguarded = self._build(forward=True,
                                                      guard=False)
                return [("2D FFT X-Y-Direction", self._fwd_unguarded)]
            if self._fwd is None:
                self._fwd = self._build(forward=True)
            return [("2D FFT X-Y-Direction", self._fwd)]
        first, xpose, last = self._slab_parts(True)
        return self._jit_stages(
            [("1D FFT Y-Direction", first, self._in_spec, self._in_spec),
             (self._xpose_desc(), xpose, self._in_spec, self._out_spec),
             ("1D FFT X-Direction", last, self._out_spec, self._out_spec)])

    def inverse_stages(self):
        if self.fft3d or self.shard == "batch":
            if self._guard_mode != "off":
                if self._inv_unguarded is None:
                    self._inv_unguarded = self._build(forward=False,
                                                      guard=False)
                return [("2D FFT X-Y-Direction", self._inv_unguarded)]
            if self._inv is None:
                self._inv = self._build(forward=False)
            return [("2D FFT X-Y-Direction", self._inv)]
        first, xpose, last = self._slab_parts(False)
        return self._jit_stages(
            [("1D FFT X-Direction", first, self._out_spec, self._out_spec),
             (self._xpose_desc(), xpose, self._out_spec, self._in_spec),
             ("1D FFT Y-Direction", last, self._in_spec, self._in_spec)])


def _map_in_tile_groups(base, x, ck, group, ans):
    """``base`` over chunks of ``ck`` images of ``x``, as ``lax.map``, in
    groups of ``group`` images: a group's answers (each ``ans``, complex64
    ``(ck, nx, nys)``) are written into real and imaginary float32
    buffers laid out ``_BATCH_MAJOR``, and the finished group goes into
    the stack in one write, which fills whole tiles of the stack's
    default layout (the group fills the sublanes). The layout constraint
    is on the float32 planes: the TPU compiler refuses one on complex64."""
    n = x.shape[0]

    def one_group(g):
        # Zeros like the input, so that under shard_map they vary as it does.
        zeros = jax.lax.full_like(x, 0, jnp.float32, (group,) + ans.shape[1:])

        def one_chunk(i, planes):
            y = base(jax.lax.dynamic_slice_in_dim(x, g * group + i * ck, ck))
            return tuple(
                with_layout_constraint(jax.lax.dynamic_update_slice_in_dim(
                    plane, part, i * ck, 0), _BATCH_MAJOR)
                for plane, part in zip(planes, (y.real, y.imag)))

        return jax.lax.fori_loop(0, group // ck, one_chunk, (zeros, zeros))

    re, im = jax.lax.map(one_group, jnp.arange(n // group))
    return jax.lax.complex(re, im).reshape((n,) + ans.shape[1:])


# ---------------------------------------------------------------------------
# contract declaration (analysis/contracts.py) — the exchange this family
# stages, next to the code that stages it.
# ---------------------------------------------------------------------------

def _contract_exchanges(plan, direction, dims=2):
    """Batched-2D: ``shard="x"`` stages one exchange (scatter spectral y,
    gather x; STREAMS chunks along the untouched batch axis);
    ``shard="batch"`` and the single-device fallback are collective-free
    by construction."""
    del dims
    if plan.fft3d or plan.shard == "batch":
        return ()
    from ..analysis import contracts as _c
    cfg = plan.config
    rendering = _c.rendering_name(cfg)
    chunks = 1
    subblocks = 1
    if rendering == "streams":
        chunks = min(cfg.resolved_streams_chunks(), plan._batch_pad)
    elif rendering == "a2a_pipe":
        chunks = ring_subblocks(plan._batch_pad,
                                cfg.resolved_overlap_subblocks())
    elif rendering in ("ring", "ring_overlap"):
        # The sub-block split slices arriving blocks along the concat
        # axis: forward gathers x (local extent nx_pad/P), inverse
        # gathers spectral y (nys_pad/P).
        p = plan.partition.num_ranks
        ext = (plan._nx_pad // p if direction == "forward"
               else plan._nys_pad // p)
        subblocks = ring_subblocks(ext, cfg.resolved_overlap_subblocks())
    return (_c.ExchangeDecl(
        "transpose", (plan._batch_pad, plan._nx_pad, plan._nys_pad),
        plan.partition.num_ranks, rendering, chunks,
        subblocks=subblocks),)


def _declare_graph(plan, direction, dims=2):
    """Batched-2D stage graph (analysis/plangraph.py): ``shard="x"`` is
    the 2D slab restriction — per-plane y FFT -> exchange -> per-plane
    x FFT (encode/decode under a compressed wire; the fused wire uses
    the unpack-only arrival — the post-transpose FFT runs along the
    gathered axis); ``shard="batch"`` and the single-device fallback are
    one collective-free fused 2D FFT node. Guard at check/enforce."""
    del dims
    from ..analysis import plangraph as _pg
    cfg = plan.config
    cdt, rdt = _pg.payload_dtypes(cfg, plan.transform)
    fwd = direction == "forward"
    b = _pg.GraphBuilder("batched2d", direction, wire=cfg.wire_dtype,
                         guards=plan._guard_mode, complex_dtype=cdt)
    in_shape = plan.input_padded_shape if fwd else plan.output_padded_shape
    out_shape = plan.output_padded_shape if fwd else plan.input_padded_shape
    in_dtype, out_dtype = (rdt, cdt) if fwd else (cdt, rdt)
    in_spec = plan._in_spec if fwd else plan._out_spec
    out_spec = plan._out_spec if fwd else plan._in_spec
    b.node("input")
    b.payload(in_shape, in_dtype, in_spec)
    if plan.fft3d or plan.shard == "batch":
        b.node("local_fft", axes=(2, 1) if fwd else (1, 2),
               label="2D FFT per plane")
        b.payload(out_shape, out_dtype, out_spec)
    else:
        (decl,) = _contract_exchanges(plan, direction)
        b.node("local_fft", axes=(2,) if fwd else (1,), label="stage 1")
        depth = _pg.shipped_schedule_depth(decl.rendering, cfg)
        fused = cfg.fused_wire_active()
        b.exchange(decl.label, decl.payload_shape, decl.axis_size,
                   decl.rendering, chunks=decl.chunks,
                   subblocks=decl.subblocks,
                   schedule_depth=depth, decoded_spec=out_spec,
                   fused_encode=fused,
                   decode_fuses=("decode",) if fused else None)
        b.node("local_fft", axes=(1,) if fwd else (2,), label="stage 2")
        b.payload(out_shape, out_dtype, out_spec)
    if plan._guard_mode != "off":
        b.node("guard")
    b.node("output")
    return b.graph()


def _register_contracts():
    from ..analysis import contracts as _c
    from ..analysis import plangraph as _pg
    _c.register_family("batched2d", "Batched2DFFTPlan", _contract_exchanges)
    _pg.register_graph_family("batched2d", _declare_graph)


_register_contracts()
