"""Pencil (2D) decomposition engine.

TPU-native re-design of the reference's pencil family
(``src/pencil/mpicufft_pencil.cpp``, 1841 LoC + Opt1 variant): the global
``Nx x Ny x Nz`` array is decomposed over a ``P1 x P2`` grid
(``pidx = pidx_i * P2 + pidx_j``, ``src/pencil/mpicufft_pencil.cpp:83-85``),
and the transform runs

    1D FFT z  ->  transpose 1 (row communicator, P2 ranks)
              ->  1D FFT y  ->  transpose 2 (column communicator, P1 ranks)
              ->  1D FFT x

Here the two sub-communicators created by ``MPI_Comm_split``
(``mpicufft_pencil.cpp:112-123``) are the two named axes of a
``Mesh(('p1','p2'))``; each transpose is a ``lax.all_to_all`` over one axis.
The three distribution stages (input / transposed / output
``Partition_Dimensions``, ``mpicufft_pencil.cpp:87-110``) become three
``PartitionSpec``s:

    input      P('p1','p2', None)   — z-pencils
    transposed P('p1', None, 'p2')  — y-pencils
    output     P(None, 'p1','p2')   — x-pencils

Partial-dimension execution ``exec_r2c(x, dims=d)`` for d in {1,2,3} mirrors
the reference's ``execR2C(out, in, d)`` early-returns
(``mpicufft_pencil.cpp:1665-1668,1710-1711``) used to test pipeline stages in
isolation.

Per-transpose communication methods: the reference takes ``-comm1/-snd1``
and ``-comm2/-snd2`` (``tests/src/pencil/main.cpp:26-63``); here
``Config.comm_method`` governs transpose 1 and ``Config.comm_method2``
transpose 2 — ``ALL2ALL`` places an explicit ``lax.all_to_all`` inside the
shard_mapped segment, ``PEER2PEER`` breaks the pipeline at that point and
lets XLA's SPMD partitioner insert/schedule the resharding collective.

The padded-shape contract matches the slab engine (see ``models/slab.py``):
every mesh-decomposed axis of a distributed global array is zero-padded to a
multiple of its mesh axis; the halved ``Nz/2+1`` z axis is padded only for
the d>=2 transposes that scatter it.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import obs
from .. import params as pm
from ..ops import fft as lf
from ..parallel.mesh import PENCIL_AXES, make_pencil_mesh
from ..parallel.transpose import (WIRE_NATIVE, all_to_all_transpose,
                                  chunked_reshard, concat_axis_chunks,
                                  pad_axis_to, pipelined_all_to_all,
                                  ring_subblocks, ring_transpose,
                                  slice_axis_to, split_axis_chunks,
                                  wire_complex_dtype, wire_decode,
                                  wire_encode)
from ..resilience import inject
from ..utils import wisdom
from .base import (DistFFTPlan, _with_pad, local_fft_fields,
                   notice_axis_smoothness, r2c_pairing, c2r_pairing)

P1_AXIS, P2_AXIS = PENCIL_AXES


class PencilFFTPlan(DistFFTPlan):
    """Distributed 3D R2C/C2R FFT with 2D (pencil) decomposition over (x, y)."""

    def __init__(self, global_size: pm.GlobalSize, partition: pm.PencilPartition,
                 config: Optional[pm.Config] = None, mesh: Optional[Mesh] = None,
                 transform: str = "r2c", dims: int = 3):
        # Wisdom resolution of "auto" Config fields (see SlabFFTPlan): the
        # comm race covers the pencil 2x2 (comm1 x comm2) matrix at dims=3.
        # ``dims`` is a resolution hint ONLY — the partial-transform depth
        # the run will execute (--fft-dim, an exec-time choice the plan
        # itself is agnostic to); it keys the wisdom entry and bounds the
        # race to the program that will actually run (at dims=2 only
        # transpose 1 exists, so comm2 is not raced).
        config = wisdom.resolve_config("pencil", global_size, partition,
                                       config, mesh=mesh,
                                       transform=transform, dims=dims)
        if mesh is None and partition.num_ranks > 1:
            mesh = make_pencil_mesh(partition.p1, partition.p2)
        if mesh is not None and partition.num_ranks > 1:
            for name, want in ((P1_AXIS, partition.p1), (P2_AXIS, partition.p2)):
                if name not in mesh.shape:
                    raise ValueError(
                        f"pencil mesh must have a {name!r} axis, got {mesh.axis_names}")
                if mesh.shape[name] != want:
                    raise ValueError(
                        f"mesh axis {name!r} has {mesh.shape[name]} devices but "
                        f"the partition asks for {want}")
        super().__init__(global_size, partition, config, mesh)
        if transform not in ("r2c", "c2c"):
            raise ValueError(f"transform must be 'r2c' or 'c2c', got {transform!r}")
        self.transform = transform
        g = global_size
        self.p1, self.p2 = partition.p1, partition.p2
        # Spectral z extent: halved for R2C, full for C2C (extension; the
        # reference core is R2C/C2R-only, BASELINE configs #1/#2 need C2C).
        self._nz_spec = g.nz if transform == "c2c" else g.nz_out
        if self.fft3d:
            self._nx_p1 = g.nx
            self._ny_p2 = g.ny
            self._ny_p1 = g.ny
            self._nzc_p2 = self._nz_spec
        else:
            self._nx_p1 = pm.padded_extent(g.nx, self.p1)
            self._ny_p2 = pm.padded_extent(g.ny, self.p2)
            self._ny_p1 = pm.padded_extent(g.ny, self.p1)
            self._nzc_p2 = pm.padded_extent(self._nz_spec, self.p2)
            self._in_spec = PartitionSpec(P1_AXIS, P2_AXIS, None)
            self._mid_spec = PartitionSpec(P1_AXIS, None, P2_AXIS)
            self._out_spec = PartitionSpec(None, P1_AXIS, P2_AXIS)
        # compiled-callable caches keyed by dims
        self._r2c_d: Dict[int, object] = {}
        self._c2r_d: Dict[int, object] = {}
        # The depth the wisdom entry was resolved under (the fallback
        # ladder's demotion stamp must target the exact cell).
        self._wisdom_dims = dims
        rows = (g.nx * g.ny if self.fft3d else
                self._nx_p1 // self.p1 * (self._ny_p2 // self.p2))
        self.r2c_paired = transform == "r2c" and r2c_pairing(
            self.config, mesh, g.nz, rows)
        self.c2r_paired = transform == "r2c" and c2r_pairing(
            self.config, mesh, g.nz, rows)
        notice_axis_smoothness("pencil", g.shape, self.config)
        obs.event("plan.created", kind="pencil", transform=transform,
                  shape=list(g.shape), grid=[self.p1, self.p2],
                  comm=self.config.comm_method.value,
                  comm2=self.config.resolved_comm2().value,
                  send=self.config.send_method.value,
                  send2=self.config.resolved_snd2().value,
                  opt=self.config.opt, wire=self.config.wire_dtype,
                  r2c_paired=self.r2c_paired, c2r_paired=self.c2r_paired,
                  **local_fft_fields(self.config, self._mxu_st))

    # -- shapes ------------------------------------------------------------

    @property
    def input_padded_shape(self) -> Tuple[int, int, int]:
        g = self.global_size
        return (self._nx_p1, self._ny_p2, g.nz)

    @property
    def output_shape(self) -> Tuple[int, int, int]:
        g = self.global_size
        return (g.nx, g.ny, self._nz_spec)

    def output_padded_shape_for(self, dims: int = 3) -> Tuple[int, int, int]:
        g = self.global_size
        if self.fft3d:
            return (g.nx, g.ny, self._nz_spec)
        if dims == 1:
            return (self._nx_p1, self._ny_p2, self._nz_spec)
        if dims == 2:
            return (self._nx_p1, g.ny, self._nzc_p2)
        return (g.nx, self._ny_p1, self._nzc_p2)

    @property
    def output_padded_shape(self) -> Tuple[int, int, int]:
        return self.output_padded_shape_for(3)

    def spec_for(self, dims: int = 3) -> PartitionSpec:
        """Output PartitionSpec per transform depth: z-pencils (d=1),
        y-pencils (d=2), x-pencils (d=3) — the three
        ``Partition_Dimensions`` of the reference."""
        if self.fft3d:
            return PartitionSpec()
        return {1: self._in_spec, 2: self._mid_spec, 3: self._out_spec}[dims]

    @property
    def output_spec(self) -> PartitionSpec:
        return self.spec_for(3)

    def output_sharding_for(self, dims: int = 3) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec_for(dims))

    # -- per-rank size tables (reference Partition_Dimensions) ------------

    def partition_dims(self, stage: str) -> pm.PartitionDims:
        """Sizes per rank along each axis for 'input' / 'transposed' /
        'output' stages (reference ``mpicufft_pencil.cpp:87-110``).
        Logical extents; pad-only shards report 0."""
        g = self.global_size
        if stage == "input":
            return pm.PartitionDims(
                tuple(pm.even_shard_sizes(g.nx, self._nx_p1, self.p1)),
                tuple(pm.even_shard_sizes(g.ny, self._ny_p2, self.p2)),
                (g.nz,))
        if stage == "transposed":
            return pm.PartitionDims(
                tuple(pm.even_shard_sizes(g.nx, self._nx_p1, self.p1)),
                (g.ny,),
                tuple(pm.even_shard_sizes(self._nz_spec, self._nzc_p2, self.p2)))
        if stage == "output":
            return pm.PartitionDims(
                (g.nx,),
                tuple(pm.even_shard_sizes(g.ny, self._ny_p1, self.p1)),
                tuple(pm.even_shard_sizes(self._nz_spec, self._nzc_p2, self.p2)))
        raise ValueError(f"unknown stage {stage!r}")

    def in_sizes(self, axis: str = "x") -> List[int]:
        """Per-rank logical input extents along a decomposed axis — the
        pencil rendering of the reference's ``getInSize`` family
        (``include/mpicufft.hpp:66-79``, inherited by
        ``mpicufft_pencil.hpp``). Input pencils are decomposed over x (the
        p1 mesh axis) and y (p2); pad-only shards report 0. Thin projection
        of ``partition_dims("input")``."""
        d = self.partition_dims("input")
        if axis == "x":
            return list(d.size_x)
        if axis == "y":
            return list(d.size_y)
        raise ValueError("pencil input is decomposed over x and y only, "
                         f"not {axis!r}")

    def out_sizes(self, axis: str) -> List[int]:
        """Per-rank logical output extents along a decomposed axis (full-
        depth dims=3 output: x-pencils, decomposed over y on p1 and the
        spectral z on p2). Reference ``getOutSize`` family
        (``include/mpicufft.hpp:66-79``)."""
        d = self.partition_dims("output")
        if axis == "y":
            return list(d.size_y)
        if axis == "z":
            return list(d.size_z)
        raise ValueError("pencil output is decomposed over y and z only, "
                         f"not {axis!r}")

    # -- logical <-> padded helpers ---------------------------------------

    def pad_input(self, x):
        g = self.global_size
        pads = [(0, self._nx_p1 - g.nx), (0, self._ny_p2 - g.ny), (0, 0)]
        if any(p[1] for p in pads):
            x = jnp.pad(x, pads)
        if self.mesh is not None:
            x = jax.device_put(x, self.input_sharding)
        return x

    def crop_real(self, r):
        g = self.global_size
        return np.asarray(r)[: g.nx, : g.ny, :]

    def crop_spectral(self, c, dims: int = 3):
        g = self.global_size
        padded = self.output_padded_shape_for(dims)
        if tuple(c.shape) != padded:
            raise ValueError(
                f"crop_spectral(dims={dims}) expects padded shape {padded}, "
                f"got {tuple(c.shape)}")
        return np.asarray(c)[: g.nx, : g.ny, : self._nz_spec]

    def pad_spectral(self, c, dims: int = 3):
        g = self.global_size
        tgt = self.output_padded_shape_for(dims)
        pads = [(0, tgt[i] - s) for i, s in enumerate((g.nx, g.ny, self._nz_spec))]
        if any(p[1] for p in pads):
            c = jnp.pad(c, pads)
        if self.mesh is not None:
            c = jax.device_put(c, self.output_sharding_for(dims))
        return c

    # -- execution ---------------------------------------------------------

    def exec_c2c(self, x, dims: int = 3):
        """Forward 3D (or partial) C2C transform (transform='c2c' plans)."""
        if self.transform != "c2c":
            raise TypeError("this plan was built with transform='r2c'; "
                            "use exec_r2c/exec_c2r")
        return self._exec_fwd(x, dims)

    def exec_c2c_inv(self, c, dims: int = 3):
        """Inverse of ``exec_c2c``."""
        if self.transform != "c2c":
            raise TypeError("this plan was built with transform='r2c'; "
                            "use exec_r2c/exec_c2r")
        return self._exec_inv(c, dims)

    def exec_r2c(self, x, dims: int = 3):
        """Forward transform of the first ``dims`` axes (z, then y, then x),
        mirroring the reference's partial-dimension ``execR2C(out, in, d)``."""
        if self.transform != "r2c":
            raise TypeError("this plan was built with transform='c2c'; "
                            "use exec_c2c/exec_c2c_inv")
        return self._exec_fwd(x, dims)

    def exec_c2r(self, c, dims: int = 3):
        """Inverse of ``exec_r2c(..., dims)``."""
        if self.transform != "r2c":
            raise TypeError("this plan was built with transform='c2c'; "
                            "use exec_c2c/exec_c2c_inv")
        return self._exec_inv(c, dims)

    def _exec_fwd(self, x, dims: int = 3):
        if dims not in (1, 2, 3):
            raise ValueError(f"dims must be 1, 2 or 3, got {dims}")
        if tuple(x.shape) not in (self.input_shape, self.input_padded_shape):
            raise ValueError(
                f"forward exec expects global shape {self.input_shape} (or "
                f"padded {self.input_padded_shape}), got {tuple(x.shape)}")
        if not self.fft3d and tuple(x.shape) == self.input_shape \
                and self.input_shape != self.input_padded_shape:
            x = self.pad_input(x)
        from ..resilience import fallback

        def get():
            if dims not in self._r2c_d:
                self._r2c_d[dims] = self._build_r2c_d(dims)
            return self._r2c_d[dims]

        return fallback.execute(self, "forward", x, get, dims=dims)

    def _exec_inv(self, c, dims: int = 3):
        if dims not in (1, 2, 3):
            raise ValueError(f"dims must be 1, 2 or 3, got {dims}")
        padded = self.output_padded_shape_for(dims)
        if tuple(c.shape) not in (self.output_shape, padded):
            raise ValueError(
                f"inverse exec(dims={dims}) expects global shape "
                f"{self.output_shape} (or padded {padded}), got {tuple(c.shape)}")
        if not self.fft3d and tuple(c.shape) == self.output_shape \
                and self.output_shape != padded:
            c = self.pad_spectral(c, dims)
        from ..resilience import fallback

        def get():
            if dims not in self._c2r_d:
                self._c2r_d[dims] = self._build_c2r_d(dims)
            return self._c2r_d[dims]

        return fallback.execute(self, "inverse", c, get, dims=dims)

    # -- resilience hooks (guards + fallback ladder) -----------------------

    def _transformed_volume(self, dims: int) -> float:
        """Product of the transformed logical extents at depth ``dims``
        (the Parseval scale; matches ``testcases._roundtrip_scale``)."""
        g = self.global_size
        return float({1: g.nz, 2: g.nz * g.ny, 3: g.n_total}[dims])

    def _guard_spec(self, direction: str, dims: int = 3):
        """GuardSpec per direction AND depth (slab contract; the partial-
        dims programs conserve energy over exactly the transformed
        axes)."""
        from ..resilience.guards import GuardSpec
        g, norm = self.global_size, self.config.norm
        n = self._transformed_volume(dims)
        c2c = self.transform == "c2c"
        out_logical = (g.nx, g.ny, self._nz_spec)
        if direction == "forward":
            return GuardSpec(
                direction="forward", check="parseval",
                scale=1.0 if norm is pm.FFTNorm.ORTHO else n,
                in_logical=self.input_shape, out_logical=out_logical,
                halved_axis=None if c2c else 2,
                halved_n=0 if c2c else g.nz)
        if not c2c:
            return GuardSpec(direction="inverse", check="finite", scale=1.0,
                             in_logical=out_logical,
                             out_logical=self.input_shape)
        scale = {pm.FFTNorm.NONE: n, pm.FFTNorm.BACKWARD: 1.0 / n,
                 pm.FFTNorm.ORTHO: 1.0}[norm]
        return GuardSpec(direction="inverse", check="parseval", scale=scale,
                         in_logical=out_logical,
                         out_logical=self.input_shape)

    def _wisdom_key_args(self) -> dict:
        return {"kind": "pencil", "transform": self.transform,
                "dims": self._wisdom_dims}

    # -- pipeline bodies ---------------------------------------------------

    def _scope_ids(self, direction: str, dims: int) -> Dict[str, str]:
        """Plan-graph node ids per pipeline part (obs/profile.py stage
        scopes) — mirrors ``_declare_graph``'s per-kind numbering exactly:
        local-FFT stages count in pipeline order, exchanges count only
        when their mesh axis is > 1 (the graph declares none otherwise)."""
        ids: Dict[str, str] = {}
        lf_n = x_n = 0

        def nlf(part: str) -> None:
            nonlocal lf_n
            lf_n += 1
            ids[part] = f"local_fft:{lf_n}"

        def nx_(part: str, p: int) -> None:
            nonlocal x_n
            if p > 1:
                x_n += 1
                ids[part] = f"exchange:{x_n}"

        if direction == "forward":
            nlf("s1")
            if dims >= 2:
                nx_("t1", self.p2)
                nlf("s2")
            if dims >= 3:
                nx_("t2", self.p1)
                nlf("s3")
        else:
            if dims >= 3:
                nlf("i3")
                nx_("t2b", self.p1)
            if dims >= 2:
                nlf("i2")
                nx_("t1b", self.p2)
            nlf("i1")
        return ids

    def _fwd_parts(self, dims: int):
        """(s1, t1, s2, t2, s3): local-FFT bodies and transpose bodies for
        the forward pipeline at depth ``dims``; t's are None when the
        pipeline stops before them."""
        g, norm = self.global_size, self.config.norm
        realigned = self.config.opt == 1
        be = self.config.fft_backend
        st = self._mxu_st
        wire = self.config.wire_dtype
        nzc_p2, ny_p1 = self._nzc_p2, self._ny_p1
        ny, nx = g.ny, g.nx
        complex_mode = self.transform == "c2c"

        def s1(xl):
            if complex_mode:
                c = lf.fft(xl, axis=2, norm=norm, backend=be, settings=st)
            else:
                c = lf.rfft(xl, axis=2, norm=norm, backend=be, settings=st,
                            paired=self.r2c_paired)
            if dims >= 2:
                c = pad_axis_to(c, 2, nzc_p2)
            return c

        def t1(cl):
            return all_to_all_transpose(cl, P2_AXIS, 2, 1, realigned=realigned,
                                        wire=wire)

        def s2(cl):
            c = slice_axis_to(cl, 1, ny)
            c = lf.fft(c, axis=1, norm=norm, backend=be, settings=st)
            if dims >= 3:
                c = pad_axis_to(c, 1, ny_p1)
            return c

        def t2(cl):
            return all_to_all_transpose(cl, P1_AXIS, 1, 0, realigned=realigned,
                                        wire=wire)

        def s3(cl):
            c = slice_axis_to(cl, 0, nx)
            return lf.fft(c, axis=0, norm=norm, backend=be, settings=st)

        # Stage scopes (obs/profile.py): graph node ids per part.
        ids = self._scope_ids("forward", dims)
        sc = obs.profile.scoped
        return (sc("pencil", ids["s1"], s1),
                sc("pencil", ids.get("t1", ""), t1) if dims >= 2 else None,
                sc("pencil", ids.get("s2", "local_fft:2"), s2),
                sc("pencil", ids.get("t2", ""), t2) if dims >= 3 else None,
                sc("pencil", ids.get("s3", "local_fft:3"), s3))

    def _inv_parts(self, dims: int):
        """(i3, t2b, i2, t1b, i1): inverse bodies mirroring ``_fwd_parts``."""
        g, norm = self.global_size, self.config.norm
        realigned = self.config.opt == 1
        be = self.config.fft_backend
        st = self._mxu_st
        wire = self.config.wire_dtype
        nx_p1, ny_p2 = self._nx_p1, self._ny_p2
        ny, nzc, nz = g.ny, self._nz_spec, g.nz
        complex_mode = self.transform == "c2c"

        def i3(cl):
            c = lf.ifft(cl, axis=0, norm=norm, backend=be, settings=st)
            return pad_axis_to(c, 0, nx_p1)

        def t2b(cl):
            return all_to_all_transpose(cl, P1_AXIS, 0, 1, realigned=realigned,
                                        wire=wire)

        def i2(cl):
            c = slice_axis_to(cl, 1, ny)
            c = lf.ifft(c, axis=1, norm=norm, backend=be, settings=st)
            return pad_axis_to(c, 1, ny_p2)

        def t1b(cl):
            return all_to_all_transpose(cl, P2_AXIS, 1, 2, realigned=realigned,
                                        wire=wire)

        def i1(cl):
            c = slice_axis_to(cl, 2, nzc)
            if complex_mode:
                return lf.ifft(c, axis=2, norm=norm, backend=be, settings=st)
            return lf.irfft(c, n=nz, axis=2, norm=norm, backend=be,
                            settings=st, paired=self.c2r_paired)

        # Stage scopes (obs/profile.py): inverse graph node numbering.
        ids = self._scope_ids("inverse", dims)
        sc = obs.profile.scoped
        return (sc("pencil", ids.get("i3", ""), i3) if dims >= 3 else None,
                sc("pencil", ids.get("t2b", ""), t2b) if dims >= 3 else None,
                sc("pencil", ids.get("i2", ""), i2) if dims >= 2 else None,
                sc("pencil", ids.get("t1b", ""), t1b) if dims >= 2 else None,
                sc("pencil", ids["i1"], i1))

    # -- pipeline builders -------------------------------------------------

    def _fwd_segments(self, dims: int):
        """(segments, start_spec) of the forward pipeline.

        Each transpose leaves exactly one axis untouched (t1 moves z<->y,
        free x; t2 moves y<->x, free z) and the FFT stage that follows it
        never transforms that axis — so under ``SendMethod.STREAMS`` the
        (transpose, next FFT) pair chunks along the free axis into K
        independent exchange->FFT piece chains (``_attach``), the pencil
        rendering of the reference's per-transpose Streams engine
        (``src/pencil/mpicufft_pencil.cpp:678-1482`` send methods)."""
        s1, t1, s2, t2, s3 = self._fwd_parts(dims)
        ids = self._scope_ids("forward", dims)
        segments = [(s1, self._in_spec)]
        if dims >= 2:
            if not self._attach(segments, self.config.comm_method,
                                self.config.send_method, t1, s2,
                                self._mid_spec, ca=0,
                                xinfo=(P2_AXIS, 2, 1),
                                scope_id=ids.get("t1", "")):
                segments.append((s2, self._mid_spec))
        if dims >= 3:
            if not self._attach(segments, self.config.resolved_comm2(),
                                self.config.resolved_snd2(), t2, s3,
                                self._out_spec, ca=2,
                                xinfo=(P1_AXIS, 1, 0),
                                scope_id=ids.get("t2", "")):
                segments.append((s3, self._out_spec))
        return segments, self._in_spec

    def _inv_segments(self, dims: int):
        """(segments, start_spec) of the inverse pipeline (free axes mirror
        the forward: t2b moves x<->y, free z; t1b moves y<->z, free x)."""
        i3, t2b, i2, t1b, i1 = self._inv_parts(dims)
        ids = self._scope_ids("inverse", dims)
        segments: List = []
        if dims >= 3:
            segments.append((i3, self._out_spec))
            if self._attach(segments, self.config.resolved_comm2(),
                            self.config.resolved_snd2(), t2b, i2,
                            self._mid_spec, ca=2,
                            xinfo=(P1_AXIS, 0, 1),
                            scope_id=ids.get("t2b", "")):
                i2 = None  # consumed into the chunked segment
        if dims >= 2:
            if i2 is not None:
                segments.append((i2, self._mid_spec))
            if self._attach(segments, self.config.comm_method,
                            self.config.send_method, t1b, i1,
                            self._in_spec, ca=0,
                            xinfo=(P2_AXIS, 1, 2),
                            scope_id=ids.get("t1b", "")):
                i1 = None
        if i1 is not None:
            segments.append((i1, self._in_spec))
        start = {3: self._out_spec, 2: self._mid_spec, 1: self._in_spec}[dims]
        return segments, start

    def _build_r2c_d(self, dims: int):
        with obs.span("plan.build", kind="pencil", direction="forward",
                      dims=dims):
            if self.fft3d:
                return self._fft3d_r2c_d(dims)
            return self._compile(*self._fwd_segments(dims),
                                 direction="forward", dims=dims)

    def _build_c2r_d(self, dims: int):
        with obs.span("plan.build", kind="pencil", direction="inverse",
                      dims=dims):
            if self.fft3d:
                return self._fft3d_c2r_d(dims)
            return self._compile(*self._inv_segments(dims),
                                 direction="inverse", dims=dims)

    def forward_fn(self, dims: int = 3):
        """Pure forward pipeline (``DistFFTPlan.forward_fn`` contract);
        ``dims`` as in ``exec_r2c``. Cached per (plan, dims); pads
        logical-shaped input like the exec path (traced, differentiable)."""
        if self._fwd_pure is None:
            self._fwd_pure = {}
        if dims not in self._fwd_pure:
            if self.fft3d:
                run = self._fft3d_r2c_d(dims, jit=False)
            else:
                run, _ = self._compose(*self._fwd_segments(dims))
            self._fwd_pure[dims] = _with_pad(run, self.input_shape,
                                             self.input_padded_shape)
        return self._fwd_pure[dims]

    def inverse_fn(self, dims: int = 3):
        """Pure inverse pipeline (``DistFFTPlan.forward_fn`` contract).
        At dims=3 logical-shaped spectral input is padded like the exec
        path; partial-depth (dims<3) input must already be in the padded
        intermediate layout ``output_padded_shape_for(dims)``."""
        if self._inv_pure is None:
            self._inv_pure = {}
        if dims not in self._inv_pure:
            if self.fft3d:
                run = self._fft3d_c2r_d(dims, jit=False)
            else:
                run, _ = self._compose(*self._inv_segments(dims))
            if dims == 3:
                run = _with_pad(run, self.output_shape,
                                self.output_padded_shape)
            self._inv_pure[dims] = run
        return self._inv_pure[dims]

    # -- per-phase staged execution (benchmark timer support) --------------

    variant_name = "pencil"

    @property
    def section_descriptions(self) -> List[str]:
        """Reference pencil phase vocabulary
        (include/mpicufft_pencil.hpp:263-287). Phases with no XLA analog
        (pack/unpack/send bookkeeping) stay 0 in the CSV."""
        def tr(prefix, send_complete):
            xs = ["First Send", "Packing", "Start Local Transpose",
                  "Start Receive", "First Receive", "Finished Receive",
                  "Start All2All", "Finished All2All", "Unpacking"]
            if send_complete:
                xs.append("Send Complete")
            return [f"{prefix} Transpose ({x})" for x in xs]
        # 24 sections; only the First transpose has a "(Send Complete)"
        # marker in the reference list.
        # "Run complete (fused)" extends the vocabulary with the mark after
        # one extra call of the fused production program (see the slab list).
        return (["init", "1D FFT Z-Direction"] + tr("First", True)
                + ["1D FFT Y-Direction"] + tr("Second", False)
                + ["1D FFT X-Direction", "Run complete",
                   "Run complete (fused)"])

    def _xpose_desc(self, which: int) -> str:
        comm = (self.config.comm_method if which == 1
                else self.config.resolved_comm2())
        prefix = "First" if which == 1 else "Second"
        kind = ("Finished All2All" if comm is pm.CommMethod.ALL2ALL
                else "Finished Receive")
        return f"{prefix} Transpose ({kind})"

    def forward_stages(self, dims: int = 3):
        """[(phase desc, jitted stage fn)] for per-phase timed execution
        (always explicit collectives; the fused exec path is unaffected)."""
        if self.fft3d:
            return [(None, lambda x: self._exec_fwd(x, dims))]
        s1, t1, s2, t2, s3 = self._fwd_parts(dims)
        specs = [("1D FFT Z-Direction", s1, self._in_spec, self._in_spec)]
        if dims >= 2:
            specs += [(self._xpose_desc(1), t1, self._in_spec, self._mid_spec),
                      ("1D FFT Y-Direction", s2, self._mid_spec, self._mid_spec)]
        if dims >= 3:
            specs += [(self._xpose_desc(2), t2, self._mid_spec, self._out_spec),
                      ("1D FFT X-Direction", s3, self._out_spec, self._out_spec)]
        return self._jit_stages(specs)

    def inverse_stages(self, dims: int = 3):
        if self.fft3d:
            return [(None, lambda c: self._exec_inv(c, dims))]
        i3, t2b, i2, t1b, i1 = self._inv_parts(dims)
        specs = []
        if dims >= 3:
            specs += [("1D FFT X-Direction", i3, self._out_spec, self._out_spec),
                      (self._xpose_desc(2), t2b, self._out_spec, self._mid_spec)]
        if dims >= 2:
            specs += [("1D FFT Y-Direction", i2, self._mid_spec, self._mid_spec),
                      (self._xpose_desc(1), t1b, self._mid_spec, self._in_spec)]
        specs.append(("1D FFT Z-Direction", i1, self._in_spec, self._in_spec))
        return self._jit_stages(specs)



    def _attach(self, segments, comm: pm.CommMethod, snd: pm.SendMethod,
                a2a, nxt, spec_after, ca: int, *,
                xinfo: Tuple[str, int, int], scope_id: str = "") -> bool:
        """Attach a transpose to the segment list.

        ALL2ALL + SYNC: explicit collective fused into the previous segment.
        ALL2ALL + STREAMS: the previous segment is extended with K
        independent (transpose -> ``nxt``) piece chains along free axis
        ``ca``; returns True to signal ``nxt`` was consumed.
        PEER2PEER + SYNC: a segment break so GSPMD inserts the resharding
        collective at the boundary.
        PEER2PEER + STREAMS: a chunked break — the boundary reshards K
        pieces independently (``chunked_reshard``, shard-aligned pieces
        since the pencil chunk axes are mesh-sharded). Measured: GSPMD
        re-fuses the pieces into one collective (see
        ``SlabFFTPlan._assemble_pure``), so this is equivalent to SYNC;
        ALL2ALL is the genuinely chunked rendering.
        RING / RING_OVERLAP (any comm): the transpose rendered as the
        ``P-1``-step ``lax.ppermute`` ring (``ring_transpose`` over
        ``xinfo = (axis_name, split, concat)``; RING_OVERLAP issues each
        step's permute on the double-buffered schedule), fused into the
        previous segment — a ring is only expressible inside shard_map,
        so the ring renderings own the exchange regardless of ``comm``.
        Every pencil post-transpose FFT runs along the gathered axis (the
        received blocks are disjoint slices of exactly that axis), so no
        per-block compute is pipelined here; the win is the ``P-1``
        distinct, independently schedulable collective-permutes GSPMD
        cannot re-fuse the way it re-fuses the chunked reshards (and the
        fused wire uses the unpack-only arrival kernel).
        """
        if snd.is_ring:
            prev_fn, _ = segments[-1]
            axis_name, split, concat = xinfo
            wire = self.config.wire_dtype
            overlap = snd is pm.SendMethod.RING_OVERLAP
            depth = self.config.resolved_overlap_depth()
            subblocks = self.config.resolved_overlap_subblocks()
            from ..ops import pallas_fft as plf
            enc_fn, arr_fn = plf.fused_ring_hooks(self.config, snd)

            def rseg(c, f=prev_fn):
                # The ring is built here (not via the scoped a2a body), so
                # the exchange scope wraps this call site directly.
                with obs.profile.stage_scope("pencil", scope_id):
                    return ring_transpose(f(c), axis_name, split, concat,
                                          wire=wire, overlap=overlap,
                                          depth=depth, subblocks=subblocks,
                                          encode_fn=enc_fn,
                                          arrive_fn=arr_fn)

            segments[-1] = (rseg, spec_after)
            return False
        streams = snd is pm.SendMethod.STREAMS
        if comm is pm.CommMethod.ALL2ALL:
            prev_fn, _ = segments[-1]
            if streams:
                k = self.config.resolved_streams_chunks()

                def seg(c, f=prev_fn, a2a=a2a, nxt=nxt, ca=ca, k=k):
                    c = f(c)
                    return concat_axis_chunks(
                        [nxt(a2a(p)) for p in split_axis_chunks(c, ca, k)],
                        ca)

                segments[-1] = (seg, spec_after)
                return True
            if self.config.resolved_overlap_subblocks() > 1:
                # ALL2ALL + SYNC/MPI_TYPE with a sub-block split: the
                # software-pipelined monolithic exchange (a2a_pipe) —
                # chunk k+1's collective issued while chunk k decodes,
                # along the same free axis STREAMS chunks.
                axis_name, split, concat = xinfo
                wire = self.config.wire_dtype
                realigned = self.config.opt == 1
                pk = self.config.resolved_overlap_subblocks()
                depth = self.config.resolved_overlap_depth()

                def pseg(c, f=prev_fn):
                    with obs.profile.stage_scope("pencil", scope_id):
                        return pipelined_all_to_all(
                            f(c), axis_name, split, concat, chunk_axis=ca,
                            chunks=pk, depth=depth, realigned=realigned,
                            wire=wire)

                segments[-1] = (pseg, spec_after)
                return False
            segments[-1] = (lambda c, f=prev_fn: a2a(f(c)), spec_after)
            return False
        # PEER2PEER boundaries: when the wire compresses, the break carries
        # the marker so _compose wraps it encode-side / decode-side (the
        # GSPMD collective then moves the planar bf16 array). wire="native"
        # appends the exact pre-wire break tuples.
        wired = self.config.wire_dtype != WIRE_NATIVE
        if streams:
            segments.append((("CHUNKED_BREAK", ca,
                              self.config.resolved_streams_chunks(), wired),
                             spec_after))
            return False
        segments.append(("WBREAK" if wired else "BREAK", spec_after))
        return False

    def _compose(self, segments, in_spec):
        """Fuse consecutive segments that share a shard_map into staged
        shard_maps; returns the pure composition and its out spec."""
        mesh = self.mesh
        wire = self.config.wire_dtype
        cdt = wire_complex_dtype(self.config.double_prec)
        stages = []
        cur_fns: List = []
        cur_in = in_spec
        cur_out = in_spec

        def flush():
            if not cur_fns:
                return
            fns = list(cur_fns)

            def seg(xl, fns=fns):
                for f in fns:
                    xl = f(xl)
                return xl

            stages.append(jax.shard_map(seg, mesh=mesh, in_specs=cur_in,
                                        out_specs=cur_out))

        def encode_break(spec):
            """Close the current stage with a wire encode and open the next
            with the decode, so the GSPMD boundary collective between them
            moves the planar bf16 array (specs gain the leading plane
            axis). Returns the encoded next-stage spec (the boundary's
            target layout, for the chunked reshard's NamedSharding). The
            fault-injection taint sits after the encode — the corrupted
            wire image is what the boundary collective moves."""
            nonlocal cur_fns, cur_in, cur_out
            cur_fns.append(
                lambda c: inject.taint_wire(wire_encode(c, wire), "gspmd"))
            cur_out = PartitionSpec(None, *cur_out)
            flush()
            cur_fns = [lambda y: wire_decode(y, cdt, wire)]
            cur_in = PartitionSpec(None, *spec)
            cur_out = spec
            return cur_in

        for fn, spec in segments:
            if fn == "BREAK":
                # Native GSPMD boundary: the stage's output IS the wire
                # payload; the injection taint (identity without
                # $DFFT_FAULT_SPEC) closes the stage.
                if cur_fns:
                    cur_fns.append(lambda c: inject.taint_wire(c, "gspmd"))
                flush()
                cur_fns = []
                cur_in = spec
                cur_out = spec
            elif fn == "WBREAK":
                # PEER2PEER + compressed wire: the boundary reshard moves
                # the encoded planes; the decode opens the next stage.
                encode_break(spec)
            elif isinstance(fn, tuple) and fn[0] == "CHUNKED_BREAK":
                # PEER2PEER + STREAMS boundary: reshard K pieces of the
                # global array independently. Measured (8-device CPU
                # mesh): GSPMD re-fuses the piece reshards into one
                # collective — see SlabFFTPlan._assemble_pure — so this
                # rendering is equivalent to SYNC; the ALL2ALL rendering
                # is the genuinely chunked pencil path.
                _, ca, k, wired = fn
                if wired:
                    # encode_break flushes the encoded producer stage and
                    # leaves the decode pending as the next stage's first
                    # fn, so appending the reshard here lands it between
                    # them: encode -> piece reshards (compressed) ->
                    # decode. The chunk axis shifts past the plane axis.
                    sh = NamedSharding(mesh, encode_break(spec))
                    ca = ca + 1
                else:
                    flush()
                    sh = NamedSharding(mesh, spec)

                def reshard(x, sh=sh, ca=ca, k=k):
                    # The pencil chunk axes are mesh-sharded identically
                    # on both sides of their boundary (x over p1 at t1, z
                    # over p2 at t2); chunked_reshard splits within each
                    # shard's local block so the piece exchanges move
                    # exactly the monolithic exchange's bytes.
                    return chunked_reshard(x, sh, ca, k)

                stages.append(reshard)
                if not wired:
                    cur_fns = []
                    cur_in = spec
                    cur_out = spec
            else:
                cur_fns.append(fn)
                cur_out = spec
        flush()

        def run(x):
            for st in stages:
                x = st(x)
            return x

        return run, segments[-1][1]

    def _compile(self, segments, in_spec, direction: str = "forward",
                 dims: int = 3):
        """Jit the pure composition with in/out shardings; at guard modes
        check/enforce the jitted program is the guarded pipeline
        ``x -> (y, stats)`` (slab ``_assemble`` contract)."""
        from ..resilience import guards
        run, out_spec = self._compose(segments, in_spec)
        mesh = self.mesh
        run, guarded = guards.maybe_wrap(self, run, direction, dims)
        outsh = NamedSharding(mesh, out_spec)
        if guarded:
            outsh = (outsh, NamedSharding(mesh, PartitionSpec()))
        return jax.jit(run,
                       in_shardings=NamedSharding(mesh, in_spec),
                       out_shardings=outsh)

    # -- single-device partial-dim fallbacks ------------------------------

    def _fft3d_r2c_d(self, dims: int, jit: bool = True):
        norm, be = self.config.norm, self.config.fft_backend
        st = self._mxu_st
        complex_mode = self.transform == "c2c"

        def run(x):
            if complex_mode:
                c = lf.fft(x, axis=2, norm=norm, backend=be, settings=st)
            else:
                c = lf.rfft(x, axis=2, norm=norm, backend=be, settings=st,
                            paired=self.r2c_paired)
            if dims >= 2:
                c = lf.fft(c, axis=1, norm=norm, backend=be, settings=st)
            if dims >= 3:
                c = lf.fft(c, axis=0, norm=norm, backend=be, settings=st)
            return c

        run = obs.profile.scoped("pencil", "local_fft:1", run)
        if not jit:
            return run
        from ..resilience import guards
        run, _ = guards.maybe_wrap(self, run, "forward", dims)
        return jax.jit(run)

    def _fft3d_c2r_d(self, dims: int, jit: bool = True):
        norm, be = self.config.norm, self.config.fft_backend
        st = self._mxu_st
        nz = self.global_size.nz
        complex_mode = self.transform == "c2c"

        def run(c):
            if dims >= 3:
                c = lf.ifft(c, axis=0, norm=norm, backend=be, settings=st)
            if dims >= 2:
                c = lf.ifft(c, axis=1, norm=norm, backend=be, settings=st)
            if complex_mode:
                return lf.ifft(c, axis=2, norm=norm, backend=be, settings=st)
            return lf.irfft(c, n=nz, axis=2, norm=norm, backend=be,
                            settings=st, paired=self.c2r_paired)

        run = obs.profile.scoped("pencil", "local_fft:1", run)
        if not jit:
            return run
        from ..resilience import guards
        run, _ = guards.maybe_wrap(self, run, "inverse", dims)
        return jax.jit(run)


# ---------------------------------------------------------------------------
# contract declaration (analysis/contracts.py) — the exchanges this family
# stages at each partial-transform depth, next to the code that stages them.
# ---------------------------------------------------------------------------

def _contract_exchanges(plan, direction, dims=3):
    """Pencil: transpose 1 over p2 (scatter z, gather y; free axis x,
    chunk axis 0 sharded over p1) from dims >= 2, transpose 2 over p1
    (scatter y, gather x; free axis z, chunk axis 2 sharded over p2)
    from dims >= 3. Payloads are the padded spectral volumes both
    transposes move (``spec_for`` shapes)."""
    # Both transposes run (mirrored) in both directions; only the ring
    # sub-block split is direction-dependent — the concat axis (the one
    # the arriving blocks slice along) flips with the direction.
    if plan.fft3d:
        return ()
    from ..analysis import contracts as _c
    cfg = plan.config
    fwd = direction == "forward"
    sub = cfg.resolved_overlap_subblocks()
    out = []
    if dims >= 2 and plan.p2 > 1:
        r1 = _c.rendering_name(cfg)
        k1 = s1 = 1
        if r1 == "streams":
            k1 = min(cfg.resolved_streams_chunks(),
                     plan._nx_p1 // plan.p1)
        elif r1 == "a2a_pipe":
            k1 = ring_subblocks(plan._nx_p1 // plan.p1, sub)
        elif r1 in ("ring", "ring_overlap"):
            # Forward t1 gathers y (concat 1); inverse t1b gathers z
            # (concat 2). Local extents, same clamp as ring_transpose.
            ext = (plan._ny_p2 // plan.p2 if fwd
                   else plan._nzc_p2 // plan.p2)
            s1 = ring_subblocks(ext, sub)
        out.append(_c.ExchangeDecl(
            "transpose 1", (plan._nx_p1, plan._ny_p2, plan._nzc_p2),
            plan.p2, r1, k1, subblocks=s1))
    if dims >= 3 and plan.p1 > 1:
        r2 = _c.rendering_name(cfg, second=True)
        k2 = s2 = 1
        if r2 == "streams":
            k2 = min(cfg.resolved_streams_chunks(),
                     plan._nzc_p2 // plan.p2)
        elif r2 == "a2a_pipe":
            k2 = ring_subblocks(plan._nzc_p2 // plan.p2, sub)
        elif r2 in ("ring", "ring_overlap"):
            # Forward t2 gathers x (concat 0); inverse t2b gathers y
            # (concat 1).
            ext = (plan._nx_p1 // plan.p1 if fwd
                   else plan._ny_p1 // plan.p1)
            s2 = ring_subblocks(ext, sub)
        out.append(_c.ExchangeDecl(
            "transpose 2", (plan._nx_p1, plan._ny_p1, plan._nzc_p2),
            plan.p1, r2, k2, subblocks=s2))
    return tuple(out)


def _declare_graph(plan, direction, dims=3):
    """Pencil stage graph (analysis/plangraph.py): z FFT -> transpose 1
    (p2 axis, present from dims >= 2 when p2 > 1) -> y FFT -> transpose
    2 (p1 axis, from dims >= 3 when p1 > 1) -> x FFT, mirrored for the
    inverse; encode/decode around each compressed exchange (the fused
    wire uses the unpack-only arrival kernel — every pencil
    post-transpose FFT runs along the gathered axis, so nothing
    pipelines per block); guard at modes check/enforce."""
    from ..analysis import plangraph as _pg
    cfg = plan.config
    cdt, rdt = _pg.payload_dtypes(cfg, plan.transform)
    fwd = direction == "forward"
    b = _pg.GraphBuilder("pencil", direction, wire=cfg.wire_dtype,
                         guards=plan._guard_mode, complex_dtype=cdt)
    decls = {d.label: d for d in _contract_exchanges(plan, direction, dims)}

    def add_exchange(label, spec_after, second=False):
        d = decls.get(label)
        if d is None:
            return
        fused = cfg.fused_wire_active(second)
        b.exchange(d.label, d.payload_shape, d.axis_size, d.rendering,
                   chunks=d.chunks, subblocks=d.subblocks,
                   schedule_depth=_pg.shipped_schedule_depth(d.rendering,
                                                             cfg),
                   decoded_spec=spec_after, fused_encode=fused,
                   decode_fuses=("decode",) if fused else None)

    if fwd:
        b.node("input")
        b.payload(plan.input_padded_shape, rdt, plan.input_spec)
        if plan.fft3d:
            b.node("local_fft", axes=tuple((2, 1, 0)[:dims]),
                   label="fft3d")
        else:
            b.node("local_fft", axes=(2,), label="z stage")
            if dims >= 2:
                add_exchange("transpose 1", plan._mid_spec)
                b.node("local_fft", axes=(1,), label="y stage")
            if dims >= 3:
                add_exchange("transpose 2", plan._out_spec, second=True)
                b.node("local_fft", axes=(0,), label="x stage")
        b.payload(plan.output_padded_shape_for(dims), cdt,
                  plan.spec_for(dims) if not plan.fft3d else "")
    else:
        b.node("input")
        b.payload(plan.output_padded_shape_for(dims), cdt,
                  plan.spec_for(dims) if not plan.fft3d else "")
        if plan.fft3d:
            b.node("local_fft", axes=tuple(reversed((2, 1, 0)[:dims])),
                   label="fft3d")
        else:
            if dims >= 3:
                b.node("local_fft", axes=(0,), label="x stage")
                add_exchange("transpose 2", plan._mid_spec, second=True)
            if dims >= 2:
                b.node("local_fft", axes=(1,), label="y stage")
                add_exchange("transpose 1", plan._in_spec)
            b.node("local_fft", axes=(2,), label="z stage")
        b.payload(plan.input_padded_shape, rdt,
                  plan.input_spec if not plan.fft3d else "")
    if plan._guard_mode != "off":
        b.node("guard")
    b.node("output")
    return b.graph()


def _register_contracts():
    from ..analysis import contracts as _c
    from ..analysis import plangraph as _pg
    _c.register_family("pencil", "PencilFFTPlan", _contract_exchanges)
    _pg.register_graph_family("pencil", _declare_graph)


_register_contracts()
