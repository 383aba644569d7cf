"""Abstract distributed-FFT plan — the TPU analog of the reference's
``MPIcuFFT<T>`` core runtime (``include/mpicufft.hpp:55-79``,
``src/mpicufft.cpp``).

API shape preserved from the reference: construct with a global size +
partition ("initFFT"), query per-rank input/output extents
(``getInSize/getInStart/getOutSize/getOutStart``), then execute forward /
inverse transforms. What changes is the execution model: instead of a
hand-scheduled pipeline of cuFFT calls, memcpy packs and MPI exchanges, a
plan compiles ONE jitted XLA program (local FFT -> all_to_all -> local FFT
[-> all_to_all -> local FFT]) over a ``jax.sharding.Mesh``, per BASELINE.json's
north star.

The reference's ``fft3d`` single-process fallback (``src/mpicufft.cpp:65``)
maps to a mesh-less plan that calls ``jnp.fft.rfftn`` directly.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import obs
from ..ops import fft as local_fft
from ..params import Config, GlobalSize, Partition
from ..resilience import fallback, guards
from ..utils import wisdom


def notice_axis_smoothness(kind: str, axes_lengths: Iterable[int],
                           config: Config) -> None:
    """Arbitrary-size axis support, the advisory half: every family
    accepts any axis length (padding handles mesh divisibility), but a
    non-5-smooth length silently leaves the fast path of the matmul /
    pallas backends (``mxu_fft._split`` degrades a prime to one dense
    O(n^2) contraction). Surface that at plan construction — with the
    fix (``fft_backend="bluestein"``, the chirp-z backend that keeps any
    length at O(n log n)) — instead of letting it show up only as a
    mystery slowdown. The xla/bluestein backends handle every length;
    no notice there."""
    from .. import obs
    from ..ops.bluestein import is_smooth
    rough = sorted({int(n) for n in axes_lengths if not is_smooth(int(n))})
    if rough and config.fft_backend in ("matmul", "matmul-r2", "pallas"):
        obs.notice(
            f"{kind} plan: non-smooth axis length(s) {rough} fall off the "
            f"{config.fft_backend} fast path (dense O(n^2) per axis); "
            "fft_backend='bluestein' keeps them O(n log n)",
            name="plan.nonsmooth_axes", kind=kind, lengths=rough,
            backend=config.fft_backend)


def local_fft_fields(config: Config, settings: Any) -> Dict[str, Any]:
    """The local-FFT backend with the MXU precision and direct-matmul
    threshold a plan traces with (None for backends without DFT
    matmuls), for the ``plan.created`` event."""
    be = config.fft_backend
    if be not in ("matmul", "matmul-r2", "pallas"):
        return {"backend": be, "precision": None, "direct_max": None}
    from ..ops import mxu_fft
    st = settings or mxu_fft.default_settings()
    return {"backend": be, "precision": st.precision.name.lower(),
            "direct_max": st.direct_max}


def r2c_pairing(config: Config, mesh: Optional[Mesh], n: int,
                rows: Optional[int]) -> bool:
    """A plan's ``r2c_paired``, decided once at construction: whether
    its R2C along the last axis, of extent ``n`` over ``rows`` local
    rows, runs as one complex FFT per pair of rows
    (``ops/fft.pairs_rows``). ``rows`` is None where the plan's R2C is
    not ``ops/fft.rfft`` along the last axis."""
    return rows is not None and local_fft.pairs_rows(
        wisdom.plan_platform(mesh), config.double_prec, config.fft_backend,
        n, rows)


def c2r_pairing(config: Config, mesh: Optional[Mesh], n: int,
                rows: Optional[int]) -> bool:
    """A plan's ``c2r_paired``, decided once at construction like
    ``r2c_pairing``: whether its C2R along the last axis, to extent
    ``n`` over ``rows`` local rows, runs as one complex inverse FFT per
    pair of rows (``ops/fft.pairs_rows``, ``_paired_irfft``)."""
    return rows is not None and local_fft.pairs_rows(
        wisdom.plan_platform(mesh), config.double_prec, config.fft_backend,
        n, rows, inverse=True)


# The chunk rule (``fft3d_chunk_rule``). The counts it tries, fastest
# first: the R2C of 1024^3 f32 on one v5e at the platform default took
# 199.6 ms in 128 chunks, 221.9 in 64, 249.5 in 32, 272.8 in 16, 283.4 in
# 8 and 313.6 in 4 (the C2R: 209.7 in 128, 217.7 in 64; median of 9).
# 256 and 512 chunks, under 8 x-planes each, took 221.8 and 225.2, and
# the C2R in 256 held 17.3 GB. Then the share of the device's limit a
# plan's programs may fill, and the model of their temporaries by kind
# of local FFT, in full-length complex cubes (fused) and complex chunks
# (chunked), from described-v5e compiles at 1024^3 f32: fused, 8.72 GB
# for the matmul four-step, 10.75 for xla's C2R (its R2C does not
# compile), 6.48 for one direct contraction per axis; in 16 chunks, 1.2
# GB for the four-step's C2R, 2.7 for xla's R2C, 3.1 for the direct C2R
# (eval/benchmarks/tpu_v5e/MEMORY_1024.md).
FFT3D_CHUNKS = (128, 64, 32, 16, 8, 4, 2)
FFT3D_FILL = 0.85
FFT3D_TEMPS = {"fourstep": (1.0, 2.5), "direct": (0.75, 6.0),
               "xla": (1.25, 5.0)}

# HBM a program may use, by ``device_kind``, for a device that reports
# no ``memory_stats`` (a described topology): the v5e's compiler refuses
# a program past 15.75 GiB ("Used 16.00G of 15.75G hbm").
HBM_LIMIT_BY_KIND = {"TPU v5 lite": int(15.75 * 2**30)}


def device_bytes_limit(mesh: Optional[Mesh] = None) -> Optional[int]:
    """The bytes a single-device plan's programs may hold on its device
    (the mesh's first, else JAX's default): ``memory_stats()
    ["bytes_limit"]`` where the device reports one, else
    ``HBM_LIMIT_BY_KIND`` for its kind, else None (the CPU: no limit
    known)."""
    dev = next(iter(mesh.devices.flat)) if mesh is not None \
        else jax.devices()[0]
    try:
        stats = dev.memory_stats() or {}
    except Exception:  # noqa: BLE001 — a described device reports none
        stats = {}
    if stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    return HBM_LIMIT_BY_KIND.get(dev.device_kind)


def fft3d_chunk_rule(shape: Sequence[int], double_prec: bool, backend: str,
                     direct_max: Optional[int], limit: Optional[int]
                     ) -> Optional[int]:
    """The chunk count of a single-device 3D R2C/C2R plan whose Config
    names none: None (the fused program) where ``limit`` is None or the
    fused programs fit in ``FFT3D_FILL`` of it; else the first count of
    ``FFT3D_CHUNKS`` (fastest first) that divides the x extent and whose
    chunked programs fit, or else the largest that divides it (None where
    none does).

    What a program holds is modelled as argument + output + temporaries:
    the real cube and the half spectrum, plus, fused, a share of one
    full-length complex cube, and chunked, the intermediate half spectrum
    between the two loops and a multiple of one chunk's full-length
    complex block (``FFT3D_TEMPS``, by the local FFT's kind: the matmul
    backends' ``"direct"`` where every extent is at most ``direct_max``,
    else ``"fourstep"``; ``"xla"`` for the others)."""
    if limit is None:
        return None
    if backend in ("matmul", "matmul-r2", "pallas"):
        kind = ("direct" if direct_max and max(shape) <= direct_max
                else "fourstep")
    else:
        kind = "xla"
    fused_temps, chunk_temps = FFT3D_TEMPS[kind]
    r = 8 if double_prec else 4
    full = 2 * r * math.prod(shape)
    half = 2 * r * math.prod(shape[:-1]) * (shape[-1] // 2 + 1)
    budget = FFT3D_FILL * limit
    if full / 2 + half + fused_temps * full <= budget:
        return None
    counts = [c for c in FFT3D_CHUNKS if shape[0] % c == 0]
    for c in counts:
        if full / 2 + 2 * half + chunk_temps * full / c <= budget:
            return c
    return max(counts, default=None)


def _with_pad(pure: Callable[..., Any], logical_shape: Sequence[int],
              padded_shape: Sequence[int]) -> Callable[..., Any]:
    """Wrap a pure pipeline so logical-shaped input is zero-padded to the
    mesh-divisible padded shape (the traced analog of the exec_* padding
    preamble; ``jnp.pad``'s vjp slices the cotangent, so the wrapper stays
    differentiable). Padded-shaped input passes through untouched; any
    other shape raises, mirroring the exec_* validation — without this a
    shape-agnostic pipeline would silently transform a wrong-shaped input
    inconsistently with the plan."""
    logical = tuple(logical_shape)
    padded = tuple(padded_shape)

    import jax.numpy as jnp

    def fn(x: Any) -> Any:
        if tuple(x.shape) == logical:
            if logical != padded:
                x = jnp.pad(x, [(0, p - s) for p, s in zip(padded, logical)])
        elif tuple(x.shape) != padded:
            raise ValueError(
                f"input shape {tuple(x.shape)} matches neither the logical "
                f"shape {logical} nor the padded shape {padded}")
        return pure(x)

    return fn


class DistFFTPlan:
    """Base class for slab / pencil plans.

    Subclasses populate ``_in_spec`` / ``_out_spec`` (PartitionSpecs over
    ``self.mesh``) plus the per-rank size tables, and implement
    ``_build_r2c`` / ``_build_c2r`` returning jitted callables over global
    arrays. Construction is the analog of the reference's
    ``initFFT(GlobalSize*, Partition*, allocate)``.
    """

    def __init__(self, global_size: GlobalSize, partition: Partition,
                 config: Optional[Config] = None, mesh: Optional[Mesh] = None):
        self.global_size = global_size
        self.partition = partition
        self.config = config or Config()
        if wisdom.unresolved(self.config):
            # The engine constructors (SlabFFTPlan/PencilFFTPlan/
            # Batched2DFFTPlan) resolve "auto" fields via wisdom before
            # reaching here; a plan must never trace with an unresolved
            # marker (ops.fft would reject the backend much later, with a
            # far worse message).
            raise ValueError(
                "Config has unresolved 'auto' fields; construct plans "
                "through the engine classes or resolve explicitly with "
                "utils.wisdom.resolve_config(...)")
        # MXU settings resolved ONCE at plan construction: when any Config
        # knob is set, every builder reads this snapshot, so a later
        # deprecated set_* call cannot split the plan's forward and inverse
        # tracings across different knob values. An all-default Config
        # resolves to None — such plans keep deferring to the mutable
        # process defaults at trace time (legacy set_* behavior).
        self._mxu_st = self.config.mxu_settings()
        # Resilience state: the guard mode is resolved ONCE here (Config
        # field -> $DFFT_GUARDS -> off), so a mid-run env change cannot
        # split a plan's directions across modes; _guard_state holds the
        # per-direction tolerances the builders stash at wrap time.
        self._guard_mode = guards.resolved_mode(self.config)
        self._guard_state = {}
        self.mesh = mesh
        # Single-process fallback flag, exactly the reference's
        # ``fft3d = (pcnt == 1)`` (src/mpicufft.cpp:65).
        self.fft3d = mesh is None or partition.num_ranks == 1
        self._r2c = None
        self._c2r = None
        self._fwd_pure = None
        self._inv_pure = None
        self._in_spec: Optional[PartitionSpec] = None
        self._out_spec: Optional[PartitionSpec] = None

    # -- sharding queries (reference getInSize/getOutSize family) ---------

    @property
    def input_spec(self) -> PartitionSpec:
        return PartitionSpec() if self.fft3d else self._in_spec

    @property
    def output_spec(self) -> PartitionSpec:
        return PartitionSpec() if self.fft3d else self._out_spec

    @property
    def input_sharding(self) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.input_spec)

    @property
    def output_sharding(self) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.output_spec)

    @property
    def input_shape(self) -> Tuple[int, int, int]:
        """Global real-space shape (x, y, z)."""
        return self.global_size.shape

    @property
    def output_shape(self) -> Tuple[int, int, int]:
        """Global spectral shape; subclasses override where the halved axis
        is not z."""
        g = self.global_size
        return (g.nx, g.ny, g.nz_out)

    def in_sizes(self, axis: str = "x") -> List[int]:
        """Per-rank input extents along the decomposed axis(es)."""
        raise NotImplementedError

    def out_sizes(self, axis: str) -> List[int]:
        raise NotImplementedError

    # -- execution --------------------------------------------------------

    def exec_r2c(self, x: Any) -> Any:
        """Forward real-to-complex transform (reference ``execR2C``),
        inside the resilience envelope (``fallback.execute``): guards
        checked per the plan's mode, pipeline failures walk the
        degradation ladder."""
        return fallback.execute(self, "forward", x, self._get_r2c)

    def exec_c2r(self, x: Any) -> Any:
        """Inverse complex-to-real transform (reference ``execC2R``)."""
        return fallback.execute(self, "inverse", x, self._get_c2r)

    def _get_r2c(self) -> Any:
        if self._r2c is None:
            self._r2c = self._build_r2c()
        return self._r2c

    def _get_c2r(self) -> Any:
        if self._c2r is None:
            self._c2r = self._build_c2r()
        return self._c2r

    def _build_r2c(self) -> Any:
        raise NotImplementedError

    def _build_c2r(self) -> Any:
        raise NotImplementedError

    def _guard_spec(self, direction: str, dims: int = 3) -> Any:
        """The family's ``guards.GuardSpec`` for one direction (only
        consulted at modes check/enforce)."""
        raise NotImplementedError

    def _wisdom_key_args(self) -> Dict[str, Any]:
        """Key components of this plan's wisdom entry (the fallback
        ladder's demotion stamp targets the exact cell that failed)."""
        raise NotImplementedError

    # -- solver protocol ---------------------------------------------------
    # The spectral-application suite (``solvers/``) drives plans through
    # this transform-agnostic surface only: forward/inverse regardless of
    # the plan's transform family, which axes the transform covers, and
    # where the R2C halving sits. Implemented here for the DistFFTPlan
    # hierarchy (slab/pencil); ``Batched2DFFTPlan`` — outside the
    # hierarchy — honors the identical contract, so a solver written
    # against it runs on every family unchanged.

    @property
    def transform_axes(self) -> Tuple[int, ...]:
        """Axes the transform covers (3D plans: all three; the batched-2D
        plan reports (1, 2) — its axis 0 is a pure batch dimension)."""
        return (0, 1, 2)

    @property
    def transform_size(self) -> int:
        """Product of the logical extents over the TRANSFORMED axes — the
        N of this plan's DFT (solvers derive normalization scales from
        it; for a batched-2D plan it is nx*ny, not the stack volume)."""
        dims = self.input_shape
        out = 1
        for a in self.transform_axes:
            out *= int(dims[a])
        return out

    @property
    def spectral_halved_axis(self) -> Optional[int]:
        """Index of the ``n//2+1``-halved spectral axis, or None for C2C
        plans (no halving)."""
        if getattr(self, "transform", "r2c") == "c2c":
            return None
        return self._halved_axis_index()

    def _halved_axis_index(self) -> int:
        """R2C halved axis of this family (pencil halves z; the slab
        engine overrides per sequence)."""
        return 2

    def exec_fwd(self, x: Any) -> Any:
        """Forward transform through the plan's own transform family
        (r2c -> ``exec_r2c``, c2c -> ``exec_c2c``) — the solver suite's
        uniform entry point."""
        if getattr(self, "transform", "r2c") == "c2c":
            return self.exec_c2c(x)
        return self.exec_r2c(x)

    def exec_inv(self, c: Any) -> Any:
        """Inverse transform (see ``exec_fwd``)."""
        if getattr(self, "transform", "r2c") == "c2c":
            return self.exec_c2c_inv(c)
        return self.exec_c2r(c)

    # -- pure pipelines (compose under user transforms) --------------------

    def forward_fn(self) -> Callable[..., Any]:
        """The PURE forward pipeline: the same composition `exec_r2c` jits,
        but with no ``jax.jit`` wrapper and no input/output sharding
        annotations, so it composes under USER transforms — ``jax.grad``
        through the distributed spectral pipeline (all_to_all transposes
        included), an enclosing ``jax.jit``, etc. A capability the
        reference's hand-rolled MPI exchanges cannot express. The sharded
        collectives and the local transforms differentiate cleanly (the
        xla backend's rfft carries its own tangent rule so that it
        transposes under shard_map). See tests/test_autodiff.py."""
        raise NotImplementedError

    def inverse_fn(self) -> Callable[..., Any]:
        """Pure inverse pipeline (see ``forward_fn``)."""
        raise NotImplementedError

    # -- single-device fallback ------------------------------------------

    def _resolve_fft3d_chunk(self, transform: str) -> None:
        """Set ``fft3d_chunk`` (None: fused) and ``fft3d_chunk_from`` once,
        when the plan is built. Only a single-device R2C plan chunks. An
        explicit ``Config.fft3d_chunk`` is kept as given (``"config"``; 1
        is the fused program). An unset one takes ``fft3d_chunk_rule``
        against the device's memory (``device_bytes_limit``): a count
        where the fused program does not fit (``"memory"``, counted as
        ``plan.fft3d_chunk_auto``), else None."""
        self.fft3d_chunk, self.fft3d_chunk_from = None, None
        if not (self.fft3d and transform == "r2c"):
            return
        nx = self.input_shape[0]
        ck = self.config.fft3d_chunk
        if ck is not None:
            if ck > 1 and nx % ck:
                raise ValueError(f"fft3d_chunk {ck} must divide the x "
                                 f"extent {nx}")
            self.fft3d_chunk = ck if ck > 1 else None
            self.fft3d_chunk_from = "config"
            return
        dm = None
        if self.config.fft_backend in ("matmul", "matmul-r2", "pallas"):
            from ..ops import mxu_fft
            dm = (self._mxu_st or mxu_fft.default_settings()).direct_max
        ck = fft3d_chunk_rule(tuple(self.input_shape),
                              self.config.double_prec,
                              self.config.fft_backend, dm,
                              device_bytes_limit(self.mesh))
        if ck is not None:
            self.fft3d_chunk, self.fft3d_chunk_from = ck, "memory"
            obs.metrics.inc("plan.fft3d_chunk_auto")

    def _scope_family(self) -> str:
        """The plan-graph family key stage scopes are named under
        (``dfft/<family>/<node-id>``; ``obs/profile.py``)."""
        from ..analysis import contracts
        return contracts.scope_family(self)

    def _fft3d_r2c(self, jit: bool = True) -> Any:
        norm, be = self.config.norm, self.config.fft_backend
        st = self._mxu_st
        ck = self.fft3d_chunk
        fam = self._scope_family()

        def fused(x: Any) -> Any:
            return local_fft.rfftn_3d(x, norm=norm, backend=be, settings=st)

        def zy(xs: Any) -> Any:
            c = local_fft.rfft(xs, axis=-1, norm=norm, backend=be,
                               settings=st, paired=self.r2c_paired)
            return local_fft.fft(c, axis=-2, norm=norm, backend=be,
                                 settings=st)

        def x_stage(cs: Any) -> Any:
            return local_fft.fft(cs, axis=-3, norm=norm, backend=be,
                                 settings=st)

        if ck is None:
            run = obs.profile.scoped(fam, "local_fft:1", fused)
        else:
            run = _chunked_3d(fam, ck, zy, x_stage, forward=True)
        return self._jit_guarded(run, "forward") if jit else run

    def _fft3d_c2r(self, jit: bool = True) -> Any:
        norm, be = self.config.norm, self.config.fft_backend
        st = self._mxu_st
        shape = self.input_shape
        ck = self.fft3d_chunk
        fam = self._scope_family()

        def fused(c: Any) -> Any:
            return local_fft.irfftn_3d(c, shape, norm=norm, backend=be,
                                       settings=st)

        def x_stage(cs: Any) -> Any:
            return local_fft.ifft(cs, axis=-3, norm=norm, backend=be,
                                  settings=st)

        def yz(cs: Any) -> Any:
            y = local_fft.ifft(cs, axis=-2, norm=norm, backend=be,
                               settings=st)
            return local_fft.irfft(y, n=shape[-1], axis=-1, norm=norm,
                                   backend=be, settings=st,
                                   paired=self.c2r_paired)

        if ck is None:
            run = obs.profile.scoped(fam, "local_fft:1", fused)
        else:
            run = _chunked_3d(fam, ck, yz, x_stage, forward=False)
        return self._jit_guarded(run, "inverse") if jit else run

    def _fft3d_c2c(self, forward: bool, jit: bool = True) -> Any:
        """Single-device full 3D C2C (both directions unnormalized under
        FFTNorm.NONE, like cuFFT's CUFFT_FORWARD/CUFFT_INVERSE)."""
        norm, be = self.config.norm, self.config.fft_backend
        st = self._mxu_st
        axes = (-3, -2, -1)

        def run(c: Any) -> Any:
            if forward:
                return local_fft.fftn(c, axes, norm=norm, backend=be, settings=st)
            return local_fft.ifftn(c, axes, norm=norm, backend=be, settings=st)

        run = obs.profile.scoped(self._scope_family(), "local_fft:1", run)
        if not jit:
            return run
        return self._jit_guarded(run, "forward" if forward else "inverse")

    def _jit_guarded(self, run: Callable[..., Any],
                     direction: str) -> Any:
        """Jit a single-device pipeline with the guard wrapper applied at
        modes check/enforce (``guards.maybe_wrap``; a no-op pass-through —
        same callable, identical HLO — at "off")."""
        run, _ = guards.maybe_wrap(self, run, direction)
        return jax.jit(run)

    # -- staged-execution helper (shared by slab/pencil/batched2d) ---------

    def _jit_stages(self, specs: Sequence[Tuple[Any, ...]]) -> List[Any]:
        # Staged execution only exists on multi-device plans (the
        # single-device fallback never builds stage specs), so the mesh
        # is always resolved here — narrow the Optional for mypy.
        assert self.mesh is not None, "staged execution needs a device mesh"
        return jit_stages(self.mesh, specs)


def jit_stages(mesh: Mesh,
               specs: Sequence[Tuple[Any, ...]]) -> List[Tuple[Any, Any]]:
    """Jit each (desc, body, in_spec, out_spec) as its own shard_mapped
    program so per-phase timers can fence between them. Module-level so
    plans outside the DistFFTPlan hierarchy (Batched2DFFTPlan) share the
    exact stage contract."""
    out = []
    for desc, fn, ispec, ospec in specs:
        sm = jax.shard_map(fn, mesh=mesh, in_specs=ispec, out_specs=ospec)
        out.append((desc, jax.jit(
            sm, in_shardings=NamedSharding(mesh, ispec),
            out_shardings=NamedSharding(mesh, ospec))))
    return out


def _chunked_3d(family: str, ck: int, planes: Callable[..., Any],
                x_stage: Callable[..., Any], forward: bool
                ) -> Callable[..., Any]:
    """The single-device 3D R2C (``forward``: ``planes`` then
    ``x_stage``) or C2R (``x_stage`` then ``planes``) as two loops of
    ``ck`` steps, so that a step's temporaries are a chunk's and not the
    cube's. ``planes`` transforms the two trailing axes of ``ck`` chunks
    of x-planes (``lax.map``, under the stage scope ``chunk:1``).
    ``x_stage`` transforms axis 0 of ``ck`` blocks of z-planes of the
    half spectrum, each written back in place (``fori_loop``, under
    ``chunk:2``), then of the planes left over (the Nyquist plane). The
    half spectrum's z axis is the major one of its TPU layout, so a block
    is one contiguous slice; blocks of y columns made the compiler lay the
    whole spectrum out again before and after the loop (17.3 GB for the
    1024^3 C2R on a v5e). Neither scope is a graph node: the transforms
    keep their innermost ``local_fft:1.ax<k>``, so only the slices, the
    writes and the loops carry the chunk scopes (metadata only)."""
    planes = obs.profile.scoped(family, "local_fft:1", planes)
    x_stage = obs.profile.scoped(family, "local_fft:1", x_stage)

    def plane_loop(x: Any) -> Any:
        with obs.profile.stage_scope(family, "chunk:1"):
            ys = jax.lax.map(planes, x.reshape((ck, -1) + x.shape[1:]))
            return ys.reshape((x.shape[0],) + ys.shape[2:])

    def block_loop(c: Any) -> Any:
        b = c.shape[2] // ck

        def one(j: Any, c: Any) -> Any:
            blk = jax.lax.dynamic_slice_in_dim(c, j * b, b, axis=2)
            return jax.lax.dynamic_update_slice_in_dim(c, x_stage(blk),
                                                       j * b, axis=2)

        with obs.profile.stage_scope(family, "chunk:2"):
            if b:
                c = jax.lax.fori_loop(0, ck, one, c)
            if ck * b == c.shape[2]:
                return c
            return jax.lax.dynamic_update_slice_in_dim(
                c, x_stage(c[:, :, ck * b:]), ck * b, axis=2)

    if forward:
        return lambda x: block_loop(plane_loop(x))
    return lambda c: plane_loop(block_loop(c))
