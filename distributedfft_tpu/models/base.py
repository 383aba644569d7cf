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

from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
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

    def _chunk_for(self, nx: int) -> Optional[int]:
        """Validated ``Config.fft3d_chunk`` for a leading extent of
        ``nx`` (None = fused path)."""
        ck = self.config.fft3d_chunk
        if not ck or ck <= 1:
            return None
        if nx % ck:
            raise ValueError(f"fft3d_chunk {ck} must divide the x extent "
                             f"{nx}")
        return ck

    def _scope_family(self) -> str:
        """The plan-graph family key stage scopes are named under
        (``dfft/<family>/<node-id>``; ``obs/profile.py``)."""
        from ..analysis import contracts
        return contracts.scope_family(self)

    def _fft3d_r2c(self, jit: bool = True) -> Any:
        norm, be = self.config.norm, self.config.fft_backend
        st = self._mxu_st
        ck = self._chunk_for(self.input_shape[0])

        def run(x: Any) -> Any:
            if ck is None:
                return local_fft.rfftn_3d(x, norm=norm, backend=be,
                                          settings=st)
            # Memory-bounded large-cube path: z+y stages per leading-axis
            # chunk (lax.map serializes them, capping the four-step
            # relayout temporaries at chunk size); the x stage needs the
            # full axis and runs on the already-halved spectrum.
            nx = x.shape[0]

            def per(xs: Any) -> Any:
                c = local_fft.rfft(xs, axis=-1, norm=norm, backend=be,
                                   settings=st, paired=self.r2c_paired)
                return local_fft.fft(c, axis=-2, norm=norm, backend=be,
                                     settings=st)

            cs = jnp.reshape(x, (ck, nx // ck) + x.shape[1:])
            c = jnp.reshape(jax.lax.map(per, cs),
                            (nx,) + x.shape[1:-1] + (x.shape[-1] // 2 + 1,))
            return local_fft.fft(c, axis=-3, norm=norm, backend=be,
                                 settings=st)

        run = obs.profile.scoped(self._scope_family(), "local_fft:1", run)
        return self._jit_guarded(run, "forward") if jit else run

    def _fft3d_c2r(self, jit: bool = True) -> Any:
        norm, be = self.config.norm, self.config.fft_backend
        st = self._mxu_st
        shape = self.input_shape
        ck = self._chunk_for(shape[0])

        def run(c: Any) -> Any:
            if ck is None:
                return local_fft.irfftn_3d(c, shape, norm=norm, backend=be,
                                           settings=st)
            nz = shape[-1]
            c = local_fft.ifft(c, axis=-3, norm=norm, backend=be,
                               settings=st)

            def per(cs: Any) -> Any:
                y = local_fft.ifft(cs, axis=-2, norm=norm, backend=be,
                                   settings=st)
                return local_fft.irfft(y, n=nz, axis=-1, norm=norm,
                                       backend=be, settings=st)

            nx = c.shape[0]
            ys = jnp.reshape(c, (ck, nx // ck) + c.shape[1:])
            return jnp.reshape(jax.lax.map(per, ys), (nx,) + shape[1:])

        run = obs.profile.scoped(self._scope_family(), "local_fft:1", run)
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
