"""Plan-time autotuning of the local-FFT backend.

cuFFT benchmarks algorithm variants inside plan creation; the reference
inherits that (its `cufftMakePlanMany64` picks kernels per shape,
``src/slab/default/mpicufft_slab.cpp:137-167``) and spends its whole harness
comparing comm-method variants. This module is the TPU rendering of both: for
a given local shard shape it races the framework's interchangeable local-FFT
backends (``ops/fft.py``: xla / matmul / pallas, and the matmul backend's MXU
precision levels) ON THE CURRENT DEVICE, gates candidates on a round-trip
accuracy budget, and returns the fastest — so ``Config.fft_backend`` can be
chosen by measurement instead of folklore. Measured v5e example (256^3 f32
roundtrip, round 2): xla 4.89 ms, matmul@HIGHEST 2.61 ms, matmul@HIGH
1.48 ms, matmul-r2@HIGH 2.64 ms, pallas (fused two-stage kernels) 3.17 ms —
a 3.3x spread that no static default gets right on every platform (on CPU,
xla wins by a similar margin; the pallas negative-result analysis lives in
``ops/pallas_fft.py``, the radix-2 one at ``mxu_fft.MXUSettings.radix2``).

Timing comes from the shared chained-roundtrip harness
(``testing/chaintimer.py``, also used by bench.py): median of (t_K - t_1)
pairs of a scalar-fenced jitted fori_loop chain. Use ``k`` large enough
that the measured work dominates the per-call constant (bench.py uses
257 at 256^3); a nonpositive median is reported as a degenerate
measurement, not a timing.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..params import OVERLAP_DEPTHS, FFTNorm
from ..resilience import inject
from . import chaintimer


class CellTimeout(RuntimeError):
    """A race cell exceeded its wall-clock budget (resilience leg 4)."""


def _cell_timeout_s() -> Optional[float]:
    """Per-cell wall-clock budget (``$DFFT_AUTOTUNE_CELL_TIMEOUT_S``,
    default 600 s; 0/negative disables). Generous by default — it exists
    to stop one WEDGED candidate (hung compile, deadlocked collective
    attempt) from stalling the whole race forever, not to clip slow
    ones."""
    raw = os.environ.get("DFFT_AUTOTUNE_CELL_TIMEOUT_S", "").strip()
    try:
        v = float(raw) if raw else 600.0
    except ValueError:
        v = 600.0
    return v if v > 0 else None


def _call_with_timeout(fn, label: str):
    """Run one race cell under the wall-clock budget: the cell runs in a
    daemon thread and an expiry raises ``CellTimeout`` — the racer then
    records the candidate as failed and the surviving candidates decide
    the race (a hung candidate degrades, never wedges). The abandoned
    thread keeps running detached (a truly hung computation cannot be
    interrupted portably); daemon status keeps it from blocking process
    exit. DISABLED in multi-controller runs: abandoning a collective on
    one process while its peers wait would trade a local hang for a
    distributed one — there the coordinator-level timeouts own the
    problem."""
    import jax
    timeout = _cell_timeout_s()
    if timeout is None or jax.process_count() > 1:
        return fn()
    box = {}

    def run():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["error"] = e

    t = threading.Thread(target=run, daemon=True,
                         name=f"autotune-cell:{label}")
    t.start()
    t.join(timeout)
    if t.is_alive():
        obs.metrics.inc("autotune.cell_timeouts")
        obs.notice(
            f"autotune: cell {label} exceeded {timeout:.0f}s; abandoned "
            "(surviving candidates decide the race)",
            name="autotune.cell_timeout", label=label, timeout_s=timeout)
        raise CellTimeout(f"race cell exceeded {timeout:.0f}s wall clock")
    if "error" in box:
        raise box["error"]
    return box["value"]


@dataclass
class Candidate:
    backend: str
    precision: Optional[str]  # matmul-only: "high" | "highest"
    direct_max: Optional[int] = None  # matmul-only: direct-plan threshold
    per_iter_ms: float = float("nan")
    rel_err: float = float("nan")
    ok: bool = False
    error: Optional[str] = None

    @property
    def label(self) -> str:
        base = self.backend if self.precision is None \
            else f"{self.backend}@{self.precision}"
        if self.direct_max is not None:
            base += f" direct({self.direct_max})"
        return base


def _measure(shape, backend: str, k: int, repeats: int, inner: int,
             x, x_absmax: float,
             settings=None) -> Tuple[float, float, Optional[str]]:
    """(per-iteration ms, roundtrip rel err, degeneracy note)."""
    import jax
    import jax.numpy as jnp

    from ..ops import fft as lf

    # Accuracy: roundtrip error relative to the input's max magnitude,
    # reduced to a scalar on device (no array readback).
    scale = 1.0 / float(np.prod(shape))
    err_fn = jax.jit(lambda a: jnp.max(jnp.abs(
        lf.irfftn_3d(lf.rfftn_3d(a, norm=FFTNorm.NONE, backend=backend,
                                 settings=settings),
                     tuple(shape), norm=FFTNorm.NONE, backend=backend,
                     settings=settings)
        * scale - a)))
    rel = float(err_fn(x)) / x_absmax

    fn1 = chaintimer.roundtrip_chain(1, shape, backend, settings=settings)
    fnK = chaintimer.roundtrip_chain(k, shape, backend, settings=settings)
    float(fn1(x))  # compile + warm
    float(fnK(x))
    per_ms, _ = chaintimer.median_pair_diff_ms(fn1, fnK, x, k, repeats, inner)
    if per_ms <= 0:
        return per_ms, rel, (f"degenerate timing (median t_K-t_1 <= 0 at "
                             f"k={k}; raise k so the work dominates noise)")
    return per_ms, rel, None


def autotune_local_fft(shape: Sequence[int], budget_rel_err: float = 1e-4,
                       k: int = 257, repeats: int = 3, inner: int = 3,
                       backends: Optional[Sequence[str]] = None,
                       double_prec: bool = False,
                       seed: int = 0, verbose: bool = False) -> List[Candidate]:
    """Race the local-FFT backends for a 3D R2C+C2R roundtrip of ``shape``
    on the current default device.

    ``double_prec`` races the f64 path instead (requires ``jax_enable_x64``;
    the matmul backend then always runs at HIGHEST, so only one matmul
    candidate is raced). Returns candidates sorted fastest-first; entries
    failing the accuracy budget, measuring degenerately, or crashing have
    ``ok=False`` (with ``error`` set for the latter two) and sort last.
    Apply the winner with ``apply_best``.
    """
    import jax

    from ..ops import fft as lf
    from ..ops import mxu_fft

    if backends is None:
        backends = lf.BACKENDS
    dt = np.float64 if double_prec else np.float32
    xs = np.random.default_rng(seed).random(tuple(shape)).astype(dt)
    x_absmax = float(np.abs(xs).max()) or 1.0
    x = jax.device_put(xs)

    cands: List[Candidate] = []
    n_max = int(max(shape))
    from ..ops.bluestein import is_smooth
    for b in backends:
        if b == "bluestein" and all(is_smooth(int(n)) for n in shape):
            # On a 5-smooth shape the bluestein backend delegates every
            # axis to the XLA expansion and is bit-identical to "xla" —
            # racing it would time the same program twice. It joins the
            # race exactly when some axis would otherwise fall off the
            # fast path (prime / non-smooth lengths).
            continue
        if b in ("matmul", "matmul-r2") and not double_prec:
            cands += [Candidate(b, "high"), Candidate(b, "highest")]
            # Past the deployed direct threshold the default plan is the
            # four-step factorization; race the all-direct plan too — on
            # v5e at 1024^3 direct once beat the four-step 2.9x (652 vs
            # 228 GFLOPS, pre-PR-12, not measured on the current code), a
            # winner no precision-only race can find. (matmul only: radix-2's
            # direct_max interacts with its split base.)
            if b == "matmul" and n_max > mxu_fft.current_settings(
                    ).direct_max:
                cands.append(Candidate(b, "high", direct_max=n_max))
        else:
            cands.append(Candidate(b, None))

    if k < 2:
        raise ValueError(f"k must be >= 2 (got {k}): the (t_K - t_1) pair "
                         "difference needs at least one extra iteration")
    import dataclasses as dc
    for c in cands:
        # Matmul variants race at their own precision (and, for the
        # all-direct candidate, direct_max) via an explicit MXUSettings
        # (context-scoped, so nothing leaks between candidates or into
        # the process defaults). The base is the DEPLOYED defaults — only
        # the raced knobs vary — so the measurement predicts the
        # configuration apply_best's Config resolves to at build time
        # (the unraced knobs fall back to the same defaults). Candidates
        # without a precision (xla, pallas, f64 matmul) race at the
        # deployed defaults unchanged.
        st = None
        if c.precision is not None:
            st = dc.replace(mxu_fft.current_settings(),
                            precision=mxu_fft.as_precision(c.precision))
            if c.direct_max is not None:
                st = dc.replace(st, direct_max=c.direct_max)
        obs.metrics.inc("autotune.race_cells")
        try:
            with obs.span("autotune.race_cell", race="local_fft",
                          label=c.label):
                def cell(c=c, st=st):
                    # The injected hang runs INSIDE the timed cell, so a
                    # simulated wedge exercises the timeout, not the race.
                    inject.maybe_hang_cell(c.label)
                    return _measure(shape, c.backend, k, repeats, inner,
                                    x, x_absmax, settings=st)

                c.per_iter_ms, c.rel_err, c.error = _call_with_timeout(
                    cell, c.label)
            c.ok = (c.error is None and c.rel_err <= budget_rel_err)
        except Exception as e:  # backend unavailable / cell timed out
            c.error = f"{type(e).__name__}: {e}"
        if verbose:
            print(f"  {c.label:16s} {c.per_iter_ms:8.3f} ms  "
                  f"rel_err {c.rel_err:.2e}  ok={c.ok}"
                  + (f"  ({c.error})" if c.error else ""), flush=True)

    # NaN per_iter_ms (crashed before timing) must not poison the sort key:
    # tuple comparison with NaN gives undefined ordering among failures.
    return sorted(cands, key=lambda c: (
        not c.ok,
        c.per_iter_ms if np.isfinite(c.per_iter_ms) else float("inf")))


def describe_failures(candidates: List[Candidate]) -> str:
    """Human-readable reason per non-ok candidate (crash/degenerate vs
    accuracy), so a failed tune is diagnosed correctly."""
    parts = []
    for c in candidates:
        if c.ok:
            continue
        parts.append(f"{c.label}: {c.error}" if c.error
                     else f"{c.label}: rel_err {c.rel_err:.2e} over budget")
    return "; ".join(parts)


@dataclass
class CommCandidate:
    """One point of the comm-strategy matrix (the reference's primary
    comparative dimension, ``include/mpicufft_slab.hpp:145-158``): global-
    redistribution strategy per transpose x data-layout opt, optionally
    crossed with the send-method axis (``send``/``chunks``: the STREAMS
    chunked-pipelined transpose at a given piece count, or the RING
    ppermute rendering; ``send=None`` keeps the base config's monolithic
    SYNC exchange — the reference's ``-snd``/``-snd2`` dimension) and the
    wire-dtype axis (``wire``: the exchange payload encoding —
    ``"bf16"`` candidates carry their measured forward error vs the
    native reference in ``wire_rel_err`` and are GATED on the error
    budget; ``wire=None`` keeps the base config's wire and is never
    folded, so an un-raced axis cannot clobber an explicit choice). The
    overlap-schedule axes follow the same contract: ``depth`` is the
    revolving-buffer ring depth (``Config.overlap_depth``) and
    ``subblocks`` the per-peer sub-block split
    (``Config.overlap_subblocks``) — a SYNC candidate with
    ``subblocks>1`` races the pipelined all-to-all rendering
    (``parallel/transpose.pipelined_all_to_all``); ``None`` keeps the
    base config's knob and is never folded."""
    comm: object                 # CommMethod for transpose 1
    comm2: Optional[object]      # pencil transpose 2 (None for slab)
    opt: int
    send: object = None          # SendMethod.STREAMS/RING variants only
    chunks: Optional[int] = None  # streams_chunks for send=STREAMS
    wire: Optional[str] = None   # wire dtype; None = base config's (unraced)
    depth: Optional[int] = None  # overlap_depth; None = base's (unraced)
    subblocks: Optional[int] = None  # overlap_subblocks; None = base's
    fwd_ms: float = float("nan")
    inv_ms: float = float("nan")
    wire_rel_err: float = float("nan")  # bf16 only: fwd max rel err vs native
    ok: bool = False
    error: Optional[str] = None

    @property
    def total_ms(self) -> float:
        return self.fwd_ms + self.inv_ms

    @property
    def label(self) -> str:
        c1 = self.comm.value
        tag = c1 if self.comm2 is None else f"{c1}+{self.comm2.value}"
        tag = f"{tag}/opt{self.opt}"
        name = getattr(self.send, "name", None)
        if name == "RING":
            tag += "/ring"
        elif name == "RING_OVERLAP":
            tag += "/ring-ovl"
            if self.depth not in (None, 2):
                tag += f"-d{self.depth}"
        elif name == "STREAMS":
            tag += f"/streams{self.chunks}"
        elif (name in ("SYNC", "MPI_TYPE")
                and self.subblocks not in (None, 1)):
            tag += "/a2a-pipe"
        if self.subblocks not in (None, 1):
            tag += f"/sub{self.subblocks}"
        if self.wire not in (None, "native"):
            tag += f"/{self.wire}"
        return tag


def _time_plan_ms(fn, x, iterations: int, warmup: int) -> float:
    """Wall-clock one jitted plan program via the shared microbench harness
    (block_until_ready fence — comm tuning targets multi-device meshes;
    a single chip has no comm axis to tune)."""
    from .microbench import _time_fn

    return _time_fn(fn, x, iterations, warmup) * 1e3


def _measure_comm_candidates(cands, kind, global_size, partition, base,
                             mesh, sequence, dims, transform, iterations,
                             warmup, seed, budget, verbose):
    """Shared measurement loop of the comm/wire racers: time every
    candidate's forward+inverse on the active mesh, and gate compressed-
    wire candidates on their measured forward error vs the FIRST
    successful native candidate's output (all native renderings agree on
    the forward output, so one reference serves every twin). Candidate
    lists must therefore order natives before compressed twins."""
    import dataclasses as dc

    import numpy as np

    from . import testcases as tc

    from ..resilience import fallback

    rdt = np.float64 if base.double_prec else np.float32
    xs = np.random.default_rng(seed).random(
        tuple(global_size.shape)).astype(rdt)
    ref_spec = None
    for c in cands:
        obs.metrics.inc("autotune.race_cells")
        try:
            with obs.span("autotune.race_cell", race="comm", label=c.label):
                # guards="off": the race must time the production program
                # without the guard readback; fallback.suppressed(): a
                # failing candidate must LOSE the race, not measure its
                # own silent demotion.
                cfg = dc.replace(base, comm_method=c.comm,
                                 comm_method2=c.comm2, opt=c.opt,
                                 guards="off")
                if c.send is not None:
                    cfg = dc.replace(cfg, send_method=c.send,
                                     send_method2=None,
                                     streams_chunks=c.chunks)
                if c.depth is not None:
                    cfg = dc.replace(cfg, overlap_depth=int(c.depth))
                if c.subblocks is not None:
                    cfg = dc.replace(cfg,
                                     overlap_subblocks=int(c.subblocks))
                if c.wire is not None:
                    cfg = dc.replace(cfg, wire_dtype=c.wire)

                def cell(cfg=cfg, label=c.label):
                    inject.maybe_hang_cell(label)
                    with fallback.suppressed():
                        plan = tc.make_plan(kind, global_size, partition,
                                            cfg, sequence=sequence,
                                            mesh=mesh, transform=transform)
                        x = plan.pad_input(xs)
                        fwd, inv = tc._fused_fns(plan, dims)
                        fwd_ms = _time_plan_ms(fwd, x, iterations, warmup)
                        spec = fwd(x)
                        inv_ms = _time_plan_ms(inv, spec, iterations,
                                               warmup)
                    return fwd_ms, spec, inv_ms

                c.fwd_ms, spec, c.inv_ms = _call_with_timeout(cell, c.label)
                compressed = c.wire not in (None, "native")
                if not compressed and ref_spec is None:
                    ref_spec = spec
                if compressed:
                    # The gate runs BEFORE ok is set: a lossy candidate
                    # whose accuracy could not be established (no native
                    # reference, or the error computation itself failed)
                    # must never rank as usable.
                    if ref_spec is None:
                        raise RuntimeError(
                            "no native reference measured before the "
                            "compressed candidate (racer list-order "
                            "contract)")
                    from .microbench import max_rel_err
                    c.wire_rel_err = max_rel_err(spec, ref_spec)
                    if not c.wire_rel_err <= budget:
                        c.error = (f"wire rel err {c.wire_rel_err:.2e} over "
                                   f"budget {budget:.0e}")
                        obs.metrics.inc("wire.budget_rejections")
                        obs.event("wire.budget_rejected", label=c.label,
                                  rel_err=float(c.wire_rel_err),
                                  budget=float(budget))
                    else:
                        c.ok = True
                else:
                    c.ok = True
        except Exception as e:  # strategy unavailable for this shape/mesh
            c.ok = False
            c.error = f"{type(e).__name__}: {e}"
        if verbose:
            werr = ("" if not np.isfinite(c.wire_rel_err)
                    else f"  wire_err {c.wire_rel_err:.2e}")
            print(f"  {c.label:28s} fwd {c.fwd_ms:8.3f} ms  "
                  f"inv {c.inv_ms:8.3f} ms  ok={c.ok}{werr}"
                  + (f"  ({c.error})" if c.error else ""), flush=True)


def _rank_and_agree(cands) -> List[CommCandidate]:
    """Sort measured candidates fastest-first, then (multi-controller
    only) force agreement on process 0's winner — candidates are
    routinely within noise, and divergent Configs build mismatched
    collective programs across processes (hang). The broadcast is
    UNCONDITIONAL (sentinel -1 = "nothing ok here"): a process whose
    candidates all failed locally must still issue the same collective as
    its peers or the agreement step deadlocks."""
    import numpy as np

    ranked = sorted(cands, key=lambda c: (
        not c.ok,
        c.total_ms if np.isfinite(c.total_ms) else float("inf")))

    import jax
    if jax.process_count() > 1 and ranked:
        from jax.experimental import multihost_utils
        idx = (next(i for i, c in enumerate(cands) if c is ranked[0])
               if ranked[0].ok else -1)
        idx = int(multihost_utils.broadcast_one_to_all(np.int32(idx)))
        if idx >= 0:
            win = cands[idx]
            ranked.remove(win)
            ranked.insert(0, win)
        else:
            # Process 0 saw no usable strategy: fail identically everywhere
            # (a per-process mix of success and failure diverges later).
            for c in ranked:
                c.ok = False
                c.error = c.error or "process 0 had no usable strategy"
    return ranked


def autotune_comm(kind: str, global_size, partition, base_config=None,
                  mesh=None, sequence=None, iterations: int = 5,
                  warmup: int = 2, race_opt: bool = True, seed: int = 0,
                  dims: int = 3, transform: str = "r2c",
                  race_send: bool = False,
                  streams_chunks: Sequence[int] = (4,),
                  overlap_depths: Sequence[int] = OVERLAP_DEPTHS,
                  overlap_splits: Sequence[int] = (1, 2),
                  race_wire: bool = False,
                  wire_error_budget: Optional[float] = None,
                  verbose: bool = False) -> List[CommCandidate]:
    """Race the communication strategies for a plan shape ON the active
    mesh: ALL2ALL (explicit ``lax.all_to_all``) vs PEER2PEER (GSPMD
    resharding) per transpose, crossed with the opt 0/1 layout axis — at
    scale the transpose is >=97% of runtime (BASELINE.md), so this axis,
    not the local-FFT backend, decides the plan. Pencil plans race the
    2x2 (comm1 x comm2) matrix like the reference's ``-comm1/-comm2``.

    ``dims`` is the pencil partial-transform depth (reference --fft-dim):
    the race times the SAME program the run will execute — at dims=2 only
    transpose 1 runs, so comm2 is not raced (it would be noise), and at
    dims=1 there is no transpose at all (every candidate ties).

    ``race_send=True`` adds the send-method axis: each ALL2ALL point also
    races the STREAMS chunked-pipelined transpose at every piece count in
    ``streams_chunks`` (the reference's ``-snd`` dimension), a pipelined
    all-to-all candidate per sub-block split in ``overlap_splits`` > 1
    (the SYNC collective software-pipelined in
    ``parallel/transpose.pipelined_all_to_all`` — it wraps the cell's own
    ``lax.all_to_all``, so it IS raced per opt point), ONE
    ``SendMethod.RING`` candidate (the ppermute ring rendering,
    ``parallel/transpose.ring_transpose``) and one ``RING_OVERLAP``
    candidate per ``overlap_depths`` x ``overlap_splits`` cell (the
    revolving-buffer ring schedule — bit-identical output, reordered
    issue; depth and sub-block split change how far the schedule runs
    ahead of the arrivals, so each combination races as its own cell and
    the wisdom store records whichever schedule won — store schema v5;
    the depth-2/split-1 cell is the shipped double-buffered default and
    keeps its legacy ``/ring-ovl`` label). The rings own the exchange
    rendering regardless of comm_method and ignore the opt layout axis
    (both are properties of the ``lax.all_to_all`` they replace), so each
    races once — under the first opt's ALL2ALL point — not per cell.
    PEER2PEER points are not crossed — GSPMD re-fuses piece reshards into
    one collective (measured, ``models/slab._assemble_pure``), so a
    P2P+STREAMS candidate would mismeasure a program identical to SYNC.

    ``race_wire=True`` adds the wire-dtype axis: every candidate cell
    gains a ``wire="bf16"`` twin (compression interacts with the
    rendering — per-block on the ring, whole-payload on the collectives —
    so the wire axis is crossed, not raced once like the ring). Twins are
    GATED on accuracy: each twin's forward output is compared against the
    first native candidate's (max rel error, relative to the reference's
    max magnitude, computed on device) and a twin over
    ``wire_error_budget`` (None -> the base config's
    ``resolved_wire_budget``) is marked not-ok, so a lossy wire can only
    win inside the user's error budget. Natives then carry
    ``wire="native"`` explicitly, so the fold records whichever side won.

    Returns candidates sorted by measured forward+inverse time; apply the
    winner with ``apply_best_comm``.
    """
    import dataclasses as dc

    from ..params import AUTO, CommMethod, Config, SendMethod

    base = base_config or Config()
    if base.wire_dtype == AUTO or race_wire:
        # Candidate plans must never construct with an unresolved marker
        # (recursion into wisdom resolution), and race_wire OWNS the
        # axis: any base wire — "auto" or an explicit "bf16" — is
        # normalized to native so un-twinned candidates are the error
        # reference and only the explicit twins run compressed (an
        # un-normalized bf16 base would run every candidate lossy with
        # the accuracy gate silently skipped).
        base = dc.replace(base, wire_dtype="native")
    budget = (wire_error_budget if wire_error_budget is not None
              else base.resolved_wire_budget())
    both = (CommMethod.ALL2ALL, CommMethod.PEER2PEER)
    opts = (0, 1) if race_opt else (base.opt,)
    race_comm2 = kind == "pencil" and dims >= 3
    # Normalized overlap axes: dedup, clamp to valid values, and keep the
    # shipped default first so the depth-2/split-1 cell is the legacy
    # candidate (depth/subblocks=None -> the base config's knobs).
    depth_axis = tuple(dict.fromkeys(
        int(d) for d in overlap_depths if int(d) >= 2)) or (2,)
    split_axis = tuple(dict.fromkeys(
        int(s) for s in overlap_splits if int(s) >= 1)) or (1,)
    cands: List[CommCandidate] = []
    for opt in opts:
        for c1 in both:
            pairs = [(c1, c2) for c2 in both] if race_comm2 else [(c1, None)]
            for cc1, cc2 in pairs:
                cands.append(CommCandidate(cc1, cc2, opt))
                if (race_send and cc1 is CommMethod.ALL2ALL
                        and cc2 in (None, CommMethod.ALL2ALL)):
                    cands += [CommCandidate(cc1, cc2, opt,
                                            send=SendMethod.STREAMS,
                                            chunks=int(k))
                              for k in streams_chunks if k and int(k) > 1]
                    # The pipelined all-to-all wraps THIS cell's
                    # lax.all_to_all (opt changes the realignment it
                    # fuses), so it races per opt point — unlike the
                    # rings below.
                    cands += [CommCandidate(cc1, cc2, opt,
                                            send=SendMethod.SYNC,
                                            subblocks=int(s))
                              for s in split_axis if int(s) > 1]
                    if opt == opts[0]:
                        # The rings are opt- and comm-agnostic (they
                        # replace the all_to_all those knobs
                        # parameterize): one candidate each, not a
                        # duplicate per matrix cell. RING_OVERLAP cells
                        # are distinct per depth x sub-block split —
                        # same math, reordered schedule, different time
                        # wherever the scheduler can overlap.
                        cands.append(CommCandidate(cc1, cc2, opt,
                                                   send=SendMethod.RING))
                        for d in depth_axis:
                            for s in split_axis:
                                cands.append(CommCandidate(
                                    cc1, cc2, opt,
                                    send=SendMethod.RING_OVERLAP,
                                    depth=None if d == 2 else d,
                                    subblocks=None if s == 1 else s))
    if race_wire:
        # Natives first (the twins' error reference), then the bf16 twin
        # of every cell. Explicit wire on both sides: the raced axis is
        # always folded, an unraced one (wire=None) never is.
        for c in cands:
            c.wire = "native"
        cands = cands + [dc.replace(c, wire="bf16") for c in cands]

    with obs.span("autotune.race_comm", kind=kind,
                  shape=list(global_size.shape), cells=len(cands),
                  race_wire=bool(race_wire)):
        _measure_comm_candidates(cands, kind, global_size, partition, base,
                                 mesh, sequence, dims, transform, iterations,
                                 warmup, seed, budget, verbose)
        return _rank_and_agree(cands)


def autotune_wire(kind: str, global_size, partition, base_config=None,
                  mesh=None, sequence=None, iterations: int = 5,
                  warmup: int = 2, seed: int = 0, dims: int = 3,
                  transform: str = "r2c",
                  error_budget: Optional[float] = None,
                  verbose: bool = False) -> List[CommCandidate]:
    """Race ONLY the wire-dtype axis on the base config's fixed comm/send
    rendering — the ``Config(wire_dtype="auto")`` path when the comm
    choice is explicit (a concrete ``comm_method`` must not be re-raced
    behind the user's back; compare ``autotune_comm(race_wire=True)``,
    which owns both axes for ``comm_method="auto"``).

    Two candidates: the base rendering at ``wire="native"`` (the error
    reference) and at ``wire="bf16"``, gated on ``error_budget`` (None ->
    the base config's ``resolved_wire_budget``) exactly like the combined
    race's twins. Returns candidates sorted fastest-first (budget
    failures last); fold the winner with ``apply_best_comm``.
    """
    import dataclasses as dc

    from ..params import AUTO, Config

    base = base_config or Config()
    if base.wire_dtype == AUTO:
        base = dc.replace(base, wire_dtype="native")
    budget = (error_budget if error_budget is not None
              else base.resolved_wire_budget())
    comm2 = base.comm_method2 if kind == "pencil" else None
    # send stays None: the measurement then runs the base config's send
    # methods UNCHANGED (send_method2 included) — setting it would make
    # _measure_comm_candidates normalize send_method2 to None and the race
    # would time/gate a rendering the caller never runs.
    cands = [CommCandidate(base.comm_method, comm2, base.opt, wire=w)
             for w in ("native", "bf16")]
    with obs.span("autotune.race_wire", kind=kind,
                  shape=list(global_size.shape)):
        _measure_comm_candidates(cands, kind, global_size, partition, base,
                                 mesh, sequence, dims, transform, iterations,
                                 warmup, seed, budget, verbose)
        return _rank_and_agree(cands)


def apply_best_comm(candidates: List[CommCandidate], base_config=None):
    """Winning comm matrix folded into a Config. Raises when nothing ran."""
    import dataclasses as dc

    from ..params import Config

    best = candidates[0]
    if not best.ok:
        errs = "; ".join(f"{c.label}: {c.error}" for c in candidates)
        raise RuntimeError(f"comm autotune: no strategy ran; {errs}")
    cfg = dc.replace(base_config or Config(), comm_method=best.comm,
                     opt=best.opt)
    if best.comm2 is not None:
        # Only overwrite comm2 when it was actually raced (pencil, dims=3);
        # otherwise a user's explicit --comm-method2 must survive, or the
        # benchmark CSVs get filed under a strategy nobody selected.
        cfg = dc.replace(cfg, comm_method2=best.comm2)
    if best.send is not None:
        # The send axis was raced (race_send) and a STREAMS variant won:
        # the piece count travels with it — send=None keeps the base
        # config's send method (a SYNC win must not clobber an explicit
        # --send-method the caller chose not to race).
        cfg = dc.replace(cfg, send_method=best.send, send_method2=None,
                         streams_chunks=best.chunks)
    if best.depth is not None:
        # Overlap axes fold exactly like the send/wire ones: only when
        # raced, so an unraced candidate keeps the caller's knobs.
        cfg = dc.replace(cfg, overlap_depth=int(best.depth))
    if best.subblocks is not None:
        cfg = dc.replace(cfg, overlap_subblocks=int(best.subblocks))
    if best.wire is not None:
        # Same contract for the wire axis: fold only when it was raced
        # (race_wire / autotune_wire set it explicitly on every
        # candidate); wire=None preserves the caller's wire_dtype.
        cfg = dc.replace(cfg, wire_dtype=best.wire)
    return cfg


def apply_best(candidates: List[Candidate]):
    """Translate the winning candidate into a ``Config``: the backend plus,
    for matmul variants, the raced precision and direct-plan threshold as
    PLAN state (``Config.mxu_precision`` / ``Config.mxu_direct_max`` — no
    process globals are touched, so other plans in the process are
    unaffected). Raises when no candidate passed."""
    from ..params import Config

    best = candidates[0]
    if not best.ok:
        raise RuntimeError(
            f"autotune: no usable backend; {describe_failures(candidates)}")
    return Config(fft_backend=best.backend, mxu_precision=best.precision,
                  mxu_direct_max=best.direct_max)
