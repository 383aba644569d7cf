"""Communication microbenchmarks — the analog of the reference's
``reference`` executable testcases 1-3 (``tests/src/reference/reference.cu``,
``tests/include/tests_reference.hpp:53-96``), which measure raw exchange
bandwidth for 1D/2D/3D-strided layouts to attribute transpose cost to memcpy
shape vs network.

On TPU the pack/exchange/unpack collapse into one collective, so the matrix
becomes: redistribution strategy (explicit ``lax.all_to_all`` vs
GSPMD-inserted) x exchange geometry (1D slab-like single transpose vs 2D
pencil-like transpose over one axis of a 2D mesh). Reported bandwidth is
*effective* bytes-of-global-array per wall-clock second — the same
"how fast can we re-distribute this volume" number the reference prints.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..parallel.mesh import make_pencil_mesh, make_slab_mesh
from ..parallel.transpose import all_to_all_transpose, realigned_pack_shape


def _time_fn(fn, x, iterations: int, warmup: int) -> float:
    for _ in range(warmup):
        y = fn(x)
    jax.block_until_ready(y if warmup else x)
    t0 = time.perf_counter()
    for _ in range(iterations):
        y = fn(x)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / iterations


_COLLECTIVE_OPS = ("all-to-all", "collective-permute", "all-gather",
                   "reduce-scatter", "all-reduce")


def async_collective_counts(hlo) -> Dict[str, int]:
    """Instance counts of the exchange collectives (and their async start
    forms) in a compiled module — the overlap detector the STREAMS negative
    result designated (``eval/benchmarks/cpumesh8/OVERLAP.md``). GSPMD can
    re-fuse K chunked piece-reshards into ONE collective (measured), but it
    cannot merge the ``P-1`` DISTINCT ``collective-permute`` steps of the
    ring rendering (``SendMethod.RING``): ``collective_permute >= P-1`` is
    the structural signature that the exchange is genuinely split, and
    nonzero ``*_start`` counts are the evidence the backend scheduled the
    transfers asynchronously (TPU emits start/done pairs; the CPU backend
    lowers every collective synchronously, so its ``async_total`` is 0 by
    construction). Accepts a compiled executable or raw HLO text.

    ``convert`` counts the dtype-conversion ops in the module — the
    compressed-wire encode/decode casts (``wire_dtype="bf16"``) land as
    ``convert``s fused into/around the collective operands. The count
    attributes a compressed program's extra ops, and the wire tier-1 gate
    (tests/test_wire.py) asserts the compression did NOT break the
    ``>= P-1`` collective-permute signature of ring plans: if GSPMD ever
    re-fused the encoded permutes, the permute count would collapse and
    the gate fails by count, not by timing drift.

    Since the analysis subsystem landed this delegates to the canonical
    counter (``analysis.hloscan.collective_census`` — which also mirrors
    the census into the obs ``hlo.*`` gauges); the name stays because the
    bench/eval layers and their JSON schemas grew around it."""
    from ..analysis.hloscan import collective_census

    return collective_census(hlo)


# Module-level so repeated calls (one per bf16 twin in a race, plus the
# bench wire rows) share one jit cache entry per shape/dtype instead of
# re-tracing a fresh lambda every time. jax.jit is lazy: building the
# wrapper at import touches no backend.
_max_rel_err = jax.jit(
    lambda u, v: jnp.max(jnp.abs(u - v)) / jnp.max(jnp.abs(v)))


def max_rel_err(a, b) -> float:
    """Max ``|a - b|`` relative to ``max |b|``, computed on device (one
    scalar readback, so it works on distributed global arrays) — the wire
    layer's single accuracy metric, shared by the autotune error gate and
    the bench wire rows so the two can never drift apart."""
    return float(_max_rel_err(a, b))


def _collectives_in(compiled) -> list:
    """Collective op names present in the compiled HLO — evidence that a
    'resharding' timing actually measured a cross-device exchange and not a
    no-op XLA elided (VERDICT r1 weak#8)."""
    hlo = compiled.as_text()
    return sorted({op for op in _COLLECTIVE_OPS if op in hlo})


def wire_probe(shape, p: int, dtype=np.float32):
    """Build + compile the PURE all-to-all exchange once; returns
    ``(time_window, info)`` where ``time_window(iterations, warmup)`` times
    one window of the compiled program (seconds) and ``info`` carries the
    exchanged bytes and the HLO collective evidence. Lets callers interleave
    repeated windows with other measurements without recompiling."""
    import jax.lax as lax

    mesh = make_slab_mesh(p)
    spec = PartitionSpec("p", None, None)
    if shape[0] % (p * p):
        # The tiled all_to_all re-splits the LOCAL shard axis by p again.
        raise ValueError(f"wire probe needs shape[0] % {p * p} == 0")
    x = jax.device_put(np.ones(shape, dtype=dtype),
                       NamedSharding(mesh, spec))
    body = jax.shard_map(
        lambda xl: lax.all_to_all(xl, "p", split_axis=0, concat_axis=0,
                                  tiled=True),
        mesh=mesh, in_specs=spec, out_specs=spec)
    fn = jax.jit(body, in_shardings=NamedSharding(mesh, spec),
                 out_shardings=NamedSharding(mesh, spec))
    compiled = fn.lower(x).compile()
    nbytes = int(np.prod(shape) * np.dtype(dtype).itemsize)
    info = {"bytes": nbytes, "collective_ops": _collectives_in(compiled)}

    def time_window(iterations: int = 10, warmup: int = 2) -> float:
        return _time_fn(compiled, x, iterations, warmup)

    return time_window, info


def overlap_race(global_shape, p: int, chunk_counts=(2, 4), k: int = 4,
                 repeats: int = 5, iterations: int = 3, warmup: int = 1,
                 backend: str = "xla", sequence: str = "ZY_Then_X",
                 comm: str = "All2All", opt: int = 1,
                 include_ring: bool = True) -> Dict:
    """Race the monolithic slab pipeline (``SendMethod.SYNC`` — one
    collective per transpose) against the STREAMS chunked/software-pipelined
    rendering (K independent per-piece FFT->exchange->FFT chains) and the
    RING ppermute rendering (``include_ring``; P-1 distinct
    collective-permute steps with per-block FFTs pipelined between them),
    measuring whether splitting the exchange buys compute/communication
    overlap — the question the reference answers with its Streams engine
    (``src/slab/default/mpicufft_slab.cpp:343-448``) and SURVEY §7 says to
    measure, not assume.

    Each variant times a K-chained forward+inverse roundtrip via the
    ``(t_K - t_1)/(K-1)`` pair difference (chaintimer contract), all within
    the same repeat so drift hits every variant equally. The result also
    carries per-variant HLO attribution (``async_collective_counts``):
    instance counts of ``all-to-all``/``collective-permute`` ops and their
    async ``*-start`` forms in the compiled module — on a backend whose
    collectives lower synchronously (CPU) no variant CAN overlap, and the
    counts say so; async starts are the evidence that the scheduler may
    hide exchange latency behind the neighbouring FFTs. The STREAMS
    chunked collectives were measured to stay fused/synchronous (zero
    async starts — the OVERLAP.md negative result); the ring's distinct
    permutes are the rendering that can fire the detector.
    """
    import jax.lax as lax

    from .. import params as pm
    from ..models.slab import SlabFFTPlan

    if k < 2:
        raise ValueError(f"overlap_race needs k >= 2 for the (t_K - t_1)"
                         f"/(K-1) pair difference, got {k}")
    g = pm.GlobalSize(*global_shape)
    scale = 1.0 / float(g.n_total)
    variants = [("sync", None)] + [(f"streams{c}", c) for c in chunk_counts]
    if include_ring:
        # Both ring schedules: the plain ring and the double-buffered
        # RING_OVERLAP issue order (bit-identical output; on a backend
        # with async collective lowering the reorder is the overlap win
        # this race exists to measure, on the synchronous CPU mesh the
        # two honestly tie).
        variants.append(("ring", None))
        variants.append(("ring-overlap", None))
    fns, hlo = {}, {}
    for name, chunks in variants:
        snd = (pm.SendMethod.RING if name == "ring"
               else pm.SendMethod.RING_OVERLAP if name == "ring-overlap"
               else pm.SendMethod.SYNC if chunks is None
               else pm.SendMethod.STREAMS)
        cfg = pm.Config(comm_method=pm.CommMethod.parse(comm),
                        send_method=snd,
                        streams_chunks=chunks, fft_backend=backend, opt=opt)
        plan = SlabFFTPlan(g, pm.SlabPartition(p), cfg, sequence=sequence)
        fwd, inv = plan.forward_fn(), plan.inverse_fn()
        ishard = NamedSharding(plan.mesh, plan._in_spec)

        def chain(kk, fwd=fwd, inv=inv):
            def run(v):
                return lax.fori_loop(
                    0, kk, lambda i, w: inv(fwd(w)) * scale, v)
            return jax.jit(run, in_shardings=ishard, out_shardings=ishard)

        x = jax.device_put(
            np.random.default_rng(0).random(
                plan.input_padded_shape).astype(np.float32), ishard)
        f1, fK = chain(1), chain(k)
        compiled = f1.lower(x).compile()
        hlo[name] = async_collective_counts(compiled)
        jax.block_until_ready(fK(x))  # compile + warm the K-chain too
        fns[name] = (f1, fK, x)

    def med(xs):
        return sorted(xs)[len(xs) // 2]

    times = {name: [] for name, _ in variants}
    for _ in range(repeats):
        per = {}
        for name in times:
            f1, fK, x = fns[name]
            tK = _time_fn(fK, x, iterations, warmup)
            t1 = _time_fn(f1, x, iterations, warmup)
            per[name] = (tK - t1) / (k - 1)
        # Paired drop anchored on the baseline (the fraction-chain
        # contract): a repeat whose "sync" sample degenerates is dropped
        # for EVERY variant, otherwise winner/best_vs_sync would compare
        # medians over disjoint repeat subsets and a one-repeat host
        # stall hitting only sync would flip the verdict.
        if per.get("sync", 0.0) <= 0:
            continue
        for name, d in per.items():
            if d > 0:
                times[name].append(d)
    out = {"shape": list(global_shape), "p": p, "k": k, "repeats": repeats,
           "backend": backend, "sequence": sequence, "comm": comm,
           "opt": opt, "variants": {}}
    for name in times:
        ts = sorted(times[name])
        rec = {"hlo": hlo[name]}
        if ts:
            rec["per_iter_ms"] = round(med(ts) * 1e3, 3)
            rec["spread_ms"] = [round(ts[0] * 1e3, 3),
                                round(ts[-1] * 1e3, 3)]
        else:
            rec["degenerate"] = True
        out["variants"][name] = rec
    timed = {n: v["per_iter_ms"] for n, v in out["variants"].items()
             if "per_iter_ms" in v}
    if timed:
        best = min(timed, key=timed.get)
        out["winner"] = best
        if "sync" in timed and timed["sync"] > 0:
            out["best_vs_sync"] = round(timed["sync"] / timed[best], 4)
    return out


def transpose_fraction_chain(plan, spec_val, k: int = 8, repeats: int = 5,
                             iterations: int = 3, warmup: int = 1,
                             selection_repeats: "int | None" = None,
                             streams_variants=(),
                             publication_repeats: "int | None" = None,
                             publication_iterations: "int | None" = None
                             ) -> Dict:
    """North-star gate measurement: the pipeline transpose's achieved
    fraction of the raw collective ceiling, with ``fraction <= 1`` holding
    BY CONSTRUCTION in expectation (VERDICT r2: a gate whose measured
    value exceeds 1 is not a gate).

    Method: K-chained jitted programs over the SAME mesh, shard shapes,
    and dtype —

    * pipeline chains: K iterations of (forward transpose ∘ inverse
      transpose), the slab pipeline's own bodies
      (``plan._xpose_bodies``), one chain per layout rendering
      (``opt0`` = XLA's native ``split != concat`` lowering, ``opt1`` =
      realigned pack + pure exchange), layout-stable per iteration;
    * ceiling chains: K iterations of two PURE exchanges
      (``split_axis == concat_axis``, zero relayout) — the same wire
      bytes per iteration, a strict subset of every pipeline iteration's
      work. TWO pure layouts are timed (the pipeline input's own shape,
      and the opt1 pack's merged-leading shape — the exchange the
      realigned pipe actually issues) and each repeat's ceiling is the
      FASTER of the two: a pure exchange of the same bytes in a better
      layout is still "pure exchange", and a ceiling the pipe can beat
      is not a ceiling (observed: the merged layout's bigger contiguous
      chunks exchange measurably faster at 128^3 on the CPU mesh).

    Each is timed as a ((t_K - t_1)/(K-1)) pair difference — the chain
    amortizes the host's run-to-run dispatch noise that made single-window
    ratios land anywhere in 0.5-1.4 — and all chains' pairs run within
    the same repeat so slow drift hits both sides of each fraction sample.

    The gate value is produced in two phases so racing variants adds no
    selection bias (max-of-noisy-medians systematically reads high): a
    SELECTION phase races every variant against the ceiling and picks the
    winner by median fraction; a fresh PUBLICATION phase then re-measures
    ONLY the winner against the ceiling — with its own, defaulting-higher
    statistics (``publication_repeats``, ``publication_iterations``;
    defaults ``repeats`` and ``2 * iterations``) — and publishes those
    repeats' median as ``fraction``. ``fraction_spread`` is the
    INTERQUARTILE range of the publication repeats (a min..max interval
    widens with every added repeat, punishing better averaging);
    ``fraction_range`` keeps the full min..max visible, and single
    outlier samples above 1 land in the range, not the spread. Result
    also carries ``variant`` (the winner's name), ``variants``
    (selection-phase medians with their min..max under
    ``fraction_range`` — rankings only, never gate values), and
    ``gate_phase``/``gate_note`` provenance strings.

    A pair difference that comes out nonpositive (work swamped by noise —
    the chaintimer degenerate contract) drops that variant's sample for
    the repeat; a repeat with NO positive ceiling sample (both pure
    layouts degenerate) is dropped for every variant. If every publication
    repeat degenerates the result carries ``degenerate: True`` and no
    fraction, which callers must not publish as a gate value.
    """
    import jax.lax as lax

    from ..parallel.mesh import SLAB_AXIS

    mesh = plan.mesh
    ispec = plan._in_spec

    def chained(body_pair, kk):
        def body(v):
            return lax.fori_loop(0, kk, lambda i, w: body_pair(w), v)
        sm = jax.shard_map(body, mesh=mesh, in_specs=ispec, out_specs=ispec)
        return jax.jit(sm, in_shardings=NamedSharding(mesh, ispec),
                       out_shardings=NamedSharding(mesh, ispec))

    def pipe_pair(realigned, chunks=None):
        xf, xi = plan._xpose_bodies(realigned, chunks=chunks)
        return lambda w: xi(xf(w))

    def pure_pair(w):
        w = lax.all_to_all(w, SLAB_AXIS, split_axis=0, concat_axis=0,
                           tiled=True)
        return lax.all_to_all(w, SLAB_AXIS, split_axis=0, concat_axis=0,
                              tiled=True)

    p = plan._P
    local0 = spec_val.shape[0] // p
    if local0 % p:
        raise ValueError(
            f"fraction chain needs the local leading extent {local0} "
            f"divisible by {p} (tiled pure exchange re-splits it)")
    # Second pure layout: the merged-leading shape the opt1 pack exchanges
    # (realigned_pack_shape — same bytes, bigger contiguous per-peer
    # chunks), derived from the same helper the transpose uses so the
    # ceiling cannot drift from the exchange the realigned pipe issues.
    merged_shape = realigned_pack_shape(spec_val.shape,
                                        plan._seq.split_axis, p)
    fns = {"opt0": (chained(pipe_pair(False), 1), chained(pipe_pair(False), k)),
           "opt1": (chained(pipe_pair(True), 1), chained(pipe_pair(True), k)),
           "raw": (chained(pure_pair, 1), chained(pure_pair, k))}
    if tuple(merged_shape) != tuple(spec_val.shape):
        # split_axis == 0 leaves the pack shape unchanged, making this
        # chain an exact duplicate of "raw" — skip rather than compile and
        # time the same program twice (ADVICE r4).
        merged_val = jax.device_put(
            jnp.zeros(merged_shape, spec_val.dtype),
            NamedSharding(mesh, ispec))
        fns["raw_merged"] = (chained(pure_pair, 1), chained(pure_pair, k))
    else:
        merged_val = None
    # Chunked-exchange (STREAMS) renderings of the realigned transpose:
    # raced in selection like any variant; a pure-transpose chain has no
    # FFT to overlap with, so this isolates the cost/benefit of splitting
    # the collective itself (overlap_race measures the full-pipeline case).
    for c in streams_variants:
        pp = pipe_pair(True, chunks=c)
        fns[f"opt1s{c}"] = (chained(pp, 1), chained(pp, k))
    args = {n: merged_val if n == "raw_merged" else spec_val for n in fns}
    for name, (f1, fK) in fns.items():  # compile + warm all chains up front
        jax.block_until_ready(f1(args[name]))
        jax.block_until_ready(fK(args[name]))

    def med(xs):
        return sorted(xs)[len(xs) // 2]

    raw_names = ("raw", "raw_merged")

    def run_repeats(names, n_repeats, n_iterations=None):
        """Paired repeats over the named chains; per-variant dropping (no
        positive ceiling sample drops the repeat for every variant). The
        repeat's ceiling — recorded under ``"ceil"`` — is the FASTER of
        the two pure layouts."""
        n_iterations = iterations if n_iterations is None else n_iterations
        fracs = {n: [] for n in names if n not in raw_names}
        times = {n: [] for n in fracs}
        times["ceil"] = []
        for _ in range(n_repeats):
            per = {}
            for name in names:
                f1, fK = fns[name]
                tK = _time_fn(fK, args[name], n_iterations, warmup)
                t1 = _time_fn(f1, args[name], n_iterations, warmup)
                per[name] = (tK - t1) / (k - 1)
            ceil_s = [per[n] for n in raw_names if n in per and per[n] > 0]
            if not ceil_s:
                continue  # no ceiling: nothing comparable this repeat
            ceil = min(ceil_s)
            contributed = False
            for n in fracs:
                if per[n] > 0:
                    times[n].append(per[n])
                    fracs[n].append(ceil / per[n])
                    contributed = True
            if contributed:
                # Keep the ceiling median paired with the variant medians:
                # a repeat that produced no variant sample must not skew
                # the published raw side either.
                times["ceil"].append(ceil)
        return fracs, times

    # SELECTION phase: race every variant; pick the winner by median
    # fraction. These samples are NOT published (max-of-noisy-medians is
    # biased high — the publication phase re-measures fresh), so callers
    # under a deadline may rank with fewer
    # repeats via ``selection_repeats``; default = the full ``repeats``
    # so raising -i on a noisy host fixes selection-phase degeneracy too.
    sel_n = repeats if selection_repeats is None else max(
        1, min(selection_repeats, repeats))
    sel_fracs, _ = run_repeats(list(fns), sel_n)
    by_variant = {}
    for n, fs in sel_fracs.items():
        if fs:
            fs = sorted(fs)
            by_variant[n] = {
                "fraction": round(med(fs), 4),
                "fraction_range": [round(fs[0], 4), round(fs[-1], 4)],
            }
    if not by_variant:
        return {"degenerate": True, "k": k, "repeats": sel_n,
                "dropped": sel_n, "phase": "selection"}
    winner = max(by_variant, key=lambda n: by_variant[n]["fraction"])

    # PUBLICATION phase: fresh paired repeats of ONLY the winner vs the
    # ceiling. This phase's median IS the gate value ("fraction"); the
    # selection fractions under "variants" rank renderings and are never
    # gate values (a max over noisy medians reads high — VERDICT r3).
    # Its repeats/inner-iterations default higher than selection's: the
    # published spread has to clear the 0.70 north star at BOTH ends and
    # stay physically plausible (<= ~1), which takes more averaging than
    # a ranking does (VERDICT r4 weak #1: a 5x2 publication straddled
    # 0.66-1.02 while the 3x2 selection sat at 0.825-0.871 — per-sample
    # noise, not a real spread).
    pub_n = repeats if publication_repeats is None else publication_repeats
    pub_i = (2 * iterations if publication_iterations is None
             else publication_iterations)
    pub_fracs, pub_times = run_repeats(
        [winner] + [n for n in raw_names if n in fns], pub_n, pub_i)
    fs = sorted(pub_fracs[winner])
    if not fs:
        return {"degenerate": True, "k": k, "repeats": pub_n,
                "dropped": pub_n, "phase": "publication",
                "variant": winner, "variants": by_variant}
    # The published interval is the INTERQUARTILE range of the repeat
    # samples, not min..max: a min..max "spread" WIDENS with more repeats
    # (each is one more outlier draw), so averaging harder makes the
    # artifact look noisier — the r4 artifact's 0.66-1.02 straddle was
    # two single-sample outliers around a stable 0.86-0.89 median. The
    # full range stays visible under "fraction_range".
    q1 = fs[(len(fs) - 1) // 4]
    q3 = fs[(3 * (len(fs) - 1) + 3) // 4]
    # 2 exchanges of the pre-transpose volume per chain iteration.
    nbytes = 2 * spec_val.nbytes
    out = {
        "fraction": round(med(fs), 4),
        "fraction_spread": [round(q1, 4), round(q3, 4)],
        "fraction_range": [round(fs[0], 4), round(fs[-1], 4)],
        "gate_phase": "publication",
        "gate_note": ("'fraction' is the publication-phase median of the "
                      f"winner ({pub_n} fresh repeats x {pub_i} inner "
                      "iterations); 'fraction_spread' is the interquartile "
                      "range of those repeats (full range under "
                      "'fraction_range'); 'variants' entries are "
                      "selection-phase rankings only, not gate values"),
        "variant": winner,
        "variants": by_variant,
        "pipe_gb_per_s": round(nbytes / med(pub_times[winner]) / 1e9, 3),
        "raw_gb_per_s": round(nbytes / med(pub_times["ceil"]) / 1e9, 3),
        "k": k, "repeats": pub_n, "iterations": pub_i,
    }
    dropped = pub_n - len(fs)
    if dropped:
        out["dropped"] = dropped
    return out


def wire_bandwidth(shape, p: int, iterations: int = 10, warmup: int = 2,
                   dtype=np.float32, windows: int = 1) -> Dict:
    """PURE all-to-all exchange bandwidth: ``lax.all_to_all`` with
    ``split_axis == concat_axis``, so the wire transfer happens with no
    shard-local relayout at all. This is the true collective ceiling the
    north-star "achieved fraction" gates against — ``transpose_bandwidth``'s
    probes additionally pay a standalone reshape/concat relayout, which a
    fused pipeline program can legitimately beat (observed: slab transpose
    at 1.0-1.4x the relayout probe on the CPU mesh)."""
    time_window, info = wire_probe(shape, p, dtype=dtype)
    # A ceiling estimate takes the BEST of ``windows`` timing windows over
    # the once-compiled program (a noisy window must not drag it down).
    dt = min(time_window(iterations, warmup) for _ in range(max(1, windows)))
    return {"seconds": dt, **info, "gb_per_s": info["bytes"] / dt / 1e9}


def transpose_bandwidth(shape, p: int, explicit: bool = True,
                        iterations: int = 10, warmup: int = 2,
                        dtype=np.float32, geometry: str = "1d",
                        pencil_axis: bool = False) -> Dict:
    """Global-transpose bandwidth for the reference's three exchange
    geometries (``tests_reference.hpp:53-96``: 1D/2D/3D-memcpy probes that
    attribute transpose cost to layout shape vs network):

    * ``"1d"`` — 1D mesh, slab transpose (x-split -> y-split).
    * ``"2d"`` — one axis of a 2D mesh (a pencil transpose: y-split ->
      z-split within each mesh row).
    * ``"3d"`` — 2D mesh with BOTH other axes sharded (x stays p1-split
      while y-split -> z-split over p2): the strided-in-two-axes exchange,
      the analog of the reference's 3D-memcpy probe.

    explicit=True  -> shard_map + lax.all_to_all (the All2All path)
    explicit=False -> GSPMD resharding via jit out_shardings (Peer2Peer
    path; XLA's SPMD partitioner chooses and schedules the collective)

    The result carries ``collective_ops``: the collectives found in the
    compiled HLO, proving the measurement exercised a real exchange.
    """
    if pencil_axis:  # legacy alias for geometry="2d"
        geometry = "2d"
    if geometry == "1d":
        mesh = make_slab_mesh(p)
        axis = "p"
        in_spec = PartitionSpec(axis, None, None)
        out_spec = PartitionSpec(None, axis, None)
        split, concat = 1, 0
        sharded_exts = (shape[0], shape[1])
    elif geometry == "2d":
        mesh = make_pencil_mesh(1, p)
        axis = "p2"
        in_spec = PartitionSpec(None, axis, None)
        out_spec = PartitionSpec(None, None, axis)
        split, concat = 2, 1
        sharded_exts = (shape[1], shape[2])
    elif geometry == "3d":
        if p % 2 or p <= 2:
            raise ValueError(
                f"3d geometry needs an even device count > 2 to doubly "
                f"shard (got p={p}); with p1=1 it would silently be the "
                f"2d probe mislabeled")
        p1, p2 = 2, p // 2
        mesh = make_pencil_mesh(p1, p2)
        axis = "p2"
        in_spec = PartitionSpec("p1", axis, None)
        out_spec = PartitionSpec("p1", None, axis)
        split, concat = 2, 1
        if shape[0] % p1:
            raise ValueError(f"3d geometry needs shape[0] % {p1} == 0")
        sharded_exts = (shape[1], shape[2])
        p = p2  # the exchanged-axis extents must divide p2
    else:
        raise ValueError(f"geometry must be '1d'|'2d'|'3d', got {geometry!r}")
    for ext in sharded_exts:
        if ext % p:
            raise ValueError(
                f"microbench extents must divide the mesh: {ext} % {p} != 0 "
                f"(the plan paths pad uneven extents; this raw-bandwidth "
                f"probe intentionally does not)")

    x = jax.device_put(np.ones(shape, dtype=dtype),
                       NamedSharding(mesh, in_spec))
    if explicit:
        body = jax.shard_map(
            lambda xl: all_to_all_transpose(xl, axis, split, concat),
            mesh=mesh, in_specs=in_spec, out_specs=out_spec)
        fn = jax.jit(body, in_shardings=NamedSharding(mesh, in_spec),
                     out_shardings=NamedSharding(mesh, out_spec))
    else:
        fn = jax.jit(lambda a: a, in_shardings=NamedSharding(mesh, in_spec),
                     out_shardings=NamedSharding(mesh, out_spec))
    compiled = fn.lower(x).compile()
    dt = _time_fn(compiled, x, iterations, warmup)
    nbytes = np.prod(shape) * np.dtype(dtype).itemsize
    return {"seconds": dt, "bytes": int(nbytes),
            "gb_per_s": nbytes / dt / 1e9,
            "geometry": geometry,
            "collective_ops": _collectives_in(compiled)}


def single_device_fft_ms(shape, iterations: int = 10, warmup: int = 2,
                         dtype=np.float32, inverse: bool = False,
                         backend: str = "xla", settings=None) -> float:
    """Reference testcase 0 analog: full 3D FFT of ``shape = (nx, ny, nz)``
    on one device (the cufftMakePlan3d baseline curve). Input is staged on
    device once. ``backend`` selects the local transform implementation
    (``ops/fft.py`` ``BACKENDS``: "xla", "matmul", "matmul-r2", or
    "pallas"); ``settings`` an optional ``mxu_fft.MXUSettings`` so a
    measured matmul winner (precision/direct_max) runs AS measured."""
    from ..ops import fft as lf

    lf.validate_backend(backend)
    shape = tuple(shape)
    x = jax.device_put(np.random.default_rng(0).random(shape).astype(dtype))
    if inverse:
        c = jax.jit(lambda a: lf.rfftn_3d(a, backend=backend,
                                          settings=settings))(x)
        jax.block_until_ready(c)
        fn = jax.jit(lambda a: lf.irfftn_3d(a, shape, backend=backend,
                                            settings=settings))
        dt = _time_fn(fn, c, iterations, warmup)
    else:
        fn = jax.jit(lambda a: lf.rfftn_3d(a, backend=backend,
                                           settings=settings))
        dt = _time_fn(fn, x, iterations, warmup)
    return dt * 1e3
