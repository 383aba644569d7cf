"""Headline benchmark — always prints exactly ONE JSON line for the driver.

This orchestrator predates the chip tool and is what ROADMAP A1 replaces;
``chip_smoke.py`` is the one-process proof that the main path runs on a
chip. Design:

* The parent process NEVER imports jax. All device work happens in child
  subprocesses, so a hang in backend init can only cost a child its
  timeout, never the final JSON line, and the parent never holds a chip.
* Child 1 (``--child mesh``) forces the CPU platform and measures the
  BASELINE.json metrics that don't need the real chip:
  raw all-to-all transpose bandwidth on the 8-device mesh, the pipeline's
  achieved fraction of it (the ">=70% of measured all-to-all bandwidth"
  north-star number), the ring rendering's HLO overlap-detector counts
  (``async_collective_ops`` in the verbose record: instance counts of
  ``all-to-all``/``collective-permute`` and their async ``*-start`` forms
  from ``microbench.async_collective_counts`` — ``collective_permute +
  collective_permute_start >= P-1`` (plain + async forms summed: TPU
  lowering rewrites each permute into a start/done pair) proves the
  SendMethod.RING exchange stays split; starts are 0 on
  the CPU mesh by construction and nonzero on a TPU mesh), and a CPU
  fallback roundtrip timing.
* Child 2 (``--child probe``) is one pre-flight claim of the default
  platform, launched at t=0 concurrently with the mesh child; only if it
  exits cleanly does the real measurement run.
* Child 2b (``--child serve``) is the serving-layer saturation bench
  (ISSUE 8): cold per-invocation plan-build+execute vs warm plan-cache
  p50 for a repeated shape, then an open-loop offered-load sweep
  (``testing/workloads.serve_load``: Poisson arrivals against the
  in-process ``serve.Server``) reporting p50/p99 latency, sustained
  FFTs/sec, shed counts and the plan-cache hit rate per rate. CPU-only
  like the mesh child, and strictly bounded.
* Child 2d (``--child fleet``) is the fleet scaling bench (ISSUE 13):
  the open-loop sweep re-driven against ``serve.Fleet`` at 1/2/4
  subprocess workers behind the plan-key router, quoting achieved
  FFTs/sec, p50/p99 and shed per worker count against the 1-worker
  plateau — the measurement ROADMAP item 2's single-process→fleet
  promotion is gated on. CPU-only, strictly bounded.
* Child 3 (``--child tpu``) times the single-chip R2C+C2R roundtrip at
  128^3 and 256^3 with the shared chained-roundtrip harness
  (distributedfft_tpu/testing/chaintimer.py: scalar-fenced jitted fori_loop
  chain, median of (t_K - t_1) pairs, so the per-call constant cancels in
  the pair difference),
  and derives GFLOPS (2.5·N^3·log2(N^3) per direction, BASELINE.md
  §Derived).

Headline value: 256^3 f32 roundtrip ms vs the reference's single-GPU
cufftPlan3d baseline (argon 256^3 inverse 2.20 ms f64 -> ~4.4 ms roundtrip;
BASELINE.md "Single-GPU reference" rows). Reference bandwidth-attribution
analog: tests_reference.hpp:53-96.

The final stdout line is COMPACT (headline metric/value/unit/vs_baseline
only, always well under a 2000-char tail capture); the full verbose record
— per-size rows, mesh metrics, diagnostics, and the tracked ``"roofline"``
block (``roofline_fraction`` per measured row, ISSUE 10's honesty gate;
computed by the children via ``evalkit.roofline.roofline_row`` since the
parent never imports jax) — is written to BENCH_DETAILS.json alongside
this file (``$DFFT_BENCH_DETAILS_PATH`` redirects it: test runs must
point it at a scratch path so a shrunken/starved run never overwrites
the committed regression reference the CI roofline gate compares
against). ``$DFFT_BENCH_CHILD_TIMEOUT_S`` (one number, or per-child
``name:seconds`` pairs — see ``_child_budget``) caps each child's grant so
one slow child degrades the run to a partial BENCH_DETAILS.json instead of
eating the driver deadline (the r01 failure mode). When no DFFT_BENCH_BACKEND is
forced, the tpu child warm-starts its backend choice from the wisdom store
($DFFT_WISDOM, utils/wisdom.py): a prior ``dfft-reference --autotune``
winner is reused so the scarce healthy chip window is spent measuring,
never re-tuning (lookup only — a miss keeps the deployed default).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

BASELINE_ROUNDTRIP_MS = 4.4  # 2 x 2.20 ms (argon single-GPU 256^3 inverse)
BUDGET_S = 450               # parent wall-clock; driver's outer limit is >480
PROBE_TIMEOUT_S = 180        # re-probe ceiling (first probe rides the budget)
MESH_TIMEOUT_S = 300
SERVE_TIMEOUT_S = 90         # serving-layer saturation bench (CPU, bounded)
FLEET_TIMEOUT_S = 180        # fleet scaling bench + 3D-volume row (CPU,
                             # bounded; ISSUE 13 + ISSUE 20)
SOLVERS_TIMEOUT_S = 75       # solvers suite bench (CPU, bounded; ISSUE 9)
MEASURE_RESERVE_S = 120      # budget step 3 needs after a successful probe
# Default sweep covers the BASELINE metric's own sizes (VERDICT r3 item 7:
# the artifact must re-measure them, not rely on committed CSVs). Headline
# size FIRST: sizes record progressively, so a deadline firing mid-1024^3
# cannot cost the 256^3 scoreboard row. 1024^3 carries the per-size
# OOM -> forward-only fallback; a deadline skip surfaces as a per-size
# diagnostic rather than a silent absence.
SIZES = (256, 128, 512, 1024)
# Batched-2D row (BASELINE config #4 family): "batch,m,chunk" measured
# after the cube sweep; "0" disables chunking (whole-stack single
# program). chunk is the lax.map slice SIZE: chunk=1 = per-plane slices,
# the MOST chunked form — the fastest in a pre-PR-12 single-chip sweep
# (not measured on the current code); the whole-stack chunk=0 program
# was never measured on a chip.
BATCHED_DEFAULT = "64,4096,1"

_REPO = os.path.dirname(os.path.abspath(__file__))


def _flops_roundtrip(n: int) -> float:
    """R2C + C2R flops (BASELINE.md §Derived). Delegates to the shared
    FLOP model; imported lazily because only the CHILD processes may
    import the package (it pulls in jax, and the parent must stay
    jax-free — see the module docstring)."""
    from distributedfft_tpu.testing.workloads import flops_roundtrip_3d
    return flops_roundtrip_3d(n)


# ---------------------------------------------------------------------------
# children (each runs in its own process; last stdout line is its JSON)
# ---------------------------------------------------------------------------

def _maybe_profile(tag: str):
    """``jax.profiler.trace`` over a child's measurement region when the
    parent was launched with ``--profile-dir`` (forwarded via
    ``DFFT_BENCH_PROFILE_DIR``), so benchmark runs produce device traces
    carrying the obs span names (``dfft:*`` TraceAnnotations). A
    nullcontext otherwise — and on ANY profiler failure, because a broken
    trace backend must never cost a measurement."""
    import contextlib
    d = os.environ.get("DFFT_BENCH_PROFILE_DIR", "")
    if not d:
        return contextlib.nullcontext()
    try:
        import jax
        return jax.profiler.trace(os.path.join(d, tag))
    except Exception:  # noqa: BLE001 — tracing is an optional extra
        return contextlib.nullcontext()


def _enter_profile(tag: str):
    """Start the child's profiler trace; returns the ENTERED context (to
    ``__exit__`` before the final print) or None when tracing is off or
    the trace failed to start — start failures (unwritable dir, nested
    trace) must never cost the measurement they decorate."""
    try:
        prof = _maybe_profile(tag)
        prof.__enter__()
        return prof
    except Exception:  # noqa: BLE001 — same contract as _maybe_profile
        return None


def _roofline_for_sizes(sizes: dict, backend: str, kind: str,
                        mesh_devices: int = 1) -> dict:
    """Tracked ``roofline_fraction`` per measured row (ISSUE 10 gate):
    ``evalkit.roofline.roofline_row`` over every non-degenerate
    ``per_iter_ms`` entry — the model the row's recorded plan actually
    ran (``direct(N)`` plan notes override the direct threshold; one-way
    modes halve the flops). Child-side (children own jax; the parent
    must stay jax-free). Failures return what was modeled — the
    roofline block is an attribution extra, never a crash."""
    rows = {}
    try:
        import re as _re

        from distributedfft_tpu.evalkit import roofline as rl
        for key, rec in (sizes or {}).items():
            ms = rec.get("per_iter_ms")
            if not ms or rec.get("degenerate"):
                continue
            mode = rec.get("mode", "roundtrip")
            if ":" in key and mode == "roundtrip":
                mode = key.split(":", 1)[1]  # "256:inverse" row keys
            dmax = None
            m = _re.search(r"direct\((\d+)\)", str(rec.get("plan", "")))
            if m:
                dmax = int(m.group(1))
            row = rl.roofline_row(
                ms, key, backend,
                mesh_devices if mesh_devices > 1 else None,
                kind=kind, mode=mode, direct_max=dmax)
            if row:
                rows[key] = row
    except Exception:  # noqa: BLE001 — attribution extra only
        pass
    return rows


def _stage_profile_brief(prof: dict) -> dict:
    """Compress one ``obs.profile.stage_profile`` report to the
    BENCH_DETAILS.json ``"stage_profile"`` row shape: the headline split
    plus one compact row per executing node (input/output and trace-file
    bookkeeping dropped)."""
    stages = []
    for row in prof.get("stages", []):
        if row.get("kind") in ("input", "output"):
            continue
        r = {"node": row["node"], "kind": row["kind"],
             "device_ms": row["device_ms"], "fraction": row["fraction"]}
        for k in ("ideal_ms", "gap_x", "note", "approx"):
            if k in row:
                r[k] = row[k]
        stages.append(r)
    return {k: prof[k] for k in
            ("family", "direction", "iters", "total_ms", "attributed_ms",
             "unattributed_ms", "exchange_ms", "compute_ms",
             "exchange_fraction") if k in prof} | {"stages": stages}


def _fold_obs_metrics(out: dict) -> None:
    """Attach the obs metrics snapshot (wisdom hits/misses, race cells,
    wire bytes, HLO census gauges) to a child's JSON record when anything
    was counted; the parent folds it into BENCH_DETAILS.json."""
    try:
        from distributedfft_tpu import obs
        snap = obs.metrics.snapshot()
        if snap["counters"] or snap["gauges"]:
            out["obs_metrics"] = snap
    except Exception:  # noqa: BLE001 — metrics are an optional extra
        pass


def _child_probe() -> int:
    """Claim the default platform, touch one device, exit cleanly."""
    import jax
    if os.environ.get("DFFT_BENCH_FORCE_CPU"):
        # Test hook (same as the tpu child's): lets the WHOLE parent
        # pipeline run on the CPU so CI can exercise the orchestration.
        jax.config.update("jax_platforms", "cpu")
    d = jax.devices()
    x = jax.device_put(1.0)
    print(json.dumps({"platform": d[0].platform, "n": len(d),
                      "ok": float(x) == 1.0}))
    return 0


def _child_tpu(deadline_s: int) -> int:
    """Chained-roundtrip timing on the default platform.

    Emits partial results if the deadline fires mid-way: each completed
    size is recorded before the next starts, and the TimeoutError path
    still prints the JSON collected so far.
    """
    def handler(signum, frame):
        raise TimeoutError(f"tpu child deadline ({deadline_s}s)")
    signal.signal(signal.SIGALRM, handler)
    signal.alarm(deadline_s)

    out = {"sizes": {}, "partial": False}
    prof = None
    try:
        import numpy as np

        import jax

        if os.environ.get("DFFT_BENCH_FORCE_CPU"):
            # Test hook: exercise this child on the CPU.
            jax.config.update("jax_platforms", "cpu")

        from distributedfft_tpu.testing import chaintimer

        backend = os.environ.get("DFFT_BENCH_BACKEND", "")
        if not backend:
            backend, src = _wisdom_backend()
            if src:
                out["backend_source"] = src
        backend = backend or "matmul"
        sizes = _bench_sizes()
        out["backend"] = backend
        out["platform"] = jax.devices()[0].platform
        out["device_kind"] = jax.devices()[0].device_kind

        from distributedfft_tpu.parallel.mesh import use_compile_cache
        use_compile_cache()

        # DFFT_BENCH_MODE: "roundtrip" (default) | "forward" | "inverse".
        # One-way modes use the on-device directional chain (VERDICT r2:
        # C2R-only rows; 1024^3 needs forward-only if the roundtrip
        # program does not fit HBM).
        mode = os.environ.get("DFFT_BENCH_MODE", "roundtrip")
        if mode not in ("roundtrip", "forward", "inverse"):
            # Fail fast: a typo'd mode must not burn the per-size retries.
            raise ValueError(f"DFFT_BENCH_MODE must be roundtrip/forward/"
                             f"inverse, got {mode!r}")
        out["mode"] = mode
        # Device trace of the measurement region (--profile-dir). Entered
        # manually: the try-block structure predates it, and the exit must
        # run on the partial/error paths too (see below, pre-print).
        prof = _enter_profile("tpu")
        for n in sizes:
            # Smaller cubes need a longer chain for the (K-1) iterations of
            # work to dominate the per-call constant's run-to-run noise
            # (chaintimer docstring). North-star cubes carry enough
            # work per iteration that a short chain suffices (and keeps
            # the program's wall clock inside the child deadline).
            k = 9 if n >= 1024 else (33 if n >= 512 else
                                     (257 if n >= 256 else 1025))
            shape = (n, n, n)
            # Per-size retry: EVERYTHING touching the device for this size
            # lives inside the retry, and a failed size gets fresh
            # compilations via clear_caches rather than aborting the whole
            # sweep. Hangs are the parent timeout's job — only fail-fast
            # errors retry here.
            last_err = None
            size_mode = mode
            fallback_reason = None
            plan_note = None
            attempts_left = 2
            while attempts_left > 0:
                attempts_left -= 1
                try:
                    if size_mode == "roundtrip" and n < 512:
                        # Continuity with the committed artifact's
                        # methodology: host-staged input, roundtrip chain.
                        x = jax.device_put(np.random.default_rng(0)
                                           .random(shape).astype(np.float32))
                        fn1 = chaintimer.roundtrip_chain(1, shape, backend)
                        fnK = chaintimer.roundtrip_chain(k, shape, backend)
                    elif size_mode == "forward-chunked":
                        # Final HBM rung for the north-star cube: chunked
                        # z/y stages (MEMORY_1024.md) — the only
                        # single-program formulation known to fit 16 GB
                        # at 1024^3.
                        x = 0  # rng seed
                        fn1 = chaintimer.chunked_forward_chain(
                            1, n, backend=backend)
                        fnK = chaintimer.chunked_forward_chain(
                            k, n, backend=backend)
                    else:
                        # Large cubes / one-way modes: input generated ON
                        # device (a 1024^3 cube is 4 GiB; host staging
                        # alone would eat the deadline). Generation (and, for "inverse", the
                        # one spectral-input-building forward) runs once
                        # per call and cancels in the pair difference.
                        st, plan_note = _direct_plan_override(backend, n)
                        x = 0  # rng seed
                        fn1 = chaintimer.directional_chain(
                            1, shape, backend, size_mode, settings=st)
                        fnK = chaintimer.directional_chain(
                            k, shape, backend, size_mode, settings=st)
                    float(fn1(x))  # compile + warm (scalar readback fences)
                    float(fnK(x))
                    per_ms, t1 = chaintimer.median_pair_diff_ms(
                        fn1, fnK, x, k, repeats=3, inner=3)
                    last_err = None
                    break
                except TimeoutError:
                    raise
                except Exception as e:  # noqa: BLE001 — roll a new compile
                    last_err = e
                    if "RESOURCE_EXHAUSTED" in str(e):
                        # Deterministic OOM: recompiling the identical
                        # program cannot help, and purging the cache would
                        # wipe the HEALTHY executables of other sizes (the
                        # cache's whole purpose). For the north-star cube
                        # fall back to forward-only with a FRESH attempt
                        # budget (the fallback must not inherit a spent
                        # one); other sizes stop retrying immediately.
                        if size_mode == "roundtrip" and n >= 1024:
                            # The direct-plan roundtrip fits 16 GB
                            # (measured 2026-07-31); reaching here means
                            # this window OOMed it anyway (or a non-matmul
                            # backend ran the four-step whose temporaries
                            # do not fit — MEMORY_1024.md). Step down.
                            size_mode = "forward"
                            fallback_reason = "roundtrip did not fit HBM"
                            attempts_left = max(attempts_left, 2)
                            continue
                        if size_mode == "forward" and n >= 1024:
                            # All-at-once forward doesn't fit either:
                            # last rung is the chunked-stage plan.
                            size_mode = "forward-chunked"
                            fallback_reason = ("all-at-once forward did "
                                               "not fit HBM")
                            attempts_left = max(attempts_left, 2)
                            continue
                        break
                    jax.clear_caches()
            if last_err is not None:
                out["sizes"][str(n)] = {
                    "error": f"{type(last_err).__name__}: {last_err}"}
                continue
            rec = {"per_iter_ms": round(per_ms, 4), "k": k}
            if plan_note and size_mode != "forward-chunked":
                rec["plan"] = plan_note
            if size_mode != "roundtrip":
                rec["mode"] = size_mode
                if size_mode != mode and fallback_reason:
                    rec["mode_fallback"] = fallback_reason
            if per_ms <= 0:
                rec["degenerate"] = True
            else:
                # One-way modes (forward / inverse / forward-chunked) do
                # half a roundtrip's transform work.
                flops = _flops_roundtrip(n) / (1 if size_mode == "roundtrip"
                                               else 2)
                rec["gflops"] = round(flops / per_ms / 1e6, 1)
            out["sizes"][str(n)] = rec
        # Inverse-direction rows (VERDICT r4 item 5: the reference ships a
        # separate inverse benchmark tree, eval/benchmarks/argon/inverse/,
        # and the committed CSV cannot prove this direction for the
        # artifact's own run). Directional chains generate input on
        # device; one attempt each — these are supplements, and a failure
        # must not eat the batched-2D row's deadline share.
        if mode == "roundtrip":
            inv_sizes = [(n, k) for n, k in ((256, 257), (512, 33))
                         if n in sizes]
            for n_inv, k_inv in inv_sizes:
                try:
                    fn1 = chaintimer.directional_chain(1, (n_inv,) * 3,
                                                       backend, "inverse")
                    fnK = chaintimer.directional_chain(k_inv, (n_inv,) * 3,
                                                       backend, "inverse")
                    float(fn1(0))
                    float(fnK(0))
                    per_ms, _ = chaintimer.median_pair_diff_ms(
                        fn1, fnK, 0, k_inv, repeats=3, inner=3)
                    rec = {"per_iter_ms": round(per_ms, 4), "k": k_inv,
                           "mode": "inverse"}
                    if per_ms > 0:
                        rec["gflops"] = round(
                            _flops_roundtrip(n_inv) / 2 / per_ms / 1e6, 1)
                    else:
                        rec["degenerate"] = True
                    out["sizes"][f"{n_inv}:inverse"] = rec
                except TimeoutError:
                    raise  # the child deadline must reach the partial path
                except Exception as e:  # noqa: BLE001 — supplement only
                    out["sizes"][f"{n_inv}:inverse"] = {
                        "error": f"{type(e).__name__}: {e}"}
        _tpu_batched2d(out, backend)
    except TimeoutError as e:
        out["partial"] = True
        out["error"] = str(e)
    except Exception as e:  # noqa: BLE001 — report, never hang the driver
        out["partial"] = True
        out["error"] = f"{type(e).__name__}: {e}"
    # Tracked roofline fractions for every measured row (runs on the
    # partial paths too — a deadline must not cost the rows already
    # measured their fractions).
    roof = _roofline_for_sizes(out.get("sizes"), out.get("backend",
                                                         "matmul"),
                               out.get("device_kind", ""))
    if roof:
        out["roofline"] = roof
    if prof is not None:
        try:
            prof.__exit__(None, None, None)
        except Exception:  # noqa: BLE001 — flushing a trace is best-effort
            pass
    _fold_obs_metrics(out)
    signal.alarm(0)
    print(json.dumps(out))
    return 0


def _tpu_batched2d(out: dict, backend: str) -> None:
    """One batched-2D roundtrip row after the cube sweep (BASELINE config
    #4 family). Keyed ``"{m}^2x{b}"`` in ``out['sizes']`` — the parent's
    headline picker only considers numeric (cube) keys, so this row can
    never displace the scoreboard size. Failures record per-size
    diagnostics; they never abort the already-measured cubes."""
    spec = os.environ.get("DFFT_BENCH_BATCHED", BATCHED_DEFAULT)
    if spec.strip() in ("", "0"):
        return
    try:
        b, m, chunk = (int(t) for t in spec.split(","))
    except ValueError:
        out["batched2d_error"] = (f"DFFT_BENCH_BATCHED must be "
                                  f"'batch,m,chunk', got {spec!r}")
        return
    key = f"{m}^2x{b}"
    try:
        import jax
        import jax.numpy as jnp
        from jax import lax

        import distributedfft_tpu as dfft
        from distributedfft_tpu.testing.chaintimer import median_pair_diff_ms
        from distributedfft_tpu.testing.workloads import flops_batched2d

        plan = dfft.Batched2DFFTPlan(b, m, m, dfft.SlabPartition(1),
                                     dfft.Config(fft_backend=backend),
                                     batch_chunk=chunk)
        fwd, inv = plan.forward_fn(), plan.inverse_fn()
        scale = 1.0 / float(m * m)

        def chain(kk):
            def run(seed):
                u = jax.random.uniform(jax.random.key(seed), (b, m, m),
                                       jnp.float32)
                def body(i, v):
                    return inv(fwd(v)) * scale
                return jnp.sum(jnp.abs(lax.fori_loop(0, kk, body, u)))
            return jax.jit(run)

        k = 5
        fn1, fnK = chain(1), chain(k)
        float(fn1(0))  # compile + warm (scalar readback fences)
        float(fnK(0))
        per_ms, _ = median_pair_diff_ms(fn1, fnK, 0, k, repeats=3, inner=3)
        rec = {"per_iter_ms": round(per_ms, 4), "k": k,
               "batch_chunk": chunk}
        if per_ms > 0:
            # flops_batched2d already counts forward+inverse — the chain
            # body is exactly one roundtrip.
            rec["gflops"] = round(flops_batched2d(b, m, m) / per_ms / 1e6, 1)
        else:
            rec["degenerate"] = True
        out["sizes"][key] = rec
    except TimeoutError:
        raise  # the child deadline owns this
    except Exception as e:  # noqa: BLE001 — diagnostics, not a crash
        out["sizes"][key] = {"error": f"{type(e).__name__}: {e}"[:300]}


def _child_mesh(deadline_s: int = MESH_TIMEOUT_S) -> int:
    """CPU-mesh metrics (CPU-only): raw all-to-all GB/s, the slab
    pipeline's achieved fraction of it, and a CPU fallback roundtrip."""
    t_child0 = time.monotonic()

    from distributedfft_tpu.parallel.mesh import force_cpu_devices
    force_cpu_devices(8)  # portable across jax releases (pre-0.5 lacks
    # the jax_num_cpu_devices option and needs the XLA flag instead)

    import jax
    import numpy as np

    import distributedfft_tpu as dfft
    from distributedfft_tpu.testing import chaintimer, microbench

    out = {}
    prof = None
    # Internal deadline mirroring _child_tpu: _child_mesh prints its
    # JSON once at exit, so without this a parent kill at
    # MESH_TIMEOUT_S discards the already-measured core gate metrics
    # (SIGALRM can lag a long C++ compile, but CPU-backend compiles
    # are seconds, bounding the overrun).
    def _handler(signum, frame):
        raise TimeoutError("mesh child deadline")
    signal.signal(signal.SIGALRM, _handler)
    signal.alarm(max(30, deadline_s - 20))
    try:
        prof = _enter_profile("mesh")
        # DFFT_BENCH_MESH_N: test hook shrinking the mesh-child volume so
        # the full parent pipeline is runnable in CI time (default =
        # BASELINE 256).
        n, p = int(os.environ.get("DFFT_BENCH_MESH_N", "256")), 8
        shape = (n, n, n)

        # Pipeline: time the transpose stage of the staged slab forward on the
        # spectral volume it actually exchanges.
        g = dfft.GlobalSize(n, n, n)
        plan = dfft.SlabFFTPlan(g, dfft.SlabPartition(p),
                                dfft.Config(comm_method=dfft.CommMethod.ALL2ALL))
        stages = plan.forward_stages()
        x = plan.pad_input(np.random.default_rng(0).random(g.shape)
                           .astype(np.float32))

        # --selftest forwarding (parent flag -> DFFT_BENCH_SELFTEST): one
        # guarded roundtrip of the mesh plan before anything is timed —
        # the PASS/FAIL line and residuals land in the child JSON.
        if os.environ.get("DFFT_BENCH_SELFTEST"):
            try:
                from distributedfft_tpu.resilience.selftest import \
                    run_selftest
                st = run_selftest(plan)
                out["selftest"] = {"ok": st["ok"], "checks": st["checks"]}
            except TimeoutError:
                raise
            except Exception as e:  # noqa: BLE001 — diagnostics only
                out["selftest"] = {"ok": False,
                                   "error": f"{type(e).__name__}: {e}"[:200]}
        vals = [x]
        xpose_fn = None
        xdesc = plan._xpose_desc()
        for desc, fn in stages:
            if desc == xdesc:
                xpose_fn = (fn, vals[-1])
            vals.append(fn(vals[-1]))
        spec = vals[1]               # complex spectral volume exchanged

        # North-star gate: the pipeline transpose's achieved fraction of the
        # raw collective ceiling, measured with the K-chained interleaved-pair
        # methodology (microbench.transpose_fraction_chain) so fraction <= 1
        # holds by construction in expectation — the ceiling chain's work is a
        # strict per-iteration subset of the pipeline chain's, and the chain
        # amortizes the dispatch noise that made single-window ratios land
        # anywhere in 0.5-1.4 (VERDICT r2 weak#1). Guarded: a precondition
        # failure must not discard the remaining mesh metrics.
        try:
            # Selection stays cheap (3 repeats x 2 inner iterations — it only
            # ranks); publication gets 13x4: VERDICT r4 weak #1 — the
            # published interval must clear 0.70 at both ends and stay <= ~1.
            # Measured 2026-07-31: at 9x4 a QUIET host gave IQR 0.874-0.925
            # in a 103 s child, while a host loaded with a concurrent test
            # suite gave 0.76-1.04 — the extra repeats buy loaded-host
            # robustness with ~200 s of deadline headroom to spare
            # (MESH_TIMEOUT_S=300, geometry matrix still to run).
            # streams_variants=(4,): the chunked-exchange (STREAMS) rendering
            # races in selection alongside opt0/opt1 — if splitting the
            # collective ever beats the monolithic realigned exchange, the
            # gate's winner (and the artifact) will say so.
            frac = microbench.transpose_fraction_chain(
                plan, spec, repeats=5, iterations=2, selection_repeats=3,
                publication_repeats=13, publication_iterations=4,
                streams_variants=(4,))
            if frac.get("degenerate"):
                # Every repeat's pair difference was swamped by noise: there
                # is no gate value to publish (NOT a fraction of 0 or 1).
                raise RuntimeError(
                    f"fraction chain degenerate ({frac['dropped']} repeats "
                    "dropped); raise k on this host")
            out["pipeline_xpose_gb_per_s"] = frac["pipe_gb_per_s"]
            out["alltoall_raw_gb_per_s"] = frac["raw_gb_per_s"]
            out["alltoall_fraction"] = frac["fraction"]
            out["alltoall_fraction_spread"] = frac["fraction_spread"]
            out["alltoall_fraction_range"] = frac["fraction_range"]
            out["alltoall_fraction_gate_phase"] = frac["gate_phase"]
            out["alltoall_fraction_gate_note"] = frac["gate_note"]
            if "variant" in frac:
                out["alltoall_fraction_variant"] = frac["variant"]
                out["alltoall_fraction_variants"] = frac["variants"]
        except TimeoutError:
            raise  # the child deadline must reach the partial-print path
        except Exception as e:  # noqa: BLE001 — ceiling probe is optional
            out["alltoall_raw_error"] = f"{type(e).__name__}: {e}"
            # Fallback: single-window pipeline bandwidth so the metric block
            # is never empty (no fraction without a same-context ceiling).
            fn, arg = xpose_fn
            t = microbench._time_fn(fn, arg, iterations=5, warmup=1)
            out["pipeline_xpose_gb_per_s"] = round(spec.nbytes / t / 1e9, 3)

        # Overlap detector (ring rendering): compile the ring-assembled slab
        # forward (SendMethod.RING, Z_Then_YX — the sequence with per-block
        # pipelined FFTs) and report the async-collective instance counts
        # from its HLO (microbench.async_collective_counts). Structural, not
        # timed: collective_permute (+ its -start form on TPU, where the
        # async lowering rewrites each permute) >= P-1 is the proof the
        # exchange is genuinely split into distinct steps XLA cannot re-fuse (the
        # STREAMS chunked reshards WERE re-fused — OVERLAP.md), and the
        # *_start counts report whether this backend scheduled them
        # asynchronously (always 0 on the CPU mesh, whose collectives lower
        # synchronously; nonzero on a TPU mesh = measured overlap
        # capability). Guarded: optional attribution data.
        try:
            rplan = dfft.SlabFFTPlan(
                g, dfft.SlabPartition(p),
                dfft.Config(send_method=dfft.SendMethod.RING),
                sequence="Z_Then_YX")
            compiled = rplan._build_r2c().lower(
                jax.ShapeDtypeStruct(rplan.input_padded_shape,
                                     np.float32)).compile()
            out["async_collective_ops"] = \
                microbench.async_collective_counts(compiled)
        except TimeoutError:
            raise
        except Exception as e:  # noqa: BLE001 — optional attribution data
            out["async_collective_error"] = f"{type(e).__name__}: {e}"

        # Wire-dtype rows: the realigned transpose pair (forward + inverse
        # exchange — plan._xpose_bodies, the exact bodies the pipeline
        # ships) timed at each wire encoding, reporting
        # wire_bytes_per_transpose (native vs bf16: HALVED for the complex64
        # payload), RAW GB/s (wire bytes / time) and EFFECTIVE GB/s
        # (logical complex bytes / time) — so a compression win shows up as
        # an effective-bandwidth gain over the same logical volume rather
        # than a mystery speedup, plus the bf16 pair's measured max rel
        # error (two lossy crossings). Guarded: optional attribution data.
        try:
            from jax.sharding import NamedSharding as _NS

            from distributedfft_tpu.parallel.transpose import wire_nbytes
            ish = _NS(plan.mesh, plan._in_spec)
            wire_rows = {}
            for w in ("native", "bf16"):
                xf, xi = plan._xpose_bodies(True, wire=w)
                fn = jax.jit(jax.shard_map(lambda v: xi(xf(v)),
                                           mesh=plan.mesh,
                                           in_specs=plan._in_spec,
                                           out_specs=plan._in_spec),
                             in_shardings=ish, out_shardings=ish)
                t = microbench._time_fn(fn, spec, iterations=3, warmup=1)
                wbytes = int(wire_nbytes(spec.shape, spec.dtype, w))
                row = {"wire_bytes_per_transpose": wbytes,
                       "raw_gb_per_s": round(2 * wbytes / t / 1e9, 3),
                       "effective_gb_per_s": round(2 * spec.nbytes / t / 1e9,
                                                   3)}
                if w != "native":
                    err = microbench.max_rel_err(fn(spec), spec)
                    row["max_rel_err"] = float(f"{err:.3e}")
                wire_rows[w] = row
            out["wire"] = {
                "rows": wire_rows,
                "note": ("per-exchange wire accounting of the realigned "
                         "transpose pair (2 exchanges per timing): "
                         "effective = logical complex bytes / time, raw = "
                         "wire bytes / time; bf16 is the opt-in lossy "
                         "planar-pair wire (-wire bf16), max_rel_err is "
                         "the measured forward+inverse pair error"),
            }
        except TimeoutError:
            raise
        except Exception as e:  # noqa: BLE001 — optional attribution data
            out["wire_error"] = f"{type(e).__name__}: {e}"

        # Geometry attribution matrix (reference testcases 1-3: 1D/2D/3D-memcpy
        # probes, tests_reference.hpp:53-96): exchange bandwidth per geometry x
        # strategy, with the collectives found in the compiled HLO as evidence.
        # Guarded: a failure here must not discard the core metrics above.
        try:
            geoms = {}
            for geom in ("1d", "2d", "3d"):
                r = microbench.transpose_bandwidth(shape, p, explicit=True,
                                                   iterations=3, warmup=1,
                                                   geometry=geom)
                geoms[geom] = {"gb_per_s": round(r["gb_per_s"], 3),
                               "hlo": ",".join(r["collective_ops"])}
            out["geometry_gb_per_s"] = geoms
        except TimeoutError:
            raise
        except Exception as e:  # noqa: BLE001 — optional attribution data
            out["geometry_error"] = f"{type(e).__name__}: {e}"

        # Distributed-pipeline roundtrip per slab sequence (VERDICT r4 item
        # 5: one non-default-sequence row the artifact measures itself —
        # Z_Then_YX exchanges the full complex volume where ZY_Then_X
        # exchanges the halved one, so their ratio is a real diagnostic, not
        # a duplicate). K-chained forward∘inverse over the mesh; scale folds
        # the Nx·Ny·Nz roundtrip factor back out so the loop is numerically
        # stationary. Guarded: diagnostics must not discard the core metrics.
        # Supplement headroom: even with the internal SIGALRM (whose
        # late firing still costs every in-flight supplement sample),
        # skip the block when the child is already deep into its grant —
        # the cheap CPU-fallback row behind it matters more.
        if time.monotonic() - t_child0 > 0.6 * MESH_TIMEOUT_S:
            out["mesh_sequence_error"] = "skipped: mesh child deadline headroom"
        else:
            try:
                import jax.numpy as jnp
                from jax import lax

                seq_rows = {}
                scale = 1.0 / float(n) ** 3
                for seq in ("ZY_Then_X", "Z_Then_YX"):
                    splan = dfft.SlabFFTPlan(
                        g, dfft.SlabPartition(p),
                        dfft.Config(comm_method=dfft.CommMethod.ALL2ALL),
                        sequence=seq)
                    fwd, inv = splan.forward_fn(), splan.inverse_fn()
                    ishard = splan.input_sharding

                    def chain(kk, fwd=fwd, inv=inv, ishard=ishard):
                        def run(v):
                            w = lax.fori_loop(
                                0, kk, lambda i, u: inv(fwd(u)) * scale, v)
                            return jnp.sum(jnp.abs(w))  # scalar fence
                        return jax.jit(run, in_shardings=ishard)

                    xs = jax.device_put(
                        np.random.default_rng(0)
                        .random(splan.input_padded_shape)
                        .astype(np.float32), ishard)
                    f1, f4 = chain(1), chain(4)
                    float(f1(xs))
                    float(f4(xs))
                    per_ms, _ = chaintimer.median_pair_diff_ms(f1, f4, xs, 4,
                                                               repeats=3,
                                                               inner=1)
                    rec = {"roundtrip_ms": round(per_ms, 3)}
                    if per_ms <= 0:
                        rec["degenerate"] = True  # chaintimer contract
                    seq_rows[seq] = rec
                out["mesh_pipeline_sequences"] = seq_rows
            except TimeoutError:
                raise
            except Exception as e:  # noqa: BLE001 — optional diagnostics
                out["mesh_sequence_error"] = f"{type(e).__name__}: {e}"

        # Stage-attributed device profile (ISSUE 12): the slab forward at
        # the mesh size under jax.profiler, device time joined onto the
        # declared plan-graph nodes — plus the overlap-schedule sweep at
        # a small size (ISSUE 16: serial ring, the shipped depth-2
        # overlap, the depth-4/8 revolving rings, the sub-block split,
        # and the pipelined all-to-all), so ROADMAP item 3's overlap
        # decision is ATTRIBUTED (which stage's time moved), not just
        # timed, and each sweep row carries its own roofline_fraction.
        # Guarded and headroom-gated: attribution extras never cost the
        # core metrics or the deadline.
        if time.monotonic() - t_child0 > 0.7 * MESH_TIMEOUT_S:
            out["stage_profile_error"] = \
                "skipped: mesh child deadline headroom"
        else:
            try:
                from distributedfft_tpu.obs import profile as prof_mod
                sp = {"alltoall": _stage_profile_brief(
                    prof_mod.stage_profile(plan, "forward", 3, iters=2))}
                ng = 64
                gg = dfft.GlobalSize(ng, ng, ng)
                ovl = dfft.SendMethod.RING_OVERLAP
                sweep = (
                    ("ring", dict(send_method=dfft.SendMethod.RING)),
                    ("ring_overlap", dict(send_method=ovl)),
                    ("ring_overlap_d4", dict(send_method=ovl,
                                             overlap_depth=4)),
                    ("ring_overlap_d8", dict(send_method=ovl,
                                             overlap_depth=8)),
                    ("ring_overlap_s2", dict(send_method=ovl,
                                             overlap_subblocks=2)),
                    ("a2a_pipe", dict(comm_method=dfft.CommMethod.ALL2ALL,
                                      opt=1, overlap_subblocks=2)),
                )
                for label, kw in sweep:
                    op = dfft.SlabFFTPlan(gg, dfft.SlabPartition(p),
                                          dfft.Config(**kw),
                                          sequence="Z_Then_YX")
                    sp[label] = _stage_profile_brief(
                        prof_mod.stage_profile(op, "forward", 3, iters=2))
                    sp[label]["n"] = ng
                out["stage_profile"] = sp
                # Per-row roofline fraction for the overlap sweep (the
                # acceptance gate: every sweep row is tracked, and the
                # CI roofline job fails a >10% residual regression on
                # any row present in the committed BENCH_DETAILS.json).
                try:
                    from distributedfft_tpu.evalkit import roofline as rl
                    oroof = {}
                    for label, _ in sweep:
                        ms = sp.get(label, {}).get("total_ms")
                        if ms:
                            row = rl.roofline_row(ms, ng, "xla", p,
                                                  kind=rl.V5E_KIND,
                                                  mode="forward")
                            if row:
                                oroof[f"overlap:{label}"] = row
                    if oroof:
                        out["overlap_roofline"] = oroof
                except Exception:  # noqa: BLE001 — attribution extra
                    pass
            except TimeoutError:
                raise
            except Exception as e:  # noqa: BLE001 — attribution extra
                out["stage_profile_error"] = f"{type(e).__name__}: {e}"

        # CPU fallback roundtrip (used as the headline only if the TPU path is
        # unreachable; CPU timers are reliable so a short chain suffices).
        x1 = jax.device_put(np.random.default_rng(0).random(shape)
                            .astype(np.float32))
        fn1 = chaintimer.roundtrip_chain(1, shape, "xla")
        fn5 = chaintimer.roundtrip_chain(5, shape, "xla")
        float(fn1(x1))
        float(fn5(x1))
        per_ms, _ = chaintimer.median_pair_diff_ms(fn1, fn5, x1, 5,
                                                   repeats=2, inner=1)
        out["cpu_roundtrip_ms"] = round(per_ms, 3)
        out["cpu_roundtrip_n"] = n
    except TimeoutError as e:
        out["partial"] = True
        out["error"] = str(e)
    except Exception as e:  # noqa: BLE001 — still print what was measured
        out["partial"] = True
        out["error"] = f"{type(e).__name__}: {e}"
    # Tracked roofline fractions for this child's measured rows (the CI
    # roofline job runs exactly this child on the CPU mesh and gates on
    # these): the single-device CPU fallback roundtrip and the
    # distributed per-sequence pipeline roundtrips. CPU fractions are
    # tiny by construction (scored against the v5e peaks, named here on
    # purpose) — they are TRACKING numbers, comparable across runs,
    # which is all the gate needs.
    try:
        from distributedfft_tpu.evalkit import roofline as rl
        roof = {}
        n_cpu = out.get("cpu_roundtrip_n")
        if out.get("cpu_roundtrip_ms") and n_cpu:
            row = rl.roofline_row(out["cpu_roundtrip_ms"], int(n_cpu),
                                  "xla", kind=rl.V5E_KIND)
            if row:
                roof[f"cpu:{n_cpu}"] = row
        mesh_n = int(os.environ.get("DFFT_BENCH_MESH_N", "256"))
        for seq, rec in (out.get("mesh_pipeline_sequences") or {}).items():
            ms = rec.get("roundtrip_ms")
            if ms and not rec.get("degenerate"):
                row = rl.roofline_row(ms, mesh_n, "xla", 8,
                                      kind=rl.V5E_KIND)
                if row:
                    roof[f"mesh:{seq}"] = row
        if roof:
            out["roofline"] = roof
    except Exception:  # noqa: BLE001 — attribution extra only
        pass
    if prof is not None:
        try:
            prof.__exit__(None, None, None)
        except Exception:  # noqa: BLE001 — flushing a trace is best-effort
            pass
    _fold_obs_metrics(out)
    signal.alarm(0)
    print(json.dumps(out))
    return 0


def _child_serve(deadline_s: int = 90) -> int:
    """Serving-layer saturation bench (ISSUE 8; CPU mesh):
    cold per-invocation plan-build+execute vs warm plan-cache p50 for a
    repeated shape, then an open-loop offered-load sweep (Poisson
    arrivals via ``testing/workloads.serve_load``) reporting p50/p99
    latency, sustained FFTs/sec, shed counts and the plan-cache hit rate
    at each rate — the steady-state workload every later perf PR is
    measured against (ROADMAP item 2)."""
    from distributedfft_tpu.parallel.mesh import force_cpu_devices
    force_cpu_devices(8)

    import numpy as np

    from distributedfft_tpu.serve import Server
    from distributedfft_tpu.testing.workloads import serve_load

    out = {}

    def _handler(signum, frame):
        raise TimeoutError("serve child deadline")
    signal.signal(signal.SIGALRM, _handler)
    signal.alarm(max(20, deadline_s - 10))
    try:
        n = int(os.environ.get("DFFT_BENCH_SERVE_N", "128"))
        shape = (n, n)
        rng = np.random.default_rng(0)

        # Cold per-invocation baseline: what every CLI run pays today —
        # a fresh plan (trace + compile) per request. Each sample uses a
        # FRESH plan object, so jit caching cannot hide the build.
        from distributedfft_tpu import Config, SlabPartition
        from distributedfft_tpu.models.batched2d import Batched2DFFTPlan
        cold_ms = []
        for i in range(3):
            x = rng.random(shape, dtype=np.float32)
            t0 = time.perf_counter()
            plan = Batched2DFFTPlan(1, n, n, SlabPartition(1), Config(),
                                    batch_chunk=1)
            np.asarray(plan.exec_forward(x[None]))
            cold_ms.append((time.perf_counter() - t0) * 1e3)
        out["cold_per_invocation_ms"] = round(sorted(cold_ms)[1], 3)

        # Warm plan-cache path: one server, repeated same-shape requests.
        with Server(latency_budget_ms=10_000) as srv:
            srv.request(rng.random(shape, dtype=np.float32))  # build once
            warm = []
            for i in range(30):
                x = rng.random(shape, dtype=np.float32)
                t0 = time.perf_counter()
                srv.request(x)
                warm.append((time.perf_counter() - t0) * 1e3)
            warm = np.asarray(warm)
            out["warm_p50_ms"] = round(float(np.percentile(warm, 50)), 3)
            out["warm_p99_ms"] = round(float(np.percentile(warm, 99)), 3)
            out["warm_speedup_vs_cold"] = round(
                out["cold_per_invocation_ms"] / out["warm_p50_ms"], 1)

        # Offered-load sweep: open loop, fresh server per rate so each
        # row's queue/EMA state is independent. The top rate is sized to
        # exceed the warm capacity so shedding is exercised, not assumed.
        warm_rate = 1e3 / max(out["warm_p50_ms"], 1e-3)
        rates = sorted({round(r, 1) for r in (
            warm_rate * 0.25, warm_rate * 0.5, warm_rate,
            warm_rate * 2.0)})
        rows = []
        for rate in rates:
            with Server(latency_budget_ms=250.0, max_queue=64) as srv:
                r = serve_load(srv, rate_hz=rate, duration_s=2.5,
                               shapes=(shape,), seed=1, warmup=2)
                snap = srv.health()["plan_cache"]
                r["plan_cache_hit_rate"] = snap["hit_rate"]
                r["shed"] = r["outcomes"]["shed"]
                rows.append(r)
        out["offered_load_sweep"] = rows
        out["shape"] = list(shape)
        out["note"] = ("open-loop Poisson arrivals (serve_load) against "
                       "dfft-serve's in-process Server on the CPU backend; "
                       "latency_budget_ms=250, max_coalesce=8, "
                       "batch_chunk=1; warm-cache p50 must beat "
                       "cold_per_invocation_ms (plan-build+execute)")
    except TimeoutError as e:
        out["partial"] = True
        out["error"] = str(e)
    except Exception as e:  # noqa: BLE001 — still print what was measured
        out["partial"] = True
        out["error"] = f"{type(e).__name__}: {e}"
    _fold_obs_metrics(out)
    signal.alarm(0)
    print(json.dumps(out))
    return 0


def _child_fleet(deadline_s: int = FLEET_TIMEOUT_S) -> int:
    """Fleet scaling bench (ISSUE 13; CPU-only): the
    open-loop Poisson sweep driven against ``serve.Fleet`` at 1, 2 and
    4 subprocess ``Server`` workers for one repeated shape. Each row
    quotes achieved FFTs/sec, p50/p99 latency and shed count at ONE
    FIXED offered rate — 2.2x the 1-worker warm capacity, past what one
    worker can carry but absorbable by two — so the rows tell a stable
    story (1 worker saturates and sheds at the latency budget; 2 and 4
    absorb the same load with falling p99) instead of chasing a
    per-worker rate that the submit harness and the rendezvous key
    split both distort. ``speedup_vs_1`` is the committed scaling
    claim. The traffic mixes over a 24-key SHAPE SET: plan-key affinity
    routing scales with key diversity — a single hot key pins to one
    worker by design, so a one-key sweep would measure nothing but that
    worker. Workers are real subprocesses sharing this host's cores
    (spawn + jax import per worker — ``spawn_s`` is the honest cost of
    a scale-up), so the CPU rows bound below ideal scaling."""
    import numpy as np

    from distributedfft_tpu.serve import Fleet
    from distributedfft_tpu.testing.workloads import serve_load

    out = {}

    def _handler(signum, frame):
        raise TimeoutError("fleet child deadline")
    signal.signal(signal.SIGALRM, _handler)
    signal.alarm(max(30, deadline_s - 10))
    rows = []
    try:
        n = int(os.environ.get("DFFT_BENCH_FLEET_N", "48"))
        shapes = [(n + 2 * i, n + 2 * i) for i in range(24)]
        rng = np.random.default_rng(0)
        rate = None
        for workers in (1, 2, 4):
            t0 = time.perf_counter()
            # cache_capacity covers the whole key mix so every row
            # measures compute capacity, not LRU thrash — the 1-worker
            # baseline would otherwise rebuild plans all drive long
            # (24 keys > the default 8 slots), flattering the fleet.
            # Each worker is pinned to ONE intra-op thread: XLA CPU
            # otherwise threads every FFT across all host cores, so a
            # single worker already saturates the box and extra
            # processes only oversubscribe (measured: 4 workers SLOWER
            # than 1 without the pin) — with it, fleet scaling is real
            # process-level parallelism up to the core count.
            single = {"XLA_FLAGS": "--xla_cpu_multi_thread_eigen=false "
                                   "intra_op_parallelism_threads=1",
                      "OMP_NUM_THREADS": "1",
                      "OPENBLAS_NUM_THREADS": "1"}
            f = Fleet(workers, worker_backend="server",
                      heartbeat_interval_s=0.5, max_coalesce=1,
                      cache_capacity=len(shapes) + 2,
                      worker_env=single,
                      latency_budget_ms=500.0)
            graceful = False
            try:
                spawn_s = time.perf_counter() - t0
                # Warm through ROUTED requests (3 per key) so exactly
                # the owner worker of each key compiles its plan —
                # bucket prewarm across all workers would dominate the
                # child budget for plans that never serve.
                warm = []
                for shape in shapes:
                    # First request per key pays the cold plan build;
                    # only the SECOND (warm) one feeds the capacity
                    # estimate.
                    for i in range(2):
                        x = rng.random(shape, dtype=np.float32)
                        t1 = time.perf_counter()
                        f.request(x, timeout_s=60)
                        if i:
                            warm.append((time.perf_counter() - t1) * 1e3)
                if rate is None:
                    # Fixed offered load for EVERY row: 2.2x the
                    # 1-worker warm capacity (bounded so the open-loop
                    # submit harness itself can hold the schedule).
                    base = 1e3 / max(float(np.median(warm)), 1e-3)
                    rate = round(min(2.2 * base, 700.0), 1)
                r = serve_load(f, rate_hz=rate, duration_s=2.0,
                               shapes=shapes, seed=1, warmup=0)
                h = f.health()
                rows.append({
                    "workers": workers, "spawn_s": round(spawn_s, 2),
                    "offered_rate_hz": rate,
                    "achieved_fps": r["achieved_fps"],
                    "p50_ms": r["p50_ms"], "p99_ms": r["p99_ms"],
                    "shed": r["outcomes"]["shed"],
                    "worker_deaths": h["counters"]["worker_deaths"],
                })
                f.close(drain=True, timeout_s=30.0)
                graceful = True
            finally:
                if not graceful:
                    # The alarm (or any failure) landed mid-drive: a
                    # drain=True close here could outlive the parent's
                    # 10 s post-alarm kill margin and lose the salvage
                    # JSON below — drop the queue and report partial
                    # rows instead (close is idempotent).
                    f.close(drain=False, timeout_s=5.0)
        out["scaling"] = rows
        out["shapes"] = [list(s) for s in shapes]
        # ISSUE 20: ONE 3D-volume row — the serving envelope (admission,
        # keying, queue, crop-to-logical) around a served slab volume vs
        # driving the same 8-device SlabFFTPlan by hand in-process. The
        # served path adds pipe transport + host crop; the row quotes
        # that overhead honestly rather than hiding it in a sweep.
        try:
            n3 = int(os.environ.get("DFFT_BENCH_FLEET_N3", "64"))
            from distributedfft_tpu import params as pm
            from distributedfft_tpu.models.slab import SlabFFTPlan
            from distributedfft_tpu.parallel.mesh import force_cpu_devices
            force_cpu_devices(8)  # before first backend touch here
            v = rng.random((n3, n3, n3), dtype=np.float32)
            f = Fleet(1, worker_backend="server", worker_devices=[8],
                      heartbeat_interval_s=0.5, cache_capacity=4)
            try:
                f.prewarm((n3, n3, n3), transform="r2c")
                f.request(v, "r2c", timeout_s=300)  # warm the route
                served = []
                for _ in range(5):
                    t1 = time.perf_counter()
                    f.request(v, "r2c", timeout_s=300)
                    served.append((time.perf_counter() - t1) * 1e3)
                f.close(drain=True, timeout_s=30.0)
            finally:
                f.close(drain=False, timeout_s=5.0)
            plan = SlabFFTPlan(pm.GlobalSize(n3, n3, n3),
                               pm.SlabPartition(8), pm.Config(),
                               transform="r2c")
            np.asarray(plan.crop_spectral(plan.exec_r2c(v)))  # warm
            direct = []
            for _ in range(5):
                t1 = time.perf_counter()
                np.asarray(plan.crop_spectral(plan.exec_r2c(v)))
                direct.append((time.perf_counter() - t1) * 1e3)
            sp50 = round(float(np.median(served)), 3)
            dp50 = round(float(np.median(direct)), 3)
            out["volume"] = {
                "shape": [n3, n3, n3], "decomp": "slab",
                "transform": "r2c", "devices": 8,
                "served_p50_ms": sp50, "direct_p50_ms": dp50,
                "envelope_overhead_ms": round(sp50 - dp50, 3),
                "envelope_overhead_x": round(sp50 / max(dp50, 1e-9), 3),
            }
        except Exception as e:  # noqa: BLE001 — the row is optional
            out["volume"] = {"error": f"{type(e).__name__}: {e}"[:200]}
        import multiprocessing as _mp
        out["host_cores"] = _mp.cpu_count()
        out["note"] = ("open-loop Poisson arrivals (serve_load) against "
                       "serve.Fleet (real subprocess Server workers "
                       "pinned to ONE intra-op thread each, rendezvous "
                       "plan-key routing over a 24-key shape mix, "
                       "max_coalesce=1) on the CPU backend; ONE fixed "
                       "offered rate (2.2x the 1-worker warm capacity) "
                       "for every row. Expect speedup_vs_1 to rise to "
                       "~host_cores workers and DEGRADE past it (router "
                       "+ worker processes oversubscribe the shared "
                       "cores) — the scaling claim is per-core, the "
                       "TPU-host fleet is where per-worker accelerators "
                       "make it linear. Compare achieved_fps against "
                       "BENCH_DETAILS.json's \"serve\" single-process "
                       "sweep plateau.")
    except TimeoutError as e:
        out["partial"] = True
        out["error"] = str(e)
        out.setdefault("scaling", rows)  # keep the rows already measured
    except Exception as e:  # noqa: BLE001 — still print what was measured
        out["partial"] = True
        out["error"] = f"{type(e).__name__}: {e}"
        out.setdefault("scaling", rows)
    if out.get("scaling"):
        ref = out["scaling"][0]["achieved_fps"] or 1.0
        for row in out["scaling"]:
            row["speedup_vs_1"] = round(row["achieved_fps"] / ref, 2)
    _fold_obs_metrics(out)
    signal.alarm(0)
    print(json.dumps(out))
    return 0


def _child_solvers(deadline_s: int = SOLVERS_TIMEOUT_S) -> int:
    """Solvers-suite bench (ISSUE 9; CPU mesh): (1) the
    Navier-Stokes RK4 step time — 2D vorticity ensemble on the batched-2D
    plan and a small 3D slab solve — the repeated-forward/inverse
    steady-state workload ROADMAP item 4 names; (2) Bluestein vs
    zero-padding throughput for a prime-size transform: the chirp-z
    backend at the EXACT length against the two things users otherwise
    do — run the prime length through the generic xla path, or pad the
    DATA to the next smooth size (which changes the transform, but is
    the classic workaround whose cost the race should quote)."""
    from distributedfft_tpu.parallel.mesh import force_cpu_devices
    force_cpu_devices(8)

    import numpy as np

    out = {}

    def _handler(signum, frame):
        raise TimeoutError("solvers child deadline")
    signal.signal(signal.SIGALRM, _handler)
    signal.alarm(max(20, deadline_s - 10))
    try:
        import jax

        from distributedfft_tpu import Config, GlobalSize, SlabPartition
        from distributedfft_tpu.models.slab import SlabFFTPlan
        from distributedfft_tpu.solvers.navier_stokes import (
            NavierStokes3D, taylor_green_3d)
        from distributedfft_tpu.testing.workloads import (flops_ns2d_step,
                                                          ns2d_chain)
        rng = np.random.default_rng(0)

        def _median_ms(fn, x, reps: int = 5):
            fn(x)  # compile + warm
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x))
                ts.append((time.perf_counter() - t0) * 1e3)
            return sorted(ts)[len(ts) // 2]

        # NS 2D step time: k-step scan chain, per-step = (t_K - t_1)/(K-1)
        # (the chaintimer pair-difference convention, so compile/dispatch
        # overheads cancel).
        b, n, k = 4, int(os.environ.get("DFFT_BENCH_NS_N", "64")), 8
        f1, _ = ns2d_chain(1, b, n, shard="x",
                           partition=SlabPartition(8))
        fk, _ = ns2d_chain(k, b, n, shard="x",
                           partition=SlabPartition(8))
        w0 = rng.random((b, n, n), dtype=np.float32)
        t1 = _median_ms(lambda v: f1(v), w0)
        tk = _median_ms(lambda v: fk(v), w0)
        step_ms = max((tk - t1) / (k - 1), 0.0)
        out["ns2d"] = {
            "batch": b, "n": n, "steps": k,
            "step_ms": round(step_ms, 3),
            "gflops": round(flops_ns2d_step(b, n) / (step_ms * 1e-3) / 1e9,
                            2) if step_ms > 0 else None,
            "note": "RK4 step = 20 distributed fwd/inv transforms "
                    "(shard='x', 8-dev CPU mesh); pair-difference timing"}

        # NS 3D smoke number: one WHOLE 1-step solve on a small slab cube
        # — 36 RHS transforms (4 RK4 stages x 9) PLUS the entry/exit
        # conversions (3 fwd + Leray projection in, 3 inv out) and the
        # call dispatch. Deliberately NOT named step_ms: it is a
        # solve-invocation time, not a pair-difference-corrected per-step
        # cost like the ns2d field, and the two must not be compared.
        n3 = int(os.environ.get("DFFT_BENCH_NS3_N", "32"))
        plan3 = SlabFFTPlan(GlobalSize(n3, n3, n3), SlabPartition(8),
                            Config(fft_backend="matmul"))
        ns3 = NavierStokes3D(plan3, 1e-3)
        sfn3 = jax.jit(ns3.solve_fn(1, 1e-3))
        u0 = taylor_green_3d(n3, dtype=np.float32)
        out["ns3d"] = {
            "n": n3,
            "solve1_ms": round(_median_ms(sfn3, u0), 3),
            "note": "whole 1-step solve_fn call (entry/exit transforms "
                    "included) — not comparable to ns2d.step_ms"}

        # Bluestein vs zero-padding: prime-size batched 1D-per-axis 2D
        # transform (np-correct length) vs the padded smooth alternative.
        p = int(os.environ.get("DFFT_BENCH_PRIME", "251"))
        from distributedfft_tpu.ops import fft as lf
        from distributedfft_tpu.ops.bluestein import chirp_length, good_size
        stack = rng.random((32, p, p), dtype=np.float32)

        def _fwd2(backend):
            def fn(x):
                c = lf.rfft(x, axis=-1, backend=backend)
                return lf.fft(c, axis=-2, backend=backend)
            return jax.jit(fn)

        ms_blue = _median_ms(_fwd2("bluestein"), stack)
        ms_xla = _median_ms(_fwd2("xla"), stack)
        g = good_size(p)
        padded = np.zeros((32, g, g), dtype=np.float32)
        padded[:, :p, :p] = stack
        ms_pad = _median_ms(_fwd2("xla"), padded)
        out["bluestein"] = {
            "prime": p, "chirp_length": chirp_length(p),
            "padded_smooth": g,
            "bluestein_ms": round(ms_blue, 3),
            "xla_generic_ms": round(ms_xla, 3),
            "zero_padded_smooth_ms": round(ms_pad, 3),
            "note": "batched 2D forward (32 planes) at the EXACT prime "
                    "length via chirp-z vs xla's generic path, and the "
                    "semantics-changing pad-to-smooth workaround "
                    "(fft_backend='auto' races these per shape)"}
    except TimeoutError as e:
        out["partial"] = True
        out["error"] = str(e)
    except Exception as e:  # noqa: BLE001 — still print what was measured
        out["partial"] = True
        out["error"] = f"{type(e).__name__}: {e}"
    _fold_obs_metrics(out)
    signal.alarm(0)
    print(json.dumps(out))
    return 0


def _direct_plan_override(backend: str, n: int):
    """(MXUSettings, artifact note) for sizes where the ALL-DIRECT matmul
    plan is the measured winner; (None, None) otherwise.

    Evidence-gated: only 1024 has an on-chip race (2026-07-31 session —
    direct 652 vs chunked four-step 228 GFLOPS, and the direct roundtrip
    FITS 16 GB at 284.96 ms where the four-step's temporaries do not).
    Other above-threshold sizes keep the deployed default plan rather
    than extrapolating the 1024^3 result. The override inherits the
    DEPLOYED settings (autotune.py pattern) so only direct_max varies."""
    if backend != "matmul" or n != 1024:
        return None, None
    import dataclasses as dc

    from distributedfft_tpu.ops import mxu_fft
    if n <= mxu_fft.current_settings().direct_max:
        return None, None  # already direct under the deployed settings
    return (dc.replace(mxu_fft.current_settings(), direct_max=n),
            f"direct({n})")


def _wisdom_backend() -> tuple:
    """(backend, source-note) warm-start from the wisdom store: the
    measured local-FFT winner for the headline cube, recorded by a prior
    ``dfft-reference --autotune`` / ``fft_backend="auto"`` run. Lookup
    ONLY — bench never races on a miss (it is about to measure anyway, and
    the chip window is scarce); any failure degrades to ("", "")."""
    try:
        from distributedfft_tpu.utils import wisdom
        n = int(_headline_size())
        be, rec = wisdom.resolve_local_backend((n, n, n), False,
                                               race_on_miss=False,
                                               default="")
        if be:
            return be, f"wisdom:{n}^3"
    except Exception:  # noqa: BLE001 — warm-start is an optimization only
        pass
    return "", ""


def _child_budget(name: str, default: float) -> float:
    """Per-child wall-clock budget (ISSUE 10 satellite — the r01 timeout
    lesson: one slow child must degrade the run to a partial
    BENCH_DETAILS.json, never eat the whole driver deadline).

    ``$DFFT_BENCH_CHILD_TIMEOUT_S`` caps each child's grant: either one
    number applying to every child (``DFFT_BENCH_CHILD_TIMEOUT_S=120``)
    or per-child ``name:seconds`` pairs, comma-separated
    (``mesh:120,tpu:180,probe:60``; children: probe, mesh, serve,
    fleet, solvers, tpu). The value OVERRIDES the built-in default for that
    child but is still bounded by the parent's remaining budget above
    the measurement reserve (main() min()s as before). Malformed tokens
    are ignored — a typo'd env must not kill a bench run."""
    spec = os.environ.get("DFFT_BENCH_CHILD_TIMEOUT_S", "").strip()
    if not spec:
        return default
    blanket = None
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        key, sep, val = tok.partition(":")
        try:
            if sep:
                if key.strip() == name:
                    return max(1.0, float(val))
            else:
                blanket = max(1.0, float(tok))
        except ValueError:
            continue
    return blanket if blanket is not None else default


def _bench_sizes() -> tuple:
    """Requested sizes from DFFT_BENCH_SIZES, dropping malformed tokens;
    falls back to the default SIZES when nothing valid remains (a typo'd
    env var must degrade to the default sweep, not crash the parent after
    the mesh metrics were already gathered — ADVICE r2)."""
    raw = os.environ.get("DFFT_BENCH_SIZES", "")
    vals = tuple(int(t) for t in (tok.strip() for tok in raw.split(","))
                 if t.isdigit() and int(t) > 0)
    return vals or SIZES


def _headline_size() -> str:
    """The size the scoreboard compares against: 256 when requested (the
    BASELINE comparison size), else the largest requested size."""
    vals = _bench_sizes()
    return "256" if 256 in vals else str(max(vals))


# ---------------------------------------------------------------------------
# parent orchestrator
# ---------------------------------------------------------------------------

def _child_cmd(name: str, extra=()):
    return [sys.executable, os.path.abspath(__file__), "--child", name,
            *map(str, extra)]


def _parse_child(name: str, stdout: str, stderr: str, returncode: int):
    """(parsed last-line JSON or None, diagnostic)."""
    lines = [ln for ln in (stdout or "").strip().splitlines() if ln.strip()]
    if lines:
        try:
            return json.loads(lines[-1]), None
        except json.JSONDecodeError:
            pass
    tail = (stderr or stdout or "").strip().splitlines()[-3:]
    return None, f"{name}: rc={returncode} no JSON; tail={' | '.join(tail)}"


def _run_child(name: str, timeout_s: float, extra=()):
    """Run a child; return (parsed last-line JSON or None, diagnostic)."""
    try:
        r = subprocess.run(_child_cmd(name, extra), capture_output=True,
                           text=True, timeout=timeout_s, cwd=_REPO)
    except subprocess.TimeoutExpired:
        return None, f"{name}: killed after {timeout_s:.0f}s timeout"
    return _parse_child(name, r.stdout, r.stderr, r.returncode)


def _start_child(name: str, extra=()):
    """Launch a child without waiting (the overlapped probe). Output goes
    to TEMP FILES, not pipes: nothing drains a pipe while the mesh child
    runs, and jax/libtpu's chatty stderr would fill the ~64 KiB pipe
    buffer and block the probe mid-claim. Returns (proc, out_f, err_f)."""
    import tempfile
    out_f = tempfile.TemporaryFile(mode="w+", encoding="utf-8")
    err_f = tempfile.TemporaryFile(mode="w+", encoding="utf-8")
    proc = subprocess.Popen(_child_cmd(name, extra), cwd=_REPO,
                            stdout=out_f, stderr=err_f, text=True)
    return proc, out_f, err_f


def _collect_child(started, name: str, timeout_s: float, started_at: float):
    """Wait for a started child; on timeout, kill ONCE and report the
    TOTAL time it ran (it may have been running long before collection)."""
    proc, out_f, err_f = started

    def _read_back():
        out_f.seek(0)
        err_f.seek(0)
        stdout, stderr = out_f.read(), err_f.read()
        out_f.close()
        err_f.close()
        return stdout, stderr

    try:
        proc.wait(timeout=max(timeout_s, 0.1))
    except subprocess.TimeoutExpired:
        proc.kill()
        try:
            proc.wait(timeout=10)
        except Exception:  # noqa: BLE001 — already killed; nothing to save
            pass
        stdout, stderr = _read_back()
        tail = (stderr or stdout or "").strip().splitlines()[-3:]
        total = time.monotonic() - started_at
        return None, (f"{name}: killed after {total:.0f}s total "
                      f"(overlapped with mesh child); "
                      f"tail={' | '.join(tail)}")
    stdout, stderr = _read_back()
    return _parse_child(name, stdout, stderr, proc.returncode)


def main() -> int:
    t0 = time.monotonic()

    def remaining() -> float:
        return BUDGET_S - (time.monotonic() - t0)

    diags = []

    # 1+2 OVERLAPPED. The pre-flight probe is launched FIRST, at t=0, and
    #    the CPU-mesh child runs while the probe waits. The probe touches
    #    only the device claim, so it cannot disturb the mesh timings'
    #    host load noticeably. A clean exit with ok:false (device answered
    #    wrong) is a failure.
    probe_started = time.monotonic()
    probe_proc = _start_child("probe")

    mesh_grant = min(_child_budget("mesh", MESH_TIMEOUT_S),
                     remaining() - MEASURE_RESERVE_S)
    mesh, d = _run_child("mesh", mesh_grant, extra=(int(mesh_grant),))
    if d:
        diags.append(d)

    # 2b. Serving-layer saturation bench (ISSUE 8): CPU-only like the mesh
    #     child (CPU-only), short and bounded — the probe keeps
    #     waiting underneath it, so its cost to the TPU path is just the
    #     wall clock it occupies above the measurement reserve.
    serve = None
    serve_grant = min(_child_budget("serve", SERVE_TIMEOUT_S),
                      remaining() - MEASURE_RESERVE_S)
    if serve_grant >= 30:
        serve, d = _run_child("serve", serve_grant,
                              extra=(int(serve_grant),))
        if d:
            diags.append(d)
    else:
        diags.append("serve: skipped, no budget above the measurement "
                     "reserve")

    # 2b'. Fleet scaling bench (ISSUE 13): CPU-only like the serve
    #     child — achieved FFTs/sec at 1/2/4 subprocess workers vs the
    #     single-process plateau; spawn-heavy, so it gets its own
    #     (larger) default budget and skips first when time is short.
    fleet = None
    fleet_grant = min(_child_budget("fleet", FLEET_TIMEOUT_S),
                      remaining() - MEASURE_RESERVE_S)
    if fleet_grant >= 45:
        fleet, d = _run_child("fleet", fleet_grant,
                              extra=(int(fleet_grant),))
        if d:
            diags.append(d)
    else:
        diags.append("fleet: skipped, no budget above the measurement "
                     "reserve")

    # 2c. Solvers-suite bench (ISSUE 9): CPU-only, short and bounded —
    #     NS step time + Bluestein-vs-padded throughput; same budget
    #     posture as the serve child.
    solvers = None
    solvers_grant = min(_child_budget("solvers", SOLVERS_TIMEOUT_S),
                        remaining() - MEASURE_RESERVE_S)
    if solvers_grant >= 30:
        solvers, d = _run_child("solvers", solvers_grant,
                                extra=(int(solvers_grant),))
        if d:
            diags.append(d)
    else:
        diags.append("solvers: skipped, no budget above the measurement "
                     "reserve")

    # Collect the probe with everything left above the measurement
    # reserve (it has already been waiting the whole mesh phase).
    tpu = None
    probe, d = _collect_child(probe_proc, "probe",
                              min(_child_budget(
                                  "probe",
                                  remaining() - MEASURE_RESERVE_S),
                                  remaining() - MEASURE_RESERVE_S),
                              probe_started)
    if probe is not None and not probe.get("ok"):
        d = d or f"probe: device answered but ok=false ({probe})"
        probe = None
    if d:
        diags.append(d)
        # A CLEAN fast failure (nothing killed) earns one immediate
        # re-probe; a killed probe does not.
        rebudget = min(PROBE_TIMEOUT_S, remaining() - MEASURE_RESERVE_S)
        if "killed" not in d and rebudget >= 30:
            probe, d = _run_child("probe", rebudget)
            if probe is not None and not probe.get("ok"):
                d = d or f"probe: device answered but ok=false ({probe})"
                probe = None
            if d:
                diags.append(d + " (re-probe)")

    # 3. Real measurement only behind a clean probe. An all-failed child
    #    gets a FRESH-PROCESS retry; partial successes merge across
    #    attempts.
    if probe and probe.get("ok"):
        # Up to 6 attempts; the budget guard is the real stop condition.
        for proc_attempt in range(6):
            if proc_attempt:
                time.sleep(15)  # let the previous child release the chip
            child_budget = int(min(remaining() - 15,
                                   _child_budget("tpu",
                                                 remaining() - 15)))
            if child_budget <= 60:
                diags.append(f"tpu: stopped, only {child_budget}s left")
                break
            t, d = _run_child("tpu", child_budget + 10,
                              extra=(child_budget,))
            if d:
                diags.append(d)
            def _measured(rec) -> bool:
                return "per_iter_ms" in rec and not rec.get("degenerate")

            if t:
                if tpu is None:
                    tpu = t
                else:  # keep newest metadata, merge measured sizes:
                    # the NEW attempt's measurement always wins; an older
                    # record survives only where the new attempt has no
                    # measurement for that size (ADVICE r2: the previous
                    # condition let a stale measurement overwrite a
                    # fresh one). The per-row roofline records merge the
                    # same way — a size carried over from an earlier
                    # attempt must keep its fraction, or the CI gate's
                    # "every measured row has a roofline row" assertion
                    # fails on a valid measurement.
                    merged = dict(t.get("sizes", {}))
                    merged_roof = dict(t.get("roofline", {}))
                    for n_key, rec in (tpu.get("sizes") or {}).items():
                        if not _measured(merged.get(n_key, {})):
                            merged[n_key] = rec
                            old_roof = (tpu.get("roofline") or {}).get(n_key)
                            if old_roof and n_key not in merged_roof:
                                merged_roof[n_key] = old_roof
                    t["sizes"] = merged
                    if merged_roof:
                        t["roofline"] = merged_roof
                    tpu = t
            # Degenerate timings (median t_K - t_1 <= 0) don't count: step 4
            # would discard them, so they must not suppress the retry. And
            # the retry gates on the HEADLINE size: a run where 128^3
            # measured but 256^3 hit a bad session must still burn a fresh
            # process on the size the scoreboard compares against.
            cur = (tpu or {}).get("sizes", {})
            good = _measured(cur.get(_headline_size(), {}))
            if good:
                break
            msg = f"tpu attempt {proc_attempt + 1}: no size measured"
            if proc_attempt < 5:
                msg += "; retrying in a fresh process"
            diags.append(msg)

    # 4. Assemble the one JSON line. Headline = the 256^3 measurement
    #    (the BASELINE comparison size); if sizes were overridden and 256
    #    is absent, the largest measured size still headlines (with no
    #    cross-size vs_baseline) instead of pretending the chip failed.
    sizes = (tpu or {}).get("sizes", {})
    measured = {s: r for s, r in sizes.items()
                if "per_iter_ms" in r and not r.get("degenerate")}
    # Headline candidates are the CUBE rows only (numeric keys); the
    # batched-2D row ("4096^2x64") reports alongside but never headlines.
    cubes = {s: r for s, r in measured.items() if s.isdigit()}
    pick = "256" if "256" in cubes else (
        max(cubes, key=int) if cubes else None)
    value = measured[pick]["per_iter_ms"] if pick else None
    platform = (tpu or {}).get("platform", "?")
    backend = (tpu or {}).get("backend",
                              os.environ.get("DFFT_BENCH_BACKEND", "matmul"))
    fallback = pick is None
    mode = (tpu or {}).get("mode", "roundtrip")
    if pick and measured[pick].get("mode"):
        mode = measured[pick]["mode"]  # per-size HBM fallback changed it
    if not fallback:
        vs = (f"(vs argon single-GPU f64 cufftPlan3d {BASELINE_ROUNDTRIP_MS} "
              "ms; vs_baseline = baseline/ours, >1 is faster)"
              if pick == "256" and mode == "roundtrip" else
              "(baseline is a 256^3 roundtrip number, so no vs_baseline "
              "for this size/mode)")
        what = {"roundtrip": "R2C+C2R roundtrip", "forward": "R2C forward",
                "inverse": "C2R inverse",
                "forward-chunked": "R2C forward (chunked stages)"}.get(
                    mode, mode)
        metric = (f"single-chip {pick}^3 f32 {what} ms on "
                  f"{platform} [{backend} backend] {vs}")
    else:
        value = (mesh or {}).get("cpu_roundtrip_ms")
        cpu_n = (mesh or {}).get("cpu_roundtrip_n", 256)
        metric = (f"CPU-FALLBACK {cpu_n}^3 f32 R2C+C2R roundtrip ms on the "
                  "CPU backend — TPU path unavailable this run (see "
                  f"diagnostics; baseline {BASELINE_ROUNDTRIP_MS} ms is a "
                  "GPU number, so no cross-platform vs_baseline is reported)")
    result = {
        "metric": metric,
        "value": value if value is not None else -1.0,
        "unit": "ms",
        "vs_baseline": (round(BASELINE_ROUNDTRIP_MS / value, 3)
                        if value and value > 0 and not fallback
                        and pick == "256" and mode == "roundtrip" else None),
    }
    if sizes:
        result["tpu_sizes"] = sizes
        gf = {k: v["gflops"] for k, v in sizes.items() if "gflops" in v}
        if gf:
            result["gflops"] = gf
    if mesh:
        result["alltoall_raw_gb_per_s"] = mesh.get("alltoall_raw_gb_per_s")
        result["alltoall_fraction"] = mesh.get("alltoall_fraction")
        if mesh.get("alltoall_fraction_spread"):
            result["alltoall_fraction_spread"] = \
                mesh["alltoall_fraction_spread"]
        for key in ("alltoall_fraction_range",
                    "alltoall_fraction_gate_phase",
                    "alltoall_fraction_gate_note"):
            if mesh.get(key):
                result[key] = mesh[key]
        if mesh.get("alltoall_fraction_variant"):
            result["alltoall_fraction_variant"] = \
                mesh["alltoall_fraction_variant"]
            result["alltoall_fraction_variants"] = \
                mesh.get("alltoall_fraction_variants")
        if mesh.get("async_collective_ops"):
            # Overlap-detector counts of the ring-assembled plan's HLO
            # (microbench.async_collective_counts): collective_permute +
            # collective_permute_start >= P-1 (the async lowering on TPU
            # rewrites each permute into a start/done pair) proves the ring
            # exchange is genuinely split; the *_start
            # counts report async scheduling (0 on the CPU mesh by
            # construction, nonzero on TPU = measured overlap capability).
            result["async_collective_ops"] = mesh["async_collective_ops"]
        if mesh.get("wire"):
            # Per-exchange wire accounting (wire_bytes_per_transpose, raw
            # vs effective GB/s per wire dtype, bf16 measured error) — the
            # compressed-wire win is visible as an effective-bandwidth
            # gain, and the halved wire bytes are pinned in the record.
            result["wire"] = mesh["wire"]
        if mesh.get("geometry_gb_per_s"):
            result["geometry_gb_per_s"] = mesh["geometry_gb_per_s"]
        if mesh.get("mesh_pipeline_sequences"):
            result["mesh_pipeline_sequences"] = \
                mesh["mesh_pipeline_sequences"]
        if mesh.get("obs_metrics"):
            # Obs registry snapshot of the mesh child (wisdom hits/misses,
            # race cells, per-shard wire bytes, HLO census gauges).
            result["obs_metrics_mesh"] = mesh["obs_metrics"]
        if mesh.get("stage_profile"):
            # Stage-attributed device profile (ISSUE 12): per-node device
            # time joined onto the declared plan graph — the all-to-all
            # slab at the mesh size plus the RING vs RING_OVERLAP pair at
            # 64^3, so the overlap decision is attributed (which stage's
            # time moved), not just timed.
            result["stage_profile"] = mesh["stage_profile"]
        elif mesh.get("stage_profile_error"):
            result["stage_profile_error"] = mesh["stage_profile_error"]
    if serve:
        # Serving-layer saturation record (ISSUE 8): cold vs warm-cache
        # latency and the offered-load sweep (p50/p99, FFTs/sec, shed,
        # plan-cache hit rate) — ROADMAP item 2's decision measurement.
        result["serve"] = serve
    if fleet:
        # Fleet scaling record (ISSUE 13): achieved FFTs/sec, p50/p99
        # and shed at 1/2/4 subprocess workers behind the plan-key
        # router, vs the single-process "serve" sweep plateau.
        result["fleet"] = fleet
    if solvers:
        # Solvers-suite record (ISSUE 9): NS RK4 step time (2D ensemble +
        # 3D cube) and Bluestein-vs-zero-padded prime-size throughput.
        result["solvers"] = solvers
    # Tracked roofline block (ISSUE 10 acceptance): one record per
    # benchmarked row, computed by the children (the parent stays
    # jax-free), merged here. CI's roofline job asserts the block exists
    # with a roofline_fraction per row and regresses the fractions
    # against the committed BENCH_DETAILS.json.
    roof_rows = {}
    roof_rows.update((mesh or {}).get("roofline") or {})
    # The overlap-depth sweep rows (ISSUE 16): one tracked fraction per
    # schedule variant (ring / depth-2/4/8 overlap / sub-block split /
    # pipelined a2a), keyed "overlap:<variant>".
    roof_rows.update((mesh or {}).get("overlap_roofline") or {})
    roof_rows.update((tpu or {}).get("roofline") or {})
    if roof_rows:
        result["roofline"] = {
            "rows": roof_rows,
            "note": ("roofline_fraction = ideal_ms / measured_ms per row "
                     "(evalkit.roofline.roofline_row: exact MXU MAC model "
                     "for matmul-family backends, nominal 2.5N·log2 N for "
                     "others, against the v5e effective peak; distributed "
                     "rows divide by the mesh size, so exchange time "
                     "shows up as lost fraction). On non-TPU backends the "
                     "fraction is a tracking number, not a utilization "
                     "claim. serve/solvers rows are not FFT-roofline-"
                     "modelable and carry no record."),
        }
    if (tpu or {}).get("obs_metrics"):
        result["obs_metrics_tpu"] = tpu["obs_metrics"]
    if (tpu or {}).get("partial"):
        diags.append(f"tpu partial: {tpu.get('error')}")
    if diags:
        result["diagnostics"] = diags

    # 5. The stdout contract: ONE COMPACT final line (headline metric /
    #    value / vs_baseline — bounded size, so even a truncated 2000-char
    #    tail capture still parses), with the verbose record persisted to
    #    BENCH_DETAILS.json for humans and the snapshot.
    compact = {"metric": result["metric"], "value": result["value"],
               "unit": result["unit"], "vs_baseline": result["vs_baseline"]}
    gf = result.get("gflops") or {}
    if pick and pick in gf:
        compact["gflops"] = gf[pick]
    # DFFT_BENCH_DETAILS_PATH redirects the verbose record away from the
    # tracked repo-root file. Test runs MUST set it: the committed
    # BENCH_DETAILS.json is the CI roofline gate's regression reference
    # (t1.yml copies it aside before benching), and a starved/noisy test
    # run silently overwriting it would lower the gate's bar.
    details = (os.environ.get("DFFT_BENCH_DETAILS_PATH")
               or os.path.join(_REPO, "BENCH_DETAILS.json"))
    try:
        with open(details, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=1, sort_keys=True)
            f.write("\n")
        compact["details"] = os.path.basename(details)
        if diags:
            compact["diagnostics_n"] = len(diags)
    except OSError:
        # Could not persist the verbose record: the one-line contract still
        # holds, and the diagnostics ride inline as before (possibly long,
        # but information-preserving).
        compact = result
    print(json.dumps(compact))
    return 0


if __name__ == "__main__":
    # --profile-dir DIR (parent only): forwarded to the children via
    # DFFT_BENCH_PROFILE_DIR, so their measurement regions run inside a
    # jax.profiler trace and the device timelines carry the obs span
    # annotations. Parsed by hand — the parent must stay argparse/jax-free
    # and the flag must not disturb the --child dispatch below.
    if "--profile-dir" in sys.argv:
        _i = sys.argv.index("--profile-dir")
        if _i + 1 >= len(sys.argv):
            print("bench.py: --profile-dir needs a directory argument",
                  file=sys.stderr)
            sys.exit(2)
        os.environ["DFFT_BENCH_PROFILE_DIR"] = sys.argv[_i + 1]
        del sys.argv[_i:_i + 2]
    # --selftest (parent only): forwarded via DFFT_BENCH_SELFTEST — the
    # mesh child runs one guarded roundtrip (resilience/selftest.py) of
    # its slab plan before the timed gates and folds the PASS/FAIL +
    # residuals into its JSON (same hand-parsing rationale as above).
    if "--selftest" in sys.argv:
        os.environ["DFFT_BENCH_SELFTEST"] = "1"
        sys.argv.remove("--selftest")
    if len(sys.argv) > 2 and sys.argv[1] == "--child":
        name = sys.argv[2]
        if name == "probe":
            sys.exit(_child_probe())
        if name == "mesh":
            sys.exit(_child_mesh(int(sys.argv[3]) if len(sys.argv) > 3
                                 else MESH_TIMEOUT_S))
        if name == "tpu":
            sys.exit(_child_tpu(int(sys.argv[3]) if len(sys.argv) > 3
                                else 300))
        if name == "serve":
            sys.exit(_child_serve(int(sys.argv[3]) if len(sys.argv) > 3
                                  else SERVE_TIMEOUT_S))
        if name == "fleet":
            sys.exit(_child_fleet(int(sys.argv[3]) if len(sys.argv) > 3
                                  else FLEET_TIMEOUT_S))
        if name == "solvers":
            sys.exit(_child_solvers(int(sys.argv[3]) if len(sys.argv) > 3
                                    else SOLVERS_TIMEOUT_S))
        print(f"unknown child {name}", file=sys.stderr)
        sys.exit(2)
    try:
        sys.exit(main())
    except Exception as e:  # noqa: BLE001 — the driver needs a JSON line
        print(json.dumps({"metric": "bench crashed", "value": -1.0,
                          "unit": "ms", "vs_baseline": None,
                          "diagnostics": [f"{type(e).__name__}: {e}"]}))
        sys.exit(0)
