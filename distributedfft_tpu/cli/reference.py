"""``reference`` executable — single-device baseline + communication
microbenchmarks (reference ``tests/src/reference/main.cpp``,
``tests/include/tests_reference.hpp:42-96``).

Testcases (the reference's 1D/2D/3D-memcpy bandwidth probes, strategy via
``--opt``: 0 = Peer2Peer/GSPMD resharding, 1 = explicit All2All):
  0: full 3D FFT on one device (the reference's gather -> cufftMakePlan3d
     baseline; in the single-controller model the gather is a device_put).
  1: 1D geometry — slab transpose over a 1D mesh.
  2: 2D geometry — pencil transpose over one axis of a 2D mesh.
  3: 3D geometry — both non-exchanged axes sharded (strided in two axes).
  4: north-star fraction gate — the slab pipeline transpose's achieved
     fraction of the raw collective ceiling, via the interleaved
     K-chained-pair methodology (``microbench.transpose_fraction_chain``:
     the ceiling's work is a per-iteration subset of the pipeline's, so
     the fraction is <=1 in expectation, reported with a spread).
Each bandwidth line reports the collectives found in the compiled HLO, so
a GSPMD 'reshard' that XLA elided would be visible as an empty list.
"""

from __future__ import annotations

import argparse
import sys

from .common import (add_common_args, maybe_profile, print_obs_snapshot,
                     print_stage_profile, setup_backend)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="reference", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    add_common_args(ap, pencil=False)
    ap.add_argument("--partition1", "-p1", type=int, default=0)
    ap.add_argument("--partition2", "-p2", type=int, default=0)
    ap.add_argument("--autotune", action="store_true",
                    help="race the local-FFT backends (xla / matmul@high / "
                         "matmul@highest / pallas) for this shape on the "
                         "current device and report the fastest that meets "
                         "the accuracy budget")
    ap.add_argument("--autotune-budget", type=float, default=1e-4,
                    help="max roundtrip rel. error a backend may incur")
    ap.add_argument("--autotune-k", type=int, default=257,
                    help="chained roundtrips per timing sample; must be "
                         "large enough that the work dominates the host's "
                         "per-call constant (257 matches bench.py at "
                         "256^3)")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    setup_backend(args)

    import jax
    import numpy as np

    shape = (args.input_dim_x, args.input_dim_y, args.input_dim_z)
    dtype = np.float64 if args.double_prec else np.float32
    it, wu = args.iterations, args.warmup_rounds

    if getattr(args, "selftest", False):
        # The reference executable has no distributed plan; its selftest
        # is the single-device roundtrip at this shape (the coordinator-
        # rank baseline every distributed run is validated against).
        from .. import params as pm
        from ..models.slab import SlabFFTPlan
        from ..resilience.selftest import run_selftest
        be = args.fft_backend if args.fft_backend != "auto" else "xla"
        plan = SlabFFTPlan(
            pm.GlobalSize(*shape), pm.SlabPartition(1),
            pm.Config(double_prec=args.double_prec, fft_backend=be,
                      guards=getattr(args, "guards", None)))
        if not run_selftest(plan)["ok"]:
            print("selftest FAILED; aborting", file=sys.stderr)
            return 1

    if args.autotune:
        from ..testing import autotune as at
        prec = "f64" if args.double_prec else "f32"
        print(f"autotuning local FFT backends for {shape} {prec} on "
              f"{jax.devices()[0].platform}:")
        with maybe_profile(args):
            ranked = at.autotune_local_fft(shape, args.autotune_budget,
                                           k=args.autotune_k,
                                           double_prec=args.double_prec,
                                           verbose=True)
        best = ranked[0]
        if not best.ok:
            print(f"no usable backend: {at.describe_failures(ranked)}",
                  file=sys.stderr)
            return 1
        print(f"best: {best.label} ({best.per_iter_ms:.3f} ms/roundtrip, "
              f"rel_err {best.rel_err:.2e})")
        # Persist the measured winner so later runs (bench.py warm-start,
        # --fft-backend auto plans of this shape) reuse it instead of
        # re-racing — the explicit "tune once" entry point.
        from ..utils import wisdom
        store = wisdom.open_store(args.wisdom, not args.no_wisdom)
        if store is not None:
            key = wisdom.local_key(shape, args.double_prec)
            if store.record(key, "local_fft", wisdom.local_fft_record(best)):
                print(f"wisdom: winner recorded -> {store.path}")
        print_obs_snapshot(args)
        return 0

    with maybe_profile(args):
        rc = _dispatch(args, shape, dtype, it, wu)
    print_obs_snapshot(args)
    return rc


def _dispatch(args, shape, dtype, it, wu) -> int:
    import jax

    from ..testing import microbench as mb

    if args.testcase == 0:
        backend = args.fft_backend
        settings = None
        if backend == "auto":
            # Bare single-device transform: resolve via the wisdom store
            # (hit -> reuse, miss -> bounded race-and-record), mirroring
            # what the plan constructors do for Config(fft_backend="auto").
            from .. import params as pm
            from ..utils import wisdom
            backend, rec = wisdom.resolve_local_backend(
                shape, args.double_prec, path=args.wisdom,
                enabled=not args.no_wisdom)
            src = "wisdom" if rec is not None else "fallback"
            print(f"fft-backend auto -> {backend} ({src})")
            if rec is not None:
                # The gate/timing in the record were measured at the raced
                # precision/direct_max — run the SAME program, not the
                # backend at default MXU settings.
                settings = pm.Config(
                    fft_backend=backend,
                    mxu_precision=rec.get("mxu_precision"),
                    mxu_direct_max=rec.get("mxu_direct_max"),
                ).mxu_settings()
        elif backend is None:
            # Bare single-device transform: the platform default a plan of
            # this shape would resolve to.
            from .. import params as pm
            from ..utils import wisdom
            fields, _ = wisdom.platform_default(
                pm.Config(double_prec=args.double_prec), pm.GlobalSize(*shape))
            backend = fields["fft_backend"]
            settings = pm.Config(**fields).mxu_settings()
        ms = mb.single_device_fft_ms(shape, it, wu, dtype,
                                     backend=backend, settings=settings)
        print(f"Run complete: {ms:.4f} ms (single-device 3D R2C, "
              f"{shape[0]}x{shape[1]}x{shape[2]})")
        if getattr(args, "profile_stages", False):
            print("stage profile: needs a declared plan graph — the "
                  "single-device baseline has none (use testcase 4 or a "
                  "decomposition executable)")
        return 0

    p = len(jax.devices())
    if args.testcase in (1, 2, 3):
        explicit = args.opt != 0  # opt 0: Peer2Peer/GSPMD, opt 1: All2All
        geometry = {1: "1d", 2: "2d", 3: "3d"}[args.testcase]
        r = mb.transpose_bandwidth(shape, p, explicit=explicit,
                                   iterations=it or 1, warmup=wu,
                                   dtype=dtype, geometry=geometry)
        kind = "All2All" if explicit else "Peer2Peer(GSPMD)"
        print(f"Bandwidth: {r['gb_per_s'] * 1e3:.2f} MB/s "
              f"[{kind}, {geometry}, {p} devices, "
              f"{r['bytes'] / 1e6:.1f} MB moved in {r['seconds'] * 1e3:.3f} ms, "
              f"collectives={r['collective_ops']}]")
        if getattr(args, "profile_stages", False):
            print("stage profile: needs a declared plan graph — the "
                  "geometry probes have none (use testcase 4 or a "
                  "decomposition executable)")
        return 0
    if args.testcase == 4:
        import numpy as np

        from .. import params as pm
        from ..models.slab import SlabFFTPlan

        g = pm.GlobalSize(*shape)
        from .common import overlap_config_kwargs
        plan = SlabFFTPlan(g, pm.SlabPartition(p),
                           pm.Config(comm_method=pm.CommMethod.ALL2ALL,
                                     double_prec=args.double_prec,
                                     guards=getattr(args, "guards", None),
                                     **overlap_config_kwargs(args)))
        x = plan.pad_input(np.random.default_rng(0).random(g.shape)
                           .astype(dtype))
        spec = plan.forward_stages()[0][1](x)
        # --streams-chunks N (N > 1: chunks=1 is byte-identical to the
        # monolithic opt1 chain) adds the chunked-exchange rendering
        # (opt1sN) to the selection race, so the gate can report whether
        # splitting the collective beats the monolithic realigned
        # exchange.
        sc = getattr(args, "streams_chunks", None)
        sv = (sc,) if sc and sc > 1 else ()
        try:
            r = mb.transpose_fraction_chain(plan, spec,
                                            repeats=max(it or 1, 3),
                                            warmup=max(wu, 1),
                                            streams_variants=sv)
        except ValueError as e:  # shape/divisibility precondition
            print(f"fraction gate unavailable for this shape: {e}",
                  file=sys.stderr)
            return 2
        if r.get("degenerate"):
            print(f"fraction chain degenerate ({r['dropped']} repeats "
                  "noise-swamped; raise -i or use a bigger size)",
                  file=sys.stderr)
            return 1
        lo, hi = r["fraction_spread"]
        rlo, rhi = r.get("fraction_range", (lo, hi))
        print(f"All2All fraction: {r['fraction']:.3f} "
              f"[{r.get('variant', 'opt0')}, IQR {lo:.3f}-{hi:.3f}, "
              f"range {rlo:.3f}-{rhi:.3f}, "
              f"pipeline {r['pipe_gb_per_s']:.3f} GB/s vs ceiling "
              f"{r['raw_gb_per_s']:.3f} GB/s, k={r['k']}, "
              f"{p} devices]")
        print_stage_profile(plan, args)
        return 0
    print(f"unknown testcase {args.testcase}", file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
