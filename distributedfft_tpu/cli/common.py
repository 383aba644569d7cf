"""Shared CLI plumbing for the three executables.

The reference ships hand-rolled parsers (``getValueOfParam``/``checkFlag``,
``tests/src/slab/main.cpp:76-118``) with both long and short option names;
here argparse carries the same flag surface (argparse accepts multi-char
short options like ``-nx`` verbatim).

Device selection: by default JAX's default backend is used (the TPU on a
chip host). Set ``--emulate-devices N`` (or env ``DFFT_EMULATE_DEVICES``) to force N virtual
CPU devices — the testing story the reference lacks (it can only test
multi-rank on real clusters, SURVEY §4).
"""

from __future__ import annotations

import argparse
import os

from ..ops.fft import BACKENDS


def add_common_args(ap: argparse.ArgumentParser, pencil: bool = False,
                    comm_tunable: bool = False) -> None:
    ap.add_argument("--input-dim-x", "-nx", type=int, required=True,
                    help="size of the input data in x-direction")
    ap.add_argument("--input-dim-y", "-ny", type=int, required=True,
                    help="size of the input data in y-direction")
    ap.add_argument("--input-dim-z", "-nz", type=int, required=True,
                    help="size of the input data in z-direction")
    ap.add_argument("--testcase", "-t", type=int, default=0,
                    help="which testcase to execute (0-4)")
    ap.add_argument("--opt", "-o", type=int, default=0, choices=(0, 1),
                    help="0: default layout; 1: realigned (coordinate "
                         "transform) layout")
    ap.add_argument("--iterations", "-i", type=int, default=1)
    ap.add_argument("--warmup-rounds", "-w", type=int, default=0)
    ap.add_argument("--cuda_aware", "-c", action="store_true", default=True,
                    help="accepted for reference CLI compatibility; "
                         "device-resident collectives are always on for TPU "
                         "(default true, matching Config.cuda_aware, so CLI "
                         "and library runs share one CSV namespace)")
    ap.add_argument("--host-staged", dest="cuda_aware", action="store_false",
                    help="label this run as host-staged (cuda=0 in CSV names)")
    ap.add_argument("--double_prec", "-d", action="store_true",
                    help="use float64/complex128 (CPU backend only; TPU has "
                         "no native f64)")
    ap.add_argument("--benchmark_dir", "-b", default="benchmarks",
                    help="prefix for the benchmark directory")
    ap.add_argument("--fft-backend", default=None,
                    choices=BACKENDS + ("auto",),
                    help="local transform implementation (default: the "
                         "platform's: MXU matmuls at HIGHEST on a TPU for "
                         "float32 and smooth extents, else XLA): XLA's FFT "
                         "expansion, MXU four-step DFT matmuls "
                         "(ops/mxu_fft.py), Pallas fused DFT+twiddle "
                         "kernels (ops/pallas_fft.py), or 'auto' — pick by "
                         "measurement via the wisdom store (race once, "
                         "reuse on every later run; see --wisdom)")
    ap.add_argument("--wisdom", default=None, metavar="PATH",
                    help="persistent plan-wisdom store (JSON; default "
                         "$DFFT_WISDOM, unset = no store): 'auto' choices "
                         "and --autotune[-comm] winners are recorded there "
                         "and reused silently on later runs — the FFTW-"
                         "wisdom analog of the reference's plan-time tuning")
    ap.add_argument("--no-wisdom", action="store_true",
                    help="never consult or write the wisdom store ('auto' "
                         "then re-races each run; with concrete backends "
                         "this is byte-identical to not having wisdom)")
    ap.add_argument("--emulate-devices", type=int,
                    default=int(os.environ.get("DFFT_EMULATE_DEVICES", "0")),
                    help="force N virtual CPU devices (0 = use real backend)")
    ap.add_argument("--profile-dir", default=None,
                    help="write a jax.profiler trace of the testcase run to "
                         "this directory (view with TensorBoard / Perfetto) — "
                         "the deep-dive complement to the per-phase Timer "
                         "CSVs, SURVEY §5 tracing; obs span names appear on "
                         "the trace as dfft:* annotations")
    ap.add_argument("--profile-stages", action="store_true",
                    help="after the run, measure a short stage-attributed "
                         "device profile: a few forward iterations under "
                         "jax.profiler.trace with device time joined back "
                         "onto the declared plan-graph nodes "
                         "(obs/profile.py) — per-stage ms, exchange-vs-"
                         "compute split, per-stage roofline gap")
    ap.add_argument("--obs", action="store_true",
                    help="observability console: print wisdom-provenance "
                         "one-liners (hit|miss|migrated) as they happen and "
                         "the obs metrics snapshot after the run (the "
                         "structured event log is separate: $DFFT_OBS_DIR / "
                         "--obs-dir)")
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="write the structured JSONL event log (spans + "
                         "events; see README 'Observability') under this "
                         "directory — same effect as $DFFT_OBS_DIR, default "
                         "off")
    ap.add_argument("--multihost", action="store_true",
                    help="join the multi-controller runtime (one process per "
                         "host; rendezvous via DFFT_COORDINATOR / "
                         "DFFT_NUM_PROCESSES / DFFT_PROCESS_ID or TPU-pod "
                         "autodetection; see parallel/multihost.py and "
                         "jobs/tpu/scripts/). Perf testcases only (0, 2)")
    if comm_tunable:
        # Only the decomposition executables run plans; the reference
        # executable's probes have no comm matrix to tune.
        ap.add_argument("--autotune-comm", action="store_true",
                        help="race the comm-strategy matrix (All2All vs "
                             "Peer2Peer per transpose, x opt 0/1) for this "
                             "size on the active mesh before running, and "
                             "use the measured winner — the TPU rendering "
                             "of the reference's primary comparative "
                             "dimension (transpose is >=97%% of runtime at "
                             "scale)")
    if pencil:
        ap.add_argument("--comm-method1", "-comm1", default="Peer2Peer",
                        help='"Peer2Peer" (XLA-scheduled redistribution), '
                             '"All2All" (explicit collective) or "auto" '
                             '(measured via the wisdom store; owns the whole '
                             'comm x send x opt x chunks choice), transpose 1')
        ap.add_argument("--send-method1", "-snd1", default="Sync",
                        help="Sync (monolithic exchange) | Streams (chunked/"
                             "pipelined transpose, see --streams-chunks) | "
                             "Ring (ppermute-ring exchange with per-block "
                             "FFTs pipelined between steps; owns the "
                             "rendering regardless of comm method) | "
                             "RingOverlap (the ring on the double-"
                             "buffered schedule — bit-identical output, "
                             "one transfer in flight under every "
                             "block's compute) | "
                             "MPI_Type (alias of Sync)")
        ap.add_argument("--comm-method2", "-comm2", default=None,
                        help="same as --comm-method1 for transpose 2")
        ap.add_argument("--send-method2", "-snd2", default=None)
    else:
        ap.add_argument("--comm-method", "-comm", default="Peer2Peer",
                        help='"Peer2Peer", "All2All" or "auto" (measured '
                             "via the wisdom store; owns the whole comm x "
                             "send x opt x chunks choice)")
        ap.add_argument("--send-method", "-snd", default="Sync",
                        help="Sync (monolithic exchange) | Streams (chunked/"
                             "pipelined transpose, see --streams-chunks) | "
                             "Ring (ppermute-ring exchange with per-block "
                             "FFTs pipelined between steps; owns the "
                             "rendering regardless of comm method) | "
                             "RingOverlap (the ring on the double-"
                             "buffered schedule — bit-identical output, "
                             "one transfer in flight under every "
                             "block's compute) | "
                             "MPI_Type (alias of Sync)")
    ap.add_argument("--streams-chunks", type=int, default=None,
                    help="piece count for the Streams pipelined transpose "
                         "(default 4; ignored unless a send method is "
                         "Streams)")
    ap.add_argument("--overlap-depth", default="auto",
                    help="revolving receive-buffer depth of the overlapped "
                         "exchange schedules (RingOverlap and the pipelined "
                         "all-to-all): up to depth-1 transfers are issued "
                         "ahead of the compute consuming them (capped at "
                         "ranks-1 ring steps). 2 | 4 | 8 | 'auto' (default: "
                         "the comm race / wisdom picks when the comm choice "
                         "is 'auto', else the shipped double-buffered "
                         "depth 2)")
    ap.add_argument("--overlap-subblocks", type=int, default=None,
                    help="split every exchanged peer block into this many "
                         "sub-blocks so the first sub-block's compute "
                         "starts before the whole block arrives (default "
                         "1 = whole blocks). With a Sync/MPI_Type send on "
                         "All2All, >1 selects the software-pipelined "
                         "all-to-all rendering (a2a_pipe) instead of the "
                         "monolithic collective")
    ap.add_argument("--wire-dtype", "-wire",
                    default=os.environ.get("DFFT_WIRE", "native"),
                    choices=("native", "bf16", "auto"),
                    help="wire encoding of the global exchanges (default "
                         "$DFFT_WIRE or 'native'): 'native' = bit-identical "
                         "payload; 'bf16' = OPT-IN LOSSY planar (real, imag) "
                         "bf16 pair encoded immediately before each "
                         "collective and decoded after — half the wire "
                         "bytes of a complex64 exchange (~2e-3 max rel "
                         "error per crossing, README 'wire dtype'); 'auto' "
                         "= race compressed vs native on this shape under "
                         "--wire-error-budget and reuse the recorded "
                         "winner via the wisdom store")
    ap.add_argument("--wire-error-budget", type=float, default=None,
                    help="max rel error (vs the native path, measured on "
                         "the actual shape) the 'auto' wire race accepts "
                         "from a compressed wire (default 2e-2); tighter "
                         "budgets fall back to native")
    ap.add_argument("--guards", default=None,
                    choices=("off", "check", "enforce"),
                    help="in-graph numerical guards (resilience layer; "
                         "default $DFFT_GUARDS or 'off'): 'check' adds a "
                         "Parseval/energy-conservation residual and (on a "
                         "compressed wire) a drift probe to every jitted "
                         "pipeline — violations are counted/noticed and a "
                         "drifting wire demotes itself to native; "
                         "'enforce' raises a structured GuardViolation "
                         "instead (README 'Resilience')")
    ap.add_argument("--selftest", action="store_true",
                    help="run one guarded forward+inverse roundtrip of "
                         "this exact plan (Parseval + roundtrip identity "
                         "+ host np.fft reference at small sizes) and "
                         "print a PASS/FAIL line before the timed loop; "
                         "FAIL aborts with exit code 1")
    ap.add_argument("--tc1-truth", choices=("host", "analytic"),
                    default="host",
                    help="testcase-1 ground truth: 'host' = dense random "
                         "input vs full np.fft on the host (reference "
                         "parity, host-memory-bound); 'analytic' = sine "
                         "field vs its closed-form spectrum, both built "
                         "on device — validates at sizes the host truth "
                         "cannot reach")


def wisdom_config_kwargs(args) -> dict:
    """Config kwargs carrying the CLI wisdom surface (--wisdom/--no-wisdom,
    shared by all four executables). Defaults reproduce pre-wisdom behavior
    exactly: no flag + no $DFFT_WISDOM = no store is ever touched."""
    return {"wisdom_path": getattr(args, "wisdom", None),
            "use_wisdom": not getattr(args, "no_wisdom", False)}


def wire_config_kwargs(args) -> dict:
    """Config kwargs carrying the CLI wire surface (-wire /
    --wire-error-budget; shared by the decomposition executables).
    Defaults reproduce pre-wire behavior exactly: no flag + no $DFFT_WIRE
    = the bit-identical native wire."""
    from .. import params as pm
    return {"wire_dtype": pm.parse_wire_dtype(
                getattr(args, "wire_dtype", "native")),
            "wire_error_budget": getattr(args, "wire_error_budget", None)}


def overlap_config_kwargs(args) -> dict:
    """Config kwargs carrying the CLI overlap surface (--overlap-depth /
    --overlap-subblocks; shared by all four executables). Defaults
    reproduce the shipped schedules exactly: depth 'auto' resolves to the
    double-buffered depth 2 outside a race, and no sub-block split keeps
    whole-block exchanges."""
    from .. import params as pm
    return {"overlap_depth": pm.parse_overlap_depth(
                getattr(args, "overlap_depth", "auto")),
            "overlap_subblocks": getattr(args, "overlap_subblocks", None)}


def resilience_config_kwargs(args) -> dict:
    """Config kwargs carrying the CLI resilience surface (--guards).
    Default None defers to $DFFT_GUARDS -> "off", reproducing pre-guard
    behavior (byte-identical programs) exactly."""
    return {"guards": getattr(args, "guards", None)}


def maybe_selftest(plan, args, dims=None) -> bool:
    """--selftest: one guarded roundtrip of the exact plan before the
    timed loop (resilience/selftest.py); returns False — abort with exit
    code 1 — on FAIL."""
    if not getattr(args, "selftest", False):
        return True
    from ..resilience.selftest import run_selftest
    return bool(run_selftest(plan, dims=dims)["ok"])


def maybe_autotune_comm(args, kind, global_size, partition, cfg,
                        sequence=None, dims=3, variant=None,
                        transform="r2c"):
    """--autotune-comm: race the comm matrix for this shape on the active
    mesh, print the measured table, and return the winning Config (the
    original one when the flag is off). ``dims`` is the pencil partial
    depth and ``transform`` the r2c/c2c choice, so the race times the
    program the run will actually execute. The winner is also recorded
    into the wisdom store when one is configured, so later runs can reuse
    it via ``comm-method auto``."""
    if not getattr(args, "autotune_comm", False):
        return cfg
    if dims < 2:
        print("autotune-comm: dims=1 performs no transpose; nothing to tune")
        return cfg
    from ..testing import autotune as at

    print(f"autotuning comm strategies for {global_size.shape} "
          f"({kind}, {partition.num_ranks} ranks, dims={dims}):")
    base = cfg  # the config the send=None candidates were actually timed on
    from .. import params as pm
    ranked = at.autotune_comm(kind, global_size, partition, base,
                              sequence=sequence, dims=dims,
                              transform=transform,
                              iterations=max(args.iterations, 3),
                              warmup=max(args.warmup_rounds, 1),
                              race_send=True,
                              # -wire auto hands the wire axis to this race
                              # (bf16 twins, error-budget-gated); an
                              # explicit -wire is respected, not re-raced.
                              race_wire=cfg.wire_dtype == pm.AUTO,
                              verbose=True)
    best = ranked[0]
    cfg = at.apply_best_comm(ranked, base)
    runner = ranked[1] if len(ranked) > 1 and ranked[1].ok else None
    delta = (f", {runner.total_ms - best.total_ms:+.3f} ms vs next "
             f"({runner.label})" if runner else "")
    print(f"best: {best.label} ({best.total_ms:.3f} ms roundtrip{delta})")
    from ..utils import wisdom
    store = wisdom.store_for_config(cfg)
    if store is not None and best.ok:
        key = wisdom.plan_key(kind, global_size.shape, cfg.double_prec,
                              partition, cfg.norm, sequence=sequence,
                              variant=variant, transform=transform,
                              dims=dims)
        if store.record(key, "comm", wisdom.comm_record(best, base)):
            print(f"wisdom: comm winner recorded -> {store.path}")
    return cfg


def maybe_profile(args):
    """Context manager: a ``jax.profiler.trace`` over the block when
    ``--profile-dir`` was given, a no-op otherwise (shared by all CLIs)."""
    import contextlib

    profile_dir = getattr(args, "profile_dir", None)
    if not profile_dir:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.trace(profile_dir)


def run_testcase(plan, args, dims=None) -> int:
    """Dispatch -t N to the testcase implementations and print the perf
    summary; shared by the slab and pencil executables. ``dims`` is the
    pencil-only --fft-dim depth (None for slab)."""
    import sys

    from ..testing import testcases as tc

    fn = {0: tc.testcase0, 1: tc.testcase1, 2: tc.testcase2,
          3: tc.testcase3, 4: tc.testcase4}.get(args.testcase)
    if fn is None:
        print(f"unknown testcase {args.testcase}", file=sys.stderr)
        return 2
    import jax
    tc1_analytic = (args.testcase == 1
                    and getattr(args, "tc1_truth", "host") == "analytic")
    if (jax.process_count() > 1 and args.testcase not in (0, 2)
            and not tc1_analytic):
        # Validation testcases compare against a host-side reference array,
        # which no single controller holds in a multi-host run. Like the
        # reference, validate at single-host scale (jobs/**/validation.json
        # run small sizes) and benchmark at pod scale. Exception: tc1 with
        # --tc1-truth analytic is fully device-resident (sine field vs
        # closed-form spectrum), so it validates at pod scale too —
        # something the reference's coordinator-rank scheme cannot do.
        print("testcases 1/3/4 validate against a host-side reference and "
              "need a single-controller run (use --emulate-devices or one "
              "host); multi-host supports perf testcases 0 and 2, plus "
              "testcase 1 with --tc1-truth analytic",
              file=sys.stderr)
        return 2
    if not maybe_selftest(plan, args, dims=dims):
        print("selftest FAILED; aborting before the timed loop",
              file=sys.stderr)
        return 1
    kwargs = {}
    if args.testcase in (0, 2, 3, 4):
        kwargs.update(iterations=args.iterations, warmup=args.warmup_rounds)
    if args.testcase == 1:
        kwargs["truth"] = getattr(args, "tc1_truth", "host")
    if dims is not None and args.testcase != 4:
        kwargs["dims"] = dims
    with maybe_profile(args):
        result = fn(plan, **kwargs)
    if "mean_ms" in result:
        print(f"Run complete: {result['mean_ms']:.4f} ms "
              f"(mean over {args.iterations} iterations)")
    print_obs_snapshot(args)
    print_stage_profile(plan, args, dims=dims)
    return 0


def setup_obs(args) -> None:
    """Apply the CLI observability surface (--obs / --obs-dir) before any
    plan is constructed, so provenance notices and build spans from the
    very first resolution are captured."""
    from .. import obs
    if getattr(args, "obs_dir", None):
        obs.enable(args.obs_dir)
    if getattr(args, "obs", False):
        obs.enable_console()


def print_stage_profile(plan, args, dims=None) -> None:
    """The ``--profile-stages`` epilogue (shared by all four CLIs): a
    short measured window of the forward plan under ``jax.profiler``,
    printed as device time per declared plan-graph node
    (``obs/profile.py``). The user asked for it, so a profile failure
    propagates and the CLI exits non-zero."""
    if not getattr(args, "profile_stages", False):
        return
    from ..obs import profile as prof_mod
    print("stage profile (measured device time per declared plan-graph "
          "node):")
    prof = prof_mod.stage_profile(plan, "forward",
                                  3 if dims is None else dims)
    print("\n".join(prof_mod.format_stage_profile(prof)))


def print_obs_snapshot(args) -> None:
    """The --obs epilogue: one compact JSON line of the metrics registry."""
    if not getattr(args, "obs", False):
        return
    import json as _json

    from .. import obs
    print("obs metrics: "
          + _json.dumps(obs.metrics.snapshot(), sort_keys=True))


def setup_backend(args) -> None:
    """Apply device emulation / multi-host rendezvous and place the compile
    cache before any jax backend use. Must be called before the first jax
    device query."""
    setup_obs(args)
    import jax

    from ..parallel.mesh import use_compile_cache
    use_compile_cache()
    if args.emulate_devices:
        if getattr(args, "multihost", False):
            raise SystemExit("--multihost and --emulate-devices are mutually "
                             "exclusive (emulation is single-process)")
        from ..parallel.mesh import force_cpu_devices
        force_cpu_devices(args.emulate_devices)
    if getattr(args, "double_prec", False):
        jax.config.update("jax_enable_x64", True)
    if getattr(args, "multihost", False):
        from ..parallel.multihost import maybe_initialize
        maybe_initialize(require=True)
