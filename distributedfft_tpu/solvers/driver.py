"""``dfft-solve`` — a pseudo-spectral solver run as a standalone,
SIGTERM-drainable, crash-resumable process (ROADMAP item 5c).

The executable half of the durability contract: run a Navier–Stokes
simulation with crash-consistent checkpointing
(``distributedfft_tpu/persist``), drain a final generation on
SIGTERM/SIGINT, and ``--resume`` a later invocation from the newest
valid generation — continuing **bit-exactly**. The CI ``resume`` chaos
scenario is exactly this binary: SIGTERM a run at step k, ``--resume``
to step n, ``cmp`` the ``--out`` field byte-for-byte against an
uninterrupted n-step run (on batched2d AND slab plans on the 8-device
CPU mesh).

The stepping engine is the serve layer's :class:`ResidentSolver`
(``serve/resident.py``) — one jitted step function applied stepwise,
never a ``lax.scan`` whose length would differ across a resume — so
``dfft-solve`` and a ``dfft-serve`` resident share one durability path
and one bit-exactness argument.

Examples::

    dfft-solve --kind ns2d --n 64 --steps 200 --emulate-devices 8 -p 8 \
        --shard x --checkpoint-dir /tmp/ck --checkpoint-policy steps:10
    dfft-solve --kind ns3d --n 32 --steps 100 --emulate-devices 8 -p 8 \
        --checkpoint-dir /tmp/ck3 --resume --out final.npy
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="dfft-solve", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--kind", default="ns2d", choices=("ns2d", "ns3d"),
                    help="ns2d: vorticity NS on a batched-2D plan; "
                         "ns3d: rotational NS on a slab plan")
    ap.add_argument("--n", type=int, default=32,
                    help="grid extent per transformed axis")
    ap.add_argument("--batch", type=int, default=1,
                    help="ns2d ensemble size (independent flows)")
    ap.add_argument("--partitions", "-p", type=int, default=1,
                    help="mesh width the plan decomposes over")
    ap.add_argument("--shard", default="batch", choices=("batch", "x"),
                    help="ns2d decomposition: 'x' exercises a real "
                         "exchange (the resume drill uses it)")
    ap.add_argument("--steps", type=int, default=50,
                    help="target step count (resume continues toward "
                         "the SAME target)")
    ap.add_argument("--dt", type=float, default=1e-3)
    ap.add_argument("--viscosity", type=float, default=1e-2)
    ap.add_argument("--double", "-d", action="store_true",
                    help="f64 state (enables jax x64)")
    ap.add_argument("--fft-backend", default=None,
                    help="local FFT backend (default: the platform's)")
    ap.add_argument("--step-interval-ms", type=float, default=0.0,
                    help="pause between steps (chaos drills use this to "
                         "widen the SIGTERM window)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="two-generation checkpoint store (same as "
                         "$DFFT_CKPT_DIR; unset = no durability)")
    ap.add_argument("--checkpoint-policy", default=None,
                    metavar="steps:N[,secs:T][,drain:on|off]",
                    help="checkpoint cadence (same as $DFFT_CKPT_POLICY; "
                         "default drain-only)")
    ap.add_argument("--resume", action="store_true",
                    help="REQUIRE a restorable checkpoint and continue "
                         "from it (refuses to start fresh)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the final PHYSICAL field as .npy (the "
                         "bit-exact comparison artifact)")
    ap.add_argument("--seed", type=int, default=0,
                    help="recorded as the RNG/forcing phase provenance")
    ap.add_argument("--emulate-devices", type=int,
                    default=int(os.environ.get("DFFT_EMULATE_DEVICES",
                                               "0")))
    ap.add_argument("--obs", action="store_true")
    ap.add_argument("--obs-dir", default=None, metavar="DIR")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    from .. import obs
    if args.obs_dir:
        obs.enable(args.obs_dir)
    if args.obs:
        obs.enable_console()
    if args.emulate_devices:
        from ..parallel.mesh import force_cpu_devices
        force_cpu_devices(args.emulate_devices)
    import jax
    if args.double:
        jax.config.update("jax_enable_x64", True)

    import numpy as np

    from .. import persist
    from ..serve.resident import ResidentSolver

    try:
        ckdir, policy = persist.resolve_env(args.checkpoint_dir,
                                            args.checkpoint_policy)
    except ValueError as e:
        raise SystemExit(f"--checkpoint-policy: {e}") from None
    if args.resume and not ckdir:
        raise SystemExit("--resume needs --checkpoint-dir (or "
                         f"${persist.ENV_DIR})")
    spec = {"kind": args.kind, "n": args.n, "batch": args.batch,
            "partitions": args.partitions, "shard": args.shard,
            "double": args.double, "fft_backend": args.fft_backend,
            "viscosity": args.viscosity, "dt": args.dt,
            "dir": ckdir,
            "policy": policy, "rng": {"seed": args.seed, "draws": 0},
            "step_interval_ms": args.step_interval_ms,
            "max_steps": args.steps, "name": "dfft-solve"}
    try:
        res = ResidentSolver.build(spec)
    except persist.CheckpointMismatch as e:
        # The documented operator error (this dir belongs to a
        # differently-configured run): a usage message, not a traceback.
        raise SystemExit(f"dfft-solve: checkpoint in {ckdir} was written "
                         f"by a different configuration — {e}") from None
    if args.resume and res.restored_from is None:
        raise SystemExit(f"--resume: no restorable checkpoint in {ckdir}")

    stop = threading.Event()

    def _graceful(signum, frame):  # noqa: ARG001 — signal contract
        print(f"dfft-solve: signal {signum} -> drain checkpoint + exit",
              flush=True)
        stop.set()

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)

    interrupted = False
    res.start()
    while res.step < args.steps:
        if stop.wait(0.02):
            interrupted = True
            break
        if not res.running:  # cheap liveness — no store I/O in the poll
            break
    # stop() drains through the policy's on-drain checkpoint — the
    # SIGTERM contract: durable state lands BEFORE the process exits 0.
    res.stop(checkpoint=True)

    out_path = None
    if args.out and not interrupted:
        phys = np.asarray(res.solver.to_physical(res.state))
        np.save(args.out, phys, allow_pickle=False)
        out_path = args.out
    summary = {"kind": args.kind, "n": args.n, "steps_target": args.steps,
               "step": res.step, "restored_from": res.restored_from,
               "checkpoints": res.checkpoints,
               "interrupted": interrupted, "error": res.error,
               "sim_time": round(res.sim_time, 9), "out": out_path}
    print(json.dumps(summary, sort_keys=True), flush=True)
    if args.obs:
        print("obs metrics: "
              + json.dumps(obs.metrics.snapshot(), sort_keys=True))
    # A stepping-thread failure is a loud failure: the run did NOT reach
    # its target and no later checkpoint will land.
    return 0 if res.error is None else 1


if __name__ == "__main__":
    sys.exit(main())
