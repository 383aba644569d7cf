"""``dfft-explain`` — resolved-plan diagnostics WITHOUT executing the FFT.

After wisdom ("auto" resolution), the ring rendering and the wire layer, a
plan's actual shape — which collective program it builds, how many bytes
cross the wire, where its config values came from — is decided at
construction and was previously visible only by reading code or timing
runs. This executable answers "why did the plan do X" for a given
config + shape:

* decomposition: kind, partition/mesh, padded shapes, partition specs;
* the per-axis FFT sequence each pipeline stage runs;
* the resolved exchange rendering (default / realigned opt1 / ring /
  streams / GSPMD) per transpose;
* wire dtype and predicted wire bytes per exchange (``wire_nbytes`` over
  the exact padded payload the plan exchanges);
* wisdom provenance: store path, on-disk schema version, hit/miss per
  consulted slot, the recorded winners and when they were recorded
  (lookup-only — a miss is REPORTED, never raced, so explain runs no
  measurement);
* HLO collective census: the forward program is lowered and compiled
  (never executed) and ``microbench.async_collective_counts`` reports the
  collective / async-start / convert instance counts;
* roofline: the least work of one roundtrip per chip — flops, HBM and
  ICI bytes, counted from the declared plan graph by
  ``obs/profile.least_work``, the benchmark's model — and its least
  time on this device (``profile.PEAKS``), with the Bluestein factor of
  any non-smooth axis;
* overlap schedule for ring-rendered exchanges (Ring / RingOverlap):
  blocks, revolving buffers, and the wire bytes in flight per device;
* checkpoint registry (``--checkpoint-dir`` / ``$DFFT_CKPT_DIR``): the
  persist store's generations (step, age, validity), the
  plan-fingerprint match verdict for THIS plan — from the same
  ``CheckpointStore.describe`` the restore path uses, so explain cannot
  disagree with restore — and the next scheduled write under the
  resolved ``CheckpointPolicy``.

Examples::

    dfft-explain --kind slab   -nx 256 -ny 256 -nz 256 -p 8 --emulate-devices 8
    dfft-explain --kind pencil -nx 64 -ny 64 -nz 64 -p1 2 -p2 4 \
        -snd1 Ring --emulate-devices 8
    dfft-explain --kind batched -nx 4096 -ny 4096 -nz 64 --shard x -p 8 \
        -wire bf16 --emulate-devices 8
"""

from __future__ import annotations

import argparse
import sys

# Shared with the graph section so explain and dfft-verify format bytes
# identically (the analysis chain is jax-free at import).
from ..analysis.plangraph import _fmt_bytes


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="dfft-explain", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--kind", choices=("slab", "pencil", "batched"),
                    default="slab", help="plan family to explain")
    ap.add_argument("--input-dim-x", "-nx", type=int, required=True)
    ap.add_argument("--input-dim-y", "-ny", type=int, required=True)
    ap.add_argument("--input-dim-z", "-nz", type=int, required=True,
                    help="(batched: the batch count, like dfft-batched)")
    ap.add_argument("--partitions", "-p", type=int, default=0,
                    help="slab/batched mesh width (default: all devices)")
    ap.add_argument("--partition1", "-p1", type=int, default=0,
                    help="pencil grid rows")
    ap.add_argument("--partition2", "-p2", type=int, default=0,
                    help="pencil grid cols")
    ap.add_argument("--sequence", "-s", default="ZY_Then_X",
                    help="slab sequence")
    ap.add_argument("--shard", default="batch", choices=("batch", "x"),
                    help="batched2d decomposed axis")
    ap.add_argument("--batch-chunk", type=int, default=0,
                    help="batched2d batch_chunk (shard=batch only; 0 = "
                         "whole stack fused)")
    ap.add_argument("--fft-dim", "-f", type=int, default=3,
                    choices=(1, 2, 3), help="pencil partial-transform depth")
    ap.add_argument("--comm-method", "-comm", "-comm1", dest="comm_method",
                    default="All2All")
    ap.add_argument("--comm-method2", "-comm2", default=None)
    ap.add_argument("--send-method", "-snd", "-snd1", dest="send_method",
                    default="Sync")
    ap.add_argument("--send-method2", "-snd2", default=None)
    ap.add_argument("--opt", "-o", type=int, default=0, choices=(0, 1))
    ap.add_argument("--streams-chunks", type=int, default=None)
    ap.add_argument("--overlap-depth", default="auto",
                    help="revolving-buffer depth for RingOverlap (2|4|8 or "
                         "'auto'; capped at ranks-1 micro-steps — the "
                         "schedule block reports the effective depth)")
    ap.add_argument("--overlap-subblocks", type=int, default=None,
                    help="split each peer block into this many sub-blocks "
                         "(rings) / pipeline the all-to-all in this many "
                         "chunks (All2All + Sync/MpiType)")
    ap.add_argument("--wire-dtype", "-wire", default="native",
                    choices=("native", "bf16", "auto"))
    ap.add_argument("--wire-error-budget", type=float, default=None)
    ap.add_argument("--fused-wire", action="store_true",
                    help="explain the fused Pallas wire-kernel rendering "
                         "(active on Ring/RingOverlap + bf16 wire only)")
    ap.add_argument("--guards", default=None,
                    choices=("off", "check", "enforce"),
                    help="explain the plan's resilience posture under this "
                         "guard mode (default: $DFFT_GUARDS -> off)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="explain the persist/ checkpoint store here "
                         "(default $DFFT_CKPT_DIR): generations, age, "
                         "step, fingerprint-match verdict vs THIS plan")
    ap.add_argument("--checkpoint-policy", default=None,
                    metavar="steps:N[,secs:T][,drain:on|off]",
                    help="resolve the checkpoint cadence shown in the "
                         "checkpoint: section (default $DFFT_CKPT_POLICY)")
    ap.add_argument("--fft-backend", default=None,
                    help="local FFT backend (default: the platform's, "
                         "utils/wisdom.platform_default)")
    ap.add_argument("--double_prec", "-d", action="store_true")
    ap.add_argument("--c2c", action="store_true",
                    help="explain the C2C transform instead of R2C")
    ap.add_argument("--wisdom", default=None, metavar="PATH")
    ap.add_argument("--no-wisdom", action="store_true")
    ap.add_argument("--emulate-devices", type=int, default=0,
                    help="force N virtual CPU devices (0 = real backend)")
    ap.add_argument("--no-compile", action="store_true",
                    help="skip the HLO collective census (no XLA compile; "
                         "everything else is pure bookkeeping)")
    ap.add_argument("--obs", action="store_true",
                    help="print the obs metrics snapshot after the report")
    ap.add_argument("--obs-dir", default=None,
                    help="write the obs event log here (same as "
                         "$DFFT_OBS_DIR)")
    ap.add_argument("--profile", action="store_true",
                    help="measure a stage-attributed device profile: run "
                         "the forward plan under jax.profiler.trace and "
                         "join device time back onto the declared plan "
                         "graph (obs/profile.py) — the ONE explain mode "
                         "that executes the FFT")
    ap.add_argument("--profile-iters", type=int, default=3,
                    help="profiled iterations for --profile (default 3; "
                         "one warmup run precedes the captured window)")
    return ap




def _rendering(comm, send, opt, p: int, fused_wire: bool = False,
               depth: int = 2, subblocks: int = 1) -> str:
    """One-line resolved rendering of a single transpose. ``depth`` /
    ``subblocks`` are the resolved overlap knobs — they pick the
    revolving-buffer ring wording (with the effective P-1 cap spelled
    out) and the pipelined all-to-all rendering."""
    from .. import params as pm
    sub = (f", each peer block split into {subblocks} sub-blocks"
           if subblocks > 1 else "")
    if send is pm.SendMethod.RING_OVERLAP:
        steps = f"{p - 1} distinct lax.ppermute step" \
            + ("s" if p > 2 else "")
        fused = (", fused Pallas wire kernels (encode-pack / decode+FFT)"
                 if fused_wire else "")
        micro = max(0, p - 1) * max(1, subblocks)
        buffers = min(depth, micro) if micro else 0
        if depth == 2 and subblocks == 1:
            return (f"ring-overlap — {steps} on the DOUBLE-BUFFERED "
                    "schedule (step t+1's permute issued before block t's "
                    f"FFT; bit-identical to Ring, reordered issue{fused})")
        cap = (f" — depth {depth} capped at {buffers} by the "
               f"{micro}-micro-step schedule" if buffers < depth else "")
        return (f"ring-overlap — {steps} on the depth-{depth} "
                f"REVOLVING-BUFFER schedule ({buffers} receive buffer"
                f"{'s' if buffers != 1 else ''} in flight{cap}{sub}; "
                f"bit-identical to Ring, reordered issue{fused})")
    if send is pm.SendMethod.RING:
        steps = f"{p - 1} distinct lax.ppermute step" \
            + ("s" if p > 2 else "")
        return (f"ring — {steps} (owns the rendering regardless of comm; "
                f"per-block FFTs pipelined where axis roles allow{sub})")
    layout = "realigned (opt1 pack, pure exchange)" if opt == 1 \
        else "default layout"
    if comm is pm.CommMethod.ALL2ALL:
        base = f"explicit shard_map lax.all_to_all, {layout}"
        if send is pm.SendMethod.STREAMS:
            return base + " — STREAMS: chunked into independent piece chains"
        if subblocks > 1:
            return (f"pipelined all-to-all — {subblocks} chunked "
                    f"collectives, chunk k+1 issued while chunk k decodes "
                    f"(revolving depth {depth}), {layout}; bit-identical "
                    "to the monolithic exchange")
        return base
    base = f"GSPMD (Peer2Peer) stage-boundary reshard, {layout}"
    if send is pm.SendMethod.STREAMS:
        return base + (" — STREAMS piece reshards (GSPMD re-fuses them "
                       "into ONE collective; honest no-op, see OVERLAP.md)")
    return base


def _wire_lines(shapes, cdt, cfg) -> list:
    """Wire block: per-exchange payload shape + wire bytes."""
    import numpy as np

    from ..parallel.transpose import wire_itemsize, wire_nbytes
    wire = cfg.wire_dtype
    lines = [f"  dtype: {wire}  "
             f"({wire_itemsize(cdt, wire)} B/elem on the wire vs "
             f"{np.dtype(cdt).itemsize} B logical)"]
    for label, shape in shapes:
        wb = wire_nbytes(shape, cdt, wire)
        lb = wire_nbytes(shape, cdt, "native")
        extra = "" if wire == "native" else \
            f" (native would be {_fmt_bytes(lb)})"
        lines.append(f"  {label}: payload {tuple(shape)} -> "
                     f"wire_nbytes {_fmt_bytes(wb)}{extra}")
    if wire == "bf16":
        lines.append(f"  lossy: ~2e-3 max rel err per crossing; budget "
                     f"{cfg.resolved_wire_budget():.0e} "
                     "(README 'wire dtype')")
    return lines


def _schedule_lines(xmeta, cdt, cfg) -> list:
    """Overlap-schedule block for ring-rendered exchanges (ISSUE 10/16):
    blocks (= ring steps), sub-block split, EFFECTIVE revolving buffers
    (the requested depth under the micro-step cap — depth 8 on 8 ranks
    holds 7 and this block says so), and the per-device wire bytes in
    flight for the chosen split — ``transpose.ring_schedule`` over the
    exact padded payload each exchange moves. Empty when no exchange is
    a ring."""
    from .. import params as pm
    from ..parallel.transpose import ring_schedule
    depth = cfg.resolved_overlap_depth()
    subblocks = cfg.resolved_overlap_subblocks()
    lines = []
    for label, shape, p, snd in xmeta:
        if not snd.is_ring:
            continue
        overlap = snd is pm.SendMethod.RING_OVERLAP
        sch = ring_schedule(shape, cdt, cfg.wire_dtype, p,
                            overlap=overlap, depth=depth,
                            subblocks=subblocks)
        split = ("" if sch["subblocks"] == 1 else
                 f" split into {sch['subblocks']} sub-blocks of "
                 f"{_fmt_bytes(sch['subblock_wire_bytes'])} "
                 f"({sch['permutes']} permutes),")
        cap = (f" (depth {depth} capped by the schedule)"
               if overlap and sch["effective_depth"] < depth else "")
        lines.append(
            f"  {label}: {sch['steps']} block(s) of "
            f"{_fmt_bytes(sch['block_wire_bytes'])} on the wire,{split} "
            f"{sch['buffers']} revolving buffer(s){cap}, "
            f"{_fmt_bytes(sch['bytes_in_flight'])} in flight per device "
            f"(mesh total {_fmt_bytes(sch['total_wire_bytes'])}, the "
            f"(P-1)/P ring discount)")
    return lines


def _wisdom_lines(prov) -> list:
    lines = []
    if prov["store_path"] is None:
        lines.append("  store: none configured (--wisdom / $DFFT_WISDOM "
                     "unset, or --no-wisdom)")
    else:
        v = prov["store_version"]
        vs = "absent on disk" if v is None else f"on-disk version {v}"
        lines.append(f"  store: {prov['store_path']} ({vs})")
    if not prov["slots"]:
        lines.append("  slots: none consulted (no 'auto' Config fields)")
        return lines
    for slot, info in prov["slots"].items():
        status = info["status"]
        if status == "hit":
            rec = info.get("record") or {}
            when = rec.get("recorded_at", "recorded_at unknown")
            detail = ", ".join(f"{k}={rec[k]}" for k in sorted(rec)
                               if k != "recorded_at")
            lines.append(f"  {slot}: hit ({detail}) [{when}]")
        elif status == "miss":
            lines.append(f"  {slot}: miss ({info.get('reason')}) — a real "
                         "run would race and record; defaults shown below")
        else:
            lines.append(f"  {slot}: {status}")
    return lines


def _resilience_lines(plan, cfg, prov) -> list:
    """Resilience posture: guard mode + derived tolerances, the fallback
    ladder that WOULD apply to this rendering, and any wisdom demotion
    stamps on the resolved cell (all static — nothing executes)."""
    import numpy as np

    from ..resilience import fallback, guards
    from ..utils import wisdom

    mode = plan._guard_mode
    src = ("Config.guards" if cfg.guards is not None
           else ("$DFFT_GUARDS" if mode != "off" else "default"))
    lines = [f"  guards: {mode} ({src})"]
    fwd = plan._guard_spec("forward")
    inv = plan._guard_spec("inverse")
    n = int(np.prod(fwd.in_logical))
    tol = guards.parseval_tolerance(cfg.double_prec, cfg.wire_dtype, n)
    dt = "f64" if cfg.double_prec else "f32"
    lines.append(f"  forward check: parseval, tolerance {tol:.2e} "
                 f"(dtype {dt}, wire {cfg.wire_dtype}, N={n})")
    lines.append(f"  inverse check: {inv.check}"
                 + ("" if inv.check == "parseval" else
                    " (C2R: arbitrary spectral input is not conjugate-"
                    "symmetric, so energy is not an invariant there)"))
    if cfg.wire_dtype != "native":
        lines.append(f"  wire drift probe: budget "
                     f"{cfg.resolved_wire_budget():.0e} "
                     "(one extra encode/decode of the spectral payload)")
    ladder = fallback.ladder_preview(cfg)
    if ladder:
        steps = " -> ".join(f"[{r}] {lbl}" for r, lbl in ladder)
        lines.append(f"  fallback ladder: {steps} -> error propagates")
    else:
        lines.append("  fallback ladder: none (default rendering — "
                     "failures propagate, never retried)")
    store = wisdom.store_for_config(cfg)
    stamps = []
    if store is not None:
        for slot in ("comm", "wire"):
            rec = store.lookup(prov["key"], slot)
            if rec and rec.get("demoted"):
                in_force = wisdom.demotion_active(rec)
                verdict = ("record reads as a miss; next race re-records"
                           if in_force else
                           "EXPIRED ($DFFT_DEMOTION_TTL_S) — record "
                           "re-admitted, stamp kept as history")
                stamps.append(
                    f"  demotion stamp [{slot}]: rung "
                    f"{rec.get('demoted_rung')} at "
                    f"{rec.get('demoted_at', '?')} — "
                    f"{rec.get('demoted_reason', '')[:80]} ({verdict})")
    lines += stamps if stamps else ["  demotion stamps: none"]
    return lines


def _checkpoint_lines(args, plan) -> list:
    """The ``checkpoint:`` section (ISSUE 14): the persist store's
    generation registry, the plan-fingerprint verdict for THIS plan, and
    the next scheduled write under the resolved policy. Built from the
    SAME ``CheckpointStore.describe``/``fingerprint_mismatch`` surface
    the restore path runs — explain cannot disagree with restore about
    which generation would load or why it would refuse."""
    import os as _os
    import time as _time

    from .. import persist
    ckdir = args.checkpoint_dir or _os.environ.get(persist.ENV_DIR, "")
    if not ckdir:
        return ["  store: none configured (--checkpoint-dir / "
                "$DFFT_CKPT_DIR unset)"]
    store = persist.CheckpointStore(ckdir)
    fp = persist.plan_fingerprint(plan)
    d = store.describe(expect_fingerprint=fp)
    lines = [f"  store: {d['directory']} "
             f"({len(persist.GENERATION_SLOTS)} generation slots)"]
    for g in d["generations"]:
        name = _os.path.basename(g["path"])
        if not g["exists"]:
            lines.append(f"  {name}: absent")
        elif g["valid"]:
            age = ("age unknown" if g["age_s"] is None
                   else f"age {g['age_s']:.1f} s")
            lines.append(f"  {name}: step {g['step']}, {age}, valid")
        else:
            lines.append(f"  {name}: INVALID ({g['reason']}) — restore "
                         "skips it (one-generation fallback)")
    lines.append(f"  plan fingerprint: {d['fingerprint_verdict']}")
    try:
        policy = persist.CheckpointPolicy.parse(
            args.checkpoint_policy
            or _os.environ.get(persist.ENV_POLICY))
    except ValueError as e:
        return lines + [f"  policy: INVALID spec ({e})"]
    latest = d["latest"]
    step = latest["step"] if latest else 0
    age = latest["age_s"] if latest and latest["age_s"] is not None else 0.0
    now = _time.monotonic()
    lines.append(f"  policy: {policy} — next write "
                 + policy.describe_next(step, step, now - age, now))
    return lines


def _serve_lines(args, kind: str, plan, cfg) -> list:
    """The ``serve:`` section: how a 2D request of this plane shape would
    be served by ``dfft-serve`` — the plan-cache key it would occupy,
    coalescing eligibility, and the circuit/ladder policy that would wrap
    it. Static (reuses the resolved plan/config; nothing executes)."""
    from .. import serve
    if kind == "batched":
        nx, ny = args.input_dim_x, args.input_dim_y
        shard = args.shard
        transform = plan.transform
        lead = []
    else:
        # The serving layer's unit of traffic is a single 2D image; for a
        # 3D plan, explain the (nx x ny) front-plane request a client
        # WOULD send (3D volumes go through the CLI/batch path).
        nx, ny = args.input_dim_x, args.input_dim_y
        shard = "batch"
        transform = "c2c" if args.c2c else "r2c"
        lead = ["  (dfft-serve serves single 2D images; this 3D plan runs "
                "through the CLI/batch path — below: the nx x ny 2D "
                "request a client would send)"]
    return lead + serve.describe_request(
        nx, ny, double=cfg.double_prec, transform=transform, shard=shard,
        config=cfg)


def _roofline_lines(plan, dims: int, tshape, backend: str) -> list:
    """Least work of one roundtrip (forward + inverse), counted from the
    declared plan graph by ``profile.least_work`` (the benchmark's
    model), and its least time on this device. Non-smooth axes get the
    Bluestein accounting (padded chirp length + flop factor), which the
    nominal 2.5·N·log2 N count does not see."""
    import jax

    from ..analysis import plangraph
    from ..ops import bluestein
    from . import profile
    lines = []
    rough = bluestein.nonsmooth_axes(tshape)
    for n in rough:
        m, over = bluestein.bluestein_axis_report(n)
        lines.append(
            f"  non-smooth axis {n}: no native fast path — bluestein "
            f"chirp length {m} (padded), ~{over:.1f}x the flops of a "
            f"smooth axis per pass"
            + ("" if backend == "bluestein" else
               f"; backend {backend} runs it "
               + ("as a dense O(n^2) contraction"
                  if backend.startswith("matmul") or backend == "pallas"
                  else "through XLA's generic expansion")
               + " (fft_backend='bluestein' takes the chirp path)"))
    if rough:
        lines.append("  (the nominal flops below assume smooth axes; "
                     "scale them by the factors above)")
    chips = plan.mesh.devices.size if plan.mesh is not None else 1
    shape = tuple(plan.global_size.shape)
    try:
        work = sum((profile.least_work(plangraph.graph_for(plan, d, dims),
                                       shape, chips)
                    for d in ("forward", "inverse")), profile.Work())
    except plangraph.MissingGraph as e:
        return lines + [f"  no declared graph ({e}): no least work"]
    lines.append(
        f"  one roundtrip, per chip of {chips}: {work.flops:.4g} flops "
        f"(2.5·N·log2 N per transformed axis), HBM "
        f"{_fmt_bytes(int(work.hbm_bytes))} (local FFTs' edges), ICI "
        f"{_fmt_bytes(int(work.ici_bytes))} sent")
    dev_kind = jax.devices()[0].device_kind
    peaks = profile.PEAKS.get(dev_kind)
    if peaks is None:
        return lines + [f"  no peaks for {dev_kind}: no least time"]
    bounds = work.bounds_s(peaks)
    bound = max(bounds, key=bounds.get)
    lines.append(f"  least time on {dev_kind}: {bounds[bound] * 1e3:.4g} ms "
                 f"(bound by {bound}; backend here: {backend})")
    return lines


def _graph_lines(plan, dims: int) -> list:
    """The ``graph:`` section: the declared stage graph (nodes, per-edge
    wire bytes, ring schedule depth) from the SAME plangraph registry
    ``dfft-verify`` checks — explain cannot disagree with the verifier
    about what pipeline this plan declares. Purely declarative (nothing
    compiles); a family without a declaration is reported, the exact
    condition the verify matrix fails on."""
    from ..analysis import plangraph
    try:
        graph = plangraph.graph_for(plan, "forward", dims)
    except plangraph.MissingGraph as e:
        return [f"  none declared ({e}) — dfft-verify fails this combo"]
    lines = plangraph.format_graph(graph)
    findings = plangraph.check_graph(graph)
    if findings:
        lines += [f"  WELL-FORMEDNESS VIOLATION: {v}" for v in findings]
    else:
        lines.append(
            f"  well-formed: {len(graph.nodes)} node(s) checked "
            "(dataflow, wire pairing, dtype flow, payload, guard "
            "arity, ring-schedule hazards)")
    return lines


def _census_lines(compiled) -> list:
    from ..testing.microbench import async_collective_counts
    c = async_collective_counts(compiled)
    order = ("all_to_all", "all_to_all_start", "collective_permute",
             "collective_permute_start", "async_total", "convert")
    return ["  " + "  ".join(f"{k}: {c[k]}" for k in order)]


def _contract_line(plan, compiled, dims: int) -> str:
    """The one-line contract verdict, sourced from the SAME registry and
    checker ``dfft-verify`` runs (``analysis/contracts.py``) — explain
    and verify cannot disagree about whether this program honors its
    declared contract."""
    from ..analysis import contracts, hloscan
    try:
        contract = contracts.contract_for(plan, "forward", dims)
    except KeyError:
        return "  contract: unverified (no contract registered for this " \
               "plan family)"
    try:
        txt = compiled.as_text()
        census = hloscan.collective_census(txt)
        staged = None
        if any(r.kind == "payload" for r in contract.rules):
            staged = hloscan.staged_exchange_total(plan, "forward", dims)
        violations = contracts.check_contract(contract, census, txt, staged)
    except Exception as e:  # noqa: BLE001 — diagnostics must not abort
        return f"  contract: unverified ({type(e).__name__}: {e})"
    if violations:
        return (f"  contract: VIOLATED [{contract.name}] — "
                + "; ".join(str(v) for v in violations))
    return f"  contract: verified ({contract.name}, " \
           f"{len(contract.rules)} rule(s); dfft-verify runs the full " \
           "matrix)"


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
    import numpy as np

    if args.double_prec:
        jax.config.update("jax_enable_x64", True)

    from .. import params as pm
    from ..testing import testcases as tc
    from ..utils import wisdom

    kind = args.kind
    transform = "c2c" if args.c2c else "r2c"
    nx, ny, nz = args.input_dim_x, args.input_dim_y, args.input_dim_z
    ndev = len(jax.devices())
    cfg = pm.Config(
        comm_method=pm.parse_comm_method(args.comm_method),
        send_method=pm.SendMethod.parse(args.send_method),
        comm_method2=(pm.parse_comm_method(args.comm_method2)
                      if args.comm_method2 else None),
        send_method2=(pm.SendMethod.parse(args.send_method2)
                      if args.send_method2 else None),
        opt=args.opt, double_prec=args.double_prec,
        fft_backend=args.fft_backend,
        streams_chunks=args.streams_chunks,
        overlap_depth=pm.parse_overlap_depth(args.overlap_depth),
        overlap_subblocks=args.overlap_subblocks,
        wire_dtype=pm.parse_wire_dtype(args.wire_dtype),
        wire_error_budget=args.wire_error_budget,
        fused_wire=bool(args.fused_wire),
        guards=args.guards,
        wisdom_path=args.wisdom, use_wisdom=not args.no_wisdom)

    if kind == "pencil":
        p1 = args.partition1 or 2
        p2 = args.partition2 or max(1, ndev // p1)
        partition = pm.PencilPartition(p1, p2)
        g = pm.GlobalSize(nx, ny, nz)
        mk_kind, variant, dims = "pencil", None, args.fft_dim
    elif kind == "batched":
        p = args.partitions or ndev
        partition = pm.SlabPartition(p)
        # Batched size-slot convention: (batch, nx, ny) with -nz = batch.
        g = pm.GlobalSize(nz, nx, ny)
        mk_kind, variant, dims = "batched2d", args.shard, 2
    else:
        p = args.partitions or ndev
        partition = pm.SlabPartition(p)
        g = pm.GlobalSize(nx, ny, nz)
        mk_kind, variant, dims = "slab", None, 3

    with obs.span("explain", kind=mk_kind, shape=list(g.shape)):
        # LOOKUP-ONLY resolution: a miss is reported, never raced —
        # explain must not execute measurement programs.
        cfg, prov = wisdom.peek_config(
            mk_kind, g, partition, cfg,
            sequence=args.sequence if kind == "slab" else None,
            transform=transform, dims=dims, variant=variant)

        # Build the plan with the fully concrete config (passes through
        # resolve_config untouched — no race can trigger).
        if kind == "batched":
            from ..models.batched2d import Batched2DFFTPlan
            plan = Batched2DFFTPlan(nz, nx, ny, partition, cfg,
                                    shard=args.shard, transform=transform,
                                    batch_chunk=args.batch_chunk)
        else:
            plan = tc.make_plan(mk_kind, g, partition, cfg,
                                sequence=args.sequence, transform=transform,
                                dims=dims)
        cfg = plan.config

        platform = jax.devices()[0].platform
        cdt = np.complex128 if args.double_prec else np.complex64
        rdt = (cdt if transform == "c2c"
               else (np.float64 if args.double_prec else np.float32))
        ranks = partition.num_ranks
        mesh_desc = (dict(plan.mesh.shape) if plan.mesh is not None
                     else "single-device (fft3d fallback)")

        out = []
        out.append(f"dfft-explain: {mk_kind} {g.nx}x{g.ny}x{g.nz} "
                   f"{transform} over {ranks} rank(s) on {platform} "
                   f"(mesh {mesh_desc})")

        out.append("decomposition:")
        out.append(f"  kind: {mk_kind}"
                   + (f"  sequence: {plan.sequence.value}"
                      if kind == "slab" else "")
                   + (f"  shard: {args.shard}" if kind == "batched" else "")
                   + (f"  dims: {dims}" if kind == "pencil" else ""))
        in_spec = getattr(plan, "_in_spec", None)
        out_spec = getattr(plan, "_out_spec", None)
        out.append(f"  input : logical {tuple(plan.input_shape)}  padded "
                   f"{tuple(plan.input_padded_shape)}  spec "
                   f"{in_spec if plan.mesh is not None else '—'}")
        out.append(f"  output: logical {tuple(plan.output_shape)}  padded "
                   f"{tuple(plan.output_padded_shape)}  spec "
                   f"{out_spec if plan.mesh is not None else '—'}")

        out.append("fft sequence:")
        xshapes = []  # (label, exchanged global payload shape)
        xmeta = []    # (label, payload shape, mesh axis size, send method)
        if kind == "slab":
            s = plan._seq
            first = ("C2C" if transform == "c2c" else "R2C") \
                + f" axis {'xyz'[s.r2c_axis]}"
            if s.pre_axes:
                first += " + C2C " + ",".join("xyz"[a] for a in s.pre_axes)
            out.append(f"  stage 1: {first}")
            if ranks > 1:
                out.append(f"  exchange: scatter {'xyz'[s.split_axis]} -> "
                           "gather x")
                xshapes.append(("transpose", plan.output_padded_shape))
                xmeta.append(("transpose", plan.output_padded_shape, ranks,
                              cfg.send_method))
            out.append("  stage 2: C2C "
                       + ",".join("xyz"[a] for a in s.post_axes))
        elif kind == "pencil":
            out.append("  stage 1: " + ("C2C z" if transform == "c2c"
                                        else "R2C z"))
            if dims >= 2 and ranks > 1:
                t1_shape = (plan._nx_p1, plan._ny_p2, plan._nzc_p2)
                out.append("  exchange 1 (p2 axis): scatter z -> gather y")
                xshapes.append(("transpose 1", t1_shape))
                xmeta.append(("transpose 1", t1_shape, plan.p2,
                              cfg.send_method))
            if dims >= 2:
                out.append("  stage 2: C2C y")
            if dims >= 3 and ranks > 1:
                t2_shape = (plan._nx_p1, plan._ny_p1, plan._nzc_p2)
                out.append("  exchange 2 (p1 axis): scatter y -> gather x")
                xshapes.append(("transpose 2", t2_shape))
                xmeta.append(("transpose 2", t2_shape, plan.p1,
                              cfg.resolved_snd2()))
            if dims >= 3:
                out.append("  stage 3: C2C x")
        else:
            out.append("  stage 1: " + ("C2C y" if transform == "c2c"
                                        else "R2C y") + " (per plane)")
            if args.shard == "x" and ranks > 1:
                out.append("  exchange: scatter spectral y -> gather x")
                bshape = (plan._batch_pad, plan._nx_pad, plan._nys_pad)
                xshapes.append(("transpose", bshape))
                xmeta.append(("transpose", bshape, ranks,
                              cfg.send_method))
                out.append("  stage 2: C2C x (per plane)")
            else:
                out.append("  stage 2: C2C x (per plane; batch sharding "
                           "issues no collectives)")
            out.append(f"  batch_chunk: {plan.batch_chunk}  chunk_group: "
                       f"{plan.chunk_group}")

        out.append("rendering:")
        if ranks == 1 or (kind == "batched" and args.shard == "batch"):
            out.append("  no exchange: "
                       + ("single-device fft3d fallback" if ranks == 1
                          else "embarrassingly parallel batch sharding "
                               "(zero collectives)"))
        elif kind == "pencil":
            out.append(f"  transpose 1: comm {cfg.comm_method.value} snd "
                       f"{cfg.send_method.value} -> "
                       + _rendering(cfg.comm_method, cfg.send_method,
                                    cfg.opt, plan.p2,
                                    cfg.fused_wire_active(),
                                    depth=cfg.resolved_overlap_depth(),
                                    subblocks=cfg
                                    .resolved_overlap_subblocks()))
            if dims >= 3:
                out.append(f"  transpose 2: comm "
                           f"{cfg.resolved_comm2().value} snd "
                           f"{cfg.resolved_snd2().value} -> "
                           + _rendering(cfg.resolved_comm2(),
                                        cfg.resolved_snd2(), cfg.opt,
                                        plan.p1,
                                        cfg.fused_wire_active(True),
                                        depth=cfg.resolved_overlap_depth(),
                                        subblocks=cfg
                                        .resolved_overlap_subblocks()))
        else:
            out.append(f"  comm {cfg.comm_method.value} snd "
                       f"{cfg.send_method.value} opt {cfg.opt} -> "
                       + _rendering(cfg.comm_method, cfg.send_method,
                                    cfg.opt, ranks,
                                    cfg.fused_wire_active(),
                                    depth=cfg.resolved_overlap_depth(),
                                    subblocks=cfg
                                    .resolved_overlap_subblocks()))
        out.append(f"  local FFT backend: {cfg.fft_backend}"
                   + (f" ({prov['backend_default']})"
                      if prov.get("backend_default") else "")
                   + (f" (mxu_precision={cfg.mxu_precision}, "
                      f"mxu_direct_max={cfg.mxu_direct_max})"
                      if cfg.fft_backend.startswith("matmul") else ""))
        out.append(f"  r2c_paired: {plan.r2c_paired}")
        out.append(f"  c2r_paired: {plan.c2r_paired}")

        out.append("graph (declared stage graph, plangraph registry):")
        out.extend(_graph_lines(plan, dims))

        sched = _schedule_lines(xmeta, cdt, cfg)
        if sched:
            out.append("overlap schedule (ring exchange, per device):")
            out.extend(sched)

        out.append("wire:")
        if xshapes:
            out.extend(_wire_lines(xshapes, cdt, cfg))
        else:
            out.append("  no exchange -> nothing on the wire")

        out.append("wisdom:")
        out.extend(_wisdom_lines(prov))

        out.append("resilience:")
        out.extend(_resilience_lines(plan, cfg, prov))

        out.append("serve:")
        out.extend(_serve_lines(args, kind, plan, cfg))

        out.append("checkpoint:")
        out.extend(_checkpoint_lines(args, plan))

        if not args.no_compile:
            out.append("hlo census (forward program, compiled, "
                       "NOT executed):")
            try:
                with obs.span("explain.compile", kind=mk_kind):
                    if kind == "pencil":
                        fn = plan._build_r2c_d(dims)
                    elif kind == "batched":
                        fn = plan._build(forward=True)
                    else:
                        fn = plan._build_r2c()
                    arg = jax.ShapeDtypeStruct(
                        tuple(plan.input_padded_shape), rdt)
                    compiled = fn.lower(arg).compile()
                out.extend(_census_lines(compiled))
                out.append(_contract_line(plan, compiled, dims))
            except Exception as e:  # noqa: BLE001 — census is best-effort
                out.append(f"  unavailable: {type(e).__name__}: {e}")
        else:
            out.append("hlo census: skipped (--no-compile)")
            out.append("  contract: unverified (needs the compiled module "
                       "— drop --no-compile or run dfft-verify)")

        out.append("roofline (least work from the plan graph):")
        out.extend(_roofline_lines(
            plan, dims, (nx, ny) if kind == "batched" else (nx, ny, nz),
            cfg.fft_backend))

        if args.profile:
            out.append("stage profile (MEASURED — jax.profiler trace of "
                       f"{max(1, args.profile_iters)} forward iteration(s), "
                       "device time joined onto the declared graph):")
            try:
                from . import profile as prof_mod
                with obs.span("explain.profile", kind=mk_kind):
                    prof = prof_mod.stage_profile(
                        plan, "forward", dims,
                        iters=max(1, args.profile_iters))
                out.extend(prof_mod.format_stage_profile(prof))
            except Exception as e:  # noqa: BLE001 — diagnostics only
                out.append(f"  unavailable: {type(e).__name__}: {e}")

        print("\n".join(out))

    if args.obs:
        import json
        print("obs metrics: "
              + json.dumps(obs.metrics.snapshot(), sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
