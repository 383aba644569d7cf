"""Graceful-degradation fallback ladder: demote, don't die.

A resolved rendering can fail AFTER construction: a ``SendMethod.RING``
program that no longer lowers on a new jax/libtpu, an opt1 relayout the
compiler rejects at some shape, a GSPMD delegation that stopped
partitioning, a compressed wire whose drift trips the guards. Today every
one of those is an unhandled exception on the hot path. This module turns
them into a LADDER: when a plan's jitted pipeline raises (trace, lower,
compile or runtime), the plan demotes exactly ONE rung, rebuilds, and
retries —

    ring/streams -> opt1 (realigned lax.all_to_all)
                 -> default layout (opt 0)
                 -> explicit All2All (from a failing GSPMD delegation)
    bf16 wire    -> native wire        (also on a check-mode GuardViolation)

until the ladder is exhausted, at which point the last error propagates
(the default SYNC/opt0/All2All/native config has zero rungs, so a plain
plan's errors are NEVER retried or masked). Every demotion is loud: an
``obs.notice``, ``fallback.demotions`` (+ per-rung) metrics, and a
DEMOTION STAMP on the plan's wisdom record (``wisdom.stamp_demotion``) so
the store stops recommending the failing cell — a stamped record reads as
a miss and re-races.

The ladder is suppressed inside autotune races (``suppressed()``): a
candidate that fails must LOSE the race, not silently measure its own
demotion. ``$DFFT_FALLBACK=off`` disables the ladder process-wide (errors
then propagate exactly as before this layer existed).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from typing import Optional, Tuple

from .. import obs
from . import guards

# Rung identifiers, in ladder order (ladder_preview / metrics vocabulary).
RUNG_SEND = "send"    # ring/streams -> SYNC at the realigned (opt1) layout
RUNG_OPT = "opt"      # opt1 -> default layout
RUNG_COMM = "comm"    # Peer2Peer (GSPMD) -> explicit All2All
RUNG_WIRE = "wire"    # compressed wire -> native


class _Tls(threading.local):
    def __init__(self):
        self.suppressed = 0


_TLS = _Tls()


@contextlib.contextmanager
def suppressed():
    """Disable the ladder for the calling thread (autotune races: a
    failing candidate must rank as failed, not measure its demotion)."""
    _TLS.suppressed += 1
    try:
        yield
    finally:
        _TLS.suppressed -= 1


def enabled() -> bool:
    if _TLS.suppressed:
        return False
    return os.environ.get("DFFT_FALLBACK", "").strip().lower() != "off"


def next_rung(cfg) -> Tuple[Optional[object], Optional[str]]:
    """``(demoted config, rung name)`` one rung down the ladder, or
    ``(None, None)`` when exhausted. Exactly one axis moves per call."""
    import dataclasses as dc

    from .. import params as pm
    sends = (cfg.send_method, cfg.send_method2)
    if (any(s not in (None, pm.SendMethod.SYNC, pm.SendMethod.MPI_TYPE)
            for s in sends)
            or cfg.resolved_overlap_subblocks() > 1):
        # The pipelined renderings — rings at any overlap depth, sub-
        # block splits, AND the pipelined all-to-all (Sync + subblocks
        # > 1) — demote to the realigned MONOLITHIC exchange (the
        # ladder's "opt1" rung), not straight to default: opt1 is the
        # better-performing safe rendering (README matrix). The overlap
        # knobs reset too, or the "demoted" cell would still be the
        # pipelined a2a.
        return dc.replace(cfg, send_method=pm.SendMethod.SYNC,
                          send_method2=None, streams_chunks=None,
                          overlap_depth=pm.AUTO, overlap_subblocks=None,
                          opt=1), RUNG_SEND
    if cfg.opt == 1:
        return dc.replace(cfg, opt=0), RUNG_OPT
    if (cfg.comm_method is pm.CommMethod.PEER2PEER
            or cfg.comm_method2 is pm.CommMethod.PEER2PEER):
        return dc.replace(cfg, comm_method=pm.CommMethod.ALL2ALL,
                          comm_method2=None), RUNG_COMM
    if cfg.wire_dtype != "native":
        return dc.replace(cfg, wire_dtype="native"), RUNG_WIRE
    return None, None


def ladder_preview(cfg) -> list:
    """Human-readable rung sequence that WOULD apply to ``cfg`` (the
    dfft-explain resilience section): ``[(rung, label), ...]``."""
    from ..utils.wisdom import _describe_comm
    out = []
    cur = cfg
    while True:
        cur, rung = next_rung(cur)
        if cur is None:
            break
        out.append((rung, _describe_comm(cur)))
    return out


# Compiled-callable caches every plan family hangs off itself; cleared on
# any config change so the next exec rebuilds under the demoted rendering.
_CACHE_ATTRS = ("_r2c", "_c2r", "_fwd", "_inv", "_fwd_unguarded",
                "_inv_unguarded", "_fwd_pure", "_inv_pure")
_CACHE_DICTS = ("_r2c_d", "_c2r_d")


def apply_config(plan, cfg) -> None:
    """Install a demoted config on a live plan: swap the config, refresh
    the MXU-settings snapshot, and drop every compiled/pure cache (and the
    guard states, whose tolerances depend on the wire)."""
    plan.config = cfg
    plan._mxu_st = cfg.mxu_settings()
    for a in _CACHE_ATTRS:
        if hasattr(plan, a):
            setattr(plan, a, None)
    for a in _CACHE_DICTS:
        d = getattr(plan, a, None)
        if isinstance(d, dict):
            d.clear()
    st = getattr(plan, "_guard_state", None)
    if isinstance(st, dict):
        st.clear()


def _stamp_wisdom(plan, rung: str, reason: str) -> None:
    """Best-effort demotion stamp on the plan's wisdom record(s): the
    slot(s) whose recommendation produced the failing cell. Stamped
    records read as misses (``wisdom._comm_hit_fold``), so the store
    stops recommending the cell until a fresh race re-records it."""
    from ..utils import wisdom
    try:
        store = wisdom.store_for_config(plan.config)
        if store is None:
            return
        ka = plan._wisdom_key_args()
        key = wisdom.plan_key(
            ka["kind"], plan.global_size.shape, plan.config.double_prec,
            plan.partition, plan.config.norm,
            transform=ka.get("transform", "r2c"),
            sequence=ka.get("sequence"), variant=ka.get("variant"),
            mesh_shape=wisdom._mesh_shape_of(plan.mesh, plan.partition),
            dims=ka.get("dims", 3))
        slots = ("wire", "comm") if rung == RUNG_WIRE else ("comm",)
        for slot in slots:
            wisdom.stamp_demotion(store, key, slot, rung, reason)
    except Exception:  # noqa: BLE001 — stamping degrades, never errors
        pass


def _note_demotion(plan, rung: str, label: str, reason: str) -> None:
    obs.metrics.inc("fallback.demotions")
    obs.metrics.inc(f"fallback.{rung}_demotions")
    fp = guards.fingerprint(plan, "n/a")
    obs.notice(
        f"fallback[{rung}]: demoting {fp['plan']} {fp['shape']} one rung "
        f"-> {label} ({reason})",
        name="fallback.demotion", rung=rung, to=label, reason=reason,
        plan=fp["plan"], shape=fp["shape"], ranks=fp["ranks"])
    # Flight-recorder trigger (ISSUE 12): a rung walk means the shipped
    # rendering failed in production — dump the evidence leading up.
    obs.flightrec.trigger("fallback_demotion",
                          f"rung {rung} -> {label}: {reason}"[:200],
                          rung=rung, plan=fp["plan"], shape=fp["shape"])
    _stamp_wisdom(plan, rung, reason)


def demote(plan, err: BaseException) -> bool:
    """Walk the plan one rung down after a pipeline failure; False when
    the ladder is exhausted or disabled (caller re-raises)."""
    if not enabled():
        return False
    cfg, rung = next_rung(plan.config)
    if cfg is None:
        return False
    from ..utils.wisdom import _describe_comm
    reason = f"{type(err).__name__}: {err}"[:300]
    _note_demotion(plan, rung, _describe_comm(cfg), reason)
    apply_config(plan, cfg)
    return True


def demote_wire(plan, reason: str) -> None:
    """Check-mode guard response: the compressed wire falls back to
    native for subsequent calls (rendering unchanged)."""
    if plan.config.wire_dtype == "native":
        return
    obs.metrics.inc("fallback.demotions")
    obs.metrics.inc("fallback.wire_demotions")
    fp = guards.fingerprint(plan, "n/a")
    obs.notice(
        f"fallback[wire]: {fp['plan']} {fp['shape']} wire "
        f"{plan.config.wire_dtype} -> native ({reason})",
        name="fallback.demotion", rung=RUNG_WIRE, to="native",
        reason=reason, plan=fp["plan"], shape=fp["shape"])
    obs.flightrec.trigger("fallback_demotion",
                          f"wire -> native: {reason}"[:200],
                          rung=RUNG_WIRE, plan=fp["plan"],
                          shape=fp["shape"])
    _stamp_wisdom(plan, RUNG_WIRE, reason)
    apply_config(plan, dataclasses.replace(plan.config,
                                           wire_dtype="native"))


def execute(plan, direction: str, x, get_runner, dims: int = 3):
    """The resilience envelope around one plan execution: run the (cached,
    possibly guarded) jitted pipeline; on failure walk the ladder one rung
    (rebuild via ``get_runner`` — the plan's builder reads the demoted
    config) and retry; on success run the host-side guard epilogue.

    ``GuardViolation`` (enforce mode) is never retried — the guard's
    verdict IS the answer. A default-rendering plan has zero rungs, so its
    errors propagate exactly as they did before this layer existed.

    Deadline plumbing (serving layer): when an ambient cooperative
    deadline is open (``resilience.deadline.scope``), the ladder walk is
    bounded by the TIGHTER of it and ``DFFT_FALLBACK_DEADLINE_S`` — a
    retry on behalf of a served request must stop when the request's
    budget is gone, and the original error (not a timeout) propagates.

    Spans: ``plan.exec`` around the whole envelope, ``plan.dispatch``
    around each call of the jitted program until it returns (``obs/
    tracing.py``); the parent less its children is the envelope's own
    host time."""
    from ..analysis.contracts import scope_family
    from . import deadline as _dl
    with obs.span("plan.exec", direction=direction,
                  family=scope_family(plan)):
        horizon = time.monotonic() + min(
            float(os.environ.get("DFFT_FALLBACK_DEADLINE_S", "600")),
            _dl.remaining_s(float("inf")))
        while True:
            try:
                with obs.span("plan.dispatch"):
                    out = get_runner()(x)
            except guards.GuardViolation:
                raise
            except Exception as err:  # noqa: BLE001 — the ladder's contract
                if time.monotonic() > horizon or not demote(plan, err):
                    raise
                continue
            return guards.finish(plan, out, direction, dims)
