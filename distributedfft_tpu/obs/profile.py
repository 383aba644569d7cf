"""Stage-attributed device profiling: join a ``jax.profiler`` trace back
against the declared plan graph.

The plan-graph IR (``analysis/plangraph.py``) says which stages a plan
*declares*; the host-side spans (``tracing.py``) say how long the *build*
took; nothing so far says where the DEVICE time of an execution goes —
how many of the 123.4 ms at 1024^3 are exchange vs local FFT vs wire
encode. This module closes that loop in three steps:

1. **Scope emission** — the plan families wrap each declared graph
   node's emitted ops in ``jax.named_scope("dfft/<family>/<node-id>")``
   (``stage_scope``); inside a stage, each one-axis local FFT opens the
   sub-scope ``<node-id>.ax<k>`` (``axis_scope``; ``k`` the transformed
   array axis, which is x/y/z on the slab and pencil plans, whose
   arrays keep the global axis order), and the wire layer tags its
   encode/decode with ``dfft/wire/encode`` / ``dfft/wire/decode``
   (``wire_scope``). Scopes are METADATA ONLY: they ride the op
   ``metadata={op_name=...}`` attribute that ``hloscan.strip_metadata``
   removes, so every fingerprint pin and the 171-combo verify matrix
   are byte-identical with scopes on (pinned by the
   ``scope-zero-overhead`` pins; ``disable_scopes()`` /
   ``$DFFT_NO_STAGE_SCOPES`` exist exactly so the pin has an off side
   to compare against).
2. **Trace ingestion** — ``capture_stage_profile`` runs a plan
   direction under ``jax.profiler.trace`` and parses the dumped
   ``*.xplane.pb`` (a minimal hand-rolled protobuf walker — the XSpace
   schema is stable and tiny, and the bench image has no tensorflow to
   parse it for us) or, as a fallback/fixture format, Chrome
   trace-events JSON (``parse_trace_events``). Nested op events (an XLA
   ``call`` wrapping its fusions) are resolved by SELF-TIME attribution
   so nothing is double counted.
3. **Graph join** — ``stage_profile`` aggregates device time by scope
   (axis sub-scopes folded into their stage) and joins it onto the
   declared graph: per-node device time, the exchange-vs-compute
   split, the unattributed remainder (dispatch, h2d, ops outside any
   scope — honesty line, never hidden), and a per-stage roofline-gap
   row (measured vs the nominal ideal for that node's axes). GSPMD (``p2p``) exchanges stage no explicit op to
   scope, so their collective lands in the unattributed remainder —
   reported, not guessed.

Consumers: ``dfft-explain --profile`` (the one explain mode that
executes), the four CLIs' ``--profile-stages`` epilogue, and the bench
mesh child's ``"stage_profile"`` block in BENCH_DETAILS.json.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import math
import os
import re
import tempfile
import threading
from typing import Any, Dict, Iterator, List, Optional, Tuple

SCOPE_PREFIX = "dfft"
ENV_NO_SCOPES = "DFFT_NO_STAGE_SCOPES"

# A scope path segment pair: "dfft/<family>/<node-id>" (also
# "dfft/wire/encode"). The op_name metadata embeds nested scopes as path
# segments; attribution takes the LAST (innermost) match.
SCOPE_RE = re.compile(r"dfft/([A-Za-z0-9_.-]+/[A-Za-z0-9_.:-]+)")

_SCOPES_FORCED_OFF = [False]


def scopes_enabled() -> bool:
    """Whether the families emit stage scopes (on by default; the
    zero-overhead pin toggles this to get its comparison side)."""
    if _SCOPES_FORCED_OFF[0]:
        return False
    return os.environ.get(ENV_NO_SCOPES, "").strip().lower() \
        not in ("1", "true", "on", "yes")


def disable_scopes() -> None:
    _SCOPES_FORCED_OFF[0] = True


def enable_scopes() -> None:
    _SCOPES_FORCED_OFF[0] = False


@contextlib.contextmanager
def scopes_off() -> Iterator[None]:
    """Force scopes off for one block, restoring the PRIOR forced state
    on exit — the zero-overhead pins' comparison side. Unlike a bare
    ``disable_scopes()``/``enable_scopes()`` pair this nests correctly
    inside a caller that already disabled scopes for its own baseline."""
    prev = _SCOPES_FORCED_OFF[0]
    _SCOPES_FORCED_OFF[0] = True
    try:
        yield
    finally:
        _SCOPES_FORCED_OFF[0] = prev


def scope_name(family: str, node_id: str) -> str:
    """The canonical scope string of one declared graph node."""
    return f"{SCOPE_PREFIX}/{family}/{node_id}"


class _Stages(threading.local):
    def __init__(self):
        # open (family, node id) stages, innermost last
        self.stack: List[Tuple[str, str]] = []


_STAGES = _Stages()


@contextlib.contextmanager
def _stage(family: str, node_id: str, jax) -> Iterator[None]:
    with jax.named_scope(scope_name(family, node_id)):
        _STAGES.stack.append((family, node_id))
        try:
            yield
        finally:
            _STAGES.stack.pop()


def stage_scope(family: str, node_id: str):
    """``jax.named_scope`` for one declared node's ops (trace-time;
    metadata only), which also records the open ``(family, node)`` for
    ``axis_scope``. A no-op context when scopes are disabled, the node id
    is falsy (an undeclared exchange), or jax is absent."""
    if not node_id or not scopes_enabled():
        return contextlib.nullcontext()
    try:
        import jax
    except Exception:  # noqa: BLE001 — jax-free interpreter
        return contextlib.nullcontext()
    return _stage(family, node_id, jax)


def axis_scope(axis: int, ndim: int):
    """The per-axis sub-scope of the innermost open stage,
    ``dfft/<family>/<node>.ax<k>`` with ``k`` the non-negative index of
    the transformed array axis: ``ops/fft.py`` opens it around each
    one-axis transform, so a trace splits a local-FFT stage by axis.
    The node id keeps its stage prefix (``slab/local_fft:1.ax2``), so
    prefix matches on the node still count the axis's ops. A no-op
    context outside a stage or with scopes disabled."""
    if not _STAGES.stack or not scopes_enabled():
        return contextlib.nullcontext()
    import jax
    family, node_id = _STAGES.stack[-1]
    return jax.named_scope(scope_name(family, f"{node_id}.ax{axis % ndim}"))


_AXIS_SUFFIX = re.compile(r"\.ax\d+$")


def stage_of(scope: str) -> str:
    """The stage a scope key belongs to: ``slab/local_fft:1.ax2`` ->
    ``slab/local_fft:1`` (other keys unchanged)."""
    return _AXIS_SUFFIX.sub("", scope)


def wire_scope(kind: str):
    """The wire layer's encode/decode scope (``dfft/wire/<kind>``) —
    nested inside the enclosing family exchange scope, so attribution
    can split wire time out of the exchange."""
    return stage_scope("wire", kind)


def scoped(family: str, node_id: str, fn):
    """Wrap a pipeline closure so its traced ops carry the node scope.
    A falsy ``node_id`` (an exchange the graph does not declare, e.g. a
    size-1 mesh axis) passes the closure through unscoped."""
    if fn is None or not node_id:
        return fn

    def wrapped(*args, **kwargs):
        with stage_scope(family, node_id):
            return fn(*args, **kwargs)

    return wrapped


# ---------------------------------------------------------------------------
# xplane parsing (minimal protobuf walker over the XSpace schema)
# ---------------------------------------------------------------------------

def _pb_fields(buf: bytes) -> Iterator[Tuple[int, int, Any]]:
    """Yield ``(field_no, wire_type, value)`` over one protobuf message.
    Varint and length-delimited fields decode; fixed32/64 pass as raw
    bytes. Raises ValueError on malformed input (callers treat that as
    'not a message')."""
    i, n = 0, len(buf)
    while i < n:
        tag, shift = 0, 0
        while True:
            if i >= n:
                raise ValueError("truncated tag")
            b = buf[i]
            i += 1
            tag |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        fno, wt = tag >> 3, tag & 7
        if wt == 0:
            v, shift = 0, 0
            while True:
                if i >= n:
                    raise ValueError("truncated varint")
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            yield fno, wt, v
        elif wt == 2:
            ln, shift = 0, 0
            while True:
                if i >= n:
                    raise ValueError("truncated length")
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            if i + ln > n:
                raise ValueError("truncated bytes field")
            yield fno, wt, buf[i:i + ln]
            i += ln
        elif wt == 5:
            yield fno, wt, buf[i:i + 4]
            i += 4
        elif wt == 1:
            yield fno, wt, buf[i:i + 8]
            i += 8
        else:
            raise ValueError(f"unsupported wire type {wt}")


def _collect_strings(buf: bytes, depth: int = 0, limit: int = 1) -> List[str]:
    """Shallow utf-8-decodable length-delimited fields of a message tree
    — the schema-drift-robust way to find an event metadata's OWN
    op_name strings (XEventMetadata.name/display_name, a tf_op stat
    string, a direct OpMetadata stat). Depth-limited to 1 so a full HLO
    module proto embedded in a module-level event's stats does NOT leak
    its per-instruction op_names onto that wrapper event —
    ``_harvest_hlo_scopes`` mines those separately and joins them by
    instruction name."""
    out: List[str] = []
    if depth > limit:
        return out
    try:
        for _, wt, v in _pb_fields(buf):
            if wt != 2 or not isinstance(v, bytes):
                continue
            try:
                s = v.decode("utf-8")
            except UnicodeDecodeError:
                s = None
            if s is not None and s.isprintable() and s:
                out.append(s)
            if len(v) > 3:
                out.extend(_collect_strings(v, depth + 1, limit))
    except ValueError:
        pass
    return out


def extract_scope(strings: List[str]) -> Optional[str]:
    """Innermost ``dfft/<x>/<y>`` scope across the given strings (last
    match of the LONGEST matching string, so the full nested path wins
    over a short prefix duplicate)."""
    best: Optional[str] = None
    best_len = -1
    for s in strings:
        ms = SCOPE_RE.findall(s)
        if ms and len(s) > best_len:
            best, best_len = ms[-1], len(s)
    return best


_INSTR_NAME_RE = re.compile(r"^[A-Za-z0-9_.\-]+$")


def _harvest_hlo_scopes(buf: bytes, out: Dict[str, str],
                        depth: int = 0) -> None:
    """Instruction-name -> scope map from any serialized HLO module
    embedded in a plane's stats. The CPU/TPU profilers attach the
    compiled module's HloProto to a module-level event; per-op events
    then carry only the instruction NAME (``fft.7``,
    ``transpose_copy_fusion.2``) — the op_name path with the named
    scopes lives on the proto's instructions. Schema-lightly: any
    message with a name-shaped field 1 string and a field 7 submessage
    whose field 2 matches the scope pattern is an HloInstructionProto
    (name=1, metadata=7{op_name=2}). First mapping wins (HLO names are
    unique module-wide; across modules a collision keeps the first)."""
    if depth > 12:
        return
    try:
        fields = list(_pb_fields(buf))
    except ValueError:
        return
    name: Optional[str] = None
    scope: Optional[str] = None
    for fno, wt, v in fields:
        if fno == 1 and wt == 2 and isinstance(v, bytes):
            try:
                s = v.decode("utf-8")
            except UnicodeDecodeError:
                continue
            if _INSTR_NAME_RE.match(s):
                name = s
        elif fno == 7 and wt == 2 and isinstance(v, bytes):
            try:
                for f2, w2, v2 in _pb_fields(v):
                    if f2 == 2 and w2 == 2 and isinstance(v2, bytes):
                        try:
                            s2 = v2.decode("utf-8")
                        except UnicodeDecodeError:
                            continue
                        ms = SCOPE_RE.findall(s2)
                        if ms:
                            scope = ms[-1]
            except ValueError:
                pass
    if name and scope:
        out.setdefault(name, scope)
    for fno, wt, v in fields:
        if wt == 2 and isinstance(v, bytes) and len(v) > 8:
            _harvest_hlo_scopes(v, out, depth + 1)


def parse_xplane(data: bytes) -> List[Dict[str, Any]]:
    """Parse one ``*.xplane.pb`` (XSpace) into
    ``[{"name", "lines": [{"name", "events": [{"name", "scope",
    "offset_ps", "dur_ps"}]}]}]``. Only the fields attribution needs."""
    planes: List[Dict[str, Any]] = []
    # Pass 1 — instruction-name -> scope from every embedded HLO module
    # proto in the WHOLE space: the profiler parks the serialized module
    # on a metadata plane (``/host:metadata``) while the op events live
    # on the execution planes, so the map must be global.
    name_scopes: Dict[str, str] = {}
    for fno, wt, v in _pb_fields(data):
        if fno == 1 and wt == 2:
            _harvest_hlo_scopes(v, name_scopes)
    for fno, wt, v in _pb_fields(data):
        if fno != 1 or wt != 2:
            continue
        name = ""
        raw_lines: List[bytes] = []
        emeta: Dict[int, Dict[str, Any]] = {}
        for f2, w2, v2 in _pb_fields(v):
            if f2 == 2 and w2 == 2:
                name = v2.decode(errors="replace")
            elif f2 == 3 and w2 == 2:
                raw_lines.append(v2)
            elif f2 == 4 and w2 == 2:
                # map<int64, XEventMetadata> entry: key=1, value=2
                key: Optional[int] = None
                mname = ""
                strings: List[str] = []
                for f3, w3, v3 in _pb_fields(v2):
                    if f3 == 1 and w3 == 0:
                        key = v3
                    elif f3 == 2 and w3 == 2:
                        strings = _collect_strings(v3)
                        for f4, w4, v4 in _pb_fields(v3):
                            if f4 == 2 and w4 == 2:
                                mname = v4.decode(errors="replace")
                if key is not None:
                    emeta[key] = {"name": mname,
                                  "scope": extract_scope(strings)}
        lines = []
        for lv in raw_lines:
            lname = ""
            events: List[Dict[str, Any]] = []
            for f2, w2, v2 in _pb_fields(lv):
                if f2 in (2, 11) and w2 == 2:
                    lname = v2.decode(errors="replace")
                elif f2 == 4 and w2 == 2:
                    mid: Optional[int] = None
                    off = 0
                    dur = 0
                    for f3, w3, v3 in _pb_fields(v2):
                        if f3 == 1 and w3 == 0:
                            mid = v3
                        elif f3 == 2 and w3 == 0:
                            off = v3
                        elif f3 == 3 and w3 == 0:
                            dur = v3
                    meta = emeta.get(mid, {})
                    ename = meta.get("name", "")
                    scope = meta.get("scope") or name_scopes.get(ename)
                    events.append({"name": ename, "scope": scope,
                                   "offset_ps": off, "dur_ps": dur})
            lines.append({"name": lname, "events": events})
        planes.append({"name": name, "lines": lines})
    return planes


# ---------------------------------------------------------------------------
# trace-events parsing (perfetto/chrome JSON; also the committed fixture)
# ---------------------------------------------------------------------------

def parse_trace_events(obj: Any) -> List[Dict[str, Any]]:
    """Chrome trace-events JSON (``{"traceEvents": [...]}`` or a bare
    list) -> the same event dicts ``parse_xplane`` produces, one flat
    line. ``ph == "X"`` complete events only; scope extracted from the
    event name and any string args; timestamps are microseconds in this
    format (converted to ps for uniformity)."""
    evs = obj.get("traceEvents", []) if isinstance(obj, dict) else obj
    out: List[Dict[str, Any]] = []
    for e in evs:
        if not isinstance(e, dict) or e.get("ph") != "X":
            continue
        strings = [str(e.get("name", ""))]
        args = e.get("args")
        if isinstance(args, dict):
            strings += [str(v) for v in args.values()
                        if isinstance(v, str)]
        out.append({"name": str(e.get("name", "")),
                    "scope": extract_scope(strings),
                    "offset_ps": int(float(e.get("ts", 0)) * 1e6),
                    "dur_ps": int(float(e.get("dur", 0)) * 1e6)})
    return out


def load_trace(path: str) -> List[Dict[str, Any]]:
    """One trace artifact -> planes. ``.pb`` parses as xplane;
    ``.json``/``.json.gz`` as trace-events (wrapped in one synthetic
    plane so the aggregation sees a uniform shape)."""
    if path.endswith(".pb"):
        with open(path, "rb") as f:
            return parse_xplane(f.read())
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8") as f:  # type: ignore[operator]
        obj = json.load(f)
    return [{"name": "trace-events",
             "lines": [{"name": "events",
                        "events": parse_trace_events(obj)}]}]


def find_trace_files(logdir: str) -> List[str]:
    """The newest profiler run directory's parseable artifacts, xplane
    preferred (per-op device events; the CPU backend's trace.json carries
    only host python events)."""
    runs = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*")))
    if not runs:
        runs = [logdir]
    run = runs[-1]
    pbs = sorted(glob.glob(os.path.join(run, "*.xplane.pb")))
    if pbs:
        return pbs
    return sorted(glob.glob(os.path.join(run, "*trace.json.gz")) +
                  glob.glob(os.path.join(run, "*trace.json")))


# ---------------------------------------------------------------------------
# aggregation (self-time, per scope)
# ---------------------------------------------------------------------------

# Lines that carry host python bookkeeping, not op executions.
_SKIP_LINES = re.compile(r"^(python|launcher|\$)", re.IGNORECASE)
# Host-runtime dispatch events on the CPU backend's thread-pool lines
# (argument handling, output allocation, pool bookkeeping): not ops.
_RUNTIME_EVENTS = re.compile(
    r"^(PjRtCpuExecutable::|CommonPjRtClient::|ThreadpoolListener::"
    r"|Handle inputs$)")
# A TPU device plane carries a module-level line beside the op-level
# one; counting both would charge every op twice.
_DEVICE_OP_LINE = "XLA Ops"


def _self_times(events: List[Dict[str, Any]]) -> List[Tuple[
        Optional[str], float]]:
    """``(scope, self_time_ps)`` per event of ONE line: an event interval
    that contains other events is charged only for the time its children
    do not cover (flame-graph self time), so a ``call`` op wrapping a
    fusion is not counted twice. An unscoped child of a scoped op (the
    CPU runtime's ``Rendezvous`` inside an ``all-to-all``, its
    ``end: <op>`` marker) is part of that op and inherits its scope."""
    evs = [e for e in events if e.get("dur_ps", 0) > 0
           and not _RUNTIME_EVENTS.match(e.get("name", ""))]
    evs.sort(key=lambda e: (e["offset_ps"], -e["dur_ps"]))
    out: List[Tuple[Optional[str], float]] = []
    stack: List[Dict[str, Any]] = []  # open ancestors, innermost last
    child_time: List[float] = []
    for e in evs:
        end = e["offset_ps"] + e["dur_ps"]
        while stack and e["offset_ps"] >= \
                stack[-1]["offset_ps"] + stack[-1]["dur_ps"]:
            parent = stack.pop()
            covered = child_time.pop()
            out.append((parent.get("scope"),
                        max(0.0, parent["dur_ps"] - covered)))
        if stack and end <= stack[-1]["offset_ps"] + stack[-1]["dur_ps"]:
            child_time[-1] += e["dur_ps"]
            if not e.get("scope") and stack[-1].get("scope"):
                e = dict(e, scope=stack[-1]["scope"])
        stack.append(e)
        child_time.append(0.0)
    while stack:
        parent = stack.pop()
        covered = child_time.pop()
        out.append((parent.get("scope"),
                    max(0.0, parent["dur_ps"] - covered)))
    return out


def aggregate_trace(planes: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Aggregate device time by scope over the op-execution lines.
    Device planes (``/device:...``) win when present (TPU), read from
    their op-level line (``XLA Ops``) where they have one; host planes
    otherwise (the CPU backend runs its ops on host thread-pool lines).
    Returns ``{"scopes": {scope: ms}, "unattributed_ms", "total_ms",
    "window_ms", "planes": [names]}`` — python bookkeeping lines are
    skipped, nested ops resolved by self time. ``window_ms`` is the
    longest first-to-last-event span of one chosen line: on a device,
    the ops of one line can never add up to more."""
    device = [p for p in planes if p["name"].startswith("/device:")
              and any(ln["events"] for ln in p["lines"])]
    chosen = device or [p for p in planes
                        if any(ln["events"] for ln in p["lines"])]
    scopes: Dict[str, float] = {}
    unattributed = 0.0
    window = 0.0
    for plane in chosen:
        lines = [ln for ln in plane["lines"]
                 if not _SKIP_LINES.match(ln["name"] or "")]
        if plane in device and any(ln["name"] == _DEVICE_OP_LINE
                                   for ln in lines):
            lines = [ln for ln in lines if ln["name"] == _DEVICE_OP_LINE]
        for line in lines:
            evs = [e for e in line["events"] if e.get("dur_ps", 0) > 0]
            if evs:
                window = max(window, max(e["offset_ps"] + e["dur_ps"]
                                         for e in evs)
                             - min(e["offset_ps"] for e in evs))
            for scope, ps in _self_times(evs):
                if scope:
                    scopes[scope] = scopes.get(scope, 0.0) + ps
                else:
                    unattributed += ps
    to_ms = 1e-9  # ps -> ms
    return {
        "scopes": {k: round(v * to_ms, 6) for k, v in sorted(scopes.items())},
        "unattributed_ms": round(unattributed * to_ms, 6),
        "total_ms": round((sum(scopes.values()) + unattributed) * to_ms, 6),
        "window_ms": round(window * to_ms, 6),
        "planes": [p["name"] for p in chosen],
    }


# ---------------------------------------------------------------------------
# capture (executes the plan — the ONE obs surface that runs the FFT)
# ---------------------------------------------------------------------------

def capture_stage_profile(plan: Any, direction: str = "forward",
                          dims: int = 3, iters: int = 3,
                          warmup: int = 1) -> Dict[str, Any]:
    """Run one direction of a live plan under ``jax.profiler.trace`` and
    aggregate its device time by stage scope. Input is synthesized at the
    padded aval and device_put BEFORE the profiled window, so transfer
    time does not pollute the attribution. Times are per iteration."""
    import jax
    import numpy as np

    from ..analysis import hloscan
    from . import tracing

    runner = hloscan._builder(plan, direction, dims)
    aval = hloscan._input_aval(plan, direction, dims)
    rng = np.random.default_rng(0)
    if np.dtype(aval.dtype).kind == "c":
        x = (rng.standard_normal(aval.shape)
             + 1j * rng.standard_normal(aval.shape)).astype(aval.dtype)
    else:
        x = rng.standard_normal(aval.shape).astype(aval.dtype)
    sharding = (plan.input_sharding if direction == "forward"
                else plan.output_sharding)
    xd = jax.device_put(x, sharding) if sharding is not None \
        else jax.device_put(x)
    for _ in range(max(0, warmup)):
        jax.block_until_ready(runner(xd))
    iters = max(1, iters)
    with tempfile.TemporaryDirectory() as td:
        with tracing.span("profile.capture", direction=direction,
                          iters=iters):
            with jax.profiler.trace(td):
                for _ in range(iters):
                    jax.block_until_ready(runner(xd))
        files = find_trace_files(td)
        if not files:
            raise RuntimeError(
                f"jax.profiler.trace produced no parseable artifact "
                f"under {td} (xplane/trace-events expected)")
        planes: List[Dict[str, Any]] = []
        for f in files:
            planes.extend(load_trace(f))
    agg = aggregate_trace(planes)
    agg = {
        "scopes": {k: round(v / iters, 6)
                   for k, v in agg["scopes"].items()},
        "unattributed_ms": round(agg["unattributed_ms"] / iters, 6),
        "total_ms": round(agg["total_ms"] / iters, 6),
        "window_ms": round(agg["window_ms"] / iters, 6),
        "planes": agg["planes"],
    }
    agg["iters"] = iters
    agg["direction"] = direction
    return agg


# ---------------------------------------------------------------------------
# graph join
# ---------------------------------------------------------------------------

def node_scope_key(graph: Any, node: Any) -> Optional[str]:
    """The aggregation key one declared node's device time lands under
    (None = the node stages nothing attributable: input/output, and
    GSPMD-owned exchanges whose collective no explicit op carries)."""
    if node.kind in ("input", "output"):
        return None
    if node.kind == "exchange":
        if node.rendering == "p2p":
            return None
        return f"{graph.family}/{node.id}"
    if node.kind in ("local_fft", "guard"):
        return f"{graph.family}/{node.id}"
    if node.encodes():
        return "wire/encode"
    if node.decodes():
        return "wire/decode"
    return None


def _node_ideal_ms(graph: Any, node: Any, ranks: int,
                   kind: str) -> Optional[float]:
    """Nominal ideal time of one local-FFT stage: 2.5*N*log2(extent) per
    transformed axis over the effective peak of a ``kind`` chip, per-chip
    share on the mesh (the roofline module's convention — communication
    deliberately unmodeled, so exchange nodes have no ideal; their
    measured time IS the roofline gap). None for a kind with no
    published peak."""
    if node.kind != "local_fft" or not node.axes:
        return None
    from ..evalkit import roofline as rl
    in_edges = graph.in_edges(node.id)
    if not in_edges:
        return None
    shape = in_edges[0].shape
    elems = 1
    for s in shape:
        elems *= int(s)
    flops = 0.0
    for a in node.axes:
        if 0 <= a < len(shape) and shape[a] > 1:
            flops += 2.5 * elems * math.log2(shape[a])
    if flops <= 0:
        return None
    peak = rl.effective_peak_tflops(kind, "high")
    if peak is None:
        return None
    return float(f"{flops / (peak * 1e12 * max(1, ranks)) * 1e3:.4g}")


def stage_profile(plan: Any, direction: str = "forward", dims: int = 3,
                  iters: int = 3, warmup: int = 1,
                  capture: Optional[Dict[str, Any]] = None
                  ) -> Dict[str, Any]:
    """The joined stage-attribution report: capture (or reuse
    ``capture``), resolve the declared graph, and emit one row per
    declared node — device time, fraction of the measured total, and the
    per-stage roofline gap — plus the exchange-vs-compute split and the
    unattributed remainder. This is the ``"stage_profile"`` block shape
    bench.py commits and ``dfft-explain --profile`` prints."""
    from ..analysis import plangraph

    graph = plangraph.graph_for(plan, direction, dims)
    agg = capture if capture is not None else capture_stage_profile(
        plan, direction, dims, iters=iters, warmup=warmup)
    scopes: Dict[str, float] = {}
    for k, v in agg["scopes"].items():  # axis sub-scopes fold into their stage
        k = stage_of(k)
        scopes[k] = scopes.get(k, 0.0) + v
    total = float(agg["total_ms"]) or 1e-12
    ranks = 1
    mesh = getattr(plan, "mesh", None)
    if mesh is not None:
        ranks = math.prod(mesh.devices.shape)
        kind = mesh.devices.flat[0].device_kind
    else:
        import jax
        kind = jax.devices()[0].device_kind
    # Nodes sharing one scope key (two encodes under a dual-exchange p2p
    # pencil both land in "wire/encode") split that key's time evenly.
    keys: Dict[str, List[Any]] = {}
    for n in graph.nodes:
        k = node_scope_key(graph, n)
        if k is not None:
            keys.setdefault(k, []).append(n)
    rows: List[Dict[str, Any]] = []
    consumed: Dict[str, float] = {}
    exchange_ms = 0.0
    compute_ms = 0.0
    for n in graph.nodes:
        k = node_scope_key(graph, n)
        share = None
        approx = False
        if k is not None:
            t = scopes.get(k, 0.0)
            nshare = len(keys[k])
            share = t / nshare
            approx = nshare > 1
            consumed[k] = t
        ms = round(share, 6) if share is not None else 0.0
        ideal = _node_ideal_ms(graph, n, ranks, kind)
        row: Dict[str, Any] = {
            "node": n.id, "kind": n.kind,
            "label": n.label or plangraph._node_brief(n),
            "device_ms": ms,
            "fraction": round(ms / total, 4),
        }
        if k is None and n.kind == "exchange":
            row["note"] = ("gspmd-owned exchange: collective carries no "
                           "stage scope; its time is in the "
                           "unattributed remainder")
        if approx:
            row["approx"] = True
        if ideal is not None:
            row["ideal_ms"] = ideal
            if ms > 0 and ideal > 0:
                row["gap_x"] = float(f"{ms / ideal:.3g}")
        if n.kind in ("exchange", "encode", "decode", "fused_kernel"):
            exchange_ms += ms
        elif n.kind in ("local_fft", "guard"):
            compute_ms += ms
        rows.append(row)
    other = {k: v for k, v in scopes.items() if k not in consumed}
    attributed = sum(consumed.values())
    return {
        "family": graph.family,
        "direction": direction,
        "iters": agg.get("iters", iters),
        "total_ms": round(total, 6),
        "window_ms": agg.get("window_ms"),
        "attributed_ms": round(attributed, 6),
        "unattributed_ms": round(
            float(agg["unattributed_ms"]) + sum(other.values()), 6),
        "exchange_ms": round(exchange_ms, 6),
        "compute_ms": round(compute_ms, 6),
        "exchange_fraction": round(exchange_ms / total, 4),
        "stages": rows,
        "other_scopes": other,
        "planes": agg.get("planes", []),
    }


def format_stage_profile(prof: Dict[str, Any]) -> List[str]:
    """Human-readable stage table (the ``dfft-explain --profile`` and
    ``--profile-stages`` rendering)."""
    lines = [
        f"  {prof['family']}/{prof['direction']}: total "
        f"{prof['total_ms']:.3f} ms/iter over {prof['iters']} iter(s) — "
        f"exchange {prof['exchange_ms']:.3f} ms "
        f"({prof['exchange_fraction']:.0%}), compute "
        f"{prof['compute_ms']:.3f} ms, unattributed "
        f"{prof['unattributed_ms']:.3f} ms"]
    for row in prof["stages"]:
        if row["kind"] in ("input", "output"):
            continue
        extra = ""
        if "ideal_ms" in row:
            extra = f"  ideal {row['ideal_ms']:.4g} ms"
            if "gap_x" in row:
                extra += f" (gap {row['gap_x']:g}x)"
        if row.get("approx"):
            extra += "  [shared scope, split evenly]"
        if row.get("note"):
            extra += f"  [{row['note']}]"
        lines.append(
            f"  {row['node']:<16} {row['device_ms']:>10.3f} ms  "
            f"{row['fraction']:>6.1%}{extra}")
    if prof["other_scopes"]:
        for k, v in sorted(prof["other_scopes"].items()):
            lines.append(f"  (other scope {k}: {v:.3f} ms)")
    return lines
