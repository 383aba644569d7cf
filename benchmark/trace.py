"""Reduce a device trace to per-device op intervals inside the window.

The window is the benchmark's own host span (``bench/window``, a
``jax.profiler.TraceAnnotation`` around the measured loop), not the
first-to-last op of a line, so idle time at both ends counts. Each
device plane contributes the events of its op-level line (``XLA Ops``);
the module line beside it would count every op twice.

Each op carries its HLO name, its HLO opcode and the innermost plan-graph
stage scope (``dfft/<family>/<node>``, ``obs/profile.scope_name``) when
one is found. Scopes and opcodes live in the HLO module protos that the
profiler parks on the ``/host:metadata`` plane; they are mined with the
small protobuf walker below (copied from ``obs/profile.py``, whose
parser read a v5e trace soundly in PR 21) and joined to the ops by
module and instruction name.

Collectives are told by HLO opcode, and by name where no opcode is
found, never by scope: GSPMD-owned exchanges have none.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

SCOPE_RE = re.compile(r"dfft/([A-Za-z0-9_.-]+/[A-Za-z0-9_.:-]+)")
_COLLECTIVES = ("all-to-all", "ragged-all-to-all", "all-gather",
                "all-reduce", "reduce-scatter", "collective-permute",
                "collective-broadcast", "send", "recv")
_COLLECTIVE_RE = re.compile(
    r"^(%s)(-start|-done)?([.\-_]|$)" % "|".join(_COLLECTIVES))
_OPCODE_RE = re.compile(r"^[a-z][a-z-]*$")
_NAME_RE = re.compile(r"^[A-Za-z0-9_.\-]+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
_INSTR_TEXT_RE = re.compile(r"%?([A-Za-z0-9_.\-]+) = ")
_OPCODE_TEXT_RE = re.compile(r" ([a-z][a-z0-9-]*)\(")
SPAN_PREFIX = "bench/"
WINDOW = "bench/window"


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: int  # ns
    end: int
    scope: Optional[str]
    opcode: str

    @property
    def collective(self) -> bool:
        return bool(_COLLECTIVE_RE.match(self.opcode)
                    or _COLLECTIVE_RE.match(self.name))


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]
    devices: Dict[str, List[Op]]  # plane name -> ops clipped to the window
    spans: List[Span]             # the benchmark's host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


# ---------------------------------------------------------------------------
# HLO protos on the metadata plane -> (module, instruction) -> scope, opcode
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    v, shift = 0, 0
    while True:
        if i >= len(buf):
            raise ValueError("truncated varint")
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return v, i


def _pb_fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field, wire type, value)`` of one protobuf message; raises
    ValueError on bytes that are no message."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        fno, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
            yield fno, wt, v
        elif wt == 2:
            ln, i = _varint(buf, i)
            if i + ln > n:
                raise ValueError("truncated bytes")
            yield fno, wt, buf[i:i + ln]
            i += ln
        elif wt == 5:
            yield fno, wt, buf[i:i + 4]
            i += 4
        elif wt == 1:
            yield fno, wt, buf[i:i + 8]
            i += 8
        else:
            raise ValueError(f"wire type {wt}")


def _text(v: object) -> Optional[str]:
    if not isinstance(v, bytes):
        return None
    try:
        s = v.decode("utf-8")
    except UnicodeDecodeError:
        return None
    return s if s and s.isprintable() else None


def _harvest(buf: bytes, out: Dict[Tuple[str, str], Tuple[str, Optional[str]]],
             module: str = "", depth: int = 0) -> None:
    """Find HloInstructionProto-shaped messages (name = 1, opcode = 2,
    metadata = 7 {op_name = 2}) in one module's proto and record
    ``(module, instruction) -> (opcode, scope)``."""
    if depth > 14:
        return
    try:
        fields = list(_pb_fields(buf))
    except ValueError:
        return
    f1 = f2 = None
    meta = None
    for fno, wt, v in fields:
        if wt != 2:
            continue
        if fno == 1 and f1 is None:
            f1 = _text(v)
        elif fno == 2 and f2 is None:
            f2 = _text(v)
        elif fno == 7:
            meta = v
    if f1 and f2 and _NAME_RE.match(f1):
        if _OPCODE_RE.match(f2):
            scope = None
            if isinstance(meta, bytes):
                try:
                    for g, w, s in _pb_fields(meta):
                        t = _text(s) if (g == 2 and w == 2) else None
                        if t and SCOPE_RE.search(t):
                            scope = SCOPE_RE.findall(t)[-1]
                except ValueError:
                    pass
            out.setdefault((module, f1), (f2, scope))
            out.setdefault(("", f1), (f2, scope))
            return
    for _, wt, v in fields:
        if wt == 2 and isinstance(v, bytes) and len(v) > 8:
            _harvest(v, out, module, depth + 1)


def hlo_index(raw: bytes) -> Dict[Tuple[str, str], Tuple[str, Optional[str]]]:
    """``(module, instruction) -> (opcode, scope)`` from the HLO protos on
    the ``/host:metadata`` plane. Each module's proto rides one event
    metadata entry, whose name (``jit_wrapped(<fingerprint>)``) is the one
    the device's ``XLA Modules`` line gives the module's runs; programs
    of one plan share their jit name, so the fingerprint tells them
    apart. ``("", instruction)`` holds the first module's entry."""
    out: Dict[Tuple[str, str], Tuple[str, Optional[str]]] = {}
    for fno, wt, plane in _pb_fields(raw):
        if fno != 1 or wt != 2:
            continue
        fields = list(_pb_fields(plane))
        if next((_text(v) for f, w, v in fields if f == 2 and w == 2),
                None) != "/host:metadata":
            continue
        for f, w, entry in fields:
            if f != 4 or w != 2:  # map<int64, XEventMetadata>
                continue
            for f2, w2, meta in _pb_fields(entry):
                if f2 == 2 and w2 == 2:
                    name = next((_text(v) for g, x, v in _pb_fields(meta)
                                 if g == 2 and x == 2), None) or ""
                    _harvest(meta, out, name)
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def _stat(stats: Dict[str, object], key: str) -> str:
    v = stats.get(key)
    return v if isinstance(v, str) else ""


def load(path: str) -> Trace:
    """Ops of every device plane and the benchmark's host spans, clipped
    to the ``bench/window`` span. Raises when the trace holds no window
    span or no device op."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    index = hlo_index(raw)
    data = ProfileData.from_serialized_xspace(raw)
    spans: List[Span] = []
    planes: Dict[str, List[Op]] = {}
    cpu_ops: List[Op] = []  # the CPU backend runs its ops on host lines

    def op(name: str, module: str, e) -> Op:
        # A TPU op event is named by its HLO text, "%fusion.3 = f32[..] fusion(..)".
        m = _INSTR_TEXT_RE.match(name)
        hlo = m.group(1) if m else name
        opcode, scope = index.get((module, hlo)) or index.get(("", hlo)) \
            or ("", None)
        if not opcode and m:
            t = _OPCODE_TEXT_RE.search(name, m.end())
            opcode = t.group(1) if t else ""
        return Op(hlo, int(e.start_ns), int(e.end_ns), scope, opcode)

    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: [e for e in ln.events if e.duration_ns > 0]
                     for ln in plane.lines}
            runs = sorted((int(e.start_ns), int(e.end_ns), e.name)
                          for e in lines.get(MODULE_LINE, []))
            ops = []
            j = 0
            for e in sorted(lines.get(OP_LINE, []), key=lambda e: e.start_ns):
                while j + 1 < len(runs) and runs[j + 1][0] <= e.start_ns:
                    j += 1
                module = runs[j][2] if runs and runs[j][0] <= e.start_ns \
                    < runs[j][1] else ""
                ops.append(op(e.name, module, e))
            if ops:
                planes[plane.name] = ops
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(Span(e.name, int(e.start_ns),
                                      int(e.end_ns)))
                elif e.duration_ns > 0:
                    st = dict(e.stats)
                    if _stat(st, "hlo_op"):
                        cpu_ops.append(op(_stat(st, "hlo_op"),
                                          _stat(st, "hlo_module"), e))
    if not planes and cpu_ops:
        planes["/host:CPU"] = cpu_ops
    windows = [s for s in spans if s.name == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW} span in the trace")
    if not planes:
        raise ValueError(f"{path}: no op on any device plane")
    w = (windows[0].start, windows[0].end)
    devices = {}
    for name, ops in sorted(planes.items()):
        devices[name] = sorted(
            (dataclasses.replace(o, start=max(o.start, w[0]),
                                 end=min(o.end, w[1]))
             for o in ops if o.end > w[0] and o.start < w[1]),
            key=lambda o: (o.start, o.end))
    return Trace(w, devices, sorted(spans, key=lambda s: s.start))


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Sequence[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def minus(a: Sequence[Tuple[int, int]],
          b: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union ``a`` less union ``b`` (both already merged and sorted)."""
    out: List[Tuple[int, int]] = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy(ops: Sequence[Op]) -> List[Tuple[int, int]]:
    return union([(o.start, o.end) for o in ops])


def busy_s(trace: Trace) -> float:
    """Seconds in which some op ran, mean over the devices."""
    return sum(length(busy(ops)) for ops in trace.devices.values()) \
        * 1e-9 / len(trace.devices)


def collective_split(ops: Sequence[Op]) -> Tuple[int, int]:
    """``(collective ns, exposed collective ns)`` of one device: the union
    of its collective ops, and the part of it in which no other op ran."""
    coll = busy([o for o in ops if o.collective])
    other = busy([o for o in ops if not o.collective])
    return length(coll), length(minus(coll, other))


def scoped_ns(ops: Sequence[Op], prefix: str) -> int:
    """Union of the ops whose stage node id starts with ``prefix``
    (``local_fft`` matches ``slab/local_fft:1``)."""
    return length(busy([o for o in ops if o.scope
                        and o.scope.split("/", 1)[-1].startswith(prefix)]))


def open_span(trace: Trace, t: int) -> str:
    """The innermost benchmark host span open at ``t`` (``bench/window``
    when only the window is)."""
    best: Optional[Span] = None
    for s in trace.spans:
        if s.start > t:
            break
        if s.end > t and (best is None or s.start >= best.start):
            best = s
    return best.name if best else "none"


def breakdown(trace: Trace, top: int = 10) -> Dict[str, list]:
    """The device ops that took most time (by HLO name and stage scope),
    and the idle time by the host span open at the start of each gap;
    seconds are means over the devices."""
    nd = len(trace.devices)
    per_op: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for ops in trace.devices.values():
        for o in ops:
            key = f"{o.name} [{o.scope or '-'}]"
            per_op[key] = per_op.get(key, 0.0) + (o.end - o.start) * 1e-9 / nd
        idle = minus([trace.window], busy(ops))
        for a, b in idle:
            k = open_span(trace, a)
            gaps[k] = gaps.get(k, 0.0) + (b - a) * 1e-9 / nd
    rank = lambda d: [[k, v] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(per_op), "idle_gaps": rank(gaps)}
