"""HLO scanning: lower/compile plan programs (never execute) and extract
the structural facts the contracts check.

Two distinct module views, used deliberately:

* the COMPILED module (``compiled_text``) — what the backend will run,
  post-GSPMD, post-fusion. The collective census runs here: "the ring's
  P-1 permutes were not re-fused" is a statement about the optimized
  program (the STREAMS chunked reshards WERE re-fused — OVERLAP.md).
* the STAGED module (``staged_text``) — the pre-optimization lowering,
  the program as the wire layer wrote it. Exchange payload bytes are
  reconciled here: the CPU backend is free to hoist a bf16 decode past a
  collective it knows is local (observed), which changes the optimized
  payload without changing what the wire layer staged — and on TPU, what
  is staged is what crosses the ICI. A wire-layer regression (encode not
  applied, payload doubled) shows up in the staged module on every
  backend.

Fingerprints (``op_graph_fingerprint``) hash the compiled text with
``metadata={...}`` attributes stripped: op metadata carries source file
and line numbers, which shift under pure refactors — the op graph is the
invariant. Byte-identity pins (obs on/off, fault spec set/unset,
guards="off") compare these.
"""

from __future__ import annotations

import hashlib
import math
import re
from typing import Any, Dict, List, Optional, Tuple

# Exchange collectives and their async start forms, as (census key, HLO op
# mnemonic) pairs. Counted as op INSTANCES — "<op>(" with the opening
# paren — so "all-to-all(" does not match the async "all-to-all-start("
# form and vice versa.
CENSUS_FORMS: Tuple[Tuple[str, str], ...] = (
    ("all_to_all", "all-to-all"),
    ("all_to_all_start", "all-to-all-start"),
    ("collective_permute", "collective-permute"),
    ("collective_permute_start", "collective-permute-start"),
    ("all_reduce", "all-reduce"),
    ("all_reduce_start", "all-reduce-start"),
    ("all_gather", "all-gather"),
    ("all_gather_start", "all-gather-start"),
    ("reduce_scatter", "reduce-scatter"),
    ("reduce_scatter_start", "reduce-scatter-start"),
)

# The ops that move an exchange payload (census keys); all_reduce and
# friends are counted but never payload-checked (guards legitimately fold
# a scalar all-reduce into their reduction under GSPMD).
EXCHANGE_OPS: Tuple[str, ...] = (
    "all_to_all", "all_to_all_start",
    "collective_permute", "collective_permute_start",
)

_HLO_SHAPE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_MLIR_SHAPE = re.compile(r"tensor<([0-9x]*)x?((?:complex<)?[a-z][a-z0-9]*>?)>")
_METADATA = re.compile(r",?\s*metadata=\{[^{}]*\}")
_MODULE_NAME = re.compile(r"^HloModule\s+\S+", re.MULTILINE)
# Source-location tables the compiler prints after the module header
# (one numbered row per line, ended by a blank line).
_LOCATION_TABLES = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
    re.MULTILINE)

_DTYPE_BYTES: Dict[str, int] = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    # MLIR spellings (StableHLO fallback when the HLO dialect is gone)
    "i1": 1, "i8": 1, "i16": 2, "i32": 4, "i64": 8,
    "complex<f32": 8, "complex<f64": 16, "complex<f32>": 8,
    "complex<f64>": 16,
}


# ---------------------------------------------------------------------------
# lowering (never executing)
# ---------------------------------------------------------------------------

def _input_aval(plan: Any, direction: str, dims: int = 3) -> Any:
    """The ShapeDtypeStruct the direction's builder is lowered against —
    exactly what the exec_* path feeds it (padded global shape)."""
    import jax
    import numpy as np

    dp = bool(plan.config.double_prec)
    cdt = np.complex128 if dp else np.complex64
    if direction == "forward":
        shape = tuple(plan.input_padded_shape)
        c2c = getattr(plan, "transform", "r2c") == "c2c"
        dt = cdt if c2c else (np.float64 if dp else np.float32)
    elif direction == "inverse":
        # Pencil plans shape their spectral input per partial-transform
        # depth; the other families have one padded spectral shape.
        getter = getattr(plan, "output_padded_shape_for", None)
        shape = tuple(getter(dims)) if getter is not None \
            else tuple(plan.output_padded_shape)
        dt = cdt
    else:
        raise ValueError(f"direction must be 'forward'|'inverse', "
                         f"got {direction!r}")
    return jax.ShapeDtypeStruct(shape, dt)


def _builder(plan: Any, direction: str, dims: int = 3) -> Any:
    """The direction's jitted builder across the three families
    (duck-typed on the family-specific builder names)."""
    fwd = direction == "forward"
    if hasattr(plan, "_build_r2c_d"):                   # pencil
        return plan._build_r2c_d(dims) if fwd else plan._build_c2r_d(dims)
    if hasattr(plan, "_build"):                         # batched2d
        return plan._build(forward=fwd)
    return plan._build_r2c() if fwd else plan._build_c2r()


def lower_plan(plan: Any, direction: str = "forward",
               dims: int = 3) -> Any:
    """Lower one direction of a plan (slab / pencil / batched2d) against
    its padded input aval — the compile-only entry every scan shares."""
    return _builder(plan, direction, dims).lower(
        _input_aval(plan, direction, dims))


def compiled_text(plan: Any, direction: str = "forward",
                  dims: int = 3) -> str:
    """Optimized (post-SPMD, post-fusion) module text of one direction."""
    return lower_plan(plan, direction, dims).compile().as_text()


def staged_text(plan: Any, direction: str = "forward",
                dims: int = 3) -> Tuple[str, str]:
    """Pre-optimization module text: ``(dialect, text)`` where dialect is
    ``"hlo"`` or (when this jax no longer exposes the HLO translation)
    ``"stablehlo"`` — the payload parser understands both."""
    lowered = lower_plan(plan, direction, dims)
    try:
        ir = lowered.compiler_ir("hlo")
        if ir is not None:
            return "hlo", ir.as_hlo_text()
    except (KeyError, ValueError, NotImplementedError, AttributeError):
        pass
    return "stablehlo", lowered.as_text()


# ---------------------------------------------------------------------------
# census
# ---------------------------------------------------------------------------

def collective_census(hlo: Any) -> Dict[str, int]:
    """Instance counts of the exchange collectives (and their async start
    forms) plus ``convert`` ops in a compiled module — the overlap/
    compression detector (``eval/benchmarks/cpumesh8/OVERLAP.md``).
    Accepts a compiled executable or raw HLO text. The counts are
    mirrored into the obs registry as ``hlo.*`` gauges (last census
    wins), so any caller's census lands in the metrics snapshot."""
    from .. import obs

    txt = hlo if isinstance(hlo, str) else hlo.as_text()
    out = {name: txt.count(f" {op}(") for name, op in CENSUS_FORMS}
    out["async_total"] = (out["all_to_all_start"]
                          + out["collective_permute_start"])
    out["convert"] = txt.count(" convert(")
    for name, v in out.items():
        obs.metrics.gauge(f"hlo.{name}", v)
    return out


def contains_op(txt: str, token: str) -> bool:
    """Whether a module's op graph mentions ``token`` — the text with its
    metadata and source-location tables stripped, so a file or function
    name never counts."""
    return token in strip_metadata(txt)


def contains_bf16(txt: str) -> bool:
    """Whether a module's op graph mentions bf16 anywhere — the
    structural pin behind the native wire's bit-identity (a native-wire
    program is bf16-FREE, not merely numerically indistinguishable)."""
    return contains_op(txt, "bf16")


# ---------------------------------------------------------------------------
# exchange payloads
# ---------------------------------------------------------------------------

def _hlo_line_bytes(line: str, mnemonic: str) -> int:
    """Byte size of the result of one HLO op line (sum over tuple
    elements — the CPU backend lowers a tiled all-to-all in tuple form,
    one operand per participant, which together make up the shard)."""
    lhs = line.split(f" {mnemonic}(")[0]
    if " = " in lhs:
        lhs = lhs.split(" = ", 1)[1]
    total = 0
    for dt, dims in _HLO_SHAPE.findall(lhs):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _mlir_result_bytes(line: str) -> int:
    """Byte size of the RESULT type(s) on a StableHLO op line — summed
    over tuple elements, mirroring the HLO branch: a tiled all-to-all can
    stage in tuple form (one operand/result per participant), and its
    payload is the sum, not the last element."""
    # The result type(s) follow the last "->" of the op's type
    # annotation; without one (older syntax) fall back to the last
    # tensor<> on the line.
    if "->" in line:
        shapes = _MLIR_SHAPE.findall(line.rsplit("->", 1)[1])
    else:
        shapes = _MLIR_SHAPE.findall(line)[-1:]
    total = 0
    for dims, dt in shapes:
        n = 1
        for d in dims.split("x"):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 0)
    return total


def exchange_payload_bytes(dialect: str, txt: str) -> Dict[str, List[int]]:
    """Per-op payload bytes (PER PARTICIPATING DEVICE) of every exchange
    collective in a staged module: ``{"all_to_all": [...],
    "collective_permute": [...]}``, one entry per op instance in module
    order. Multiply by the mesh size for global wire bytes (the
    convention ``wire_nbytes``/``wire_bytes_per_transpose`` report)."""
    out: Dict[str, List[int]] = {"all_to_all": [], "collective_permute": []}
    if dialect == "hlo":
        for line in txt.splitlines():
            for key, mnemonic in (("all_to_all", "all-to-all"),
                                  ("collective_permute",
                                   "collective-permute")):
                if f" {mnemonic}(" in line:
                    out[key].append(_hlo_line_bytes(line, mnemonic))
    else:
        for line in txt.splitlines():
            if "stablehlo.all_to_all" in line:
                out["all_to_all"].append(_mlir_result_bytes(line))
            elif "stablehlo.collective_permute" in line:
                out["collective_permute"].append(_mlir_result_bytes(line))
    return out


def predicted_payload_bytes(shape: Any, dtype: Any, wire: str,
                            ring_size: int = 0) -> int:
    """GLOBAL wire bytes one exchange of ``shape``/``dtype`` moves under
    the wire encoding — ``wire_nbytes`` with the ring discount applied:
    a ring of ``ring_size`` ranks never sends the local block, so its
    P-1 permute steps together carry ``(P-1)/P`` of the payload. The
    monolithic collectives (``ring_size=0``) carry it whole (the tiled
    all-to-all's local->local block stays in the accounting, matching
    ``wire_bytes_per_transpose``)."""
    from ..parallel.transpose import wire_nbytes

    nb = wire_nbytes(shape, dtype, wire)
    if ring_size > 1:
        # The discount divides exactly: every ring payload is padded to
        # ring_size blocks before the steps are staged.
        return nb * (ring_size - 1) // ring_size
    return nb


def staged_exchange_total(plan: Any, direction: str = "forward",
                          dims: int = 3) -> Optional[int]:
    """GLOBAL staged exchange bytes of one direction: per-device payload
    sum x mesh size. None when the staged module carries no explicit
    exchange (GSPMD renderings stage sharding constraints, not
    collectives — the partitioner picks those later)."""
    dialect, txt = staged_text(plan, direction, dims)
    per_dev = exchange_payload_bytes(dialect, txt)
    ops = per_dev["all_to_all"] + per_dev["collective_permute"]
    if not ops:
        return None
    mesh = getattr(plan, "mesh", None)
    size = math.prod(mesh.devices.shape) if mesh is not None else 1
    return sum(ops) * size


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def strip_metadata(txt: str) -> str:
    """Compiled module text with op ``metadata={...}`` (source file/line),
    the source-location tables and the module name dropped — the op
    graph, stable across pure refactors that only move code."""
    txt = _LOCATION_TABLES.sub("", _METADATA.sub("", txt))
    return _MODULE_NAME.sub("HloModule _", txt)


def op_graph_fingerprint(txt: str) -> str:
    """sha256 of the metadata-stripped module text — the byte-identity
    currency of the zero-overhead-off pins (obs on/off, fault spec
    set/unset, guards="off" vs never-guarded)."""
    return hashlib.sha256(strip_metadata(txt).encode()).hexdigest()


def plan_fingerprint(plan: Any, direction: str = "forward",
                     dims: int = 3) -> str:
    """``op_graph_fingerprint`` of one direction's compiled module."""
    return op_graph_fingerprint(compiled_text(plan, direction, dims))
