"""Declarative plan/HLO contracts: what each rendering's compiled program
MUST look like, checked without executing anything.

A **contract** is resolved per combo (family x rendering x direction x
wire x guards) from two declarative sources:

* the family's exchange declaration (``models/{slab,pencil,batched2d}.py``
  register an ``exchanges(plan, direction, dims)`` function next to the
  family) — one ``ExchangeDecl`` per global exchange the direction
  stages: its payload shape, participating axis size, and rendering;
* the rendering algebra in this module — how each exchange rendering
  contributes to the expected collective census:

  ============  =========================================================
  rendering     census contribution
  ============  =========================================================
  ``a2a``       exactly 1 ``all-to-all`` (sync or async-start form)
  ``streams``   exactly K ``all-to-all``\\ s (the chunked piece chains)
  ``a2a_pipe``  exactly K ``all-to-all``\\ s (the software-pipelined
                monolithic exchange; same K-instance pin as streams —
                a GSPMD re-fuse back into one collective fails it)
  ``ring``      >= (P-1) x S ``collective-permute``\\ s (S = sub-block
                split), 0 ``all-to-all``\\ s — the un-fusable
                split-exchange signature (OVERLAP.md)
  ``p2p``       GSPMD owns the schedule: >= 1 collective, exact counts
                unpinnable across backends (every exact rule degrades to
                a lower bound when a GSPMD exchange is present)
  ============  =========================================================

Cross-cutting rules resolved from plan state:

* **forbidden ops** — a native-wire program is bf16-FREE (the structural
  form of bit-identity); a plan with no exchanges (single-device
  reference path, batch sharding) carries ZERO exchange collectives, and
  zero all-reduces when guards are off;
* **payload reconciliation** — the staged module's summed exchange bytes
  equal the prediction from ``wire_nbytes`` over the declared payload
  shapes (ring exchanges carry the exact ``(P-1)/P`` discount: the local
  block never travels). Skipped when GSPMD stages no explicit collective.

``verify_plan`` is the one-call API: build the contract for a live plan,
compile both module views, return the violations (empty = verified).
Each violation names its contract and rule, so a failing gate says WHICH
invariant broke, not just that a count changed.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import hloscan

# Rendering keys of a single exchange (``ExchangeDecl.rendering``).
# "ring_overlap" is the revolving-buffer ring schedule (SendMethod.
# RING_OVERLAP at any overlap depth, with or without the fused wire
# kernels): same census algebra and (P-1)/P payload discount as "ring" —
# the permutes must stay distinct and un-fusable whichever schedule
# issued them, which is exactly the pin that stops GSPMD from
# serializing the overlap back. "a2a_pipe" is the software-pipelined
# monolithic exchange (ALL2ALL + SYNC/MPI_TYPE with overlap_subblocks >
# 1, ``transpose.pipelined_all_to_all``): K chunked all-to-alls like
# "streams", pinned to exactly K so a GSPMD re-fuse back into one
# collective fails the census.
RENDERINGS = ("a2a", "streams", "a2a_pipe", "ring", "ring_overlap", "p2p")

# The renderings that stage a ppermute ring (shared by the census and
# payload resolution below).
_RING_RENDERINGS = ("ring", "ring_overlap")


@dataclasses.dataclass(frozen=True)
class ExchangeDecl:
    """One global exchange a plan direction stages: the declarative unit
    the family modules register (``label`` names it in diagnostics;
    ``payload_shape`` is the GLOBAL padded payload; ``axis_size`` the
    participating mesh-axis extent; ``chunks`` the resolved STREAMS /
    a2a_pipe piece count, 1 otherwise; ``subblocks`` the resolved ring
    sub-block split — each peer step becomes ``subblocks`` distinct
    permutes, so the census scales with it)."""

    label: str
    payload_shape: Tuple[int, ...]
    axis_size: int
    rendering: str
    chunks: int = 1
    subblocks: int = 1

    def __post_init__(self) -> None:
        if self.rendering not in RENDERINGS:
            raise ValueError(
                f"rendering must be one of {RENDERINGS}, "
                f"got {self.rendering!r}")
        if self.subblocks < 1:
            raise ValueError(
                f"subblocks must be >= 1, got {self.subblocks}")


@dataclasses.dataclass(frozen=True)
class Rule:
    """One resolved check. ``kind``:

    * ``census``  — ``combined count of ``op`` <cmp> value`` on the
      compiled module (sync + async-start forms summed, the TPU-portable
      count the tier-1 gates always used);
    * ``forbid``  — substring ``op`` absent from the compiled text;
    * ``payload`` — staged exchange bytes == value (global convention).
    """

    kind: str
    op: str
    cmp: str = "=="
    value: int = 0
    why: str = ""

    def describe(self) -> str:
        if self.kind == "forbid":
            return f"forbid {self.op!r} in compiled HLO"
        if self.kind == "payload":
            return f"staged exchange payload == {self.value} B"
        return f"census {self.op} {self.cmp} {self.value}"


@dataclasses.dataclass(frozen=True)
class Contract:
    """A fully-resolved combo contract: ``name`` is
    ``<family>/<rendering-summary>`` and lands verbatim in diagnostics."""

    name: str
    family: str
    direction: str
    wire: str
    guards: str
    exchanges: Tuple[ExchangeDecl, ...]
    rules: Tuple[Rule, ...]


@dataclasses.dataclass(frozen=True)
class ContractViolation:
    """One broken rule, carrying enough to act on: the contract name (the
    diagnostic the mutation tests assert on), the rule, and what the
    module actually contained."""

    contract: str
    rule: Rule
    got: Any

    def __str__(self) -> str:
        return (f"[{self.contract}] violated: {self.rule.describe()} "
                f"(got {self.got})"
                + (f" — {self.rule.why}" if self.rule.why else ""))


# ---------------------------------------------------------------------------
# family registry (populated by the model modules at import)
# ---------------------------------------------------------------------------

_FAMILIES: Dict[str, Callable[..., Tuple[ExchangeDecl, ...]]] = {}
_FAMILY_OF_CLASS: Dict[str, str] = {}


def register_family(family: str, plan_class_name: str,
                    exchanges: Callable[..., Tuple[ExchangeDecl, ...]]
                    ) -> None:
    """Called by each model module, next to the family it declares:
    ``exchanges(plan, direction, dims)`` returns the direction's
    ``ExchangeDecl`` tuple."""
    _FAMILIES[family] = exchanges
    _FAMILY_OF_CLASS[plan_class_name] = family


def family_of(plan: Any) -> str:
    name = type(plan).__name__
    fam = _FAMILY_OF_CLASS.get(name)
    if fam is None:
        raise KeyError(
            f"no contract family registered for plan class {name!r} "
            f"(known: {sorted(_FAMILY_OF_CLASS)})")
    return fam


def scope_family(plan: Any) -> str:
    """The family key a plan's stage scopes are named under
    (``dfft/<family>/<node-id>``; ``obs/profile.py``): the registered
    contract family, falling back to the class name for plan types the
    registry does not know. The ONE resolution both the models' scope
    emission and the guard layer use, so scope names can never disagree
    between emitters."""
    try:
        return family_of(plan)
    except KeyError:
        return type(plan).__name__.lower()


def rendering_name(config: Any, second: bool = False) -> str:
    """The rendering key one transpose resolves to from a (concrete)
    Config — the same classification ``dfft-explain`` prints."""
    from .. import params as pm

    comm = config.resolved_comm2() if second else config.comm_method
    send = config.resolved_snd2() if second else config.send_method
    if send is pm.SendMethod.RING_OVERLAP:
        return "ring_overlap"
    if send is pm.SendMethod.RING:
        return "ring"
    if send is pm.SendMethod.STREAMS:
        # GSPMD re-fuses the piece reshards into ONE collective
        # (OVERLAP.md): structurally the p2p contract applies.
        return "p2p" if comm is pm.CommMethod.PEER2PEER else "streams"
    if comm is pm.CommMethod.PEER2PEER:
        return "p2p"
    if config.resolved_overlap_subblocks() > 1:
        # ALL2ALL + SYNC/MPI_TYPE with a sub-block split: the
        # software-pipelined monolithic exchange.
        return "a2a_pipe"
    return "a2a"


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------

def _complex_dtype(plan: Any) -> Any:
    import numpy as np

    return np.complex128 if plan.config.double_prec else np.complex64


def contract_for(plan: Any, direction: str = "forward",
                 dims: int = 3) -> Contract:
    """Resolve the declarative contract for one direction of a live plan."""
    family = family_of(plan)
    decls = tuple(_FAMILIES[family](plan, direction, dims))
    cfg = plan.config
    return contract_from_decls(family, direction, cfg.wire_dtype,
                               getattr(plan, "_guard_mode", "off"),
                               _complex_dtype(plan), decls)


def contract_from_decls(family: str, direction: str, wire: str,
                        guards: str, complex_dtype: Any,
                        decls: Tuple[ExchangeDecl, ...]) -> Contract:
    """The rendering algebra over an explicit declaration set — the
    resolution core of ``contract_for``, factored out so a contract can
    be synthesized from ANY declaration source (``plangraph`` derives
    one from a declared stage graph, proving the graph's exchanges
    against the same compiled census the family contract pins)."""
    cdt = complex_dtype

    n_a2a = 0          # deterministic all-to-all instances
    ring_steps = 0     # minimum collective-permute instances
    n_gspmd = 0        # exchanges whose schedule GSPMD owns
    payload = 0        # staged bytes of the deterministic exchanges
    for d in decls:
        if d.rendering == "a2a":
            n_a2a += 1
        elif d.rendering in ("streams", "a2a_pipe"):
            n_a2a += max(1, d.chunks)
        elif d.rendering in _RING_RENDERINGS:
            # Each peer step travels as ``subblocks`` distinct permutes
            # (the block-granularity micro-steps).
            ring_steps += max(0, d.axis_size - 1) * max(1, d.subblocks)
        else:
            n_gspmd += 1
        if d.rendering != "p2p":
            payload += hloscan.predicted_payload_bytes(
                d.payload_shape, cdt, wire,
                ring_size=(d.axis_size
                           if d.rendering in _RING_RENDERINGS else 0))

    rules: List[Rule] = []
    summary = "+".join(sorted({d.rendering for d in decls})) or "none"
    name = f"{family}/{summary}"
    if not decls:
        # The no-exchange contract: the single-device reference path and
        # batch sharding issue ZERO collectives (and zero all-reduces
        # until guards add their scalar reduction).
        for op in ("all_to_all", "collective_permute", "all_gather",
                   "reduce_scatter"):
            rules.append(Rule("census", op, "==", 0,
                              why="no-exchange path must stay "
                                  "collective-free"))
        if guards == "off":
            rules.append(Rule("census", "all_reduce", "==", 0,
                              why="guards off: nothing may reduce"))
    elif n_gspmd == 0:
        rules.append(Rule("census", "all_to_all", "==", n_a2a,
                          why="monolithic exchanges: one collective each; "
                              "STREAMS/a2a_pipe: one per chunk"))
        if ring_steps:
            rules.append(Rule("census", "collective_permute", ">=",
                              ring_steps,
                              why="ring steps must stay distinct "
                                  "(un-fusable) permutes"))
        else:
            rules.append(Rule("census", "collective_permute", "==", 0,
                              why="no ring declared: a permute would be "
                                  "a rendering regression"))
        rules.append(Rule("payload", "exchange", "==", payload,
                          why="staged wire bytes must reconcile with "
                              "wire_nbytes over the declared payloads"))
    else:
        # GSPMD owns part of the schedule: exact pins degrade to lower
        # bounds, plus "every boundary emits at least one collective".
        if n_a2a:
            rules.append(Rule("census", "all_to_all", ">=", n_a2a,
                              why="explicit exchanges survive GSPMD"))
        if ring_steps:
            rules.append(Rule("census", "collective_permute", ">=",
                              ring_steps,
                              why="ring steps must stay distinct "
                                  "(un-fusable) permutes"))
        rules.append(Rule("census", "exchange_total", ">=",
                          n_a2a + ring_steps + n_gspmd,
                          why="each GSPMD boundary reshards through at "
                              "least one collective"))
    if wire == "native":
        rules.append(Rule("forbid", "bf16",
                          why="native wire is structurally bf16-free, "
                              "not merely numerically close"))
    return Contract(name=name, family=family, direction=direction,
                    wire=wire, guards=guards, exchanges=decls,
                    rules=tuple(rules))


# ---------------------------------------------------------------------------
# checking
# ---------------------------------------------------------------------------

def _combined(census: Dict[str, int], op: str) -> int:
    """Sync + async-start instance count of one census op (or the
    combined exchange total)."""
    if op == "exchange_total":
        return sum(_combined(census, o)
                   for o in ("all_to_all", "collective_permute",
                             "all_gather", "reduce_scatter"))
    return census.get(op, 0) + census.get(f"{op}_start", 0)


def _cmp(cmp: str, got: int, want: int) -> bool:
    if cmp == "==":
        return got == want
    if cmp == ">=":
        return got >= want
    if cmp == "<=":
        return got <= want
    raise ValueError(f"unknown comparison {cmp!r}")


def check_contract(contract: Contract, census: Dict[str, int],
                   compiled_txt: str,
                   staged_total: Optional[int]) -> List[ContractViolation]:
    """Check one resolved contract against the module facts; returns the
    violations (empty = the combo verifies)."""
    out: List[ContractViolation] = []
    for rule in contract.rules:
        if rule.kind == "census":
            got = _combined(census, rule.op)
            if not _cmp(rule.cmp, got, rule.value):
                out.append(ContractViolation(contract.name, rule, got))
        elif rule.kind == "forbid":
            if hloscan.contains_op(compiled_txt, rule.op):
                out.append(ContractViolation(contract.name, rule,
                                             f"{rule.op!r} present"))
        elif rule.kind == "payload":
            if staged_total is None:
                # GSPMD staged no explicit collective; nothing to
                # reconcile (the census rules still apply).
                continue
            if staged_total != rule.value:
                out.append(ContractViolation(contract.name, rule,
                                             f"{staged_total} B"))
        else:  # pragma: no cover - Rule kinds are closed above
            raise ValueError(f"unknown rule kind {rule.kind!r}")
    return out


def verify_plan(plan: Any, direction: str = "forward", dims: int = 3,
                contract: Optional[Contract] = None
                ) -> List[ContractViolation]:
    """Lower + compile one direction of a live plan and check it against
    its (or an explicitly supplied) contract. The one-call API the test
    gates and ``dfft-verify`` share — and the census lands in the
    ``hlo.*`` obs gauges as a side effect, like every census."""
    contract = contract or contract_for(plan, direction, dims)
    txt = hloscan.compiled_text(plan, direction, dims)
    census = hloscan.collective_census(txt)
    staged = None
    if any(r.kind == "payload" for r in contract.rules):
        staged = hloscan.staged_exchange_total(plan, direction, dims)
    return check_contract(contract, census, txt, staged)
