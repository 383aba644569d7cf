"""What the program reports of itself, for the per-layer metrics that
read it: its per-axis local-FFT sub-scopes on the device trace
(``dfft/<family>/<node>.ax<k>``, ``obs/profile.axis_scope``) and its
``plan.exec``/``plan.dispatch`` spans in the flight-recorder ring
(``obs/flightrec.py``, fed by ``resilience/fallback.execute``). A program
without them (an older one) gives None here, never an error.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

from benchmark import trace


def per_step_ms(ctx, keep: Callable[[trace.Op], bool]) -> Optional[float]:
    """Union of the device time of the ops ``keep`` selects, per step and
    chip (the mean over the devices), in ms; None without a trace."""
    if ctx.trace is None:
        return None
    devs = ctx.trace.devices.values()
    ns = sum(trace.length(trace.busy([o for o in ops if keep(o)]))
             for ops in devs) / len(devs)
    return ns * 1e-6 / ctx.steps


def axis_ms(ctx, axis: int) -> Optional[float]:
    """``per_step_ms`` of the ops whose stage node ends in ``.ax<axis>``:
    one array axis of the local FFT, forward and inverse together; None
    where no op carries such a scope."""
    tag = f".ax{axis}"
    ms = per_step_ms(ctx, lambda o: bool(o.scope and o.scope.endswith(tag)))
    return ms if ms else None


def exec_spans() -> List[Tuple[float, float]]:
    """``(exec ms, dispatch ms)`` of each ``plan.exec`` span in the ring,
    oldest first: its duration and that of its ``plan.dispatch``
    children (more than one where the fallback ladder retried). A span
    whose children the full ring dropped is left out."""
    from distributedfft_tpu.obs import flightrec

    out: List[Tuple[float, float]] = []
    children: List[dict] = []  # dispatch spans since the last exec span
    for r in flightrec.snapshot():
        if r.get("ev") != "span":
            continue
        if r["name"] == "plan.dispatch" and r.get("parent") == "plan.exec":
            children.append(r)
        elif r["name"] == "plan.exec":
            mine = [c["dur_ms"] for c in children if c["seq"] > r["seq"]]
            if mine:
                out.append((r["dur_ms"], sum(mine)))
            children = []
    return out


def window_exec_spans(ctx) -> Optional[List[Tuple[float, float]]]:
    """The window's ``exec_spans``: the last ``len(ctx.call_s)`` of them,
    since no ``exec_*`` call follows the window. Where the bounded ring
    dropped the window's first calls (a window of more than about a
    thousand calls), the ones it holds, all of the window's last. None
    where it holds none."""
    spans = exec_spans()
    n = len(ctx.call_s)
    if not n or not spans:
        return None
    return spans[-n:]
