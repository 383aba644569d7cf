"""One run of one benchmark cell, driven by data.

Everything a cell uses is found by name, so a new cell adds files and
entries and edits none:

- the cell: ``BENCHMARK.json`` ``workloads``;
- its configuration: ``benchmark/configs/<config>.json``, whose ``plan``
  names the plan kind, ``benchmark/plans/<plan>.py`` (``build``);
- its traffic mix: ``benchmark/traffic/<mix>.json``, whose ``loop`` names
  ``benchmark/loops/<loop>.py`` (``validate``, ``window``) and whose
  ``calls`` name ``benchmark/calls/<call>.py`` each (``load.py``);
- each metric: ``benchmark/metrics/<metric>.py``, a ``read(ctx)`` that
  returns a number, or None where it finds nothing to read.

A run: set-up (compile cache, plan, inputs made on the device from the
seed, one warm step per input), then the loop's window; a step drawn from
the seed has what of its answers is compared reduced on the device as it
finishes. After the window: the peak memory, the trace reduction
(``--trace 1``), and the comparison of the sampled answers with the
float64 reference once the program's arrays are freed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib.util
import json
import os
import random
import re
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_PARTS: Dict[str, Any] = {}


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def part(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, loaded once (metric names hold
    dots, so files are loaded by path, not imported by module name)."""
    if not _NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    key = f"{kind}/{name}"
    if key not in _PARTS:
        spec = importlib.util.spec_from_file_location(
            "benchmark_" + re.sub(r"\W", "_", key),
            os.path.join(BENCH, kind, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _PARTS[key] = mod
    return _PARTS[key]


def _data(kind: str, name: str) -> Dict[str, Any]:
    if not _NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    metrics: List[Dict[str, Any]]    # end_to_end entries of this cell
    per_layer: List[Dict[str, Any]]  # per_layer entries of this cell


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_json: str = os.path.join(ROOT, "BENCHMARK.json")
              ) -> Cell:
    with open(bench_json) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} (known: {sorted(cells)})")
    w = cells[name]
    return Cell(name, int(w["chips"]), _data("configs", w["config"]),
                _data("traffic", w["traffic"]),
                [m for m in spec["end_to_end"] if _reports(m, name)],
                [m for m in spec["per_layer"] if _reports(m, name)])


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    steps: int
    window_s: float                  # host clock, first dispatch to last block
    setup_s: float
    peak_bytes: int                  # fullest chip
    call_s: List[float]              # host time of each call until it returns
    work: Any                        # work.Work, per chip and step
    peaks: Dict[str, float]
    trace: Any = None                # trace.Trace of the traced run
    stats: Dict[str, Any] = dataclasses.field(default_factory=dict)  # loop's


def process_age_s() -> float:
    """Seconds since this process started (``/proc``, 10 ms ticks)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start_ticks / os.sysconf("SC_CLK_TCK")


def check_devices(devices, chips: int) -> None:
    """A TPU, with as many chips as the cell asks for, or no run."""
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")


_COMPILES: List[str] = []
_IN_WINDOW = [False]
_LISTENING: List[bool] = []


def _on_event(event: str, *_a, **_k) -> None:
    if _IN_WINDOW[0] and event.startswith("/jax/core/compile/"):
        _COMPILES.append(event)


def _to_host(tree, memo: Dict[int, Any]):
    """``tree`` with its device arrays on the host, each array copied once
    however often it appears."""
    if isinstance(tree, dict):
        return {k: _to_host(v, memo) for k, v in tree.items()}
    if isinstance(tree, list):
        return [_to_host(v, memo) for v in tree]
    if id(tree) not in memo:
        memo[id(tree)] = np.asarray(tree)
    return memo[id(tree)]


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        control: str = "", plans: Optional[Dict[str, Any]] = None,
        keep_trace: str = "", log=sys.stderr) -> Dict[str, Any]:
    """One run. ``plans`` (one process running many seeds, as
    ``controls.py`` does) keeps each control's plan, and so its compiled
    programs, from one run to the next."""
    import jax

    from distributedfft_tpu.parallel.mesh import use_compile_cache

    from . import load, trace as btrace, work as bwork

    loop = part("loops", cell.traffic["loop"])
    loop.validate(cell.traffic)
    names = list(cell.traffic["calls"])
    calls = [part("calls", c) for c in names]
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    d0 = devices[0]
    marks = [("backend", process_age_s())]
    print(f"device platform={d0.platform} kind={d0.device_kind!r} "
          f"count={len(devices)} compile_cache={cache}", file=log, flush=True)
    check_devices(devices, cell.chips)
    peaks = bwork.peaks(d0.device_kind)

    ctl = cell.config.get("controls", {}).get(control, {}) if control else {}
    if control and not ctl:
        raise KeyError(f"no control {control!r} in {cell.config['name']}")
    own = plans is None
    plans = {} if own else plans
    if control not in plans:
        plans[control] = part("plans", cell.config["plan"]).build(
            cell.config, ctl.get("config", {}))
    plan = plans[control]
    marks.append(("plan", process_age_s()))
    annotate = (jax.profiler.TraceAnnotation if trace
                else lambda name: contextlib.nullcontext())
    call_s: List[float] = []
    step = load.step_fn(plan, names, calls, annotate, call_s)
    inputs = load.make_inputs(plan, calls[0], int(cell.traffic["inputs"]),
                              seed)
    marks.append(("inputs", process_age_s()))
    shape = tuple(cell.config["global_size"])
    sel = load.sample_planes(seed, shape[0],
                             int(cell.config.get("check_planes", shape[0])))

    def keep(i, outs):
        """What of each answer is compared, reduced on the device."""
        chain, kept = [("input", inputs[i])], []
        for name, call, out in zip(names, calls, outs):
            kept.append(call.keep(plan, sel, chain, out))
            chain.append((name, out))
        return kept

    for i, x in enumerate(inputs):  # warm every program the window runs
        jax.block_until_ready(keep(i, step(x)))
    marks.append(("warm", process_age_s()))
    call_s.clear()
    step_work = bwork.step_work(plan, calls, cell.chips)

    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _LISTENING.append(True)
    _COMPILES.clear()
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else ""
    if trace:
        jax.profiler.start_trace(tdir)
    rng = random.Random(seed)
    k = int(cell.traffic["sample"])
    sample: List[tuple] = []  # (step, input index, kept), a reservoir

    def done(n, i, outs):
        j = n if len(sample) < k else rng.randrange(n + 1)
        if j < k:
            item = (n, i, keep(i, outs))
            if j < len(sample):
                sample[j] = item
            else:
                sample.append(item)

    setup_s = process_age_s()
    print("setup " + " ".join(f"{m}_at={v:.2f}" for m, v in marks),
          file=log, flush=True)
    _IN_WINDOW[0] = True
    stats = loop.window(step, inputs, seconds, annotate, done)
    _IN_WINDOW[0] = False
    n, window_s = stats["steps"], stats["window_s"]
    if trace:
        jax.profiler.stop_trace()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:cell.chips])  # the CPU reports none
    print(f"window steps={n} seconds={window_s:.6f} setup_s={setup_s:.3f} "
          f"compiles_in_window={len(_COMPILES)} peak_bytes={peak} "
          f"sampled={[s[0] for s in sample]}", file=log, flush=True)

    tr = None
    if trace:
        pbs = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True)
        if keep_trace:
            shutil.copy(pbs[0], keep_trace)
        tr = btrace.load(pbs[0])
        shutil.rmtree(tdir, ignore_errors=True)

    # The kept answers on the host, then the program's arrays freed.
    t_ref = time.perf_counter()
    kept = _to_host([s[2] for s in sample], {})
    d2h_s = time.perf_counter() - t_ref
    del inputs, sample, step, plan, keep, done
    if own:
        plans.clear()
    gc.collect()
    errs: Dict[str, float] = {}
    for per_call in kept:
        for call, kc in zip(calls, per_call):
            errs[call.NAME] = max(errs.get(call.NAME, 0.0),
                                  call.error(sel, shape, kc))
    del kept
    ref_s = time.perf_counter() - t_ref
    print(f"reference d2h_s={d2h_s:.3f} total_s={ref_s:.3f} "
          f"host_cpus={os.cpu_count()}", file=log, flush=True)
    limits = cell.config["limits"]
    checks = {k2: {"value": v, "limit": limits[k2]}
              for k2, v in sorted(errs.items())}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    ctx = Context(n, window_s, setup_s, peak, call_s, step_work, peaks, tr,
                  stats)
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in (cell.per_layer if trace else cell.metrics):
        v = part("metrics", m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result: Dict[str, Any] = {"correct": correct, "attempted": n,
                              "failed": 0, "metrics": metrics,
                              "device": device}
    if tr is not None:
        device["busy_s"] = btrace.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = btrace.breakdown(tr)
    result["compiles_in_window"] = len(_COMPILES)
    result["reference_s"] = ref_s
    result["seed"] = seed
    if control:
        result["control"] = control
    result["checks"] = checks
    return result
