"""Bytes each chip sends per step (``work.py``, from the plan graph's
exchange edges) over the device time of its collective ops per step
times the ICI peak, in %. Collectives are told by HLO opcode, so every
rendering counts, GSPMD-owned ones included."""

from benchmark import trace


def read(ctx):
    if ctx.trace is None or ctx.work.ici_bytes <= 0:
        return None
    devs = ctx.trace.devices.values()
    ns = sum(trace.collective_split(ops)[0] for ops in devs) / len(devs)
    if ns <= 0:
        return None
    per_step_s = ns * 1e-9 / ctx.steps
    return ctx.work.ici_bytes / (per_step_s * ctx.peaks["ici_bytes_per_s"]) \
        * 100.0
