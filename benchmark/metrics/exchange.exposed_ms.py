"""Collective time per step during which no other op runs on that
device, as the mean over the cell's devices."""

from benchmark import trace


def read(ctx):
    if ctx.trace is None or ctx.work.ici_bytes <= 0:
        return None
    devs = ctx.trace.devices.values()
    ns = sum(trace.collective_split(ops)[1] for ops in devs) / len(devs)
    return ns * 1e-6 / ctx.steps
