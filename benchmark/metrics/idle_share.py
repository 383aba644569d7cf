"""Share of the traced window in which no op ran on a device: 1 minus
the union of the op intervals of its ``XLA Ops`` line over the
benchmark's window span, as the mean over the cell's devices, in %."""

from benchmark import trace


def read(ctx):
    if ctx.trace is None:
        return None
    return (1.0 - trace.busy_s(ctx.trace) / ctx.trace.window_s) * 100.0
