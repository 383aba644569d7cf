"""Least time of the step's local-FFT work (``work.py``: the larger of
its nominal flops over the bf16 peak and its compulsory bytes over the
HBM bandwidth) over the device time of the ops under the ``local_fft``
stage scopes, per step and chip, in %. The work depends only on shapes,
so the yardstick is the same whichever backend runs the FFT."""

from benchmark import trace


def read(ctx):
    if ctx.trace is None:
        return None
    devs = ctx.trace.devices.values()
    ns = sum(trace.scoped_ns(ops, "local_fft") for ops in devs) / len(devs)
    if ns <= 0:
        return None
    return ctx.work.local_fft_s(ctx.peaks) / (ns * 1e-9 / ctx.steps) * 100.0
