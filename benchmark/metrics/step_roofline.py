"""Least time of the whole step (``work.py``: the largest of its flops
over the bf16 peak, its local-FFT bytes over the HBM bandwidth and the
bytes each chip sends over the ICI peak) over the traced time per step,
in %. It bounds every kernel's share: a later change that takes a kernel
off the path, and so silences its roofline, still shows here."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.work.step_s(ctx.peaks) / (ctx.trace.window_s / ctx.steps) \
        * 100.0
