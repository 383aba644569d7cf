"""Time per step: the whole window over the steps completed in it."""


def read(ctx):
    return ctx.window_s / ctx.steps * 1e3
