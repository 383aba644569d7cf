"""Mean host time of an ``exec_*`` call until it returns, before any
block: the plan's dispatch cost (the benchmark's own span)."""


def read(ctx):
    if not ctx.call_s:
        return None
    return sum(ctx.call_s) / len(ctx.call_s) * 1e3
