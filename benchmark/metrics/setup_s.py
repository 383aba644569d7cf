"""Process start to the first timed step: imports, backend, plan, inputs,
compilation or compile-cache loads, one warm step per input."""


def read(ctx):
    return ctx.setup_s
