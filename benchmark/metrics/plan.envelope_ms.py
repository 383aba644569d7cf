"""Mean self time of the window's ``plan.exec`` spans outside their
``plan.dispatch`` child: the library's own host work around the jitted
program's call (guards, the fallback ladder), not JAX's dispatch."""

from benchmark import program


def read(ctx):
    spans = program.window_exec_spans(ctx)
    if spans is None:
        return None
    return sum(e - d for e, d in spans) / len(spans)
