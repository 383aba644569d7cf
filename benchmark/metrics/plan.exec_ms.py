"""Mean host time of the window's ``exec_*`` calls as the program's own
``plan.exec`` span reads it (the envelope every ``exec_*`` passes,
``resilience/fallback.execute``), from the flight-recorder ring."""

from benchmark import program


def read(ctx):
    spans = program.window_exec_spans(ctx)
    if spans is None:
        return None
    return sum(e for e, _ in spans) / len(spans)
