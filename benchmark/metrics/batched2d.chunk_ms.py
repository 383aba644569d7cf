"""Device time per step and chip of the batched 2D plan's chunk loop
itself: the union of the ops whose innermost stage scope is
``dfft/batched2d/chunk:<k>`` (the per-chunk slices, the writes of each
chunk's answer into the stack), forward and inverse together, as the
mean over the cell's devices. The loop's own control op (``while``) is
left out: its interval spans its body, the transform included. None
where no op carries the scope (an unchunked plan, an older program)."""

from benchmark import program


def read(ctx):
    ms = program.per_step_ms(
        ctx, lambda o: bool(o.scope and o.scope.startswith("batched2d/chunk:")
                            and o.opcode != "while"))
    return ms if ms else None
