"""Device time per step and chip that no layer owns: the union of the
ops with no ``dfft/`` stage scope (on the slab cell, the c64 boundary's
``X64Combine``/``X64Split*`` and the input's relayout copy), as the mean
over the cell's devices."""

from benchmark import program


def read(ctx):
    return program.per_step_ms(ctx, lambda o: not o.scope)
