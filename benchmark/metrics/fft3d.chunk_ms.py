"""Device time per step and chip of the single-device 3D plan's two chunk
loops themselves: the union of the ops whose innermost stage scope is
``dfft/slab/chunk:<k>`` (``chunk:1`` the loop over chunks of x-planes,
``chunk:2`` the loop over blocks of z-planes of the half spectrum: their
slices, the writes of each chunk's answer, the spectrum's c64 split or
combine), as the mean over the cell's devices. The loops' own control
ops (``while``) are left out: their intervals span their bodies, the
transforms included. None where no op carries the scope (a fused plan,
an older program)."""

from benchmark import program


def read(ctx):
    ms = program.per_step_ms(
        ctx, lambda o: bool(o.scope and o.scope.startswith("slab/chunk:")
                            and o.opcode != "while"))
    return ms if ms else None
