"""Device time per step and chip of the local FFT along array axis 2
(z on the slab and pencil plans): the union of the ops under the
stage sub-scopes ``dfft/<family>/<node>.ax2``, forward and inverse
together, as the mean over the cell's devices."""

from benchmark import program


def read(ctx):
    return program.axis_ms(ctx, 2)
