"""The device runtime's own high-water mark of allocated memory
(``Device.memory_stats()["peak_bytes_in_use"]``, read by the benchmark
after the window), on the fullest chip, in GB. It holds the cell's
inputs, the step in flight with its temporaries, and the few planes a
sampled step keeps for the comparison: no whole answer of the
benchmark's own."""


def read(ctx):
    return ctx.peak_bytes / 1e9
