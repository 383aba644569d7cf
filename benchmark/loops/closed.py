"""``"loop": "closed"``: one caller, each step started when the last one
has finished (``block_until_ready``), as the reference times each
iteration. The mix's keys: ``clients`` (1), ``inputs`` (how many
device-resident inputs made from the seed the steps use in turn),
``sample`` (how many steps of the window, drawn from the seed, have
their answers compared).
"""

import time


def validate(mix):
    if mix.get("clients") != 1:
        raise ValueError(f"closed loop: one client, got {mix}")
    if int(mix.get("inputs", 0)) < 1 or int(mix.get("sample", 0)) < 1:
        raise ValueError(f"closed loop: inputs >= 1 and sample >= 1, "
                         f"got {mix}")


def window(step, inputs, seconds, annotate, done):
    """Steps for ``seconds`` on the host clock; ``done(n, i, outs)``
    after each step's block. Returns the steps and the window's length,
    from the first dispatch to the last block."""
    import jax

    n = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with annotate("bench/window"):
        while True:
            i = n % len(inputs)
            with annotate("bench/step"):
                outs = step(inputs[i])
                with annotate("bench/block"):
                    jax.block_until_ready(outs[-1])
            done(n, i, outs)
            del outs
            n += 1
            if time.perf_counter() >= deadline:
                break
    return {"steps": n, "window_s": time.perf_counter() - t0}
