"""``"loop": "closed_last"``: one caller, each step started when the
last one has finished (``block_until_ready``), as ``closed.py``; the
answers of the window's last step are the ones compared, and they are
handed to ``done`` after the window's clock has stopped. The mix's keys:
``clients`` (1), ``inputs`` (how many device-resident inputs made from
the seed the steps use in turn), ``sample`` (1: the last step).

Why not ``closed.py``'s sampling: there each sampled step reduces its
answers on the device inside the window, and how many steps the
reservoir samples depends on the seed. Where that reduction is long
against the step (a whole-stack c64 answer is split into two f32 planes
before any slice of it is read: about 33 ms of device time on the
48 x 4096^2 stack, next to a step of about half a second), the seed
then moves the window's time per step by more than its bound allows.
"""

import time


def validate(mix):
    if mix.get("clients") != 1:
        raise ValueError(f"closed_last loop: one client, got {mix}")
    if int(mix.get("inputs", 0)) < 1 or int(mix.get("sample", 0)) != 1:
        raise ValueError(f"closed_last loop: inputs >= 1 and sample == 1, "
                         f"got {mix}")


def window(step, inputs, seconds, annotate, done):
    """Steps for ``seconds`` on the host clock, then ``done(0, i, outs)``
    for the last one: the only step offered to the harness's reservoir,
    so its first (the harness logs it as ``sampled=[0]``). Returns the
    steps and the window's length, from the first dispatch to the last
    block."""
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
            n += 1
            if time.perf_counter() >= deadline:
                break
            del outs
    window_s = time.perf_counter() - t0
    done(0, i, outs)
    return {"steps": n, "window_s": window_s}
