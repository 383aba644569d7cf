"""The one generator of the benchmark's traffic, and what it shares with
the call files.

A traffic mix is a data file, ``benchmark/traffic/<mix>.json``:

- ``loop``: the loop kind, ``benchmark/loops/<loop>.py``, which drives
  the window and reads the mix's other keys (``closed``: ``clients``,
  ``inputs``, ``sample``);
- ``calls``: the calls one step chains, each fed the previous one's
  answer; each is ``benchmark/calls/<call>.py``, and the first one makes
  the step's inputs from ``--seed``.

A new mix over existing loops and calls is a new data file; a new loop
kind or call is a new file beside these.
"""

from __future__ import annotations

import time
from typing import Callable, List, Sequence

import numpy as np


def seed32(seed: int) -> int:
    """A 32-bit key word from any whole-number seed (the driver's exceed
    32 signed bits)."""
    return int(np.random.SeedSequence(abs(int(seed))).generate_state(1)[0])


def make_inputs(plan, first_call, count: int, seed: int) -> List:
    """The step's ``count`` inputs, made on the device(s) by the first
    call's ``make_input`` from keys folded out of the seed."""
    import jax

    key = jax.random.key(seed32(seed))
    gen = first_call.make_input(plan)
    out = [gen(jax.random.fold_in(key, i)) for i in range(count)]
    jax.block_until_ready(out)
    return out


def sample_planes(seed: int, n0: int, count: int) -> List[int]:
    """``count`` planes along axis 0 drawn from the seed (all of them
    where ``count`` reaches ``n0``)."""
    if count >= n0:
        return list(range(n0))
    rng = np.random.default_rng(seed32(seed))
    return sorted(rng.choice(n0, count, replace=False).tolist())


_TAKE: dict = {}


def take_planes(out, sel: Sequence[int]):
    """The planes ``sel`` along axis 0 of ``out``, sliced on the device by
    one program for every seed (the indices are an argument). One slice
    a plane: a gather (``jnp.take``) of 16 planes of the 1024^3 spectrum
    holds 2.0 GB of temporaries per v5e chip, the slices 0.54 GB."""
    import jax
    import jax.numpy as jnp

    if len(sel) not in _TAKE:
        _TAKE[len(sel)] = jax.jit(lambda o, idx: jnp.stack([
            jax.lax.dynamic_index_in_dim(o, idx[j], 0, keepdims=False)
            for j in range(len(sel))]))
    return _TAKE[len(sel)](out, jnp.asarray(sel, dtype=jnp.int32))


def step_fn(plan, names: Sequence[str], calls: Sequence,
            annotate: Callable, call_s: list) -> Callable:
    """One step: the chained calls (``names``, their files ``calls``),
    each under ``annotate`` (a host span, or nothing). Returns every
    call's answer. ``call_s`` collects each call's host time until it
    returns, before any block."""
    fns = [c.program(plan) for c in calls]

    def step(x):
        outs = []
        for name, fn in zip(names, fns):
            with annotate(f"bench/{name}"):
                t = time.perf_counter()
                x = fn(x)
                call_s.append(time.perf_counter() - t)
            outs.append(x)
        return outs

    return step
