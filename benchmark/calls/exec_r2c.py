"""``exec_r2c``: the plan's 3D real-to-complex transform, unnormalised.

A call of a traffic mix's ``calls`` is this file's name; the harness
loads ``benchmark/calls/<call>.py``. Each call file gives:

- ``make_input(plan)``: where the call starts a step, one jitted
  function from a key to a step's input, made on the device(s);
- ``program(plan)``: the timed callable;
- ``work(plan, chips)``: its least work per chip (``work.Work``);
- ``keep(plan, sel, chain, out)``: what of its answer ``out`` is compared,
  reduced on the device as soon as the step is sampled, so no full answer
  outlives its step (``chain``: ``[(name, array)]``, the step's input and
  the answers of the calls before this one; ``sel``: the seed-drawn
  planes along axis 0);
- ``error(sel, shape, kept)``: the relative L2 error of the kept answer
  against the float64 reference (``reference.py``), on the host, once
  the program's arrays are freed.
"""

NAME = "r2c_rel_l2"


def make_input(plan):
    """A real cube, uniform on [-1, 1), in the plan's input layout."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.float64 if plan.config.double_prec else jnp.float32
    shape = tuple(plan.input_shape)
    # A padded plan pads inside exec; its sharding is of the padded cube.
    out = (plan.input_sharding if shape == tuple(plan.input_padded_shape)
           else None)
    return jax.jit(lambda k: jax.random.uniform(k, shape, dtype, -1.0, 1.0),
                   out_shardings=out)


def program(plan):
    return plan.exec_r2c


def work(plan, chips):
    from distributedfft_tpu.analysis import plangraph

    from benchmark import work as bwork

    return bwork.graph_work(plangraph.graph_for(plan, "forward", 3),
                            tuple(plan.global_size.shape), chips)


def keep(plan, sel, chain, out):
    from benchmark import load

    # The call's input stays alive anyway where it is the step's input.
    return {"x": chain[-1][1], "planes": load.take_planes(out, sel)}


def error(sel, shape, kept):
    from benchmark import reference

    return reference.rel_l2(kept["planes"],
                            reference.rfftn_planes(kept["x"], sel))
