"""``exec_c2r``: the plan's 3D complex-to-real transform, unnormalised as
cuFFT's (no 1/N). The interface is ``exec_r2c.py``'s.

After an ``exec_r2c`` in the same step, its answer is compared whole, on
the device, with N times the R2C's input: C2R(R2C(u)) is exactly N u,
which is exact in float32 for N a power of two, and so is the difference
of two close float32 numbers. Alone, its planes ``sel`` are compared with
the reference's inverse of the step's input spectrum.
"""

import numpy as np

NAME = "c2r_rel_l2"


def make_input(plan):
    """The R2C of a uniform real cube, made with ``jax.numpy.fft`` (not
    the program), in the plan's output layout."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.float64 if plan.config.double_prec else jnp.float32
    shape = tuple(plan.input_shape)
    return jax.jit(lambda k: jnp.fft.rfftn(
        jax.random.uniform(k, shape, dtype, -1.0, 1.0)),
        out_shardings=plan.output_sharding)


def program(plan):
    return plan.exec_c2r


def work(plan, chips):
    from distributedfft_tpu.analysis import plangraph

    from benchmark import work as bwork

    return bwork.graph_work(plangraph.graph_for(plan, "inverse", 3),
                            tuple(plan.global_size.shape), chips)


_ERR = {}


def _roundtrip_err(n_total):
    import jax
    import jax.numpy as jnp

    if n_total not in _ERR:
        _ERR[n_total] = jax.jit(lambda o, u: jnp.sqrt(
            jnp.sum(jnp.square(o - u * n_total))
            / jnp.sum(jnp.square(u * n_total))))
    return _ERR[n_total]


def keep(plan, sel, chain, out):
    if len(chain) >= 2 and chain[-1][0] == "exec_r2c":
        n_total = float(np.prod(plan.global_size.shape))
        return {"err": _roundtrip_err(n_total)(out, chain[-2][1])}
    from benchmark import load

    return {"c": chain[-1][1], "planes": load.take_planes(out, sel)}


def error(sel, shape, kept):
    from benchmark import reference

    if "err" in kept:
        return float(kept["err"])
    return reference.rel_l2(kept["planes"], reference.irfftn_planes(
        kept["c"], sel, shape[-1]))
