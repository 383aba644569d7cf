"""``batched_c2r``: the batched 2D plan's complex-to-real transform of a
stack of half spectra, ``exec_inverse``, unnormalised as cuFFT's (no
1/(nx ny)). The interface is ``exec_r2c.py``'s.

After a ``batched_r2c`` in the same step, its answer is compared whole,
on the device, with nx·ny times the R2C's input: the batch axis carries
no transform (``plan.transform_size``), so C2R(R2C(u)) is exactly
nx·ny u, which is exact in float32 for nx·ny a power of two. Alone, its
images ``sel`` are compared with the reference's inverse of the step's
input spectrum.
"""

NAME = "c2r_rel_l2"


def make_input(plan):
    """The 2D R2C of a uniform real stack, made with ``jax.numpy.fft``
    (not the program), in the plan's output layout."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.float64 if plan.config.double_prec else jnp.float32
    shape = tuple(plan.input_shape)
    return jax.jit(lambda k: jnp.fft.rfft2(
        jax.random.uniform(k, shape, dtype, -1.0, 1.0)),
        out_shardings=plan.output_sharding)


def program(plan):
    return plan.exec_inverse


def work(plan, chips):
    from distributedfft_tpu.analysis import plangraph

    from benchmark import work as bwork

    return bwork.graph_work(plangraph.graph_for(plan, "inverse", 2),
                            tuple(plan.global_size.shape), chips)


def keep(plan, sel, chain, out):
    from benchmark import harness, load

    if len(chain) >= 2 and chain[-1][0] == "batched_r2c":
        # The slab cell's roundtrip distance, with nx*ny for N.
        err = harness.part("calls", "exec_c2r")._roundtrip_err
        return {"err": err(float(plan.transform_size))(out, chain[-2][1])}

    return {"c": load.take_planes(chain[-1][1], sel),
            "images": load.take_planes(out, sel)}


def error(sel, shape, kept):
    from benchmark import reference, reference2d

    if "err" in kept:
        return float(kept["err"])
    return reference.rel_l2(kept["images"], reference2d.irfft2_images(
        kept["c"], range(len(sel)), shape[-1]))
