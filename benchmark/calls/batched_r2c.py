"""``batched_r2c``: the batched 2D plan's real-to-complex transform of a
stack of images, ``exec_forward``, unnormalised. The interface is
``exec_r2c.py``'s.

Its answer's images ``sel`` (drawn from the seed along the batch axis)
and the same images of its input are sliced on the device as a sampled
step finishes, and compared on the host with the float64 ``rfft2`` of
those images (``reference2d.py``).
"""

NAME = "r2c_rel_l2"


def make_input(plan):
    """A real stack of images, uniform on [-1, 1), in the plan's input
    layout."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.float64 if plan.config.double_prec else jnp.float32
    shape = tuple(plan.input_shape)
    return jax.jit(lambda k: jax.random.uniform(k, shape, dtype, -1.0, 1.0),
                   out_shardings=plan.input_sharding)


def program(plan):
    return plan.exec_forward


def work(plan, chips):
    from distributedfft_tpu.analysis import plangraph

    from benchmark import work as bwork

    return bwork.graph_work(plangraph.graph_for(plan, "forward", 2),
                            tuple(plan.global_size.shape), chips)


def keep(plan, sel, chain, out):
    from benchmark import load

    return {"x": load.take_planes(chain[-1][1], sel),
            "images": load.take_planes(out, sel)}


def error(sel, shape, kept):
    from benchmark import reference, reference2d

    return reference.rel_l2(kept["images"], reference2d.rfft2_images(
        kept["x"], range(len(sel))))
