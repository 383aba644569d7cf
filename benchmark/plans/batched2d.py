"""``"plan": "batched2d"``: a stack of images transformed over its last
two axes by ``Batched2DFFTPlan``, on one chip (``SlabPartition(1)``: no
mesh, the batch axis a pure batch dimension), in sequential chunks of
``batch_chunk`` images (``lax.map``) where the configuration sets it.
"""


def build(config, overrides):
    """The plan of ``config`` (a configuration file), its ``Config``
    fields updated by ``overrides`` (a control's)."""
    import distributedfft_tpu as dfft

    fields = {"double_prec": config["precision"] == "float64",
              **config.get("config", {}), **overrides}
    batch, nx, ny = config["global_size"]
    return dfft.Batched2DFFTPlan(batch, nx, ny, dfft.SlabPartition(1),
                                 dfft.Config(**fields), transform="r2c",
                                 batch_chunk=config.get("batch_chunk"))
