"""``"plan": "slab"``: the reference's slab decomposition, ``SlabFFTPlan``.

A configuration file names its plan kind; the harness loads
``benchmark/plans/<kind>.py`` and calls ``build``. Another kind (pencil,
the chunked one-chip plan, a solver) is another file with the same
``build``.
"""


def build(config, overrides):
    """The plan of ``config`` (a configuration file), its ``Config``
    fields updated by ``overrides`` (a control's)."""
    import distributedfft_tpu as dfft

    fields = {"double_prec": config["precision"] == "float64",
              **config.get("config", {}), **overrides}
    return dfft.SlabFFTPlan(dfft.GlobalSize(*config["global_size"]),
                            dfft.SlabPartition(int(config["partition"])),
                            dfft.Config(**fields), sequence=config["sequence"])
