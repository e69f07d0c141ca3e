"""runtime filters: device milliseconds per query of the filter kernels,
bloom_build, bloom_probe and key_range (the zone map's build), and of the
eager searchsorted that the semi-join reducer probes with (module
jit_searchsorted: any eager jnp.searchsorted in the engine lands there,
and today only core/psts.semi_join_mask makes one)."""

#: XLA module names of the runtime-filter programs.
MODULES = r"^jit_(bloom_build|bloom_probe|key_range|searchsorted)$"


def read(ctx):
    return ctx.device_ms_per_query(MODULES)
