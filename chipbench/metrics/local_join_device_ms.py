"""local join: device milliseconds per query of the per-partition joins
(hash_join, sort_join, the tiled_probe and bitonic_sort_tile kernels) and
the gather of the matched build rows (eager jnp.take, module jit__take;
the semi-join filter's probe takes through the same program)."""

#: XLA module names of the local-join programs.
MODULES = r"^jit_(hash_join|sort_join|tiled_probe|bitonic_sort_tile|_take)$"


def read(ctx):
    return ctx.device_ms_per_query(MODULES)
