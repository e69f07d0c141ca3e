"""aggregate: device milliseconds per query of the group-by's programs,
the segmentation (module jit__local_segments) and the per-column
aggregate (jit__agg_column). Its shuffle counts under the exchange."""

#: XLA module names of the group-by programs.
MODULES = r"^jit_(_local_segments|_agg_column)$"


def read(ctx):
    return ctx.device_ms_per_query(MODULES)
