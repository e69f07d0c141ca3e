"""exchange: device milliseconds per query of shuffle routing
(_dest_partition, _route), the all-to-all's per-column gather and the
partition_hist kernel. A broadcast is a reshape, with no program of its
own."""

#: XLA module names of the exchange programs.
MODULES = r"^jit_(_dest_partition|_route|_all_to_all|partition_hist)$"


def read(ctx):
    return ctx.device_ms_per_query(MODULES)
