"""executor host path: device milliseconds per query of the compaction the
executor runs after every join and group-by (joins/table.py:
_max_live, _front_order and the per-column take_along_axis)."""

#: XLA module names of the compaction programs.
MODULES = r"^jit_(_max_live|_front_order|take_along_axis)$"


def read(ctx):
    return ctx.device_ms_per_query(MODULES)
