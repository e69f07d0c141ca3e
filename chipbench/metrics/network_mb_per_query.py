"""exchange: megabytes (1e6 bytes) that crossed partitions per query, the
engine's exact count (ExecutionResult.network_bytes over every result and
shared producer of the window). A count, not a speed."""


def read(ctx):
    if not ctx.records:
        return None
    return ctx.network_bytes / 1e6 / len(ctx.records)
