"""service / planner: host milliseconds per query from the start of its
parse and bind to the end of QueryService.submit (optimise, quote), from
the benchmark's own span."""


def read(ctx):
    if not ctx.records:
        return None
    return 1e3 * sum(r.plan_s for r in ctx.records) / len(ctx.records)
