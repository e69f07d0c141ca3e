"""executor host path: programs compiled, or loaded from the persistent
compilation cache, inside the measured window. The warm-up should leave
none."""


def read(ctx):
    return float(ctx.compiles)
