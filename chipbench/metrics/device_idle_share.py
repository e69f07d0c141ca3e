"""device: 1 minus the union of the device's busy intervals over the
traced window."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.idle_share()
