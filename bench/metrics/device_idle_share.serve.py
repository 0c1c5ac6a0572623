"""Share of a serving cell's traced window in which no operation ran on
the device."""

UNIT = "%"


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
