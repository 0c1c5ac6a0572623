"""Share of a training cell's traced window in which no operation ran on
the device."""

UNIT = "%"


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
