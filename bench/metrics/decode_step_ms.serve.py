"""Median over the window of the serving engine's own decode step time
(``PagedServer.decode_times_s``: the host clock around one decode step and
the readback of its logits)."""
import statistics

UNIT = "ms"


def read(ctx):
    if ctx.kind != "serve" or not ctx.records["window"]["decode_times_s"]:
        return None
    return 1e3 * statistics.median(ctx.records["window"]["decode_times_s"])
