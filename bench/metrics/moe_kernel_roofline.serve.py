"""Roofline share of the expert kernel in serving: the least time of the
expert FFN work of every decode step and prefill chunk in the traced
window (routed rows, each touched expert's weights read once) over the
device time of the expert kernels' ops there."""
import re

UNIT = "%"
#: The expert kernels' device ops.
PATTERN = re.compile(r"(?:^|_)(?:esffn|esmm|esfk|ess|estmm)(?:_\w+)?_pallas")


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None:
        return None
    kernel_s = ctx.trace.kernel_s(PATTERN)
    if kernel_s <= 0:
        return None
    p, least = ctx.program, 0.0
    for tick in ctx.records["traced"]["ticks"]:
        if tick["decode"]:
            least += p.expert_least_time_s(len(tick["decode"]),
                                           ctx.device_kind)
        for rows, _, _ in tick["prefill"]:
            least += p.expert_least_time_s(rows, ctx.device_kind)
    return 100.0 * least / kernel_s
