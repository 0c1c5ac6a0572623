"""Roofline share of the expert kernels in a training step: the least time
of the expert FFN work (forward and backward, routed rows, each weight
read once) over the device time of the expert kernels' ops in the trace."""
import re

UNIT = "%"
#: The expert kernels' device ops (esffn, esmm, esfk, ess, estmm, and their
#: transposes in the backward pass).
PATTERN = re.compile(r"(?:^|_)(?:esffn|esmm|esfk|ess|estmm)(?:_\w+)?_pallas")


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None:
        return None
    kernel_s = ctx.trace.kernel_s(PATTERN)
    if kernel_s <= 0:
        return None
    least = ctx.program.expert_least_time_s(ctx.device_kind)
    return 100.0 * least * ctx.records["traced_steps"] / kernel_s
