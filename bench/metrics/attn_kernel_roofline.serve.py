"""Roofline share of the attention kernels in serving: the least time of
every decode step's paged attention in the traced window (QK and PV FLOPs,
each cached K and V row read once) over the device time of the attention
kernels' ops there."""
import re

UNIT = "%"
#: The attention kernels' device ops.
PATTERN = re.compile(r"(?:^|_)(?:paged_attention|flash_attention|flash)\w*_pallas"
                     r"|(?:^|_)_(?:paged|flash)_kernel")


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None:
        return None
    kernel_s = ctx.trace.kernel_s(PATTERN)
    if kernel_s <= 0:
        return None
    p = ctx.program
    least = sum(p.decode_attention_least_time_s(tick["decode"],
                                                ctx.device_kind)
                for tick in ctx.records["traced"]["ticks"] if tick["decode"])
    return 100.0 * least / kernel_s
