"""Model FLOP/s utilization of serving: the forward FLOPs of every prompt
token prefilled and every output token decoded in the window (routed
experts only; the head only where a token is produced), counted from
shapes, over the window times the chips' bf16 peak."""
from bench.peaks import peaks

UNIT = "%"


def read(ctx):
    if ctx.kind != "serve":
        return None
    p, win = ctx.program, ctx.records["window"]
    flops = 0
    for tick in win["ticks"]:
        for context in tick["decode"]:
            flops += p.token_flops(context, head=True)
        for rows, start, last in tick["prefill"]:
            flops += sum(p.token_flops(start + i, head=False)
                         for i in range(rows))
            if last:
                flops += p.token_flops(0, head=True) - p.token_flops(
                    0, head=False)
    peak = peaks(ctx.device_kind)["bf16_flops"] * ctx.chips
    return 100.0 * flops / (win["window_s"] * peak)
