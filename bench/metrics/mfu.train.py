"""Model FLOP/s utilization of a training step: the forward and backward
FLOPs the model needs per step (routed experts only, no recomputation),
counted from shapes, over the step time times the chips' bf16 peak."""
from bench.peaks import peaks

UNIT = "%"


def read(ctx):
    if ctx.kind != "train":
        return None
    flops = ctx.program.model_flops_per_step()
    peak = peaks(ctx.device_kind)["bf16_flops"] * ctx.chips
    return 100.0 * flops / (ctx.records["step_s"] * peak)
